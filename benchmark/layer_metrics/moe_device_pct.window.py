"""Kernels: ``moe_device_pct`` in the cell of window and full attention
layers, where all 64 experts are held and 6 take a token: device time of
the expert sum's grouped matrix products (the ``ragged-dot`` custom calls,
three a layer, as ``layer_metrics/moe_device_pct.py`` matches them) over
the traced window. That reader's records are this cell's too; the metric
has an entry of its own because ``tests/benchmark_tests/test_latent_moe.py``
takes the metrics whose ``workloads`` is its cell alone for that cell's."""
from benchmark import harness

read = harness.load_reader("moe_device_pct")

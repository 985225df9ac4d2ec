"""``moe_unheld_pct`` in the cell of linear-attention and latent-attention layers
(``serve_reason_state_closed``): ``layer_metrics/moe_unheld_pct.py``'s reader on
this cell's records. The metric has an entry of its own because
``tests/benchmark_tests/test_latent_moe.py`` and ``test_window_moe.py``
pin the accepted entries' ``workloads`` to one cell each."""
from benchmark import harness

read = harness.load_reader("moe_unheld_pct")

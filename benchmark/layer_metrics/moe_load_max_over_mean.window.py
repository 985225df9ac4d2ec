"""Experts: ``moe_load_max_over_mean`` in the cell of window and full
attention layers: the busiest of the 64 experts' tokens over their mean,
over the window (the program's device counter ``moe_expert_tokens``, as
``layer_metrics/moe_load_max_over_mean.py`` reads it); an entry of its own
for the reason ``moe_device_pct.window.py`` gives."""
from benchmark import harness

read = harness.load_reader("moe_load_max_over_mean")

"""KV cache: ``prefix_hit_pct`` in the cell of window and full attention
layers: share of the prompt tokens admitted in the window that the prefix
cache served (``layer_metrics/prefix_hit_pct.py``'s counters). A second ask
counts only as far as the full group holds its chain AND the window group
the window's tail of it, so an eviction order that loses tails shows here;
an entry of its own for the reason ``moe_device_pct.window.py`` gives."""
from benchmark import harness

read = harness.load_reader("prefix_hit_pct")

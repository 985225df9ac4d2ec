"""Model step: mean per traced tick of the device's idle time while the
host was in ``serve/forward`` or ``model/layer`` outside their children
(launches, the embedding and head) or in ``kv/release_window``
(``benchmark/idle_spans.py``)."""
from benchmark import idle_spans


def read(run):
    return idle_spans.idle_ms(run, "tick_idle_launch_ms")

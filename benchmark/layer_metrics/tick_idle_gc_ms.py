"""Device: mean per traced tick of the device's idle time while the
collector ran (a ``host/gc`` span open on any thread: a collection stops
every Python thread; ``benchmark/idle_spans.py``)."""
from benchmark import idle_spans


def read(run):
    return idle_spans.idle_ms(run, "tick_idle_gc_ms")

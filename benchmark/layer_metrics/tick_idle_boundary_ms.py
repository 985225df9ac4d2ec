"""Scheduler: mean per traced tick of the device's idle time while the host
was at the tick's boundary: in ``serve/sync`` (the read-back's tail),
``serve/emit``, ``serve/schedule``, ``kv/admit``, ``kv/begin_ragged``,
``state/admit`` or ``serve/tick``'s own time (``benchmark/idle_spans.py``)."""
from benchmark import idle_spans


def read(run):
    return idle_spans.idle_ms(run, "tick_idle_boundary_ms")

"""Scheduler: mean per traced tick of the self time of ``serve/emit``:
everything after the tick's sync (compile observatory, request-trace spans,
sampling, verify, ``_push_token``, finish). The device is idle meanwhile."""
from benchmark import tick_spans


def read(run):
    return tick_spans.phase_ms(run, "tick_emit_ms")

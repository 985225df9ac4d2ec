"""Device: mean per traced tick of the self time of ``serve/sync``, the tick's
one sync: the host blocked on the device for the logits' argmax."""
from benchmark import tick_spans


def read(run):
    return tick_spans.phase_ms(run, "tick_sync_ms")

"""Model step: mean per traced tick of the self times of ``serve/forward`` and
its ``model/layer`` spans: the eager dispatch of the model's small programs
outside the attention kernel's Python entry."""
from benchmark import tick_spans


def read(run):
    return tick_spans.phase_ms(run, "tick_dispatch_ms")

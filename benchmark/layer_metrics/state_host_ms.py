"""KV cache: mean per traced tick of the host's time for the state a slot:
the self time of ``state/admit`` (a slot's rows zeroed at admission) and of
the two kernel spans ``attn/kda_step`` and ``attn/kda_chunk`` (their host
halves: the dispatch of the jitted kernels, a KDA layer each)."""
from benchmark import tick_spans

SPANS = ("state/admit", "attn/kda_step", "attn/kda_chunk")


def read(run):
    ticks = tick_spans.window_ticks(run)
    if ticks is None:
        return None
    total = sum(t["self"].get(name, 0.0) for t in ticks for name in SPANS)
    if not total:
        return None
    return 1e3 * total / len(ticks)

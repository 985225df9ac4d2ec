"""What the KDA readers share: the args of the traced window's
``attn/kda_step`` and ``attn/kda_chunk`` spans (the program's own, with
their args: rows; spans, tokens, padded tokens, chunks), one span a KDA
layer a tick. Kept are the spans that begin between the first and the last
stamp of ``run["kernel_calls"]`` (inside the window, as ``tick_spans``
keeps its ticks). A program without the spans reads as nothing."""
from __future__ import annotations

STEP, CHUNK = "attn/kda_step", "attn/kda_chunk"


def kept(run):
    """-> {span name: [(args, seconds)]} or None."""
    calls = run.get("kernel_calls")
    if not calls:
        return None
    try:
        from paddle_tpu.profiler import get_tracer
    except ImportError:
        return None
    tracer = get_tracer()
    origin = getattr(tracer, "origin", None)
    completed = getattr(tracer, "completed", None)
    if origin is None or completed is None:
        return None
    first, last = min(c[0] for c in calls), max(c[0] for c in calls)
    out = {STEP: [], CHUNK: []}
    for s in completed():
        if s.name in out and s.args and first <= origin + s.ts <= last:
            out[s.name].append((s.args, s.dur))
    return out if out[STEP] or out[CHUNK] else None

"""Where a serving tick's host time goes: per-tick self times of the spans
the program records inside its tick (``paddle_tpu.profiler.span``; the table
is in docs/OBSERVABILITY.md), for the ``tick_*`` per-layer metrics.

The program keeps its completed spans in a process-global tracer, which
outlives the engine the driver deletes, so they are read here after the run.
Kept are the ``serve/tick`` spans whose start lies between the first and the
last stamp of ``run["kernel_calls"]`` (traced runs have them; they lie inside
the window), each with everything nested in it. A span's self time is its
duration less what its children cover, so nested spans are never counted
twice. A program without the spans (the parent of the PR that brought them)
reads as nothing: every reader then returns None.
"""
from __future__ import annotations

TICK = "serve/tick"

#: metric -> the spans whose self times it sums
PHASES = {
    "tick_schedule_ms": ("serve/schedule",),
    "tick_emit_ms": ("serve/emit",),
    "tick_kv_host_ms": ("kv/admit", "kv/begin_ragged"),
    "tick_dispatch_ms": ("serve/forward", "model/layer"),
    "tick_attn_host_ms": ("attn/qblock", "attn/qblock_schedule"),
    "tick_sync_ms": ("serve/sync",),
}


def program_spans():
    """The program's completed spans as records ``{"name", "t0", "dur",
    "id", "parent"}`` with ``t0`` on ``time.perf_counter``'s clock, or None
    where the program cannot say."""
    try:
        from paddle_tpu.profiler import get_tracer
    except ImportError:
        return None
    tracer = get_tracer()
    origin = getattr(tracer, "origin", None)
    completed = getattr(tracer, "completed", None)
    if origin is None or completed is None:
        return None
    return [{"name": s.name, "t0": origin + s.ts, "dur": s.dur,
             "id": s.span_id, "parent": s.parent_id} for s in completed()]


def ticks_between(spans, first, last):
    """-> one record a kept tick, in time order: ``{"t0", "t1", "self":
    {span name: seconds of self time of the spans of that name in it}}``."""
    by_id = {s["id"]: s for s in spans}
    covered = {}                         # id -> seconds its children cover
    for s in spans:
        if s["parent"] in by_id:
            covered[s["parent"]] = covered.get(s["parent"], 0.0) + s["dur"]
    ticks = {s["id"]: {"t0": s["t0"], "t1": s["t0"] + s["dur"], "self": {}}
             for s in spans
             if s["name"] == TICK and first <= s["t0"] <= last}
    for s in spans:
        top = s
        while top["name"] != TICK and top["parent"] in by_id:
            top = by_id[top["parent"]]
        tick = ticks.get(top["id"])
        if tick is None:
            continue
        own = max(s["dur"] - covered.get(s["id"], 0.0), 0.0)
        tick["self"][s["name"]] = tick["self"].get(s["name"], 0.0) + own
    return sorted(ticks.values(), key=lambda t: t["t0"])


def window_ticks(run):
    """The kept ticks of a run, or None where there are none."""
    calls = run.get("kernel_calls")
    if not calls:
        return None
    spans = program_spans()
    if not spans:
        return None
    stamps = [c[0] for c in calls]
    return ticks_between(spans, min(stamps), max(stamps)) or None


def phase_seconds(ticks, metric):
    return sum(t["self"].get(name, 0.0)
               for t in ticks for name in PHASES[metric])


def phase_ms(run, metric):
    """Mean per kept tick of the self time of the metric's spans."""
    ticks = window_ticks(run)
    if ticks is None:
        return None
    return 1e3 * phase_seconds(ticks, metric) / len(ticks)


def cover_pct(run):
    """The six phases' self times over the kept ticks' extent (first
    tick's start to last tick's end): what moves outside the spans, or
    between two ticks, shows here as a falling share."""
    ticks = window_ticks(run)
    if ticks is None:
        return None
    extent = ticks[-1]["t1"] - ticks[0]["t0"]
    if extent <= 0:
        return None
    return 100.0 * sum(phase_seconds(ticks, m) for m in PHASES) / extent

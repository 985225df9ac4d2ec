"""Where the device waits in a traced serving tick: every stretch of the
device's idle time inside the kept ticks, put under the program span the
host was in, for the ``tick_idle_*`` per-layer metrics.

The program's spans (``paddle_tpu.profiler.span``, read here from its
process-global tracer after the run) carry ``wall_time``, ``time.time_ns()``
at their start; the device's events in ``run["trace"]["events"]`` are
nanoseconds since the profile's ``profile_start_time``, a stamp on the same
clock, but a TPU's events sit ~1.8 ms early against it (PERF.md section 6)
and the reduction does not keep it. So the spans' offset is estimated, one
number a run, from what the tick guarantees: the host cannot return from
``serve/sync`` before the device has produced the answer, and nothing
launched in the tick runs after it, so every sync ends in the device's idle
time, after the last busy stretch before it by the host's wake-up. The
offsets at which the most syncs end in idle time form one stretch; its
upper end puts the sync with the smallest such lag at its busy end
(:func:`estimate_start`): the estimate is late by the smallest lag of any
tick, a lowest percentile that no copy after a sync can pull down.

Kept are the engine thread's ``serve/tick`` spans (the thread that records
them) that lie inside the device events' extent; a kept tick whose extent
holds no device event is one whose events the trace lost: it is counted,
left out of the means, and named in the run's log. The device's idle
intervals over the kept ticks' span are split at span boundaries; each
piece goes to ``host/gc`` if the collector's span is open on any thread
(it stops every thread), else to the innermost span open on the engine
thread, else to :data:`OUTSIDE`. A program whose tracer lacks ``dropped``
(its spans' ``wall_time`` was not on the profiler's clock), or that
dropped spans, reads as nothing.
"""
from __future__ import annotations

import numpy as np

from benchmark import harness
from benchmark.trace_reduce import busy_intervals

TICK, SYNC, GC = "serve/tick", "serve/sync", "host/gc"
#: idle time under no span of the engine thread (between two ticks)
OUTSIDE = "(no span)"

#: metric -> the spans whose idle time it sums (``serve/tick``: its self)
GROUPS = {
    "tick_idle_boundary_ms": ("serve/sync", "serve/emit", "serve/schedule",
                              "kv/admit", "kv/begin_ragged", "state/admit",
                              "serve/tick"),
    "tick_idle_launch_ms": ("serve/forward", "model/layer",
                            "kv/release_window"),
    "tick_idle_attn_host_ms": ("attn/qblock", "attn/qblock_schedule",
                               "attn/kda_step", "attn/kda_chunk"),
    "tick_idle_gc_ms": (GC,),
}

#: syncs whose ends propose offsets, how far from a proposal the exact
#: search looks, and how many proposals it looks around
REFS = 16
WINDOW_NS = 1e7
BASINS = 5


def program_spans():
    """-> (spans, counts) from the program's tracer, a span as ``{"name",
    "t0" (ns on ``time.time_ns``'s clock), "dur" (ns), "tid", "id",
    "parent"}``, ``counts`` its ``dropped``, ``gc_short`` and
    ``gc_short_s``; None where the program cannot say."""
    try:
        from paddle_tpu.profiler import get_tracer
    except ImportError:
        return None
    tracer = get_tracer()
    dropped = getattr(tracer, "dropped", None)
    completed = getattr(tracer, "completed", None)
    if dropped is None or completed is None:
        return None
    counts = {"dropped": dropped,
              "gc_short": getattr(tracer, "gc_short", 0),
              "gc_short_s": getattr(tracer, "gc_short_s", 0.0)}
    return [{"name": s.name, "t0": s.wall_time * 1e9, "dur": s.dur * 1e9,
             "tid": s.tid, "id": s.span_id, "parent": s.parent_id}
            for s in completed()], counts


# -- the offset -------------------------------------------------------------

def _idle_count(offsets, syncs, starts, ends, chunk=256):
    """For each offset, how many of the syncs' ends fall in the device's idle
    time (a gap between two busy stretches, its start included)."""
    out = []
    for i in range(0, len(offsets), chunk):
        points = syncs[None, :] - offsets[i:i + chunk, None]
        j = np.searchsorted(starts, points, side="right") - 1
        idle = (j >= 0) & (j + 1 < len(starts)) & (
            points >= ends[np.clip(j, 0, len(ends) - 1)])
        out.append(idle.sum(axis=1))
    return np.concatenate(out)


def _plateau_top(syncs, starts, ends, around):
    """-> (most syncs' ends in idle time at any offset within
    :data:`WINDOW_NS` of ``around``, the upper end of the highest stretch
    of offsets where that many are): a device event after some syncs'
    ends splits off stretches below the true one, where those ends have
    passed the event."""
    g0, g1 = ends[:-1], starts[1:]
    lo, hi = around - WINDOW_NS, around + WINDOW_NS
    marks = []                           # (offset, +1 opens / -1 closes)
    for s in syncs:
        a = np.searchsorted(g1, s - hi, side="left")
        b = np.searchsorted(g0, s - lo, side="right")
        for g in range(a, b):
            x0, x1 = max(s - g1[g], lo), min(s - g0[g], hi)
            if x0 <= x1:
                marks += [(x0, 1), (x1, -1)]
    marks.sort(key=lambda m: (m[0], -m[1]))
    best, top, count = 0, None, 0
    for x, step in marks:
        count += step
        if count > best:
            best = count
        elif step < 0 and count == best - 1:
            top = x
    return best, top


def estimate_start(sync_ends, starts, ends):
    """The profile's start on the spans' clock (ns), from the ticks'
    ``serve/sync`` ends and the device's busy intervals (sorted, disjoint
    ``starts`` / ``ends``, ns since the profile's start); None where there
    is nothing to align.

    At the true offset every sync ends in the device's idle time, some
    (the host's wake-up) after the last busy end before it. Coarse: each
    of :data:`REFS` syncs spread over the window proposes that it ended
    where one of the longest idle gaps begins, and the proposals under
    which most syncs end in idle time are kept, :data:`BASINS` of them at
    least two windows apart (ticks of nearly one length make a shift by a
    tick look good too). Exact: within :data:`WINDOW_NS` of each, the
    offsets at which the most syncs end in idle time; the basin where
    that is most wins, and the upper end of its highest such stretch
    (looked for again around itself until it holds) puts the sync with the
    smallest lag at its busy end: the estimate is late by that lag."""
    syncs = np.sort(np.asarray(sync_ends, float))
    if len(syncs) < 2 or len(starts) < 2:
        return None
    # spans exist only while the profile records: the syncs that can lie
    # inside the events' extent are those of its length after the first
    syncs = syncs[syncs <= syncs[0] + (ends[-1] - starts[0])]
    gap_len = starts[1:] - ends[:-1]
    k = min(len(gap_len), 2 * len(syncs))
    gaps = ends[:-1][np.argpartition(-gap_len, k - 1)[:k]]

    def spread(n):
        return syncs[np.linspace(0, len(syncs) - 1,
                                 min(n, len(syncs))).astype(int)]

    offsets = np.unique((spread(REFS)[:, None] - gaps[None, :]).ravel())
    order = np.argsort(-_idle_count(offsets, spread(128), starts, ends),
                       kind="stable")
    chosen = []
    for off in offsets[order]:
        if all(abs(off - c) > 2 * WINDOW_NS for c in chosen):
            chosen.append(off)
            if len(chosen) == BASINS:
                break
    found = [_plateau_top(syncs, starts, ends, c) for c in chosen]
    top = max(found, key=lambda f: f[0])[1]
    for _ in range(4 if top is not None else 0):  # cut by the window's edge
        again = _plateau_top(syncs, starts, ends, top)[1]
        if again == top:
            break
        top = again
    return top


# -- intervals ----------------------------------------------------------------

def _intersect(a, b):
    """Sorted disjoint [lo, hi] lists -> their intersection."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _subtract(a, b):
    """Sorted disjoint [lo, hi] lists -> ``a`` without ``b``."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > lo:
                out.append((lo, b[k][0]))
            lo = max(lo, b[k][1])
            k += 1
        if lo < hi:
            out.append((lo, hi))
    return out


def _union(intervals):
    return [tuple(iv) for iv in busy_intervals(
        [(None, lo, hi - lo) for lo, hi in intervals])]


def innermost(spans):
    """Spans of one thread as (lo, hi, name), nested -> sorted disjoint
    (lo, hi, name) segments, each named by the innermost span open in it."""
    out, stack, cursor = [], [], None

    def emit(lo, hi, name):
        if hi > lo:
            out.append((lo, hi, name))

    for lo, hi, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][1] <= lo:
            top = stack.pop()
            emit(cursor, top[1], top[2])
            cursor = max(cursor, top[1])
        if stack:
            emit(cursor, lo, stack[-1][2])
        stack.append((lo, hi, name))
        cursor = lo
    while stack:
        top = stack.pop()
        emit(cursor, top[1], top[2])
        cursor = max(cursor, top[1])
    return out


def _by_name(pieces, segments):
    """Time of ``pieces`` under each segment's name, the rest under
    ``OUTSIDE``; both lists sorted and disjoint."""
    out, j = {}, 0
    for lo, hi in pieces:
        while j < len(segments) and segments[j][1] <= lo:
            j += 1
        rest, k = hi - lo, j
        while k < len(segments) and segments[k][0] < hi:
            ov = min(hi, segments[k][1]) - max(lo, segments[k][0])
            if ov > 0:
                out[segments[k][2]] = out.get(segments[k][2], 0.0) + ov
                rest -= ov
            k += 1
        if rest > 0:
            out[OUTSIDE] = out.get(OUTSIDE, 0.0) + rest
    return out


# -- the split ------------------------------------------------------------------

def split(run, log=True):
    """-> {"start_ns", "kept", "lost" (ticks), "lost_s" (their seconds),
    "gc_short", "gc_short_s" (the tracer's count of collections too short
    to be spans, and their seconds), "idle_ms": {span name: ms of device
    idle a kept tick with events}} or None where there is nothing to
    read."""
    trace = run.get("trace")
    if not trace or not trace.get("events"):
        return None
    found = program_spans()
    if found is None:
        return None
    spans, counts = found
    if counts["dropped"]:
        return None
    ticks = [s for s in spans if s["name"] == TICK]
    if not ticks:
        return None
    tids = [s["tid"] for s in ticks]
    engine = max(set(tids), key=tids.count)
    ticks = [s for s in ticks if s["tid"] == engine]
    tick_ids = {s["id"] for s in ticks}
    syncs = [s["t0"] + s["dur"] for s in spans
             if s["name"] == SYNC and s["parent"] in tick_ids]
    events = next(iter(trace["events"].values()))
    busy = np.asarray(busy_intervals(events), float).reshape(-1, 2)
    if not len(busy):
        return None
    starts, ends = busy[:, 0], busy[:, 1]
    start = estimate_start(syncs, starts, ends)
    if start is None:
        return None

    def on_axis(s):
        return s["t0"] - start, s["t0"] + s["dur"] - start

    extent = [on_axis(s) for s in ticks]
    kept = sorted(iv for iv in extent
                  if iv[0] >= starts[0] and iv[1] <= ends[-1])
    if not kept:
        return None
    lost = []
    for lo, hi in kept:
        i = np.searchsorted(ends, lo, side="right")
        if i == len(ends) or starts[i] >= hi:
            lost.append((lo, hi))
    n = len(kept) - len(lost)
    if log:
        harness.log(f"idle spans: {len(kept)} ticks kept, {len(lost)} "
                    f"lost-event ticks left out; profile start estimated "
                    f"at {start:.0f} ns; {counts['gc_short']} short "
                    f"generation-0 collections counted, not spans, "
                    f"{1e3 * counts['gc_short_s']:.1f} ms in all")
    if not n:
        return None
    lo, hi = kept[0][0], kept[-1][1]
    idle = _subtract([(lo, hi)], [tuple(iv) for iv in busy
                                   if iv[1] > lo and iv[0] < hi])
    idle = _subtract(idle, _union(lost))
    gc_open = _union(on_axis(s) for s in spans if s["name"] == GC)
    segments = innermost([(*on_axis(s), s["name"]) for s in spans
                          if s["tid"] == engine and s["name"] != GC])
    by_name = _by_name(_subtract(idle, gc_open), segments)
    gc_ns = sum(b - a for a, b in _intersect(idle, gc_open))
    if gc_ns:
        by_name[GC] = gc_ns
    return {"start_ns": start, "kept": len(kept), "lost": len(lost),
            "lost_s": sum(b - a for a, b in lost) / 1e9,
            "gc_short": counts["gc_short"],
            "gc_short_s": counts["gc_short_s"],
            "idle_ms": {k: v / 1e6 / n for k, v in by_name.items()}}


_last = (None, None, None)


def _cached(run):
    """One split a run: the four readers share it. Held by identity, so
    it relies on the harness passing the same run object (with the same
    trace) to each reader."""
    global _last
    if _last[0] is not run or _last[1] is not run.get("trace"):
        _last = (run, run.get("trace"), split(run))
    return _last[2]


def idle_ms(run, metric):
    """Mean per kept tick of the device's idle time under the metric's
    spans, in ms."""
    found = _cached(run)
    if found is None:
        return None
    return sum(found["idle_ms"].get(name, 0.0) for name in GROUPS[metric])


def idle_by_span(run):
    """The whole table: {span name: ms of device idle a kept tick}, largest
    first, with the kept and lost tick counts; None where there is none."""
    found = _cached(run)
    if found is None:
        return None
    return dict(found, idle_ms=dict(sorted(found["idle_ms"].items(),
                                           key=lambda kv: -kv[1])))

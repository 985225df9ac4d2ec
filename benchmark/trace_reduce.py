"""From a profiler trace to numbers: device busy and idle time, time by
operation name, the longest idle gaps and what the host was doing in them.

The reduction works on a neutral form, so that a small recorded trace can be
kept as a JSON fixture and the same code reads both:

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns], ...]}]}]}

``from_xplane`` makes that form from ``jax.profiler.ProfileData``.
"""
from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
#: the device line whose events are single operations (kernels, fusions,
#: copies, collectives); "XLA Modules" holds whole programs, "Steps" steps
OP_LINE = "XLA Ops"
#: host spans the benchmark's own wrappers write (jax.profiler.TraceAnnotation)
HOST_SPAN_PREFIX = "bench:"


def from_xplane(path, host_prefix=HOST_SPAN_PREFIX):
    """Device planes whole; of the host planes only the benchmark's spans."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                      for ev in line.events
                      if device or ev.name.startswith(host_prefix)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace):
    return [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]


def op_events(plane, line_name=OP_LINE):
    """Sorted by start. No such line is an error, not an empty answer: a
    trace read against the wrong line would report an idle chip."""
    for line in plane["lines"]:
        if line["name"] == line_name:
            return sorted(line["events"], key=lambda e: (e[1], -e[2]))
    raise KeyError(f"plane {plane['name']!r} has no line {line_name!r} "
                   f"(has {[l['name'] for l in plane['lines']]})")


def host_spans(trace, prefix=HOST_SPAN_PREFIX):
    spans = []
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            spans += [e for e in line["events"] if e[0].startswith(prefix)]
    return sorted(spans, key=lambda e: e[1])


def busy_intervals(events):
    """Union of [start, end) of the events, as a sorted list."""
    merged = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        end = start + dur
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def busy_seconds(events):
    return sum(e - s for s, e in busy_intervals(events)) / 1e9


def self_seconds_by_name(events):
    """Device time by operation name, a parent (a loop, a call) counted
    without the part its children cover, so names add up to busy time."""
    out = {}
    stack = []          # [name, end, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, self_ns = stack.pop()
            out[name] = out.get(name, 0) + self_ns

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(dur, max(stack[-1][1] - start, 0))
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return {k: v / 1e9 for k, v in out.items()}


def seconds_matching(events, pattern):
    """Summed duration and count of the events whose name matches."""
    rx = re.compile(pattern)
    hits = [e for e in events if rx.search(e[0])]
    return sum(e[2] for e in hits) / 1e9, len(hits)


def idle_gaps(events, spans=(), top=10):
    """The ``top`` longest gaps between busy intervals, each named by the
    host span that overlaps it most (``unattributed`` where none does).
    -> [[name, seconds], ...], longest first."""
    merged = busy_intervals(events)
    gaps = [(b[0] - a[1], a[1], b[0]) for a, b in zip(merged, merged[1:])]
    gaps.sort(reverse=True)
    out = []
    for dur, g0, g1 in gaps[:top]:
        best, best_overlap = "unattributed", 0
        for name, start, sdur in spans:
            ov = min(g1, start + sdur) - max(g0, start)
            if ov > best_overlap:
                best, best_overlap = name, ov
        out.append([best, dur / 1e9])
    return out


def exposed_seconds(events, pattern):
    """Time of the events matching ``pattern`` (collectives) during which
    no other operation runs on the device."""
    rx = re.compile(pattern)
    coll = [e for e in events if rx.search(e[0])]
    rest = busy_intervals([e for e in events if not rx.search(e[0])])
    exposed = 0
    for s, e in busy_intervals(coll):
        covered = sum(max(min(e, r1) - max(s, r0), 0) for r0, r1 in rest)
        exposed += (e - s) - covered
    return exposed / 1e9


_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(name, limit=96):
    """A device event's name is its whole HLO instruction. For the
    breakdown: the instruction's own name, its opcode and, for a custom
    call, its target (``%fusion.27 fusion``, ``%jvp_jit__fwd__.4
    custom-call tpu_custom_call``)."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:limit]
    op = _OPCODE.search(" " + rest)
    target = _TARGET.search(rest)
    parts = [head] + ([op.group(1)] if op else []) + (
        [target.group(1)] if target else [])
    return " ".join(parts)[:limit]


def reduce_trace(trace, top=10):
    """-> {"busy_s" (mean over device planes), "per_device": [...],
    "device_ops": [[name, s], ...], "idle_gaps": [[name, s], ...],
    "events": {plane: op events}}."""
    planes = device_planes(trace)
    if not planes:
        raise KeyError("the trace has no device plane: "
                       f"{[p['name'] for p in trace['planes']]}")
    spans = host_spans(trace)
    per_device, by_name, events_of = [], {}, {}
    for plane in planes:
        ev = op_events(plane)
        events_of[plane["name"]] = ev
        per_device.append(busy_seconds(ev))
        for name, s in self_seconds_by_name(ev).items():
            by_name[name] = by_name.get(name, 0.0) + s / len(planes)
    first = events_of[planes[0]["name"]]
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": sum(per_device) / len(per_device),
            "per_device": per_device,
            "device_ops": [[short_name(k), v] for k, v in ops],
            "idle_gaps": idle_gaps(first, spans, top),
            "events": events_of}


def idle_pct(run):
    """For the ``device_idle_pct.*`` readers: the share of the traced window
    in which no operation ran on the device (mean over the chips used), or
    nothing where the run has no trace."""
    trace = run.get("trace")
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def summary_for_dump(trace, events_per_line=400):
    """What a builder needs to look at one trace by hand: every plane and
    line by name with its event count, and the first events of each line."""
    return {"planes": [
        {"name": p["name"], "lines": [
            {"name": l["name"], "count": len(l["events"]),
             "events": l["events"][:events_per_line]} for l in p["lines"]]}
        for p in trace["planes"]]}

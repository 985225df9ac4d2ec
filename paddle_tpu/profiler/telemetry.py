"""Unified runtime telemetry: process-global metrics registry + span tracer.

Two substrates every subsystem shares (ISSUE 2; the per-stage accounting
Piper and the Gemma-on-TPU comparison lean on — step breakdown, MFU,
latency percentiles):

* :class:`MetricRegistry` — thread-safe labeled Counter / Gauge /
  Histogram families with fixed-bucket percentile estimation,
  Prometheus-style text exposition (:func:`metrics_text`) and JSONL
  snapshot export. One process-global instance (:func:`get_registry`)
  is fed by the autograd tape, ``jit/to_static``, ``distributed.comm``,
  ``io.DataLoader``, the serving engines and ``TelemetryCallback``.
* :class:`SpanTracer` — nested spans with true wall-clock begin/duration,
  per-thread ids and parent linkage. Backs ``RecordEvent`` and
  ``export_chrome_tracing`` (the Profiler's trace is assembled from
  these spans, not fabricated from cumulative totals).

Everything here is stdlib-only and cheap when idle: span recording is
gated on :meth:`SpanTracer.enable` (the Profiler enables it while
recording) and the tape's per-op observer is installed only while
op telemetry is explicitly enabled (``TelemetryCallback`` / Profiler).
"""
from __future__ import annotations

import bisect
import itertools
import json
import os
import threading
import time
from collections import deque

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricRegistry", "get_registry",
    "metrics", "metrics_text", "Span", "SpanTracer", "get_tracer",
    "enable_op_telemetry", "disable_op_telemetry", "op_telemetry",
    "DEFAULT_LATENCY_BUCKETS", "DEFAULT_RATIO_BUCKETS",
]

# Prometheus-style cumulative latency bounds (seconds). ``inf`` is
# implicit as the final +Inf bucket.
DEFAULT_LATENCY_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
    0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
)

# Bounds for [0, 1]-valued observations (utilization / occupancy ratios —
# e.g. the serving engine's chunk-budget utilization histogram).
DEFAULT_RATIO_BUCKETS = (0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0)

_INF = float("inf")


def _escape_label(v) -> str:
    return (str(v).replace("\\", "\\\\").replace("\n", "\\n")
            .replace('"', '\\"'))


def _fmt_labels(names, values) -> str:
    if not names:
        return ""
    inner = ",".join(f'{n}="{_escape_label(v)}"'
                     for n, v in zip(names, values))
    return "{" + inner + "}"


class _Family:
    """Base for one named metric family: a dict of children keyed by the
    label-value tuple. Lock is shared with the owning registry."""

    kind = "untyped"

    def __init__(self, name, help, labels, lock):
        self.name = name
        # real Prometheus scrapers warn on empty HELP text — an
        # undescribed family self-documents with its own name
        self.help = help or name
        self.label_names = tuple(labels)
        self._lock = lock
        self._children = {}

    def _key(self, kwargs):
        if set(kwargs) != set(self.label_names):
            raise ValueError(
                f"metric {self.name} expects labels {self.label_names}, "
                f"got {tuple(kwargs)}")
        return tuple(kwargs[n] for n in self.label_names)

    def labels(self, **kwargs):
        key = self._key(kwargs)
        child = self._children.get(key)
        if child is None:
            with self._lock:
                child = self._children.setdefault(key, self._new_child())
        return child

    def _default_child(self):
        """The unlabeled singleton child (for labels=() families)."""
        return self.labels()

    def reset(self):
        with self._lock:
            for c in self._children.values():
                c._reset()

    def collect(self):
        with self._lock:
            return {
                "type": self.kind,
                "help": self.help,
                "label_names": list(self.label_names),
                "series": {
                    ",".join(map(str, k)) if k else "": c._snapshot()
                    for k, c in self._children.items()
                },
            }

    def expose(self, lines):
        lines.append(f"# HELP {self.name} {self.help}")
        lines.append(f"# TYPE {self.name} {self.kind}")
        with self._lock:
            items = list(self._children.items())
        for key, child in items:
            child._expose(lines, self.name,
                          _fmt_labels(self.label_names, key),
                          self.label_names, key)


class Counter(_Family):
    kind = "counter"

    class _Child:
        __slots__ = ("value",)

        def __init__(self):
            self.value = 0.0

        def inc(self, amount=1.0):
            self.value += amount

        def _reset(self):
            self.value = 0.0

        def _snapshot(self):
            return self.value

        def _expose(self, lines, name, labelstr, *_):
            lines.append(f"{name}{labelstr} {self.value:g}")

    def _new_child(self):
        return Counter._Child()

    def inc(self, amount=1.0, **labels):
        c = self.labels(**labels)
        with self._lock:
            c.inc(amount)

    def value(self, **labels):
        return self.labels(**labels).value


class Gauge(_Family):
    kind = "gauge"

    class _Child:
        __slots__ = ("value",)

        def __init__(self):
            self.value = 0.0

        def _reset(self):
            self.value = 0.0

        def _snapshot(self):
            return self.value

        def _expose(self, lines, name, labelstr, *_):
            lines.append(f"{name}{labelstr} {self.value:g}")

    def _new_child(self):
        return Gauge._Child()

    def set(self, value, **labels):
        c = self.labels(**labels)
        with self._lock:
            c.value = float(value)

    def inc(self, amount=1.0, **labels):
        c = self.labels(**labels)
        with self._lock:
            c.value += amount

    def set_max(self, value, **labels):
        """High-water update: keep the maximum ever set (live-bytes)."""
        c = self.labels(**labels)
        with self._lock:
            if value > c.value:
                c.value = float(value)

    def value(self, **labels):
        return self.labels(**labels).value


class Histogram(_Family):
    kind = "histogram"

    class _Child:
        __slots__ = ("bounds", "counts", "sum", "count")

        def __init__(self, bounds):
            self.bounds = bounds          # sorted, excludes +Inf
            self._reset()

        def _reset(self):
            self.counts = [0] * (len(self.bounds) + 1)
            self.sum = 0.0
            self.count = 0

        def observe(self, value):
            self.counts[bisect.bisect_left(self.bounds, value)] += 1
            self.sum += value
            self.count += 1

        def percentile(self, p):
            """Fixed-bucket estimate with linear interpolation inside the
            winning bucket; the +Inf bucket clamps to its lower bound."""
            if self.count == 0:
                return 0.0
            rank = self.count * (p / 100.0)
            cum = 0
            lo = 0.0
            for i, c in enumerate(self.counts):
                cum += c
                if cum >= rank and c > 0:
                    hi = self.bounds[i] if i < len(self.bounds) else None
                    if hi is None:
                        return lo
                    frac = (rank - (cum - c)) / c
                    return lo + (hi - lo) * min(max(frac, 0.0), 1.0)
                if i < len(self.bounds):
                    lo = self.bounds[i]
            return lo

        def _snapshot(self):
            cum = 0
            buckets = {}
            for i, b in enumerate(self.bounds):
                cum += self.counts[i]
                buckets[f"{b:g}"] = cum
            buckets["+Inf"] = self.count
            return {
                "count": self.count,
                "sum": self.sum,
                "buckets": buckets,
                "p50": self.percentile(50),
                "p95": self.percentile(95),
                "p99": self.percentile(99),
            }

        def _expose(self, lines, name, labelstr, label_names, key):
            cum = 0
            for i, b in enumerate(self.bounds):
                cum += self.counts[i]
                ls = _fmt_labels(tuple(label_names) + ("le",),
                                 tuple(key) + (f"{b:g}",))
                lines.append(f"{name}_bucket{ls} {cum}")
            ls = _fmt_labels(tuple(label_names) + ("le",),
                             tuple(key) + ("+Inf",))
            lines.append(f"{name}_bucket{ls} {self.count}")
            lines.append(f"{name}_sum{labelstr} {self.sum:g}")
            lines.append(f"{name}_count{labelstr} {self.count}")

    def __init__(self, name, help, labels, lock,
                 buckets=DEFAULT_LATENCY_BUCKETS):
        super().__init__(name, help, labels, lock)
        self.bounds = tuple(sorted(float(b) for b in buckets
                                   if b != _INF))

    def _new_child(self):
        return Histogram._Child(self.bounds)

    def observe(self, value, **labels):
        c = self.labels(**labels)
        with self._lock:
            c.observe(float(value))

    def percentile(self, p, **labels):
        with self._lock:
            return self.labels(**labels).percentile(p)


class MetricRegistry:
    """Process-global, thread-safe registry of metric families.

    Families are get-or-create by name — repeated ``counter(...)`` calls
    from different call sites share one family (a kind mismatch raises).
    """

    def __init__(self):
        self._lock = threading.RLock()
        self._families: dict = {}

    def _get_or_create(self, cls, name, help, labels, **kw):
        with self._lock:
            fam = self._families.get(name)
            if fam is None:
                fam = cls(name, help, labels, self._lock, **kw)
                self._families[name] = fam
            elif not isinstance(fam, cls):
                raise TypeError(
                    f"metric {name} already registered as {fam.kind}")
            return fam

    def counter(self, name, help="", labels=()) -> Counter:
        return self._get_or_create(Counter, name, help, labels)

    def gauge(self, name, help="", labels=()) -> Gauge:
        return self._get_or_create(Gauge, name, help, labels)

    def histogram(self, name, help="", labels=(),
                  buckets=DEFAULT_LATENCY_BUCKETS) -> Histogram:
        return self._get_or_create(Histogram, name, help, labels,
                                   buckets=buckets)

    def get(self, name):
        return self._families.get(name)

    def collect(self) -> dict:
        with self._lock:
            fams = list(self._families.values())
        return {f.name: f.collect() for f in fams}

    def to_text(self) -> str:
        """Prometheus text exposition format."""
        with self._lock:
            fams = list(self._families.values())
        lines: list = []
        for f in fams:
            f.expose(lines)
        return "\n".join(lines) + ("\n" if lines else "")

    def export_jsonl(self, path, extra=None) -> dict:
        """Append one JSON snapshot line to ``path``; returns the record.

        Multi-process safe: the whole line goes down in a single
        ``os.write`` on an ``O_APPEND`` fd, so concurrent ranks
        appending to one file (bench_telemetry.jsonl) can interleave
        only whole lines, never partial ones.

        Size-capped: when the file would grow past
        ``PADDLE_TELEMETRY_JSONL_MAX_MB`` (default 16, ``0`` disables),
        it first rotates to ``<path>.1`` (atomic ``os.replace``,
        clobbering the previous rotation) — an append-forever snapshot
        file must not eat the disk across bench runs."""
        rec = {"unix_time": time.time(), "metrics": self.collect()}
        if extra:
            rec.update(extra)
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        line = (json.dumps(rec) + "\n").encode()
        try:
            max_mb = float(os.environ.get("PADDLE_TELEMETRY_JSONL_MAX_MB",
                                          "16"))
        except ValueError:
            max_mb = 16.0
        if max_mb > 0:
            try:
                if os.path.getsize(path) + len(line) > max_mb * (1 << 20):
                    os.replace(path, f"{path}.1")
            except OSError:
                pass               # no file yet / raced: append fresh
        fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
        try:
            os.write(fd, line)
        finally:
            os.close(fd)
        return rec

    def reset(self):
        """Zero every series (families and label sets are kept)."""
        with self._lock:
            fams = list(self._families.values())
        for f in fams:
            f.reset()


_REGISTRY = MetricRegistry()


def get_registry() -> MetricRegistry:
    return _REGISTRY


def metrics(reset=False) -> dict:
    """Snapshot of every registered metric family (nested dict). With
    ``reset=True`` the counters/histograms are zeroed after reading
    (per-window accounting, mirroring ``comm_stats``)."""
    snap = _REGISTRY.collect()
    if reset:
        _REGISTRY.reset()
    return snap


def metrics_text() -> str:
    """The registry in Prometheus text exposition format."""
    return _REGISTRY.to_text()


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------


class Span:
    """One completed (or open) span. ``ts``/``dur`` are seconds on the
    tracer's monotonic clock (``ts_us``/``dur_us`` for chrome traces);
    ``wall_time`` is ``time.time_ns()`` read when the span opened, in
    seconds: the clock a ``jax.profiler`` session stamps its
    ``profile_start_time`` with, so ``wall_time * 1e9 -
    profile_start_time`` is the span's start on the device trace's axis."""

    __slots__ = ("name", "ts", "dur", "tid", "span_id", "parent_id",
                 "wall_time", "args")

    def __init__(self, name, ts, tid, span_id, parent_id, wall_time,
                 args=None):
        self.name = name
        self.ts = ts
        self.dur = 0.0
        self.tid = tid
        self.span_id = span_id
        self.parent_id = parent_id
        self.wall_time = wall_time
        self.args = args

    @property
    def ts_us(self):
        return self.ts * 1e6

    @property
    def dur_us(self):
        return self.dur * 1e6

    def as_dict(self):
        return {"name": self.name, "ts": self.ts, "dur": self.dur,
                "tid": self.tid, "span_id": self.span_id,
                "parent_id": self.parent_id, "wall_time": self.wall_time,
                "args": self.args}

    def __repr__(self):
        return (f"<Span {self.name} ts={self.ts:.6f} dur={self.dur:.6f} "
                f"tid={self.tid}>")


class SpanTracer:
    """Nested span recorder with real begin timestamps and per-thread
    parent linkage. Enable/disable is refcounted (the Profiler enables
    it for each recording window); when disabled, begin/end are no-ops.
    Completed spans land in a bounded deque and are pulled with
    :meth:`drain`; ``dropped`` counts the spans the full deque pushed out
    since the last ``drain``, and ``gc_short`` / ``gc_short_s`` the
    generation-0 collections too short to be a ``host/gc`` span
    (``profiler/spans.py``) and their seconds."""

    def __init__(self, max_spans=200_000):
        # re-entrant: the collector's hook (``profiler/spans.py``) records
        # on the thread that runs a collection, which may hold this lock
        self._lock = threading.RLock()
        self._done: deque = deque(maxlen=max_spans)
        self._tls = threading.local()
        self._enabled = 0
        self._ids = itertools.count(1)  # next() is atomic: no lock
        self._tids: dict = {}          # thread ident -> small stable tid
        # monotonic origin, so ts is comparable across threads
        self._t0 = time.perf_counter()
        self.dropped = 0
        self.gc_short = 0
        self.gc_short_s = 0.0

    # -- lifecycle -----------------------------------------------------------
    def enable(self):
        with self._lock:
            self._enabled += 1

    def disable(self):
        with self._lock:
            self._enabled = max(0, self._enabled - 1)

    @property
    def enabled(self) -> bool:
        return self._enabled > 0

    @property
    def origin(self) -> float:
        """``time.perf_counter()`` at ``ts`` 0: ``origin + span.ts`` is a
        span's start on the clock of everybody else's stamps."""
        return self._t0

    def _tid(self):
        ident = threading.get_ident()
        tid = self._tids.get(ident)
        if tid is None:
            with self._lock:
                tid = self._tids.setdefault(ident, len(self._tids))
        return tid

    def _stack(self):
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _new_span(self, name, ts, args, ago=0.0, nest=True):
        """A span that began ``ago`` seconds before now; ``nest=False``:
        with no parent."""
        stack = self._stack()
        parent = stack[-1].span_id if stack and nest else None
        return Span(name, ts, self._tid(), next(self._ids), parent,
                    time.time_ns() / 1e9 - ago, args)

    def _record(self, sp):
        """Queue a completed span; the caller holds ``_lock``."""
        if len(self._done) == self._done.maxlen:
            self.dropped += 1
        self._done.append(sp)

    def count_short_gc(self, seconds):
        with self._lock:
            self.gc_short += 1
            self.gc_short_s += seconds

    # -- recording -----------------------------------------------------------
    def begin(self, name, **args):
        """Open a nested span; returns the Span (or None when disabled).
        Must be closed with :meth:`end` on the same thread."""
        if not self.enabled:
            return None
        return self.open(name, args or None)

    def open(self, name, args=None):
        """``begin`` whether enabled or not: for ``profiler.span``, which
        has its own switch (a ``jax.profiler`` session enables nothing
        here)."""
        sp = self._new_span(name, time.perf_counter() - self._t0, args)
        self._stack().append(sp)
        return sp

    def end(self, span=None, record=True):
        """Close the innermost open span of this thread (or the given
        span and anything opened after it). ``record=False`` drops the
        closed spans instead of queueing them."""
        if span is None and not self.enabled:
            return None
        stack = self._stack()
        if not stack:
            return None
        if span is not None and span not in stack:
            return None                  # closed already, with its parent
        now = time.perf_counter() - self._t0
        target = span if span is not None else stack[-1]
        while stack:
            sp = stack.pop()
            sp.dur = max(now - sp.ts, 0.0)
            if record:
                with self._lock:
                    self._record(sp)
            if sp is target:
                return sp
        return None

    def span(self, name, **args):
        """Context manager form."""
        tracer = self

        class _Ctx:
            def __enter__(self):
                self._sp = tracer.begin(name, **args)
                return self._sp

            def __exit__(self, *exc):
                if self._sp is not None:
                    tracer.end(self._sp)
                return False

        return _Ctx()

    def add_complete(self, name, duration, end_ts=None, **args):
        """Record an already-finished span (the tape's dispatch hook
        measures after the fact): begin = end - duration, parented to
        this thread's currently-open span."""
        if not self.enabled:
            return None
        now = (end_ts if end_ts is not None
               else time.perf_counter() - self._t0)
        ts = max(now - duration, 0.0)
        sp = self._new_span(name, ts, args or None,
                            ago=time.perf_counter() - self._t0 - ts)
        sp.dur = duration
        with self._lock:
            self._record(sp)
        return sp

    def add_interruption(self, name, duration, args=None):
        """Record an already-finished span that interrupted this thread
        (the collector): whether enabled or not, as ``open``, and with no
        parent, so that the spans it interrupted keep their self times."""
        ts = max(time.perf_counter() - self._t0 - duration, 0.0)
        sp = self._new_span(name, ts, args, ago=duration, nest=False)
        sp.dur = duration
        with self._lock:
            self._record(sp)
        return sp

    # -- consumption ---------------------------------------------------------
    def drain(self):
        """Pull (and clear) every completed span, and zero the counts of
        what the deque dropped and of the short collections."""
        with self._lock:
            # swapped, not cleared: a span the collector's hook records in
            # here lands in one deque or the other
            out, self._done = self._done, deque(maxlen=self._done.maxlen)
            self.dropped = self.gc_short = 0
            self.gc_short_s = 0.0
        return list(out)

    def completed(self):
        """Every completed span, left in place (``drain`` clears)."""
        with self._lock:
            return list(self._done)

    def __len__(self):
        return len(self._done)


def spans_to_chrome(spans, pid=None):
    """Chrome-tracing ``traceEvents`` from completed spans — real per-span
    ``ts``/``dur`` (µs) and per-thread ``tid``, no fabricated timeline."""
    pid = os.getpid() if pid is None else pid
    events = []
    for s in sorted(spans, key=lambda x: x.ts):
        args = dict(s.args or {})
        args["span_id"] = s.span_id
        if s.parent_id is not None:
            args["parent_id"] = s.parent_id
        events.append({
            "name": s.name, "ph": "X", "pid": pid, "tid": s.tid,
            "ts": round(s.ts_us, 3), "dur": max(round(s.dur_us, 3), 0.001),
            "args": args,
        })
    return events


_TRACER = SpanTracer()


def get_tracer() -> SpanTracer:
    return _TRACER


# ---------------------------------------------------------------------------
# tape op telemetry (installed on demand — zero overhead when off)
# ---------------------------------------------------------------------------

_op_lock = threading.Lock()
_op_depth = 0
_op_metrics = None     # (counter, histogram) lazily created


def _observe_op(name, dt):
    global _op_metrics
    m = _op_metrics
    if m is None:
        r = get_registry()
        m = _op_metrics = (
            r.counter("paddle_op_dispatch_total",
                      "eager ops dispatched through the autograd tape",
                      labels=("op",)),
            r.histogram("paddle_op_dispatch_seconds",
                        "host wall time per eager op dispatch"),
        )
    m[0].inc(op=name)
    m[1].observe(dt)


def enable_op_telemetry():
    """Install the per-op observer on the autograd tape (refcounted).
    While installed, every eager dispatch feeds
    ``paddle_op_dispatch_total{op=...}`` and
    ``paddle_op_dispatch_seconds``."""
    global _op_depth
    from ..autograd import tape
    with _op_lock:
        _op_depth += 1
        if _observe_op not in tape._op_observers:
            tape._op_observers.append(_observe_op)


def disable_op_telemetry():
    global _op_depth
    from ..autograd import tape
    with _op_lock:
        _op_depth = max(0, _op_depth - 1)
        if _op_depth == 0 and _observe_op in tape._op_observers:
            tape._op_observers.remove(_observe_op)


class op_telemetry:
    """Context manager form of enable/disable_op_telemetry."""

    def __enter__(self):
        enable_op_telemetry()
        return self

    def __exit__(self, *exc):
        disable_op_telemetry()
        return False

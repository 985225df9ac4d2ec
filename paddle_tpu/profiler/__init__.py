"""paddle.profiler (reference: ``python/paddle/profiler/profiler.py`` —
``Profiler(targets, scheduler, on_trace_ready)``, ``make_scheduler`` step
windows, ``RecordEvent`` annotations, chrome-trace export, summary tables,
``benchmark()`` ips timer; C++ side host tracer + CUPTI — SURVEY.md §5.1).

TPU-native: device/kernel timelines come from ``jax.profiler`` (XPlane →
TensorBoard/Perfetto — the CUPTI analogue); host-side per-op wall times come
from the eager tape's dispatch hook, giving the op summary table without a
native tracer. ``RecordEvent`` maps to ``jax.profiler.TraceAnnotation`` so
user annotations show up inside the device trace.
"""
from __future__ import annotations

import contextlib
import enum
import json
import os
import time
from collections import defaultdict

import jax

from .telemetry import (  # noqa: F401  (re-exported facade)
    MetricRegistry, SpanTracer, Span, get_registry, get_tracer,
    metrics, metrics_text, enable_op_telemetry, disable_op_telemetry,
    op_telemetry, spans_to_chrome,
)
from . import spans as _spans
from .spans import span, tracing_active  # noqa: F401
from . import flight_recorder  # noqa: F401
from .flight_recorder import (  # noqa: F401  (re-exported facade)
    FlightRecorder, Watchdog, get_flight_recorder, gather_metrics,
    publish_snapshot, publish_component_state, gather_component_states,
    merge_chrome_traces, merge_rank_snapshots,
    desync_report, straggler_report,
)
from . import request_trace  # noqa: F401
from .request_trace import (  # noqa: F401  (re-exported facade)
    TraceContext, RequestTraceStore, SLOMonitor, start_request,
    finish_request, request_timeline, recent_timelines,
    timeline_to_chrome, get_slo_monitor, reset_slo_monitor, slo_report,
    cost_table, get_trace_store,
)
from . import timeseries  # noqa: F401
from .timeseries import (  # noqa: F401  (re-exported facade)
    MetricsHistory, get_history, history, history_tick,
)
from . import alerts  # noqa: F401
from .alerts import (  # noqa: F401  (re-exported facade)
    AlertEngine, AlertRule, ThresholdRule, BurnRateRule,
    get_alert_engine, active_alerts,
)
from . import step_phase  # noqa: F401
from . import memory  # noqa: F401
from .memory import (  # noqa: F401  (re-exported facade)
    MemoryTimeline, module_breakdown, register_model_breakdown,
)
from . import tensor_stats  # noqa: F401
from .tensor_stats import (  # noqa: F401  (re-exported facade)
    NumericsSentinel, NonFiniteGradError, get_sentinel,
)
from . import ledger  # noqa: F401
from .ledger import (  # noqa: F401  (re-exported facade)
    StepLedger, DivergenceError, get_ledger, tensor_digest,
    first_divergence, publish_ledger, gather_ledgers, compare_store,
    export_golden,
)
from . import exporter  # noqa: F401
from .exporter import (  # noqa: F401  (re-exported facade)
    TelemetryServer, maybe_start_exporter, exporter_enabled,
)
from . import scrape  # noqa: F401
from .scrape import (  # noqa: F401  (re-exported facade)
    FleetScraper, fleet_metrics, fleet_metrics_text, parse_metrics_text,
    start_fleet_scraper, stop_fleet_scraper, get_fleet_scraper,
)
from . import eventlog  # noqa: F401
from .eventlog import (  # noqa: F401  (re-exported facade)
    EventLog, log_event, get_event_log,
)
from . import compile_observatory  # noqa: F401
from .compile_observatory import (  # noqa: F401  (re-exported facade)
    CompileObservatory, get_observatory,
)

__all__ = [
    "Profiler", "ProfilerTarget", "ProfilerState", "make_scheduler",
    "export_chrome_tracing", "export_protobuf", "RecordEvent", "load_profiler_result",
    "benchmark", "comm_stats", "span", "tracing_active",
    "MetricRegistry", "SpanTracer", "get_registry", "get_tracer",
    "metrics", "metrics_text", "enable_op_telemetry", "disable_op_telemetry",
    "FlightRecorder", "Watchdog", "get_flight_recorder", "gather_metrics",
    "publish_snapshot", "publish_component_state",
    "gather_component_states", "merge_chrome_traces",
    "merge_rank_snapshots", "desync_report", "straggler_report",
    "TraceContext", "RequestTraceStore", "SLOMonitor", "start_request",
    "finish_request", "request_timeline", "recent_timelines",
    "timeline_to_chrome", "get_slo_monitor", "reset_slo_monitor",
    "slo_report", "cost_table", "get_trace_store",
    "MetricsHistory", "get_history", "history", "history_tick",
    "AlertEngine", "AlertRule", "ThresholdRule", "BurnRateRule",
    "get_alert_engine", "active_alerts",
    "step_phase", "memory", "tensor_stats", "ledger",
    "MemoryTimeline", "module_breakdown", "register_model_breakdown",
    "NumericsSentinel", "NonFiniteGradError", "get_sentinel",
    "StepLedger", "DivergenceError", "get_ledger", "tensor_digest",
    "first_divergence", "publish_ledger", "gather_ledgers",
    "compare_store", "export_golden",
    "exporter", "scrape", "eventlog",
    "TelemetryServer", "maybe_start_exporter", "exporter_enabled",
    "FleetScraper", "fleet_metrics", "fleet_metrics_text",
    "parse_metrics_text", "start_fleet_scraper", "stop_fleet_scraper",
    "get_fleet_scraper", "EventLog", "log_event", "get_event_log",
    "compile_observatory", "CompileObservatory", "get_observatory",
]


def comm_stats(reset=False):
    """Snapshot of the gradient-communication counters
    (``distributed.comm.CommStats``): collective calls, logical vs wire
    bytes, compression ratio, max quantization error. ``reset=True``
    zeroes the counters after reading (per-window accounting)."""
    from ..distributed.comm import get_comm_stats, reset_comm_stats
    d = get_comm_stats().as_dict()
    if reset:
        reset_comm_stats()
    return d


class ProfilerTarget(enum.Enum):
    CPU = 0
    GPU = 1          # alias: the accelerator
    TPU = 1
    CUSTOM_DEVICE = 2


class ProfilerState(enum.Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


def make_scheduler(*, closed, ready, record, repeat=0, skip_first=0):
    """Step-window state machine (reference ``make_scheduler``): per cycle,
    ``closed`` steps off, ``ready`` steps warming, ``record`` steps on;
    repeated ``repeat`` times (0 = forever), after ``skip_first`` steps."""
    cycle = closed + ready + record
    assert cycle > 0

    def scheduler(step):
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * cycle:
            return ProfilerState.CLOSED
        pos = s % cycle
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def _default_scheduler(step):
    return ProfilerState.RECORD


def export_chrome_tracing(dir_name, worker_name=None):
    """on_trace_ready callback: dump the recorded spans as a
    chrome-tracing JSON next to the jax xplane dump. Events carry REAL
    per-span begin timestamps, durations and per-thread ``tid`` from the
    span tracer (readable in Perfetto) — not a fabricated sequential
    timeline from cumulative op totals."""
    def handler(prof):
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"worker_{os.getpid()}"
        path = os.path.join(dir_name, f"{name}.pt.trace.json")
        events = spans_to_chrome(prof._drain_spans())
        if not events:
            # timer_only / span-less window: fall back to the op summary
            # (still one event per op, zero-based synthetic timeline,
            # flagged as such so consumers can tell)
            t = 0
            for op, (cnt, total) in sorted(prof._op_stats.items()):
                events.append({"name": op, "ph": "X", "pid": 0, "tid": 0,
                               "ts": t, "dur": max(total * 1e6, 1),
                               "args": {"calls": cnt, "synthetic_ts": True}})
                t += max(total * 1e6, 1)
        with open(path, "w") as f:
            json.dump({"traceEvents": events,
                       "displayTimeUnit": "ms"}, f)
        prof._exported_path = path
    return handler


def export_protobuf(dir_name, worker_name=None):
    return export_chrome_tracing(dir_name, worker_name)


class RecordEvent:
    """User annotation: ``profiler.span`` under the user's own name (a
    ``paddle_tpu:<name>`` annotation in the device trace plus a nested span
    in the span tracer: wall-clock begin/duration, thread id, parent
    linkage), on whenever a profiler records. Usable as context manager or
    begin()/end()."""

    def __init__(self, name, event_type=None):
        self.name = name
        self._t0 = None
        self._span = _spans.NULL

    def begin(self):
        self._t0 = time.perf_counter()
        self._span = _spans.open_span(self.name, kind="user")

    def end(self):
        self._span.end()
        prof = Profiler._current
        if prof is not None and prof._recording and self._t0 is not None:
            dt = time.perf_counter() - self._t0
            cnt, total = prof._op_stats[f"user::{self.name}"]
            prof._op_stats[f"user::{self.name}"] = (cnt + 1, total + dt)

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *a):
        self.end()


class Profiler:
    """paddle.profiler.Profiler facade.

    with Profiler(targets=[ProfilerTarget.CPU, ProfilerTarget.GPU],
                  scheduler=make_scheduler(closed=1, ready=1, record=2),
                  on_trace_ready=export_chrome_tracing('./log')) as p:
        for batch in loader:
            train_step(batch)
            p.step()
    p.summary()
    """

    _current = None

    def __init__(self, *, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, record_shapes=False, profile_memory=False,
                 with_flops=False, emit_nvtx=False):
        if callable(scheduler):
            self._scheduler = scheduler
        elif isinstance(scheduler, (tuple, list)) and len(scheduler) == 2:
            lo, hi = scheduler
            self._scheduler = make_scheduler(closed=lo, ready=0,
                                             record=hi - lo, repeat=1)
        else:
            self._scheduler = _default_scheduler
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        self.targets = targets or [ProfilerTarget.CPU]
        self._step = 0
        self._state = ProfilerState.CLOSED
        self._recording = False
        self._op_stats = defaultdict(lambda: (0, 0.0))
        self._step_times = []
        self._t_step = None
        self._jax_tracing = False
        self._trace_dir = None
        self._exported_path = None
        self._spans = []

    # -- tape hook ----------------------------------------------------------
    def _record_op(self, op_name, dt):
        cnt, total = self._op_stats[op_name]
        self._op_stats[op_name] = (cnt + 1, total + dt)
        tracer = get_tracer()
        if tracer.enabled:
            tracer.add_complete(op_name, dt, kind="op")

    def _drain_spans(self):
        """Spans recorded since the last drain (tracer + carried-over)."""
        self._spans.extend(get_tracer().drain())
        out, self._spans = self._spans, []
        return out

    # -- lifecycle ----------------------------------------------------------
    def start(self):
        Profiler._current = self
        # session hygiene: a prior profiler with no on_trace_ready leaves
        # its completed spans queued in the process-global tracer — this
        # session's exports must not inherit them
        get_tracer().drain()
        self._spans = []
        from ..autograd import tape
        tape._profiler = self
        self._transition(self._scheduler(self._step))
        self._t_step = time.perf_counter()
        return self

    def stop(self):
        if self._state in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN):
            self._stop_recording()
            if self._on_trace_ready:
                self._on_trace_ready(self)
        from ..autograd import tape
        tape._profiler = None
        Profiler._current = None
        self._state = ProfilerState.CLOSED

    def step(self, num_samples=None):
        now = time.perf_counter()
        if self._t_step is not None:
            self._step_times.append((now - self._t_step, num_samples))
        self._t_step = now
        self._step += 1
        new = self._scheduler(self._step)
        # fire once per RETURNING step, not once per state CHANGE: a
        # scheduler yielding RECORD_AND_RETURN on consecutive steps must
        # export each completed window, not silently skip all but the
        # first (each export drains the spans/ops of its own window)
        ret = self._state == ProfilerState.RECORD_AND_RETURN
        if new != self._state:
            self._transition(new)
        if ret and self._on_trace_ready:
            self._on_trace_ready(self)

    def _transition(self, new):
        rec_states = (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN)
        was = self._state in rec_states
        want = new in rec_states
        if want and not was:
            self._start_recording()
        elif was and not want:
            self._stop_recording()
        self._state = new

    def _start_recording(self):
        self._recording = True
        get_tracer().enable()
        if not self._timer_only and any(t != ProfilerTarget.CPU
                                        for t in self.targets):
            self._trace_dir = os.environ.get("PADDLE_PROFILER_XPLANE_DIR",
                                             "/tmp/paddle_tpu_xplane")
            try:
                jax.profiler.start_trace(self._trace_dir)
                self._jax_tracing = True
            except (RuntimeError, ValueError):
                self._jax_tracing = False

    def _stop_recording(self):
        self._recording = False
        tracer = get_tracer()
        if tracer.enabled:
            tracer.disable()
            # completed spans of this window stay queued in the tracer
            # until the export handler (or the next one) drains them
        if self._jax_tracing:
            try:
                jax.profiler.stop_trace()
            except (RuntimeError, ValueError):
                pass
            self._jax_tracing = False

    def __enter__(self):
        return self.start()

    def __exit__(self, *a):
        self.stop()

    # -- reporting ----------------------------------------------------------
    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        unit = {"s": 1.0, "ms": 1e3, "us": 1e6}[time_unit]
        lines = ["-" * 64,
                 f"{'Name':<36}{'Calls':>8}{'Total(' + time_unit + ')':>14}",
                 "-" * 64]
        for op, (cnt, total) in sorted(self._op_stats.items(),
                                       key=lambda kv: -kv[1][1]):
            lines.append(f"{op:<36}{cnt:>8}{total * unit:>14.3f}")
        if self._step_times:
            times = [t for t, _ in self._step_times]
            lines.append("-" * 64)
            lines.append(f"steps: {len(times)}  avg step "
                         f"{sum(times) / len(times) * unit:.3f}{time_unit}")
        out = "\n".join(lines)
        print(out)
        return out

    @property
    def averages(self):
        return {op: total / max(cnt, 1)
                for op, (cnt, total) in self._op_stats.items()}


def load_profiler_result(path):
    with open(path) as f:
        return json.load(f)


class _Benchmark:
    """paddle.profiler.utils benchmark timer — reports ips (reference:
    Profiler.timer_only path / hapi ips metric)."""

    def __init__(self):
        self.reset()

    def reset(self):
        self._t0 = None
        self._samples = 0
        self._steps = 0
        self._elapsed = 0.0

    def begin(self):
        self.reset()
        self._t0 = time.perf_counter()

    def step(self, num_samples=None):
        self._steps += 1
        if num_samples:
            self._samples += num_samples

    def end(self):
        if self._t0 is not None:
            self._elapsed = time.perf_counter() - self._t0
            self._t0 = None            # timer stopped; elapsed is final

    def ips(self):
        # while the timer is RUNNING, throughput is live (elapsed up to
        # now) — the old implicit end() latched _elapsed on the first
        # read and every later ips() reported that stale window
        if self._t0 is not None:
            elapsed = time.perf_counter() - self._t0
        else:
            elapsed = self._elapsed
        denom = elapsed or 1e-9
        return (self._samples or self._steps) / denom

    def step_info(self, unit="samples"):
        return f"ips: {self.ips():.2f} {unit}/s"


_benchmark = _Benchmark()


def benchmark():
    return _benchmark

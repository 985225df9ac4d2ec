"""The span tracer's clock, its count of what it drops, and the collector's
``host/gc`` span (docs/OBSERVABILITY.md, "Spans inside the serving tick")."""
import gc
import threading
import time
import types

import pytest

from paddle_tpu import profiler
from paddle_tpu.profiler import spans as spans_mod
from paddle_tpu.profiler import telemetry


@pytest.fixture
def tracer():
    tracer = profiler.get_tracer()
    tracer.drain()
    yield tracer
    while tracer.enabled:
        tracer.disable()
    spans_mod.latch()
    tracer.drain()


def gc_spans(tracer):
    return [s for s in tracer.completed() if s.name == spans_mod.GC_SPAN]


def test_no_profiler_leaves_the_collector_alone(tracer):
    before = list(gc.callbacks)
    assert spans_mod.latch() is False
    assert gc.callbacks == before
    assert spans_mod.span("serve/tick") is spans_mod.NULL
    gc.collect()
    assert tracer.completed() == [] and tracer.gc_short == 0


def test_the_collector_is_a_span_only_while_latched(tracer):
    before = list(gc.callbacks)
    tracer.enable()
    assert spans_mod.latch() is True
    assert spans_mod._on_gc in gc.callbacks
    with profiler.span("serve/emit"):
        gc.collect()
    done = threading.Thread(target=gc.collect)
    done.start()
    done.join()
    for _ in range(20):
        gc.collect(0)                    # well under a millisecond each
    # the two full passes asked for (the process may add its own)
    found = [s for s in gc_spans(tracer) if s.args["generation"] == 2]
    assert len(found) >= 2
    here, there = found[:2]
    assert {"collected", "uncollectable"} <= set(here.args)
    # on the thread that ran it, with no parent: the emit keeps its self time
    emit = next(s for s in tracer.completed() if s.name == "serve/emit")
    assert here.tid == emit.tid != there.tid
    assert here.parent_id is None
    assert emit.ts <= here.ts and here.ts + here.dur <= emit.ts + emit.dur
    assert tracer.gc_short > 0 and 0 < tracer.gc_short_s < 1e-3 * \
        tracer.gc_short
    tracer.disable()
    assert spans_mod.latch() is False
    assert gc.callbacks == before
    recorded = len(gc_spans(tracer))
    gc.collect()
    assert len(gc_spans(tracer)) == recorded


def test_wall_time_is_the_profilers_clock_at_open(monkeypatch):
    """A tracer that has lived 10 s while the system clock was stepped
    5 ms: ``wall_time`` is ``time.time_ns()`` at the span's open, not the
    tracer's birth plus ``perf_counter``'s seconds since."""
    clock = {"perf": 100.0, "ns": 1_792_000_000_000_000_000}
    fake = types.SimpleNamespace(**{
        k: v for k, v in vars(time).items() if not k.startswith("__")})
    fake.perf_counter = lambda: clock["perf"]
    fake.time_ns = lambda: clock["ns"]
    monkeypatch.setattr(telemetry, "time", fake)
    tr = telemetry.SpanTracer()
    clock["perf"] += 10.0
    clock["ns"] += 10_005_000_000
    sp = tr.open("outer")
    assert abs(sp.wall_time * 1e9 - clock["ns"]) < 1e6
    assert sp.ts == pytest.approx(10.0)
    clock["perf"] += 0.25
    clock["ns"] += 250_000_000
    tr.end(sp)
    late = tr.add_interruption("host/gc", 0.05)
    assert abs(late.wall_time * 1e9 - (clock["ns"] - 5e7)) < 1e6
    assert late.parent_id is None and late.dur == 0.05


def test_wall_time_lies_beside_time_ns(tracer):
    tracer.enable()
    t = time.time_ns()
    sp = tracer.begin("x")
    tracer.end(sp)
    assert t - 1e6 <= sp.wall_time * 1e9 <= time.time_ns() + 1e6


def test_dropped_counts_an_overflow():
    tr = telemetry.SpanTracer(max_spans=3)
    tr.enable()
    for i in range(5):
        tr.end(tr.begin(f"s{i}"))
    tr.add_complete("late", 0.001)
    assert tr.dropped == 3
    assert [s.name for s in tr.completed()] == ["s3", "s4", "late"]
    assert len(tr.drain()) == 3 and tr.dropped == 0
    tr.end(tr.begin("again"))
    assert tr.dropped == 0


@pytest.mark.parametrize("generation", [2, 0])
def test_a_collection_inside_the_tracers_lock_returns(tracer, monkeypatch,
                                                      generation):
    """A collection runs at the interpreter's next check, which may fall
    where the thread holds the tracer's lock (in ``_record``): the hook,
    recording on that thread, must not wait on the lock it holds. A full
    pass is a span, a short generation-0 pass a count. (A tracer of its
    own, so that a hook that does wait strands only that one.)"""
    own = telemetry.SpanTracer()
    monkeypatch.setattr(spans_mod, "get_tracer", lambda: own)
    own.enable()
    assert spans_mod.latch() is True
    record = telemetry.SpanTracer._record
    fired = []

    def collecting(self, sp):
        if not fired:
            fired.append(sp.name)
            gc.collect(generation)
        record(self, sp)

    monkeypatch.setattr(telemetry.SpanTracer, "_record", collecting)
    worker = threading.Thread(
        target=lambda: own.end(own.begin("serve/emit")), daemon=True)
    worker.start()
    worker.join(10)
    assert not worker.is_alive(), "the collector's hook waited on the lock"
    own.disable()
    spans_mod.latch()
    assert fired == ["serve/emit"]
    names = [s.name for s in own.completed()]
    assert "serve/emit" in names
    if generation:
        assert spans_mod.GC_SPAN in names
    else:
        assert spans_mod.GC_SPAN in names or own.gc_short == 1

"""The reader of where the device waits in a traced serving tick
(``benchmark/idle_spans.py`` and the four ``tick_idle_*`` per-layer
metrics), on hand-made runs: device events and program spans with a planted
offset between their clocks and known idle time under each phase. CPU only;
nothing here is a device result."""
import os
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness, idle_spans  # noqa: E402

MANIFEST = harness.load_manifest()
SERVING = ["serve_chat_closed", "serve_docs_latent_closed",
           "serve_mixed_window_closed", "serve_reason_state_closed"]
LAYER_OF = {"tick_idle_boundary_ms": "scheduler",
            "tick_idle_launch_ms": "model step",
            "tick_idle_attn_host_ms": "kernels",
            "tick_idle_gc_ms": "device"}
#: the profile's start on ``time.time_ns``'s clock, as a run would see it
START = 1_792_036_649_118_947_334
MS = 1e6


class Hand:
    """A run made by hand: ``tick`` lays one serving tick of the engine
    thread (tid 0) at ``t`` ms on the device's axis, with the device's
    busy stretches around it, and returns the tick's end. By hand, a tick's
    device idle is: boundary 2.4 ms + the sync's ``lag`` behind the device
    (emit 1.0, the tick's own 0.1, schedule 0.7, admit 0.3, begin_ragged
    0.3), launch 0.3 (forward 0.1, a layer 0.2), attention's host half
    0.3."""

    def __init__(self, lag=0.0):
        self.spans, self.events, self.tails, self.lag = [], [], [], lag

    def span(self, name, lo, hi, parent=None, tid=0):
        self.spans.append({"name": name, "t0": START + lo * MS,
                           "dur": (hi - lo) * MS, "tid": tid,
                           "id": len(self.spans) + 1, "parent": parent})
        return len(self.spans)

    def busy(self, lo, hi):
        self.events.append(["op", int(lo * MS), int((hi - lo) * MS)])

    def tick(self, t, tail=0.0, lost=False, late_copy=False, lag=None):
        self.tails.append(tail)
        end_busy = t + 8.0 + tail
        sync_end = end_busy + (self.lag if lag is None else lag)
        end = sync_end + 1.1
        tick = self.span("serve/tick", t, end)
        sched = self.span("serve/schedule", t, t + 1.0, tick)
        self.span("kv/admit", t + 0.2, t + 0.5, sched)
        self.span("kv/begin_ragged", t + 1.0, t + 1.3, tick)
        fwd = self.span("serve/forward", t + 1.3, t + 6.3, tick)
        layer = self.span("model/layer", t + 1.5, t + 3.5, fwd)
        self.span("attn/qblock", t + 2.0, t + 3.0, layer)
        layer = self.span("model/layer", t + 3.8, t + 5.8, fwd)
        self.span("attn/kda_step", t + 4.0, t + 4.6, layer)
        self.span("serve/sync", t + 6.3, sync_end, tick)
        self.span("serve/emit", sync_end, end - 0.1, tick)
        if not lost:
            self.busy(t + 1.4, t + 2.2)
            self.busy(t + 2.5, t + 5.0)
            self.busy(t + 5.2, end_busy)
        if late_copy:
            self.busy(sync_end + late_copy, sync_end + late_copy + 0.03)
        return end

    def run(self):
        return {"trace": {"events": {"/device:TPU:0": sorted(
            self.events, key=lambda e: e[1])}}}


def windows(n=40, lost=(), gc_in=None, late=(), seed=0, lag=0.0,
            lags=None, copy_after=0.02, jitter=3.0):
    """``n`` ticks back to back, tails drawn from ``seed``, one busy
    stretch before and after them (so that every tick lies inside the
    events' extent); ``gc_in``: a ``host/gc`` of 0.2 ms on another thread
    in that tick's admit; ``late``: ticks with a device event of 0.03 ms
    ``copy_after`` ms after the sync's end; ``lags``: the sync's lag a
    tick, ms (else ``lag`` for all); ``jitter``: the spread of the ticks'
    lengths, ms."""
    h = Hand(lag)
    rng = np.random.default_rng(seed)
    h.busy(0.0, 1.0)
    t = 2.0
    for i in range(n):
        if i == gc_in:
            h.span("host/gc", t + 0.2, t + 0.4, tid=3)
        t = h.tick(t, tail=float(rng.uniform(0, jitter)), lost=i in lost,
                   late_copy=copy_after if i in late else False,
                   lag=None if lags is None else float(lags[i]))
    h.busy(t + 1.0, t + 2.0)
    return h


def use(monkeypatch, h, dropped=0):
    counts = {"dropped": dropped, "gc_short": 5, "gc_short_s": 2e-3}
    monkeypatch.setattr(idle_spans, "program_spans",
                        lambda: (h.spans, counts))
    return h.run()


def read(name, run):
    return harness.load_reader(name)(run)


def test_the_four_metrics_and_the_lost_tick_by_hand(monkeypatch):
    h = windows(40, lost=(7,), gc_in=12)
    run = use(monkeypatch, h)
    n = 39                                   # 40 kept, one lost
    want = {"tick_idle_boundary_ms": (2.4 * n - 0.2) / n,
            "tick_idle_launch_ms": 0.3, "tick_idle_attn_host_ms": 0.3,
            "tick_idle_gc_ms": 0.2 / n}
    for name, ms in want.items():
        assert read(name, run) == pytest.approx(ms, abs=1e-3), name
    table = idle_spans.idle_by_span(run)
    assert (table["kept"], table["lost"]) == (40, 1)
    assert (table["gc_short"], table["gc_short_s"]) == (5, 2e-3)
    assert table["lost_s"] == pytest.approx(1e-3 * (9.1 + h.tails[7]),
                                            abs=1e-6)
    assert abs(table["start_ns"] - START) < 1e3
    by = table["idle_ms"]
    assert by["serve/emit"] == pytest.approx(1.0, abs=1e-3)
    assert by["kv/admit"] == pytest.approx((0.3 * n - 0.2) / n, abs=1e-3)
    assert by["attn/qblock"] == pytest.approx(0.3, abs=1e-3)
    # the kda span lies in busy time; between two ticks only the clocks'
    # rounding
    assert "attn/kda_step" not in by
    assert by.get(idle_spans.OUTSIDE, 0.0) < 1e-3
    assert list(by) == sorted(by, key=lambda k: -by[k])


def estimate(h):
    syncs = [s["t0"] + s["dur"] for s in h.spans
             if s["name"] == "serve/sync"]
    events = h.run()["trace"]["events"]["/device:TPU:0"]
    busy = np.asarray(idle_spans.busy_intervals(events), float)
    return idle_spans.estimate_start(syncs, busy[:, 0], busy[:, 1])


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_estimator_finds_a_planted_offset(seed):
    """Lags of 2-8 us and a device event 20 us after the sync in a tenth of
    the ticks: within 10 us."""
    h = windows(200, late=range(3, 200, 10), seed=seed,
                lag=0.002 + 0.006 * seed / 2)
    assert abs(estimate(h) - START) < 10e3


@pytest.mark.parametrize("copy_after,jitter", [(0.02, 3.0), (1.5, 3.0),
                                               (0.02, 0.3)])
def test_the_estimator_is_late_by_the_smallest_lag(copy_after, jitter):
    """The host's wake-up after a sync spreads over milliseconds when other
    threads hold the interpreter (my chip runs, PR 35): the estimate is
    late by the smallest lag, whatever the others and the late events, and
    ticks of nearly one length do not pull it a tick off."""
    rng = np.random.default_rng(7)
    lags = np.minimum(rng.lognormal(np.log(1.5), 1.0, 300), 20.0)
    h = windows(300, late=range(5, 300, 10), seed=3, lags=lags,
                copy_after=copy_after, jitter=jitter)
    assert estimate(h) - START == pytest.approx(lags.min() * MS, abs=1e3)


def test_a_truncated_tracer_or_an_old_program_reads_nothing(monkeypatch):
    h = windows(10)
    run = use(monkeypatch, h, dropped=3)
    for name in LAYER_OF:
        assert read(name, run) is None
    monkeypatch.setattr(idle_spans, "program_spans", lambda: None)
    assert read("tick_idle_gc_ms", h.run()) is None
    monkeypatch.undo()
    # the parent's tracer: no ``dropped``, its ``wall_time`` on another clock
    from paddle_tpu import profiler
    monkeypatch.setattr(profiler, "get_tracer", lambda: types.SimpleNamespace(
        completed=lambda: [], origin=0.0))
    assert idle_spans.program_spans() is None
    assert read("tick_idle_launch_ms", windows(10).run()) is None
    # every tick lost: nothing kept to average over
    run = use(monkeypatch, windows(10, lost=range(10)))
    assert read("tick_idle_boundary_ms", run) is None


def test_the_innermost_span_and_the_interval_arithmetic():
    segs = idle_spans.innermost([(0, 10, "a"), (2, 5, "b"), (3, 4, "c"),
                                 (6, 8, "d")])
    assert segs == [(0, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 5, "b"),
                    (5, 6, "a"), (6, 8, "d"), (8, 10, "a")]
    assert idle_spans._subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [
        (0, 1), (2, 4), (6, 9)]
    assert idle_spans._intersect([(0, 3), (5, 9)], [(2, 6)]) == [(2, 3),
                                                                  (5, 6)]
    assert idle_spans._by_name([(1, 7), (11, 12)], segs) == {
        "a": 2, "b": 2, "c": 1, "d": 1, idle_spans.OUTSIDE: 1}


def test_program_spans_read_the_programs_tracer_on_its_clock():
    import time
    from paddle_tpu import profiler
    tracer = profiler.get_tracer()
    tracer.drain()
    tracer.enable()
    try:
        t = time.time_ns()
        with tracer.span("outer"):
            with tracer.span("inner"):
                pass
    finally:
        tracer.disable()
    spans, counts = idle_spans.program_spans()
    tracer.drain()
    recs = {r["name"]: r for r in spans}
    assert counts == {"dropped": 0, "gc_short": tracer.gc_short,
                      "gc_short_s": tracer.gc_short_s}
    assert recs["inner"]["parent"] == recs["outer"]["id"]
    assert t - 1e3 <= recs["outer"]["t0"] <= recs["inner"]["t0"] \
        <= time.time_ns() + 1e3


@pytest.mark.parametrize("name", sorted(LAYER_OF))
def test_manifest_entry(name):
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert entry == {"name": name, "unit": "ms", "better": "lower",
                     "source": "device_trace", "layer": LAYER_OF[name],
                     "moves": "serve_tok_s", "workloads": SERVING}


def test_the_four_entries_stand_at_the_end_of_per_layer():
    """What a PR adds to the benchmark goes at the end of its list: an
    entry put in the middle reads to the driver as a change to the
    accepted entry it displaced."""
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert names[-len(LAYER_OF):] == list(LAYER_OF)

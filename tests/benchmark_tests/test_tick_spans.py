"""The readers of the spans inside the serving tick
(``benchmark/tick_spans.py`` and the ``tick_*`` per-layer metrics), on
hand-made span records with known self times. CPU only; nothing here is a
device result."""
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness, tick_spans  # noqa: E402

MANIFEST = harness.load_manifest()
LAYER_OF = {"tick_schedule_ms": "scheduler", "tick_emit_ms": "scheduler",
            "tick_kv_host_ms": "KV cache", "tick_dispatch_ms": "model step",
            "tick_attn_host_ms": "kernels", "tick_sync_ms": "device",
            "tick_span_cover_pct": "scheduler"}


class Build:
    """Hand-made span records: ``add`` nests under ``parent``."""

    def __init__(self):
        self.spans = []

    def add(self, name, t0, dur, parent=None):
        self.spans.append({"name": name, "t0": t0, "dur": dur,
                           "id": len(self.spans) + 1, "parent": parent})
        return self.spans[-1]["id"]


def one_tick(b, t0, layers=2):
    """A tick of 1.000 s at ``t0`` whose self times are, by hand: schedule
    0.100 - 0.030 admit = 0.070; kv 0.030 + 0.020; forward 0.600 less two
    layers of 0.250 = 0.100; each layer 0.250 less its 0.200 qblock = 0.050;
    each qblock 0.200 less its 0.150 schedule = 0.050; sync 0.180; emit
    0.090; 0.010 of the tick outside its children."""
    tick = b.add("serve/tick", t0, 1.000)
    sched = b.add("serve/schedule", t0, 0.100, tick)
    b.add("kv/admit", t0 + 0.010, 0.030, sched)
    b.add("kv/begin_ragged", t0 + 0.100, 0.020, tick)
    fwd = b.add("serve/forward", t0 + 0.120, 0.600, tick)
    for i in range(layers):
        layer = b.add("model/layer", t0 + 0.150 + 0.270 * i, 0.250, fwd)
        qb = b.add("attn/qblock", t0 + 0.160 + 0.270 * i, 0.200, layer)
        b.add("attn/qblock_schedule", t0 + 0.160 + 0.270 * i, 0.150, qb)
    b.add("serve/sync", t0 + 0.720, 0.180, tick)
    b.add("serve/emit", t0 + 0.900, 0.090, tick)
    return tick


@pytest.fixture
def run(monkeypatch):
    """Three ticks at 10, 11 and 12.5 s; kernel stamps from 10.2 (inside the
    first tick) to 12.9 s: the first tick began before them and is left out,
    as is a stray span outside any tick."""
    b = Build()
    for t0 in (10.0, 11.0, 12.5):
        one_tick(b, t0)
    b.add("kv/admit", 12.1, 0.3)
    monkeypatch.setattr(tick_spans, "program_spans", lambda: b.spans)
    return {"kernel_calls": [(10.2, [1], [5]), (11.3, [1], [6]),
                             (12.9, [1], [7])]}


def read(name, run):
    return harness.load_reader(name)(run)


def test_self_times_by_hand(run):
    want = {"tick_schedule_ms": 70.0, "tick_emit_ms": 90.0,
            "tick_kv_host_ms": 50.0, "tick_dispatch_ms": 200.0,
            "tick_attn_host_ms": 400.0, "tick_sync_ms": 180.0}
    for name, ms in want.items():
        assert read(name, run) == pytest.approx(ms), name
    # six phases: 0.990 s a tick, two ticks, over 11.0 .. 13.5 s
    assert read("tick_span_cover_pct", run) == pytest.approx(
        100 * 2 * 0.990 / 2.5)


def test_a_tick_outside_the_stamps_is_left_out(run):
    ticks = tick_spans.window_ticks(run)
    assert [t["t0"] for t in ticks] == [11.0, 12.5]
    run["kernel_calls"][0] = (9.9, [1], [5])
    assert [t["t0"] for t in tick_spans.window_ticks(run)] == [10.0, 11.0,
                                                               12.5]
    run["kernel_calls"] = [(50.0, [1], [5])]
    assert tick_spans.window_ticks(run) is None
    assert read("tick_sync_ms", run) is None


def test_nested_attention_spans_are_not_counted_twice(run):
    ticks = tick_spans.window_ticks(run)
    for t in ticks:
        # the attention spans' 0.400 s lie inside the layers' 0.500 s,
        # which lie inside the forward's 0.600 s: self times add up to it
        inside = sum(t["self"][n] for n in (
            "serve/forward", "model/layer", "attn/qblock",
            "attn/qblock_schedule"))
        assert inside == pytest.approx(0.600)
        assert sum(t["self"].values()) == pytest.approx(1.000)
        assert t["self"]["serve/tick"] == pytest.approx(0.010)
    whole = sum(read(n, run) for n in tick_spans.PHASES)
    assert whole == pytest.approx(990.0)


def test_fewer_layers_less_attention_time(monkeypatch):
    b = Build()
    one_tick(b, 1.0, layers=1)
    monkeypatch.setattr(tick_spans, "program_spans", lambda: b.spans)
    run = {"kernel_calls": [(0.5, [1], [1]), (1.5, [1], [1])]}
    assert read("tick_attn_host_ms", run) == pytest.approx(200.0)
    assert read("tick_dispatch_ms", run) == pytest.approx(350.0 + 50.0)


@pytest.mark.parametrize("name", sorted(LAYER_OF))
def test_none_with_nothing_to_read(name, monkeypatch):
    calls = [(1.0, [1], [1])]
    assert read(name, {}) is None
    assert read(name, {"kernel_calls": None}) is None
    # a program without the spans (the parent commit): no ``origin``
    monkeypatch.setattr(tick_spans, "program_spans", lambda: None)
    assert read(name, {"kernel_calls": calls}) is None
    monkeypatch.setattr(tick_spans, "program_spans", lambda: [])
    assert read(name, {"kernel_calls": calls}) is None


def test_program_spans_reads_the_programs_tracer(monkeypatch):
    import time
    from paddle_tpu import profiler
    tracer = profiler.get_tracer()
    tracer.drain()
    tracer.enable()
    try:
        t = time.perf_counter()
        with tracer.span("outer"):
            with tracer.span("inner"):
                pass
    finally:
        tracer.disable()
    recs = {r["name"]: r for r in tick_spans.program_spans()}
    tracer.drain()
    assert recs["inner"]["parent"] == recs["outer"]["id"]
    assert t <= recs["outer"]["t0"] <= recs["inner"]["t0"] <= \
        time.perf_counter()
    # a tracer that cannot say where its clock starts reads as nothing
    monkeypatch.setattr(profiler, "get_tracer", lambda: object())
    assert tick_spans.program_spans() is None


@pytest.mark.parametrize("name", sorted(LAYER_OF))
def test_manifest_entry(name):
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert entry["layer"] == LAYER_OF[name]
    assert entry["moves"] == "serve_tok_s"
    assert entry["workloads"] == ["serve_chat_closed"]
    assert entry["source"] == "program_span"
    pct = name == "tick_span_cover_pct"
    assert entry["unit"] == ("%" if pct else "ms")
    assert entry["better"] == ("higher" if pct else "lower")
    assert callable(harness.load_reader(name))
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}

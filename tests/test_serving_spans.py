"""The spans inside the serving tick (docs/OBSERVABILITY.md, "Spans inside
the serving tick"): on while a profiler records, off otherwise."""
import glob
import os
import threading

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.inference import ContinuousServingEngine
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.profiler import spans as spans_mod

LAYERS = 2

#: span -> its parent, as the table has them
PARENTS = {
    "serve/tick": None,
    "serve/schedule": "serve/tick",
    "kv/admit": "serve/schedule",
    "kv/begin_ragged": "serve/tick",
    "serve/forward": "serve/tick",
    "model/layer": "serve/forward",
    "attn/qblock": "model/layer",
    "attn/qblock_schedule": "attn/qblock",
    "serve/sync": "serve/tick",
    "serve/emit": "serve/tick",
}


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    return LlamaForCausalLM(llama_tiny(num_hidden_layers=LAYERS))


@pytest.fixture
def clean_tracer():
    tracer = profiler.get_tracer()
    tracer.drain()
    yield tracer
    tracer.drain()
    spans_mod.latch()


def serve(model, prompts, new=4):
    """-> (tokens of every request, the engine's counters per tick)."""
    eng = ContinuousServingEngine(model, max_batch_size=4, page_size=8,
                                  max_len=64, token_budget=16)
    out = [None] * len(prompts)

    def call(i):
        out[i] = eng.generate(prompts[i], max_new_tokens=new,
                              timeout=300).numpy()

    with eng:
        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        assert not any(t.is_alive() for t in threads)
    return out, eng


def prompts():
    rng = np.random.RandomState(3)
    return [rng.randint(1, 128, (1, n)).astype(np.int64)
            for n in (5, 11, 23)]


@pytest.fixture(scope="module")
def traced(model, tmp_path_factory):
    """A few ragged ticks under an open ``jax.profiler`` session."""
    tracer, tmp_path = profiler.get_tracer(), tmp_path_factory.mktemp("xp")
    tracer.drain()
    jax.profiler.start_trace(str(tmp_path))
    try:
        out, eng = serve(model, prompts())
    finally:
        jax.profiler.stop_trace()
    spans_mod.latch()
    # the collector's spans interrupt any of these: tests of their own
    spans = [s for s in tracer.drain() if s.name != spans_mod.GC_SPAN]
    return out, eng, spans, tmp_path


def test_names_and_parents(traced):
    _, eng, spans, _ = traced
    by_id = {s.span_id: s for s in spans}
    assert {s.name for s in spans} == set(PARENTS)
    for s in spans:
        parent = by_id.get(s.parent_id)
        assert (parent.name if parent else None) == PARENTS[s.name], s
    ticks = [s for s in spans if s.name == "serve/tick"]
    assert len(ticks) == eng.ragged_steps >= 3
    per_tick = {n: sum(s.name == n for s in spans) / len(ticks)
                for n in PARENTS}
    assert per_tick["model/layer"] == per_tick["attn/qblock"] == LAYERS
    assert per_tick["attn/qblock_schedule"] == LAYERS
    for n in ("serve/schedule", "kv/begin_ragged", "serve/forward",
              "serve/sync", "serve/emit"):
        assert per_tick[n] == 1, n
    layers = [s.args["i"] for s in spans if s.name == "model/layer"]
    assert layers == list(range(LAYERS)) * len(ticks)
    assert all(s.args["compiled"] == 1 for s in spans
               if s.name == "model/layer")
    for s in spans:
        if s.name == "attn/qblock":
            assert s.args["jobs"] >= 1 and s.args["blocks"] >= 1
            # one grid axis, the flat job list: its real jobs and padding
            assert 1 <= s.args["real_jobs"] <= s.args["jobs"]
            assert s.args["steps"] == s.args["jobs"]


def test_tick_args_are_the_engines_counter_deltas(traced):
    out, eng, spans, _ = traced
    ticks = sorted((s for s in spans if s.name == "serve/tick"),
                   key=lambda s: s.ts)
    assert [t.args["tick"] for t in ticks] == list(
        range(1, eng.ragged_steps + 1))
    assert sum(t.args["useful"] for t in ticks) == eng.useful_tokens_total
    assert sum(t.args["padded"] for t in ticks) == eng.padded_tokens_total
    assert sum(t.args["n_decode"] for t in ticks) == eng.ragged_decode_tokens
    assert sum(t.args["n_prefill"] for t in ticks) == \
        eng.ragged_prefill_tokens
    assert [t.args["compiled_layers"] for t in ticks] == [LAYERS] * len(ticks)
    assert eng.compiled_layer_calls == LAYERS * eng.ragged_steps
    for t in ticks:
        assert t.args["useful"] == t.args["n_decode"] + t.args["n_prefill"]
        assert sum(q for q, _ in t.args["spans"]) == t.args["useful"]
        assert all(ctx >= q for q, ctx in t.args["spans"])
    emitted = sum(s.args["emitted"] for s in spans if s.name == "serve/emit")
    assert emitted == sum(o.shape[1] - p.shape[1]
                          for o, p in zip(out, prompts()))
    admitted = sum(s.args["admitted"] for s in spans if s.name == "kv/admit")
    assert admitted == eng.prefills == len(out)


def test_children_partition_each_tick(traced):
    _, _, spans, _ = traced
    children = {}
    for s in spans:
        if PARENTS[s.name] == "serve/tick":
            children.setdefault(s.parent_id, []).append(s)
    for t in (s for s in spans if s.name == "serve/tick"):
        kids = sorted(children[t.span_id], key=lambda s: s.ts)
        assert [k.name for k in kids] == [
            "serve/schedule", "kv/begin_ragged", "serve/forward",
            "serve/sync", "serve/emit"]
        for a, b in zip(kids, kids[1:]):
            assert a.ts + a.dur <= b.ts + 1e-9        # in turn, no overlap
        assert sum(k.dur for k in kids) >= 0.98 * t.dur, (t, kids)


def test_spans_lie_on_the_callers_clock(traced):
    import time
    _, _, spans, _ = traced
    origin = profiler.get_tracer().origin
    now = time.perf_counter()
    for s in spans:
        assert now - 600 < origin + s.ts <= origin + s.ts + s.dur <= now


def test_xplane_holds_the_annotations(traced):
    *_, spans, tmp_path = traced
    files = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1
    data = jax.profiler.ProfileData.from_file(files[0])
    names = {}
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(spans_mod.PREFIX):
                    names[ev.name] = names.get(ev.name, 0) + 1
    ticks = sum(s.name == "serve/tick" for s in spans)
    assert names[spans_mod.PREFIX + "serve/tick"] >= ticks
    assert set(names) >= {spans_mod.PREFIX + n for n in PARENTS}


def test_no_session_no_span_and_the_same_tokens(model, traced, clean_tracer):
    traced_out = traced[0]
    clean_tracer.drain()
    assert not profiler.tracing_active()
    out, eng = serve(model, prompts())
    assert eng.ragged_steps >= 3
    assert clean_tracer.completed() == []
    assert spans_mod.span("serve/tick") is spans_mod.NULL
    for a, b in zip(out, traced_out):
        np.testing.assert_array_equal(a, b)


def test_tracing_active_without_jaxs_private_attribute(monkeypatch, tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert profiler.tracing_active()
        monkeypatch.delattr(spans_mod._jax_profiler._profile_state,
                            "profile_session")
        assert not profiler.tracing_active()
        monkeypatch.undo()
        monkeypatch.setattr(spans_mod, "_jax_profiler", object())
        assert not profiler.tracing_active()
        monkeypatch.undo()
        assert profiler.tracing_active()
    finally:
        jax.profiler.stop_trace()
    assert not profiler.tracing_active()


def test_profiler_facade_switches_the_spans_on(clean_tracer):
    assert spans_mod.latch() is False
    with profiler.Profiler(targets=[profiler.ProfilerTarget.CPU]):
        assert spans_mod.latch() is True
        with profiler.span("outer", a=1) as sp:
            with profiler.span("inner"):
                pass
            sp.set(b=2)
    assert spans_mod.latch() is False
    done = {s.name: s for s in clean_tracer.completed()}
    assert done["inner"].parent_id == done["outer"].span_id
    assert done["outer"].args == {"a": 1, "b": 2}


def test_discard_records_nothing_and_end_twice_is_one_span(clean_tracer):
    clean_tracer.enable()
    try:
        spans_mod.latch()
        outer = profiler.span("outer").begin()
        inner = profiler.span("inner").begin()
        inner.discard()
        inner.end()
        outer.end()
        outer.end(late=1)
    finally:
        clean_tracer.disable()
    done = [s for s in clean_tracer.completed()
            if s.name != spans_mod.GC_SPAN]
    assert [s.name for s in done] == ["outer"] and done[0].args is None


def test_a_site_reached_while_jax_traces_records_nothing(clean_tracer):
    clean_tracer.enable()
    try:
        spans_mod.latch()

        @jax.jit
        def f(x):
            with profiler.span("inside_jit"):
                return x + 1

        f(1.0)
        with profiler.span("eager"):
            f(2.0)
    finally:
        clean_tracer.disable()
    assert [s.name for s in clean_tracer.completed()
            if s.name != spans_mod.GC_SPAN] == ["eager"]

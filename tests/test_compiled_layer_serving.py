"""A decoder layer of a ragged serving tick as two compiled programs around
the eager kernel entry (models/llama.py ``RaggedLayerPrograms``): the same
tokens and logits as the eager pieces, one executable a token bucket, pools
donated and still readable, the engine's counter, and eager pieces for every
caller that traces the model."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework.core import Tensor
from paddle_tpu.framework.functional import FunctionalModule
from paddle_tpu.inference import ContinuousServingEngine
from paddle_tpu.models import (GPTForCausalLM, LlamaForCausalLM, gpt_tiny,
                               llama_tiny)
from paddle_tpu.models import llama as llama_mod
from paddle_tpu.models.generation import SlotPagedKVCache, block_hash_chain

LAYERS = 2
ENGINE = dict(max_batch_size=4, page_size=8, max_len=64, token_budget=16)


def make_model():
    paddle.seed(0)
    return LlamaForCausalLM(llama_tiny(num_hidden_layers=LAYERS))


@pytest.fixture(scope="module")
def model():
    return make_model()


@pytest.fixture
def eager_pieces(monkeypatch):
    """The test's own switch: every forward takes the eager pieces."""
    def force():
        monkeypatch.setattr(llama_mod.LlamaModel, "_ragged_programs",
                            lambda self, *a: None)
    return force


def serve(model, waves, new=5, **kw):
    """Each wave's prompts reach the engine in order while its loop is held
    at a tick boundary, so the schedule is the same in every run. ->
    (tokens a request, the logits of every forward, the engine, what
    ``inspect(cache)`` returned on the loop's thread at the end)."""
    inspect = kw.pop("inspect", lambda cache: None)
    eng = ContinuousServingEngine(model, **dict(ENGINE, **kw))
    logits, forward = [], model.forward

    def recording(*a, **k):
        out = forward(*a, **k)
        logits.append(np.asarray(out._data, np.float32))
        return out

    out = {}

    def call(key, prompt):
        out[key] = eng.generate(prompt, max_new_tokens=new,
                                timeout=300).numpy()

    model.forward = recording
    try:
        with eng:
            for w, prompts in enumerate(waves):
                held, gate = threading.Event(), threading.Event()
                holder = threading.Thread(
                    target=eng.run_on_loop,
                    args=(lambda e: (held.set(), gate.wait(300)), 300))
                holder.start()
                assert held.wait(300)
                threads = []
                for i, p in enumerate(prompts):
                    depth = eng._q.qsize()
                    t = threading.Thread(target=call, args=((w, i), p))
                    t.start()
                    threads.append(t)
                    while eng._q.qsize() == depth and t.is_alive():
                        threading.Event().wait(0.005)
                gate.set()
                for t in threads + [holder]:
                    t.join(300)
                assert not any(t.is_alive() for t in threads)
            seen = eng.run_on_loop(lambda e: inspect(e._cache), 300)
    finally:
        del model.forward
    return [out[k] for k in sorted(out)], logits, eng, seen


def prompts(lengths, seed=3):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, 128, (1, n)).astype(np.int64) for n in lengths]


def traffic(case):
    """-> (waves of prompts, engine options, what the run has to show)."""
    if case == "prefix_hit":
        # the second wave's first prompt repeats the first wave's
        # 24-token prompt and goes on: its leading blocks hit the index
        first = prompts((24, 7))
        longer = np.concatenate([first[0], prompts((9,), seed=5)[0]], axis=1)
        return ([first, [longer] + prompts((13,), seed=6)], {},
                lambda eng, cache: cache.prefix_hits >= 2)
    if case == "spec_verify":
        return ([prompts((5, 11, 23))], {"spec_k": 3},
                lambda eng, cache: eng.spec_drafted_tokens > 0)
    if case == "int8_pools":
        return ([prompts((5, 11, 23))], {"kv_dtype": "int8"},
                lambda eng, cache: cache.kv_quant)
    return [prompts((5, 11, 23))], {}, lambda eng, cache: True


@pytest.mark.parametrize("case", ["plain", "prefix_hit", "spec_verify",
                                  "int8_pools"])
def test_same_tokens_and_logits_as_the_eager_pieces(model, eager_pieces,
                                                    case):
    waves, kw, shown = traffic(case)
    if "spec_k" in kw:
        kw.update(spec_decode=True, draft_model=model)
    toks, logits, eng, seen = serve(model, waves, inspect=lambda c: c, **kw)
    assert eng.compiled_layer_calls == LAYERS * eng.ragged_steps > 0
    assert shown(eng, seen), case
    eager_pieces()
    toks_e, logits_e, eng_e, _ = serve(model, waves, **kw)
    assert eng_e.compiled_layer_calls == 0
    assert eng_e.ragged_steps == eng.ragged_steps
    for a, b in zip(toks, toks_e):
        np.testing.assert_array_equal(a, b)
    assert len(logits) == len(logits_e)
    # int8 pools: a row whose largest element rounds the other way moves
    # its scale by one step of 1/127
    tol = 5e-2 if case == "int8_pools" else 2e-5
    for a, b in zip(logits, logits_e):
        np.testing.assert_allclose(a, b, rtol=tol, atol=tol)


def ragged_step(model, cache, spans, tokens, bucket):
    """One ragged forward over ``spans`` = [(slot, n_new)], padded."""
    flat = np.zeros(bucket, np.int64)
    pos = np.zeros(bucket, np.int32)
    ragged, off = [], 0
    for slot, n in spans:
        start = int(cache.lens[slot])
        flat[off:off + n] = tokens[slot][start:start + n]
        pos[off:off + n] = np.arange(start, start + n)
        ragged.append((slot, off, n))
        off += n
    cache.begin_ragged(ragged)
    with paddle.no_grad():
        return np.asarray(model.forward(Tensor(flat[None]), cache=cache,
                                        position_ids=pos)._data)[0, :off]


@pytest.mark.parametrize("kv_dtype", ["native", "int8"])
def test_copy_on_write_page_under_a_prefix_hit(model, eager_pieces, kv_dtype):
    model.eval()
    rng = np.random.RandomState(11)
    shared = rng.randint(1, 128, 16)
    tokens = {0: np.concatenate([shared, rng.randint(1, 128, 8)]),
              1: np.concatenate([shared, rng.randint(1, 128, 8)])}
    # what slot 1 is fed past position 12 is not what the shared page holds
    fed = {0: tokens[0], 1: tokens[1].copy()}
    fed[1][12:16] = (shared[12:16] + 1) % 128

    def run():
        cache = SlotPagedKVCache(2, page_size=8, max_len=32,
                                 kv_dtype=kv_dtype)
        out = []
        assert cache.assign(0, tokens[0])[0] == 0
        out.append(ragged_step(model, cache, [(0, 16)], tokens, 16))
        assert cache.commit_prefix(0) == 2
        assert cache.assign(1, tokens[1])[0] == 16      # two blocks hit
        # a write into the middle of slot 1's SHARED block 1, as a
        # rejected speculative tail leaves it: the page is copied first
        cache.lens[1] = 12
        out.append(ragged_step(model, cache, [(0, 1), (1, 6)], fed, 8))
        assert cache.cow_copies == 1
        out.append(ragged_step(model, cache, [(0, 1), (1, 1)], fed, 2))
        return out, cache

    got, cache = run()
    assert cache.compiled_layer_calls == 3 * LAYERS
    eager_pieces()
    want, cache_e = run()
    assert cache_e.compiled_layer_calls == 0
    tol = 5e-2 if kv_dtype == "int8" else 2e-5
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=tol, atol=tol)
        np.testing.assert_array_equal(a.argmax(-1), b.argmax(-1))
    # the index's page kept its content, the copy diverged: in both runs
    for c in (cache, cache_e):
        kp = next(iter(c._pools.values()))[0]
        shared_page = c._index[block_hash_chain(shared, 8)[1]]
        assert c._tables[1, 1] != shared_page
        assert not np.array_equal(np.asarray(kp[:, shared_page]),
                                  np.asarray(kp[:, c._tables[1, 1]]))


def test_one_executable_a_token_bucket_and_none_after_warmup():
    model = make_model()                   # its own program cache
    eng = ContinuousServingEngine(model, **ENGINE)
    eng.warmup_programs()
    programs = model.llama._programs
    buckets = eng.declared_token_buckets()
    assert programs.program_counts() == {"pre": len(buckets),
                                         "post": len(buckets)}
    # awkward lengths: none is a bucket size
    toks, _, eng2, _ = serve(model, [prompts((13, 3, 21))])
    assert eng2.ragged_buckets_used <= buckets
    assert eng2.compiled_layer_calls == LAYERS * eng2.ragged_steps
    assert model.llama._programs is programs
    assert programs.program_counts() == {"pre": len(buckets),
                                         "post": len(buckets)}


def test_executables_follow_the_buckets_used_not_the_layers():
    model = make_model()
    _, _, eng, _ = serve(model, [prompts((13, 3, 21))])
    used = len(eng.ragged_buckets_used)
    assert 1 < used < LAYERS * used
    assert model.llama._programs.program_counts() == {"pre": used,
                                                      "post": used}


def test_weights_swapped_after_construction_are_followed(model):
    """The benchmark's ``load_weights`` replaces ``p._data`` on a built
    model: the programs take weights as arguments, so they follow."""
    other = make_model()
    toks, *_ = serve(other, [prompts((5, 11))])
    saved = [(p, p._data) for p in other.parameters()]
    rng = np.random.RandomState(1)
    try:
        for p, a in saved:
            p._data = jnp.asarray(
                np.asarray(a) + 0.05 * rng.standard_normal(a.shape),
                a.dtype)
        counts = other.llama._programs.program_counts()
        swapped, *_ = serve(other, [prompts((5, 11))])
        assert other.llama._programs.program_counts() == counts
        want = [other.generate(Tensor(p), max_new_tokens=5).numpy()
                for p in prompts((5, 11))]
    finally:
        for p, a in saved:
            p._data = a
    for a, b in zip(swapped, want):
        np.testing.assert_array_equal(a, b)
    assert any((a != b).any() for a, b in zip(swapped, toks))


def test_replicas_sharing_one_model_trace_at_the_same_time():
    """Thread-tier replicas share one model and meet new token buckets at
    the same moment: a trace never shows its tracers to the other thread."""
    model = make_model()
    ps = prompts((5, 11))
    out, errors = {}, []

    def replica(i):
        try:
            with ContinuousServingEngine(model, **ENGINE) as eng:
                out[i] = [eng.generate(p, max_new_tokens=5,
                                       timeout=300).numpy() for p in ps]
                assert eng.compiled_layer_calls == LAYERS * eng.ragged_steps
        except Exception as e:            # shown by the assert below
            errors.append(e)

    threads = [threading.Thread(target=replica, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(600)
    assert not errors
    want = [model.generate(Tensor(p), max_new_tokens=5).numpy() for p in ps]
    for got in out.values():
        for a, b in zip(got, want):
            np.testing.assert_array_equal(a, b)


def test_a_trace_never_puts_tracers_into_the_served_layers(monkeypatch):
    """The programs are traced over a twin of layer 0: while a trace is
    open, every parameter another thread could read is still an array."""
    model = make_model()
    rope = llama_mod.fused_ops.fused_rotary_position_embedding
    leaked = []

    def watching(q, k, **kw):
        if isinstance(q._data, jax.core.Tracer):
            leaked.append(any(isinstance(p._data, jax.core.Tracer)
                              for p in model.parameters()))
        return rope(q, k, **kw)

    monkeypatch.setattr(llama_mod.fused_ops,
                        "fused_rotary_position_embedding", watching)
    _, _, eng, _ = serve(model, [prompts((5,))])
    assert eng.compiled_layer_calls > 0
    assert leaked and not any(leaked)


def test_donated_pools_stay_live_and_readable(model):
    def inspect(cache):
        pools = list(cache._pools.values())
        chain = next(c for c in cache._chain if c) if any(cache._chain) \
            else None
        blob = cache.export_pages(list(cache._index)[:1])
        return pools, chain, blob, cache.num_pages, cache.commit_prefix(0)

    _, _, eng, (pools, _, blob, num_pages, _) = serve(
        model, [prompts((24, 9, 17))], new=8, inspect=inspect)
    assert eng.ragged_steps >= 8
    assert len(pools) == LAYERS
    cfg = model.config
    for kp, vp in pools:
        for a in (kp, vp):
            assert not a.is_deleted()
            assert a.shape == (cfg.num_key_value_heads, num_pages, 8,
                               cfg.head_dim)
            assert a.dtype == jnp.float32
            assert np.isfinite(np.asarray(a)).all()
    assert blob is not None and len(blob["digests"]) == 1
    k0 = blob["layers"][0][0]
    assert np.abs(np.asarray(k0)).sum() > 0       # a filled page came out


def test_counter_reads_zero_on_a_model_without_the_pieces():
    paddle.seed(0)
    gpt = GPTForCausalLM(gpt_tiny(num_hidden_layers=LAYERS))
    ps = prompts((5, 11, 23))
    toks, _, eng, _ = serve(gpt, [ps])
    assert eng.ragged_steps > 0 and eng.compiled_layer_calls == 0
    for p, got in zip(ps, toks):
        want = gpt.generate(Tensor(p), max_new_tokens=5).numpy()
        np.testing.assert_array_equal(got, want)


def test_int8_weight_streams_keep_the_eager_pieces():
    """``quantize_linears`` keeps each layer's int8 weights outside its
    parameters: shared programs would bake in layer 0's."""
    model = make_model()
    ps = prompts((5, 11))
    toks, _, eng, _ = serve(model, [ps], weight_dtype="int8")
    assert eng.quantized_linears > 0 and eng.ragged_steps > 0
    assert eng.compiled_layer_calls == 0
    eng2 = ContinuousServingEngine(model, **ENGINE)
    with eng2:
        again = [eng2.generate(p, max_new_tokens=5, timeout=300).numpy()
                 for p in ps]
    for a, b in zip(toks, again):
        np.testing.assert_array_equal(a, b)


# -- a caller that traces the model sees the eager pieces ------------------

#: pinned from the tree before the layer was split into pieces: loss,
#: sum of |logits|, and gradient norms of ``llama_tiny`` (2 layers, seed 0)
#: on the batch below
PINNED_LOSS = 4.86262321472168
PINNED_LOGITS_ABS_SUM = 403.82318115234375
PINNED_GRAD_NORMS = {
    "llama.embed_tokens.weight": 1.7168415784835815,
    "llama.layers.0.self_attn.q_proj.weight": 0.01474534347653389,
    "llama.layers.0.self_attn.k_proj.weight": 0.014518397860229015,
    "llama.layers.0.self_attn.v_proj.weight": 0.9859728813171387,
    "llama.layers.0.self_attn.o_proj.weight": 0.7893410921096802,
    "llama.layers.0.mlp.gate_proj.weight": 0.22501172125339508,
    "llama.layers.0.mlp.up_proj.weight": 0.22168771922588348,
    "llama.layers.0.mlp.down_proj.weight": 0.23110096156597137,
    "llama.layers.0.input_layernorm.weight": 0.021220870316028595,
    "llama.layers.0.post_attention_layernorm.weight": 0.006050217896699905,
    "llama.layers.1.self_attn.q_proj.weight": 0.012378818355500698,
    "llama.layers.1.self_attn.o_proj.weight": 0.8847503066062927,
    "llama.layers.1.mlp.down_proj.weight": 0.20587122440338135,
    "llama.norm.weight": 0.032687436789274216,
    "lm_head.weight": 1.5756826400756836,
}


def test_forward_and_gradient_equal_the_values_before_the_split():
    model = make_model()
    model.train()
    fm = FunctionalModule(model, training=True)
    rng = np.random.RandomState(7)
    ids = rng.randint(1, 128, (2, 12)).astype(np.int64)
    labels = np.roll(ids, -1, axis=1)

    def loss_fn(ps):
        (loss, logits), _ = fm(ps, [], jax.random.key(0), jnp.asarray(ids),
                               labels=jnp.asarray(labels))
        return loss, logits

    step = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))
    (loss, logits), grads = step(fm.param_arrays())
    assert model.llama._programs is None          # never asked for
    np.testing.assert_allclose(float(loss), PINNED_LOSS, rtol=1e-6)
    np.testing.assert_allclose(float(jnp.abs(logits).sum()),
                               PINNED_LOGITS_ABS_SUM, rtol=1e-6)
    names = [n for n, p in model.named_parameters() if p is not None]
    norms = {n: float(jnp.linalg.norm(g)) for n, g in zip(names, grads)}
    for n, want in PINNED_GRAD_NORMS.items():
        np.testing.assert_allclose(norms[n], want, rtol=1e-5, err_msg=n)


def pjit_names(jaxpr):
    """Names of every nested ``pjit`` of a jaxpr, at any depth."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pjit":
            out.append(eqn.params["name"])
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out += pjit_names(inner)
    return out


def test_a_traced_layer_takes_the_eager_pieces(model):
    layer = model.llama.layers[0]
    fm = FunctionalModule(layer, training=False)
    hidden = jnp.ones((1, 8, model.config.hidden_size), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda p, h: fm(p, [], jax.random.key(0), h)[0])(
            fm.param_arrays(), hidden)
    assert not {"pre_fn", "post_fn"} & set(pjit_names(jaxpr.jaxpr))

    # and so does the whole model when a ragged step is armed but the
    # forward is being traced (concrete inputs are what engages the path)
    cache = SlotPagedKVCache(2, page_size=8, max_len=32)
    whole = FunctionalModule(model, training=False)
    ids = jnp.ones((1, 8), jnp.int32)
    pos = np.arange(8, dtype=np.int32)

    def traced(p):
        cache.begin_ragged([(0, 0, 8)])
        return whole(p, [], jax.random.key(0), ids, cache=cache,
                     position_ids=pos)[0]

    jaxpr = jax.make_jaxpr(traced)(whole.param_arrays())
    assert cache.compiled_layer_calls == 0
    assert not {"pre_fn", "post_fn"} & set(pjit_names(jaxpr.jaxpr))


def test_two_kinds_of_layer_in_one_model_get_programs_by_kind(monkeypatch):
    """A model whose layer 0 is dense and whose others are expert layers
    (``models/deepseek_v3.py``): one pair of programs a KIND and token
    bucket, every layer of every tick compiled, the eager pieces' tokens and
    logits."""
    from paddle_tpu.models import deepseek_v3 as ds
    paddle.seed(0)
    model = ds.DeepseekV3ForCausalLM(ds.deepseek_v3_tiny())
    assert [l.kind for l in model.model.layers] == ["dense", "moe", "moe"]
    waves = [prompts((5, 11, 23))]
    toks, logits, eng, _ = serve(model, waves)
    assert eng.compiled_layer_calls == 3 * eng.ragged_steps > 0
    programs = model.model._programs
    assert sorted(programs._kinds) == ["dense", "moe"]
    buckets = len(eng.ragged_buckets_used)
    assert programs.program_counts() == {"pre": 2 * buckets,
                                         "post": 2 * buckets}
    # the expert layers' device counters came back with the ticks' syncs
    assert eng.model_counters["moe_expert_tokens"].sum() == \
        2 * 4 * eng.useful_tokens_total
    monkeypatch.setattr(ds.DeepseekV3Model, "_ragged_programs",
                        lambda self, *a: None)
    toks_e, logits_e, eng_e, _ = serve(model, waves)
    assert eng_e.compiled_layer_calls == 0
    for a, b in zip(toks, toks_e):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(logits, logits_e):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


def test_llama_layers_are_one_kind(model):
    serve(model, [prompts((5,))])
    assert list(model.llama._programs._kinds) == ["layer"]

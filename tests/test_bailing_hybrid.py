"""``models/bailing_hybrid.py`` (Ling-3.0-flash's family) at a CI size
against ``benchmark/reference/bailing_hybrid.py`` on seeded weights: the
cache-less forward; chunked prefill then decode through the serving cache's
state a slot (LOGITS, spans off the chunk multiple, two requests reusing one
slot); the four 4-way expert shares; the engine on the normal path; and
everything that refuses a cache with a state."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.inference import ContinuousServingEngine
from paddle_tpu.models.bailing_hybrid import (
    BailingHybridConfig, BailingHybridForCausalLM, bailing_hybrid_tiny)
from paddle_tpu.models.deepseek_v3 import DeepseekV3Config
from paddle_tpu.models.generation import HostKVPool, SlotPagedKVCache

from benchmark import weights_bailing_hybrid as weights
from benchmark.reference import bailing_hybrid as ref

SEED = 5
#: the tiny model's configuration as a benchmark file would state it
CFG = dict(
    vocab_size=128, hidden_size=64, intermediate_size=160,
    moe_intermediate_size=32, num_hidden_layers=7, num_attention_heads=4,
    head_dim=16, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, num_experts=16, num_experts_per_tok=4, n_group=4,
    topk_group=2, num_shared_experts=1,
    moe_shared_expert_intermediate_size=32, first_k_dense_replace=1,
    layer_kinds=["kda"] * 6 + ["mla"], rope_theta=10000.0,
    max_position_embeddings=128, short_conv_kernel_size=4,
    kda_lower_bound=-5, rms_norm_eps=1e-6, routed_scaling_factor=2.5,
    norm_topk_prob=True, initializer_range=0.02, router_bias_std=0.005,
    held_experts=[0, 16], rope_scaling=None)


def build(cfg=CFG, seed=SEED):
    kw = {k: v for k, v in cfg.items() if k != "router_bias_std"}
    model = BailingHybridForCausalLM(BailingHybridConfig(**kw))
    model.eval()
    named = list(model.named_parameters())
    table = weights.leaf_table(cfg)
    assert [(n, tuple(p.shape)) for n, p in named] == \
        [(n, tuple(s)) for n, s, _ in table]
    for (_, p), a in zip(named, weights.make_weights(cfg, seed, "float32")):
        p._data = a
    return model


@pytest.fixture(scope="module")
def model():
    return build()


def reference_logits(ids, cfg=CFG):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits(
            cfg, ref.seeded_group(cfg, SEED, "float32"), np.asarray(ids)))


def new_cache(**kw):
    kw.setdefault("enable_prefix_cache", False)
    return SlotPagedKVCache(3, page_size=8, max_len=64, num_pages=40,
                            state_layers=True, **kw)


def ticks(model, cache, seqs, schedule):
    """What the engine's ticks do: each tick a list of (slot, new tokens),
    packed into one flat batch padded to a multiple of 8 -> {slot: the
    logits of its tokens}."""
    out = {s: [] for s in seqs}
    for tick in schedule:
        spans, flat, pos = [], [], []
        for slot, n in tick:
            a = int(cache.lens[slot])
            spans.append((slot, len(flat), n))
            flat += list(seqs[slot][a:a + n])
            pos += list(range(a, a + n))
        pad = -len(flat) % 8
        cache.begin_ragged(spans)
        lg = np.asarray(model.forward(
            paddle.to_tensor(np.asarray(flat + [0] * pad)[None]), cache=cache,
            position_ids=np.asarray(pos + [0] * pad, np.int32))._data[0])
        for slot, qs, n in spans:
            out[slot].append(lg[qs:qs + n])
    return {s: np.concatenate(v) for s, v in out.items() if v}


def test_the_layers_are_what_the_configuration_says(model):
    assert [l.kind for l in model.model.layers] == \
        ["kda_dense"] + ["kda_moe"] * 5 + ["mla_moe"]
    assert model.kv_state_layers == 6 and not model.supports_cache
    # the published rule where no list is given: every sixth layer is MLA
    kinds = BailingHybridConfig(num_hidden_layers=12).layer_kinds
    assert kinds == (["kda"] * 5 + ["mla"]) * 2
    spec = model.model.layers[1].state_spec()
    assert spec["S"] == ((4, 16, 16), jnp.float32)
    assert spec["conv"][0] == (3, 3 * 4 * 16)


def test_cacheless_forward_against_the_reference(model):
    ids = np.random.default_rng(0).integers(1, 128, 40)
    got = np.asarray(model(paddle.to_tensor(ids[None]))._data[0])
    assert np.abs(got - reference_logits(ids)).max() < 2e-5


def test_chunked_prefill_then_decode_against_the_references_full_forward(
        model):
    """Two slots in one flat batch: a 37-token prompt in chunks of 19 and 18
    (neither a multiple of the kernel's 16 rows nor of a page) beside a
    20-token one in 5 + 15, then single-token steps; then ANOTHER request
    in slot 1: its logits are a fresh sequence's (admission zeroed the
    state). LOGITS compared, through the compiled layer programs."""
    rng = np.random.default_rng(1)
    seqs = {0: rng.integers(1, 128, 45), 1: rng.integers(1, 128, 30)}
    cache = new_cache()
    cache.assign(0, seqs[0][:37])
    cache.assign(1, seqs[1][:20])
    schedule = ([[(0, 19)], [(0, 18), (1, 5)], [(0, 1), (1, 15)]]
                + [[(1, 1), (0, 1)]] * 7 + [[(1, 1)]] * 3)
    got = ticks(model, cache, seqs, schedule)
    for slot, ids in seqs.items():
        assert len(got[slot]) == len(ids)
        assert np.abs(got[slot] - reference_logits(ids)).max() < 2e-5
    assert cache.compiled_layer_calls == 7 * len(schedule)
    counted = cache.state_counters
    assert counted["kda_steps"] == len(schedule)
    assert counted["kda_chunk_tokens"] == 19 + 18 + 5 + 15
    assert counted["kda_step_rows"] == 45 + 30 - 57
    # the one MLA layer's pool is the cache's only pool
    assert len(cache._pools) == 1 and len(cache._states) == 6
    cache.free(1)
    seqs = {1: rng.integers(1, 128, 20)}
    cache.assign(1, seqs[1][:18])
    assert cache.state_resets == 1
    got = ticks(model, cache, seqs, [[(1, 18)], [(1, 1)], [(1, 1)]])
    assert np.abs(got[1] - reference_logits(seqs[1])).max() < 2e-5


def test_a_slot_that_is_not_reset_serves_another_requests_state(
        model, monkeypatch):
    """The fault ``state_not_reset`` shows in the logits."""
    monkeypatch.setattr(SlotPagedKVCache, "reset_state",
                        lambda self, slot: None)
    rng = np.random.default_rng(2)
    cache = new_cache()
    first, second = rng.integers(1, 128, 12), rng.integers(1, 128, 12)
    cache.assign(0, first)
    ticks(model, cache, {0: first}, [[(0, 12)]])
    cache.free(0)
    cache.assign(0, second)
    got = ticks(model, cache, {0: second}, [[(0, 12)]])[0]
    assert np.abs(got - reference_logits(second)).max() > 1e-3


def test_the_four_expert_shares_add_up_to_the_uncut_layer():
    """Section 4 of the model-configs guide: one expert layer cut 4 ways
    (experts 0-3, 4-7, 8-11, 12-15 of 16), each share the held experts'
    sum + the shared expert: the four sums, the shared expert counted ONCE,
    are the uncut reference's layer."""
    x = jnp.asarray(np.random.default_rng(3).normal(size=(1, 24, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        w = ref.seeded_group(CFG, SEED, "float32")(weights.layer_prefix(1))
        idx, wts, _ = ref.ds._route(x[0], w["experts.router"],
                                    w["experts.router_bias"], CFG)
        want = np.asarray(ref.expert_half(
            jnp.zeros_like(x[0]), x[0], idx, wts, w, (0, 16), 32))
    total, shared = 0.0, None
    for lo in (0, 4, 8, 12):
        layer = build(dict(CFG, held_experts=[lo, 4])).model.layers[1]
        # a share holds ITS experts of the uncut layer's
        for name in ("w_gate", "w_up", "w_down"):
            getattr(layer.experts, name)._data = w["experts." + name][
                lo:lo + 4]
        routed, counts = layer.experts(paddle.to_tensor(x))
        total = total + np.asarray(routed._data[0])
        shared = np.asarray(layer.shared_experts(paddle.to_tensor(x))._data[0])
    assert np.abs(total + shared - want).max() < 2e-5


def test_the_engine_serves_it_on_the_normal_path(model):
    """Ragged scheduler, the state a slot beside the latent pool, three
    kinds of compiled layer, more requests than slots: every served token
    is the reference's own first choice."""
    engine = ContinuousServingEngine(model, max_batch_size=2, page_size=8,
                                     max_len=64, token_budget=16,
                                     prefill_chunk_tokens=16)
    assert engine.state_layers == 6 and not engine.enable_prefix_cache
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, 128, n) for n in (37, 5, 22)]
    with engine:
        outs = [np.asarray(engine.generate(p[None], max_new_tokens=4))[0]
                for p in prompts]
        counted = engine.kv_counters()
    assert engine.compiled_layer_calls == 7 * engine.ragged_steps > 0
    assert counted["kda_chunk_tokens"] >= 37 + 5 + 22 - 3
    assert counted["state_resets"] >= 2
    for p, o in zip(prompts, outs):
        seq = np.concatenate([p, o[-4:]])
        want = reference_logits(seq)[len(p) - 1:-1].argmax(-1)
        assert (want == o[-4:]).all()


@pytest.mark.parametrize("what", ["prefix_cache", "int8_pages", "sep",
                                  "host_tier", "rollback", "export",
                                  "import", "no_state_flag"])
def test_what_cannot_serve_a_state_refuses_it(model, what):
    if what == "prefix_cache":
        with pytest.raises(NotImplementedError, match="prefix cache"):
            new_cache(enable_prefix_cache=True)
    elif what == "int8_pages":
        with pytest.raises(NotImplementedError, match="int8"):
            new_cache(kv_dtype="int8")
    elif what == "sep":
        with pytest.raises(NotImplementedError, match="sep"):
            new_cache(allow_page_overcommit=True)
        with pytest.raises(NotImplementedError, match="sep"):
            new_cache().assign_sep(0, 16, 8)
    elif what == "host_tier":
        with pytest.raises(NotImplementedError, match="host KV tier"):
            new_cache(host_pool=HostKVPool(8))
    elif what == "rollback":
        cache = new_cache()
        cache.assign(0, np.arange(1, 9))
        cache.begin_ragged([(0, 0, 8)])
        cache.advance(8)
        with pytest.raises(NotImplementedError, match="state a slot"):
            cache.rollback(0, 2)
    elif what == "export":
        with pytest.raises(NotImplementedError, match="state a slot"):
            new_cache().export_pages([b"x"])
    elif what == "import":
        with pytest.raises(NotImplementedError, match="state a slot"):
            new_cache().import_pages({"pages": []})
    else:
        cache = SlotPagedKVCache(2, page_size=8, max_len=64, num_pages=20,
                                 enable_prefix_cache=False)
        cache.begin_ragged([(0, 0, 8)])
        with pytest.raises(ValueError, match="state_layers"):
            model.forward(paddle.to_tensor(np.ones((1, 8), np.int64)),
                          cache=cache, position_ids=np.arange(8, dtype=np.int32))


@pytest.mark.parametrize("kw,match", [
    (dict(spec_decode=True), "speculative"),
    (dict(host_pool_mb=8), "host KV tier"),
    (dict(kv_dtype="int8"), "int8"),
    (dict(sep_prefill=True, sep_stripe_tokens=8), "sep")])
def test_the_engine_refuses_what_a_state_cannot_have(model, kw, match):
    with pytest.raises(NotImplementedError, match=match):
        ContinuousServingEngine(model, max_batch_size=2, page_size=8,
                                max_len=64, token_budget=16, **kw)


def test_the_engine_passes_the_prefix_cache_on_only_when_asked(model):
    engine = ContinuousServingEngine(model, max_batch_size=2, page_size=8,
                                     max_len=64, token_budget=16,
                                     enable_prefix_cache=True)
    with pytest.raises(NotImplementedError, match="prefix cache"):
        engine._new_cache()


@pytest.mark.parametrize("kw,match", [
    (dict(expert_swiglu_limit_list=[0, 4, 0, 0, 0, 0, 0]), "clamp"),
    (dict(share_expert_swiglu_limit_list=[0] * 6 + [5]), "clamp"),
    (dict(num_nextn_predict_layers=1), "multi-token"),
    (dict(kda_safe_gate=False), "unbounded"),
    (dict(score_function="softmax"), "router"),
    (dict(layer_kinds=["kda"] * 3), "layer_kinds")])
def test_the_configuration_refuses_what_is_not_built(kw, match):
    with pytest.raises(ValueError, match=match):
        bailing_hybrid_tiny(**kw)
    # a limit of a layer that the cut leaves out is nobody's business
    bailing_hybrid_tiny(expert_swiglu_limit_list=[0] * 7 + [4],
                        layer_indices=[0, 1, 2, 3, 4, 5, 6])


def test_deepseek_v3s_configuration_keeps_refusing_what_it_does_not_build():
    with pytest.raises(ValueError, match="query rank"):
        DeepseekV3Config(q_lora_rank=None)
    with pytest.raises(ValueError, match="output gate"):
        DeepseekV3Config(attn_output_gate="head_wise")

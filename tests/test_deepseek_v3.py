"""``models/deepseek_v3.py`` at a small size against the plain reference
(``benchmark/reference/deepseek_v3.py``, which imports nothing of the
program and is handed the program's own seeded parameters): the cache-less
forward (expanded attention), chunked prefill then decode through
``SlotPagedKVCache`` behind the engine (absorbed attention over the latent
pool), a re-asked prompt served from prefix pages, the MTP module, the
shares of an expert layer adding up to the uncut layer, the router's group
rule on hand-made scores, and the latent pool's layout.

Tolerance. Program and reference both compute in float32 here (the conftest
pins the highest matmul precision), in different orders of operations:
logits of magnitude ~0.5 agree to a few 1e-7. ``ATOL`` = 1e-4 leaves 100x
room above that and lies 20x under what bf16 storage of the activations
costs (2^-9 relative on values of ~0.5: ~2e-3 after 3 layers), so the same
comparison with the model in bf16 fails it, which one test asserts.
"""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework.core import Tensor
from paddle_tpu.incubate.distributed.models.moe import held as held_mod
from paddle_tpu.incubate.distributed.models.moe.held import (
    HeldExperts, group_limited_topk, grouped_tiling, held_expert_sum,
    sigmoid_group_route, tile_vmem_bytes)
from paddle_tpu.inference import ContinuousServingEngine
from paddle_tpu.models.deepseek_v3 import (DeepseekV3ForCausalLM,
                                           deepseek_v3_tiny)
from paddle_tpu.models.generation import SlotPagedKVCache, kv_page_nbytes
from qblock_oracle import qblock_schedule

ref = importlib.import_module("benchmark.reference.deepseek_v3")
rpa = importlib.import_module("paddle_tpu.ops.pallas.ragged_paged_attention")

ATOL = 1e-4
ENGINE = dict(max_batch_size=2, page_size=8, max_len=96, token_budget=16,
              prefill_chunk_tokens=16)


def make_model(dtype="float32", **kw):
    paddle.seed(7)
    paddle.set_default_dtype(dtype)
    try:
        model = DeepseekV3ForCausalLM(deepseek_v3_tiny(**kw))
    finally:
        paddle.set_default_dtype("float32")
    rng = np.random.default_rng(11)
    for name, p in model.named_parameters():
        if name.endswith("router_bias"):        # non-zero: it must choose
            p._data = jnp.asarray(rng.normal(0, 0.05, p.shape), jnp.float32)
    model.eval()
    return model


def ref_config(model):
    c = model.config
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "kv_lora_rank", "rms_norm_eps", "rope_theta",
            "rope_scaling", "n_group", "topk_group", "num_experts_per_tok",
            "routed_scaling_factor", "norm_topk_prob",
            "first_k_dense_replace", "n_routed_experts")
    cfg = {k: getattr(c, k) for k in keys}
    cfg["held_experts"] = list(c.held_experts or (0, c.n_routed_experts))
    return cfg


def ref_group(model):
    """The program's parameters as the reference takes its weights."""
    named = {n: p._data for n, p in model.named_parameters()}
    return lambda prefix: {n[len(prefix):]: a for n, a in named.items()
                           if n.startswith(prefix)}


@pytest.fixture(scope="module")
def model():
    return make_model()


@pytest.fixture(scope="module")
def prompt():
    return np.random.default_rng(3).integers(1, 128, 40)


def test_cacheless_forward_matches_the_reference(model, prompt):
    got = np.asarray(model(Tensor(prompt[None]))._data[0])
    want = np.asarray(ref.logits(ref_config(model), ref_group(model), prompt))
    assert np.abs(want).max() > 0.1            # the comparison has a scale
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)


def test_bf16_in_place_of_float32_fails_the_tolerance(model, prompt):
    low = make_model("bfloat16")
    got = np.asarray(low(Tensor(prompt[None]))._data[0], np.float32)
    want = np.asarray(ref.logits(ref_config(model), ref_group(model), prompt))
    assert np.abs(got - want).max() > 3 * ATOL


def serve_logits(model, prompts, new=6, **kw):
    """Serve ``prompts`` one after another; -> for each (served tokens,
    {position: the logits the engine sampled its successor from or would
    have})."""
    eng = ContinuousServingEngine(model, **dict(ENGINE, **kw))
    seen, forward = [], model.forward

    def recording(*a, **k):
        out = forward(*a, **k)
        seen.append((np.asarray(k["position_ids"]),
                     np.asarray(out._data[0], np.float32)))
        return out

    results = []
    model.forward = recording
    try:
        with eng:
            for p in prompts:
                del seen[:]
                toks = eng.generate(p[None], max_new_tokens=new,
                                    timeout=300).numpy()[0]
                at = {}
                for pos, lg in seen:       # one request: one span at 0
                    n = 1
                    while n < len(pos) and pos[n] == pos[0] + n:
                        n += 1
                    at.update({int(pos[i]): lg[i] for i in range(n)})
                results.append((toks, at))
    finally:
        del model.forward
    return results, eng


def test_chunked_prefill_and_decode_through_the_latent_pool(model, prompt):
    """Absorbed attention over the paged latent pool, compiled layers,
    chunks of 16 then single tokens: the logits at every position equal the
    reference's full forward over prompt + served tokens."""
    ((toks, at),), eng = serve_logits(model, [prompt])
    want = np.asarray(ref.logits(ref_config(model), ref_group(model), toks))
    assert sorted(at) == list(range(len(toks) - 1))
    got = np.stack([at[i] for i in range(len(toks) - 1)])
    np.testing.assert_allclose(got, want[:-1], atol=ATOL, rtol=0)
    assert eng.compiled_layer_calls == 3 * eng.ragged_steps
    np.testing.assert_array_equal(toks[len(prompt):],
                                  want[len(prompt) - 1:-1].argmax(-1))


def test_a_reasked_prompt_is_served_from_prefix_pages(model, prompt):
    (cold, warm), eng = serve_logits(model, [prompt, prompt])
    assert eng.prompt_tokens_cached == 32          # 4 of its 5 full pages
    assert eng.prompt_tokens_admitted == 80
    np.testing.assert_array_equal(cold[0], warm[0])
    for pos, lg in warm[1].items():                # only the tail was run
        assert pos >= 32
        np.testing.assert_allclose(lg, cold[1][pos], atol=ATOL, rtol=0)
    # what the model counted on the device came back with the ticks' syncs
    per_expert = eng.model_counters["moe_expert_tokens"]
    assert per_expert.shape == (16,) and per_expert.sum() > 0
    assert eng.model_counters["moe_unheld_tokens"] == 0


def test_mtp_logits_match_the_reference(prompt):
    model = make_model(num_nextn_predict_layers=1)
    got = np.asarray(model.mtp_logits(prompt[None])._data[0])
    want = np.asarray(ref.mtp_logits(ref_config(model), ref_group(model),
                                     prompt))
    assert got.shape == (len(prompt) - 1, 128)
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
    with pytest.raises(ValueError, match="MTP"):
        make_model().mtp_logits(prompt[None])


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(model):
    """held = each quarter of the 16 experts in turn; the shared expert is
    what every chip computes alike and counts once."""
    moe = model.model.layers[1].mlp
    cfg, group = ref_config(model), ref_group(model)
    w = {"mlp." + k: v for k, v in group("model.layers.1.mlp.").items()}
    x = jnp.asarray(np.random.default_rng(5).normal(0, 1, (24, 64)),
                    jnp.float32)
    want = np.asarray(ref.moe(x, w, cfg))          # all 16 held: uncut
    shared = np.asarray(moe.shared_experts(Tensor(x))._data)
    total, tokens = shared.copy(), 0
    for lo in (0, 4, 8, 12):
        part = HeldExperts(64, 32, 16, 4, n_group=4, topk_group=2,
                           scale=2.5, held=(lo, 4))
        full = moe.experts
        part.router._data, part.router_bias._data = (full.router._data,
                                                     full.router_bias._data)
        for name in ("w_gate", "w_up", "w_down"):
            getattr(part, name)._data = getattr(full, name)._data[lo:lo + 4]
        out, counts = part(Tensor(x))
        total += np.asarray(out._data)
        tokens += int(counts["moe_expert_tokens"]._data.sum())
        # the reference, told the same share, gives the same part
        one = np.asarray(ref.moe(x, {k: (v[lo:lo + 4] if ".w_" in k else v)
                                     for k, v in w.items()},
                                 dict(cfg, held_experts=[lo, 4])))
        np.testing.assert_allclose(np.asarray(out._data) + shared, one,
                                   atol=ATOL, rtol=0)
    np.testing.assert_allclose(total, want, atol=ATOL, rtol=0)
    assert tokens == 24 * 4                        # no token dropped


def test_router_group_rule_on_hand_made_scores():
    """8 experts in 4 groups of 2, 2 groups kept, 2 experts a token."""
    s = jnp.asarray([[0.9, 0.1,   0.5, 0.45,   0.6, 0.3,   0.2, 0.2]])
    # group sums of the two best: 1.0, 0.95, 0.9, 0.4 -> groups 0 and 1;
    # the best two among them: experts 0 and 2
    assert group_limited_topk(s, 4, 2, 2).tolist() == [[0, 2]]
    # a group of two middling experts beats a group with one star
    star = jnp.asarray([[0.99, 0.0, 0.55, 0.5, 0.52, 0.51, 0.0, 0.0]])
    assert sorted(group_limited_topk(star, 4, 2, 2)[0].tolist()) == [2, 4]
    # the bias chooses but does not weigh: logits whose sigmoid is ``s``
    x = jnp.eye(8, dtype=jnp.float32)[:1]
    w_router = jnp.zeros((8, 8)).at[0].set(jnp.log(s[0] / (1 - s[0])))
    bias = jnp.zeros(8).at[7].set(5.0).at[6].set(5.0)
    idx, w = sigmoid_group_route(x, w_router, bias, n_group=4, topk_group=2,
                                 top_k=2, scale=2.5)
    assert sorted(idx[0].tolist()) == [6, 7]       # chosen by the bias
    np.testing.assert_allclose(np.asarray(w[0]), [1.25, 1.25], rtol=1e-5)
    np.testing.assert_allclose(float(w.sum()), 2.5, rtol=1e-6)
    idx, w = sigmoid_group_route(x, w_router, jnp.zeros(8), n_group=4,
                                 topk_group=2, top_k=2, scale=2.5)
    assert idx[0].tolist() == [0, 2]
    np.testing.assert_allclose(np.asarray(w[0]),
                               2.5 * np.array([0.9, 0.5]) / 1.4, rtol=1e-5)


def test_the_reference_says_by_how_much_its_router_decided(model, prompt):
    """The reference's margins on hand-made scores (the same 8 experts in 4
    groups as above); its own choices forced on it change nothing; and the
    readings ``served_gaps`` gives beside the served tokens."""
    cfg = dict(n_group=4, topk_group=2, num_experts_per_tok=2,
               routed_scaling_factor=2.5)
    s = jnp.asarray([0.9, 0.1, 0.5, 0.45, 0.6, 0.3, 0.2, 0.2])
    x = jnp.eye(8, dtype=jnp.float32)[:1]
    w_router = jnp.zeros((8, 8)).at[0].set(jnp.log(s / (1 - s)))
    idx, wts, margins = ref._route(x, w_router, jnp.zeros(8), cfg)
    assert idx[0].tolist() == [0, 2]
    # groups 1.0, 0.95 kept, 0.9 the best one dropped: 0.05; experts 0.9,
    # 0.5 chosen, 0.45 the best one left among the kept groups: 0.05
    np.testing.assert_allclose(np.asarray(margins[0]), [0.05, 0.05],
                               atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(ref.route(x, w_router, jnp.zeros(8), cfg)[1]),
        np.asarray(wts))
    # one forward, its routing kept; forced on a second pass: the same
    rcfg, group = ref_config(model), ref_group(model)
    seen = []
    want = np.asarray(ref.logits(rcfg, group, prompt, seen=seen))
    assert len(seen) == 2 and seen[0][0].shape == (40, 4)
    assert seen[0][1].shape == (40, 2) and float(seen[0][1].min()) >= 0
    got = np.asarray(ref.logits(rcfg, group, prompt, routing=seen))
    np.testing.assert_allclose(got, want, atol=1e-6)
    # other experts forced: another answer
    rolled = [((idx + 1) % 16, m) for idx, m in seen]
    assert np.abs(np.asarray(ref.logits(rcfg, group, prompt, routing=rolled))
                  - want).max() > 1e-3
    # the readings (weights drawn from a seed here, so the tokens given as
    # served are arbitrary ones)
    served = want[31:39].argmax(-1)
    cfg_w = dict(rcfg, initializer_range=0.02, router_bias_std=0.05,
                 intermediate_size=model.config.intermediate_size,
                 moe_intermediate_size=model.config.moe_intermediate_size,
                 q_lora_rank=model.config.q_lora_rank, n_shared_experts=1,
                 num_nextn_predict_layers=0)
    gaps = ref.served_gaps(cfg_w, 5, [(prompt[:32], served)], 48,
                           quant="int8", dtype="float32",
                           readings=("bf16", "bf16_routed"))
    assert set(gaps) == {"served", "margin", "margins", "altered", "int8",
                         "bf16", "bf16_routed"}
    assert all(len(gaps[k]) == 8 for k in gaps)
    assert np.asarray(gaps["margins"]).shape == (8, 2, 2)
    assert gaps["margin"] == [float(np.min(m)) for m in gaps["margins"]]
    assert min(gaps["altered"]) > 0


def test_the_latent_pool_is_one_array_a_layer(model, prompt):
    cache = SlotPagedKVCache(2, page_size=8, max_len=64, num_pages=20)
    cache.assign(0, prompt[:16])
    cache.begin_ragged([(0, 0, 16)])
    model.forward(Tensor(prompt[None, :16]), cache=cache,
                  position_ids=np.arange(16, dtype=np.int32))
    assert len(cache._pools) == 3
    for pools in cache._pools.values():
        (pool,) = pools                            # never a K and a V pool
        assert pool.shape == (1, 20, 16 + 8, 8) and pool.dtype == jnp.float32
    assert cache.page_nbytes == 3 * 8 * 24 * 4
    assert cache.page_nbytes == kv_page_nbytes(1, 24, 8, num_layers=3,
                                               latent=True)
    # at the published widths in bf16: 576 values a token a layer
    assert kv_page_nbytes(1, 576, 16, native_dtype="bfloat16", num_layers=5,
                          latent=True) == 92160
    # pages leave and come back whole (disaggregated handoff, host tier)
    cache.commit_prefix(0)
    blob = cache.export_pages(cache._chain[0])
    assert [len(arrs) for arrs in blob["layers"]] == [1, 1, 1]
    other = SlotPagedKVCache(2, page_size=8, max_len=64, num_pages=20)
    assert other.import_pages(blob) == 2
    # a latent layer attends through an armed ragged step alone
    with pytest.raises(NotImplementedError, match="begin_ragged"):
        model.forward(Tensor(prompt[None, :8]), cache=other,
                      position_ids=np.arange(8, dtype=np.int32))


def _dense_latent_attention(q, pool, tables, ss, qs, ql, cl, vd, scale):
    out = np.zeros(q.shape[:2] + (vd,), np.float32)
    page = pool.shape[3]                   # [1, pages, d, page_size]
    for i in range(len(ss)):
        rows = np.concatenate([np.asarray(pool[0, tables[ss[i], p]]).T
                               for p in range(-(-cl[i] // page))])[:cl[i]]
        for j in range(ql[i]):
            vis = cl[i] - ql[i] + j + 1
            s = np.asarray(q[qs[i] + j], np.float64) @ rows[:vis].T * scale
            w = np.exp(s - s.max(-1, keepdims=True))
            out[qs[i] + j] = (w / w.sum(-1, keepdims=True)) @ rows[:vis, :vd]
    return out


def test_latent_kernel_and_its_flat_job_list():
    rng = np.random.default_rng(0)
    heads, d, vd, page, slots, pps, n_pages = 4, 24, 16, 8, 5, 12, 40
    pool = jnp.asarray(rng.standard_normal((1, n_pages, d, page)),
                       jnp.float32)
    tables = np.zeros((slots, pps), np.int32)
    tables[:, :7] = rng.permutation(np.arange(1, n_pages))[:35].reshape(5, 7)
    ss, qs = np.array([0, 2, 4, 1]), np.array([0, 1, 2, 3])
    ql, cl = np.array([1, 1, 1, 20]), np.array([50, 9, 33, 41])
    q = jnp.asarray(rng.standard_normal((32, heads, d)), jnp.float32)
    got = np.asarray(rpa.ragged_paged_attention(
        q, pool, None, tables, ss, qs, ql, cl, sm_scale=0.3, value_dim=vd,
        interpret=True))
    want = _dense_latent_attention(q, pool, tables, ss, qs, ql, cl, vd, 0.3)
    np.testing.assert_allclose(got[:23], want[:23], atol=2e-6, rtol=0)
    assert np.isfinite(got).all()                  # padding rows too
    # the flat list holds the jobs of the grid of ``blocks x the longest
    # block's jobs`` (tests/qblock_oracle.py), block by block
    row_slot, row_ctx, jobs = rpa.latent_job_list(32, ss, qs, ql, cl, tables,
                                                  8, page)
    _, _, jp, js, jk = qblock_schedule(32, ss, qs, ql, cl, tables, 8, page)
    assert jobs.shape == (4, 64) and (jobs[0, 1:] >= jobs[0, :-1]).all()
    for b in range(4):
        mine = {tuple(j[1:]) for j in jobs.T if j[0] == b and j[2] >= 0}
        theirs = {(p, s, k) for p, s, k in zip(jp[b], js[b], jk[b])
                  if s >= 0}
        # (the oracle also walks slot 0's first page for padding rows)
        assert mine == theirs or mine | {(tables[0, 0], 0, 0)} == theirs
    assert (row_slot[:23] >= 0).all() and (row_slot[23:] == -1).all()
    assert row_ctx[3:23].tolist() == list(range(22, 42))


# -- the held experts' grouped products: the tile comes from the shapes -----


@pytest.mark.parametrize("rows,k,n,out,want", [
    # the three expert cells at their 512-token bucket: gate / up, then down
    (4096, 2560, 768, 2, (128, 2560, 768)),          # Ling
    (4096, 768, 2560, 4, (128, 768, 2560)),
    (4096, 7168, 2048, 2, (128, 7168, 256)),         # GigaChat
    (4096, 2048, 7168, 4, (128, 2048, 1024)),
    (3072, 2560, 768, 2, (128, 2560, 768)),          # SmallThinker
    (3072, 768, 2560, 4, (128, 768, 2560)),
    (192, 2560, 768, 2, (64, 2560, 768)),            # its decode tick
    (128, 7168, 2048, 2, (128, 7168, 256)),          # GigaChat's
    (8, 7168, 2048, 2, (8, 7168, 256)),              # a one-token bucket
    (6, 2560, 768, 2, None),          # ... whose rows no tile divides
    (65536, 7168, 2048, 2, (128, 7168, 256)),  # 16 x the cell's tokens a tick
    (4096, 7100, 2048, 2, None),      # a K that 128 does not divide
    (4096, 7168, 2000, 2, None),      # an N that no block divides
    (4096, 32768, 2048, 2, None),     # a K whose narrowest block does not fit
])
def test_grouped_tiling_is_arithmetic_on_shapes(rows, k, n, out, want):
    got = grouped_tiling(rows, k, n, 2, out)
    assert got == want
    if got:
        tm, tk, tn = got
        assert rows % tm == 0 and tk == k and n % tn == 0 and tn % 128 == 0
        assert held_mod.TILE_ROWS_MIN <= tm <= held_mod.TILE_ROWS_MAX


def test_grouped_tiling_never_passes_its_vmem_budget():
    """Over a grid of widths, row counts and item sizes: inside the budget,
    and no wider block of columns would have been."""
    for k in (128, 768, 2048, 2560, 7168, 18432):
        for n in (128, 768, 2048, 7168):
            for itemsize, out in ((2, 2), (2, 4), (4, 4), (1, 4)):
                for rows in (8, 48, 192, 4096):
                    got = grouped_tiling(rows, k, n, itemsize, out)
                    if got is None:
                        assert tile_vmem_bytes(
                            min(rows, 128), k, 128, itemsize,
                            out) > held_mod.TILE_VMEM_BUDGET, (rows, k, n)
                        continue
                    tm, tk, tn = got
                    assert tile_vmem_bytes(tm, tk, tn, itemsize, out) \
                        <= held_mod.TILE_VMEM_BUDGET, (got, k, n)
                    wider = [w for w in range(tn + 128, n + 1, 128)
                             if n % w == 0]
                    assert not wider or tile_vmem_bytes(
                        tm, tk, wider[0], itemsize,
                        out) > held_mod.TILE_VMEM_BUDGET
    # the result's item size defaults to the operands'
    assert grouped_tiling(1024, 7168, 2048, 2) == (128, 7168, 256)


def _held_case(dtype=jnp.float32, s=48, h=256, m=128, experts=16, held=4,
               k=4):
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(0, 1, (s, h)), dtype)
    idx = jnp.asarray(np.stack([rng.choice(experts, k, replace=False)
                                for _ in range(s)]), jnp.int32)
    w = jnp.asarray(rng.uniform(0.1, 1, (s, k)), jnp.float32)
    wg, wu = (jnp.asarray(rng.normal(0, 0.05, (held, h, m)), dtype)
              for _ in range(2))
    wd = jnp.asarray(rng.normal(0, 0.05, (held, m, h)), dtype)
    return x, idx, w, wg, wu, wd


def _no_tile(monkeypatch):
    monkeypatch.setattr(held_mod, "grouped_tiling", lambda *a, **kw: None)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_held_expert_sum_is_the_same_bits_with_and_without_the_hint(
        dtype, monkeypatch):
    """The tile is a hint to the TPU compiler and changes no operand, no
    accumulation and no output type: on the CPU, where it is ignored, the
    hinted and the unhinted sum are equal bit for bit, and the hint is
    there (the lowered text carries the rule's triple)."""
    args = _held_case(jnp.dtype(dtype))
    fn = jax.jit(lambda *a: held_expert_sum(*a, 4))
    tile = grouped_tiling(48 * 4, 256, 128, jnp.dtype(dtype).itemsize)
    assert tile == (64, 256, 128)
    assert 'ragged_dot_tiling = "64,256,128"' in fn.lower(*args).as_text()
    hinted = fn(*args)
    _no_tile(monkeypatch)
    plain = jax.jit(lambda *a: held_expert_sum(*a, 4))
    assert "ragged_dot_tiling" not in plain.lower(*args).as_text()
    for a, b in zip(hinted, plain(*args)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def _hinted_products(text):
    return [ln for ln in text.splitlines()
            if "dot_general" in ln and "ragged_dot_tiling" in ln]


@pytest.mark.parametrize("wrt", [(0, 3, 4, 5), (0,), (4,)])
def test_transposed_grouped_products_carry_no_hint(wrt, monkeypatch):
    """JAX keeps an operation's metadata for its transposes, and the
    forward's tile is wrong for them (another contraction, other columns):
    ``_grouped_product`` writes its derivative in products of their own, so
    the gradient's lowered text holds the triple on the three forward
    products and on nothing else, and the gradients are the bits JAX's own
    derivative of ``ragged_dot`` gives."""
    args = _held_case(jnp.bfloat16)

    def loss(*a):
        out, _, _ = held_expert_sum(*a, 4)
        return (out.astype(jnp.float32) ** 2).sum()

    grad = jax.jit(jax.grad(loss, argnums=wrt))
    assert len(_hinted_products(grad.lower(*args).as_text())) == 3
    ours = grad(*args)
    monkeypatch.setattr(
        held_mod, "_grouped_product",
        lambda rows, w, sizes, out_dtype: jax.lax.ragged_dot(
            rows, w, sizes, preferred_element_type=out_dtype))
    theirs = jax.jit(jax.grad(lambda *a: loss(*a), argnums=wrt))
    assert not _hinted_products(theirs.lower(*args).as_text())
    for a, b in zip(ours, theirs(*args)):
        assert a.dtype == b.dtype and float(jnp.abs(a).sum()) > 0
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    # forward mode too
    x, dx = args[0], jnp.ones_like(args[0])
    jax.jvp(lambda x: loss(x, *args[1:]), (x,), (dx,))


def test_gradient_through_held_experts_is_unchanged_by_the_hint(monkeypatch):
    """Through the layer and the tape: parameter and input gradients equal
    bit for bit with and without the forward's tile."""
    def grads():
        paddle.seed(11)
        layer = HeldExperts(64, 32, 16, 4, n_group=4, topk_group=2,
                            scale=2.5, held=(4, 8))
        x = paddle.to_tensor(np.random.default_rng(2).normal(
            0, 1, (24, 64)).astype("float32"), stop_gradient=False)
        out, _ = layer(x)
        (out * out).sum().backward()
        return [np.asarray(t.grad._data) for t in
                (x, layer.router, layer.w_gate, layer.w_up, layer.w_down)]

    hinted = grads()
    _no_tile(monkeypatch)
    for a, b in zip(hinted, grads()):
        np.testing.assert_array_equal(a, b)
    assert all(np.abs(g).sum() > 0 for g in hinted[2:])

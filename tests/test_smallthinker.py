"""SmallThinker (window and full attention layers, routed ReGLU experts) at a
tiny size on the CPU: hidden 64, 8 layers = two periods of (full without
rotary, 3 x window with it), a window of 8 tokens, pages of 4, 8 experts of
which 3 a token; seeded random weights. The oracle is the benchmark's plain
reference (``benchmark/reference/smallthinker.py``: float32, no cache, no
kernel, imports nothing of the program). The kernel runs in interpret mode."""
import importlib
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.incubate.distributed.models.moe.held import (  # noqa: E402
    HeldExperts, topk_softmax_route)
from paddle_tpu.inference import ContinuousServingEngine  # noqa: E402
from paddle_tpu.models.generation import SlotPagedKVCache  # noqa: E402
from paddle_tpu.models.smallthinker import (  # noqa: E402
    SmallThinkerForCausalLM, smallthinker_tiny)
from benchmark import weights_smallthinker as weights  # noqa: E402
from benchmark.reference import smallthinker as ref  # noqa: E402

WINDOW, PAGE = 8, 4
CFG = dict(vocab_size=128, hidden_size=64, num_hidden_layers=8,
           num_attention_heads=4, num_key_value_heads=2, head_dim=16,
           moe_ffn_hidden_size=32, moe_num_primary_experts=8,
           moe_num_active_primary_experts=3, sliding_window_size=WINDOW,
           rope_theta=10000.0, max_position_embeddings=128,
           rms_norm_eps=1e-6, initializer_range=0.02,
           rope_layout=[0, 1, 1, 1] * 2,
           sliding_window_layout=[0, 1, 1, 1] * 2)
SEED = 5


#: one period of layers: the tests that need the mechanism and not the
#: depth run half as long on it
CFG4 = dict(CFG, num_hidden_layers=4, rope_layout=[0, 1, 1, 1],
            sliding_window_layout=[0, 1, 1, 1])


def build(cfg=CFG):
    model = SmallThinkerForCausalLM(smallthinker_tiny(
        num_hidden_layers=cfg["num_hidden_layers"]))
    model.eval()
    named = [(n, p) for n, p in model.named_parameters()]
    table = weights.leaf_table(cfg)
    assert [(n, tuple(p.shape)) for n, p in named] == [
        (n, tuple(s)) for n, s, _ in table]
    for (_, p), a in zip(named, weights.make_weights(cfg, SEED, "float32")):
        p._data = a
    return model


@pytest.fixture(scope="module")
def model():
    return build()


@pytest.fixture(scope="module")
def model4():
    return build(CFG4)


def reference_logits(ids, cfg=CFG):
    return np.asarray(ref.logits(cfg, ref.seeded_group(cfg, SEED, "float32"),
                                 np.asarray(ids)))


def new_cache(window_pages=24, **kw):
    return SlotPagedKVCache(2, page_size=PAGE, max_len=64, num_pages=40,
                            window_groups={WINDOW: window_pages}, **kw)


def served_logits(model, cache, slot, ids, chunk, start=0):
    """What the engine's ticks do for one slot: ``ids[start:]`` in chunks
    of ``chunk`` tokens through the armed cache (the compiled layer
    programs around the kernel entry) -> logits [len(ids) - start, vocab]."""
    out = []
    for a in range(start, len(ids), chunk):
        piece = np.asarray(ids[a:a + chunk])
        cache.begin_ragged([(slot, 0, len(piece))])
        lg = model.forward(paddle.to_tensor(piece[None]), cache=cache,
                           position_ids=np.arange(a, a + len(piece),
                                                  dtype=np.int32))
        out.append(np.asarray(lg._data[0]))
    return np.concatenate(out)


def test_the_layers_are_what_the_layouts_say(model):
    assert model.kv_layer_windows == [None, 8, 8, 8] * 2
    assert [l.kind for l in model.model.layers] == \
        ["full", "window", "window", "window"] * 2
    assert [l.self_attn.use_rope for l in model.model.layers] == \
        [False, True, True, True] * 2
    assert not model.supports_cache


def test_cacheless_forward_against_the_reference(model):
    """(a) 40 tokens = 5 windows, the window masked in plain XLA."""
    ids = np.random.default_rng(0).integers(1, 128, 40)
    got = np.asarray(model(paddle.to_tensor(ids[None]))._data[0])
    assert np.abs(got - reference_logits(ids)).max() < 2e-5


def test_chunked_prefill_then_decode_against_the_references_full_forward(
        model):
    """(b) through the cache with two page groups: a 26-token prompt in
    chunks of 8, then 6 single-token steps, to 32 tokens = 4 windows;
    logits, not tokens. Blocks of the window group go back DURING the
    request, and the full group keeps every one."""
    ids = np.random.default_rng(1).integers(1, 128, 32)
    cache = new_cache()
    cache.assign(0, ids[:26])
    got = served_logits(model, cache, 0, ids[:26], 8)
    released_in_prefill = cache.window_blocks_released
    assert released_in_prefill > 0            # during the request
    got = np.concatenate([got, served_logits(model, cache, 0, ids, 1, 26)])
    assert np.abs(got - reference_logits(ids)).max() < 2e-5
    assert cache.compiled_layer_calls == 8 * (4 + 6)
    full, window = cache._groups
    assert int(full.n_blocks[0]) == 8 and int(full.first[0]) == 0
    # a query at 32 sees keys from 25 on: blocks 0..5 are gone
    assert int(window.first[0]) == (32 - WINDOW + 1) // PAGE == 6
    assert not window.tables[0, :6].any() and window.tables[0, 6:8].all()
    assert cache.window_blocks_released == 6 > released_in_prefill
    # they are the prompt's 6 full blocks: each entered the index as it was
    # released and stays cached (a block of answers would have gone to the
    # free list)
    assert len(window.index) == 6
    assert cache.group_usage() == [("full", 8, 39), ("window8", 8, 23)]


def test_the_engine_serves_it_on_the_normal_path(model4):
    model = model4
    """Greedy tokens of two requests through ``ContinuousServingEngine``
    (ragged scheduler, compiled layer programs, both page groups) equal the
    cache-less forward's own greedy continuation."""
    eng = ContinuousServingEngine(model, max_batch_size=2, page_size=PAGE,
                                  max_len=64, token_budget=8,
                                  prefill_chunk_tokens=8)
    assert eng.window_groups == {8: 13} and eng.kv_windows == [8]
    assert eng.declared_kernel_buckets(window=8)[8] == [1024]
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, 128, n) for n in (30, 11)]
    with eng:
        outs = [eng.generate(p[None], max_new_tokens=6).numpy()[0]
                for p in prompts]
        counters = eng.kv_counters()
    for p, out in zip(prompts, outs):
        seq = list(p)
        for _ in range(6):
            lg = model(paddle.to_tensor(np.asarray(seq)[None]))._data[0, -1]
            seq.append(int(jnp.argmax(lg)))
        assert out.tolist() == seq
    assert eng.compiled_layer_calls == 4 * eng.ragged_steps
    from paddle_tpu.profiler import metrics
    snap = metrics()
    assert {"full,used", "window8,capacity"} <= set(
        snap["paddle_kv_group_pages"]["series"])
    assert snap["paddle_kv_window_events_total"]["series"][
        "window_blocks_released"] > 0
    assert counters["window_blocks_released"] > 0
    assert set(counters["group_pages"]) == {"full", "window8"}
    assert eng.model_counters["moe_expert_tokens"].shape == (8,)


def test_a_second_ask_hits_the_prefix_before_and_after_the_tail_is_evicted(
        model4):
    """(c) the hit needs the whole chain in the full group and the
    window's tail in the window group; once eviction has taken the tail it
    is shortened (here to nothing) and never reads a freed page."""
    rng = np.random.default_rng(3)
    doc = rng.integers(1, 128, 32)
    asks = [np.concatenate([doc, rng.integers(1, 128, 5)]) for _ in range(3)]
    cache = new_cache()
    cache.assign(0, asks[0])
    first = served_logits(model4, cache, 0, asks[0], 8)
    assert np.abs(first - reference_logits(asks[0], CFG4)).max() < 2e-5
    cache.commit_prefix(0)
    cache.free(0)
    # the second ask: the document's 8 blocks, of which the window group
    # maps the tail that a query at 32 still sees (from key 25: block 6 on)
    cached, hits, _ = cache.assign(1, asks[1])
    assert (cached, hits) == (32, 8)
    window = cache._groups[1]
    assert int(window.first[1]) == 6 and not window.tables[1, :6].any()
    assert cache.prefix_hits_shortened_by_window == 0
    got = served_logits(model4, cache, 1, asks[1], 8, start=32)
    assert np.abs(got - reference_logits(asks[1], CFG4)[32:]).max() < 2e-5
    cache.commit_prefix(1)
    cache.free(1)
    # eviction takes the window group's cached blocks; the full group
    # still holds the chain
    while cache._evict_lru(window):
        pass
    assert cache.window_blocks_evicted > 0 and not window.index
    cached, hits, _ = cache.assign(0, asks[2])
    assert (cached, hits) == (0, 0)
    assert cache.prefix_hits_shortened_by_window == 1
    assert not cache._tables[0].any()      # the full group's refs undone
    got = served_logits(model4, cache, 0, asks[2], 8)
    assert np.abs(got - reference_logits(asks[2], CFG4)).max() < 2e-5


def test_a_hit_is_shortened_to_where_the_window_tail_is_whole(model4):
    """A window group that holds blocks 2..5 of a cached chain of 8 serves
    a hit of 6 blocks (its tail, blocks 4 and 5, is there), not of 8."""
    rng = np.random.default_rng(4)
    doc = rng.integers(1, 128, 32)
    cache = new_cache()
    cache.assign(0, np.concatenate([doc, [7]]))
    served_logits(model4, cache, 0, np.concatenate([doc, [7]]), 8)
    cache.commit_prefix(0)
    cache.free(0)
    window = cache._groups[1]
    chain = list(cache._index)             # the full group's 8 digests
    assert len(chain) == 8
    for d in chain[:2] + chain[6:]:
        page = window.index.pop(d)
        del window.page_digest[page]
        window.ref[page] = 0
        window.free.append(page)
    ask = np.concatenate([doc, [9, 9]])
    cached, hits, _ = cache.assign(1, ask)
    assert (cached, hits) == (24, 6)
    assert cache.prefix_hits_shortened_by_window == 1
    assert int(window.first[1]) == (24 - WINDOW + 1) // PAGE == 4
    got = served_logits(model4, cache, 1, ask, 8, start=24)
    assert np.abs(got - reference_logits(ask, CFG4)[24:]).max() < 2e-5


def test_four_shares_of_a_layers_experts_add_up_to_the_uncut_layer():
    """(d) ``held=(0, 2)`` .. ``(6, 2)``: each share's router ranks all 8
    experts and computes its 2; the four parts add up to the reference's
    uncut expert layer, routed from another input than the experts read."""
    w = weights.make_group(CFG, SEED, weights.layer_prefix(1), "float32")
    rng = np.random.default_rng(5)
    g = jnp.asarray(rng.standard_normal((24, 64)), jnp.float32)
    h = jnp.asarray(rng.standard_normal((24, 64)), jnp.float32)
    idx, wts, _ = ref.route(h, w["experts.router"], CFG)
    whole = np.asarray(ref.experts(g, idx, wts, w, CFG))
    total, tokens = 0.0, []
    for lo in (0, 2, 4, 6):
        share = HeldExperts(64, 32, 8, 3, held=(lo, 2), router="topk_softmax",
                            activation="relu")
        share.router._data = w["experts.router"]
        for name in ("w_gate", "w_up", "w_down"):
            getattr(share, name)._data = w["experts." + name][lo:lo + 2]
        out, counts = share(paddle.to_tensor(g),
                            router_input=paddle.to_tensor(h))
        total = total + np.asarray(out._data)
        tokens += np.asarray(counts["moe_expert_tokens"]).tolist()
    assert np.abs(total - whole).max() < 1e-5
    assert sum(tokens) == 24 * 3
    assert tokens == [int((np.asarray(idx) == e).sum()) for e in range(8)]


def test_the_softmax_router_weighs_the_kept_logits_alone():
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((5, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    idx, p = topk_softmax_route(x, w, top_k=3)
    z = np.asarray(x) @ np.asarray(w)
    for t in range(5):
        best = np.argsort(-z[t])[:3]
        assert sorted(np.asarray(idx[t]).tolist()) == sorted(best.tolist())
        kept = z[t][np.asarray(idx[t])]
        assert np.allclose(np.asarray(p[t]),
                           np.exp(kept) / np.exp(kept).sum(), atol=1e-6)


@pytest.mark.parametrize("fault", ["window_ignored", "released_early"])
def test_the_two_window_faults_fail_the_comparison(model4, monkeypatch,
                                                   fault):
    """(e) the same chunked prefill and decode with the mechanism broken:
    window layers that attend the whole context, and a window block given
    back one block early; both come out far from the reference."""
    if fault == "window_ignored":
        rpa = importlib.import_module(
            "paddle_tpu.ops.pallas.ragged_paged_attention")
        entry = rpa.ragged_paged_attention
        monkeypatch.setattr(
            rpa, "ragged_paged_attention",
            lambda *a, window=None, **kw: entry(*a, **kw))
        monkeypatch.setattr(SlotPagedKVCache, "_release_windows",
                            lambda self, slots: 0)
    else:
        first_live = SlotPagedKVCache._window_first_live
        monkeypatch.setattr(
            SlotPagedKVCache, "_window_first_live",
            lambda self, g, filled: first_live(self, g, filled) + 1)
    ids = np.random.default_rng(1).integers(1, 128, 29)
    cache = new_cache()
    cache.assign(0, ids[:26])
    got = np.concatenate([served_logits(model4, cache, 0, ids[:26], 8),
                          served_logits(model4, cache, 0, ids, 1, 26)])
    assert np.abs(got - reference_logits(ids, CFG4)).max() > 1e-3


def test_what_cannot_serve_a_windowed_model_refuses_it(model):
    for kw in (dict(spec_decode=True), dict(sep_prefill=True),
               dict(host_pool_mb=8), dict(kv_dtype="int8")):
        with pytest.raises(NotImplementedError, match="window layers"):
            ContinuousServingEngine(model, max_batch_size=2, page_size=PAGE,
                                    max_len=64, **kw)
    cache = new_cache()
    for call in (lambda: cache.export_pages([b"x"]),
                 lambda: cache.import_pages({"page_size": PAGE}),
                 lambda: cache.assign_sep(0, 32, 8),
                 lambda: cache.rollback(0, 1)):
        with pytest.raises(NotImplementedError, match="window group"):
            call()
    with pytest.raises(NotImplementedError):
        new_cache(kv_dtype="int8")
    with pytest.raises(ValueError, match="do not cover"):
        new_cache(window_pages=4)
    with pytest.raises(ValueError, match="declares no window"):
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
        ContinuousServingEngine(LlamaForCausalLM(llama_tiny()),
                                window_num_pages=9)

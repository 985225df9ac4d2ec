"""Serving fast path (ISSUE 4): prefix-cache KV reuse over the shared
refcounted page pool, chunked decode-interleaved prefill, and the
non-blocking admission scheduler — greedy-oracle parity, page
refcount/copy-on-write lifecycle, decode liveness, and timeout
cancellation."""
import threading
import time

import numpy as np
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import ContinuousServingEngine
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.models.generation import SlotPagedKVCache, block_hash_chain
from kv_fill import write_rows


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    # rope table large enough for the 128-token shared-system-prompt runs
    return LlamaForCausalLM(llama_tiny(num_hidden_layers=2,
                                       max_position_embeddings=256))


def _oracle(model, p, n):
    return np.asarray(model.generate(paddle.to_tensor(p),
                                     max_new_tokens=n)._data)


# ---------------------------------------------------------------------------
# acceptance: shared system prompt -> prefix reuse, bit-identical outputs
# ---------------------------------------------------------------------------

def test_shared_system_prompt_reuse_and_parity(model):
    """8 requests sharing a 128-token system prompt: after the first
    prefills and registers the shared blocks, the other 7 prefill only
    their unique 8-token tails — telemetry shows hits and >= 7 x (shared
    blocks x page_size) cached tokens, while greedy outputs stay
    bit-identical to the prefix-cache-off path and the dense oracle."""
    rng = np.random.RandomState(0)
    sys_prompt = rng.randint(0, 128, 128)
    prompts = [np.concatenate([sys_prompt, rng.randint(0, 128, 8)])
               .astype(np.int64)[None] for _ in range(8)]

    def run(prefix_cache):
        eng = ContinuousServingEngine(
            model, max_batch_size=4, max_len=160, page_size=16,
            enable_prefix_cache=prefix_cache, prefill_chunk_tokens=32)
        results = [None] * 8
        with eng:
            # request 0 fills (and, when enabled, registers) the prefix
            results[0] = np.asarray(eng.generate(
                prompts[0], max_new_tokens=4, timeout=300).numpy())

            def call(i):
                results[i] = np.asarray(eng.generate(
                    prompts[i], max_new_tokens=4, timeout=300).numpy())

            threads = [threading.Thread(target=call, args=(i,))
                       for i in range(1, 8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        return results, eng

    got_on, eng_on = run(True)
    got_off, eng_off = run(False)
    for a, b in zip(got_on, got_off):
        np.testing.assert_array_equal(a, b)
    # spot-check against the dense concat-cache oracle too
    for i in (0, 3):
        np.testing.assert_array_equal(got_on[i],
                                      _oracle(model, prompts[i], 4))
    cache = eng_on._cache
    assert cache.prefix_hits > 0
    # 7 followers x 8 shared full blocks x 16 tokens/page
    assert cache.cached_tokens_total >= 7 * 8 * 16
    assert eng_off._cache.prefix_hits == 0
    assert eng_off._cache.cached_tokens_total == 0


def test_chunked_prefill_matches_dense_oracle(model):
    """A prompt much longer than the chunk budget prefills in several
    fixed-bucket chunks yet decodes bit-identically to the dense path."""
    rng = np.random.RandomState(1)
    p = rng.randint(0, 128, (1, 50)).astype(np.int64)
    want = _oracle(model, p, 5)
    eng = ContinuousServingEngine(model, max_batch_size=2, max_len=64,
                                  prefill_chunk_tokens=16)
    with eng:
        got = np.asarray(eng.generate(p, max_new_tokens=5,
                                      timeout=300).numpy())
    np.testing.assert_array_equal(got, want)
    assert eng.prefill_chunks >= 4          # ceil(50/16) chunks
    assert eng.prefills == 1                # still one admission


def test_env_flag_disables_prefix_cache(model, monkeypatch):
    monkeypatch.setenv("PADDLE_SERVING_PREFIX_CACHE", "0")
    eng = ContinuousServingEngine(model)
    assert eng.enable_prefix_cache is False
    monkeypatch.setenv("PADDLE_SERVING_PREFIX_CACHE", "1")
    assert ContinuousServingEngine(model).enable_prefix_cache is True


# ---------------------------------------------------------------------------
# cache-level lifecycle: refcounts, copy-on-write, eviction
# ---------------------------------------------------------------------------

def _write_tokens(cache, slot, layer, tokens):
    """Push synthetic K/V for ``tokens`` through a ragged span (the
    content is the token value broadcast, so page content is checkable)."""
    s = len(tokens)
    t = np.asarray(tokens, np.float32)
    k = np.broadcast_to(t[None, :, None, None], (1, s, 1, 4)).copy()
    write_rows(cache, slot, layer, np.zeros((1, s, 1, 4), np.float32), k, k)


def test_refcount_and_cow_lifecycle():
    layer = object()
    cache = SlotPagedKVCache(2, page_size=4, max_len=32,
                             enable_prefix_cache=True)
    prompt = np.arange(12)
    chain = block_hash_chain(prompt, 4)

    cached, hits, misses = cache.assign(0, prompt)
    assert (cached, hits, misses) == (0, 0, 3)
    _write_tokens(cache, 0, layer, prompt)
    assert cache.commit_prefix(0) == 3
    pages0 = cache._tables[0, :3].copy()
    assert (cache._ref[pages0] == 2).all()          # slot 0 + index

    # identical prompt on slot 1: full-block reuse capped so >= 1 token
    # still prefills (the model must emit last-token logits)
    cached, hits, misses = cache.assign(1, prompt)
    assert (cached, hits) == (8, 2)
    assert (cache._tables[1, :2] == pages0[:2]).all()
    assert (cache._ref[pages0[:2]] == 3).all()

    cache.free(0)
    assert (cache._ref >= 0).all()
    assert (cache._ref[pages0[:2]] == 2).all()      # index + slot 1
    cache.free(0)                                   # double free: no-op
    assert (cache._ref >= 0).all()

    # copy-on-write: force a mid-block write into slot 1's SHARED block 1
    cache.lens[1] = 6
    _write_tokens(cache, 1, layer, np.arange(100, 102))
    assert cache.cow_copies == 1
    assert cache._tables[1, 1] != pages0[1]
    assert cache._index[chain[1]] == pages0[1]      # index entry intact
    # the index's copy kept its original content, the COW page diverged
    kp, _ = cache._pools[id(layer)]
    assert float(kp[0, pages0[1], 2, 0]) == 6.0     # original token value
    assert float(kp[0, cache._tables[1, 1], 2, 0]) == 100.0

    cache.free(1)
    assert (cache._ref >= 0).all()
    # only the 3 registered pages remain charged to the pool
    assert cache.used_page_count == 3
    assert (cache._ref[pages0] == 1).all()


def test_pool_eviction_reclaims_index_pages():
    """When the free list empties, LRU prefix-index entries with no live
    users are evicted instead of failing allocation."""
    layer = object()
    # 1 slot x 4 pages/seq + scratch = 4 allocatable pages
    cache = SlotPagedKVCache(1, page_size=4, max_len=16,
                             enable_prefix_cache=True)
    for i in range(4):
        prompt = np.arange(8) + 1000 * i            # 2 full blocks each
        cache.assign(0, prompt)
        _write_tokens(cache, 0, layer, prompt)
        cache.commit_prefix(0)
        cache.free(0)
        assert (cache._ref >= 0).all()
    # 4 rounds x 2 registered blocks through a 4-page pool forced
    # evictions; the pool never overflowed and stays fully utilized
    assert cache.used_page_count <= 4
    assert len(cache._index) <= 4
    # a fresh identical prompt still round-trips
    cached, hits, _ = cache.assign(0, np.arange(8) + 3000)
    assert cached == hits * 4


def test_eviction_frees_the_oldest_entry_no_slot_maps_and_no_other():
    """``_evict_lru`` walks the index from its old end, in place: a page a
    live slot still maps is passed over (and keeps its entry), a prefix hit
    makes its entries the newest, and one call frees exactly one page."""
    layer = object()
    cache = SlotPagedKVCache(2, page_size=4, max_len=16, num_pages=12,
                             enable_prefix_cache=True)
    chains = []
    for i in range(3):                               # a, b, c: 2 blocks each
        prompt = np.arange(8) + 1000 * i
        chains.append(block_hash_chain(prompt, 4))
        cache.assign(0, prompt)
        _write_tokens(cache, 0, layer, prompt)
        cache.commit_prefix(0)
        cache.free(0)
    a, b, c = chains
    assert list(cache._index) == a + b + c           # oldest first
    # a again, and live: its first block is a hit (the last token of a
    # prompt is always computed, so its second block is not)
    cached, hits, _ = cache.assign(1, np.arange(8))
    assert (cached, hits) == (4, 1)
    held = [d for d in a if cache._ref[cache._index[d]] > 1]
    assert held == a[:1]                             # slot 1 maps it
    # the hit made that entry the newest
    assert list(cache._index) == a[1:] + b + c + a[:1]
    before = dict(cache._index)
    order = []
    while cache._evict_lru():
        gone = [d for d in before if d not in cache._index]
        order += [d for d in gone if d not in order]
        assert len(order) == cache.prefix_evictions_device   # one a call
    # from the old end, the mapped entry passed over and kept
    assert order == a[1:] + b + c
    assert list(cache._index) == held
    assert all(cache._ref[cache._index[d]] == 2 for d in held)
    cache.free(1)
    assert (cache._ref >= 0).all()


def test_refcount_underflow_raises():
    cache = SlotPagedKVCache(1, page_size=4, max_len=16)
    page = cache._alloc_page()
    cache._decref(page)
    with pytest.raises(RuntimeError, match="underflow"):
        cache._decref(page)


# ---------------------------------------------------------------------------
# scheduler: decode liveness between chunks, timeout cancellation
# ---------------------------------------------------------------------------

def test_decode_liveness_between_prefill_chunks(model):
    """Chunked prefill must not head-of-line-block decoding: while a long
    prompt prefills chunk by chunk, the already-admitted request keeps
    earning decode steps between consecutive chunks."""
    rng = np.random.RandomState(2)
    short = rng.randint(0, 128, (1, 4)).astype(np.int64)
    long_p = rng.randint(0, 128, (1, 40)).astype(np.int64)
    eng = ContinuousServingEngine(model, max_batch_size=2, max_len=80,
                                  prefill_chunk_tokens=8,
                                  enable_prefix_cache=False)
    with eng:
        t = threading.Thread(target=lambda: eng.generate(
            short, max_new_tokens=40, timeout=300))
        t.start()
        deadline = time.time() + 60
        while eng.decode_steps < 1 and time.time() < deadline:
            time.sleep(0.005)
        assert eng.decode_steps >= 1, "short request never started decoding"
        eng.generate(long_p, max_new_tokens=2, timeout=300)
        t.join()
    events = list(eng.events)
    # the long prompt ran on the second slot in >= 5 chunks (40/8)
    chunk_slots = {e[1] for e in events if e[0] == "chunk"}
    assert len(chunk_slots) == 2
    long_slot = max(chunk_slots)        # short admitted first -> slot 0
    idx = [i for i, e in enumerate(events)
           if e[0] == "chunk" and e[1] == long_slot]
    assert len(idx) >= 5
    for a, b in zip(idx, idx[1:]):
        between = [e for e in events[a + 1:b]
                   if e[0] == "decode" and e[1] >= 1]
        assert between, f"no decode step between chunks {a} and {b}"


def test_timeout_cancellation_frees_slot_and_stops_decoding(model):
    """A timed-out request must not keep burning decode steps to
    max_new_tokens: the scheduler frees its slot/pages at the next step
    boundary and the engine keeps serving."""
    rng = np.random.RandomState(3)
    p = rng.randint(0, 128, (1, 4)).astype(np.int64)
    eng = ContinuousServingEngine(model, max_batch_size=2, max_len=128)
    with eng:
        with pytest.raises(TimeoutError):
            eng.generate(p, max_new_tokens=120, timeout=0.05)
        deadline = time.time() + 60
        while eng.cancelled_rows < 1 and time.time() < deadline:
            time.sleep(0.01)
        assert eng.cancelled_rows >= 1
        # slot and pages were released, nowhere near the 120-token budget
        deadline = time.time() + 60
        while eng._cache.used_page_count > 0 and time.time() < deadline:
            time.sleep(0.01)
        assert eng._cache.used_page_count == 0
        assert eng.decode_steps < 120
        # engine still serves afterwards
        out = eng.generate(p, max_new_tokens=2, timeout=120)
        assert np.asarray(out.numpy()).shape == (1, 6)


def test_cancelled_pending_rows_skipped_at_admission(model):
    """A request that times out while still queued never occupies a slot."""
    rng = np.random.RandomState(4)
    p = rng.randint(0, 128, (1, 4)).astype(np.int64)
    eng = ContinuousServingEngine(model, max_batch_size=1, max_len=128)
    with eng:
        blocker = threading.Thread(target=lambda: eng.generate(
            p, max_new_tokens=60, timeout=300))
        blocker.start()
        deadline = time.time() + 60
        while eng.prefills < 1 and time.time() < deadline:
            time.sleep(0.005)
        prefills_before = eng.prefills
        with pytest.raises(TimeoutError):
            # the single slot is busy for many steps; this one queues and
            # times out before admission
            eng.generate(p, max_new_tokens=2, timeout=0.05)
        blocker.join()
        # give the scheduler a beat to sweep the cancelled pending row
        deadline = time.time() + 60
        while eng.cancelled_rows < 1 and time.time() < deadline:
            time.sleep(0.01)
    assert eng.cancelled_rows >= 1
    assert eng.prefills == prefills_before   # never admitted


# ---------------------------------------------------------------------------
# telemetry wiring
# ---------------------------------------------------------------------------

def test_prefix_and_chunk_telemetry(model):
    from paddle_tpu.profiler import metrics
    rng = np.random.RandomState(5)
    shared = rng.randint(0, 128, 32)
    p1 = np.concatenate([shared, rng.randint(0, 128, 4)]).astype(
        np.int64)[None]
    p2 = np.concatenate([shared, rng.randint(0, 128, 6)]).astype(
        np.int64)[None]
    eng = ContinuousServingEngine(model, max_batch_size=2, max_len=64,
                                  page_size=16, prefill_chunk_tokens=16,
                                  enable_prefix_cache=True)
    with eng:
        eng.generate(p1, max_new_tokens=2, timeout=300)
        eng.generate(p2, max_new_tokens=2, timeout=300)
    assert eng._cache.prefix_hits >= 2      # 32-token shared = 2 blocks
    snap = metrics()
    assert snap["paddle_serving_prefix_hits"]["series"][""] >= 2
    assert snap["paddle_serving_prefix_cached_tokens"]["series"][""] >= 32
    # the scheduler observes batch-level budget utilization
    util = snap["paddle_serving_token_budget_utilization"]["series"][""]
    assert util["count"] >= eng.ragged_steps > 0
    assert "paddle_serving_page_pool_occupancy" in snap
    assert "paddle_serving_prefix_misses" in snap

"""Tiered KV cache (ISSUE 19): host-RAM prefix spill under the
prefix-index LRU — demote-on-evict through the ``export_pages`` codec,
promote-on-admission back to device pages, second-level LRU bound, COW
interplay, and exact legacy behavior with the tier off."""
import numpy as np
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import ContinuousServingEngine
from paddle_tpu.inference.serving import _engine_state
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.models.generation import (HostKVPool, SlotPagedKVCache,
                                          block_hash_chain)
from kv_fill import write_rows
from paddle_tpu.profiler.telemetry import metrics


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    return LlamaForCausalLM(llama_tiny(num_hidden_layers=2))


def _oracle(model, p, n):
    return np.asarray(model.generate(paddle.to_tensor(p),
                                     max_new_tokens=n)._data)


def _mk_cache(pool_mb, **kw):
    kw.setdefault("page_size", 4)
    kw.setdefault("max_len", 32)
    kw.setdefault("num_pages", 9)
    return SlotPagedKVCache(1, host_pool=HostKVPool(pool_mb), **kw)


def _prefill(cache, slot, toks, kv, rng, layer=None):
    """Admit + prefill the uncached suffix with caller-supplied K/V
    content; returns the cached (reused) token count. The layer object
    keys the cache's per-layer pool, so callers reuse one per cache
    (``cache._test_layer`` by default)."""
    if layer is None:
        layer = cache.__dict__.setdefault("_test_layer", object())
    h, d = 4, 8
    cache.assign(slot, toks)
    start = int(cache.lens[slot])
    n = len(toks) - start
    q = rng.standard_normal((1, n, h, d)).astype(np.float32)
    write_rows(cache, slot, layer, q, kv[0][:, start:start + n],
               kv[1][:, start:start + n])
    cache.commit_prefix(slot)
    return start


def _page_kv(n, rng):
    return (rng.standard_normal((1, n, 2, 8)).astype(np.float32),
            rng.standard_normal((1, n, 2, 8)).astype(np.float32))


# ---------------------------------------------------------------------------
# demote -> promote roundtrip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_dtype", ["native", "int8"])
def test_demote_promote_roundtrip_bit_exact(kv_dtype):
    """Evicting every ref==1 index page spills it to the host pool; a
    later admission promotes the pages back bit-exactly (int8 pools
    roundtrip their quantized codes AND scales untouched)."""
    rng = np.random.default_rng(1)
    kw = {} if kv_dtype == "native" else {"kv_dtype": "int8"}
    cache = _mk_cache(64, **kw)
    toks = np.arange(16)
    kv = _page_kv(16, rng)
    _prefill(cache, 0, toks, kv, rng)
    snap = {dg: cache._page_entry(p) for dg, p in cache._index.items()}
    cache.free(0)
    while cache._evict_lru():
        pass
    assert len(cache._index) == 0
    assert cache.host_demotions == len(snap)
    assert cache.prefix_evictions_device == len(snap)
    assert cache.host_pool.used_bytes > 0

    cached = _prefill(cache, 0, toks, kv, rng)
    assert cached == 12                     # (16-1)//4 matchable blocks
    assert cache.host_promotions == 3
    for dg, entry_old in snap.items():
        if dg not in cache._index:          # unmatchable 4th block
            continue
        entry_new = cache._page_entry(int(cache._index[dg]))
        for (ko, vo), (kn, vn) in zip(entry_old["layers"],
                                      entry_new["layers"]):
            assert np.array_equal(ko, kn) and np.array_equal(vo, vn)
        if kv_dtype == "int8":
            assert entry_old["kv_dtype"] == "int8"
            for so, sn in zip(entry_old["scales"], entry_new["scales"]):
                assert np.array_equal(so[0], sn[0])
                assert np.array_equal(so[1], sn[1])


def test_promotion_removes_host_copy():
    """Promotion is a move, not a copy: the device index becomes the
    authoritative home again and the host entry is gone."""
    rng = np.random.default_rng(2)
    cache = _mk_cache(64)
    toks = np.arange(16)
    kv = _page_kv(16, rng)
    _prefill(cache, 0, toks, kv, rng)
    cache.free(0)
    while cache._evict_lru():
        pass
    n_host = len(cache.host_pool)
    assert n_host == 4
    _prefill(cache, 0, toks, kv, rng)
    assert len(cache.host_pool) == n_host - cache.host_promotions


# ---------------------------------------------------------------------------
# second-level LRU bound
# ---------------------------------------------------------------------------

def test_host_pool_lru_bound_enforced():
    entry = {"page_size": 4, "kv_dtype": "native",
             "native_dtype": "float32",
             "layers": [(np.zeros((2, 4, 64), np.float32),
                         np.zeros((2, 4, 64), np.float32))],
             "scales": None}
    per = HostKVPool.entry_nbytes(entry)
    pool = HostKVPool(per * 3 / (1024 * 1024))   # room for exactly 3
    for i in range(8):
        assert pool.put(bytes([i]), dict(entry))
    assert len(pool) == 3
    assert pool.evictions == 5
    assert pool.used_bytes <= pool.max_bytes
    # LRU order: oldest survivors are 5, 6, 7; get() refreshes recency
    assert bytes([4]) not in pool and bytes([5]) in pool
    assert pool.get(bytes([5])) is not None
    pool.put(bytes([8]), dict(entry))
    assert bytes([5]) in pool and bytes([6]) not in pool


def test_oversized_entry_rejected():
    entry = {"page_size": 4, "kv_dtype": "native",
             "native_dtype": "float32",
             "layers": [(np.zeros((2, 4, 4096), np.float32),
                         np.zeros((2, 4, 4096), np.float32))],
             "scales": None}
    pool = HostKVPool(0.01)                  # smaller than one entry
    assert not pool.put(b"x", entry)
    assert len(pool) == 0 and pool.used_bytes == 0


# ---------------------------------------------------------------------------
# COW / refcount interplay with promoted pages
# ---------------------------------------------------------------------------

def test_promoted_page_shared_then_written_cow():
    """A promoted page re-registered under the index behaves exactly
    like a first-class prefix page: shared by two slots, a mid-block
    write triggers copy-on-write and the index copy keeps its bytes."""
    rng = np.random.default_rng(3)
    layer = object()
    cache = SlotPagedKVCache(2, page_size=4, max_len=32, num_pages=9,
                             host_pool=HostKVPool(64))
    toks = np.arange(12)
    chain = block_hash_chain(toks, 4)
    kv = _page_kv(12, rng)

    def fill(slot):
        cache.assign(slot, toks)
        start = int(cache.lens[slot])
        n = 12 - start
        t = np.asarray(toks[start:], np.float32)
        k = np.broadcast_to(t[None, :, None, None], (1, n, 1, 4)).copy()
        write_rows(cache, slot, layer, np.zeros((1, n, 1, 4), np.float32),
                   k, k)
        cache.commit_prefix(slot)

    fill(0)
    cache.free(0)
    while cache._evict_lru():
        pass
    assert cache.host_demotions == 3
    fill(0)                                  # promotes 2 matchable blocks
    assert cache.host_promotions == 2
    fill(1)                                  # shares the promoted pages
    shared = int(cache._tables[1, 1])
    assert shared == int(cache._tables[0, 1])
    assert cache._ref[shared] == 3           # index + slot 0 + slot 1

    # mid-block write into slot 1's shared (promoted) block 1
    cache.lens[1] = 6
    t = np.asarray([100.0, 101.0], np.float32)
    k = np.broadcast_to(t[None, :, None, None], (1, 2, 1, 4)).copy()
    write_rows(cache, 1, layer, np.zeros((1, 2, 1, 4), np.float32), k, k)
    assert cache.cow_copies == 1
    assert int(cache._tables[1, 1]) != shared
    assert int(cache._index[chain[1]]) == shared
    kp, _ = cache._pools[id(layer)]
    assert float(kp[0, shared, 2, 0]) == 6.0            # index copy intact
    assert float(kp[0, int(cache._tables[1, 1]), 2, 0]) == 100.0


# ---------------------------------------------------------------------------
# mismatch rejection
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("corrupt", ["page_size", "kv_dtype"])
def test_geometry_mismatch_rejected(corrupt):
    """A host entry whose page geometry or dtype no longer matches the
    pool is dropped on promotion (never written into device pages), and
    the chain walk stops at the bad block."""
    rng = np.random.default_rng(4)
    pool = HostKVPool(64)
    cache = SlotPagedKVCache(1, page_size=4, max_len=32, num_pages=9,
                             host_pool=pool)
    toks = np.arange(16)
    _prefill(cache, 0, toks, _page_kv(16, rng), rng)
    chain = block_hash_chain(toks, 4)
    cache.free(0)
    while cache._evict_lru():
        pass
    dg = bytes(chain[0])
    pool._entries[dg][corrupt] = \
        8 if corrupt == "page_size" else "int8"
    cached = _prefill(cache, 0, toks, _page_kv(16, rng), rng)
    assert cache.host_promote_rejects == 1
    assert dg not in pool                    # dropped, not retried
    assert cached == 0                       # walk stopped at block 0
    assert cache.host_promotions == 0


# ---------------------------------------------------------------------------
# PADDLE_KV_HOST_POOL_MB=0: exact legacy eviction
# ---------------------------------------------------------------------------

def test_pool_mb_zero_restores_legacy(monkeypatch):
    monkeypatch.setenv("PADDLE_KV_HOST_POOL_MB", "0")
    rng = np.random.default_rng(5)
    cache = SlotPagedKVCache(1, page_size=4, max_len=32, num_pages=9)
    assert not cache.host_pool.enabled
    toks = np.arange(16)
    kv = _page_kv(16, rng)
    _prefill(cache, 0, toks, kv, rng)
    cache.free(0)
    while cache._evict_lru():
        pass
    assert cache.host_demotions == 0
    assert len(cache.host_pool) == 0
    assert cache.prefix_evictions_device == 4
    cached = _prefill(cache, 0, toks, kv, rng)
    assert cached == 0                       # evicted prefix is just gone
    assert cache.host_promotions == 0


def test_env_pool_mb_enables_engine_tier(model, monkeypatch):
    monkeypatch.setenv("PADDLE_KV_HOST_POOL_MB", "8")
    eng = ContinuousServingEngine(model)
    assert eng.host_pool_mb == 8.0
    assert eng._host_pool.enabled
    assert eng._host_pool.max_bytes == 8 * 1024 * 1024
    monkeypatch.setenv("PADDLE_KV_HOST_POOL_MB", "-1")
    with pytest.raises(ValueError):
        ContinuousServingEngine(model)


# ---------------------------------------------------------------------------
# engine-level: eviction churn with the tier on, bit-identical outputs
# ---------------------------------------------------------------------------

def test_engine_host_tier_parity_and_telemetry(model):
    """Three requests through a pool too small to keep both prefixes
    resident: with the host tier on, the third request's prefix promotes
    from host RAM (promotions > 0) and every output matches both the
    tier-off engine and the dense oracle; the kv-tier metric families
    are populated."""
    rng = np.random.RandomState(7)
    pA = rng.randint(0, 128, (1, 24)).astype(np.int64)
    pB = rng.randint(0, 128, (1, 24)).astype(np.int64)
    wants = [_oracle(model, p, 4) for p in (pA, pB, pA)]
    outs = {}
    for mb in (0, 64):
        eng = ContinuousServingEngine(model, max_batch_size=1,
                                      page_size=4, max_len=32,
                                      num_pages=10, host_pool_mb=mb)
        with eng:
            outs[mb] = [np.asarray(eng.generate(
                p, max_new_tokens=4, timeout=300).numpy())
                for p in (pA, pB, pA)]
            promos = eng._cache.host_promotions
            state = _engine_state(eng)
        if mb:
            assert promos > 0
            assert eng._host_pool.demotions > 0
            assert state["kv_host_tier"]["enabled"]
            assert state["kv_host_tier"]["promotions"] == \
                eng._host_pool.promotions
        else:
            assert promos == 0 and len(eng._host_pool) == 0
    for got, want in zip(outs[0], wants):
        np.testing.assert_array_equal(got, want)
    for got, want in zip(outs[64], wants):
        np.testing.assert_array_equal(got, want)
    snap = metrics()
    assert snap["paddle_kv_host_pool_bytes"]["series"]["capacity"] >= 0
    assert "used" in snap["paddle_kv_host_pool_bytes"]["series"]
    assert snap["paddle_kv_host_demotions_total"]["series"][""] > 0
    assert snap["paddle_kv_host_promotions_total"]["series"][""] > 0
    ev = snap["paddle_serving_prefix_evictions_total"]["series"]
    assert ev.get("device", 0) > 0


def test_export_pages_reads_through_host_tier():
    """Disagg handoff: a chain whose pages were demoted still exports —
    the blob reads through the host tier and reports how many pages it
    served from there (the router's handoff_host_pages accounting)."""
    rng = np.random.default_rng(8)
    cache = _mk_cache(64)
    toks = np.arange(16)
    kv = _page_kv(16, rng)
    _prefill(cache, 0, toks, kv, rng)
    chain = list(cache._index)
    cache.free(0)
    while cache._evict_lru():
        pass
    blob = cache.export_pages(chain)
    assert blob is not None and blob["host_pages"] == 4
    dst = _mk_cache(64)
    _prefill(dst, 0, np.arange(100, 104), _page_kv(4, rng), rng)
    dst.free(0)
    assert dst.import_pages(blob) == 4
    assert _prefill(dst, 0, toks, kv, rng) == 12

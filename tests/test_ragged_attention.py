"""Ragged paged attention (ISSUE 7): one kernel + token-budget scheduler
for true continuous batching — kernel parity vs the dense reference
across ragged descriptor layouts, and engine acceptance that greedy
outputs under the ragged scheduler stay bit-identical to the dense
oracle (incl. prefix-cache hits and cancellation)."""
import threading
import time

import numpy as np
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import ContinuousServingEngine
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.ops.pallas.ragged_paged_attention import (
    ragged_paged_attention, ragged_paged_attention_reference,
    _ragged_paged_attention_xla, _token_descriptors)


# ---------------------------------------------------------------------------
# kernel parity vs the dense reference, across descriptor layouts
# ---------------------------------------------------------------------------

def _pool(nslots=4, pages_per_seq=4, page=8, kv_heads=2, d=32, seed=0):
    rng = np.random.RandomState(seed)
    npages = nslots * pages_per_seq + 1          # page 0 = scratch
    kp = jnp.asarray(rng.randn(kv_heads, npages, page, d), jnp.float32)
    vp = jnp.asarray(rng.randn(kv_heads, npages, page, d), jnp.float32)
    tbl = np.zeros((nslots, pages_per_seq), np.int32)
    for s in range(nslots):
        tbl[s] = np.arange(1 + s * pages_per_seq,
                           1 + (s + 1) * pages_per_seq)
    return kp, vp, tbl


def _check(layout, nslots=4, heads=4, d=32, seed=0, tokens=None):
    """layout: list of (slot, q_start, q_len, context_len)."""
    kp, vp, tbl = _pool(nslots=nslots, d=d, seed=seed)
    seq_slots = np.asarray([x[0] for x in layout], np.int32)
    q_starts = np.asarray([x[1] for x in layout], np.int32)
    q_lens = np.asarray([x[2] for x in layout], np.int32)
    ctx = np.asarray([x[3] for x in layout], np.int32)
    T = tokens or int((q_starts + q_lens).max())
    rng = np.random.RandomState(seed + 1)
    q = jnp.asarray(rng.randn(T, heads, d), jnp.float32)
    ref = np.asarray(ragged_paged_attention_reference(
        q, kp, vp, tbl, seq_slots, q_starts, q_lens, ctx))
    out = np.asarray(ragged_paged_attention(
        q, kp, vp, jnp.asarray(tbl), seq_slots, q_starts, q_lens, ctx,
        interpret=True))
    ts, tc = _token_descriptors(T, seq_slots, q_starts, q_lens, ctx)
    xla = np.asarray(_ragged_paged_attention_xla(
        q, kp, vp, jnp.asarray(tbl), ts, tc, sm_scale=d ** -0.5))
    for slot, qs, ql, _ in layout:               # pad rows are garbage
        np.testing.assert_allclose(out[qs:qs + ql], ref[qs:qs + ql],
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(xla[qs:qs + ql], ref[qs:qs + ql],
                                   rtol=2e-5, atol=2e-5)
    return out, ref


def test_kernel_pure_decode():
    # every span is one token — the continuous-batching steady state
    _check([(0, 0, 1, 7), (1, 1, 1, 19), (2, 2, 1, 32), (3, 3, 1, 1)])


def test_kernel_pure_prefill():
    _check([(0, 0, 9, 9), (1, 9, 14, 14), (2, 23, 5, 5)])


def test_kernel_mixed_prefill_decode_with_padding():
    # decode tokens + chunked-prefill continuation (context > q_len) +
    # bucket padding at the tail (tokens=32 > last span end)
    _check([(0, 0, 1, 12), (1, 1, 1, 25), (2, 2, 11, 18), (3, 13, 6, 6)],
           tokens=32)


def test_kernel_single_token_tail():
    # a prefill span of exactly 1 token (prompt tail after a prefix-cache
    # hit) must behave like decode with its own context
    _check([(0, 0, 1, 17), (1, 1, 1, 8)])


def test_kernel_shared_prefix_pages():
    """Two slots whose block tables alias the same leading pages (a
    prefix-cache hit): outputs must match a reference reading through
    the same aliased tables."""
    kp, vp, tbl = _pool(nslots=2, pages_per_seq=4)
    tbl[1, :2] = tbl[0, :2]                      # shared 16-token prefix
    layout = [(0, 0, 1, 20), (1, 1, 3, 19)]
    seq_slots = np.asarray([x[0] for x in layout], np.int32)
    q_starts = np.asarray([x[1] for x in layout], np.int32)
    q_lens = np.asarray([x[2] for x in layout], np.int32)
    ctx = np.asarray([x[3] for x in layout], np.int32)
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(4, 4, 32), jnp.float32)
    out = np.asarray(ragged_paged_attention(
        q, kp, vp, jnp.asarray(tbl), seq_slots, q_starts, q_lens, ctx,
        interpret=True))
    ref = np.asarray(ragged_paged_attention_reference(
        q, kp, vp, tbl, seq_slots, q_starts, q_lens, ctx))
    for _, qs, ql, _ in layout:
        np.testing.assert_allclose(out[qs:qs + ql], ref[qs:qs + ql],
                                   rtol=2e-5, atol=2e-5)


def test_kernel_matches_decode_kernel_on_pure_decode():
    """A pure-decode ragged batch runs the SAME streaming recurrence as
    the fixed-shape decode kernel — outputs agree to float tolerance."""
    from paddle_tpu.ops.pallas.paged_attention import paged_attention
    kp, vp, tbl = _pool(nslots=3)
    lens = np.asarray([7, 19, 30], np.int32)
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(3, 4, 32), jnp.float32)
    decode = np.asarray(paged_attention(q, kp, vp, jnp.asarray(tbl),
                                        jnp.asarray(lens), interpret=True))
    ragged = np.asarray(ragged_paged_attention(
        q, kp, vp, jnp.asarray(tbl), np.arange(3, dtype=np.int32),
        np.arange(3, dtype=np.int32), np.ones(3, np.int32), lens,
        interpret=True))
    np.testing.assert_allclose(ragged, decode, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# engine acceptance: ragged scheduler == oracle
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    return LlamaForCausalLM(llama_tiny(num_hidden_layers=2,
                                       max_position_embeddings=256))


def _oracle(model, p, n):
    return np.asarray(model.generate(paddle.to_tensor(p),
                                     max_new_tokens=n)._data)


def test_ragged_vs_legacy_mixed_workload_bit_identical(model):
    """The PR's acceptance bar: a mixed 8-request workload (shared
    prefixes, staggered arrivals, one timeout cancellation) through the
    ragged token-budget scheduler produces greedy outputs bit-identical
    to the dense oracle (``model.generate``) on every request."""
    rng = np.random.RandomState(0)
    shared = rng.randint(0, 128, 48)
    specs = [3, 9, 5, 14, 7, 4, 11, 6]           # unique tail lengths
    prompts = [np.concatenate([shared, rng.randint(0, 128, t)])
               .astype(np.int64)[None] for t in specs]

    def run():
        eng = ContinuousServingEngine(
            model, max_batch_size=4, max_len=96, page_size=16,
            prefill_chunk_tokens=24, token_budget=32)
        results = [None] * len(prompts)
        with eng:
            # request 0 lands first and registers the shared prefix
            results[0] = np.asarray(eng.generate(
                prompts[0], max_new_tokens=6, timeout=300).numpy())

            def call(i):
                time.sleep(0.01 * i)             # staggered arrivals
                results[i] = np.asarray(eng.generate(
                    prompts[i], max_new_tokens=6, timeout=300).numpy())

            threads = [threading.Thread(target=call, args=(i,))
                       for i in range(1, len(prompts))]
            for t in threads:
                t.start()
            # one extra request that gives up while the engine is busy
            with pytest.raises(TimeoutError):
                eng.generate(prompts[0], max_new_tokens=30, timeout=0.001)
            for t in threads:
                t.join()
            deadline = time.time() + 60
            while eng.cancelled_rows < 1 and time.time() < deadline:
                time.sleep(0.01)
        assert eng.cancelled_rows >= 1
        return results, eng

    got_r, eng_r = run()
    for got, p in zip(got_r, prompts):
        np.testing.assert_array_equal(got, _oracle(model, p, 6))
    # the ragged run really used the single program family, with both
    # prefill and decode tokens flowing through it
    assert eng_r.ragged_steps > 0
    assert eng_r.ragged_prefill_tokens > 0
    assert eng_r.ragged_decode_tokens > 0
    # prefix-cache hits happened under the ragged scheduler too
    assert eng_r._cache.prefix_hits > 0


def test_ragged_bucket_set_bounded(model):
    """Every compiled shape the scheduler runs must come from the
    declared bucket family — no per-request shapes, no unbounded
    recompiles — and the per-tick pack never exceeds the budget."""
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, 128, (1, n)).astype(np.int64)
               for n in (29, 4, 17, 40)]
    eng = ContinuousServingEngine(model, max_batch_size=4, max_len=64,
                                  token_budget=16, prefill_chunk_tokens=64)
    with eng:
        threads = [threading.Thread(
            target=lambda p=p: eng.generate(p, max_new_tokens=4,
                                            timeout=300))
            for p in prompts]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert eng.ragged_steps > 0
    assert eng.ragged_buckets_used, "no ragged step ran"
    assert eng.ragged_buckets_used <= eng.declared_token_buckets(), (
        eng.ragged_buckets_used, eng.declared_token_buckets())
    assert max(eng.ragged_buckets_used) <= eng.token_budget
    # a 40-token prompt through a 16-token budget takes several ticks
    assert eng.ragged_steps >= 3


def test_ragged_respects_chunk_cap_and_emits_events(model):
    """prefill_chunk_tokens still caps any ONE sequence's per-tick span
    (fairness), and the scheduler emits chunk/decode events so liveness
    remains observable."""
    rng = np.random.RandomState(3)
    p = rng.randint(0, 128, (1, 40)).astype(np.int64)
    eng = ContinuousServingEngine(model, max_batch_size=2, max_len=64,
                                  prefill_chunk_tokens=8, token_budget=64)
    with eng:
        out = np.asarray(eng.generate(p, max_new_tokens=2,
                                      timeout=300).numpy())
    np.testing.assert_array_equal(out, _oracle(model, p, 2))
    chunks = [e for e in eng.events if e[0] == "chunk"]
    assert len(chunks) >= 5                      # ceil(40/8)
    assert max(c[2] for c in chunks) <= 8
    assert eng.prefill_chunks == len(chunks)


def test_ragged_env_knobs(model, monkeypatch):
    monkeypatch.setenv("PADDLE_SERVING_TOKEN_BUDGET", "128")
    eng = ContinuousServingEngine(model)
    assert eng.token_budget == 128
    # budget is clamped so every decode slot keeps its per-tick token
    monkeypatch.setenv("PADDLE_SERVING_TOKEN_BUDGET", "4")
    assert ContinuousServingEngine(
        model, max_batch_size=8).token_budget == 8


def test_unarmed_cache_attend_raises():
    """``attend`` has three modes (a ragged step, the two sep modes) and
    no default: a cache nobody armed says which call is missing."""
    from paddle_tpu.models.generation import SlotPagedKVCache
    cache = SlotPagedKVCache(2, page_size=8, max_len=32)
    x = jnp.zeros((1, 4, 2, 8), jnp.float32)
    with pytest.raises(RuntimeError, match="begin_ragged"):
        cache.attend(object(), x, x, x)
    assert not cache._pools                   # and built nothing
    assert not hasattr(cache, "begin_prefill")
    assert not hasattr(cache, "begin_decode")


def test_ragged_telemetry_and_flight_state(model):
    from paddle_tpu.profiler import metrics
    from paddle_tpu.inference.serving import _engine_state
    rng = np.random.RandomState(4)
    p = rng.randint(0, 128, (1, 20)).astype(np.int64)
    eng = ContinuousServingEngine(model, max_batch_size=2, max_len=48,
                                  token_budget=16)
    with eng:
        eng.generate(p, max_new_tokens=3, timeout=300)
        state = _engine_state(eng)
    snap = metrics()
    ragged = snap["paddle_serving_ragged_tokens_total"]["series"]
    assert ragged.get("prefill", 0) >= 20
    assert ragged.get("decode", 0) >= 2
    util = snap["paddle_serving_token_budget_utilization"]["series"][""]
    assert util["count"] >= eng.ragged_steps > 0
    # flight-recorder state provider carries the ragged scheduler fields
    for key in ("ragged_steps", "token_budget", "ragged_prefill_tokens",
                "ragged_decode_tokens", "ragged_buckets_used",
                "padded_tokens_total", "useful_tokens_total"):
        assert key in state, key

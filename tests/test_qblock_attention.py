"""Device-tier decode speed (ISSUE 16): q-block ragged attention grid,
int8 weights end-to-end, and batched drafting.

Three layers, one bar each:

* the fixed-q-block ragged kernel agrees with the XLA form and the
  dense oracle on every descriptor layout (straddling spans, pure
  decode, shared-prefix page aliasing, int8-KV pages, padded tail
  blocks), and greedy token streams through the engine are
  BIT-identical to ``model.generate``;
* ``quantize_linears`` + ``weight_dtype="int8"`` routes Linear forwards
  through the int8 GEMM and the fully-quantized serving config is
  bit-stable across same-seed runs (ledger token-stream attestation);
* ``DraftModelDrafter.propose_batch`` drafts for every live sequence in
  one padded forward per step, bit-identical to per-sequence
  ``propose``, inside a power-of-two compiled-program family.
"""
import importlib
import threading

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import ContinuousServingEngine
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.models.generation import quantize_kv_rows
from qblock_oracle import qblock_schedule
from paddle_tpu.ops.pallas.ragged_paged_attention import (
    ragged_paged_attention, ragged_paged_attention_reference,
    qblock_job_list, latent_job_list, job_bucket, job_buckets,
    warm_descriptors, MIN_JOBS, LATENT_MIN_JOBS, MAX_JOBS,
    DEFAULT_QBLOCK, _qblock_rows, _token_descriptors,
    _ragged_paged_attention_pallas_qblock, _ragged_paged_attention_xla)


# ---------------------------------------------------------------------------
# kernel parity: q-block grid vs the XLA form vs dense oracle
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


#: tolerance between two runs of the SAME form (the XLA form eager and
#: under jit): ~1 ulp of reordered float32 reductions. A masking bug
#: would be O(1), int8-KV error ~1e-2.
KERNEL_TOL = dict(rtol=1e-6, atol=1e-6)
#: q-block kernel against the XLA form on the same pages (native or the
#: same int8 rows and scales): an online softmax page by page against
#: one softmax over the gathered context
FORM_TOL = dict(rtol=2e-5, atol=2e-5)


def _parity(layout, tokens=None, q_block=8, heads=4, d=32, seed=0,
            tbl_edit=None, quant=False, **pool):
    """Run the SAME descriptors through the q-block interpret kernel and
    the XLA form: span rows must agree to FORM_TOL (on int8 pages too:
    both read the same rows and scales) and match the dense reference to
    float (int8: quantization) tolerance."""
    kp, vp, tbl = _pool(nslots=max(x[0] for x in layout) + 1, d=d,
                        seed=seed, **pool)
    if tbl_edit is not None:
        tbl_edit(tbl)
    seq_slots = np.asarray([x[0] for x in layout], np.int32)
    q_starts = np.asarray([x[1] for x in layout], np.int32)
    q_lens = np.asarray([x[2] for x in layout], np.int32)
    ctx = np.asarray([x[3] for x in layout], np.int32)
    T = tokens or int((q_starts + q_lens).max())
    rng = np.random.RandomState(seed + 1)
    q = jnp.asarray(rng.randn(T, heads, d), jnp.float32)
    sm = d ** -0.5
    if quant:
        kq, ks = quantize_kv_rows(np.asarray(kp))
        vq, vs = quantize_kv_rows(np.asarray(vp))
        kq, ks = jnp.asarray(kq), jnp.asarray(ks)
        vq, vs = jnp.asarray(vq), jnp.asarray(vs)
        qb = np.asarray(_ragged_paged_attention_pallas_qblock(
            q, kq, vq, jnp.asarray(tbl), seq_slots, q_starts, q_lens, ctx,
            sm_scale=sm, interpret=True, k_scales=ks, v_scales=vs,
            q_block=q_block))
        ts, tc = _token_descriptors(T, seq_slots, q_starts, q_lens, ctx)
        tok = np.asarray(_ragged_paged_attention_xla(
            q, kq, vq, jnp.asarray(tbl), ts, tc, sm_scale=sm,
            k_scales=ks, v_scales=vs))
        ref_tol = dict(rtol=5e-2, atol=5e-2)    # int8 quantization error
    else:
        qb = np.asarray(_ragged_paged_attention_pallas_qblock(
            q, kp, vp, jnp.asarray(tbl), seq_slots, q_starts, q_lens, ctx,
            sm_scale=sm, interpret=True, q_block=q_block))
        ts, tc = _token_descriptors(T, seq_slots, q_starts, q_lens, ctx)
        tok = np.asarray(_ragged_paged_attention_xla(
            q, kp, vp, jnp.asarray(tbl), ts, tc, sm_scale=sm))
        ref_tol = dict(rtol=2e-5, atol=2e-5)
    ref = np.asarray(ragged_paged_attention_reference(
        q, kp, vp, tbl, seq_slots, q_starts, q_lens, ctx))
    for slot, qs, ql, _ in layout:               # pad rows are garbage
        np.testing.assert_allclose(qb[qs:qs + ql], tok[qs:qs + ql],
                                   **FORM_TOL)
        assert np.isfinite(qb[qs:qs + ql]).all()
        np.testing.assert_allclose(qb[qs:qs + ql], ref[qs:qs + ql],
                                   **ref_tol)
    return qb, tok


def test_qblock_straddling_spans_parity():
    # spans crossing q-block boundaries: a 9-token prefill straddles
    # blocks 0→1, a 6-token chunk straddles 1→2 — each block mixes rows
    # of different owners, the masking worst case
    _parity([(0, 0, 1, 31), (1, 1, 9, 25), (2, 10, 6, 6), (3, 16, 1, 4)],
            q_block=8)


def test_qblock_pure_decode_parity():
    # the continuous-batching steady state: every span is one token, so
    # one q block carries up to q_block distinct owners
    _parity([(0, 0, 1, 7), (1, 1, 1, 19), (2, 2, 1, 32), (3, 3, 1, 1)],
            q_block=8)


def test_qblock_shared_prefix_aliased_pages():
    # slot 1's table aliases slot 0's leading pages (a prefix-cache
    # hit): the job list must walk the aliased page once per owner
    def alias(tbl):
        tbl[1, :2] = tbl[0, :2]
    _parity([(0, 0, 1, 20), (1, 1, 3, 19)], tbl_edit=alias, seed=7)


def test_qblock_padded_tail_blocks():
    # tokens=24 with spans ending at 10: blocks 1..2 are pure padding
    # (row slot -1, one sentinel job) — they must stay finite and never
    # poison the valid rows
    _parity([(0, 0, 4, 12), (1, 4, 6, 6)], tokens=24, q_block=8)


def test_qblock_int8_kv_parity():
    # int8 KV pages: the q-block kernel scales scores and weights by the
    # row scales the XLA form dequantizes the rows with — same FORM_TOL
    _parity([(0, 0, 1, 12), (1, 1, 5, 25), (2, 6, 9, 9)], quant=True)


def test_qblock_small_block_size():
    # q_block smaller than most spans: every span straddles
    _parity([(0, 0, 7, 15), (1, 7, 5, 5), (2, 12, 1, 30)], q_block=2)


def _jobs_of(jobs, b):
    """A block's real jobs in list order, as (page, slot, kv offset)."""
    return [tuple(int(x) for x in j[1:]) for j in jobs.T
            if j[0] == b and j[2] >= 0]


def test_qblock_schedule_contract():
    """Sentinels, ordering and length of the flat job list."""
    kp, vp, tbl = _pool(nslots=3, page=8, pages_per_seq=5)
    seq_slots = np.asarray([0, 1, 2], np.int32)
    q_starts = np.asarray([0, 1, 10], np.int32)
    q_lens = np.asarray([1, 9, 6], np.int32)
    ctx = np.asarray([33, 25, 6], np.int32)
    row_slot, row_ctx, jobs = qblock_job_list(
        17, seq_slots, q_starts, q_lens, ctx, tbl, 8, 8)
    assert row_slot.shape == (24,)               # ceil(17/8)*8
    # rows outside every span (slot -1 / ctx 0) differ from pad jobs (-2)
    np.testing.assert_array_equal(row_slot[16:], -1)
    np.testing.assert_array_equal(row_ctx[16:], 0)
    block, page, slot, kv = jobs
    # the list as it is: 8 + 5 real jobs and block 2's one job, no padding
    assert jobs.shape == (4, 14) and jobs.dtype == np.int32
    # ONE list in block order: a block's jobs are neighbours
    assert (np.diff(block) >= 0).all() and set(block) == {0, 1, 2}
    # block 2 holds padding rows alone: one job that matches nothing
    # (sentinel slot -2, the scratch page 0), so that its output is written
    assert (slot[:13] >= 0).all()
    assert (block[13], page[13], slot[13], kv[13]) == (2, 0, -2, 0)
    # the latent kernel walks the same list padded to a power of two with
    # such jobs on the last block; the array the Llama kernel's list rides
    # in has a bucket of its own, which the grid never walks
    _, _, padded = latent_job_list(17, seq_slots, q_starts, q_lens, ctx,
                                   tbl, 8, 8)
    assert padded.shape == (4, LATENT_MIN_JOBS)
    np.testing.assert_array_equal(padded[:, :14], jobs)
    assert (padded[0, 14:] == 2).all() and (padded[2, 14:] == -2).all()
    assert (padded[1, 14:] == 0).all()
    assert job_bucket(14) == MIN_JOBS == job_bucket(MIN_JOBS)
    # every real job's page comes from its owner's block table; within a
    # block the owners stand in order of first appearance and each
    # owner's pages ascend, to the bound of its last row in the block
    assert _jobs_of(jobs, 0) == (
        [(int(tbl[0, p]), 0, 8 * p) for p in range(5)]       # ctx 33
        + [(int(tbl[1, p]), 1, 8 * p) for p in range(3)])    # rows to 23
    assert _jobs_of(jobs, 1) == (
        [(int(tbl[1, p]), 1, 8 * p) for p in range(4)]       # rows to 25
        + [(int(tbl[2, 0]), 2, 0)])
    assert _jobs_of(jobs, 2) == []
    # the matrix the grid walked before, job for job (it also walked slot
    # 0's first page for the padding token 16)
    _, _, jp, js, jk = qblock_schedule(17, seq_slots, q_starts, q_lens, ctx,
                                       tbl, 8, 8)
    for b in range(3):
        theirs = [(int(p), int(s), int(k))
                  for p, s, k in zip(jp[b], js[b], jk[b]) if s >= 0]
        assert _jobs_of(jobs, b) == theirs[:len(_jobs_of(jobs, b))]
        assert theirs[len(_jobs_of(jobs, b)):] in (
            [], [(int(tbl[0, 0]), 0, 0)])
    # decode-only blocks stop at each owner's context, not the table end
    _, _, jobs2 = qblock_job_list(
        3, np.arange(3, dtype=np.int32), np.arange(3, dtype=np.int32),
        np.ones(3, np.int32), np.asarray([7, 19, 30], np.int32), tbl, 8, 8)
    assert int((jobs2[2] >= 0).sum()) == 1 + 3 + 4   # ceil(7/8)+(19/8)+(30/8)
    # a slot met twice in one block (two spans of one sequence, as a
    # speculative verify packs them) is walked ONCE, to its longest bound
    _, _, jobs3 = qblock_job_list(
        8, np.asarray([1, 0, 1]), np.asarray([0, 2, 3]),
        np.asarray([2, 1, 2]), np.asarray([10, 5, 20]), tbl, 8, 8)
    assert _jobs_of(jobs3, 0) == (
        [(int(tbl[1, p]), 1, 8 * p) for p in range(3)]
        + [(int(tbl[0, 0]), 0, 0)])


def test_job_buckets_and_warm_descriptors():
    """The declared family of one token bucket: every bucket between one
    job a block and the most its pairs can hold, and descriptors that land
    a call in each of them."""
    assert job_bucket(0) == job_bucket(1024) == 1024
    assert job_bucket(1025) == job_bucket(8192) == 8192
    assert job_bucket(8193) == job_bucket(MAX_JOBS) == MAX_JOBS == 32768
    with pytest.raises(ValueError, match="over the 32768"):
        job_bucket(MAX_JOBS + 1)        # 16 bytes a job: the chip's SMEM
    # the latent kernel's grid is its padded list: powers of two from 64
    assert [job_bucket(n, latent=True) for n in (0, 64, 65, 5000)] == [
        LATENT_MIN_JOBS, 64, 128, 8192]
    assert job_buckets(512, 8, 16, 136, latent=True) == [
        64, 128, 256, 512, 1024, 2048, 4096, 8192, 16384]
    # a family's ladder stops at what the list can hold
    assert job_buckets(1024, 8, 64, 512) == [1024, 8192, MAX_JOBS]
    # 256 tokens, 32 sequences, 128 pages a sequence: 64 pairs x 128
    assert job_buckets(256, 8, 32, 128) == [1024, 8192]
    assert job_buckets(1, 8, 32, 128) == [1024]              # 128 jobs
    assert job_buckets(8, 8, 32, 128) == [1024]              # 1,024 jobs
    assert job_buckets(16, 8, 32, 128) == [1024, 8192]       # 2,048 jobs
    assert job_buckets(16384, 8, 4, 2) == [8192]     # 2,048 blocks at least
    for tokens in (1, 8, 24, 256):
        for want in job_buckets(tokens, 8, 32, 128):
            tbl, ss, qs, ql, cl = warm_descriptors(tokens, want, 8, 16, 128)
            _, _, jobs = qblock_job_list(tokens, ss, qs, ql, cl, tbl, 8, 16)
            assert jobs.shape[1] == min(want, tokens * 128)
            assert job_bucket(jobs.shape[1]) == want, (tokens, want)
            assert (np.diff(qs) > 0).all() and (cl <= 128 * 16).all()


#: one tick of ``serve_chat_closed`` (benchmark/traffic/chat_closed_32.json
#: on benchmark/configs/mistral-7b-serve-16l.json): 30 decode rows part of
#: the way through their output and one 226-token chunk of a long prompt,
#: in the 256-token bucket; pages of 16, ``max_len`` 2048
def _cell_tick(seed=0, chunk_ctx=1100):
    rng = np.random.RandomState(seed)
    prompt = np.clip(np.exp(rng.normal(np.log(256), 0.8, 30)), 32, 1536)
    done = rng.uniform(0, 1, 30) * np.clip(
        np.exp(rng.normal(np.log(48), 0.7, 30)), 8, 192)
    ctx = np.concatenate([(prompt + done).astype(np.int32) + 1,
                          [chunk_ctx]]).astype(np.int32)
    q_lens = np.asarray([1] * 30 + [226], np.int32)
    q_starts = np.arange(31, dtype=np.int32)
    return np.arange(31, dtype=np.int32), q_starts, q_lens, ctx


def test_qblock_grid_walks_the_jobs_that_exist():
    """The mechanism, pinned on the CPU: on a tick of the cell's shape the
    (job, KV head) pairs the grid walks are at most twice the real ones
    (they are the real ones, and one more for a block of padding rows). A
    grid of ``blocks x the longest block's jobs`` walks 8,192 a head here
    for under 2,000 real ones and fails this."""
    from paddle_tpu.profiler import get_tracer, spans
    kv_heads, group, d, page, pps = 8, 4, 16, 16, 128
    ss, qs, ql, cl = _cell_tick()
    tbl = (1 + np.arange(32 * pps, dtype=np.int32)).reshape(32, pps)
    _, _, jobs = qblock_job_list(256, ss, qs, ql, cl, tbl, 8, page)
    real = int((jobs[2] >= 0).sum())
    assert 1000 < real and jobs.shape[1] <= 2 * real
    # ... and by the counters the kernel's own span carries, which is what
    # ``qblock_job_fill_pct`` reads on the chip
    pool = jnp.zeros((kv_heads, 5, page, d), jnp.float32)
    q = jnp.zeros((16, kv_heads * group, d), jnp.float32)
    tracer = get_tracer()
    tracer.drain()
    tracer.enable()
    try:
        spans.latch()
        _ragged_paged_attention_pallas_qblock(
            q, pool, pool, np.zeros((3, 4), np.int32), ss[:3], qs[:3],
            np.ones(3, np.int32), np.asarray([40, 17, 64], np.int32),
            sm_scale=1.0, interpret=True)
    finally:
        tracer.disable()
        spans.latch()
    got = [s for s in tracer.drain() if s.name == "attn/qblock"][-1].args
    assert got["real_jobs"] == 3 + 2 + 4 and got["blocks"] == 2
    # one grid axis, the list itself: block 1's one job is its only other
    assert got["jobs"] == got["steps"] == 3 + 2 + 4 + 1


def test_qblock_cell_shape_parity():
    # the cell's tick: 8 KV heads x group 4 in one grid step, decode
    # blocks of 8 owners beside 29 blocks of one long prefill span. Every
    # span row against the gather+softmax form; a sample of rows against
    # the dense oracle
    ss, qs, ql, cl = _cell_tick(seed=3, chunk_ctx=700)
    cl = np.minimum(cl, 1024)
    kp, vp, tbl = _pool(nslots=31, d=16, kv_heads=8, page=16,
                        pages_per_seq=64)
    q = jnp.asarray(np.random.RandomState(1).randn(256, 32, 16), jnp.float32)
    sm = 16 ** -0.5
    qb = np.asarray(_ragged_paged_attention_pallas_qblock(
        q, kp, vp, tbl, ss, qs, ql, cl, sm_scale=sm, interpret=True))
    ts, tc = _token_descriptors(256, ss, qs, ql, cl)
    dense = np.asarray(_ragged_paged_attention_xla(
        q, kp, vp, tbl, ts, tc, sm_scale=sm))
    assert np.isfinite(qb).all()
    np.testing.assert_allclose(qb, dense, rtol=2e-5, atol=2e-5)
    rows = np.r_[0:30:4, 30:256:45]              # 8 decode rows, 6 chunk rows
    ref = np.asarray(ragged_paged_attention_reference(
        q[rows], kp, vp, tbl, np.asarray(ts)[rows],
        np.arange(len(rows)), np.ones(len(rows), np.int32),
        np.asarray(tc)[rows]))
    np.testing.assert_allclose(qb[rows], ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("quant", [False, True], ids=["native", "int8"])
def test_qblock_traced_descriptors_fall_back(quant):
    """The q-block schedule needs concrete descriptor values (host-side
    numpy); under jit tracing the public entry answers with the XLA form
    (to KERNEL_TOL of that form called directly) and stays correct against
    the oracle, on native and on int8 pools."""
    kp, vp, tbl = _pool(nslots=2)
    seq_slots = np.asarray([0, 1], np.int32)
    q_starts = np.asarray([0, 4], np.int32)
    q_lens = np.asarray([4, 3], np.int32)
    ctx = np.asarray([12, 3], np.int32)
    rng = np.random.RandomState(9)
    q = jnp.asarray(rng.randn(7, 4, 32), jnp.float32)
    pools, scales, tol = (kp, vp), {}, dict(rtol=2e-5, atol=2e-5)
    if quant:
        kq, ks = quantize_kv_rows(np.asarray(kp))
        vq, vs = quantize_kv_rows(np.asarray(vp))
        pools = (jnp.asarray(kq), jnp.asarray(vq))
        scales = dict(k_scales=jnp.asarray(ks), v_scales=jnp.asarray(vs))
        tol = dict(rtol=5e-2, atol=5e-2)        # int8 quantization error

    @jax.jit
    def f(q, ss, qs, ql, cx):
        return ragged_paged_attention(q, *pools, jnp.asarray(tbl),
                                      ss, qs, ql, cx, interpret=True,
                                      **scales)

    out = np.asarray(f(q, seq_slots, q_starts, q_lens, ctx))
    ts, tc = _token_descriptors(7, seq_slots, q_starts, q_lens, ctx)
    xla = np.asarray(_ragged_paged_attention_xla(
        q, *pools, jnp.asarray(tbl), ts, tc, sm_scale=32 ** -0.5, **scales))
    np.testing.assert_allclose(out, xla, **KERNEL_TOL)
    ref = np.asarray(ragged_paged_attention_reference(
        q, kp, vp, tbl, seq_slots, q_starts, q_lens, ctx))
    np.testing.assert_allclose(out, ref, **tol)


def test_latent_pool_under_jit_raises():
    """A latent pool is read by the q-block kernel alone: traced
    descriptors have no XLA form to fall back to."""
    pool = jnp.zeros((1, 3, 32, 8), jnp.float32)
    q = jnp.zeros((2, 4, 32), jnp.float32)
    tbl = np.zeros((1, 2), np.int32)

    @jax.jit
    def f(q, ss, qs, ql, cx):
        return ragged_paged_attention(q, pool, None, tbl, ss, qs, ql, cx,
                                      value_dim=16, interpret=True)

    one = np.ones(1, np.int32)
    with pytest.raises(NotImplementedError, match="not jit tracers"):
        f(q, one * 0, one * 0, one * 2, one * 2)
    # the same call with concrete descriptors is served
    out = ragged_paged_attention(q, pool, None, tbl, one * 0, one * 0,
                                 one * 2, one * 2, value_dim=16,
                                 interpret=True)
    assert out.shape == (2, 4, 16)


# ---------------------------------------------------------------------------
# engine acceptance: the q-block engine == model.generate, bit for bit
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    return LlamaForCausalLM(llama_tiny(num_hidden_layers=2,
                                       max_position_embeddings=256))


def _oracle(model, p, n):
    return np.asarray(model.generate(paddle.to_tensor(p),
                                     max_new_tokens=n)._data)


def _drive(eng, prompts, new_tokens):
    results = [None] * len(prompts)
    with eng:
        threads = [threading.Thread(
            target=lambda i=i, p=p: results.__setitem__(
                i, np.asarray(eng.generate(p, max_new_tokens=new_tokens,
                                           timeout=300).numpy())))
            for i, p in enumerate(prompts)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    return results


def test_engine_qblock_vs_token_bit_identical(model, monkeypatch):
    """Acceptance bar: a mixed chunked-prefill + decode workload under
    the q-block kernel produces greedy outputs bit-identical to the dense
    oracle on every request — and to the same engine with the kernel's
    entry swapped for the XLA form (argmax absorbs the forms' 2e-5)."""
    rpa = importlib.import_module(
        "paddle_tpu.ops.pallas.ragged_paged_attention")
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, 128, (1, n)).astype(np.int64)
               for n in (23, 5, 37, 11)]

    def run():
        eng = ContinuousServingEngine(
            model, max_batch_size=4, max_len=64, token_budget=16,
            prefill_chunk_tokens=16)
        out = _drive(eng, prompts, 5)
        assert eng.ragged_steps > 0
        return out

    got_qb = run()

    def xla_entry(q, kp, vp, tbl, ss, qs, ql, cl, *, sm_scale, interpret,
                  k_scales=None, v_scales=None, window=None):
        ts, tc = _token_descriptors(q.shape[0], ss, qs, ql, cl)
        return _ragged_paged_attention_xla(
            q, kp, vp, tbl, ts, tc, sm_scale=sm_scale, k_scales=k_scales,
            v_scales=v_scales, window=window)

    monkeypatch.setattr(rpa, "_ragged_paged_attention_pallas_qblock",
                        xla_entry)
    got_xla = run()
    for p, a, b in zip(prompts, got_qb, got_xla):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, _oracle(model, p, 5))


def test_dead_switches_change_nothing(model, monkeypatch):
    """``PADDLE_SERVING_RAGGED``, ``PADDLE_TPU_RAGGED_IMPL`` and
    ``PADDLE_TPU_RAGGED_QBLOCK`` are gone, not half-read: with all three
    set an engine still serves in ragged ticks through the q-block entry,
    eight rows a block, and its tokens are the ones served without them."""
    rpa = importlib.import_module(
        "paddle_tpu.ops.pallas.ragged_paged_attention")
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, 128, (1, n)).astype(np.int64)
               for n in (19, 4, 26)]
    entry, calls = rpa._ragged_paged_attention_pallas_qblock, []

    def spy(*a, **kw):
        calls.append(kw.get("q_block"))
        return entry(*a, **kw)

    monkeypatch.setattr(rpa, "_ragged_paged_attention_pallas_qblock", spy)

    def run():
        eng = ContinuousServingEngine(
            model, max_batch_size=2, max_len=48, token_budget=16,
            prefill_chunk_tokens=16)
        out = _drive(eng, prompts, 4)
        return out, eng.ragged_steps, len(calls)

    plain, _, n_plain = run()
    monkeypatch.setenv("PADDLE_SERVING_RAGGED", "0")
    monkeypatch.setenv("PADDLE_TPU_RAGGED_IMPL", "token")
    monkeypatch.setenv("PADDLE_TPU_RAGGED_QBLOCK", "4")
    assert _qblock_rows() == DEFAULT_QBLOCK == 8
    got, ticks, n_all = run()
    assert ticks > 0 and n_all - n_plain == 2 * ticks    # two layers a tick
    assert set(calls) == {None}             # no caller overrides the block
    for a, b in zip(plain, got):
        np.testing.assert_array_equal(a, b)


def test_engine_has_no_scheduler_switch(model):
    """One scheduler: the constructor takes no ``enable_ragged``."""
    with pytest.raises(TypeError, match="enable_ragged"):
        ContinuousServingEngine(model, enable_ragged=False)


def _random_tick(rng, slots, max_len, budget, page, num_pages):
    """A tick the ragged scheduler could pack within an engine's limits:
    up to ``slots`` sequences, a span each, ``budget`` tokens in all,
    contexts to ``max_len``, block tables that may share pages (prefix
    hits alias them, so the page pool bounds nothing here)."""
    nseq = int(rng.integers(1, slots + 1))
    kind = rng.integers(0, 3)              # decode / mixed / prefill-heavy
    q_lens = np.ones(nseq, np.int64)
    left = budget - nseq
    for i in rng.permutation(nseq)[:{0: 0, 1: 1, 2: nseq}[int(kind)]]:
        q_lens[i] += int(rng.integers(0, left + 1))
        left = budget - int(q_lens.sum())
    q_lens = np.minimum(q_lens, max_len)
    gaps = rng.integers(0, 2, nseq) * (rng.random(nseq) < 0.1)
    q_starts = np.cumsum(q_lens + gaps) - q_lens
    keep = q_starts + q_lens <= budget
    q_lens, q_starts = q_lens[keep], q_starts[keep]
    ctx = np.array([rng.integers(n, max_len + 1) if rng.random() < 0.7
                    else max_len for n in q_lens])
    pps = -(-max_len // page)
    tables = rng.integers(1, num_pages, (slots, pps)).astype(np.int32)
    seq_slots = rng.permutation(slots)[:len(q_lens)]
    return tables, seq_slots, q_starts, q_lens, ctx


@pytest.mark.parametrize("limits", [
    dict(max_batch_size=32, max_len=2048, token_budget=256, page_size=16,
         num_pages=2049),                            # serve_chat_closed's
    dict(max_batch_size=4, max_len=64, token_budget=16, page_size=8,
         num_pages=None),
    dict(max_batch_size=8, max_len=200, token_budget=24, page_size=16,
         num_pages=40),                              # a pool far too small
])
def test_engine_declares_every_kernel_bucket_a_tick_can_reach(model, limits):
    """Property: whatever a tick within the engine's limits holds, its
    (token bucket, job bucket) is one ``warmup_programs`` compiles, so no
    kernel program is first met inside a window."""
    from paddle_tpu.inference.serving import _token_bucket
    eng = ContinuousServingEngine(model, **limits)
    family = eng.declared_kernel_buckets()
    assert sorted(family) == sorted(eng.declared_token_buckets())
    rng = np.random.default_rng(28)
    num_pages = limits["num_pages"] or 1 + eng.max_batch * -(
        -eng.max_len // eng.page_size)
    met = set()
    for _ in range(300):
        tables, ss, qs, ql, cl = _random_tick(
            rng, eng.max_batch, eng.max_len, eng.token_budget,
            eng.page_size, num_pages)
        padded = _token_bucket(int((qs + ql).max()), eng.token_budget)
        jobs = job_bucket(qblock_job_list(
            padded, ss, qs, ql, cl, tables, DEFAULT_QBLOCK,
            eng.page_size)[2].shape[1])
        assert jobs in family[padded], (padded, jobs, ql, cl)
        met.add((padded, jobs))
    # the worst case is reachable, not a bound's slack: a span astride
    # every q-block boundary and the other slots a token each, all at the
    # full context, over shared pages
    b, qb = max(family), DEFAULT_QBLOCK
    astride = [k * qb + qb - 1 for k in range(min(-(-b // qb) - 1,
                                                  eng.max_batch))]
    free = [t for t in range(b) if t % qb not in (0, qb - 1)]
    single = free[:eng.max_batch - len(astride)]
    qs = np.sort(np.asarray(astride + single))
    ql = np.where(np.isin(qs, astride), 2, 1)
    tables = np.ones((eng.max_batch, -(-eng.max_len // eng.page_size)),
                     np.int32)
    jobs = qblock_job_list(b, np.arange(len(qs)), qs, ql,
                           np.full(len(qs), eng.max_len), tables, qb,
                           eng.page_size)[2].shape[1]
    assert job_bucket(jobs) == family[b][-1]
    assert jobs > family[b][-1] // 2 or len(family[b]) == 1
    assert len({t for t, _ in met}) >= 4       # the draws vary the tick


def test_warmup_compiles_the_declared_kernel_family(model):
    """``warmup_programs`` calls the kernel once for every declared (token
    bucket, job bucket), through the cache and the public op, and a served
    tick then lands in one of them."""
    import importlib
    rpa = importlib.import_module(
        "paddle_tpu.ops.pallas.ragged_paged_attention")
    eng = ContinuousServingEngine(model, max_batch_size=2, max_len=32,
                                  page_size=8, token_budget=8)
    assert eng.declared_kernel_buckets() == {1: [1024], 2: [1024],
                                             4: [1024], 8: [1024]}
    # 256 pages a sequence: a tick of 8 tokens can hold 5 x 256 jobs
    eng = ContinuousServingEngine(model, max_batch_size=4, max_len=2048,
                                  page_size=8, token_budget=8)
    family = eng.declared_kernel_buckets()
    assert family == {1: [1024], 2: [1024], 4: [1024], 8: [1024, 8192]}
    seen = []
    entry = rpa._ragged_paged_attention_pallas_qblock

    def spy(q, *a, **kw):
        out = entry(q, *a, **kw)
        seen.append(q.shape[0])
        return out

    lengths = []
    build = rpa._qblock_jobs

    def lists(*a, **kw):
        out = build(*a, **kw)
        lengths.append(job_bucket(out[2].shape[1]))
        return out

    rpa._ragged_paged_attention_pallas_qblock = spy
    rpa._qblock_jobs = lists
    try:
        took = eng.warmup_programs()
        warmed = set(zip(seen, lengths))
        assert warmed == {(t, j) for t, js in family.items() for j in js}
        assert took["serving.ragged_attention"] > 0
        del seen[:], lengths[:]
        rng = np.random.RandomState(2)
        _drive(eng, [rng.randint(0, 128, (1, 21)).astype(np.int64)], 3)
        assert seen and set(zip(seen, lengths)) <= warmed
    finally:
        rpa._ragged_paged_attention_pallas_qblock = entry
        rpa._qblock_jobs = build


# ---------------------------------------------------------------------------
# int8 weights end-to-end
# ---------------------------------------------------------------------------

def test_quantize_linears_routes_and_bounds_error():
    """quantize_linears snapshots every Linear's int8 weights, keeps the
    master copy consistent (dequantized), and eval-mode forwards route
    through int8_linear with bounded quantization error."""
    from paddle_tpu import nn
    from paddle_tpu.quantization import quantize_linears, int8_linear

    paddle.seed(3)
    net = nn.Sequential(nn.Linear(32, 48), nn.ReLU(), nn.Linear(48, 16))
    rng = np.random.RandomState(3)
    x = paddle.to_tensor(rng.randn(4, 32).astype(np.float32))
    net.eval()
    ref = np.asarray(net(x)._data)               # float forward
    lin0 = net[0]
    w_before = np.asarray(lin0.weight._data).copy()
    n = quantize_linears(net)
    assert n == 2
    assert lin0._w_int8 is not None and lin0._w_int8.dtype == np.int8
    # per-column absmax quantization: error <= scale/2 per element
    w_after = np.asarray(lin0.weight._data)
    assert np.abs(w_after - w_before).max() <= lin0._w_scale.max() * 0.5 + 1e-6
    # eval forward now routes through the int8 GEMM and equals the
    # explicit int8_linear call bit-for-bit
    out = np.asarray(net(x)._data)
    manual = np.asarray(net[2].forward(
        paddle.nn.functional.relu(int8_linear(
            x, lin0._w_int8, lin0._w_scale, lin0.bias)))._data)
    np.testing.assert_array_equal(out, manual)
    # quantization moves the output by at most the int8 error budget
    np.testing.assert_allclose(out, ref, rtol=0.1, atol=0.1)
    # idempotent: a second call quantizes nothing new
    assert quantize_linears(net) == 0
    # deterministic: repeat forward is bit-identical
    np.testing.assert_array_equal(out, np.asarray(net(x)._data))


def test_engine_weight_dtype_knob(monkeypatch):
    """PADDLE_WEIGHT_DTYPE=int8 quantizes at engine construction; junk
    values are rejected up front."""
    paddle.seed(0)
    m = LlamaForCausalLM(llama_tiny(num_hidden_layers=1))
    monkeypatch.setenv("PADDLE_WEIGHT_DTYPE", "int8")
    eng = ContinuousServingEngine(m, max_batch_size=2, max_len=48)
    assert eng.weight_dtype == "int8"
    assert eng.quantized_linears > 0
    monkeypatch.setenv("PADDLE_WEIGHT_DTYPE", "int4")
    with pytest.raises(ValueError):
        ContinuousServingEngine(m, max_batch_size=2, max_len=48)


def test_fully_int8_serving_bit_stable_with_attestation():
    """The fully-quantized device-tier config — int8 weights AND int8 KV
    pages on the q-block grid — is bit-stable: two same-seed engine runs
    deliver identical tokens, attested by identical ledger token-stream
    digests."""
    from paddle_tpu.profiler import ledger, request_trace as rt

    def run_once():
        paddle.seed(0)
        m = LlamaForCausalLM(llama_tiny(num_hidden_layers=1))
        eng = ContinuousServingEngine(
            m, max_batch_size=2, max_len=48, token_budget=16,
            prefill_chunk_tokens=16, weight_dtype="int8", kv_dtype="int8")
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, 128, (1, n)).astype(np.int64)
                   for n in (13, 21)]
        traces = [rt.start_request(prompt_tokens=p.shape[1],
                                   max_new_tokens=4) for p in prompts]
        outs = [None] * len(prompts)
        with eng:
            threads = [threading.Thread(
                target=lambda i=i: outs.__setitem__(
                    i, np.asarray(eng.generate(
                        prompts[i], max_new_tokens=4, timeout=300,
                        trace=traces[i]).numpy())))
                for i in range(len(prompts))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        digs = [ledger.stream_digest(t.trace_id, 0) for t in traces]
        assert eng.quantized_linears > 0
        assert eng.ragged_buckets_used <= eng.declared_token_buckets()
        return outs, digs

    ledger.enable(mode="warn")
    try:
        outs_a, digs_a = run_once()
        outs_b, digs_b = run_once()
    finally:
        ledger.disable()
    for a, b in zip(outs_a, outs_b):
        np.testing.assert_array_equal(a, b)
    assert all(d is not None for d in digs_a)
    assert digs_a == digs_b


# ---------------------------------------------------------------------------
# batched drafting
# ---------------------------------------------------------------------------

def _draft_model(seed=7):
    paddle.seed(seed)
    return LlamaForCausalLM(llama_tiny(num_hidden_layers=1,
                                       vocab_size=97, hidden_size=32,
                                       intermediate_size=64))


def test_propose_batch_bit_identical_fewer_forwards():
    """propose_batch == per-sequence propose, bit for bit, with one
    forward per draft STEP instead of one per sequence per step."""
    from paddle_tpu.inference.speculative import DraftModelDrafter

    m = _draft_model()
    rng = np.random.RandomState(4)
    hists = [rng.randint(0, 97, n).tolist() for n in (9, 3, 17, 1)]
    ks = [3, 0, 2, 4]
    solo = DraftModelDrafter(m, window=16)
    want = [solo.propose(h, k) for h, k in zip(hists, ks)]
    batch = DraftModelDrafter(m, window=16)
    got = batch.propose_batch(hists, ks)
    assert got == want
    assert len(got[3]) == 4 and got[1] == []
    assert solo.forwards == sum(ks)              # 9
    assert batch.forwards == max(ks)             # 4: one per step


def test_propose_batch_prefix_stable():
    """Over-asking then trimming equals asking exactly — the engine
    over-asks with an optimistic cap and trims to sequential room."""
    from paddle_tpu.inference.speculative import DraftModelDrafter

    m = _draft_model(seed=42)
    rng = np.random.RandomState(8)
    hists = [rng.randint(0, 97, n).tolist() for n in (7, 12)]
    d = DraftModelDrafter(m, window=16)
    long = d.propose_batch(hists, [5, 5])
    short = d.propose_batch(hists, [2, 3])
    assert long[0][:2] == short[0] and long[1][:3] == short[1]


def test_propose_batch_pow2_program_family():
    """Every draft forward runs a power-of-two (rows, width) shape with
    width capped at the drafter window — a bounded compiled-program
    family, not per-(batch, length) shapes."""
    from paddle_tpu.inference.speculative import DraftModelDrafter

    m = _draft_model(seed=1)
    shapes = []
    orig = m.forward
    m.forward = lambda x: (shapes.append(tuple(x.shape)), orig(x))[1]
    try:
        d = DraftModelDrafter(m, window=16)
        rng = np.random.RandomState(2)
        hists = [rng.randint(0, 97, n).tolist() for n in (30, 5, 11)]
        d.propose_batch(hists, [3, 3, 3])
    finally:
        m.forward = orig
    assert shapes, "no draft forward ran"
    for r, w in shapes:
        assert r & (r - 1) == 0 and w & (w - 1) == 0, (r, w)
        assert w <= 16


def test_engine_draft_batch_bit_parity(model, monkeypatch):
    """Speculative decode with batched drafting on vs off: identical
    greedy outputs, fewer draft forwards, and the env knob
    (PADDLE_SPEC_DRAFT_BATCH=0) restores the per-sequence path."""
    rng = np.random.RandomState(6)
    prompts = [rng.randint(0, 128, (1, n)).astype(np.int64)
               for n in (19, 9)]

    def run(batched):
        eng = ContinuousServingEngine(
            model, max_batch_size=2, max_len=64, token_budget=16,
            prefill_chunk_tokens=16, spec_decode=True, spec_k=3,
            draft_model=model, draft_batch=batched)
        out = _drive(eng, prompts, 6)
        assert eng.spec_drafted_tokens > 0
        return out, eng

    got_on, eng_on = run(True)
    got_off, eng_off = run(False)
    for a, b in zip(got_on, got_off):
        np.testing.assert_array_equal(a, b)
    assert eng_on.spec_draft_ticks > 0
    # batched: at most spec_k forwards per tick regardless of rows; the
    # per-sequence path pays forwards ~= drafted tokens
    assert eng_on.spec_draft_forwards <= eng_off.spec_draft_forwards
    assert eng_on.spec_draft_forwards <= eng_on.spec_draft_ticks * 3
    monkeypatch.setenv("PADDLE_SPEC_DRAFT_BATCH", "0")
    eng = ContinuousServingEngine(model, spec_decode=True, spec_k=3,
                                  draft_model=model)
    assert eng.draft_batch is False
    monkeypatch.setenv("PADDLE_SPEC_DRAFT_BATCH", "1")
    assert ContinuousServingEngine(model, spec_decode=True, spec_k=3,
                                   draft_model=model).draft_batch is True

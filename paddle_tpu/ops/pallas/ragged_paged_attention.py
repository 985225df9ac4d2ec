"""Ragged paged attention — ONE kernel for mixed prefill + decode over the
shared paged KV pool (reference: "Ragged Paged Attention", arxiv
2604.15464; ROADMAP item 1 after PR 4's two-program serving tick).

The serving scheduler packs a tick's work into ONE flat token batch:
every decoding slot contributes its single current token, every
mid-prefill slot contributes a span of prompt tokens, and the whole
batch is padded to a bounded bucket size. Each sequence is described by
``(slot, q_start, q_len, context_len)``:

* ``slot``         — row of ``block_tables`` (the sequence's page map);
* ``q_start``      — offset of the sequence's first token in the flat
                     ``q`` batch (``q_starts`` must be non-decreasing);
* ``q_len``        — number of NEW tokens this step (1 for decode);
* ``context_len``  — total context INCLUDING the new tokens, so query
                     ``j`` of the span attends positions
                     ``[0, context_len - q_len + j]`` — causal masking
                     inside the ragged span falls out of the same
                     per-token context bound the decode kernel uses.

Tokens outside every span (bucket padding) attend one garbage key
(page 0 slot 0, the pool's scratch page) and their output is discarded
by the caller — identical to the decode kernel's inactive-slot story.

Tiers (``PADDLE_TPU_RAGGED_IMPL``), mirroring
``ops/pallas/paged_attention.py``:

* ``auto`` / ``inrepo`` / ``qblock`` / ``token``: an in-repo kernel —
  block-table-steered dynamic BlockSpec index maps (scalar prefetch in
  SMEM), online-softmax scratch accumulation, the decode kernel's
  streaming recurrence with per-TOKEN (not per-row) context bounds and
  table rows. Compiled by Mosaic on a TPU backend (a compiler error
  propagates), run in interpret mode by the CPU tests;
* ``xla``: a plain-XLA gather+softmax, no kernel at all — only ever
  reached through this explicit switch.

Two in-repo grids. The default **q-block** grid ``(q_blocks, kv_head,
jobs)`` tiles the flat batch into fixed ``PADDLE_TPU_RAGGED_QBLOCK``-row
blocks over the cumulative span offsets and walks a host-built job list
(one (page, owner-slot, kv-offset) per KV page any sequence in the
block needs) — one grid step covers a whole block of tokens against one
page, so a mixed tick runs far fewer, fatter MXU steps. A block may
straddle span boundaries: rows past a span's causal bound mask with
-inf exactly like the per-token kernel, and cross-span keys are steered
out with a finite ``BIG_NEG`` so alien jobs are bitwise no-ops (see
``BIG_NEG``). The historical **per-token** grid ``(tokens, kv_head,
pages)`` remains as the escape hatch (``PADDLE_TPU_RAGGED_IMPL=token``)
and is used automatically under jit tracing, where the q-block
schedule's host-side job build cannot run. The two grids run the SAME
online-softmax recurrence in the same per-row page order — the masking
is an exact no-op on alien jobs, so outputs agree to ~1 ulp (the only
reorder is the dot shape itself: ``[q_block*group, d]`` vs
``[group, d]`` MXU tiles accumulate in different orders) and greedy
token streams through the serving engine are bit-identical.

Unused block-table entries MUST be 0 (a valid page): their scores are
masked by the per-token context bound but the DMA address must be in
range.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...profiler import spans as _spans
from .paged_attention import NEG_INF, _device_call, _scale_rows

#: finite cross-span mask for the q-block kernel. The causal bound keeps
#: NEG_INF (= -inf, matching the per-token kernel bit for bit on a row's
#: own pages); keys belonging to ANOTHER sequence's job must stay finite:
#: a row whose first visited job is alien would otherwise accumulate
#: m = -inf and hit exp(-inf - -inf) = NaN, which no later correction
#: can wash out. With -1e30, the first own-slot job's rescale factor
#: exp(-1e30 - m_real) underflows to exactly 0.0, erasing the alien
#: garbage bitwise; alien jobs after it are exact no-ops (weights
#: exp(-1e30 - m_real) = 0.0, correction exp(0) = 1.0).
BIG_NEG = -1e30

#: the latent kernel's products take their operands as they are stored: a
#: global ``jax_default_matmul_precision`` of "highest" (the test suite's)
#: would ask Mosaic for a float32 product of bf16 operands, which it refuses
_DEFAULT = jax.lax.Precision.DEFAULT

#: default q-block rows (tokens per grid step); PADDLE_TPU_RAGGED_QBLOCK
DEFAULT_QBLOCK = 8


def _qblock_rows():
    import os
    try:
        qb = int(os.environ.get("PADDLE_TPU_RAGGED_QBLOCK",
                                str(DEFAULT_QBLOCK)))
    except ValueError:
        qb = DEFAULT_QBLOCK
    return max(qb, 1)


def _token_descriptors(num_tokens, seq_slots, q_starts, q_lens,
                       context_lens):
    """Expand per-sequence ``(slot, q_start, q_len, context_len)``
    descriptors into the per-token arrays the kernel grid consumes:
    ``tok_slot[t]`` (block-table row) and ``tok_ctx[t]`` (key positions
    visible to token ``t``). Padding tokens — outside every span — get
    ``(slot 0, ctx 1)``: one finite, discarded garbage score instead of
    an all-masked NaN softmax. Pure jnp, so it traces under jit."""
    seq_slots = jnp.asarray(seq_slots, jnp.int32)
    q_starts = jnp.asarray(q_starts, jnp.int32)
    q_lens = jnp.asarray(q_lens, jnp.int32)
    context_lens = jnp.asarray(context_lens, jnp.int32)
    tok = jnp.arange(num_tokens, dtype=jnp.int32)
    nseq = q_starts.shape[0]
    seq_of = jnp.clip(
        jnp.searchsorted(q_starts, tok, side="right").astype(jnp.int32) - 1,
        0, nseq - 1)
    off = tok - q_starts[seq_of]
    valid = (off >= 0) & (off < q_lens[seq_of])
    tok_slot = jnp.where(valid, seq_slots[seq_of], 0)
    tok_ctx = jnp.where(
        valid, context_lens[seq_of] - q_lens[seq_of] + off + 1, 1)
    return tok_slot, tok_ctx


def qblock_schedule(num_tokens, seq_slots, q_starts, q_lens, context_lens,
                    block_tables, q_block, page_size):
    """Host-side (numpy, concrete-value) schedule for the q-block grid.

    Tiles the flat packed batch into fixed ``q_block``-row blocks over
    the cumulative span offsets and enumerates, per block, the "jobs"
    its grid steps execute: one (physical page, owner slot, kv offset)
    triple per KV page any sequence appearing in the block still needs.
    Pages of one slot are listed ascending, slots in first-appearance
    order, so each row sees its own pages in exactly the per-token
    kernel's order. The job count is padded to a power of two so the
    compiled-program family stays bounded (grid = (blocks, kv_heads, J)
    with J from a small bucket set, vs (tokens, kv_heads, pages)).

    Sentinels: rows past ``num_tokens`` (block padding) get slot -1 /
    ctx 0; padding jobs get slot -2 / page 0. They can never match each
    other, so every row's score matrix keeps at least one finite entry
    (BIG_NEG) and the online softmax never sees an all--inf row.

    Returns ``(row_slot [B*q_block], row_ctx [B*q_block],
    job_page [B, J], job_slot [B, J], job_kv [B, J])`` int32 numpy.
    """
    import numpy as np

    ss = np.asarray(seq_slots, np.int32).reshape(-1)
    qs = np.asarray(q_starts, np.int32).reshape(-1)
    ql = np.asarray(q_lens, np.int32).reshape(-1)
    cl = np.asarray(context_lens, np.int32).reshape(-1)
    tbl = np.asarray(block_tables, np.int32)
    pages_per_seq = tbl.shape[1]
    T = int(num_tokens)
    q_block = max(int(q_block), 1)

    tok = np.arange(T, dtype=np.int32)
    nseq = qs.shape[0]
    seq_of = np.clip(
        np.searchsorted(qs, tok, side="right").astype(np.int32) - 1,
        0, max(nseq - 1, 0))
    off = tok - qs[seq_of]
    valid = (off >= 0) & (off < ql[seq_of])
    ts = np.where(valid, ss[seq_of], 0).astype(np.int32)
    tc = np.where(valid, cl[seq_of] - ql[seq_of] + off + 1, 1).astype(
        np.int32)

    nblocks = -(-T // q_block)
    t_pad = nblocks * q_block
    row_slot = np.full(t_pad, -1, np.int32)
    row_ctx = np.zeros(t_pad, np.int32)
    row_slot[:T] = ts
    row_ctx[:T] = tc
    bs = row_slot.reshape(nblocks, q_block)
    bc = row_ctx.reshape(nblocks, q_block)

    jobs = []
    max_jobs = 1
    for b in range(nblocks):
        block_jobs = []
        seen = []
        for r in range(q_block):
            slot = int(bs[b, r])
            if slot < 0 or slot in seen:
                continue
            seen.append(slot)
            cmax = int(bc[b][bs[b] == slot].max())
            n_pages = min(max(-(-cmax // page_size), 1), pages_per_seq)
            for p in range(n_pages):
                block_jobs.append((int(tbl[slot, p]), slot, p * page_size))
        if not block_jobs:
            block_jobs.append((0, -2, 0))
        jobs.append(block_jobs)
        max_jobs = max(max_jobs, len(block_jobs))

    num_jobs = 1 << (max_jobs - 1).bit_length()
    job_page = np.zeros((nblocks, num_jobs), np.int32)
    job_slot = np.full((nblocks, num_jobs), -2, np.int32)
    job_kv = np.zeros((nblocks, num_jobs), np.int32)
    for b, block_jobs in enumerate(jobs):
        for j, (page, slot, kv) in enumerate(block_jobs):
            job_page[b, j] = page
            job_slot[b, j] = slot
            job_kv[b, j] = kv
    return row_slot, row_ctx, job_page, job_slot, job_kv


def _qblock_masked_scores(s, kv_start, jslot, row_slot, row_ctx):
    """Causal bound with NEG_INF (bitwise the per-token kernel's mask on
    a row's own pages), then the whole row to finite BIG_NEG wherever
    the row's sequence does not own this job's page."""
    pos = kv_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < row_ctx, s, NEG_INF)
    return jnp.where(row_slot == jslot, s, BIG_NEG)


def _qblock_kernel(jp_ref, js_ref, jk_ref, rs_ref, rc_ref, q_ref, k_ref,
                   v_ref, o_ref, m_ref, l_ref, acc_ref, *, sm_scale,
                   num_jobs):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    jslot = js_ref[b, j]
    jkv = jk_ref[b, j]
    row_slot = rs_ref[0][:, :1]                    # [Qg, 1]
    row_ctx = rc_ref[0][:, :1]
    q = q_ref[0, 0].astype(jnp.float32)            # [Qg, d]
    k = k_ref[0, 0].astype(jnp.float32)            # [page_size, d]
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale
    s = _qblock_masked_scores(s, jkv, jslot, row_slot, row_ctx)

    m_prev = m_ref[...][:, :1]                     # [Qg, 1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    w = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_ref[...][:, :1] * corr + jnp.sum(w, -1, keepdims=True)
    pv = jax.lax.dot_general(                      # [Qg, d]
        w, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == num_jobs - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...][:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _qblock_kernel_quant(jp_ref, js_ref, jk_ref, rs_ref, rc_ref, q_ref,
                         k_ref, v_ref, ks_ref, vs_ref, o_ref, m_ref,
                         l_ref, acc_ref, *, sm_scale, num_jobs):
    """int8-KV q-block variant: same job walk; the per-row fp32 scales
    arrive as a ``[1, page_size]`` lane vector and scale the scores /
    weights around the int8 dots (see ``_decode_kernel_quant``)."""
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    jslot = js_ref[b, j]
    jkv = jk_ref[b, j]
    row_slot = rs_ref[0][:, :1]
    row_ctx = rc_ref[0][:, :1]
    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * (ks_ref[0, 0] * sm_scale)
    s = _qblock_masked_scores(s, jkv, jslot, row_slot, row_ctx)

    m_prev = m_ref[...][:, :1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    w = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_ref[...][:, :1] * corr + jnp.sum(w, -1, keepdims=True)
    pv = jax.lax.dot_general(
        w * vs_ref[0, 0], v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == num_jobs - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...][:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _ragged_paged_attention_pallas_qblock(q, k_pages, v_pages,
                                          block_tables, seq_slots,
                                          q_starts, q_lens, context_lens,
                                          *, sm_scale, interpret,
                                          k_scales=None, v_scales=None,
                                          q_block=None, value_dim=None):
    """Q-block tier: grid ``(q_blocks, kv_heads, jobs)`` over the flat
    packed batch — one grid step covers ``q_block`` tokens against one
    KV page, so a mixed prefill+decode tick runs far fewer (and fatter)
    MXU steps than the per-token grid. Requires concrete descriptors
    (the job schedule is built host-side).

    ``v_pages is None`` is the LATENT call: one pool of one KV head whose
    row a token holds keys and values alike (``value_dim``: the values are
    that prefix of the row). The host half (this entry, its spans, now
    with ``latent=1``) is shared; the schedule is a flat job list
    (:func:`latent_job_list`) and the device half a wrapper of its own name
    (:func:`_latent_qblock_device`), so a device trace tells the two
    kernels apart."""
    import numpy as np

    tokens, heads, d = q.shape
    qb = q_block or _qblock_rows()
    if v_pages is None:
        page_size = k_pages.shape[3]            # [1, pages, d, page_size]
        with _spans.span("attn/qblock", latent=1) as sp:
            with _spans.span("attn/qblock_schedule", latent=1):
                row_slot, row_ctx, jobs = latent_job_list(
                    tokens, seq_slots, q_starts, q_lens, context_lens,
                    block_tables, qb, page_size)
            sp.set(jobs=jobs.shape[1], blocks=row_slot.shape[0] // qb)
            return _latent_call(jobs, row_slot, row_ctx, q, k_pages,
                                sm_scale, interpret, value_dim, qb)
    kv_heads, _, page_size, _ = k_pages.shape
    group = heads // kv_heads
    with _spans.span("attn/qblock") as sp:
        with _spans.span("attn/qblock_schedule"):
            row_slot, row_ctx, job_page, job_slot, job_kv = qblock_schedule(
                tokens, seq_slots, q_starts, q_lens, context_lens,
                block_tables, qb, page_size)
            nblocks = job_page.shape[0]
            # per-ROW metadata rides as [B, Qg, 128] VMEM lanes so the
            # kernel can slice [:, :1] — the same layout trick the softmax
            # scratch uses (broadcast host-side: one transfer, no extra
            # eager device ops)
            rows = np.repeat(row_slot.reshape(nblocks, qb), group, axis=1)
            rowc = np.repeat(row_ctx.reshape(nblocks, qb), group, axis=1)
            rs = np.broadcast_to(rows[:, :, None],
                                 (nblocks, qb * group, 128))
            rc = np.broadcast_to(rowc[:, :, None],
                                 (nblocks, qb * group, 128))
        sp.set(jobs=job_page.shape[1], blocks=nblocks)
        return _qblock_device(job_page, job_slot, job_kv, rs, rc, q,
                              k_pages, v_pages, k_scales, v_scales,
                              sm_scale=sm_scale, interpret=interpret)


@_device_call
def _qblock_device(job_page, job_slot, job_kv, rs, rc, q, k_pages, v_pages,
                   k_scales, v_scales, *, sm_scale, interpret):
    """Device half of the q-block tier: the schedule arrives as arrays
    (``job_*`` [B, J] scalar-prefetched, ``rs``/``rc`` [B, Qg, 128] row
    slot and context bound), so one compiled program serves every tick of
    a (tokens, blocks, jobs) shape."""
    tokens, heads, d = q.shape
    kv_heads, _, page_size, _ = k_pages.shape
    group = heads // kv_heads
    nblocks, num_jobs = job_page.shape
    qg_rows = rs.shape[1]
    qb = qg_rows // group
    t_pad = nblocks * qb

    qp = jnp.pad(q, ((0, t_pad - tokens), (0, 0), (0, 0)))
    qg = qp.reshape(nblocks, qb, kv_heads, group, d).transpose(
        0, 2, 1, 3, 4).reshape(nblocks, kv_heads, qg_rows, d)
    rs, rc = jnp.asarray(rs), jnp.asarray(rc)

    quant = k_scales is not None
    kernel = functools.partial(
        _qblock_kernel_quant if quant else _qblock_kernel,
        sm_scale=sm_scale, num_jobs=num_jobs)
    page_spec = pl.BlockSpec((1, 1, page_size, d),
                             lambda b, h, j, jp, js, jk:
                             (h, jp[b, j], 0, 0))
    scale_spec = pl.BlockSpec((1, 1, 1, page_size),
                              lambda b, h, j, jp, js, jk:
                              (h, jp[b, j], 0, 0))
    row_spec = pl.BlockSpec((1, qg_rows, 128),
                            lambda b, h, j, jp, js, jk: (b, 0, 0))
    in_specs = [
        row_spec, row_spec,
        pl.BlockSpec((1, 1, qg_rows, d),
                     lambda b, h, j, jp, js, jk: (b, h, 0, 0)),
        page_spec, page_spec,
    ]
    operands = [rs, rc, qg, k_pages, v_pages]
    if quant:
        in_specs += [scale_spec, scale_spec]
        operands += [_scale_rows(k_scales), _scale_rows(v_scales)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nblocks, kv_heads, num_jobs),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, qg_rows, d),
                               lambda b, h, j, jp, js, jk: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((qg_rows, 128), jnp.float32),
            pltpu.VMEM((qg_rows, 128), jnp.float32),
            pltpu.VMEM((qg_rows, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nblocks, kv_heads, qg_rows, d),
                                       q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(job_page), jnp.asarray(job_slot), jnp.asarray(job_kv),
      *operands)
    out = out.reshape(nblocks, kv_heads, qb, group, d).transpose(
        0, 2, 1, 3, 4).reshape(t_pad, heads, d)
    return out[:tokens]


#: smallest flat job list of the latent kernel; lists are padded to a
#: power of two from here, so the family of compiled programs stays small
LATENT_MIN_JOBS = 64


def latent_job_list(num_tokens, seq_slots, q_starts, q_lens, context_lens,
                    block_tables, q_block, page_size):
    """Host-side schedule of the latent q-block kernel, vectorized: the
    same jobs as :func:`qblock_schedule` (one a KV page a sequence of a
    q-block needs, a sequence's pages ascending) as ONE flat list in block
    order, so the grid walks the jobs that exist and not ``blocks x the
    longest block's jobs``: with 64 query heads on one KV head a block of 8
    decode rows at 8 k of context has 500 jobs and a block of prefill rows
    64. Padding rows (outside every span) get slot -1 / ctx 0 and own no
    job; a block without jobs gets one that matches nothing (slot -2), so
    that its output is written; the list is padded to a power of two with
    such jobs on the last block.

    Returns ``(row_slot [B*q_block], row_ctx [B*q_block], jobs [4, J])``
    int32 numpy; ``jobs`` rows are (q-block, physical page, owner slot,
    kv offset)."""
    import numpy as np

    ss = np.asarray(seq_slots, np.int64).reshape(-1)
    qs = np.asarray(q_starts, np.int64).reshape(-1)
    ql = np.asarray(q_lens, np.int64).reshape(-1)
    cl = np.asarray(context_lens, np.int64).reshape(-1)
    tbl = np.asarray(block_tables, np.int32)
    pages_per_seq = tbl.shape[1]
    qb = max(int(q_block), 1)
    nblocks = -(-int(num_tokens) // qb)
    t_pad = nblocks * qb

    row_slot = np.full(t_pad, -1, np.int32)
    row_ctx = np.zeros(t_pad, np.int32)
    live = ql > 0
    ss, qs, ql, cl = ss[live], qs[live], ql[live], cl[live]
    span_of = np.repeat(np.arange(len(qs)), ql)
    off = np.arange(len(span_of)) - np.repeat(np.cumsum(ql) - ql, ql)
    tok = qs[span_of] + off
    row_slot[tok] = ss[span_of]
    row_ctx[tok] = (cl - ql)[span_of] + off + 1

    # one (block, sequence) pair a q-block a span overlaps; the pages it
    # needs reach the context bound of the span's last row in the block
    b0, b1 = qs // qb, (qs + ql - 1) // qb
    nb = b1 - b0 + 1
    pair_span = np.repeat(np.arange(len(qs)), nb)
    pair_block = b0[pair_span] + (np.arange(len(pair_span))
                                  - np.repeat(np.cumsum(nb) - nb, nb))
    last_row = np.minimum(qs[pair_span] + ql[pair_span],
                          (pair_block + 1) * qb) - 1
    cmax = (cl - ql)[pair_span] + (last_row - qs[pair_span]) + 1
    n_pages = np.clip(-(-cmax // page_size), 1, pages_per_seq)
    pair_slot = ss[pair_span]
    # a slot met twice in one block (two spans of one sequence) is walked
    # once, as far as its longest bound
    key = pair_block * (int(tbl.shape[0]) + 1) + pair_slot
    uniq, inv = np.unique(key, return_inverse=True)
    if len(uniq) != len(key):
        pages = np.zeros(len(uniq), np.int64)
        np.maximum.at(pages, inv, n_pages)
        first = np.zeros(len(uniq), np.int64)
        first[inv[::-1]] = np.arange(len(key))[::-1]
        pair_block, pair_slot, n_pages = (pair_block[first],
                                          pair_slot[first], pages)
    empty = np.setdiff1d(np.arange(nblocks), pair_block)
    pair_block = np.concatenate([pair_block, empty])
    pair_slot = np.concatenate([pair_slot, np.full(len(empty), -2)])
    n_pages = np.concatenate([n_pages, np.ones(len(empty), np.int64)])
    order = np.argsort(pair_block, kind="stable")
    pair_block, pair_slot, n_pages = (pair_block[order], pair_slot[order],
                                      n_pages[order])

    total = int(n_pages.sum())
    num_jobs = max(1 << (total - 1).bit_length(), LATENT_MIN_JOBS)
    jobs = np.zeros((4, num_jobs), np.int32)
    jobs[0, total:] = nblocks - 1
    jobs[2, total:] = -2
    page_idx = np.arange(total) - np.repeat(np.cumsum(n_pages) - n_pages,
                                            n_pages)
    slot = np.repeat(pair_slot, n_pages)
    jobs[0, :total] = np.repeat(pair_block, n_pages)
    jobs[1, :total] = np.where(slot >= 0,
                               tbl[np.maximum(slot, 0), page_idx], 0)
    jobs[2, :total] = slot
    jobs[3, :total] = page_idx * page_size
    return row_slot, row_ctx, jobs


def _latent_kernel(jobs_ref, rs_ref, rc_ref, q_ref, kv_ref, o_ref, m_ref,
                   l_ref, acc_ref, *, sm_scale, num_jobs, value_dim):
    """One grid step: a q-block's rows (``q_block`` tokens x all heads)
    against one page of latent rows (``[d, page_size]``: a token a column,
    as the pool keeps them), read once for keys and values. The
    products take the pool's type as their operands' (bf16 x bf16 products
    are exact in the float32 accumulator) and the softmax runs in
    float32; the weights enter the second product in the pool's type."""
    j = pl.program_id(0)
    blk = jobs_ref[0, j]
    first = (j == 0) | (jobs_ref[0, jnp.maximum(j - 1, 0)] != blk)
    last = (j == num_jobs - 1) | (
        jobs_ref[0, jnp.minimum(j + 1, num_jobs - 1)] != blk)

    @pl.when(first)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    jslot = jobs_ref[2, j]

    @pl.when(jslot >= 0)                            # -2: matches no row
    def _step():
        q = q_ref[0]                                # [rows, d]
        kv = kv_ref[0, 0]                           # [d, page_size]
        s = jax.lax.dot_general(
            q, kv, (((1,), (0,)), ((), ())), precision=_DEFAULT,
            preferred_element_type=jnp.float32) * sm_scale
        s = _qblock_masked_scores(s, jobs_ref[3, j], jslot,
                                  rs_ref[0][:, :1], rc_ref[0][:, :1])
        m_prev = m_ref[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        w = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_ref[...][:, :1] * corr + jnp.sum(w, -1, keepdims=True)
        pv = jax.lax.dot_general(
            w.astype(kv.dtype), kv[:value_dim],
            (((1,), (1,)), ((), ())), precision=_DEFAULT,
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(last)
    def _finalize():
        l = jnp.maximum(l_ref[...][:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _latent_qblock_device(jobs, row_slot, row_ctx, q, kv_pages, sm_scale,
                          interpret, value_dim, q_block):
    """Device half of the latent q-block tier: ``jobs`` [4, J] is scalar-
    prefetched, the rows' slot and context bound arrive one a TOKEN and are
    spread over the heads here, on the device (64 heads a token would make
    them 16 MB a layer on the host's side of the link)."""
    tokens, heads, d = q.shape
    _, _, _, page_size = kv_pages.shape
    num_jobs = jobs.shape[1]
    t_pad = row_slot.shape[0]
    nblocks, rows = t_pad // q_block, q_block * heads

    qg = jnp.pad(q, ((0, t_pad - tokens), (0, 0), (0, 0))).reshape(
        nblocks, rows, d)

    def spread(a):
        a = jnp.repeat(jnp.asarray(a, jnp.int32).reshape(nblocks, q_block),
                       heads, axis=1)
        return jnp.broadcast_to(a[:, :, None], (nblocks, rows, 128))

    kernel = functools.partial(_latent_kernel, sm_scale=sm_scale,
                               num_jobs=num_jobs, value_dim=value_dim)
    row_spec = pl.BlockSpec((1, rows, 128), lambda j, jobs: (jobs[0, j], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_jobs,),
        in_specs=[
            row_spec, row_spec,
            pl.BlockSpec((1, rows, d), lambda j, jobs: (jobs[0, j], 0, 0)),
            pl.BlockSpec((1, 1, d, page_size),
                         lambda j, jobs: (0, jobs[1, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, rows, value_dim),
                               lambda j, jobs: (jobs[0, j], 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, value_dim), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nblocks, rows, value_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.asarray(jobs), spread(row_slot), spread(row_ctx), qg, kv_pages)
    return out.reshape(t_pad, heads, value_dim)[:tokens]


#: the jitted device half: ``_latent_qblock_device`` in a device trace
_latent_qblock_jit = jax.jit(_latent_qblock_device,
                             static_argnums=(5, 6, 7, 8))


def _latent_call(jobs, row_slot, row_ctx, q, kv_pages, sm_scale, interpret,
                 value_dim, q_block):
    """Interpret mode eager (the CPU tests' numerics), else jitted."""
    fn = _latent_qblock_device if interpret else _latent_qblock_jit
    return fn(jobs, row_slot, row_ctx, q, kv_pages, sm_scale, interpret,
              value_dim, q_block)


def _ragged_kernel(slots_ref, ctx_ref, tables_ref, q_ref, k_ref, v_ref,
                   o_ref, m_ref, l_ref, acc_ref, *, sm_scale, page_size,
                   pages_per_seq, group):
    t = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ctx = ctx_ref[t]
    q = q_ref[0, 0].astype(jnp.float32)            # [group, d]
    k = k_ref[0, 0].astype(jnp.float32)            # [page_size, d]
    v = v_ref[0, 0].astype(jnp.float32)
    # s[g, ps] — one plain 2-D MXU dot per (token, head, page)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale
    pos = p * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < ctx, s, NEG_INF)

    m_prev = m_ref[...][:, :1]                     # [g, 1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    w = jnp.exp(s - m_new)                         # masked -> 0
    corr = jnp.exp(m_prev - m_new)
    l_new = l_ref[...][:, :1] * corr + jnp.sum(w, -1, keepdims=True)
    pv = jax.lax.dot_general(                      # [g, d]
        w, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(p == pages_per_seq - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...][:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _ragged_kernel_quant(slots_ref, ctx_ref, tables_ref, q_ref, k_ref,
                         v_ref, ks_ref, vs_ref, o_ref, m_ref, l_ref,
                         acc_ref, *, sm_scale, page_size, pages_per_seq,
                         group):
    """int8-KV variant of :func:`_ragged_kernel`: page blocks arrive as
    int8 rows plus one fp32 scale per (page, slot) row (a
    ``[1, page_size]`` lane vector scaling the scores / weights around
    the dots, see ``_decode_kernel_quant``) — fp32 pages never exist in
    HBM."""
    t = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ctx = ctx_ref[t]
    q = q_ref[0, 0].astype(jnp.float32)            # [group, d]
    k = k_ref[0, 0].astype(jnp.float32)            # [page_size, d]
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * (ks_ref[0, 0] * sm_scale)
    pos = p * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < ctx, s, NEG_INF)

    m_prev = m_ref[...][:, :1]                     # [g, 1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    w = jnp.exp(s - m_new)                         # masked -> 0
    corr = jnp.exp(m_prev - m_new)
    l_new = l_ref[...][:, :1] * corr + jnp.sum(w, -1, keepdims=True)
    pv = jax.lax.dot_general(                      # [g, d]
        w * vs_ref[0, 0], v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(p == pages_per_seq - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...][:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@_device_call
def _ragged_paged_attention_pallas_quant(q, k_pages, v_pages, k_scales,
                                         v_scales, block_tables, tok_slot,
                                         tok_ctx, *, sm_scale, interpret):
    tokens, heads, d = q.shape
    kv_heads, _, page_size, _ = k_pages.shape
    pages_per_seq = block_tables.shape[1]
    group = heads // kv_heads
    qg = q.reshape(tokens, kv_heads, group, d)

    kernel = functools.partial(
        _ragged_kernel_quant, sm_scale=sm_scale, page_size=page_size,
        pages_per_seq=pages_per_seq, group=group)
    page_spec = pl.BlockSpec((1, 1, page_size, d),
                             lambda t, h, p, slot, ctx, tbl:
                             (h, tbl[slot[t], p], 0, 0))
    scale_spec = pl.BlockSpec((1, 1, 1, page_size),
                              lambda t, h, p, slot, ctx, tbl:
                              (h, tbl[slot[t], p], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(tokens, kv_heads, pages_per_seq),
        in_specs=[
            pl.BlockSpec((1, 1, group, d),
                         lambda t, h, p, slot, ctx, tbl: (t, h, 0, 0)),
            page_spec, page_spec, scale_spec, scale_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, group, d),
                               lambda t, h, p, slot, ctx, tbl: (t, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, 128), jnp.float32),
            pltpu.VMEM((group, 128), jnp.float32),
            pltpu.VMEM((group, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tokens, kv_heads, group, d),
                                       q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(tok_slot, jnp.int32), jnp.asarray(tok_ctx, jnp.int32),
      jnp.asarray(block_tables, jnp.int32), qg, k_pages, v_pages,
      _scale_rows(k_scales), _scale_rows(v_scales))
    return out.reshape(tokens, heads, d)


@_device_call
def _ragged_paged_attention_pallas(q, k_pages, v_pages, block_tables,
                                   tok_slot, tok_ctx, *, sm_scale,
                                   interpret):
    tokens, heads, d = q.shape
    kv_heads, _, page_size, _ = k_pages.shape
    pages_per_seq = block_tables.shape[1]
    group = heads // kv_heads
    qg = q.reshape(tokens, kv_heads, group, d)

    kernel = functools.partial(
        _ragged_kernel, sm_scale=sm_scale, page_size=page_size,
        pages_per_seq=pages_per_seq, group=group)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(tokens, kv_heads, pages_per_seq),
        in_specs=[
            pl.BlockSpec((1, 1, group, d),
                         lambda t, h, p, slot, ctx, tbl: (t, h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, d),
                         lambda t, h, p, slot, ctx, tbl:
                         (h, tbl[slot[t], p], 0, 0)),
            pl.BlockSpec((1, 1, page_size, d),
                         lambda t, h, p, slot, ctx, tbl:
                         (h, tbl[slot[t], p], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, d),
                               lambda t, h, p, slot, ctx, tbl: (t, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, 128), jnp.float32),
            pltpu.VMEM((group, 128), jnp.float32),
            pltpu.VMEM((group, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tokens, kv_heads, group, d),
                                       q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(tok_slot, jnp.int32), jnp.asarray(tok_ctx, jnp.int32),
      jnp.asarray(block_tables, jnp.int32), qg, k_pages, v_pages)
    return out.reshape(tokens, heads, d)


def _ragged_impl():
    import os
    return os.environ.get("PADDLE_TPU_RAGGED_IMPL", "auto").lower()


def _qblock_eligible(impl, *values):
    """The q-block schedule is built host-side, so it needs concrete
    descriptor/block-table values — under jit tracing the per-token grid
    (whose index maps trace fine) is the escape hatch."""
    if impl in ("token", "pertoken", "xla"):
        return False
    return not any(isinstance(v, jax.core.Tracer) for v in values)


def _ragged_paged_attention_xla(q, k_pages, v_pages, block_tables,
                                tok_slot, tok_ctx, *, sm_scale,
                                k_scales=None, v_scales=None):
    """Vectorized jittable XLA tier: gather each token's sequence pages
    as dense KV (dequantized when int8 row scales are given), then
    masked softmax-attention. O(tokens * S_max) HBM — the explicit
    ``PADDLE_TPU_RAGGED_IMPL=xla`` tier."""
    kv_heads, _, page_size, d = k_pages.shape
    tokens, heads, _ = q.shape
    group = heads // kv_heads
    tbl = jnp.asarray(block_tables, jnp.int32)[jnp.asarray(tok_slot,
                                                           jnp.int32)]
    kg, vg = k_pages[:, tbl], v_pages[:, tbl]
    if k_scales is not None:
        kg = kg.astype(jnp.float32) * k_scales[:, tbl][..., None]
        vg = vg.astype(jnp.float32) * v_scales[:, tbl][..., None]
    # [kv, tokens, pages, slot, d] -> [tokens, kv, S, d]
    ks = jnp.moveaxis(kg, 1, 0).reshape(tokens, kv_heads, -1, d)
    vs = jnp.moveaxis(vg, 1, 0).reshape(tokens, kv_heads, -1, d)
    qb = (q * sm_scale).reshape(tokens, kv_heads, group, d)
    s = jnp.einsum("tkgd,tksd->tkgs", qb.astype(jnp.float32),
                   ks.astype(jnp.float32))
    valid = (jnp.arange(ks.shape[2])[None, :]
             < jnp.asarray(tok_ctx, jnp.int32)[:, None])
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("tkgs,tksd->tkgd", w, vs.astype(jnp.float32))
    return o.reshape(tokens, heads, d).astype(q.dtype)


def ragged_paged_attention(q, k_pages, v_pages, block_tables, seq_slots,
                           q_starts, q_lens, context_lens, *,
                           sm_scale=None, k_scales=None, v_scales=None,
                           value_dim=None, interpret=False):
    """Mixed prefill+decode attention over a shared paged KV cache.

    A LATENT pool is ``k_pages`` [1, num_pages, d, page_size] (a page's
    tokens are its columns) with ``v_pages=None``: every query head attends
    the one row a token, whose first ``value_dim`` values are also the
    values; the result is
    ``[tokens, heads, value_dim]``. It runs on the q-block tier only
    (concrete descriptors).

    q               [tokens, heads, head_dim] — the flat packed batch
    k_pages/v_pages [kv_heads, num_pages, page_size, head_dim]
    block_tables    [slots, pages_per_seq] int32 (unused entries = 0)
    seq_slots       [nseq] int32 — block-table row per sequence
    q_starts        [nseq] int32 — NON-DECREASING span offsets into q
    q_lens          [nseq] int32 — span length (1 = decode; a
                    speculative verify span is the current token plus k
                    drafted tokens, q_len = k+1)
    context_lens    [nseq] int32 — total context incl. this span
    k_scales/v_scales [kv_heads, num_pages, page_size] f32 — per-row
                    dequant scales for int8 pages (None = native pages)
    -> [tokens, heads, head_dim]; rows outside every span are garbage.
    """
    tokens, heads, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    impl = _ragged_impl()
    eligible = _qblock_eligible(impl, seq_slots, q_starts, q_lens,
                                context_lens, block_tables)
    if v_pages is None:
        if not eligible or value_dim is None or k_scales is not None:
            raise NotImplementedError(
                "a latent pool is read by the q-block kernel alone: "
                "concrete descriptors, a value_dim, native pages "
                f"(PADDLE_TPU_RAGGED_IMPL={impl!r})")
        return _ragged_paged_attention_pallas_qblock(
            q, k_pages, None, block_tables, seq_slots, q_starts, q_lens,
            context_lens, sm_scale=sm_scale, interpret=interpret,
            value_dim=int(value_dim))
    if eligible:
        return _ragged_paged_attention_pallas_qblock(
            q, k_pages, v_pages, block_tables, seq_slots, q_starts, q_lens,
            context_lens, sm_scale=sm_scale, interpret=interpret,
            k_scales=k_scales, v_scales=v_scales)
    tok_slot, tok_ctx = _token_descriptors(tokens, seq_slots, q_starts,
                                           q_lens, context_lens)
    if impl == "xla":
        return _ragged_paged_attention_xla(
            q, k_pages, v_pages, block_tables, tok_slot, tok_ctx,
            sm_scale=sm_scale, k_scales=k_scales, v_scales=v_scales)
    if k_scales is not None:
        return _ragged_paged_attention_pallas_quant(
            q, k_pages, v_pages, k_scales, v_scales, block_tables,
            tok_slot, tok_ctx, sm_scale=sm_scale, interpret=interpret)
    return _ragged_paged_attention_pallas(
        q, k_pages, v_pages, block_tables, tok_slot, tok_ctx,
        sm_scale=sm_scale, interpret=interpret)


def ragged_paged_attention_reference(q, k_pages, v_pages, block_tables,
                                     seq_slots, q_starts, q_lens,
                                     context_lens):
    """Dense numpy-style oracle: per sequence, gather its context from
    the pages and run plain causal softmax attention for its span. Rows
    outside every span are zero."""
    import numpy as np

    tokens, heads, d = q.shape
    kv_heads, _, page_size, _ = k_pages.shape
    group = heads // kv_heads
    out = np.zeros((tokens, heads, d), np.float32)
    tbl = np.asarray(block_tables)
    for i in range(len(np.asarray(seq_slots))):
        slot = int(np.asarray(seq_slots)[i])
        qs = int(np.asarray(q_starts)[i])
        ql = int(np.asarray(q_lens)[i])
        ctx = int(np.asarray(context_lens)[i])
        n_pages = -(-ctx // page_size)
        ks = jnp.concatenate([k_pages[:, int(tbl[slot, p])]
                              for p in range(n_pages)], axis=1)[:, :ctx]
        vs = jnp.concatenate([v_pages[:, int(tbl[slot, p])]
                              for p in range(n_pages)], axis=1)[:, :ctx]
        for j in range(ql):
            vis = ctx - ql + j + 1                 # causal inside the span
            qb = q[qs + j].reshape(kv_heads, group, d).astype(jnp.float32)
            s = jnp.einsum("kgd,ksd->kgs", qb,
                           ks[:, :vis].astype(jnp.float32)) / math.sqrt(d)
            w = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("kgs,ksd->kgd", w,
                           vs[:, :vis].astype(jnp.float32))
            out[qs + j] = np.asarray(o.reshape(heads, d))
    return jnp.asarray(out).astype(q.dtype)

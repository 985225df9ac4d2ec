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

One Pallas kernel and one XLA form, chosen by what the entry can
observe:

* concrete descriptors (the serving tick): the in-repo **q-block** kernel
  — block-table-steered dynamic BlockSpec index maps (scalar prefetch in
  SMEM), online-softmax scratch accumulation, the decode kernel's
  streaming recurrence with per-TOKEN (not per-row) context bounds and
  table rows. Compiled by Mosaic on a TPU backend (a compiler error
  propagates), run in interpret mode by the CPU tests;
* descriptors that are ``jit`` tracers: a plain-XLA gather+softmax
  (:func:`_ragged_paged_attention_xla`), because the kernel's job list is
  built on the host. It is also the tests' second reference.

The q-block grid ``(jobs,)`` tiles the flat batch into fixed
``DEFAULT_QBLOCK``-row blocks over the cumulative span offsets and walks
ONE flat host-built job list in block order (:func:`qblock_job_list`: one
(q-block, page, owner-slot, kv-offset) per KV page any sequence in the
block needs; the grid's bound is the list's own length, read on the
device) — one grid step covers a whole block of tokens, every KV head of
it, against one page, so a mixed tick runs few, fat MXU steps, and the
grid holds the jobs that exist: a block's softmax state starts at its
first job and its output is written at its last, and a job without an
owner (a block of padding rows has one) skips the body. A block may
straddle span boundaries: rows past a span's causal bound mask with
-inf, and cross-span keys are steered out with a finite ``BIG_NEG`` so
alien jobs are bitwise no-ops (see ``BIG_NEG``). The latent (one pool,
one KV head) kernel walks the same list.

Unused block-table entries MUST be 0 (a valid page): their scores are
masked by the per-token context bound but the DMA address must be in
range.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...profiler import spans as _spans
from .flash_attention import _jit_unless_interpret
from .paged_attention import NEG_INF, _scale_rows

#: finite cross-span mask for the q-block kernel. The causal bound keeps
#: NEG_INF (= -inf, the decode kernel's mask, on a row's own pages); keys
#: belonging to ANOTHER sequence's job must stay finite:
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

#: q-block rows (tokens per grid step)
DEFAULT_QBLOCK = 8


def _qblock_rows():
    return DEFAULT_QBLOCK


def _token_descriptors(num_tokens, seq_slots, q_starts, q_lens,
                       context_lens):
    """Expand per-sequence ``(slot, q_start, q_len, context_len)``
    descriptors into the per-token arrays the XLA form consumes:
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


#: the q-block kernel's job list reaches the device in an array of one of
#: these lengths (MIN_JOBS x JOBS_STEP^k, MAX_JOBS at the most): the grid's
#: length is the list's own (a dynamic grid bound), so the array's padding
#: is never walked and the buckets can be coarse, which keeps the family of
#: compiled programs small (one a token bucket and job bucket:
#: :func:`job_buckets`). The latent kernel's grid is the padded list itself
#: (a static bound): powers of two from LATENT_MIN_JOBS. MAX_JOBS is what
#: the chip's scalar memory holds of a list (16 bytes a job of 1 MiB, the
#: rest left to the compiler)
MIN_JOBS = 1024
JOBS_STEP = 8
LATENT_MIN_JOBS = 64
MAX_JOBS = 32768


def job_bucket(total, latent=False):
    """The length of the array that carries a list of ``total`` jobs."""
    if total > MAX_JOBS:
        raise ValueError(
            f"a ragged attention call of {total} (q-block, KV page) jobs is "
            f"over the {MAX_JOBS} the q-block kernel's job list can hold: "
            "lower the token budget or max_len, or raise page_size")
    b, step = (LATENT_MIN_JOBS, 2) if latent else (MIN_JOBS, JOBS_STEP)
    while b < total:
        b *= step
    return min(b, MAX_JOBS)


def window_pages(window, q_block, page_size, pages_per_seq):
    """The most pages a (q-block, sequence) pair walks under a ``window``
    (None: the whole table): its rows' bounds lie within ``q_block`` of
    each other, so the keys any of them sees are ``window + q_block - 1``
    neighbours, which touch one page more than they fill."""
    if window is None:
        return int(pages_per_seq)
    return min(int(pages_per_seq),
               (int(window) + int(q_block) - 2) // int(page_size) + 2)


def job_buckets(num_tokens, q_block, max_seqs, pages_per_seq, latent=False):
    """Every :func:`job_bucket` a call over ``num_tokens`` tokens can land
    in, whatever its descriptors: a sequence's span is contiguous, so a
    call of ``b`` q-blocks and at most ``max_seqs`` sequences has at most
    ``b + max_seqs`` (block, sequence) pairs (and at most one a token), each
    of at most ``pages_per_seq`` jobs (a windowed layer's caller gives
    :func:`window_pages`), and at least one job a block. The ladder stops
    at ``MAX_JOBS``: a call beyond it is refused."""
    blocks = -(-int(num_tokens) // q_block)
    most = min(int(num_tokens), blocks + int(max_seqs)) * int(pages_per_seq)
    top = job_bucket(min(most, MAX_JOBS), latent)
    out = [job_bucket(min(blocks, MAX_JOBS), latent)]
    while out[-1] < top:
        out.append(job_bucket(out[-1] + 1, latent))
    return out


def warm_descriptors(num_tokens, jobs, q_block, page_size, pages_per_seq,
                     window=None):
    """Descriptors ``(block_tables, seq_slots, q_starts, q_lens,
    context_lens)`` of a call over ``num_tokens`` tokens whose flat list has
    exactly ``jobs`` jobs (clipped to what that many tokens can hold), for
    warming the compiled program of a job bucket through the public op:
    single-token spans of sequences of their own, the first token of every
    q-block among them, over tables that point at page 0. Under a
    ``window`` (a multiple of the page) a single row walks at most
    ``window // page_size + 1`` pages, and its context is the shortest
    that needs its pages, so that none of them falls behind the window."""
    t, p = int(num_tokens), int(pages_per_seq)
    if window is not None:
        p = min(p, int(window) // int(page_size) + 1)
    pages = np.zeros(t, np.int64)
    pages[::q_block] = 1                    # no q-block without a job
    room = p - pages
    left = int(np.clip(jobs, pages.sum(), t * p)) - int(pages.sum())
    filled = np.minimum(np.cumsum(room), left)
    pages += np.diff(filled, prepend=0)
    rows = np.flatnonzero(pages).astype(np.int32)
    ctx = pages[rows] * page_size
    if window is not None:
        ctx -= page_size - 1
    return (np.zeros((t, p), np.int32), rows, rows,
            np.ones(len(rows), np.int32), ctx.astype(np.int32))


def qblock_job_list(num_tokens, seq_slots, q_starts, q_lens, context_lens,
                    block_tables, q_block, page_size, window=None):
    """Host-side schedule of both q-block kernels, vectorized: the flat
    packed batch is tiled into ``q_block``-row blocks over the cumulative
    span offsets, and the "jobs" are one (q-block, physical page, owner
    slot, kv offset) a KV page a sequence of a q-block still needs, as ONE
    flat list in block order, so the grid walks the jobs that exist and not
    ``blocks x the longest block's jobs``: 8 decode rows at 350 tokens of
    context are a block of 176 jobs, 8 rows of a prefill chunk one of 2 to
    90. Within a block the sequences stand in order of first appearance and
    a sequence's pages ascend, so each row meets its own pages in
    ascending order, as the decode kernel does.

    ``window`` (a sliding-window layer: a row at position ``i`` sees keys
    ``j > i - window``): a (q-block, sequence) pair's pages start at the
    page of the first key its FIRST row in the block can see, and no job
    is emitted for a page wholly behind it. None: the list of a full
    layer, as ever.

    Sentinels: rows outside every span (bucket and block padding) get slot
    -1 / ctx 0 and own no job; a block without jobs gets one that matches
    nothing (slot -2, page 0), so that its output is written. -1 and -2
    never match each other, and the kernels skip a job of slot -2.

    Returns ``(row_slot [B*q_block], row_ctx [B*q_block], jobs [4, n])``
    int32 numpy; ``jobs`` rows are (q-block, physical page, owner slot,
    kv offset), and ``n`` is the list's own length: no padding."""
    return _qblock_jobs(num_tokens, seq_slots, q_starts, q_lens, context_lens,
                        block_tables, q_block, page_size, window)[:3]


def _qblock_jobs(num_tokens, seq_slots, q_starts, q_lens, context_lens,
                 block_tables, q_block, page_size, window=None):
    """:func:`qblock_job_list` and, fourth, how many jobs the same call
    would have walked with no lower bound (the list's own length where
    ``window`` is None)."""
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
    if window is None:
        first_page = np.zeros(len(pair_span), np.int64)
    else:
        # ... and start at the first key the span's FIRST row in the block
        # sees: a row whose bound is ``c`` sees positions ``c - window ..``
        first_row = np.maximum(qs[pair_span], pair_block * qb)
        cmin = (cl - ql)[pair_span] + (first_row - qs[pair_span]) + 1
        first_page = np.minimum(np.maximum(cmin - int(window), 0)
                                // page_size, n_pages - 1)
    pair_slot = ss[pair_span]
    # a slot met twice in one block (two spans of one sequence) is walked
    # once, from its nearest lower bound as far as its longest bound
    key = pair_block * (int(tbl.shape[0]) + 1) + pair_slot
    uniq, inv = np.unique(key, return_inverse=True)
    if len(uniq) != len(key):
        pages = np.zeros(len(uniq), np.int64)
        np.maximum.at(pages, inv, n_pages)
        lows = np.full(len(uniq), pages_per_seq, np.int64)
        np.minimum.at(lows, inv, first_page)
        first = np.zeros(len(uniq), np.int64)
        first[inv[::-1]] = np.arange(len(key))[::-1]
        keep = np.argsort(first)            # order of first appearance
        pair_block, pair_slot, n_pages, first_page = (
            pair_block[first[keep]], pair_slot[first[keep]], pages[keep],
            lows[keep])
    empty = np.setdiff1d(np.arange(nblocks), pair_block)
    pair_block = np.concatenate([pair_block, empty])
    pair_slot = np.concatenate([pair_slot, np.full(len(empty), -2)])
    n_pages = np.concatenate([n_pages, np.ones(len(empty), np.int64)])
    first_page = np.concatenate([first_page, np.zeros(len(empty), np.int64)])
    order = np.argsort(pair_block, kind="stable")
    pair_block, pair_slot, n_pages, first_page = (
        pair_block[order], pair_slot[order], n_pages[order],
        first_page[order])

    unwindowed = int(n_pages.sum())
    n_pages = n_pages - first_page
    total = int(n_pages.sum())
    jobs = np.empty((4, total), np.int32)
    page_idx = np.arange(total) - np.repeat(np.cumsum(n_pages) - n_pages,
                                            n_pages)
    if window is not None:
        page_idx = page_idx + np.repeat(first_page, n_pages)
    slot = np.repeat(pair_slot, n_pages)
    jobs[0] = np.repeat(pair_block, n_pages)
    jobs[1] = np.where(slot >= 0, tbl[np.maximum(slot, 0), page_idx], 0)
    jobs[2] = slot
    jobs[3] = page_idx * page_size
    return row_slot, row_ctx, jobs, unwindowed


def _padded_jobs(jobs, length, count=False):
    """``jobs`` [4, n] in an array of ``length`` columns; the padding is
    jobs that match nothing (slot -2, page 0) on the last q-block.
    ``count`` (``n < length``: the caller adds a column to the bucket): the
    last column, which is then never a job, says ``n`` in its kv-offset
    entry: the grid's bound for a kernel that reads it on the device (one
    array to move, not two; a fifth row would double the array in the
    chip's scalar memory, which tiles rows by 8)."""
    n = jobs.shape[1]
    out = np.zeros((4, length), np.int32)
    out[:, :n] = jobs
    out[0, n:] = jobs[0, -1]
    out[2, n:] = -2
    if count:
        assert n < length
        out[3, -1] = n
    return out


def latent_job_list(num_tokens, seq_slots, q_starts, q_lens, context_lens,
                    block_tables, q_block, page_size):
    """:func:`qblock_job_list` for the latent kernel, whose grid is the
    list as it arrives: padded to a power of two (``LATENT_MIN_JOBS`` at
    the least) with jobs that match nothing, which the kernel skips."""
    row_slot, row_ctx, jobs = qblock_job_list(
        num_tokens, seq_slots, q_starts, q_lens, context_lens, block_tables,
        q_block, page_size)
    return row_slot, row_ctx, _padded_jobs(
        jobs, job_bucket(jobs.shape[1], latent=True))


def _qblock_masked_scores(s, kv_start, jslot, row_slot, row_ctx,
                          window=None):
    """Causal bound with NEG_INF (the decode kernel's mask, on a row's
    own pages), then the whole row to finite BIG_NEG wherever
    the row's sequence does not own this job's page. ``window`` (static):
    the keys behind ``row_ctx - window`` as well, with the finite mask: a
    later row of a q-block sees nothing in the pages that only the block's
    first row still reaches, and a first page of -inf alone would leave
    NaN in its running maximum (see ``BIG_NEG``)."""
    pos = kv_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < row_ctx, s, NEG_INF)
    if window is not None:
        s = jnp.where(pos >= row_ctx - window, s, BIG_NEG)
    return jnp.where(row_slot == jslot, s, BIG_NEG)


def _job_walk(jobs_ref, num_jobs, m_ref, l_ref, acc_ref, step, finalize,
              skip):
    """One grid step of a walk over the first ``num_jobs`` (a Python
    number or a scalar read in the kernel) of the flat job list
    ``jobs_ref``: the softmax state starts anew at a q-block's first job
    and the output is written at its last (a block's jobs are neighbours
    in the list); ``step(jslot, kv_start)`` runs the job. ``skip``: a job
    without an owner (slot -2) costs the grid step alone; worth its branch
    where the list is padded (else such a job is the one of a q-block of
    padding rows, and running it is an exact no-op on rows nobody reads)."""
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
    if skip:
        pl.when(jslot >= 0)(lambda: step(jslot, jobs_ref[3, j]))
    else:
        step(jslot, jobs_ref[3, j])
    pl.when(last)(finalize)


def _qblock_kernel(jobs_ref, rows_ref, q_ref, k_ref, v_ref, *rest, sm_scale,
                   quant, unroll, window=None):
    """One grid step: a q-block's rows (``q_block`` tokens x all query
    heads) against one KV page, EVERY KV head of it: the page block is
    ``[kv_heads, 1, page_size, d]`` and each head runs the decode
    kernel's two 2-D float32 products and its float32 softmax on its own
    ``[q_block*group, d]`` rows and its own slice of the scratch arrays
    (a loop over the heads, unrolled for the chip, so that the heads'
    chains interleave). ``jobs_ref`` [4, J + 1]: the list and, at ``[3, J]``,
    its length. ``quant`` (int8 KV): the per-row float32 scales arrive as
    ``[kv_heads, 1, 1, page_size]`` lane vectors and scale the scores /
    weights around the int8 products (see ``_decode_kernel_quant``)."""
    if quant:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest

    def step(jslot, kv_start):
        row_slot = rows_ref[0, 0][:, :1]               # [Qg, 1]
        row_ctx = rows_ref[1, 0][:, :1]

        def head(h, carry):
            q = q_ref[0, h].astype(jnp.float32)        # [Qg, d]
            k = k_ref[h, 0].astype(jnp.float32)        # [page_size, d]
            v = v_ref[h, 0].astype(jnp.float32)
            scale = ks_ref[h, 0] * sm_scale if quant else sm_scale
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = _qblock_masked_scores(s, kv_start, jslot, row_slot, row_ctx,
                                      window)
            m_prev = m_ref[h][:, :1]                   # [Qg, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            w = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_ref[h][:, :1] * corr + jnp.sum(w, -1, keepdims=True)
            pv = jax.lax.dot_general(                  # [Qg, d]
                w * vs_ref[h, 0] if quant else w, v,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc_ref[h] = acc_ref[h] * corr + pv
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])
            return carry

        jax.lax.fori_loop(0, k_ref.shape[0], head, 0, unroll=unroll)

    def finalize():
        l = jnp.maximum(l_ref[...][:, :, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)

    _job_walk(jobs_ref, jobs_ref[3, jobs_ref.shape[1] - 1], m_ref, l_ref,
              acc_ref, step, finalize, skip=False)


def _ragged_paged_attention_pallas_qblock(q, k_pages, v_pages,
                                          block_tables, seq_slots,
                                          q_starts, q_lens, context_lens,
                                          *, sm_scale, interpret,
                                          k_scales=None, v_scales=None,
                                          q_block=None, value_dim=None,
                                          window=None):
    """The q-block kernel's entry: grid ``(jobs,)`` over the flat packed
    batch — one grid step covers ``q_block`` tokens (all their heads)
    against one KV page, and the grid is the list of such (q-block, page)
    jobs that exist (:func:`qblock_job_list`). Requires concrete
    descriptors (the job list is built host-side).

    ``v_pages is None`` is the LATENT call: one pool of one KV head whose
    row a token holds keys and values alike (``value_dim``: the values are
    that prefix of the row). The host half (this entry, its spans, then
    with ``latent=1``, the job list) is shared; the device half is a
    wrapper of its own name (:func:`_latent_qblock_device` beside
    :func:`_qblock_device`), so a device trace tells the two kernels
    apart. The ``attn/qblock`` span says how far the list fills the grid:
    ``jobs`` (the grid steps of one KV head's walk), ``real_jobs`` (those
    with an owner), ``steps`` (grid steps the call runs, over all grid
    axes) and ``blocks``; a windowed layer's call also ``window`` and
    ``jobs_without_window`` (what the same call would have walked with no
    lower bound)."""
    tokens = q.shape[0]
    qb = q_block or _qblock_rows()
    latent = v_pages is None
    page_size = k_pages.shape[3 if latent else 2]   # latent: tokens = columns
    if latent and window is not None:
        raise NotImplementedError("the latent kernel takes no window")
    args = {"latent": 1} if latent else {}
    with _spans.span("attn/qblock", **args) as sp:
        with _spans.span("attn/qblock_schedule", **args):
            if latent:
                row_slot, row_ctx, jobs = latent_job_list(
                    tokens, seq_slots, q_starts, q_lens, context_lens,
                    block_tables, qb, page_size)
            else:
                row_slot, row_ctx, jobs, unwindowed = _qblock_jobs(
                    tokens, seq_slots, q_starts, q_lens, context_lens,
                    block_tables, qb, page_size, window)
        n = jobs.shape[1]                   # the grid, either kernel's
        if sp is not _spans.NULL:           # counted only where recorded
            sp.set(jobs=n, blocks=row_slot.shape[0] // qb,
                   real_jobs=int((jobs[2] >= 0).sum()), steps=n)
            if window is not None:
                sp.set(window=int(window), jobs_without_window=unwindowed)
        if latent:
            return _latent_call(jobs, row_slot, row_ctx, q, k_pages,
                                sm_scale, interpret, value_dim, qb)
        return _qblock_device(
            _padded_jobs(jobs, job_bucket(n) + 1, count=True),
            np.stack([row_slot, row_ctx]).reshape(2, -1, qb), q, k_pages,
            v_pages, k_scales, v_scales, sm_scale=sm_scale,
            interpret=interpret,
            window=None if window is None else int(window))


def _spread_rows(a, rows_a_token):
    """Row metadata ``a`` [..., B, q_block], one value a token, spread over
    the ``rows_a_token`` kernel rows of a token and over 128 lanes, so that
    the kernel can slice ``[:, :1]`` (the softmax scratch's layout trick).
    On the device: host-side these are half a megabyte a layer at 4 rows a
    token and 16 MB at 64."""
    a = jnp.repeat(jnp.asarray(a, jnp.int32), rows_a_token, axis=-1)
    return jnp.broadcast_to(a[..., None], a.shape + (128,))


@_jit_unless_interpret(static_argnames=("sm_scale", "interpret", "window"))
def _qblock_device(jobs, rows, q, k_pages, v_pages, k_scales, v_scales, *,
                   sm_scale, interpret, window=None):
    """Device half of the q-block tier: the schedule arrives as two arrays
    (``jobs`` [4, J + 1], scalar-prefetched: the list and at ``[3, J]`` its
    own length; ``rows`` [2, B, q_block]: slot and context bound, one value
    a token). The grid is ``(jobs[3, J],)``, a bound read on the device, so
    one compiled program serves every tick of a (tokens,
    :func:`job_bucket`) shape and walks no padding. ``window`` is static:
    a sliding-window layer's program masks the keys behind it too."""
    tokens, heads, d = q.shape
    kv_heads, _, page_size, _ = k_pages.shape
    group = heads // kv_heads
    _, nblocks, qb = rows.shape
    t_pad, qg_rows = nblocks * qb, qb * group

    qp = jnp.pad(q, ((0, t_pad - tokens), (0, 0), (0, 0)))
    qg = qp.reshape(nblocks, qb, kv_heads, group, d).transpose(
        0, 2, 1, 3, 4).reshape(nblocks, kv_heads, qg_rows, d)

    quant = k_scales is not None
    kernel = functools.partial(_qblock_kernel, sm_scale=sm_scale,
                               quant=quant, unroll=not interpret,
                               window=window)
    page_spec = pl.BlockSpec((kv_heads, 1, page_size, d),
                             lambda j, jobs: (0, jobs[1, j], 0, 0))
    scale_spec = pl.BlockSpec((kv_heads, 1, 1, page_size),
                              lambda j, jobs: (0, jobs[1, j], 0, 0))
    row_spec = pl.BlockSpec((2, 1, qg_rows, 128),
                            lambda j, jobs: (0, jobs[0, j], 0, 0))
    block_spec = pl.BlockSpec((1, kv_heads, qg_rows, d),
                              lambda j, jobs: (jobs[0, j], 0, 0, 0))
    in_specs = [row_spec, block_spec, page_spec, page_spec]
    operands = [_spread_rows(rows, group), qg, k_pages, v_pages]
    if quant:
        in_specs += [scale_spec, scale_spec]
        operands += [_scale_rows(k_scales), _scale_rows(v_scales)]
    jobs = jnp.asarray(jobs, jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(jobs[3, -1],),
        in_specs=in_specs,
        out_specs=block_spec,
        scratch_shapes=[
            pltpu.VMEM((kv_heads, qg_rows, 128), jnp.float32),
            pltpu.VMEM((kv_heads, qg_rows, 128), jnp.float32),
            pltpu.VMEM((kv_heads, qg_rows, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nblocks, kv_heads, qg_rows, d),
                                       q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jobs, *operands)
    out = out.reshape(nblocks, kv_heads, qb, group, d).transpose(
        0, 2, 1, 3, 4).reshape(t_pad, heads, d)
    return out[:tokens]


def _latent_kernel(jobs_ref, rs_ref, rc_ref, q_ref, kv_ref, o_ref, m_ref,
                   l_ref, acc_ref, *, sm_scale, num_jobs, value_dim):
    """One grid step: a q-block's rows (``q_block`` tokens x all heads)
    against one page of latent rows (``[d, page_size]``: a token a column,
    as the pool keeps them), read once for keys and values. The
    products take the pool's type as their operands' (bf16 x bf16 products
    are exact in the float32 accumulator) and the softmax runs in
    float32; the weights enter the second product in the pool's type."""
    def step(jslot, kv_start):
        q = q_ref[0]                                # [rows, d]
        kv = kv_ref[0, 0]                           # [d, page_size]
        s = jax.lax.dot_general(
            q, kv, (((1,), (0,)), ((), ())), precision=_DEFAULT,
            preferred_element_type=jnp.float32) * sm_scale
        s = _qblock_masked_scores(s, kv_start, jslot,
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

    def finalize():
        l = jnp.maximum(l_ref[...][:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)

    _job_walk(jobs_ref, num_jobs, m_ref, l_ref, acc_ref, step, finalize,
              skip=True)


def _latent_qblock_device(jobs, row_slot, row_ctx, q, kv_pages, sm_scale,
                          interpret, value_dim, q_block):
    """Device half of the latent q-block tier: ``jobs`` [4, J] is scalar-
    prefetched, the rows' slot and context bound arrive one a TOKEN and are
    spread over the heads here, on the device."""
    tokens, heads, d = q.shape
    _, _, _, page_size = kv_pages.shape
    num_jobs = jobs.shape[1]
    t_pad = row_slot.shape[0]
    nblocks, rows = t_pad // q_block, q_block * heads

    qg = jnp.pad(q, ((0, t_pad - tokens), (0, 0), (0, 0))).reshape(
        nblocks, rows, d)

    def spread(a):
        return _spread_rows(jnp.reshape(a, (nblocks, q_block)), heads)

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


def _ragged_paged_attention_xla(q, k_pages, v_pages, block_tables,
                                tok_slot, tok_ctx, *, sm_scale,
                                k_scales=None, v_scales=None, window=None):
    """Vectorized jittable XLA tier: gather each token's sequence pages
    as dense KV (dequantized when int8 row scales are given), then
    masked softmax-attention. O(tokens * S_max) HBM: what runs where the
    descriptors are tracers (the q-block kernel's job list is built on
    the host), and the tests' second reference."""
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
    key_pos = jnp.arange(ks.shape[2])[None, :]
    ctx = jnp.asarray(tok_ctx, jnp.int32)[:, None]
    valid = key_pos < ctx
    if window is not None:
        valid &= key_pos >= ctx - window
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("tkgs,tksd->tkgd", w, vs.astype(jnp.float32))
    return o.reshape(tokens, heads, d).astype(q.dtype)


def ragged_paged_attention(q, k_pages, v_pages, block_tables, seq_slots,
                           q_starts, q_lens, context_lens, *,
                           sm_scale=None, k_scales=None, v_scales=None,
                           value_dim=None, interpret=False, window=None):
    """Mixed prefill+decode attention over a shared paged KV cache.

    A LATENT pool is ``k_pages`` [1, num_pages, d, page_size] (a page's
    tokens are its columns) with ``v_pages=None``: every query head attends
    the one row a token, whose first ``value_dim`` values are also the
    values; the result is
    ``[tokens, heads, value_dim]``. It is read by the q-block kernel
    alone (concrete descriptors).

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
    window          None, or a sliding-window layer's length: the query
                    at position ``i`` sees keys ``i - window < j <= i``
                    (``window`` keys with its own; the Mistral
                    convention). Pages wholly behind every row's window
                    are neither walked nor read, so their block-table
                    entries may be 0 (``SlotPagedKVCache`` releases them)
    -> [tokens, heads, head_dim]; rows outside every span are garbage.

    Concrete descriptors and block tables run the q-block kernel, whose
    job list is built on the host; under ``jit`` tracing they are tracers
    and the jittable XLA form answers instead.
    """
    tokens, heads, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    traced = any(isinstance(v, jax.core.Tracer) for v in (
        seq_slots, q_starts, q_lens, context_lens, block_tables))
    if v_pages is None:
        if traced or value_dim is None or k_scales is not None \
                or window is not None:
            raise NotImplementedError(
                "a latent pool is read by the q-block kernel alone: "
                "concrete descriptors (not jit tracers), a value_dim, "
                "native pages, no window")
        return _ragged_paged_attention_pallas_qblock(
            q, k_pages, None, block_tables, seq_slots, q_starts, q_lens,
            context_lens, sm_scale=sm_scale, interpret=interpret,
            value_dim=int(value_dim))
    if not traced:
        return _ragged_paged_attention_pallas_qblock(
            q, k_pages, v_pages, block_tables, seq_slots, q_starts, q_lens,
            context_lens, sm_scale=sm_scale, interpret=interpret,
            k_scales=k_scales, v_scales=v_scales, window=window)
    tok_slot, tok_ctx = _token_descriptors(tokens, seq_slots, q_starts,
                                           q_lens, context_lens)
    return _ragged_paged_attention_xla(
        q, k_pages, v_pages, block_tables, tok_slot, tok_ctx,
        sm_scale=sm_scale, k_scales=k_scales, v_scales=v_scales,
        window=window)


def ragged_paged_attention_reference(q, k_pages, v_pages, block_tables,
                                     seq_slots, q_starts, q_lens,
                                     context_lens, window=None):
    """Dense numpy-style oracle: per sequence, gather its context from
    the pages and run plain causal softmax attention for its span (over
    the last ``window`` keys a query where a window is given). Rows
    outside every span are zero."""
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
            low = 0 if window is None else max(vis - int(window), 0)
            qb = q[qs + j].reshape(kv_heads, group, d).astype(jnp.float32)
            s = jnp.einsum("kgd,ksd->kgs", qb,
                           ks[:, low:vis].astype(jnp.float32)) / math.sqrt(d)
            w = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("kgs,ksd->kgd", w,
                           vs[:, low:vis].astype(jnp.float32))
            out[qs + j] = np.asarray(o.reshape(heads, d))
    return jnp.asarray(out).astype(q.dtype)

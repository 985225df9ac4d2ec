"""Paged-attention decode kernel (reference: the serving attention tier —
``paddle/phi/kernels/fusion/gpu/block_multihead_attention`` /
``fused_multi_transformer``'s paged KV cache; SURVEY.md §2.2 "Incubate"
serving block, VERDICT.md round-1 item 10).

TPU-native design: the KV cache lives in HBM as fixed-size pages in
**kv-head-major** layout ``[kv_heads, num_pages, page_size, head_dim]`` —
each (head, page) block is a contiguous, tile-aligned ``[page_size, d]``
slab, so a page fetch is one aligned HBM→VMEM DMA and every in-kernel dot
is a plain 2-D MXU matmul (no batched dot_general, which Mosaic lowers
poorly). A per-sequence block table maps logical context positions to
pages (vLLM layout). One decode step attends ONE query token per sequence
over its paged context.

Tiers (``PADDLE_TPU_PAGED_IMPL``), mirroring how the reference wires the
vendored FA2 library as a phi kernel (SURVEY.md §2.1 "Flash-attention
integration"):

* ``auto`` / ``inrepo`` (default): the in-repo kernel below — grid
  ``(batch, kv_head, pages)``, block-table-steered dynamic BlockSpec
  index maps (scalar prefetch in SMEM), online-softmax scratch
  accumulation, the same streaming recurrence as the flash kernel.
  Compiled by Mosaic on a TPU backend (a compiler error propagates),
  run in interpret mode by the CPU tests;
* ``jax``: delegate to ``jax.experimental.pallas.ops.tpu.paged_attention``
  (manual double-buffered page DMA, megacore support; native pages only);
* ``xla``: a plain-XLA gather + masked softmax, no kernel at all.

Unused block-table entries MUST be 0 (a valid page): their scores are
masked by ``context_lens`` but the DMA address must be in range.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _jit_unless_interpret

NEG_INF = float("-inf")


#: kernel wrappers here take (*arrays, sm_scale=, interpret=): compiled
#: path jitted, interpret path eager (see flash_attention)
_device_call = _jit_unless_interpret(static_argnames=("sm_scale", "interpret"))


def _decode_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, sm_scale, page_size,
                   pages_per_seq, group):
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ctx = lens_ref[b]
    q = q_ref[0, 0].astype(jnp.float32)            # [group, d]
    k = k_ref[0, 0].astype(jnp.float32)            # [page_size, d]
    v = v_ref[0, 0].astype(jnp.float32)
    # s[g, ps] — one plain 2-D MXU dot
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


def _decode_kernel_quant(tables_ref, lens_ref, q_ref, k_ref, v_ref, ks_ref,
                         vs_ref, o_ref, m_ref, l_ref, acc_ref, *, sm_scale,
                         page_size, pages_per_seq, group):
    """int8-KV variant of :func:`_decode_kernel`: the page blocks arrive
    as int8 rows plus one fp32 scale per (page, slot) row, so the fp32
    pages never exist in HBM. The scales ride as a ``[1, page_size]``
    lane vector (see :func:`_scale_rows`) and are applied AFTER the K dot
    and BEFORE the V dot — ``(q·kq_j)·s_j == q·(kq_j·s_j)`` — which
    scales ``[group, page_size]`` scores instead of ``[page_size, d]``
    pages and needs no in-kernel transpose."""
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ctx = lens_ref[b]
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


def _scale_rows(scales):
    """``[kv_heads, num_pages, page_size]`` row scales as
    ``[kv_heads, num_pages, 1, page_size]``: a per-page block of it,
    ``(1, 1, 1, page_size)``, has its last two dims EQUAL to the array's,
    which is what the TPU compiler asks of a block narrower than an
    (8, 128) tile — ``(1, 1, page_size)`` over the 3-D array is refused."""
    kv_heads, num_pages, page_size = scales.shape
    return jnp.asarray(scales, jnp.float32).reshape(
        kv_heads, num_pages, 1, page_size)


@_device_call
def _paged_attention_pallas_quant(q, k_pages, v_pages, k_scales, v_scales,
                                  block_tables, context_lens, *, sm_scale,
                                  interpret):
    batch, heads, d = q.shape
    kv_heads, _, page_size, _ = k_pages.shape
    pages_per_seq = block_tables.shape[1]
    group = heads // kv_heads
    qg = q.reshape(batch, kv_heads, group, d)

    kernel = functools.partial(
        _decode_kernel_quant, sm_scale=sm_scale, page_size=page_size,
        pages_per_seq=pages_per_seq, group=group)
    page_spec = pl.BlockSpec((1, 1, page_size, d),
                             lambda b, h, p, tbl, ln: (h, tbl[b, p], 0, 0))
    scale_spec = pl.BlockSpec((1, 1, 1, page_size),
                              lambda b, h, p, tbl, ln: (h, tbl[b, p], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch, kv_heads, pages_per_seq),
        in_specs=[
            pl.BlockSpec((1, 1, group, d),
                         lambda b, h, p, tbl, ln: (b, h, 0, 0)),
            page_spec, page_spec, scale_spec, scale_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, group, d),
                               lambda b, h, p, tbl, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, 128), jnp.float32),
            pltpu.VMEM((group, 128), jnp.float32),
            pltpu.VMEM((group, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, kv_heads, group, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32),
      jnp.asarray(context_lens, jnp.int32), qg, k_pages, v_pages,
      _scale_rows(k_scales), _scale_rows(v_scales))
    return out.reshape(batch, heads, d)


@_device_call
def _paged_attention_pallas(q, k_pages, v_pages, block_tables, context_lens,
                            *, sm_scale, interpret):
    batch, heads, d = q.shape
    kv_heads, _, page_size, _ = k_pages.shape
    pages_per_seq = block_tables.shape[1]
    group = heads // kv_heads
    qg = q.reshape(batch, kv_heads, group, d)

    kernel = functools.partial(
        _decode_kernel, sm_scale=sm_scale, page_size=page_size,
        pages_per_seq=pages_per_seq, group=group)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch, kv_heads, pages_per_seq),
        in_specs=[
            pl.BlockSpec((1, 1, group, d),
                         lambda b, h, p, tbl, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, d),
                         lambda b, h, p, tbl, ln: (h, tbl[b, p], 0, 0)),
            pl.BlockSpec((1, 1, page_size, d),
                         lambda b, h, p, tbl, ln: (h, tbl[b, p], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, d),
                               lambda b, h, p, tbl, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, 128), jnp.float32),
            pltpu.VMEM((group, 128), jnp.float32),
            pltpu.VMEM((group, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, kv_heads, group, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32),
      jnp.asarray(context_lens, jnp.int32), qg, k_pages, v_pages)
    return out.reshape(batch, heads, d)


def paged_attention(q, k_pages, v_pages, block_tables, context_lens, *,
                    sm_scale=None, k_scales=None, v_scales=None,
                    interpret=False):
    """One-token decode attention over a paged KV cache.

    q              [batch, heads, head_dim]
    k_pages/v_pages [kv_heads, num_pages, page_size, head_dim]
    block_tables   [batch, pages_per_seq] int32 (unused entries = 0)
    context_lens   [batch] int32 — tokens already in context (incl. this one)
    k_scales/v_scales [kv_heads, num_pages, page_size] f32 — per-row
                   dequant scales for int8 pages (None = native pages)
    -> [batch, heads, head_dim]
    """
    batch, heads, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    impl = "inrepo"
    if not interpret and jax.default_backend() == "tpu":
        import os
        impl = os.environ.get("PADDLE_TPU_PAGED_IMPL", "auto").lower()
    if impl == "xla":
        return _paged_attention_xla(
            q, k_pages, v_pages, block_tables, context_lens,
            sm_scale=sm_scale, k_scales=k_scales, v_scales=v_scales)
    if k_scales is not None:
        if impl == "jax":
            raise ValueError(
                "PADDLE_TPU_PAGED_IMPL=jax has no int8-KV dequant hook; "
                "use auto/inrepo or xla with kv_dtype=int8")
        return _paged_attention_pallas_quant(
            q, k_pages, v_pages, k_scales, v_scales, block_tables,
            context_lens, sm_scale=sm_scale, interpret=interpret)
    if impl == "jax":
        from jax.experimental.pallas.ops.tpu.paged_attention import (
            paged_attention as _jax_paged)
        pages_per_seq = block_tables.shape[1]
        ppcb = next(n for n in (8, 4, 2, 1) if pages_per_seq % n == 0)
        # the production kernel applies no softmax scale: fold into q
        return _jax_paged(
            (q * sm_scale).astype(q.dtype), k_pages, v_pages,
            jnp.asarray(context_lens, jnp.int32),
            jnp.asarray(block_tables, jnp.int32),
            pages_per_compute_block=ppcb)
    return _paged_attention_pallas(q, k_pages, v_pages, block_tables,
                                   context_lens, sm_scale=sm_scale,
                                   interpret=interpret)


def _paged_attention_xla(q, k_pages, v_pages, block_tables, context_lens,
                         *, sm_scale, k_scales=None, v_scales=None):
    """Vectorized jittable XLA decode attention over the paged cache: one
    gather materializes each sequence's pages as dense KV (dequantized
    when int8 row scales are given), then masked softmax-attention.
    O(batch·S_max) HBM for the gathered KV — the explicit
    ``PADDLE_TPU_PAGED_IMPL=xla`` tier, never reached unannounced."""
    kv_heads, _, page_size, d = k_pages.shape
    batch, heads, _ = q.shape
    group = heads // kv_heads
    kg, vg = k_pages[:, block_tables], v_pages[:, block_tables]
    if k_scales is not None:
        kg = kg.astype(jnp.float32) * k_scales[:, block_tables][..., None]
        vg = vg.astype(jnp.float32) * v_scales[:, block_tables][..., None]
    # [kv_heads, batch, pages_per_seq, page_size, d] -> [b, kv, S, d]
    ks = jnp.moveaxis(kg, 1, 0).reshape(batch, kv_heads, -1, d)
    vs = jnp.moveaxis(vg, 1, 0).reshape(batch, kv_heads, -1, d)
    qb = (q * sm_scale).reshape(batch, kv_heads, group, d)
    s = jnp.einsum("bkgd,bksd->bkgs", qb.astype(jnp.float32),
                   ks.astype(jnp.float32))
    valid = (jnp.arange(ks.shape[2])[None, :]
             < jnp.asarray(context_lens, jnp.int32)[:, None])
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bksd->bkgd", w, vs.astype(jnp.float32))
    return o.reshape(batch, heads, d).astype(q.dtype)


def paged_attention_reference(q, k_pages, v_pages, block_tables,
                              context_lens):
    """Dense numpy-style oracle for tests (kv-major page layout)."""
    batch, heads, d = q.shape
    kv_heads, _, page_size, _ = k_pages.shape
    group = heads // kv_heads
    outs = []
    for b in range(batch):
        ctx = int(context_lens[b])
        n_pages = -(-ctx // page_size)
        ks = jnp.concatenate([k_pages[:, int(block_tables[b, p])]
                              for p in range(n_pages)], axis=1)[:, :ctx]
        vs = jnp.concatenate([v_pages[:, int(block_tables[b, p])]
                              for p in range(n_pages)], axis=1)[:, :ctx]
        qb = q[b].reshape(kv_heads, group, d).astype(jnp.float32)
        s = jnp.einsum("kgd,ksd->kgs", qb, ks.astype(jnp.float32))
        s = s / math.sqrt(d)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgs,ksd->kgd", w, vs.astype(jnp.float32))
        outs.append(o.reshape(heads, d))
    return jnp.stack(outs).astype(q.dtype)

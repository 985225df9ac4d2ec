"""Flash attention as Pallas TPU kernels (fwd + bwd).

Reference analogue: the FA2 CUDA kernels Paddle vendors and wires as phi
kernels (``paddle/phi/kernels/gpu/flash_attn_kernel``, ``third_party/flashattn``
— SURVEY.md §2.1), surfaced through
``paddle.nn.functional.scaled_dot_product_attention``. On TPU the same tiling
idea maps onto Pallas/Mosaic: the grid iterates KV blocks sequentially per
(batch, head, Q-block) with online-softmax state (m, l, acc) carried in VMEM
scratch, so logits are never materialized in HBM — O(seq) memory like FA2.

Extras beyond a plain FA port, needed by the ring-attention (context-parallel)
layer (SURVEY.md §5.7):

* ``q_offset`` / ``kv_offset`` runtime scalars (SMEM) give each block's global
  position, so causal masking stays exact when Q and KV are shards of a longer
  sequence rotating around the 'sep'/cp mesh axis.
* the forward also returns the per-row logsumexp (``lse``) so partial results
  from different KV shards merge with the standard online-softmax combine —
  the same contract FA2 exposes via ``softmax_lse`` for PaddleNLP's
  ``RingFlashAttention``.

Layouts: public API is Paddle's flash-attn layout ``[batch, seq, heads, dim]``;
kernels run in ``[batch, heads, seq, dim]``. GQA is supported by mapping each
query head to its KV group in the BlockSpec index map (no materialized
repeats).
"""
from __future__ import annotations

import functools
import inspect
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(-1e30)   # large-negative instead of -inf: keeps exp()/where() NaN-free

# Tunable via env for the MFU sweep (BASELINE.md): block sizes set the
# VMEM working set vs grid-parallelism trade on the MXU — 128 is the safe
# default; 256/512 on Q can lift arithmetic intensity at long seq.
import os as _os

DEFAULT_BLOCK_Q = int(_os.environ.get("PADDLE_TPU_FA_BLOCK_Q", "128"))
DEFAULT_BLOCK_K = int(_os.environ.get("PADDLE_TPU_FA_BLOCK_K", "128"))


def _cdiv(a, b):
    return (a + b - 1) // b


# ---------------------------------------------------------------------------
# Reference (pure XLA) — also the numerical oracle for tests
# ---------------------------------------------------------------------------

def mha_reference(q, k, v, causal=True, sm_scale=None, q_offset=0,
                  kv_offset=0, with_lse=False):
    """Plain-XLA attention in kernel layout [b, h, s, d] (GQA-aware).

    Returns ``out`` or ``(out, lse)``; lse is fp32 [b, h, sq].
    """
    b, hq, sq, d = q.shape
    hk, sk = k.shape[1], k.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if hk != hq:
        # GQA via grouped einsum — no materialized K/V head repeats
        g = hq // hk
        qg = q.reshape(b, hk, g, sq, d).astype(jnp.float32)
        logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg,
                            k.astype(jnp.float32)).reshape(b, hq, sq, sk)
        logits = logits * sm_scale
    else:
        logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                            k.astype(jnp.float32)) * sm_scale
    if causal:
        qi = jnp.arange(sq)[:, None] + q_offset
        ki = jnp.arange(k.shape[2])[None, :] + kv_offset
        logits = jnp.where(qi >= ki, logits, NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    dead = m <= NEG_INF          # fully-masked row: zero output (kernel contract)
    p = jnp.where(dead, 0.0, jnp.exp(logits - m))
    l = jnp.sum(p, axis=-1, keepdims=True)
    if hk != hq:
        pg = p.reshape(b, hk, hq // hk, sq, sk)
        out = jnp.einsum("bhgqk,bhkd->bhgqd", pg,
                         v.astype(jnp.float32)).reshape(b, hq, sq, d)
        out = out / jnp.maximum(l, 1e-30)
    else:
        out = jnp.einsum("bhqk,bhkd->bhqd", p,
                         v.astype(jnp.float32)) / jnp.maximum(l, 1e-30)
    out = out.astype(q.dtype)
    if not with_lse:
        return out
    lse = (m + jnp.log(jnp.maximum(l, 1e-30)))[..., 0]
    lse = jnp.where(l[..., 0] <= 1e-30, NEG_INF, lse)
    return out, lse


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, sm_scale, causal, block_q, block_k,
                kv_blocks, kv_len):
    i = pl.program_id(2)          # q block
    j = pl.program_id(3)          # kv block (sequential)
    q_off = off_ref[0]
    kv_off = off_ref[1]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # global positions of this tile's rows/cols
    q_ids = q_off + i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_local = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    k_ids = kv_off + k_local

    # skip tiles that are entirely in the causal future
    run = True
    if causal:
        first_q = q_off + i * block_q
        last_q = first_q + block_q - 1
        first_k = kv_off + j * block_k
        run = last_q >= first_k

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        mask = k_local < kv_len
        if causal:
            mask = jnp.logical_and(mask, q_ids >= k_ids)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]                       # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)  # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                      # fully-masked rows -> 0
        corr = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == kv_blocks - 1)
    def _finalize():
        l = l_ref[:, :1]
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        lse = m_ref[:, :1] + jnp.log(jnp.maximum(l, 1e-30))
        lse = jnp.where(l <= 1e-30, NEG_INF, lse)
        # lane-replicated (block_q, 128) store: Mosaic needs >=(8,128) tiles
        lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:])


def _jit_unless_interpret(**jit_kwargs):
    """Decorator: the compiled (non-interpret) path of a kernel wrapper
    under ``jax.jit``. EAGER callers — the serving engine runs the model
    op by op, ``model.generate``'s prefill, a dygraph backward — then hit
    jit's in-memory cache; a bare ``pl.pallas_call`` re-traces, re-lowers
    and looks its executable up again on EVERY eager call (the first chip
    run spent minutes there). Interpret mode stays as it was: eager, the
    CPU tests' numerics. The wrapped function takes an ``interpret``
    argument, which must be among the static ones."""
    def decorate(fn):
        jitted = jax.jit(fn, **jit_kwargs)
        bind = inspect.signature(fn).bind

        @functools.wraps(fn)
        def call(*args, **kw):
            interpret = bind(*args, **kw).arguments["interpret"]
            return (fn if interpret else jitted)(*args, **kw)
        return call
    return decorate


@_jit_unless_interpret(static_argnums=(3, 4, 7, 8, 9))
def _fwd(q, k, v, causal, sm_scale, q_offset, kv_offset, block_q, block_k,
         interpret):
    b, hq, sq, d = q.shape
    hk, sk = k.shape[1], k.shape[2]
    group = hq // hk
    block_q = min(block_q, max(sq, 8))
    block_k = min(block_k, max(sk, 8))
    sq_pad = _cdiv(sq, block_q) * block_q
    sk_pad = _cdiv(sk, block_k) * block_k
    if sq_pad != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_pad - sq), (0, 0)))
    if sk_pad != sk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, sk_pad - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, sk_pad - sk), (0, 0)))
    q_blocks = sq_pad // block_q
    kv_blocks = sk_pad // block_k
    offs = jnp.asarray(
        jnp.stack([jnp.asarray(q_offset, jnp.int32),
                   jnp.asarray(kv_offset, jnp.int32)]), jnp.int32)

    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, kv_blocks=kv_blocks, kv_len=sk)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, hq, q_blocks, kv_blocks),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h, i, j: (b_, h // group, j, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h, i, j: (b_, h // group, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 128),
                         lambda b_, h, i, j: (b_, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sq_pad, d), q.dtype),
            jax.ShapeDtypeStruct((b, hq, sq_pad, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(offs, q, k, v)
    return out[:, :, :sq], lse[:, :, :sq, 0]


# ---------------------------------------------------------------------------
# Backward kernels (FA2-style recompute; dq pass + dk/dv pass)
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, acc_ref, *, sm_scale, causal, block_q, block_k,
                   kv_blocks, kv_len):
    i = pl.program_id(2)
    j = pl.program_id(3)
    q_off = off_ref[0]
    kv_off = off_ref[1]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = True
    if causal:
        run = (q_off + i * block_q + block_q - 1) >= (kv_off + j * block_k)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        q_ids = q_off + i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_local = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_local < kv_len
        if causal:
            mask = jnp.logical_and(mask, q_ids >= (kv_off + k_local))
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(j == kv_blocks - 1)
    def _finalize():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, sm_scale, causal,
                    block_q, block_k, q_blocks, kv_len):
    j = pl.program_id(2)          # kv block
    i = pl.program_id(3)          # q block (sequential)
    q_off = off_ref[0]
    kv_off = off_ref[1]

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    run = True
    if causal:
        run = (q_off + i * block_q + block_q - 1) >= (kv_off + j * block_k)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        q_ids = q_off + i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_local = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_local < kv_len
        if causal:
            mask = jnp.logical_and(mask, q_ids >= (kv_off + k_local))
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)         # (bq, bk)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(i == q_blocks - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


@_jit_unless_interpret(static_argnums=(0, 1, 2, 3, 4))
def _bwd(causal, sm_scale, block_q, block_k, interpret, res, g):
    q, k, v, out, lse, offs = res
    do, g_lse = g
    b, hq, sq, d = q.shape
    hk, sk = k.shape[1], k.shape[2]
    group = hq // hk
    block_q = min(block_q, max(sq, 8))
    block_k = min(block_k, max(sk, 8))
    sq_pad = _cdiv(sq, block_q) * block_q
    sk_pad = _cdiv(sk, block_k) * block_k

    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    # lse is a differentiable output (ring merge uses it): dlse/ds_j = p_j, so
    # its cotangent folds into the delta term of ds = p*(dp - delta)
    if g_lse is not None and getattr(g_lse, "dtype", None) != jax.dtypes.float0:
        delta = delta - g_lse.astype(jnp.float32)

    def padq(x):
        return jnp.pad(x, ((0, 0), (0, 0), (0, sq_pad - sq)) +
                       (((0, 0),) if x.ndim == 4 else ())) if sq_pad != sq else x

    def padk(x):
        return jnp.pad(x, ((0, 0), (0, 0), (0, sk_pad - sk), (0, 0))) \
            if sk_pad != sk else x

    qp, dop = padq(q), padq(do)
    # padded q rows: lse = +inf so p = exp(s - inf) = 0 (NEG_INF would explode)
    lsep = jnp.pad(lse, ((0, 0), (0, 0), (0, sq_pad - sq)),
                   constant_values=jnp.inf) if sq_pad != sq else lse
    deltap = padq(delta)
    # lane-replicated (…, 128) layout for per-row scalars (Mosaic tiling)
    lsep = jnp.broadcast_to(lsep[..., None], (*lsep.shape, 128))
    deltap = jnp.broadcast_to(deltap[..., None], (*deltap.shape, 128))
    kp, vp = padk(k), padk(v)
    q_blocks = sq_pad // block_q
    kv_blocks = sk_pad // block_k

    q_spec = pl.BlockSpec((1, 1, block_q, d), lambda b_, h, i, j: (b_, h, i, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, d),
                           lambda b_, h, i, j: (b_, h // group, j, 0))
    row_spec = pl.BlockSpec((1, 1, block_q, 128),
                            lambda b_, h, i, j: (b_, h, i, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          kv_blocks=kv_blocks, kv_len=sk),
        grid=(b, hq, q_blocks, kv_blocks),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[q_spec],
        out_shape=[jax.ShapeDtypeStruct((b, hq, sq_pad, d), q.dtype)],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(offs, qp, kp, vp, dop, lsep, deltap)[0][:, :, :sq]

    # dk/dv per *query* head (grid over full hq), then reduce over the GQA group
    kv_q_spec = pl.BlockSpec((1, 1, block_k, d),
                             lambda b_, h, j, i: (b_, h // group, j, 0))
    q_spec2 = pl.BlockSpec((1, 1, block_q, d), lambda b_, h, j, i: (b_, h, i, 0))
    row_spec2 = pl.BlockSpec((1, 1, block_q, 128),
                             lambda b_, h, j, i: (b_, h, i, 0))
    dkv_out_spec = pl.BlockSpec((1, 1, block_k, d),
                                lambda b_, h, j, i: (b_, h, j, 0))
    dk_full, dv_full = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          q_blocks=q_blocks, kv_len=sk),
        grid=(b, hq, kv_blocks, q_blocks),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  q_spec2, kv_q_spec, kv_q_spec, q_spec2, row_spec2, row_spec2],
        out_specs=[dkv_out_spec, dkv_out_spec],
        out_shape=[jax.ShapeDtypeStruct((b, hq, sk_pad, d), jnp.float32),
                   jax.ShapeDtypeStruct((b, hq, sk_pad, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(offs, qp, kp, vp, dop, lsep, deltap)
    dk_full = dk_full[:, :, :sk]
    dv_full = dv_full[:, :, :sk]
    if group > 1:
        dk = dk_full.reshape(b, hk, group, sk, d).sum(axis=2)
        dv = dv_full.reshape(b, hk, group, sk, d).sum(axis=2)
    else:
        dk, dv = dk_full, dv_full
    d_offs = np.zeros(offs.shape, dtype=jax.dtypes.float0)  # int input: float0 cotangent
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), d_offs)


# ---------------------------------------------------------------------------
# custom_vjp wrapper (kernel layout [b, h, s, d])
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, offs, causal, sm_scale, block_q, block_k, interpret):
    out, _ = _fwd(q, k, v, causal, sm_scale, offs[0], offs[1],
                  block_q, block_k, interpret)
    return out


def _flash_fwd_rule(q, k, v, offs, causal, sm_scale, block_q, block_k,
                    interpret):
    out, lse = _fwd(q, k, v, causal, sm_scale, offs[0], offs[1],
                    block_q, block_k, interpret)
    return out, (q, k, v, out, lse, offs)


def _flash_bwd_rule(causal, sm_scale, block_q, block_k, interpret, res, g):
    return _bwd(causal, sm_scale, block_q, block_k, interpret, res, (g, None))


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_with_lse(q, k, v, offs, causal, sm_scale, block_q, block_k,
                    interpret):
    return _fwd(q, k, v, causal, sm_scale, offs[0], offs[1], block_q, block_k,
                interpret)


def _flash_lse_fwd_rule(q, k, v, offs, causal, sm_scale, block_q, block_k,
                        interpret):
    out, lse = _fwd(q, k, v, causal, sm_scale, offs[0], offs[1],
                    block_q, block_k, interpret)
    return (out, lse), (q, k, v, out, lse, offs)


_flash_with_lse.defvjp(_flash_lse_fwd_rule, _bwd)


def _default_interpret():
    return jax.default_backend() != "tpu"


def _xla_fallback(q, k, v, causal, sm_scale, q_offset, kv_offset,
                  with_lse=False, chunk=1024):
    """Plain-XLA chunked path (kernel layout). Chunks the query axis so
    the fp32 logits temporary is O(chunk*sk), not O(sq*sk) — long
    sequences without the kernel degrade to slow, not to OOM.
    Each chunk is wrapped in ``jax.checkpoint`` so the backward also
    recomputes its logits/probabilities per chunk: without it jax AD
    saves every chunk's O(chunk*sk) softmax residuals, which together
    re-materialize the full S×S memory this tier exists to avoid."""
    sq, sk = q.shape[2], k.shape[2]
    if sq <= chunk:
        return mha_reference(q, k, v, causal=causal, sm_scale=sm_scale,
                             q_offset=q_offset, kv_offset=kv_offset,
                             with_lse=with_lse)

    @functools.partial(jax.checkpoint, static_argnums=(3, 4))
    def one_chunk(qc, k, v, start, hi):
        # the kv trim happens INSIDE the checkpoint boundary: the saved
        # residual stays the one shared full k/v buffer, the sliced
        # copies are recomputed in backward (slicing outside would pin
        # every chunk's kv prefix live simultaneously — O(sq²·d/chunk))
        return mha_reference(qc, k[:, :, :hi], v[:, :, :hi], causal=causal,
                             sm_scale=sm_scale, q_offset=q_offset + start,
                             kv_offset=kv_offset, with_lse=with_lse)

    # causal + static offsets: chunk [start, start+chunk) can only attend
    # to kv positions <= q_offset+start+chunk-1, so trim the kv suffix —
    # the triangle costs half the FLOPs of the full rectangle
    trim = causal and isinstance(q_offset, int) and isinstance(kv_offset, int)
    outs, lses = [], []
    for start in range(0, sq, chunk):
        hi = sk
        if trim:
            hi = max(min(sk, q_offset + start + chunk - kv_offset), 1)
        res = one_chunk(q[:, :, start:start + chunk], k, v, start, hi)
        if with_lse:
            outs.append(res[0])
            lses.append(res[1])
        else:
            outs.append(res)
    if with_lse:
        return jnp.concatenate(outs, axis=2), jnp.concatenate(lses, axis=2)
    return jnp.concatenate(outs, axis=2)


# ---------------------------------------------------------------------------
# Pure-XLA flash attention (no Mosaic): lax.scan online-softmax forward +
# custom_vjp blockwise-recompute backward — flash MEMORY behavior
# (O(block²) logits temporaries, O(S) residuals) from plain XLA ops, for
# the SDPA long-sequence route where the Pallas kernel does not apply
# (masked/CPU/flag-disabled calls).
# ---------------------------------------------------------------------------

def _xfa_blocks(sq, sk):
    bq = min(int(_os.environ.get("PADDLE_TPU_XFA_BLOCK_Q", "512")), sq)
    bk = min(int(_os.environ.get("PADDLE_TPU_XFA_BLOCK_K", "1024")), sk)
    return bq, bk


def _xflash_fwd_impl(q, k, v, offs, causal, sm_scale):
    """Grouped-GQA online-softmax forward. q [b,hq,sq,d]; k/v [b,hk,sk,d];
    returns (out [b,hq,sq,d], lse fp32 [b,hq,sq]) with mha_reference's
    conventions (natural-log lse; fully-masked rows -> out 0, lse NEG_INF)."""
    b, hq, sq, d = q.shape
    hk, sk = k.shape[1], k.shape[2]
    g = hq // hk
    bq, bk = _xfa_blocks(sq, sk)
    nq, nk = sq // bq, sk // bk
    q_off = jnp.asarray(offs[0], jnp.int32)
    kv_off = jnp.asarray(offs[1], jnp.int32)
    qg = q.reshape(b, hk, g, sq, d)

    def one_q_block(qi, qblk):                     # qblk [b,hk,g,bq,d]
        m0 = jnp.full((b, hk, g, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hk, g, bq), jnp.float32)
        a0 = jnp.zeros((b, hk, g, bq, d), jnp.float32)

        def step(carry, kj):
            m, l, acc = carry
            kblk = jax.lax.dynamic_slice_in_dim(k, kj * bk, bk, axis=2)
            vblk = jax.lax.dynamic_slice_in_dim(v, kj * bk, bk, axis=2)
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qblk, kblk,
                           preferred_element_type=jnp.float32) * sm_scale
            if causal:
                qpos = q_off + qi * bq + jnp.arange(bq, dtype=jnp.int32)
                kpos = kv_off + kj * bk + jnp.arange(bk, dtype=jnp.int32)
                s = jnp.where(qpos[:, None] >= kpos[None, :], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            # dead rows (everything masked): exponents of NEG_INF-vs-NEG_INF
            # must not become exp(0)=1 — shift by 0 instead
            m_eff = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
            p = jnp.exp(s - m_eff[..., None])
            alpha = jnp.exp(m - m_eff)
            l_new = l * alpha + p.sum(-1)
            pv = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(v.dtype), vblk,
                            preferred_element_type=jnp.float32)
            return (m_new, l_new, acc * alpha[..., None] + pv), None

        (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                      jnp.arange(nk, dtype=jnp.int32))
        l_safe = jnp.maximum(l, 1e-30)
        out = (acc / l_safe[..., None]).astype(q.dtype)
        m_eff = jnp.where(m <= NEG_INF / 2, 0.0, m)
        lse = jnp.where(l <= 1e-30, NEG_INF, m_eff + jnp.log(l_safe))
        return out, lse

    qblocks = jnp.moveaxis(qg.reshape(b, hk, g, nq, bq, d), 3, 0)

    def scan_q(_, xs):
        qi, qblk = xs
        return None, one_q_block(qi, qblk)

    _, (outs, lses) = jax.lax.scan(
        scan_q, None, (jnp.arange(nq, dtype=jnp.int32), qblocks))
    out = jnp.moveaxis(outs, 0, 3).reshape(b, hq, sq, d)
    lse = jnp.moveaxis(lses, 0, 3).reshape(b, hq, sq)
    return out, lse


def _xflash_bwd_impl(q, k, v, offs, out, lse, dout, causal, sm_scale,
                     g_lse=None):
    """Blockwise-recompute backward (FA2 structure in plain XLA): one scan
    over q blocks carrying fp32 dk/dv accumulators, inner scan over kv
    blocks; p is recomputed from lse so no S×S residual exists."""
    b, hq, sq, d = q.shape
    hk, sk = k.shape[1], k.shape[2]
    g = hq // hk
    bq, bk = _xfa_blocks(sq, sk)
    nq, nk = sq // bq, sk // bk
    q_off = jnp.asarray(offs[0], jnp.int32)
    kv_off = jnp.asarray(offs[1], jnp.int32)

    delta = (dout.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
    # lse is a differentiable output (ring merge uses it): dlse/ds_j = p_j,
    # so its cotangent folds into the delta term of ds = p*(dp - delta) —
    # same handling as the Mosaic path's _bwd
    if g_lse is not None and getattr(g_lse, "dtype", None) != \
            jax.dtypes.float0:
        delta = delta - g_lse.astype(jnp.float32)
    shp5 = (b, hk, g, nq, bq)
    qb = jnp.moveaxis(q.reshape(b, hk, g, nq, bq, d), 3, 0)
    dob = jnp.moveaxis(dout.reshape(b, hk, g, nq, bq, d), 3, 0)
    lseb = jnp.moveaxis(lse.reshape(*shp5), 3, 0)
    deltab = jnp.moveaxis(delta.reshape(*shp5), 3, 0)

    def per_q(carry, xs):
        dk, dv = carry
        qi, qblk, doblk, lseblk, dblk = xs
        live = (lseblk > NEG_INF / 2).astype(jnp.float32)

        def step(inner, kj):
            dq_acc, dk, dv = inner
            kblk = jax.lax.dynamic_slice_in_dim(k, kj * bk, bk, axis=2)
            vblk = jax.lax.dynamic_slice_in_dim(v, kj * bk, bk, axis=2)
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qblk, kblk,
                           preferred_element_type=jnp.float32) * sm_scale
            if causal:
                qpos = q_off + qi * bq + jnp.arange(bq, dtype=jnp.int32)
                kpos = kv_off + kj * bk + jnp.arange(bk, dtype=jnp.int32)
                s = jnp.where(qpos[:, None] >= kpos[None, :], s, NEG_INF)
            p = jnp.exp(s - lseblk[..., None]) * live[..., None]
            dp = jnp.einsum("bhgqd,bhkd->bhgqk", doblk, vblk,
                            preferred_element_type=jnp.float32)
            ds = p * (dp - dblk[..., None]) * sm_scale
            pc, dsc = p.astype(v.dtype), ds.astype(q.dtype)
            dq_blk = jnp.einsum("bhgqk,bhkd->bhgqd", dsc, kblk,
                                preferred_element_type=jnp.float32)
            dk_blk = jnp.einsum("bhgqk,bhgqd->bhkd", dsc, qblk,
                                preferred_element_type=jnp.float32)
            dv_blk = jnp.einsum("bhgqk,bhgqd->bhkd", pc, doblk,
                                preferred_element_type=jnp.float32)
            dk = jax.lax.dynamic_update_slice_in_dim(
                dk, jax.lax.dynamic_slice_in_dim(dk, kj * bk, bk, 2)
                + dk_blk, kj * bk, 2)
            dv = jax.lax.dynamic_update_slice_in_dim(
                dv, jax.lax.dynamic_slice_in_dim(dv, kj * bk, bk, 2)
                + dv_blk, kj * bk, 2)
            return (dq_acc + dq_blk, dk, dv), None

        dq0 = jnp.zeros((b, hk, g, bq, d), jnp.float32)
        (dq_blk, dk, dv), _ = jax.lax.scan(
            step, (dq0, dk, dv), jnp.arange(nk, dtype=jnp.int32))
        return (dk, dv), dq_blk

    dk0 = jnp.zeros((b, hk, sk, d), jnp.float32)
    dv0 = jnp.zeros((b, hk, sk, d), jnp.float32)
    (dk, dv), dqs = jax.lax.scan(
        per_q, (dk0, dv0),
        (jnp.arange(nq, dtype=jnp.int32), qb, dob, lseb, deltab))
    dq = jnp.moveaxis(dqs, 0, 3).reshape(b, hq, sq, d).astype(q.dtype)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _xflash(q, k, v, offs, causal, sm_scale):
    out, _ = _xflash_fwd_impl(q, k, v, offs, causal, sm_scale)
    return out


def _xflash_fwd_rule(q, k, v, offs, causal, sm_scale):
    out, lse = _xflash_fwd_impl(q, k, v, offs, causal, sm_scale)
    return out, (q, k, v, offs, out, lse)


def _xflash_bwd_rule(causal, sm_scale, res, g):
    q, k, v, offs, out, lse = res
    dq, dk, dv = _xflash_bwd_impl(q, k, v, offs, out, lse, g, causal,
                                  sm_scale)
    return dq, dk, dv, None


_xflash.defvjp(_xflash_fwd_rule, _xflash_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _xflash_with_lse(q, k, v, offs, causal, sm_scale):
    return _xflash_fwd_impl(q, k, v, offs, causal, sm_scale)


def _xflash_lse_fwd_rule(q, k, v, offs, causal, sm_scale):
    out, lse = _xflash_fwd_impl(q, k, v, offs, causal, sm_scale)
    return (out, lse), (q, k, v, offs, out, lse)


def _xflash_lse_bwd_rule(causal, sm_scale, res, g):
    q, k, v, offs, out, lse = res
    dout, g_lse = g
    dq, dk, dv = _xflash_bwd_impl(q, k, v, offs, out, lse, dout, causal,
                                  sm_scale, g_lse=g_lse)
    return dq, dk, dv, None


_xflash_with_lse.defvjp(_xflash_lse_fwd_rule, _xflash_lse_bwd_rule)


def _scanq(q, k, v, causal, sm_scale, q_offset, kv_offset,
           with_lse=False, chunk=1024):
    """Single-level scan tier: ``lax.scan`` over q-chunks, full-K plain
    attention per chunk, ``jax.checkpoint`` body. Compared to the other
    non-Mosaic tiers: graph size is CONSTANT in sequence length (the
    unrolled chunked tier emits one subgraph per chunk) and there is no
    scan-in-scan / custom_vjp structure (the _xflash formulation).
    Memory O(chunk·sk) fwd and bwd
    (remat body; k/v are closure constants whose cotangents the scan
    transpose accumulates). Requires sq % chunk == 0 (callers fall back
    to the chunked tier otherwise)."""
    b, h, sq, d = q.shape
    nq = sq // chunk
    qb = jnp.moveaxis(q.reshape(b, h, nq, chunk, d), 2, 0)
    q_off = jnp.asarray(q_offset, jnp.int32)

    @jax.checkpoint
    def body(qi, qc):
        return mha_reference(qc, k, v, causal=causal, sm_scale=sm_scale,
                             q_offset=q_off + qi * chunk,
                             kv_offset=kv_offset, with_lse=True)

    def step(carry, xs):
        qi, qc = xs
        return carry, body(qi, qc)

    _, (outs, lses) = jax.lax.scan(
        step, None, (jnp.arange(nq, dtype=jnp.int32), qb))
    out = jnp.moveaxis(outs, 0, 2).reshape(b, h, sq, d)
    if with_lse:
        return out, jnp.moveaxis(lses, 0, 2).reshape(b, h, sq)
    return out


def _xfa_mode():
    """PADDLE_TPU_XFA selects the non-Mosaic training tier:
    ``1`` (default) the scan-formulation online-softmax flash (_xflash);
    ``scanq`` the single-level scan-over-q-chunks tier; ``0`` the
    unrolled chunked-reference tier."""
    mode = _os.environ.get("PADDLE_TPU_XFA", "1")
    if mode not in ("0", "1", "scanq"):
        raise ValueError(f"PADDLE_TPU_XFA={mode!r}: expected 0, 1 or scanq")
    return mode


def _xfa_chunk():
    return max(int(_os.environ.get("PADDLE_TPU_XFA_CHUNK", "1024")), 1)


def _xflash_ok(q, k):
    """The scan formulation needs block-divisible sequence axes; other
    shapes stay on the chunked-reference fallback."""
    if _xfa_mode() != "1":
        return False
    sq, sk = q.shape[2], k.shape[2]
    bq, bk = _xfa_blocks(sq, sk)
    return sq % bq == 0 and sk % bk == 0


def _scanq_ok(q):
    chunk = _xfa_chunk()
    return (_xfa_mode() == "scanq" and q.shape[2] % chunk == 0
            and q.shape[2] > chunk)


def xla_attention(q, k, v, causal=True, sm_scale=None, q_offset=0,
                  kv_offset=0, with_lse=False):
    """Non-Mosaic attention in kernel layout [b, h, s, d]: the single
    dispatch point for the pure-XLA tiers (``PADDLE_TPU_XFA`` selects
    _xflash / _scanq / the unrolled chunked tier). Used by the SDPA
    long-sequence memory-safety route; the Pallas kernel never drops
    here on its own."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if _xflash_ok(q, k):
        offs = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                          jnp.asarray(kv_offset, jnp.int32)])
        if with_lse:
            return _xflash_with_lse(q, k, v, offs, causal, sm_scale)
        return _xflash(q, k, v, offs, causal, sm_scale)
    if _scanq_ok(q):
        return _scanq(q, k, v, causal, sm_scale, q_offset, kv_offset,
                      with_lse=with_lse, chunk=_xfa_chunk())
    return _xla_fallback(q, k, v, causal, sm_scale, q_offset, kv_offset,
                         with_lse=with_lse)


def flash_attention(q, k, v, causal=True, sm_scale=None, q_offset=0,
                    kv_offset=0, block_q=DEFAULT_BLOCK_Q,
                    block_k=DEFAULT_BLOCK_K, interpret=None, kernel_layout=False):
    """Flash attention. Layout [b, s, h, d] (paddle flash-attn convention) or
    [b, h, s, d] with ``kernel_layout=True``. Differentiable (custom VJP with
    FA2-style blockwise recompute)."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = _default_interpret()
    if not kernel_layout:
        q, k, v = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    offs = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                      jnp.asarray(kv_offset, jnp.int32)])
    out = _flash(q, k, v, offs, causal, sm_scale, block_q, block_k,
                 interpret)
    if not kernel_layout:
        out = jnp.swapaxes(out, 1, 2)
    return out


def flash_attention_with_lse(q, k, v, causal=True, sm_scale=None, q_offset=0,
                             kv_offset=0, block_q=DEFAULT_BLOCK_Q,
                             block_k=DEFAULT_BLOCK_K, interpret=None):
    """Kernel-layout [b, h, s, d] flash attention returning (out, lse) for
    online-softmax merging across KV shards (ring attention)."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = _default_interpret()
    offs = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                      jnp.asarray(kv_offset, jnp.int32)])
    return _flash_with_lse(q, k, v, offs, causal, sm_scale, block_q, block_k,
                           interpret)

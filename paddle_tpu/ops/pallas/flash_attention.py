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

import os as _os


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
# Tiles from the shapes
# ---------------------------------------------------------------------------

LANES = 128
KERNELS = ("fwd", "dq", "dkv")

#: the largest (block_q, block_k) of a tile. A grid step costs ~0.35 us
#: whatever it computes, so a tile is as large as pays: on one v5e chip at
#: [1, 4096, 32 / 8, 128] bf16, causal, 1,024 x 1,024 is the fastest of
#: {256, 512, 1024} x {256, 512, 1024, 2048} for all three kernels (forward
#: 1.62 ms a call, 2.96 at 512 x 512, 13.6 at 128 x 128; dq 1.87 / 2.20 /
#: 10.9; dkv 1.98 / 2.18 / 11.0), and 2,048 keys lose again to the causal
#: half of a diagonal tile that is computed and masked away (PERF.md, PR 30)
_TILE_CAP = (1024, 1024)

#: what a kernel's working set may take of a core's VMEM (128 MiB on v5e);
#: Mosaic's own default scope is 16 MiB, so a larger set states its limit
_VMEM_BUDGET = 48 * 2 ** 20
_VMEM_DEFAULT_SCOPE = 16 * 2 ** 20


def _cdiv(a, b):
    return (a + b - 1) // b


def _fit(length, cap):
    """Block length for an axis of ``length``: the axis itself below one
    lane row (as ever), else a multiple of 128 in as few equal blocks of at
    most ``cap`` as cover it (4,224 under a cap of 1,024: 5 x 896)."""
    if length <= LANES:
        return max(length, 8)
    return _cdiv(_cdiv(length, _cdiv(length, cap)), LANES) * LANES


def vmem_plan(kernel, block_q, block_k, head_dim, dtype):
    """Bytes of VMEM one grid step of ``kernel`` holds at a tile: blocks
    that Pallas moves are double-buffered, per-row float32 scalars ride
    lane-replicated (128 wide) or as 8-sublane rows, and the score tile
    counts once for each float32 [block_q, block_k] value alive at a time
    (scores, probabilities, and in the backward dP and dS). ``limit`` is
    the ``vmem_limit_bytes`` the call states: None where Mosaic's default
    scope carries the set, else twice the sum."""
    item = jnp.dtype(dtype).itemsize
    q_blk, k_blk = block_q * head_dim * item, block_k * head_dim * item
    tile = block_q * block_k * 4
    cols = block_q * LANES * 4               # [block_q, 128] float32
    if kernel == "fwd":
        plan = {"q": 2 * q_blk, "kv": 4 * k_blk, "out": 2 * q_blk,
                "rows": 2 * cols + 2 * cols,          # lse out; m, l
                "acc": block_q * head_dim * 4, "tiles": 3 * tile}
    elif kernel == "dq":
        plan = {"q": 4 * q_blk, "kv": 4 * k_blk, "out": 2 * q_blk,
                "rows": 4 * cols, "acc": block_q * head_dim * 4,
                "tiles": 4 * tile}
    elif kernel == "dkv":
        plan = {"q": 4 * q_blk, "kv": 4 * k_blk, "out": 4 * k_blk,
                "rows": 4 * 8 * block_q * 4,
                "acc": 2 * block_k * head_dim * 4, "tiles": 4 * tile}
    else:
        raise ValueError(f"kernel {kernel!r}: expected one of {KERNELS}")
    plan["total"] = sum(plan.values())
    plan["limit"] = (None if 2 * plan["total"] <= _VMEM_DEFAULT_SCOPE
                     else 2 * plan["total"])
    return plan


def tile_rule(kernel, sq, sk, head_dim, dtype):
    """The (block_q, block_k) ``kernel`` ('fwd' / 'dq' / 'dkv') runs at
    when the caller names none: from what the wrapper sees, nothing else.
    The cap, fitted to the two lengths, halved along the keys and then the
    queries while the kernel's working set is over the budget (a wider
    head, float32 operands or dkv's second accumulator take smaller
    tiles first)."""
    cap_q, cap_k = _TILE_CAP
    while True:
        block_q, block_k = _fit(sq, cap_q), _fit(sk, cap_k)
        if (vmem_plan(kernel, block_q, block_k, head_dim, dtype)["total"]
                <= _VMEM_BUDGET):
            return block_q, block_k
        if cap_k >= cap_q and cap_k > LANES:
            cap_k //= 2
        elif cap_q > LANES:
            cap_q //= 2
        else:
            return block_q, block_k


def _tiles(kernel, block_q, block_k, sq, sk, head_dim, dtype):
    """The tile a call runs at and its padded lengths: the caller's
    ``block_q`` / ``block_k`` (clipped to the axis, as ever) or the rule's."""
    rule_q, rule_k = tile_rule(kernel, sq, sk, head_dim, dtype)
    block_q = rule_q if block_q is None else min(block_q, max(sq, 8))
    block_k = rule_k if block_k is None else min(block_k, max(sk, 8))
    return (block_q, block_k,
            _cdiv(sq, block_q) * block_q, _cdiv(sk, block_k) * block_k)


def _compiler_params(kernel, block_q, block_k, head_dim, dtype):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=vmem_plan(kernel, block_q, block_k, head_dim,
                                   dtype)["limit"])


def _plan_counts(block_q, block_k, sq, sk, causal, q_offset, kv_offset):
    """One (batch, query head) plane of a grid at a tile: steps walked,
    steps that compute, tiles that run the mask arithmetic, and the
    (query, key) pairs the attention needs over those inside computed
    tiles. The same three conditions the kernels evaluate."""
    q_blocks, kv_blocks = _cdiv(sq, block_q), _cdiv(sk, block_k)
    first_q = q_offset + block_q * np.arange(q_blocks)[:, None]
    first_k = kv_offset + block_k * np.arange(kv_blocks)[None, :]
    edge = np.broadcast_to(
        block_k * (np.arange(kv_blocks)[None, :] + 1) > sk,
        (q_blocks, kv_blocks))
    if causal:
        run = first_q + block_q - 1 >= first_k
        masked = run & ((first_q < first_k + block_k - 1) | edge)
        rows = q_offset + np.arange(sq)
        needed = int(np.clip(rows - kv_offset + 1, 0, sk).sum())
    else:
        run = np.ones((q_blocks, kv_blocks), bool)
        masked = edge
        needed = sq * sk
    compute = int(run.sum())
    return {"tile": (block_q, block_k), "steps": q_blocks * kv_blocks,
            "compute_steps": compute, "masked_tiles": int(masked.sum()),
            "pairs_needed": needed,
            "pairs_computed": compute * block_q * block_k}


def grid_plan(sq, sk, head_dim, dtype, causal=True, q_offset=0, kv_offset=0):
    """What the three kernels' grids do with a call of these shapes, for
    one (batch, query head) plane each: ``{kernel: {tile, steps,
    compute_steps, masked_tiles, pairs_needed, pairs_computed}}``. Pure
    arithmetic on the shapes (offsets as integers): the price of a large
    tile is ``pairs_needed / pairs_computed``, the share of the products
    inside computed tiles that the attention asked for."""
    plan = {}
    for kernel in KERNELS:
        block_q, block_k, _, _ = _tiles(kernel, None, None, sq, sk,
                                        head_dim, dtype)
        plan[kernel] = _plan_counts(block_q, block_k, sq, sk, causal,
                                    int(q_offset), int(kv_offset))
    return plan


# ---------------------------------------------------------------------------
# What the three kernels share: a tile's case, its mask, its products
# ---------------------------------------------------------------------------

_NT = (((1,), (1,)), ((), ()))       # a @ b.T
_NN = (((1,), (0,)), ((), ()))       # a @ b


def _dot(a, b, dims):
    """A product on the MXU with its operands in the dtype they arrive in
    and a float32 result. bf16 operands state the default precision: a
    global ``jax_default_matmul_precision`` of "highest" (the test suite's)
    would ask Mosaic for a float32 product of bf16 operands, which it
    refuses."""
    precision = (None if a.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


def _tile_case(off_ref, i, j, *, causal, block_q, block_k, kv_len):
    """``(run, masked)`` of tile (query block ``i``, key block ``j``):
    ``run`` is false where every key of it lies in every query's future,
    ``masked`` true where some pair of it must not count (the tile crosses
    the diagonal, or the padding behind ``kv_len``). Python booleans where
    the shapes alone decide."""
    edge = False if kv_len % block_k == 0 else (j + 1) * block_k > kv_len
    if not causal:
        return True, edge
    first_q = off_ref[0] + i * block_q
    first_k = off_ref[1] + j * block_k
    run = first_q + block_q - 1 >= first_k
    diag = first_q < first_k + block_k - 1
    return run, (diag if edge is False else jnp.logical_or(diag, edge))


def _on_tile(run, masked, body):
    """Run ``body(masked)`` for a tile's case: not at all, without the
    mask arithmetic (interior tiles go straight from scores to ``exp``),
    or with it."""
    if masked is False:
        pl.when(run)(functools.partial(body, False))
        return
    pl.when(jnp.logical_and(run, masked))(functools.partial(body, True))
    pl.when(jnp.logical_and(run, jnp.logical_not(masked)))(
        functools.partial(body, False))


def _pair_mask(off_ref, i, j, *, causal, block_q, block_k, kv_len,
               keys_first=False):
    """Which pairs of tile (i, j) count: [block_q, block_k], or
    [block_k, block_q] with ``keys_first``."""
    shape = (block_k, block_q) if keys_first else (block_q, block_k)
    q_axis, k_axis = (1, 0) if keys_first else (0, 1)
    k_local = j * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, k_axis)
    mask = k_local < kv_len
    if causal:
        q_ids = off_ref[0] + i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, shape, q_axis)
        mask = jnp.logical_and(mask, q_ids >= off_ref[1] + k_local)
    return mask


def _kv_index_map(group, kv_blocks, *, causal, block_q, block_k, **_):
    """The K / V index map of the grids that walk key blocks last (forward,
    dq). Causal: a step in the future names the last block its query block
    needs, the one it already holds, so Pallas fetches nothing (block 0
    where a whole shard lies in the future, and nothing computes)."""
    def index(b_, h, i, j, off):
        if causal:
            last_q = off[0] + (i + 1) * block_q - 1 - off[1]
            j = jnp.minimum(j, jnp.minimum(
                jax.lax.div(jnp.maximum(last_q, 0), block_k), kv_blocks - 1))
        return (b_, h // group, j, 0)
    return index


def _first_q_block(off, j, *, block_q, block_k, q_blocks):
    """The first query block that key block ``j`` reaches."""
    first_k = off[1] + j * block_k - off[0]
    return jnp.minimum(jax.lax.div(jnp.maximum(first_k, 0), block_q),
                       q_blocks - 1)


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, sm_scale, kv_blocks, **tile):
    i = pl.program_id(2)          # q block
    j = pl.program_id(3)          # kv block (sequential)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _compute(masked):
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        s = _dot(q, k, _NT) * sm_scale
        if masked:
            s = jnp.where(_pair_mask(off_ref, i, j, **tile), s, NEG_INF)
        m_prev = m_ref[:, :1]                       # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + _dot(p.astype(v.dtype), v, _NN)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    _on_tile(*_tile_case(off_ref, i, j, **tile), _compute)

    @pl.when(j == kv_blocks - 1)
    def _finalize():
        # a row that met no key it may see still holds m = NEG_INF, and its
        # masked scores counted as exp(NEG_INF - NEG_INF) = 1: it is dead
        dead = m_ref[:, :1] <= NEG_INF
        l = jnp.where(dead, 0.0, l_ref[:, :1])
        o_ref[0, 0] = jnp.where(dead, 0.0, acc_ref[...] / jnp.maximum(
            l, 1e-30)).astype(o_ref.dtype)
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


def _offsets(q_offset, kv_offset):
    return jnp.stack([jnp.asarray(q_offset, jnp.int32),
                      jnp.asarray(kv_offset, jnp.int32)])


def _pad_axis2(x, to, value=0):
    """Pad axis 2 (the sequence, kernel layout) of ``x`` up to ``to``."""
    if x.shape[2] == to:
        return x
    pads = [(0, 0)] * x.ndim
    pads[2] = (0, to - x.shape[2])
    return jnp.pad(x, pads, constant_values=value)


def _operands(*xs):
    """The operands in one dtype (mixed inputs take the widest)."""
    dtype = jnp.result_type(*xs)
    return tuple(x.astype(dtype) for x in xs)


@_jit_unless_interpret(static_argnums=(3, 4, 7, 8, 9))
def _fwd(q, k, v, causal, sm_scale, q_offset, kv_offset, block_q, block_k,
         interpret):
    q, k, v = _operands(q, k, v)
    b, hq, sq, d = q.shape
    hk, sk = k.shape[1], k.shape[2]
    group = hq // hk
    block_q, block_k, sq_pad, sk_pad = _tiles("fwd", block_q, block_k, sq,
                                              sk, d, q.dtype)
    q, k, v = _pad_axis2(q, sq_pad), _pad_axis2(k, sk_pad), _pad_axis2(
        v, sk_pad)
    q_blocks, kv_blocks = sq_pad // block_q, sk_pad // block_k
    tile = dict(causal=causal, block_q=block_q, block_k=block_k, kv_len=sk)

    q_spec = pl.BlockSpec((1, 1, block_q, d),
                          lambda b_, h, i, j, off: (b_, h, i, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, d),
                           _kv_index_map(group, kv_blocks, **tile))
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale,
                          kv_blocks=kv_blocks, **tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hq, q_blocks, kv_blocks),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=[
                q_spec,
                pl.BlockSpec((1, 1, block_q, LANES),
                             lambda b_, h, i, j, off: (b_, h, i, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((block_q, LANES), jnp.float32),
                pltpu.VMEM((block_q, LANES), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sq_pad, d), q.dtype),
            jax.ShapeDtypeStruct((b, hq, sq_pad, LANES), jnp.float32),
        ],
        compiler_params=_compiler_params("fwd", block_q, block_k, d, q.dtype),
        interpret=interpret,
    )(_offsets(q_offset, kv_offset), q, k, v)
    return out[:, :, :sq], lse[:, :, :sq, 0]


# ---------------------------------------------------------------------------
# Backward kernels (FA2-style recompute; dq pass + dk/dv pass)
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, acc_ref, *, sm_scale, kv_blocks, **tile):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute(masked):
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]
        p = jnp.exp(_dot(q, k, _NT) * sm_scale - lse)
        if masked:
            p = jnp.where(_pair_mask(off_ref, i, j, **tile), p, 0.0)
        ds = p * (_dot(do, v, _NT) - delta)     # x sm_scale: at the end
        acc_ref[...] += _dot(ds.astype(k.dtype), k, _NN)

    _on_tile(*_tile_case(off_ref, i, j, **tile), _compute)

    @pl.when(j == kv_blocks - 1)
    def _finalize():
        dq_ref[0, 0] = (acc_ref[...] * sm_scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, sm_scale, q_blocks,
                    steps, **tile):
    """dK and dV of one key block of one KV head: the sequential axis
    walks every query block of every query head of the group, so the GQA
    sum happens in the accumulators. Scores are computed keys-first
    ([block_k, block_q]): lse and delta then ride as rows, and all four
    products are plain ``a @ b`` / ``a @ b.T``."""
    j = pl.program_id(2)                              # kv block
    t = pl.program_id(3)                              # (group head, q block)
    i = jax.lax.rem(t, q_blocks)

    @pl.when(t == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _compute(masked):
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        lse = lse_ref[0, 0]                           # (1, bq)
        delta = delta_ref[0, 0]
        p = jnp.exp(_dot(k, q, _NT) * sm_scale - lse)     # (bk, bq)
        if masked:
            p = jnp.where(_pair_mask(off_ref, i, j, keys_first=True, **tile),
                          p, 0.0)
        dv_acc[...] += _dot(p.astype(do.dtype), do, _NN)
        ds = p * (_dot(v, do, _NT) - delta)     # x sm_scale: at the end
        dk_acc[...] += _dot(ds.astype(q.dtype), q, _NN)

    _on_tile(*_tile_case(off_ref, i, j, **tile), _compute)

    @pl.when(t == steps - 1)
    def _finalize():
        dk_ref[0, 0] = (dk_acc[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dq(q, k, v, do, lse, delta, offs, causal, sm_scale, block_q,
            block_k, interpret):
    """dQ. ``lse`` / ``delta`` are float32 [b, hq, sq]."""
    b, hq, sq, d = q.shape
    hk, sk = k.shape[1], k.shape[2]
    group = hq // hk
    block_q, block_k, sq_pad, sk_pad = _tiles("dq", block_q, block_k, sq, sk,
                                              d, q.dtype)
    q_blocks, kv_blocks = sq_pad // block_q, sk_pad // block_k
    tile = dict(causal=causal, block_q=block_q, block_k=block_k, kv_len=sk)

    # lane-replicated per-row scalars (Mosaic tiling); a padded q row gets
    # lse = +inf, so that its p = exp(s - inf) = 0
    def cols(x, value):
        x = _pad_axis2(x, sq_pad, value)
        return jnp.broadcast_to(x[..., None], (*x.shape, LANES))

    q_spec = pl.BlockSpec((1, 1, block_q, d),
                          lambda b_, h, i, j, off: (b_, h, i, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, d),
                           _kv_index_map(group, kv_blocks, **tile))
    col_spec = pl.BlockSpec((1, 1, block_q, LANES),
                            lambda b_, h, i, j, off: (b_, h, i, 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale,
                          kv_blocks=kv_blocks, **tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hq, q_blocks, kv_blocks),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, col_spec, col_spec],
            out_specs=[q_spec],
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b, hq, sq_pad, d), q.dtype)],
        compiler_params=_compiler_params("dq", block_q, block_k, d, q.dtype),
        interpret=interpret,
    )(offs, _pad_axis2(q, sq_pad), _pad_axis2(k, sk_pad),
      _pad_axis2(v, sk_pad), _pad_axis2(do, sq_pad), cols(lse, jnp.inf),
      cols(delta, 0))[0]
    return dq[:, :, :sq]


def _bwd_dkv(q, k, v, do, lse, delta, offs, causal, sm_scale, block_q,
             block_k, interpret):
    """dK, dV in k's shape [b, hk, sk, d]: the group's sum included."""
    b, hq, sq, d = q.shape
    hk, sk = k.shape[1], k.shape[2]
    group = hq // hk
    block_q, block_k, sq_pad, sk_pad = _tiles("dkv", block_q, block_k, sq,
                                              sk, d, q.dtype)
    q_blocks, kv_blocks = sq_pad // block_q, sk_pad // block_k
    steps = group * q_blocks
    tile = dict(causal=causal, block_q=block_q, block_k=block_k, kv_len=sk)

    def rows(x, value):     # per-row scalars along the lanes: [b, hq, 1, sq]
        return _pad_axis2(x, sq_pad, value)[:, :, None, :]

    def q_index(b_, h, j, t, off):
        head, i = h * group + jax.lax.div(t, q_blocks), jax.lax.rem(
            t, q_blocks)
        if causal:      # a step before the diagonal names the first it needs
            i = jnp.maximum(i, _first_q_block(
                off, j, block_q=block_q, block_k=block_k, q_blocks=q_blocks))
        return (b_, head, i, 0)

    def row_index(*step):
        b_, head, i, _ = q_index(*step)
        return (b_, head, 0, i)

    q_spec = pl.BlockSpec((1, 1, block_q, d), q_index)
    row_spec = pl.BlockSpec((1, 1, 1, block_q), row_index)
    kv_spec = pl.BlockSpec((1, 1, block_k, d),
                           lambda b_, h, j, t, off: (b_, h, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale,
                          q_blocks=q_blocks, steps=steps, **tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hk, kv_blocks, steps),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
            out_specs=[kv_spec, kv_spec],
            scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                            pltpu.VMEM((block_k, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b, hk, sk_pad, d), k.dtype),
                   jax.ShapeDtypeStruct((b, hk, sk_pad, d), v.dtype)],
        compiler_params=_compiler_params("dkv", block_q, block_k, d, q.dtype),
        interpret=interpret,
    )(offs, _pad_axis2(q, sq_pad), _pad_axis2(k, sk_pad),
      _pad_axis2(v, sk_pad), _pad_axis2(do, sq_pad), rows(lse, jnp.inf),
      rows(delta, 0))
    return dk[:, :, :sk], dv[:, :, :sk]


@_jit_unless_interpret(static_argnums=(0, 1, 2, 3, 4))
def _bwd(causal, sm_scale, block_q, block_k, interpret, res, g):
    q, k, v, out, lse, offs = res
    do, g_lse = g
    dtypes = q.dtype, k.dtype, v.dtype
    q, k, v, do = _operands(q, k, v, do)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    # lse is a differentiable output (ring merge uses it): dlse/ds_j = p_j, so
    # its cotangent folds into the delta term of ds = p*(dp - delta)
    if g_lse is not None and getattr(g_lse, "dtype", None) != jax.dtypes.float0:
        delta = delta - g_lse.astype(jnp.float32)
    args = (q, k, v, do, lse, delta, offs, causal, sm_scale, block_q,
            block_k, interpret)
    dq = _bwd_dq(*args)
    dk, dv = _bwd_dkv(*args)
    d_offs = np.zeros(offs.shape, dtype=jax.dtypes.float0)  # int input: float0 cotangent
    return (dq.astype(dtypes[0]), dk.astype(dtypes[1]), dv.astype(dtypes[2]),
            d_offs)


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
                    kv_offset=0, block_q=None, block_k=None, interpret=None,
                    kernel_layout=False):
    """Flash attention. Layout [b, s, h, d] (paddle flash-attn convention) or
    [b, h, s, d] with ``kernel_layout=True``. Differentiable (custom VJP with
    FA2-style blockwise recompute). ``block_q`` / ``block_k`` name a tile
    for all three kernels; left out, each kernel takes ``tile_rule``'s."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = _default_interpret()
    if not kernel_layout:
        q, k, v = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    offs = _offsets(q_offset, kv_offset)
    out = _flash(q, k, v, offs, causal, sm_scale, block_q, block_k,
                 interpret)
    if not kernel_layout:
        out = jnp.swapaxes(out, 1, 2)
    return out


def flash_attention_with_lse(q, k, v, causal=True, sm_scale=None, q_offset=0,
                             kv_offset=0, block_q=None, block_k=None,
                             interpret=None):
    """Kernel-layout [b, h, s, d] flash attention returning (out, lse) for
    online-softmax merging across KV shards (ring attention)."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = _default_interpret()
    offs = _offsets(q_offset, kv_offset)
    return _flash_with_lse(q, k, v, offs, causal, sm_scale, block_q, block_k,
                           interpret)

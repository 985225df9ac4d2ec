"""Weight-only int8 matmul Pallas kernel (reference analogue: the int8
inference path of ``paddle/fluid/inference`` + phi int8 GEMM kernels /
weight-only-quant GEMM in the fusion tier; SURVEY.md §2.1, §7.0 "Pallas
(Mosaic) kernels ... quantized" tier).

TPU rationale: weight-only int8 halves (vs bf16) or quarters (vs f32) the
HBM traffic of the GEMM's weight stream — the bound resource for small-batch
decode. The kernel streams int8 weight tiles into VMEM and dequantizes
per-tile (per-output-channel scales) right before the MXU dot, so the full
f32 weight matrix never exists in HBM.

Grid (m, n, k) with k innermost (sequential): f32 accumulator scratch
persists across k steps, output written at the last k step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

def _cdiv(a, b):
    return (a + b - 1) // b


def _kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, k_steps):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)          # dequant: int8 -> f32 tile
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _done():
        scale = s_ref[...][0]                    # [bn] per-channel scales
        o_ref[...] = (acc_ref[...] * scale[None, :]).astype(o_ref.dtype)


def int8_matmul(x, w_int8, scale, block_m=128, block_n=128, block_k=128,
                out_dtype=None, interpret=None):
    """x [M, K] float; w_int8 [K, N] int8; scale [N] f32 (per output channel,
    dequant = int8 * scale). Returns x @ (w_int8 * scale) [M, N]."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    m, kdim = x.shape
    _, n = w_int8.shape
    out_dtype = out_dtype or x.dtype
    block_m = min(block_m, max(m, 8))
    block_n = min(block_n, max(n, 128))
    block_k = min(block_k, max(kdim, 128))
    mp, np_, kp = (_cdiv(m, block_m) * block_m, _cdiv(n, block_n) * block_n,
                   _cdiv(kdim, block_k) * block_k)
    if (mp, kp) != (m, kdim):
        x = jnp.pad(x, ((0, mp - m), (0, kp - kdim)))
    if (kp, np_) != (kdim, n):
        w_int8 = jnp.pad(w_int8, ((0, kp - kdim), (0, np_ - n)))
    if np_ != n:
        scale = jnp.pad(scale, (0, np_ - n))
    k_steps = kp // block_k

    out = pl.pallas_call(
        functools.partial(_kernel, k_steps=k_steps),
        grid=(mp // block_m, np_ // block_n, k_steps),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w_int8, scale[None, :].astype(jnp.float32))
    return out[:m, :n]


def quantize_weight(w):
    """f32 [K, N] -> (int8 [K, N], scale [N]) symmetric per-output-channel
    (abs-max over the reduction axis K)."""
    amax = jnp.max(jnp.abs(w), axis=0)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w / scale[None, :]), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)

"""Fused transformer ops (reference: ``paddle/phi/kernels/fusion/`` —
``fused_rope``, ``fused_rms_norm``, ``fused_swiglu``; Python surface
``paddle.incubate.nn.functional``, SURVEY.md §2.1/§2.2 "Incubate").

TPU-native: each "fused" op is expressed as plain jax.numpy — XLA fuses the
elementwise chains into the surrounding matmuls (SURVEY.md §7.0: the CUDA
fusion tier maps to XLA fusion + Pallas for the rest), so there is nothing to
hand-fuse here except keeping the ops in one traced region. One exception,
under differentiation: a chain with a transcendental that feeds SEVERAL
products is cloned into each product's operand and recomputed on every pass
of its tiling, so ``fused_swiglu``'s rules fence their results (below).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..autograd.tape import apply


def rope_freqs(head_dim, max_position, base=10000.0, dtype=jnp.float32):
    """Precompute RoPE cos/sin tables of shape [max_position, head_dim]."""
    inv = 1.0 / (base ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_position, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)                      # [S, D/2]
    emb = jnp.concatenate([freqs, freqs], axis=-1)  # [S, D] (neox layout)
    return jnp.cos(emb).astype(dtype), jnp.sin(emb).astype(dtype)


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def fused_rotary_position_embedding(q, k=None, v=None, sin=None, cos=None,
                                    position_ids=None,
                                    use_neox_rotary_style=True):
    """paddle.incubate.nn.functional.fused_rotary_position_embedding.

    q/k/v layout [batch, seq, heads, head_dim]; cos/sin [max_pos, head_dim]
    (or broadcastable). Returns rotated (q, k, v) — entries None where the
    input was None.
    """
    def rot(x, cs, sn, pos):
        if x is None:
            return None
        s = x.shape[1]
        if pos is not None:
            cs = jnp.take(cs, pos, axis=0)      # [b, s, d] or [s, d]
            sn = jnp.take(sn, pos, axis=0)
        else:
            cs, sn = cs[:s], sn[:s]
        cs = jnp.expand_dims(cs, -2)             # [.., s, 1, d]
        sn = jnp.expand_dims(sn, -2)
        while cs.ndim < x.ndim:                  # prepend batch dims
            cs, sn = cs[None], sn[None]
        if use_neox_rotary_style:
            return x * cs + _rotate_half(x) * sn
        # GPT-J interleaved style
        x1, x2 = x[..., ::2], x[..., 1::2]
        c2, s2 = cs[..., ::2], sn[..., ::2]
        o1 = x1 * c2 - x2 * s2
        o2 = x2 * c2 + x1 * s2
        return jnp.stack([o1, o2], axis=-1).reshape(x.shape)

    def fn(*ts):
        it = iter(ts)
        qq = next(it)
        kk = next(it) if k is not None else None
        vv = next(it) if v is not None else None
        return tuple(x for x in (
            rot(qq, cos, sin, position_ids),
            rot(kk, cos, sin, position_ids),
            vv) if x is not None)

    args = [t for t in (q, k, v) if t is not None]
    out = apply(fn, *args, op_name="fused_rope")
    out = list(out) if isinstance(out, (tuple, list)) else [out]
    res = []
    for t in (q, k, v):
        res.append(out.pop(0) if t is not None else None)
    return tuple(res)


# -- fused_swiglu: the worked example for the custom-op extension API
# (utils.register_op — the TPU-native PD_BUILD_OP; reference: fused_bias_act
# swiglu backward kernel). Three functions:
#   * ``_swiglu_primal``: what an undifferentiated call compiles (serving);
#   * ``_swiglu_fwd`` / ``_swiglu_vjp``: the rules under differentiation.
# Under differentiation every result is a chain with a transcendental that
# feeds matrix products (``down_proj`` forward and weight gradient; the
# ``gate_proj`` / ``up_proj`` input and weight gradients). XLA's TPU
# pipeline keeps such a chain in float32 and clones it into EACH consumer
# product's operand, where it is recomputed on every pass of the product's
# tiling. So each rule computes its chain once (float32 arithmetic), rounds
# once to the inputs' dtype and fences the array with
# ``optimization_barrier``: a product then reads a ready-made operand.
# Residuals are the two inputs alone; the backward pass recomputes the
# sigmoid in its single pass over them.

def _swiglu_primal(a, g):
    s = 1.0 / (1.0 + jnp.exp(-a))
    return jnp.asarray(a * s * g, a.dtype)


def _swiglu_fwd(a, g):
    wide = jnp.promote_types(a.dtype, jnp.float32)
    af, gf = a.astype(wide), g.astype(wide)
    s = 1.0 / (1.0 + jnp.exp(-af))
    out = (af * s * gf).astype(a.dtype)
    return jax.lax.optimization_barrier(out), (a, g)


def _swiglu_vjp(res, cot):
    a, g = res
    wide = jnp.promote_types(a.dtype, jnp.float32)
    af, gf, cf = a.astype(wide), g.astype(wide), cot.astype(wide)
    s = 1.0 / (1.0 + jnp.exp(-af))
    d_silu = s * (1.0 + af * (1.0 - s))       # d/da [a*sigmoid(a)]
    return jax.lax.optimization_barrier(
        ((cf * gf * d_silu).astype(a.dtype), (cf * af * s).astype(g.dtype)))


_fused_swiglu_op = None


def _swiglu_registered():
    global _fused_swiglu_op
    if _fused_swiglu_op is None:
        from ..utils.custom_op import register_op
        _fused_swiglu_op = register_op(_swiglu_fwd, name="fused_swiglu",
                                       vjp=_swiglu_vjp,
                                       primal=_swiglu_primal, override=True)
    return _fused_swiglu_op


def fused_swiglu(x, gate=None):
    """swiglu(x, gate) = silu(x) * gate (paddle.incubate fused_swiglu)."""
    if gate is None:
        x, gate = apply(lambda a: tuple(jnp.split(a, 2, axis=-1)), x,
                        op_name="swiglu_split")
    return _swiglu_registered()(x, gate)


def jax_silu(a):
    return a * (1.0 / (1.0 + jnp.exp(-a)))

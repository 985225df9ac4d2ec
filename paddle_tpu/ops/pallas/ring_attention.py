"""Ring flash attention — context parallelism over a mesh axis.

Reference analogue: PaddleNLP's ``RingFlashAttention`` built on core Paddle's
sep/cp comm group + ``batch_isend_irecv`` p2p KV rotation + the FA2 kernel's
``softmax_lse`` output (SURVEY.md §2.3 "CP / ring attention", §5.7 mechanism 3).

TPU-native design (SURVEY.md §5.7 "TPU-native plan"): runs inside
``shard_map`` over the 'sep' axis. Each device holds a sequence shard of
Q/K/V; KV shards rotate around the ring with ``lax.ppermute`` (lowered to ICI
neighbor exchanges) while each step's partial attention comes from the Pallas
flash kernel (``flash_attention_with_lse``) with *global* causal offsets, and
partials merge with the online-softmax combine. The whole loop is unrolled in
the trace (ring size is a static mesh-axis size) so XLA overlaps each
ppermute with the next step's compute.

Gradients: the flash kernel has a custom VJP and ppermute/merge are
differentiable, so ``jax.grad`` through this function yields the ring
backward (reverse rotation) automatically.

Note on load balance: with pure causal masking, later ring ranks do more
useful work per step (the classic ring-attention skew). The standard fix —
zigzag/striped sequence placement — is a data-layout choice left to the
caller; masking here stays exact for any offsets.
"""
from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention_with_lse, mha_reference, NEG_INF

#: PADDLE_SEP_RING_IMPL values (as PADDLE_TPU_PAGED_IMPL's): "auto"
#: and "kernel" run the Pallas flash kernel (Mosaic on a TPU backend,
#: interpret mode off it); "xla" forces the pure reference.
SEP_RING_IMPLS = ("auto", "kernel", "xla")


def sep_ring_impl():
    v = os.environ.get("PADDLE_SEP_RING_IMPL", "auto").lower()
    if v not in SEP_RING_IMPLS:
        raise ValueError(f"PADDLE_SEP_RING_IMPL {v!r} not in "
                         f"{SEP_RING_IMPLS}")
    return v


def _merge(out, lse, out_i, lse_i):
    """Online-softmax merge of two normalized partials (kernel layout)."""
    new_lse = jnp.logaddexp(lse, lse_i)
    w = jnp.exp(lse - new_lse)[..., None]
    w_i = jnp.exp(lse_i - new_lse)[..., None]
    return out * w + out_i * w_i, new_lse


def ring_partial(q, k, v, q_offset, kv_offset, sm_scale, impl=None,
                 interpret=None):
    """One ring step: normalized partial + lse for q (kernel layout
    [b, h, sq, d], global position ``q_offset``) against one KV block at
    global position ``kv_offset``, causal. Tiering matches
    ragged_paged_attention: ``auto``/``kernel`` route through
    ``flash_attention_with_lse`` (Mosaic on a TPU backend,
    interpret-pallas off it); ``xla`` is the zero-Pallas reference."""
    if impl is None:
        impl = sep_ring_impl()
    if impl == "xla":
        return mha_reference(q, k, v, causal=True, sm_scale=sm_scale,
                             q_offset=q_offset, kv_offset=kv_offset,
                             with_lse=True)
    return flash_attention_with_lse(q, k, v, causal=True,
                                    sm_scale=sm_scale, q_offset=q_offset,
                                    kv_offset=kv_offset,
                                    interpret=interpret)


def blockwise_causal_attention(q, q_offset, kv_blocks, sm_scale=None,
                               impl=None, interpret=None):
    """The ring-attention schedule run block-sequentially on one host:
    causal attention of ``q`` (kernel layout [b, h, sq, d] at global
    position ``q_offset``) over ``kv_blocks`` — a list of ``(k, v,
    kv_offset)`` tuples, each one ring step — merged with the
    online-softmax combine. Fully-masked blocks contribute lse=-inf and
    drop out of the merge exactly. This is the single-process stand-in
    for the sep-ring: block ``i`` is what replica ``i % sep_ways`` would
    compute, and because every block partial is a fixed-shape kernel
    call, the compiled-program set stays bounded by the stripe shape."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    out = jnp.zeros(q.shape, jnp.float32)
    lse = jnp.full(q.shape[:3], NEG_INF, jnp.float32)
    if impl is None:
        impl = sep_ring_impl()
    for k, v, kv_offset in kv_blocks:
        out_i, lse_i = ring_partial(q, k, v, q_offset, kv_offset,
                                    sm_scale, impl=impl,
                                    interpret=interpret)
        out, lse = _merge(out, lse, out_i.astype(jnp.float32), lse_i)
    return out.astype(q.dtype)


def ring_flash_attention(q, k, v, axis_name="sep", causal=True, sm_scale=None,
                         axis_size=None, interpret=None, use_kernel=True):
    """Blockwise ring attention over ``axis_name``; call inside shard_map/jit.

    q/k/v: local sequence shards, paddle layout [b, s_local, h, d].
    ``axis_size`` must be the static mesh-axis size (defaults to the global
    mesh's); ``use_kernel=False`` computes per-step partials with the pure-XLA
    reference instead of the Pallas kernel (debug/CPU path).
    """
    if axis_size is None:
        from ...distributed import mesh as mesh_mod
        axis_size = mesh_mod.axis_size(axis_name)
    n = int(axis_size)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])

    # -> kernel layout [b, h, s, d]
    q = jnp.swapaxes(q, 1, 2)
    k = jnp.swapaxes(k, 1, 2)
    v = jnp.swapaxes(v, 1, 2)
    s_local = q.shape[2]
    idx = jax.lax.axis_index(axis_name)
    q_off = idx * s_local

    out = jnp.zeros(q.shape, jnp.float32)
    lse = jnp.full(q.shape[:3], NEG_INF, jnp.float32)
    k_cur, v_cur = k, v
    perm = [(i, (i + 1) % n) for i in range(n)]

    for step in range(n):
        kv_idx = (idx - step) % n
        kv_off = kv_idx * s_local
        if use_kernel:
            out_i, lse_i = flash_attention_with_lse(
                q, k_cur, v_cur, causal=causal, sm_scale=sm_scale,
                q_offset=q_off, kv_offset=kv_off, interpret=interpret)
        else:
            out_i, lse_i = mha_reference(
                q, k_cur, v_cur, causal=causal, sm_scale=sm_scale,
                q_offset=q_off, kv_offset=kv_off, with_lse=True)
        out, lse = _merge(out, lse, out_i.astype(jnp.float32), lse_i)
        if step < n - 1:
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)

    return jnp.swapaxes(out.astype(q.dtype), 1, 2)

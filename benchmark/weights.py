"""Seeded weights for a Llama-family configuration, made on the device.

One table of leaves (name, shape, kind) in the order the program's
``named_parameters()`` yields them, and one jitted function that fills all of
them from ``--seed`` in the type they are run in. The driver writes the
arrays into the program's model; the plain reference calls the same function
again after the program's state is freed, so it takes nothing the program
made. Imports nothing of the program.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def leaf_table(cfg):
    """[(name, shape, kind)]; kind is 'norm' (ones) or 'matrix' (random).
    Linear weights are [in, out], the program's (Paddle's) layout."""
    h, m, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    d = cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    leaves = [("llama.embed_tokens.weight", (v, h), "matrix")]
    for i in range(cfg["num_hidden_layers"]):
        p = f"llama.layers.{i}."
        leaves += [
            (p + "self_attn.q_proj.weight", (h, nq * d), "matrix"),
            (p + "self_attn.k_proj.weight", (h, nkv * d), "matrix"),
            (p + "self_attn.v_proj.weight", (h, nkv * d), "matrix"),
            (p + "self_attn.o_proj.weight", (nq * d, h), "matrix"),
            (p + "mlp.gate_proj.weight", (h, m), "matrix"),
            (p + "mlp.up_proj.weight", (h, m), "matrix"),
            (p + "mlp.down_proj.weight", (m, h), "matrix"),
            (p + "input_layernorm.weight", (h,), "norm"),
            (p + "post_attention_layernorm.weight", (h,), "norm"),
        ]
    leaves += [("llama.norm.weight", (h,), "norm"),
               ("lm_head.weight", (h, v), "matrix")]
    return leaves


def param_count(cfg):
    return sum(math.prod(s) for _, s, _ in leaf_table(cfg))


def seed_key(seed):
    """A key from any whole number up to 2**63: the low 31 bits seed it and
    the rest is folded in, so seeds past 2**31 neither overflow nor alias."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def leaf(key, i, shape, kind, std, dtype):
    if kind == "norm":
        return jnp.ones(shape, dtype)
    # uniform with the configuration's initializer_range as its standard
    # deviation, from 16 random bits a value: the cheapest draw that fills
    # 3.8 B bf16 weights in one call
    bits = jax.random.bits(jax.random.fold_in(key, i), shape, jnp.uint16)
    u = bits.astype(jnp.float32) * (1.0 / 65535.0) - 0.5
    return (u * (std * math.sqrt(12.0))).astype(dtype)


@functools.lru_cache(maxsize=None)
def _maker(table, std, dtype_name, shardings):
    dtype = jnp.dtype(dtype_name)

    def make(key):
        return [leaf(key, i, shape, kind, std, dtype)
                for i, (_, shape, kind) in enumerate(table)]

    return jax.jit(make, out_shardings=shardings)


def make_weights(cfg, seed, dtype="bfloat16", shardings=None):
    """All leaves of ``leaf_table(cfg)`` in one jitted call."""
    table = tuple(leaf_table(cfg))
    if shardings is not None:
        shardings = tuple(shardings)
    fn = _maker(table, float(cfg["initializer_range"]), dtype, shardings)
    return fn(seed_key(seed))

"""Seeded weights for a SmallThinker-family configuration, made on the
device: ``weights_deepseek_v3.py``'s scheme (one table of leaves named as
the program's ``named_parameters()`` names them; leaf ``i`` drawn from
``fold_in(key, i)`` whoever asks, so the whole table in one call, for the
program's model, and one layer's leaves at a time, for the plain reference,
give the same arrays). Only the experts the configuration holds
(``held_experts = [first, count]``; all of them where the key is absent)
are drawn, stacked ``[count, in, out]``. Imports nothing of the program.

Kinds: ``norm`` (ones), ``matrix`` (uniform with the configuration's
``initializer_range`` as standard deviation, in the run's type), ``router``
(the same, float32: the router has no bias in this family).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.weights import leaf, seed_key


def held(cfg):
    return tuple(cfg.get("held_experts") or
                 (0, cfg["moe_num_primary_experts"]))


def layer_leaves(cfg, p):
    """One decoder layer under the prefix ``p``, in the program's order."""
    h, m, d = cfg["hidden_size"], cfg["moe_ffn_hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    n = held(cfg)[1]
    a, e = p + "self_attn.", p + "experts."
    return [(a + "q_proj.weight", (h, nq * d), "matrix"),
            (a + "k_proj.weight", (h, nkv * d), "matrix"),
            (a + "v_proj.weight", (h, nkv * d), "matrix"),
            (a + "o_proj.weight", (nq * d, h), "matrix"),
            (e + "router", (h, cfg["moe_num_primary_experts"]), "router"),
            (e + "w_gate", (n, h, m), "matrix"),
            (e + "w_up", (n, h, m), "matrix"),
            (e + "w_down", (n, m, h), "matrix"),
            (p + "input_layernorm.weight", (h,), "norm"),
            (p + "post_attention_layernorm.weight", (h,), "norm")]


def layer_prefix(i):
    return f"model.layers.{i}."


def leaf_table(cfg):
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    leaves = [("model.embed_tokens.weight", (v, h), "matrix")]
    for i in range(cfg["num_hidden_layers"]):
        leaves += layer_leaves(cfg, layer_prefix(i))
    return leaves + [("model.norm.weight", (h,), "norm"),
                     ("lm_head.weight", (h, v), "matrix")]


def param_count(cfg):
    return sum(math.prod(s) for _, s, _ in leaf_table(cfg))


@functools.lru_cache(maxsize=None)
def _maker(entries, std, dtype_name):
    """``entries``: ((global leaf index, shape, kind), ...)."""
    dtype = jnp.dtype(dtype_name)

    def make(key):
        return [leaf(key, i, shape, "matrix" if kind == "router" else kind,
                     std, jnp.float32 if kind == "router" else dtype)
                for i, shape, kind in entries]

    return jax.jit(make)


def _make(cfg, seed, dtype, entries):
    return _maker(tuple(entries), float(cfg["initializer_range"]),
                  dtype)(seed_key(seed))


def make_weights(cfg, seed, dtype="bfloat16"):
    """Every leaf of ``leaf_table(cfg)``, in its order, in one call."""
    return _make(cfg, seed, dtype, [
        (i, shape, kind) for i, (_, shape, kind) in
        enumerate(leaf_table(cfg))])


def make_group(cfg, seed, prefix, dtype="bfloat16"):
    """{name without ``prefix``: array} of the leaves under ``prefix``:
    the same arrays ``make_weights`` gives them."""
    picked = [(i, name, shape, kind) for i, (name, shape, kind) in
              enumerate(leaf_table(cfg)) if name.startswith(prefix)]
    arrs = _make(cfg, seed, dtype, [(i, s, k) for i, _, s, k in picked])
    return {name[len(prefix):]: a for (_, name, _, _), a in
            zip(picked, arrs)}

"""Seeded weights for a DeepSeek-V3-family configuration, made on the device.

One table of leaves (name, shape, kind), named as the program's
``named_parameters()`` names them, and jitted functions that fill them from
``--seed``. Leaf ``i`` of the table is drawn from ``fold_in(key, i)``
whoever asks for it, so the whole table in one call (the driver, into the
program's model) and one group of leaves at a time (the plain reference,
layer by layer, after the program's state is freed) give the same arrays.
Only the experts the configuration holds (``held_experts = [first, count]``)
are drawn, stacked ``[count, in, out]``. Imports nothing of the program.

Kinds: ``norm`` (ones), ``matrix`` (uniform with the configuration's
``initializer_range`` as standard deviation, in the run's type), ``router``
(the same, float32), ``router_bias`` (uniform with ``router_bias_std``,
float32: small and non-zero, so that choosing on ``s + b`` and weighing on
``s`` differ).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.weights import leaf, seed_key


def attention_leaves(cfg, p):
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    a = p + "self_attn."
    return [(a + "q_a_proj.weight", (h, qr), "matrix"),
            (a + "q_a_layernorm.weight", (qr,), "norm"),
            (a + "q_b_proj.weight", (qr, nh * (nope + rope)), "matrix"),
            (a + "kv_a_proj_with_mqa.weight", (h, kvr + rope), "matrix"),
            (a + "kv_a_layernorm.weight", (kvr,), "norm"),
            (a + "kv_b_proj.weight", (kvr, nh * (nope + vd)), "matrix"),
            (a + "o_proj.weight", (nh * vd, h), "matrix")]


def mlp_leaves(p, h, m):
    return [(p + "gate_proj.weight", (h, m), "matrix"),
            (p + "up_proj.weight", (h, m), "matrix"),
            (p + "down_proj.weight", (m, h), "matrix")]


def layer_leaves(cfg, p, moe):
    """One decoder layer under the prefix ``p``, in the program's order."""
    h, m = cfg["hidden_size"], cfg["moe_intermediate_size"]
    leaves = attention_leaves(cfg, p)
    if moe:
        held = cfg["held_experts"][1]
        e = p + "mlp.experts."
        leaves += [(e + "router", (h, cfg["n_routed_experts"]), "router"),
                   (e + "router_bias", (cfg["n_routed_experts"],),
                    "router_bias"),
                   (e + "w_gate", (held, h, m), "matrix"),
                   (e + "w_up", (held, h, m), "matrix"),
                   (e + "w_down", (held, m, h), "matrix")]
        leaves += mlp_leaves(p + "mlp.shared_experts.", h,
                             m * cfg["n_shared_experts"])
    else:
        leaves += mlp_leaves(p + "mlp.", h, cfg["intermediate_size"])
    return leaves + [(p + "input_layernorm.weight", (h,), "norm"),
                     (p + "post_attention_layernorm.weight", (h,), "norm")]


def layer_prefix(i):
    return f"model.layers.{i}."


def leaf_table(cfg):
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    leaves = [("model.embed_tokens.weight", (v, h), "matrix")]
    for i in range(cfg["num_hidden_layers"]):
        leaves += layer_leaves(cfg, layer_prefix(i),
                               i >= cfg["first_k_dense_replace"])
    leaves += [("model.norm.weight", (h,), "norm"),
               ("lm_head.weight", (h, v), "matrix")]
    for j in range(cfg.get("num_nextn_predict_layers", 0)):
        p = f"mtp.{j}."
        leaves += [(p + "hnorm.weight", (h,), "norm"),
                   (p + "enorm.weight", (h,), "norm"),
                   (p + "eh_proj.weight", (2 * h, h), "matrix")]
        leaves += layer_leaves(cfg, p + "block.", True)
    return leaves


def param_count(cfg):
    return sum(math.prod(s) for _, s, _ in leaf_table(cfg))


def _draw(key, i, shape, kind, cfg, dtype):
    if kind == "router":
        return leaf(key, i, shape, "matrix", cfg["initializer_range"],
                    jnp.float32)
    if kind == "router_bias":
        return leaf(key, i, shape, "matrix", cfg["router_bias_std"],
                    jnp.float32)
    return leaf(key, i, shape, kind, cfg["initializer_range"], dtype)


@functools.lru_cache(maxsize=None)
def _maker(entries, std, bias_std, dtype_name):
    """``entries``: ((global leaf index, shape, kind), ...)."""
    cfg = {"initializer_range": std, "router_bias_std": bias_std}
    dtype = jnp.dtype(dtype_name)

    def make(key):
        return [_draw(key, i, shape, kind, cfg, dtype)
                for i, shape, kind in entries]

    return jax.jit(make)


def _make(cfg, seed, dtype, entries):
    fn = _maker(tuple(entries), float(cfg["initializer_range"]),
                float(cfg["router_bias_std"]), dtype)
    return fn(seed_key(seed))


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

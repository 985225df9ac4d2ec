"""Seeded weights for a bailing-hybrid configuration (Ling-3.0-flash), made
on the device: ``weights_deepseek_v3.py``'s scheme (one table of leaves named
as the program's ``named_parameters()`` names them, leaf ``i`` drawn from
``fold_in(key, i)`` whoever asks for it, only the held experts drawn) with
this family's leaves. Imports nothing of the program.

Kinds beside ``norm`` (ones), ``matrix``, ``router`` (float32) and
``router_bias`` (uniform with ``router_bias_std``, float32): ``conv`` (the
depthwise convolution's weight a tap and channel: uniform with standard
deviation ``1 / sqrt(taps)``, so that the convolution keeps its input's
scale), ``a_log`` (uniform in ``[ln 0.5, ln 2]``, float32) and ``dt_bias``
(uniform in ``[-8, 1]``, float32): with the decay gate's logits ``x W_f`` of
about unit variance a channel's ``g = -5 sigmoid(e^{A_log} (x W_f +
dt_bias))`` then spreads from about -4.7 to about -1e-3 (a step's decay from
0.01 to 0.999) and is pinned to neither end.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.weights import leaf, seed_key

#: the (low, high) of the two uniform draws of a KDA layer's decay gate
A_LOG_RANGE = (math.log(0.5), math.log(2.0))
DT_BIAS_RANGE = (-8.0, 1.0)


def layer_kinds(cfg):
    return cfg["layer_kinds"]


def held(cfg):
    """(first, count) of the experts the configuration holds."""
    return tuple(cfg.get("held_experts") or (0, cfg["num_experts"]))


def mlp_leaves(p, h, m):
    return [(p + "gate_proj.weight", (h, m), "matrix"),
            (p + "up_proj.weight", (h, m), "matrix"),
            (p + "down_proj.weight", (m, h), "matrix")]


def kda_leaves(cfg, p):
    h, nh, d = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["head_dim"])
    a = p + "linear_attn."
    return [(a + "conv_weight", (cfg["short_conv_kernel_size"], 3 * nh * d),
             "conv"),
            (a + "A_log", (nh,), "a_log"),
            (a + "dt_bias", (nh * d,), "dt_bias"),
            (a + "q_proj.weight", (h, nh * d), "matrix"),
            (a + "k_proj.weight", (h, nh * d), "matrix"),
            (a + "v_proj.weight", (h, nh * d), "matrix"),
            (a + "f_proj.weight", (h, nh * d), "matrix"),
            (a + "b_proj.weight", (h, nh), "matrix"),
            (a + "g_proj.weight", (h, nh * d), "matrix"),
            (a + "o_norm.weight", (d,), "norm"),
            (a + "o_proj.weight", (nh * d, h), "matrix")]


def mla_leaves(cfg, p):
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    rank, nope, rope, vd = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                            cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    a = p + "self_attn."
    return [(a + "q_proj.weight", (h, nh * (nope + rope)), "matrix"),
            (a + "kv_a_proj_with_mqa.weight", (h, rank + rope), "matrix"),
            (a + "kv_a_layernorm.weight", (rank,), "norm"),
            (a + "kv_b_proj.weight", (rank, nh * (nope + vd)), "matrix"),
            (a + "g_proj.weight", (h, nh), "matrix"),
            (a + "o_proj.weight", (nh * vd, h), "matrix")]


def layer_leaves(cfg, p, kind, dense):
    """One decoder layer under the prefix ``p``, in the program's order."""
    h, m = cfg["hidden_size"], cfg["moe_intermediate_size"]
    leaves = [(p + "input_layernorm.weight", (h,), "norm")]
    leaves += (mla_leaves if kind == "mla" else kda_leaves)(cfg, p)
    leaves += [(p + "post_attention_layernorm.weight", (h,), "norm")]
    if dense:
        return leaves + mlp_leaves(p + "mlp.", h, cfg["intermediate_size"])
    count = held(cfg)[1]
    e = p + "experts."
    leaves += [(e + "router", (h, cfg["num_experts"]), "router"),
               (e + "router_bias", (cfg["num_experts"],), "router_bias"),
               (e + "w_gate", (count, h, m), "matrix"),
               (e + "w_up", (count, h, m), "matrix"),
               (e + "w_down", (count, m, h), "matrix")]
    return leaves + mlp_leaves(
        p + "shared_experts.", h,
        cfg["moe_shared_expert_intermediate_size"]
        * cfg["num_shared_experts"])


def layer_prefix(i):
    return f"model.layers.{i}."


def leaf_table(cfg):
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    leaves = [("model.embed_tokens.weight", (v, h), "matrix")]
    for i, kind in enumerate(layer_kinds(cfg)):
        leaves += layer_leaves(cfg, layer_prefix(i), kind,
                               i < cfg["first_k_dense_replace"])
    return leaves + [("model.norm.weight", (h,), "norm"),
                     ("lm_head.weight", (h, v), "matrix")]


def param_count(cfg):
    return sum(math.prod(s) for _, s, _ in leaf_table(cfg))


def _uniform(key, i, shape, lo, hi):
    return jax.random.uniform(jax.random.fold_in(key, i), shape, jnp.float32,
                              lo, hi)


def _draw(key, i, shape, kind, std, bias_std, taps, dtype):
    if kind == "router":
        return leaf(key, i, shape, "matrix", std, jnp.float32)
    if kind == "router_bias":
        return leaf(key, i, shape, "matrix", bias_std, jnp.float32)
    if kind == "conv":
        return leaf(key, i, shape, "matrix", taps ** -0.5, dtype)
    if kind == "a_log":
        return _uniform(key, i, shape, *A_LOG_RANGE)
    if kind == "dt_bias":
        return _uniform(key, i, shape, *DT_BIAS_RANGE)
    return leaf(key, i, shape, kind, std, dtype)


@functools.lru_cache(maxsize=None)
def _maker(entries, std, bias_std, taps, dtype_name):
    """``entries``: ((global leaf index, shape, kind), ...)."""
    dtype = jnp.dtype(dtype_name)

    def make(key):
        return [_draw(key, i, shape, kind, std, bias_std, taps, dtype)
                for i, shape, kind in entries]

    return jax.jit(make)


def _make(cfg, seed, dtype, entries):
    fn = _maker(tuple(entries), float(cfg["initializer_range"]),
                float(cfg["router_bias_std"]),
                int(cfg["short_conv_kernel_size"]), dtype)
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

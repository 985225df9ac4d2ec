"""Plain reference for the DeepSeek-V3 family (``model_type: deepseek_v3``;
GigaChat3.1-702B-A36B is the same block), written from the published layer
equations:

* attention, every layer: ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb`` ->
  heads x (nope + rope); ``[c_kv | k_r] = x W_kva``, ``c_kv = RMSNorm(c_kv)``,
  ``k_r`` ONE rope head shared by all heads; ``[k_nope | v] = c_kv W_kvb`` ->
  heads x (nope + v). Rope on ``q_rope`` and ``k_r``: pairs ``(x[2i],
  x[2i+1])`` rotated by the position's angle ``i`` (the published code
  de-interleaves, then rotates halves: the same products), yarn
  frequencies. Scores ``(q_nope.k_nope + q_rope.k_r) * (nope + rope)^-0.5 *
  m^2`` with ``m = 0.1 * mscale_all_dim * ln(factor) + 1``; causal softmax;
  heads x v -> ``W_o``. EXPANDED form only: no cache, no absorbed weights;
* feed-forward: SwiGLU in the leading dense layers; after them ``s =
  sigmoid(x W_r)`` over all routed experts; the choice on ``s + b``: the
  experts in ``n_group`` groups, a group's score the sum of its two best,
  the best ``topk_group`` groups kept, the best ``num_experts_per_tok``
  experts among them; weights ``s_i / sum(s_chosen) *
  routed_scaling_factor``; output = the chosen experts' SwiGLU, summed by a
  plain loop over the experts (a mask a expert, every token through every
  expert: no sort, no grouped product) + the shared expert on every token;
* multi-token prediction: ``h' = [RMSNorm(h_t) | RMSNorm(Emb(x_{t+1}))]
  W_p``, one further expert layer, the shared final norm and head.

Departures from the published model, each the configuration's own cut: only
the experts ``held_experts = [first, count]`` exist, and what the absent
ones would add is left out (the router still ranks all of them); the
vocabulary is the configuration's slice.

Float32 arithmetic with every matrix product at ``Precision.HIGHEST``; no
kernel, no cache, no batching. It imports nothing of the program: weights
come as plain dicts of arrays, made again from the seed one layer at a time
(``benchmark.weights_deepseek_v3.make_group``), so the whole model never
stands in memory at once. ``quant`` is the control of "how correct is
decided" (as ``reference/llama.py``): every linear layer's operands
rounded to int8; the router stays float32, as a deployment would keep it.
``quant="bf16"`` is no control but a reading: every linear layer's operands
and every layer's results rounded to bf16, the configuration's own
precision, to tell what that precision alone does to the served tokens;
with ``routing`` (the float32 pass's chosen experts forced on it) it tells
how much of that comes from router choices that flip on rounding.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights_deepseek_v3 as weights_mod
from benchmark.reference import llama as _llama
from benchmark.reference.llama import HI

#: queries a block of the attention: one head's scores stand as [block, s]
QUERY_BLOCK = 2048


def _bf16(x):
    """``x`` rounded to bf16's 8 bits, kept in float32 (``reduce_precision``:
    XLA may drop a pair of converts)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _linear(x, w, quant):
    if quant != "bf16":
        return _llama._linear(x, w, quant)
    return _bf16(jnp.matmul(_bf16(x), _bf16(w.astype(jnp.float32)),
                            precision=HI))


def _rms_norm(x, w, eps, quant=None):
    if quant != "bf16":
        return _llama._rms_norm(x, w, eps, quant)
    return _bf16(_llama._rms_norm(x, w, eps))


def yarn_inv_freq(cfg):
    """The rope frequencies [rope / 2] and the scale of the cos / sin
    tables, after the published ``DeepseekV3YarnRotaryEmbedding``."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    sc = cfg.get("rope_scaling")
    extra = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not sc:
        return extra, 1.0
    factor, orig = float(sc["factor"]), sc["original_max_position_embeddings"]

    def correction(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(correction(sc["beta_fast"])), 0)
    high = min(math.ceil(correction(sc["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv = extra / factor * ramp + extra * (1 - ramp)
    return inv, mscale(factor, sc.get("mscale", 1)) / mscale(
        factor, sc.get("mscale_all_dim", 0))


def mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def softmax_scale(cfg):
    sc = cfg.get("rope_scaling") or {}
    m = mscale(float(sc.get("factor", 1)), sc.get("mscale_all_dim", 0))
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def rope(x, cfg, start=0):
    """``x`` [s, ..., rope]: pairs (2i, 2i+1) rotated at positions
    ``start ..``; returned in the order [even results | odd results]
    (queries and keys alike, so their products are unchanged)."""
    inv, scale = yarn_inv_freq(cfg)
    pos = jnp.arange(start, start + x.shape[0], dtype=jnp.float32)
    ang = pos[:, None] * jnp.asarray(inv, jnp.float32)[None]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (ang.shape[-1],)
    cos = (jnp.cos(ang) * scale).reshape(shape)
    sin = (jnp.sin(ang) * scale).reshape(shape)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _head_attention(q, k, v, scale):
    """One head: q, k [s, d], v [s, dv]; causal; queries in blocks."""
    s = q.shape[0]
    block = min(QUERY_BLOCK, s)
    pad = -s % block
    qb = jnp.pad(q, ((0, pad), (0, 0))).reshape(-1, block, q.shape[-1])
    starts = jnp.arange(qb.shape[0]) * block

    def one(args):
        qi, start = args
        sc = jnp.matmul(qi, k.T, precision=HI) * scale
        rows = start + jnp.arange(block)[:, None]
        sc = jnp.where(jnp.arange(s)[None, :] <= rows, sc, -jnp.inf)
        return jnp.matmul(jax.nn.softmax(sc, -1), v, precision=HI)

    return jax.lax.map(one, (qb, starts)).reshape(-1, v.shape[-1])[:s]


def attention(x, w, cfg, quant=None):
    """``x`` [s, hidden] (already input-normed) -> [s, hidden]."""
    s = x.shape[0]
    nh, nope, rp, vd, rank = (
        cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
        cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"])
    eps = cfg["rms_norm_eps"]
    c_q = _rms_norm(_linear(x, w["self_attn.q_a_proj.weight"], quant),
                    w["self_attn.q_a_layernorm.weight"], eps, quant)
    q = _linear(c_q, w["self_attn.q_b_proj.weight"], quant).reshape(
        s, nh, nope + rp)
    kva = _linear(x, w["self_attn.kv_a_proj_with_mqa.weight"], quant)
    c_kv = _rms_norm(kva[:, :rank], w["self_attn.kv_a_layernorm.weight"],
                     eps, quant)
    k_r = rope(kva[:, rank:], cfg)                            # [s, rope]
    kv = _linear(c_kv, w["self_attn.kv_b_proj.weight"], quant).reshape(
        s, nh, nope + vd)
    qh = jnp.concatenate([q[..., :nope], rope(q[..., nope:], cfg)], -1)
    kh = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r[:, None, :], (s, nh, rp))], -1)
    out = jax.lax.map(
        lambda a: _head_attention(*a, softmax_scale(cfg)),
        (qh.transpose(1, 0, 2), kh.transpose(1, 0, 2),
         kv[..., nope:].transpose(1, 0, 2)))                  # [nh, s, vd]
    return _linear(out.transpose(1, 0, 2).reshape(s, nh * vd),
                   w["self_attn.o_proj.weight"], quant)


def swiglu(x, gate, up, down, quant=None):
    g = _linear(x, gate, quant)
    return _linear(g * jax.nn.sigmoid(g) * _linear(x, up, quant), down,
                   quant)


def _route(x, w_router, bias, cfg):
    """-> (chosen experts [s, k], weights [s, k], margins [s, 2]), float32
    throughout. ``margins``: by how much of a choice score the last kept
    group beats the first dropped one, and the last chosen expert the best
    one left among the kept groups: a token whose margins are small is one
    whose choice a rounding upstream can flip."""
    s = x.shape[0]
    ng, kept, k = cfg["n_group"], cfg["topk_group"], \
        cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(jnp.matmul(x, w_router.astype(jnp.float32),
                                       precision=HI))
    choice = scores + bias.astype(jnp.float32)[None]
    groups = choice.reshape(s, ng, -1)
    group_score = jnp.sort(groups, -1)[..., -2:].sum(-1)      # two best
    # the kept groups by rank, ties to the lower index as ``top_k`` breaks
    # them: rank = how many groups beat this one
    beats = (group_score[:, None, :] > group_score[:, :, None]) | (
        (group_score[:, None, :] == group_score[:, :, None])
        & (jnp.arange(ng)[None, None, :] < jnp.arange(ng)[None, :, None]))
    kept_mask = beats.sum(-1) < kept
    masked = jnp.where(kept_mask[:, :, None], groups, -jnp.inf).reshape(s, -1)
    idx = jnp.argsort(-masked, axis=-1, stable=True)[:, :k]
    wts = jnp.take_along_axis(scores, idx, -1)
    if cfg.get("norm_topk_prob", True):
        wts = wts / (wts.sum(-1, keepdims=True) + 1e-20)
    by_group = -jnp.sort(-group_score, -1)
    by_expert = -jnp.sort(-masked, -1)
    margins = jnp.stack(
        [by_group[:, kept - 1] - by_group[:, kept] if kept < ng
         else jnp.full(s, jnp.inf),
         by_expert[:, k - 1] - by_expert[:, k]], -1)
    return idx, wts * cfg["routed_scaling_factor"], margins


def route(x, w_router, bias, cfg):
    """-> (chosen experts [s, k], weights [s, k])."""
    return _route(x, w_router, bias, cfg)[:2]


def moe(x, w, cfg, quant=None, forced=None, seen=False):
    """The held experts' part of the routed sum + the shared expert.
    ``forced`` [s, k]: experts chosen elsewhere, weighed by this pass's own
    scores. With ``seen`` -> (output, chosen experts, margins)."""
    idx, wts, margins = _route(x, w["mlp.experts.router"],
                               w["mlp.experts.router_bias"], cfg)
    if forced is not None:
        scores = jax.nn.sigmoid(jnp.matmul(
            x, w["mlp.experts.router"].astype(jnp.float32), precision=HI))
        idx, wts = forced, jnp.take_along_axis(scores, forced, -1)
        if cfg.get("norm_topk_prob", True):
            wts = wts / (wts.sum(-1, keepdims=True) + 1e-20)
        wts = wts * cfg["routed_scaling_factor"]
    first, count = cfg["held_experts"]
    out = swiglu(x, w["mlp.shared_experts.gate_proj.weight"],
                 w["mlp.shared_experts.up_proj.weight"],
                 w["mlp.shared_experts.down_proj.weight"], quant)
    for e in range(count):                # a plain loop over the experts
        weight = jnp.sum(jnp.where(idx == first + e, wts, 0.0), -1)
        out = out + weight[:, None] * swiglu(
            x, w["mlp.experts.w_gate"][e], w["mlp.experts.w_up"][e],
            w["mlp.experts.w_down"][e], quant)
    return (out, idx, margins) if seen else out


def layer(h, w, cfg, is_moe, quant=None, forced=None):
    """-> (the layer's output, its routing ``(chosen experts, margins)``;
    ``None`` for a dense layer)."""
    eps = cfg["rms_norm_eps"]
    keep = _bf16 if quant == "bf16" else (lambda a: a)
    h = keep(h + attention(
        _rms_norm(h, w["input_layernorm.weight"], eps, quant), w, cfg, quant))
    x = _rms_norm(h, w["post_attention_layernorm.weight"], eps, quant)
    if is_moe:
        out, idx, margins = moe(x, w, cfg, quant, forced, seen=True)
        return keep(h + out), (idx, margins)
    return keep(h + swiglu(
        x, w["mlp.gate_proj.weight"], w["mlp.up_proj.weight"],
        w["mlp.down_proj.weight"], quant)), None


@functools.lru_cache(maxsize=None)
def _jitted_layer(cfg_items, is_moe, quant):
    cfg = _thaw(cfg_items)
    return jax.jit(functools.partial(layer, cfg=cfg, is_moe=is_moe,
                                     quant=quant))


def _freeze(cfg):
    """The keys the mathematics reads, hashable (a jit a configuration)."""
    keys = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "kv_lora_rank", "rms_norm_eps", "rope_theta",
            "n_group", "topk_group", "num_experts_per_tok",
            "routed_scaling_factor", "norm_topk_prob")
    sc = cfg.get("rope_scaling")
    return tuple((k, cfg[k]) for k in keys if k in cfg) + (
        ("held_experts", tuple(cfg["held_experts"])),
        ("rope_scaling", tuple(sorted(sc.items())) if sc else None))


def _thaw(items):
    cfg = dict(items)
    cfg["held_experts"] = list(cfg["held_experts"])
    cfg["rope_scaling"] = (dict(cfg["rope_scaling"])
                           if cfg["rope_scaling"] else None)
    return cfg


def hidden_states(cfg, group, ids, quant=None, routing=None, seen=None):
    """ids [s] -> (final-norm hidden states [s, hidden], the last layer's
    output before that norm), float32. ``group(prefix)`` gives the leaves
    under ``prefix`` as {short name: array}; it is called for one layer at a
    time and its arrays are dropped before the next. ``seen`` (a list)
    receives each expert layer's ``(chosen experts, margins)``; ``routing``
    (such a list, of another pass) forces that pass's choices on this one."""
    frozen = _freeze(cfg)
    h = group("model.embed_tokens.")["weight"][ids].astype(jnp.float32)
    forced = iter(routing or ())
    for i in range(cfg["num_hidden_layers"]):
        is_moe = i >= cfg["first_k_dense_replace"]
        w = group(weights_mod.layer_prefix(i))
        h, routed = _jitted_layer(frozen, is_moe, quant)(
            h, w, forced=next(forced)[0] if is_moe and routing else None)
        if routed is not None and seen is not None:
            seen.append(routed)
        del w
    norm = group("model.norm.")["weight"]
    return _rms_norm(h, norm, cfg["rms_norm_eps"], quant), h


def logits(cfg, group, ids, at=None, quant=None, routing=None, seen=None):
    """Next-token logits [len(at) or s, vocab] over the sliced
    vocabulary."""
    h, _ = hidden_states(cfg, group, jnp.asarray(ids, jnp.int32), quant,
                         routing, seen)
    if at is not None:
        h = h[jnp.asarray(at)]
    return _linear(h, group("lm_head.")["weight"], quant)


def mtp_logits(cfg, group, ids, quant=None):
    """Logits [s - 1, vocab] whose row ``t`` predicts ``x_{t+2}``."""
    ids = jnp.asarray(ids, jnp.int32)
    eps = cfg["rms_norm_eps"]
    _, prenorm = hidden_states(cfg, group, ids, quant)
    emb = group("model.embed_tokens.")["weight"][ids[1:]].astype(jnp.float32)
    w = group("mtp.0.")
    x = _linear(jnp.concatenate(
        [_rms_norm(prenorm[:-1], w["hnorm.weight"], eps, quant),
         _rms_norm(emb, w["enorm.weight"], eps, quant)], -1),
        w["eh_proj.weight"], quant)
    block = {k[len("block."):]: v for k, v in w.items()
             if k.startswith("block.")}
    h, _ = layer(x, block, cfg, True, quant)
    h = _rms_norm(h, group("model.norm.")["weight"], eps, quant)
    return _linear(h, group("lm_head.")["weight"], quant)


def seeded_group(cfg, seed, dtype="bfloat16"):
    """``group`` for :func:`hidden_states`: each call draws its leaves
    again from the seed."""
    return lambda prefix: weights_mod.make_group(cfg, seed, prefix, dtype)


def served_gaps(cfg, seed, sequences, width, quant=None, dtype="bfloat16",
                readings=()):
    """As ``reference/llama.py::served_gaps``: ``sequences`` is [(prompt
    ids, served ids)]; one forward over each prompt with its served tokens,
    right-padded to ``width``. For every served token, how far its
    reference logit lies below the reference's best at that position
    (``served``), and the least router margin of its position over the
    expert layers (``margin``; ``margins`` has them all, [layer][group,
    expert]), and how far a served token altered by one id would lie
    (``altered``). Under the name of ``quant`` and of each of ``readings``
    (``int8``, ``bf16``, and ``bf16_routed``: at bf16 with the float32
    pass's router choices forced on it): how far that pass's first choice
    lies below the best."""
    group = seeded_group(cfg, seed, dtype)
    max_new = max(len(s) for _, s in sequences)
    wanted = tuple(readings) + ((quant,) if quant else ())
    out = {k: [] for k in ("served", "margin", "margins", "altered") + wanted}
    for prompt, served in sequences:
        n, t = len(prompt), len(served)
        ids = np.zeros(width, np.int32)
        ids[:n] = prompt
        ids[n:n + t] = served
        at = np.minimum(np.arange(n - 1, n - 1 + max_new), width - 1)
        seen = []
        ref = np.asarray(logits(cfg, group, ids, at, seen=seen))[:t]
        best = ref.max(-1)
        out["served"] += list(best - ref[np.arange(t), np.asarray(served)])
        margins = (np.stack([np.asarray(m)[at[:t]] for _, m in seen], 1)
                   if seen else np.full((t, 1, 2), np.inf))
        out["margins"] += margins.tolist()
        out["margin"] += list(margins.min((1, 2)))
        nxt = (np.asarray(served) + 1) % ref.shape[-1]
        out["altered"] += list(best - ref[np.arange(t), nxt])
        for name in wanted:
            q, routed, _ = name.partition("_routed")
            low = np.asarray(logits(cfg, group, ids, at, q,
                                    routing=seen if routed else None))[:t]
            out[name] += list(best - ref[np.arange(t), low.argmax(-1)])
    return {k: [float(x) for x in v] if k != "margins" else v
            for k, v in out.items()}

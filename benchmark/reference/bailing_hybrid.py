"""Plain reference for the bailing-hybrid family (``model_type:
bailing_hybrid``; ``inclusionAI/Ling-3.0-flash``), written from the layer
equations of ISSUE 33. Pre-norm residual blocks; ``x`` is a layer's input
after ``input_layernorm`` (RMSNorm); ``H`` heads of ``d``.

* KDA layers (Kimi Delta Attention: Kimi Linear, arXiv:2510.26692; the
  flash-linear-attention library's ``fla/layers/kda.py`` and
  ``fla/ops/kda/naive.py``): ``q~, k~, v~ = x W_q, x W_k, x W_v``; a causal
  depthwise convolution of ``short_conv_kernel_size`` taps on each, then
  SiLU: ``u_t = silu(sum_j w_j u~_{t-3+j})``, zeros before the sequence's
  start; a head: ``q = l2norm(q) d^-1/2``, ``k = l2norm(k)`` (eps 1e-6);
  ``g_t = kda_lower_bound * sigmoid(exp(A_log_h) (x W_f + dt_bias))``, a
  value a channel in ``(-5, 0)``; ``beta_t = sigmoid(x W_b)``, a scalar a
  head; a head's state ``S`` [d keys, d values], zero at the start: ``S' =
  diag(exp(g_t)) S``, ``S = S' + beta_t k_t (v_t - S'^T k_t)^T``, ``o_t =
  S^T q_t``: TOKEN BY TOKEN under ``lax.scan`` (no chunk form, no cache);
  ``o = rmsnorm_head(o) * sigmoid(x W_g)``, then ``W_o``.
* MLA layers, EXPANDED form: ``q = x W_q`` (no query rank) -> heads x (nope
  + rope); ``[c | k_r] = x W_kva``, ``c = RMSNorm(c)``; ``[k_n | v] = c
  W_kvb``; interleaved rope (pairs ``(x[2i], x[2i+1])``, theta as
  configured, no scaling) on ``q_r`` and the one shared ``k_r``; scores times
  ``(nope + rope)^-1/2``; causal softmax; a head's output times ``sigmoid(x
  W_hg)_h``; ``W_o``.
* feed-forward: SwiGLU in the leading dense layer; after it DeepSeek-V3's
  router (sigmoid scores, the choice on score + bias, groups scored by the
  sum of their two best, ``topk_group`` groups kept, ``num_experts_per_tok``
  experts, weights the chosen scores normalised then times
  ``routed_scaling_factor``) over ALL ``num_experts``, SwiGLU experts, one
  shared expert on every token. Only the experts ``held_experts = [first,
  count]`` exist: what an absent one would add is left out. A token goes
  through ITS routed experts only: an expert after another, each over the
  rows the router gave it, gathered into a buffer as long as the fullest
  expert's rows (a power of two, read from the routing before the experts
  run, so no token is ever dropped), and added back.

What the published ``config.json`` leaves open, each listed under the
configuration file's ``assumed`` with these sources:

* ``use_qk_norm`` on a KDA layer is the l2 norm above (fla/layers/kda.py
  normalises q and k inside the kernel, ``use_qk_l2norm_in_kernel``); on an
  MLA layer it is the latent's RMSNorm alone (DeepSeek-V3's
  ``kv_a_layernorm``);
* ``no_kda_lora``: ``W_f`` and ``W_g`` are full-rank (fla's are low-rank
  pairs; the config says not);
* ``kda_safe_gate`` with ``kda_lower_bound``: the bounded gate above (Kimi
  Linear's later checkpoints; fla's ``kda_gate`` with ``lower_bound``);
* ``num_kv_heads_for_linear_attn`` 0: as many as query heads;
* ``group_norm_size`` 1: the output norm is a head's own RMSNorm, one
  weight over ``d``;
* ``max_window_layers``: unused (no layer has a window);
* the router's bias is drawn with ``router_bias_std`` and the router runs in
  float32 (PERF.md, PR 27); ``initializer_range`` 0.02;
* ``A_log``, ``dt_bias`` and the convolution's weights: the draws of
  ``benchmark/weights_bailing_hybrid.py``.

Float32 arithmetic with every matrix product at ``Precision.HIGHEST``. It
imports nothing of the program: weights come as plain dicts of arrays, made
again from the seed one layer at a time. ``quant="int8"`` is the control of
"how correct is decided" (``reference/llama.py``): every linear layer's
operands rounded to int8; the router, the gates' logits' additive terms and
the recurrence stay float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights_bailing_hybrid as weights_mod
from benchmark.reference import deepseek_v3 as ds
from benchmark.reference.llama import HI, _linear, _rms_norm
from benchmark.reference.smallthinker import fit_width, logit_rms


def _l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def recurrence(q, k, v, g, beta):
    """The gated delta rule token by token from a zero state: ``q, k, v, g``
    [s, H, d], ``beta`` [s, H] -> ``o`` [s, H, d]."""
    h, d = q.shape[1:]

    def one(state, x):
        qt, kt, vt, gt, bt = x
        state = jnp.exp(gt)[:, :, None] * state
        seen = jnp.einsum("hkv,hk->hv", state, kt, precision=HI)
        state = state + kt[:, :, None] * (bt[:, None] * (vt - seen))[:, None]
        return state, jnp.einsum("hkv,hk->hv", state, qt, precision=HI)

    return jax.lax.scan(one, jnp.zeros((h, d, d), jnp.float32),
                        (q, k, v, g, beta))[1]


def kda(x, w, cfg, quant=None):
    """``x`` [s, hidden] (already input-normed) -> [s, hidden]."""
    s = x.shape[0]
    nh, d, taps = (cfg["num_attention_heads"], cfg["head_dim"],
                   cfg["short_conv_kernel_size"])
    a = "linear_attn."
    qkv = jnp.concatenate([_linear(x, w[a + n + "_proj.weight"], quant)
                           for n in "qkv"], -1)
    conv = w[a + "conv_weight"].astype(jnp.float32)
    padded = jnp.pad(qkv, ((taps - 1, 0), (0, 0)))
    u = sum(conv[j] * padded[j:j + s] for j in range(taps))
    u = u * jax.nn.sigmoid(u)
    q, k, v = (t.reshape(s, nh, d) for t in jnp.split(u, 3, -1))
    q, k = _l2norm(q) * d ** -0.5, _l2norm(k)
    f = _linear(x, w[a + "f_proj.weight"], quant) + w[a + "dt_bias"]
    g = cfg["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(w[a + "A_log"])[:, None] * f.reshape(s, nh, d))
    beta = jax.nn.sigmoid(_linear(x, w[a + "b_proj.weight"], quant))
    o = _rms_norm(recurrence(q, k, v, g, beta), w[a + "o_norm.weight"],
                  cfg["rms_norm_eps"])
    gate = jax.nn.sigmoid(_linear(x, w[a + "g_proj.weight"], quant))
    return _linear(o.reshape(s, nh * d) * gate, w[a + "o_proj.weight"],
                   quant)


def mla(x, w, cfg, quant=None):
    """``x`` [s, hidden] (already input-normed) -> [s, hidden]."""
    s = x.shape[0]
    nh, nope, rp, vd, rank = (
        cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
        cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"])
    a = "self_attn."
    q = _linear(x, w[a + "q_proj.weight"], quant).reshape(s, nh, nope + rp)
    kva = _linear(x, w[a + "kv_a_proj_with_mqa.weight"], quant)
    c_kv = _rms_norm(kva[:, :rank], w[a + "kv_a_layernorm.weight"],
                     cfg["rms_norm_eps"])
    k_r = ds.rope(kva[:, rank:], cfg)
    kv = _linear(c_kv, w[a + "kv_b_proj.weight"], quant).reshape(
        s, nh, nope + vd)
    qh = jnp.concatenate([q[..., :nope], ds.rope(q[..., nope:], cfg)], -1)
    kh = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r[:, None, :], (s, nh, rp))], -1)
    out = jax.lax.map(
        lambda t: ds._head_attention(*t, (nope + rp) ** -0.5),
        (qh.transpose(1, 0, 2), kh.transpose(1, 0, 2),
         kv[..., nope:].transpose(1, 0, 2)))                  # [nh, s, vd]
    gate = jax.nn.sigmoid(_linear(x, w[a + "g_proj.weight"], quant))
    return _linear((out.transpose(1, 0, 2) * gate[:, :, None]).reshape(
        s, nh * vd), w[a + "o_proj.weight"], quant)


def mixer_half(h, w, cfg, kind, quant=None):
    """-> (the residual stream after the mixer, the post-attention norm's
    output, the router's choice ``(experts, weights, margins)``)."""
    eps = cfg["rms_norm_eps"]
    x = _rms_norm(h, w["input_layernorm.weight"], eps)
    h = h + (mla if kind == "mla" else kda)(x, w, cfg, quant)
    y = _rms_norm(h, w["post_attention_layernorm.weight"], eps)
    if "experts.router" not in w:
        return h, y, None
    return h, y, ds._route(y, w["experts.router"], w["experts.router_bias"],
                           cfg)


def dense_half(h, y, w, quant=None):
    return h + ds.swiglu(y, w["mlp.gate_proj.weight"],
                         w["mlp.up_proj.weight"],
                         w["mlp.down_proj.weight"], quant)


def expert_half(h, y, idx, wts, w, held, rows, quant=None):
    """The shared expert on every token + the held experts, each over ITS
    tokens only: ``rows`` is the length of the buffer an expert's tokens
    are gathered into (at least the fullest expert's)."""
    s = y.shape[0]
    first, _ = held
    out = ds.swiglu(y, w["shared_experts.gate_proj.weight"],
                    w["shared_experts.up_proj.weight"],
                    w["shared_experts.down_proj.weight"], quant)
    padded = jnp.concatenate([y, jnp.zeros((1, y.shape[1]), y.dtype)])

    def one(out, x):
        e, w_gate, w_up, w_down = x
        weight = jnp.sum(jnp.where(idx == first + e, wts, 0.0), -1)
        mine = jnp.nonzero(jnp.any(idx == first + e, -1), size=rows,
                           fill_value=s)[0]
        part = ds.swiglu(padded[mine], w_gate, w_up, w_down, quant)
        weight = jnp.concatenate([weight, jnp.zeros(1)])[mine]
        return out.at[mine].add(weight[:, None] * part, mode="drop"), None

    count = w["experts.w_gate"].shape[0]
    out, _ = jax.lax.scan(one, out, (
        jnp.arange(count), w["experts.w_gate"], w["experts.w_up"],
        w["experts.w_down"]))
    return h + out


def _freeze(cfg):
    """The keys the mathematics reads, hashable (a jit a configuration)."""
    keys = ("num_attention_heads", "head_dim", "short_conv_kernel_size",
            "kda_lower_bound", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "kv_lora_rank", "rms_norm_eps", "rope_theta",
            "n_group", "topk_group", "num_experts_per_tok",
            "routed_scaling_factor", "norm_topk_prob")
    return tuple((k, cfg[k]) for k in keys) + (("rope_scaling", None),)


@functools.lru_cache(maxsize=None)
def _jitted(piece, cfg_items, *static):
    cfg = dict(cfg_items)
    if piece == "mixer":
        return jax.jit(functools.partial(mixer_half, cfg=cfg, kind=static[0],
                                         quant=static[1]))
    if piece == "dense":
        return jax.jit(functools.partial(dense_half, quant=static[0]))
    return jax.jit(functools.partial(expert_half, held=static[0],
                                     rows=static[1], quant=static[2]))


def fullest_expert_rows(idx, held):
    """The buffer's length for ``expert_half``: the next power of two over
    the most tokens any held expert got (8 at the least)."""
    first, count = held
    local = np.asarray(idx).reshape(-1) - first
    local = local[(local >= 0) & (local < count)]
    most = int(np.bincount(local, minlength=count).max()) if len(local) \
        else 0
    return max(8, 1 << max(most - 1, 0).bit_length())


def hidden_states(cfg, group, ids, quant=None, seen=None):
    """ids [s] -> final-norm hidden states [s, hidden], float32.
    ``group(prefix)`` gives the leaves under ``prefix`` as {short name:
    array}, one layer at a time. ``seen`` (a list) receives each expert
    layer's router margins [s, 2]."""
    if cfg.get("rope_scaling"):
        raise ValueError("rope scaling is not part of this family")
    frozen, held = _freeze(cfg), weights_mod.held(cfg)
    h = group("model.embed_tokens.")["weight"][ids].astype(jnp.float32)
    for i, kind in enumerate(weights_mod.layer_kinds(cfg)):
        w = group(weights_mod.layer_prefix(i))
        h, y, routed = _jitted("mixer", frozen, kind, quant)(h, w)
        if routed is None:
            h = _jitted("dense", frozen, quant)(h, y, w)
        else:
            idx, wts, margins = routed
            if seen is not None:
                seen.append(margins)
            h = _jitted("experts", frozen, held,
                        fullest_expert_rows(idx, held), quant)(
                h, y, idx, wts, w)
        del w
    return _rms_norm(h, group("model.norm.")["weight"], cfg["rms_norm_eps"])


def logits(cfg, group, ids, at=None, quant=None, seen=None):
    """Next-token logits [len(at) or s, vocab]."""
    h = hidden_states(cfg, group, jnp.asarray(ids, jnp.int32), quant, seen)
    if at is not None:
        h = h[jnp.asarray(at)]
    return _linear(h, group("lm_head.")["weight"], quant)


def seeded_group(cfg, seed, dtype="bfloat16"):
    """``group`` for :func:`hidden_states`: each call draws its leaves
    again from the seed."""
    return lambda prefix: weights_mod.make_group(cfg, seed, prefix, dtype)


def served_gaps(cfg, seed, sequences, widths, quant=None, dtype="bfloat16",
                programs=None):
    """As ``reference/smallthinker.py::served_gaps``: ``sequences`` is
    [(prompt ids, served ids)]; one forward over each prompt with its
    served tokens, right-padded to the shortest of ``widths`` that holds it
    (the model is causal: what stands behind a position does not move it).
    For every served token: how far its reference logit lies below the
    reference's best (``served``), the least router margin of its position
    over the expert layers (``margin``), how far a served token altered by
    one id would lie (``altered``); under ``quant``'s name how far that
    pass's first choice lies below the best. ``programs``: the program's OWN
    logits at the served positions -> ``rms`` (and ``rms_<quant>`` of that
    pass's logits), position by position."""
    group = seeded_group(cfg, seed, dtype)
    out = {k: [] for k in ("served", "margin", "altered")
           + ((quant,) if quant else ())
           + (("rms",) if programs is not None else ())
           + (("rms_" + quant,) if programs is not None and quant else ())}
    for i, (prompt, served) in enumerate(sequences):
        n, t = len(prompt), len(served)
        width = fit_width(n + t, widths)
        ids = np.zeros(width, np.int32)
        ids[:n] = prompt
        ids[n:n + t] = served
        at = np.minimum(np.arange(n - 1, n - 1 + t), width - 1)
        seen = []
        ref = np.asarray(logits(cfg, group, ids, at, seen=seen))
        best = ref.max(-1)
        out["served"] += list(best - ref[np.arange(t), np.asarray(served)])
        out["margin"] += list(np.stack(
            [np.asarray(m)[at] for m in seen], 1).min((1, 2)))
        nxt = (np.asarray(served) + 1) % ref.shape[-1]
        out["altered"] += list(best - ref[np.arange(t), nxt])
        if programs is not None:
            out["rms"] += list(logit_rms(programs[i], ref))
        if quant:
            low = np.asarray(logits(cfg, group, ids, at, quant))
            out[quant] += list(best - ref[np.arange(t), low.argmax(-1)])
            if programs is not None:
                out["rms_" + quant] += list(logit_rms(low, ref))
    return {k: [float(x) for x in v] for k, v in out.items()}

"""Plain reference for the SmallThinker family
(``PowerInfer/SmallThinker-21BA3B-Instruct``), written from the layer
equations of ISSUE 31 and the configuration file's ``assumed``. One layer
over ``x`` [s, hidden], layer ``l``, eps as configured::

    h   = RMSNorm_in(x)
    z   = h W_r                        # float32: the router reads the INPUT
    idx = top_k(z);  p = softmax(z[idx])              # over the kept logits
    q, k, v = h W_q, h W_k, h W_v      # GQA heads of head_dim, no bias
    if rope_layout[l]:  q, k = rotary(q, k)    # rotate-half, whole head_dim
    visible(i, j) = j <= i and (not sliding_window_layout[l]
                                or j > i - sliding_window_size)
    x1  = x + softmax(q k^T / sqrt(head_dim) | visible) v W_o
    g   = RMSNorm_post(x1)
    out = x1 + sum_{e in idx} p_e W_down^e (relu(W_gate^e g) * (W_up^e g))

then a final RMSNorm and the untied head. Departures from what
``config.json`` states, each an assumption the configuration lists: the
router's input is the output of ``input_layernorm`` (the family's report,
arXiv:2507.20984, and its published modelling code place the router before
attention); no attention bias, no q/k norm; the window keeps keys ``j > i -
sliding_window_size`` (that many keys with the query's own: the Mistral
convention); top-k on the logits and a softmax over the kept ones
(``moe_primary_router_apply_softmax``), after which ``norm_topk_prob`` has
nothing to do. Only the experts ``held_experts = [first, count]`` exist
where the configuration gives that key (all of them otherwise): the router
ranks every expert, and what an absent one would add is left out.

Float32 arithmetic with every matrix product at ``Precision.HIGHEST``; no
kernel, no cache, no batching; attention one head and one block of queries
at a time; the experts by a plain loop (one after another, a mask an
expert, every token through every expert: no sort, no grouped product). It imports nothing of
the program: weights come as plain dicts of arrays, made again from the
seed one layer at a time (``benchmark.weights_smallthinker.make_group``),
so the 5.6 B parameters never stand in memory at once. ``quant="int8"`` is
the control of "how correct is decided" (``reference/llama.py``): every
linear layer's operands rounded to int8; the router stays float32, as a
deployment would keep it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights_smallthinker as weights_mod
from benchmark.reference.llama import HI, _linear, _rms_norm, _rope

#: queries a block of the attention: one head's scores stand as [block, s]
QUERY_BLOCK = 2048


def _head_attention(q, k, v, window):
    """One KV head and its query group: q [g, s, d], k / v [s, d]; causal,
    and under a ``window`` only the last ``window`` keys a query; queries
    in blocks."""
    g, s, d = q.shape
    block = min(QUERY_BLOCK, s)
    pad = -s % block
    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0))).reshape(
        g, -1, block, d).transpose(1, 0, 2, 3)          # [blocks, g, block, d]
    starts = jnp.arange(qb.shape[0]) * block

    def one(args):
        qi, start = args
        sc = jnp.einsum("gqd,td->gqt", qi, k, precision=HI) / jnp.sqrt(
            jnp.float32(d))
        rows = start + jnp.arange(block)[:, None]
        cols = jnp.arange(s)[None, :]
        visible = cols <= rows
        if window is not None:
            visible &= cols > rows - window
        sc = jnp.where(visible[None], sc, -jnp.inf)
        return jnp.einsum("gqt,td->gqd", jax.nn.softmax(sc, -1), v,
                          precision=HI)

    out = jax.lax.map(one, (qb, starts))                # [blocks, g, block, d]
    return out.transpose(1, 0, 2, 3).reshape(g, -1, d)[:, :s]


def attention(x, w, cfg, use_rope, window, quant=None):
    """``x`` [s, hidden] (already input-normed) -> [s, hidden]."""
    s = x.shape[0]
    nq, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    q = _linear(x, w["self_attn.q_proj.weight"], quant).reshape(s, nq, d)
    k = _linear(x, w["self_attn.k_proj.weight"], quant).reshape(s, nkv, d)
    v = _linear(x, w["self_attn.v_proj.weight"], quant).reshape(s, nkv, d)
    if use_rope:
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    qg = q.reshape(s, nkv, nq // nkv, d).transpose(1, 2, 0, 3)
    out = jax.lax.map(lambda a: _head_attention(*a, window),
                      (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return _linear(out.transpose(2, 0, 1, 3).reshape(s, nq * d),
                   w["self_attn.o_proj.weight"], quant)


def route(h, w_router, cfg):
    """-> (chosen experts [s, k], weights [s, k], margin [s]): the best
    ``k`` logits, a softmax over them, and by how much of a logit the last
    kept expert beats the first dropped one: a token whose margin is small
    is one whose choice a rounding upstream can flip. Ties go to the lower
    index, as ``top_k`` breaks them."""
    k = cfg["moe_num_active_primary_experts"]
    z = jnp.matmul(h, w_router.astype(jnp.float32), precision=HI)
    order = jnp.argsort(-z, axis=-1, stable=True)
    ranked = jnp.take_along_axis(z, order, -1)
    return (order[:, :k], jax.nn.softmax(ranked[:, :k], -1),
            ranked[:, k - 1] - ranked[:, k])


def experts(g, idx, wts, w, cfg, quant=None):
    """The held experts' part of the routed sum, by a plain loop: one
    expert after another (a sequential ``scan``, so that 64 experts are one
    compiled body and not 64), every token through each, weighed by what
    the router gave that expert for the token (0 where it was not chosen)."""
    first, count = weights_mod.held(cfg)

    def one(out, e_and_weights):
        e, w_gate, w_up, w_down = e_and_weights
        weight = jnp.sum(jnp.where(idx == first + e, wts, 0.0), -1)
        gate = _linear(g, w_gate, quant)
        up = _linear(g, w_up, quant)
        return out + weight[:, None] * _linear(
            jnp.maximum(gate, 0.0) * up, w_down, quant), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(g), (
        jnp.arange(count), w["experts.w_gate"], w["experts.w_up"],
        w["experts.w_down"]))
    return out


def layer(x, w, cfg, use_rope, window, quant=None):
    """-> (the layer's output, its router's margins [s])."""
    eps = cfg["rms_norm_eps"]
    h = _rms_norm(x, w["input_layernorm.weight"], eps)
    idx, wts, margin = route(h, w["experts.router"], cfg)
    x1 = x + attention(h, w, cfg, use_rope, window, quant)
    g = _rms_norm(x1, w["post_attention_layernorm.weight"], eps)
    return x1 + experts(g, idx, wts, w, cfg, quant), margin


def _freeze(cfg):
    """The keys the mathematics reads, hashable (a jit a configuration)."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta", "moe_num_active_primary_experts",
            "moe_num_primary_experts")
    return tuple((k, cfg[k]) for k in keys) + (
        ("held_experts", weights_mod.held(cfg)),)


@functools.lru_cache(maxsize=None)
def _jitted_layer(cfg_items, use_rope, window, quant):
    return jax.jit(functools.partial(layer, cfg=dict(cfg_items),
                                     use_rope=use_rope, window=window,
                                     quant=quant))


def window_of(cfg, i):
    return cfg["sliding_window_size"] if cfg["sliding_window_layout"][i] \
        else None


def hidden_states(cfg, group, ids, quant=None, seen=None):
    """ids [s] -> final-norm hidden states [s, hidden], float32.
    ``group(prefix)`` gives the leaves under ``prefix`` as {short name:
    array}; it is called for one layer at a time and its arrays are dropped
    before the next. ``seen`` (a list) receives each layer's margins."""
    frozen = _freeze(cfg)
    x = group("model.embed_tokens.")["weight"][ids].astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        w = group(weights_mod.layer_prefix(i))
        x, margin = _jitted_layer(frozen, bool(cfg["rope_layout"][i]),
                                  window_of(cfg, i), quant)(x, w)
        if seen is not None:
            seen.append(margin)
        del w
    return _rms_norm(x, group("model.norm.")["weight"], cfg["rms_norm_eps"])


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


def fit_width(n, widths):
    """The shortest of ``widths`` (one number or several) that holds ``n``
    tokens: a short request is not padded to the longest's length."""
    widths = sorted(np.atleast_1d(widths).tolist())
    return next((w for w in widths if w >= n), widths[-1])


def logit_rms(logits, ref):
    """For each position: the root mean square, over the vocabulary, of how
    far ``logits`` [t, vocab] lie from the reference's ``ref``; NaN where a
    row of ``logits`` is NaN (a position that was not read)."""
    d = np.asarray(logits, np.float32) - ref
    return np.sqrt(np.mean(d * d, axis=-1))


def served_gaps(cfg, seed, sequences, widths, quant=None, dtype="bfloat16",
                programs=None):
    """As ``reference/deepseek_v3.py::served_gaps``: ``sequences`` is
    [(prompt ids, served ids)]; one forward over each prompt with its
    served tokens, right-padded to the shortest of ``widths`` that holds
    it. For every served token: how far its reference logit lies below the
    reference's best at that position (``served``), the least router margin
    of its position over the layers (``margin``), and how far a served
    token altered by one id would lie (``altered``). Under ``quant``'s
    name: how far that pass's first choice lies below the best.

    ``programs``: for each sequence the program's OWN logits at the served
    positions, [served, vocab] with NaN rows where it has none; then
    ``rms`` says, position by position, how far they lie from the
    reference's (:func:`logit_rms`), and ``rms_<quant>`` the same of that
    pass's logits: a number that rounding moves at every position, where
    the tokens' gaps move only where two logits lie close."""
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
            [np.asarray(m)[at] for m in seen], 1).min(1))
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

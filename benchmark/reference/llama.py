"""Plain reference for the Llama family (Mistral-7B is the same block):
RMSNorm pre-norm, rotary embedding in the half-split layout, grouped-query
causal attention, SwiGLU, untied head, next-token cross entropy, AdamW.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``: no kernel, no cache, no batching tricks. It imports
nothing of the program and takes nothing the program made: its weights come
from ``benchmark.weights`` and the seed. Storage types are the
configuration's (bf16 parameters and moments); only the arithmetic is
float32.

``quant`` makes the control of "How correct is decided": the same reference
with the operands of every linear layer rounded to int8 (one scale a token
row for activations, one a column for weights, symmetric), the step down from
bf16 that a v5e's 393 TOP/s int8 peak would tempt. The rounding is
straight-through, so the backward pass keeps its precision. ``"int8"`` keeps
every other operation in float32; ``"bf16_int8"`` also rounds each
operation's result to bf16, as a bf16 program with int8 products would.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import weights as weights_mod

HI = jax.lax.Precision.HIGHEST
LEAVES_PER_LAYER = 9


def _fake_int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)


CONTROLS = (None, "int8", "bf16_int8")


def _act(x, quant):
    """An operation's result as the control keeps it."""
    if quant == "bf16_int8":
        # reduce_precision, not a pair of converts: XLA may drop those
        # (xla_allow_excess_precision), and did on the chip
        return x + jax.lax.stop_gradient(
            jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
            - x)
    return x


def _linear(x, w, quant):
    w = w.astype(jnp.float32)
    if quant not in CONTROLS:
        raise ValueError(f"unknown control precision {quant!r}")
    if quant is not None:
        x, w = _fake_int8(x, -1), _fake_int8(w, 0)
    return _act(jnp.matmul(x, w, precision=HI), quant)


def _rms_norm(x, w, eps, quant=None):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return _act(x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32), quant)


def _rope(x, theta):
    """x [s, heads, d]; positions 0..s-1; half-split (NeoX) layout."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    half = d // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


@jax.checkpoint
def _group_attention(q, k, v):
    """One kv head and its query group: q [g, s, d], k/v [s, d]. Under
    ``checkpoint`` so a backward pass never holds every group's scores."""
    s, d = k.shape
    sc = jnp.einsum("gsd,td->gst", q, k, precision=HI) / jnp.sqrt(
        jnp.float32(d))
    mask = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(mask[None], sc, -jnp.inf)
    return jnp.einsum("gst,td->gsd", jax.nn.softmax(sc, -1), v,
                      precision=HI)


def _layer(h, lw, cfg, quant):
    """h [s, hidden] float32, one sequence."""
    wq, wk, wv, wo, wg, wu, wd, n1, n2 = lw
    nq, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    s = h.shape[0]
    x = _rms_norm(h, n1, cfg["rms_norm_eps"], quant)
    q = _rope(_linear(x, wq, quant).reshape(s, nq, d), cfg["rope_theta"])
    k = _rope(_linear(x, wk, quant).reshape(s, nkv, d), cfg["rope_theta"])
    v = _linear(x, wv, quant).reshape(s, nkv, d)
    qg = q.reshape(s, nkv, nq // nkv, d).transpose(1, 2, 0, 3)
    o = jax.lax.map(lambda a: _group_attention(*a),
                    (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    o = _act(o.transpose(2, 0, 1, 3).reshape(s, nq * d), quant)
    h = _act(h + _linear(o, wo, quant), quant)
    x = _rms_norm(h, n2, cfg["rms_norm_eps"], quant)
    gate = _linear(x, wg, quant)
    act = _act(gate * jax.nn.sigmoid(gate) * _linear(x, wu, quant), quant)
    return _act(h + _linear(act, wd, quant), quant)


def split_leaves(ws, cfg):
    """leaf list -> (embedding, [layer leaves], final norm, head)."""
    n = cfg["num_hidden_layers"]
    layers = [ws[1 + i * LEAVES_PER_LAYER:1 + (i + 1) * LEAVES_PER_LAYER]
              for i in range(n)]
    return ws[0], layers, ws[1 + n * LEAVES_PER_LAYER], ws[-1]


def hidden_states(ws, ids, cfg, quant=None, remat=False):
    """ids [s] -> final-norm hidden states [s, hidden], float32."""
    emb, layers, norm, _ = split_leaves(ws, cfg)
    h = emb[ids].astype(jnp.float32)
    layer = functools.partial(_layer, cfg=cfg, quant=quant)
    if remat:
        layer = jax.checkpoint(layer)
    for lw in layers:
        h = layer(h, lw)
    return _rms_norm(h, norm, cfg["rms_norm_eps"], quant)


def row_loss(ws, ids, labels, cfg, quant=None):
    """Mean next-token cross entropy of one sequence."""
    h = hidden_states(ws, ids, cfg, quant, remat=True)
    logits = _linear(h, ws[-1], quant)
    logp = logits - jax.nn.logsumexp(logits, -1, keepdims=True)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], -1))


# -- training: the first steps of AdamW, as the configuration states them ---

def adamw_update(p, g, m, v, opt):
    """One leaf. Moments and parameters are stored in their own type, the
    arithmetic is float32, no bias correction: the trainer's stated rule."""
    b1, b2, eps, wd, lr = (opt["beta1"], opt["beta2"], opt["eps"],
                           opt["weight_decay"], opt["lr"])
    g = g.astype(jnp.float32)
    mf = b1 * m.astype(jnp.float32) + (1 - b1) * g
    vf = b2 * v.astype(jnp.float32) + (1 - b2) * g * g
    pf = p.astype(jnp.float32)
    pf = pf - lr * (mf / (jnp.sqrt(vf) + eps) + wd * pf)
    return pf.astype(p.dtype), mf.astype(m.dtype), vf.astype(v.dtype)


def _norm(a):
    a = a.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(a * a))


def train_readings(cfg, opt, seed, batches, quant=None, dtype="bfloat16"):
    """Follow ``len(batches)`` steps from the seed's weights. Each batch is
    (ids [b, s], labels [b, s]) as numpy. Returns the readings that decide
    ``correct``: each step's loss, every leaf's gradient norm at the first
    step, and every leaf's norm of change over all the steps."""
    grad_fn = jax.jit(jax.value_and_grad(
        functools.partial(row_loss, cfg=cfg, quant=quant)))

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
    def update(p, g, m, v):
        out = [adamw_update(*leaf, opt) for leaf in zip(p, g, m, v)]
        return tuple(map(list, zip(*out)))

    @jax.jit
    def norms(arrs):
        return jnp.stack([_norm(a) for a in arrs])

    @jax.jit
    def delta_norms(a, b):
        return jnp.stack([_norm(x.astype(jnp.float32) - y.astype(jnp.float32))
                          for x, y in zip(a, b)])

    p = weights_mod.make_weights(cfg, seed, dtype)
    m = [jnp.zeros_like(a) for a in p]
    v = [jnp.zeros_like(a) for a in p]
    losses, grad_norms = [], None
    for ids, labels in batches:
        rows = ids.shape[0]
        loss, acc = 0.0, None
        for r in range(rows):
            l, g = grad_fn(p, jnp.asarray(ids[r], jnp.int32),
                           jnp.asarray(labels[r], jnp.int32))
            loss += float(l) / rows
            if rows == 1:
                acc = g
            else:
                g = [a.astype(jnp.float32) / rows for a in g]
                acc = g if acc is None else [x + y for x, y in zip(acc, g)]
        losses.append(loss)
        if grad_norms is None:
            grad_norms = [float(x) for x in norms(acc)]
        p, m, v = update(p, acc, m, v)
    # the starting weights again from the seed, not a copy kept all along:
    # the steps above need the room
    change = [float(x) for x in delta_norms(
        p, weights_mod.make_weights(cfg, seed, dtype))]
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


# -- serving: logits of served tokens ---------------------------------------

def served_gaps(cfg, seed, sequences, width, quant=None, dtype="bfloat16"):
    """``sequences`` is [(prompt ids, served ids)]. One forward over each
    prompt with its served tokens, right-padded to ``width`` (causal: padding
    cannot reach back). For every served token, how far its reference logit
    lies below the reference's best at that position; with ``quant`` also how
    far the token that the lower precision puts first lies below it, and
    how far a served token altered by one (the next id) would lie.
    Returns {"served": [gaps], "control": [gaps] or None, "altered": [gaps]
    or None}."""
    import numpy as np
    ws = weights_mod.make_weights(cfg, seed, dtype)
    max_new = max(len(s) for _, s in sequences)

    @functools.partial(jax.jit, static_argnames=("q",))
    def logits_at(ws, ids, at, q):
        h = hidden_states(ws, ids, cfg, q)
        return _linear(h[at], ws[-1], q)

    served, control, altered = [], [], []
    for prompt, out in sequences:
        n, t = len(prompt), len(out)
        ids = np.zeros(width, np.int32)
        ids[:n] = prompt
        ids[n:n + t] = out
        # position n-1+i predicts served token i; pad the index list so one
        # program serves every request
        at = np.minimum(np.arange(n - 1, n - 1 + max_new), width - 1)
        ref = np.asarray(logits_at(ws, jnp.asarray(ids), jnp.asarray(at),
                                   None))[:t]
        best = ref.max(-1)
        served += list(best - ref[np.arange(t), np.asarray(out)])
        if quant is not None:
            low = np.asarray(logits_at(ws, jnp.asarray(ids), jnp.asarray(at),
                                       quant))[:t]
            control += list(best - ref[np.arange(t), low.argmax(-1)])
            nxt = (np.asarray(out) + 1) % ref.shape[-1]
            altered += list(best - ref[np.arange(t), nxt])
    return {"served": [float(x) for x in served],
            "control": [float(x) for x in control] if quant else None,
            "altered": [float(x) for x in altered] if quant else None}

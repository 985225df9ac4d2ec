"""Kimi Delta Attention (KDA; Kimi Linear, arXiv:2510.26692): a gated delta
rule with a decay a channel, as two ops of a serving tick.

A head keeps a state ``S`` [d keys, d values] (float32) a sequence. One token
with ``q, k, v`` [d], a log decay ``g`` [d] (``g <= 0``, a value a key
channel) and a write strength ``beta`` (a scalar)::

    S' = diag(exp(g)) S
    S  = S' + beta k (v - S'^T k)^T
    o  = S^T q

``q`` arrives scaled and ``q``, ``k`` normalised: the ops do neither.

* :func:`kda_step`: one token a row for the decode rows of many slots: each
  row's state is read from ``state[slot]``, updated and written back in
  place.
* :func:`kda_chunk`: the prefill spans of a tick, varlen: each span starts
  from ``state[slot]`` and leaves its end state there. The spans' tokens are
  packed into JOBS of ``SUB`` rows (:func:`chunk_plan`, on the host); a job
  is the chunkwise form of the recurrence: with ``G_i = sum_{m<=i} g_m``,
  ``A_ij = beta_i (k_i e^{G_i}) . (k_j e^{-G_j})`` for ``j < i``, ``T = (I +
  A)^-1``, ``W = T (beta K e^G)``, ``U = T (beta V)``, ``V' = U - W S_0``,
  ``o_i = (q_i e^{G_i})^T S_0 + sum_{j<=i} [(q_i e^{G_i}) . (k_j e^{-G_j})]
  v'_j``, ``S_C = diag(e^{G_C}) S_0 + sum_j (k_j e^{G_C - G_j}) v'_j^T``.
  ``e^{-G}`` is what bounds a job to ``SUB`` = 16 rows: the model's gate
  keeps ``g > -5``, so over 16 rows it is at most ``e^80``, which float32
  holds.

Both ops have two forms of one signature: a Pallas kernel (the only tier on
a TPU; ``interpret=True`` runs it anywhere) and a plain-XLA form that walks
the tokens one by one under ``lax.scan`` (what a CPU run uses). In a device
trace the kernels are the Mosaic calls of ``_kda_step_device`` and
``_kda_chunk_device``.

``state`` is ``[slots + 1, heads, d, d]``: the last row is a scratch slot
for padding rows and jobs. Both ops return ``(o [tokens, heads, d] in q's
type, zeros at the rows that were not theirs, state)``; ``state`` is
donated.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["SUB", "kda_recurrence", "kda_step", "kda_chunk", "chunk_plan",
           "chunk_jobs_bound", "step_rows"]

#: rows a job of the chunk kernel: over 16 rows of g > -5, e^{-G} <= e^80
SUB = 16
#: heads a grid step of the step kernel
STEP_HEADS = 8

_HI = jax.lax.Precision.HIGHEST


def kda_recurrence(q, k, v, g, beta, s0=None):
    """The recurrence itself, token by token: ``q, k, v, g`` [T, H, d],
    ``beta`` [T, H], ``s0`` [H, d, d] or None (zeros) -> ``(o [T, H, d]
    float32, S [H, d, d])``."""
    t, h, d = q.shape
    f = jnp.float32
    s0 = jnp.zeros((h, d, d), f) if s0 is None else s0.astype(f)

    def one(s, x):
        qi, ki, vi, gi, bi = x
        s = jnp.exp(gi)[:, :, None] * s
        ks = jnp.einsum("hkv,hk->hv", s, ki, precision=_HI)
        s = s + ki[:, :, None] * (bi[:, None] * (vi - ks))[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, qi, precision=_HI)

    s, o = jax.lax.scan(one, s0, (q.astype(f), k.astype(f), v.astype(f),
                                  g.astype(f), beta.astype(f)))
    return o, s


# -- the host's plan of a tick's prefill spans --------------------------------

def chunk_jobs_bound(tokens, spans):
    """The jobs that ``spans`` spans of more than one token, ``tokens``
    tokens in all, make at the most: a span of ``n`` is ``ceil(n / SUB)``."""
    return tokens // SUB + spans


def chunk_plan(tokens, starts, lens, slots, scratch, jobs=None):
    """The packed layout of a tick's prefill spans, host arrays:

    * ``pack`` [J * SUB]: the flat token each packed row holds, ``tokens``
      (a row of zeros) for a padding row;
    * ``unpack`` [tokens]: each flat token's packed row, ``J * SUB`` for a
      token outside the spans;
    * ``meta`` [2, J]: a job's slot, and 1 where the job starts from the
      slot's state (a span's first; 0: from the job before it).

    ``jobs``: pad the list to this many (with jobs of padding rows on the
    ``scratch`` slot). Also returns the spans' tokens and the jobs they
    need."""
    need = int(sum(-(-int(n) // SUB) for n in lens))
    total = need if jobs is None else int(jobs)
    if need > total:
        raise ValueError(f"{need} jobs do not fit a list of {total}")
    pack = np.full(total * SUB, tokens, np.int32)
    unpack = np.full(tokens, total * SUB, np.int32)
    meta = np.zeros((2, total), np.int32)
    meta[0, :], meta[1, :] = scratch, 1
    j = 0
    for start, n, slot in zip(starts, lens, slots):
        start, n = int(start), int(n)
        nj = -(-n // SUB)
        rows = j * SUB + np.arange(n)
        pack[rows] = start + np.arange(n)
        unpack[start:start + n] = rows
        meta[0, j:j + nj] = slot
        meta[1, j + 1:j + nj] = 0
        j += nj
    return {"pack": pack, "unpack": unpack, "meta": meta,
            "tokens": int(sum(int(n) for n in lens)), "jobs": need}


def step_rows(tokens, rows, slots, scratch, pad_to):
    """``(rows, slots)`` padded to ``pad_to``: a padding row reads the
    zero row ``tokens`` and the ``scratch`` slot."""
    n = len(rows)
    if n > pad_to:
        raise ValueError(f"{n} rows do not fit {pad_to}")
    r = np.full(pad_to, tokens, np.int32)
    s = np.full(pad_to, scratch, np.int32)
    r[:n], s[:n] = rows, slots
    return r, s


def _with_zero_row(*arrays):
    return [jnp.concatenate([a, jnp.zeros((1,) + a.shape[1:], a.dtype)])
            for a in arrays]


# -- one token a row ----------------------------------------------------------

def _step_kernel(slots_ref, a_ref, k_ref, q_ref, bv_ref, b_ref, s_ref,
                 o_ref, s_out_ref):
    """A grid step: one row, ``STEP_HEADS`` heads. ``a`` (the decay), ``k``
    and ``q`` stand as COLUMNS [d, heads] (keys on the sublanes, as in the
    state), ``beta v`` and ``beta`` as rows [heads, d]."""
    del slots_ref
    rows = []
    for h in range(a_ref.shape[-1]):
        a, k, q = (r[0, 0][:, h:h + 1] for r in (a_ref, k_ref, q_ref))
        s = a * s_ref[0, h]                                   # [d, d]
        ks = jnp.sum(k * s, axis=0, keepdims=True)            # [1, d]
        s = s + k * (bv_ref[0, 0][h:h + 1] - b_ref[0, 0][h:h + 1] * ks)
        s_out_ref[0, h] = s
        rows.append(jnp.sum(q * s, axis=0, keepdims=True))
    o_ref[0, 0] = jnp.concatenate(rows, axis=0)


def _kda_step_device(q, k, v, g, beta, state, rows, slots, interpret):
    """Device half of :func:`kda_step` (Pallas)."""
    t, h, d = q.shape
    r = rows.shape[0]
    hb = STEP_HEADS if h % STEP_HEADS == 0 else h
    f = jnp.float32
    qz, kz, vz, gz, bz = _with_zero_row(q, k, v, g, beta)

    def take(a):
        return a[rows].astype(f)

    def cols(a):                                 # [r, h, d] -> [r, hb', d, hb]
        return a.reshape(r, h // hb, hb, d).swapaxes(2, 3)

    def rws(a):
        return a.reshape(r, h // hb, hb, d)

    b = take(bz)[:, :, None]
    col_spec = pl.BlockSpec((1, 1, d, hb), lambda i, j, s: (i, j, 0, 0))
    row_spec = pl.BlockSpec((1, 1, hb, d), lambda i, j, s: (i, j, 0, 0))
    state_spec = pl.BlockSpec((1, hb, d, d), lambda i, j, s: (s[i], j, 0, 0))
    o, state = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(r, h // hb),
            in_specs=[col_spec, col_spec, col_spec, row_spec, row_spec,
                      state_spec],
            out_specs=[row_spec, state_spec]),
        out_shape=[jax.ShapeDtypeStruct((r, h // hb, hb, d), f),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret, name="kda_step",
    )(slots, cols(jnp.exp(take(gz))), cols(take(kz)), cols(take(qz)),
      rws(b * take(vz)), rws(jnp.broadcast_to(b, (r, h, d))), state)
    out = jnp.zeros((t, h, d), q.dtype).at[rows].set(
        o.reshape(r, h, d).astype(q.dtype), mode="drop")
    return out, state


def _kda_step_xla(q, k, v, g, beta, state, rows, slots, interpret=None):
    """Plain-XLA form of :func:`kda_step`: the rows one after another."""
    del interpret
    t, h, d = q.shape
    f = jnp.float32
    qz, kz, vz, gz, bz = _with_zero_row(q, k, v, g, beta)

    def one(state, x):
        row, slot = x
        o, s = kda_recurrence(qz[row][None], kz[row][None], vz[row][None],
                              gz[row][None], bz[row][None], state[slot])
        return state.at[slot].set(s), o[0]

    state, o = jax.lax.scan(one, state, (rows, slots))
    out = jnp.zeros((t, h, d), q.dtype).at[rows].set(o.astype(q.dtype),
                                                     mode="drop")
    return out, state.astype(f)


_STEP = {"pallas": jax.jit(_kda_step_device, static_argnums=(8,),
                           donate_argnums=(5,)),
         "xla": jax.jit(_kda_step_xla, static_argnums=(8,),
                        donate_argnums=(5,))}


def _impl(impl, interpret):
    if impl is None:
        impl = "pallas" if interpret or jax.default_backend() == "tpu" \
            else "xla"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"unknown form {impl!r}")
    return impl, bool(interpret)


def kda_step(q, k, v, g, beta, state, rows, slots, *, impl=None,
             interpret=False):
    """One token a row: ``rows`` [R] are flat token indices (``tokens``:
    a padding row), ``slots`` [R] their states' rows (padding rows: the
    scratch slot). -> ``(o [tokens, H, d], state)``; ``state`` is donated
    and updated in place."""
    impl, interpret = _impl(impl, interpret)
    return _STEP[impl](q, k, v, g, beta, state,
                       jnp.asarray(rows, jnp.int32),
                       jnp.asarray(slots, jnp.int32), interpret)


# -- a tick's prefill spans ---------------------------------------------------

def _chunk_kernel(meta_ref, q_ref, k_ref, bk_ref, bv_ref, g_ref, s_ref,
                  o_ref, s_out_ref, s_scr):
    """A grid step: one head, one job of ``SUB`` rows (module docstring).
    ``g_ref`` holds ``G``, the inclusive sum of ``g`` over the job's rows.
    The products of two rows of the job take their exponents relative to
    the job's middle row (``e^{+-40}`` at the most: ``q e^{G}`` itself
    underflows at ``G = -80``); the products with ``S_0`` take the decays
    themselves."""
    j = pl.program_id(1)

    @pl.when(meta_ref[1, j] == 1)
    def _():
        s_scr[...] = s_ref[0, 0]

    def dot(a, b, dims=((1,), (0,))):
        return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                                   preferred_element_type=jnp.float32)

    nt = ((1,), (1,))
    s0 = s_scr[...]
    q, k, bk, bv, big = (r[...] for r in (q_ref, k_ref, bk_ref, bv_ref,
                                          g_ref))
    c, d = q.shape
    mid, last = big[c // 2 - 1:c // 2], big[c - 1:c]
    down, down_mid, up_mid = (jnp.exp(big), jnp.exp(big - mid),
                              jnp.exp(mid - big))
    ku = k * up_mid
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    # A^T, strictly upper: column i of it is row i of A
    at = jnp.where(row < col, dot(ku, bk * down_mid, nt), 0.0)
    # (I + A) T = I by forward substitution, a row at a time
    t = (row == col).astype(jnp.float32)
    for i in range(1, c):
        a_i = jnp.sum(jnp.where(col == i, at, 0.0), axis=1, keepdims=True)
        new = jnp.sum(a_i * t, axis=0, keepdims=True)
        t = jnp.where(row == i, t - new, t)
    vp = dot(t, bv) - dot(dot(t, bk * down), s0)              # [c, d]
    p = jnp.where(col <= row, dot(q * down_mid, ku, nt), 0.0)
    o_ref[...] = dot(q * down, s0) + dot(p, vp)
    # e^{G_C} as a column, keys on the sublanes
    rd = jax.lax.broadcasted_iota(jnp.int32, (d, d), 0)
    cd = jax.lax.broadcasted_iota(jnp.int32, (d, d), 1)
    el = jnp.sum(jnp.where(rd == cd, jnp.broadcast_to(jnp.exp(last), (d, d)),
                           0.0), axis=1, keepdims=True)
    s_new = el * s0 + dot(k * jnp.exp(last - big), vp, ((0,), (0,)))
    s_scr[...] = s_new
    s_out_ref[0, 0] = s_new


def _kda_chunk_device(q, k, v, g, beta, state, o_init, pack, unpack, meta,
                      interpret):
    """Device half of :func:`kda_chunk` (Pallas): the packed rows ``q``,
    ``k``, ``beta k``, ``beta v`` and ``G`` [J * SUB, H * d] float32."""
    _, h, d = q.shape
    jobs = meta.shape[1]
    p = jobs * SUB
    f = jnp.float32
    qz, kz, vz, gz, bz = (a[pack].astype(f)
                          for a in _with_zero_row(q, k, v, g, beta))
    big = jnp.cumsum(gz.reshape(jobs, SUB, h, d), axis=1)
    b = bz[:, :, None]
    ops = [a.reshape(p, h * d) for a in (qz, kz, b * kz, b * vz, big)]
    tok_spec = pl.BlockSpec((SUB, d), lambda hh, j, m: (j, hh))
    state_spec = pl.BlockSpec((1, 1, d, d), lambda hh, j, m: (m[0, j], hh,
                                                              0, 0))
    o, state = pl.pallas_call(
        _chunk_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(h, jobs),
            in_specs=[tok_spec] * 5 + [state_spec],
            out_specs=[tok_spec, state_spec],
            scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((p, h * d), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret, name="kda_chunk",
    )(meta, *ops, state)
    return _unpack(o.reshape(p, h, d), o_init, unpack, q.dtype), state


def _unpack(o_packed, o_init, unpack, dtype):
    """The spans' rows back in the flat order; ``o_init`` elsewhere."""
    p = o_packed.shape[0]
    oz = jnp.concatenate([o_packed.astype(dtype),
                          jnp.zeros((1,) + o_packed.shape[1:], dtype)])
    mine = (unpack < p)[:, None, None]
    return jnp.where(mine, oz[unpack], o_init.astype(dtype))


def _kda_chunk_xla(q, k, v, g, beta, state, o_init, pack, unpack, meta,
                   interpret=None):
    """Plain-XLA form of :func:`kda_chunk`: a job after another, a job's
    rows one by one (a padding row has ``g = 0, k = 0, beta = 0`` and
    leaves the state as it is)."""
    del interpret
    f = jnp.float32
    qz, kz, vz, gz, bz = (a[pack].astype(f)
                          for a in _with_zero_row(q, k, v, g, beta))
    p, h, d = qz.shape
    jobs = meta.shape[1]

    def job(carry, x):
        state, s_cur = carry
        slot, first, rows = x
        s_in = jnp.where(first == 1, state[slot], s_cur)
        o, s = kda_recurrence(*rows, s_in)
        return (state.at[slot].set(s), s), o

    rows = tuple(a.reshape((jobs, SUB) + a.shape[1:])
                 for a in (qz, kz, vz, gz, bz))
    (state, _), o = jax.lax.scan(
        job, (state, jnp.zeros((h, d, d), f)), (meta[0], meta[1], rows))
    return _unpack(o.reshape(p, h, d), o_init, unpack, q.dtype), \
        state.astype(f)


_CHUNK = {"pallas": jax.jit(_kda_chunk_device, static_argnums=(10,),
                            donate_argnums=(5,)),
          "xla": jax.jit(_kda_chunk_xla, static_argnums=(10,),
                         donate_argnums=(5,))}


def kda_chunk(q, k, v, g, beta, state, plan, o_init=None, *, impl=None,
              interpret=False):
    """The prefill spans of ``plan`` (:func:`chunk_plan`; its arrays may be
    on the device already), each from its slot's state to its slot's state.
    -> ``(o [tokens, H, d]: the spans' rows, ``o_init`` (zeros) at the
    others, state)``; ``state`` is donated."""
    impl, interpret = _impl(impl, interpret)
    if o_init is None:
        o_init = jnp.zeros(q.shape, q.dtype)
    return _CHUNK[impl](q, k, v, g, beta, state, o_init,
                        jnp.asarray(plan["pack"], jnp.int32),
                        jnp.asarray(plan["unpack"], jnp.int32),
                        jnp.asarray(plan["meta"], jnp.int32), interpret)

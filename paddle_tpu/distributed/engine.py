"""Jitted SPMD pipeline engine (reference: the 1F1B / interleaved schedules
of ``python/paddle/distributed/fleet/meta_parallel/pipeline_parallel.py`` +
the p2p activation exchange in ``pp_utils/p2p_communication.py``; SURVEY.md
§2.3 "PP", §3.4, §7.1 M4, §7.3 item 2).

TPU-native design: instead of per-rank processes exchanging tensors with
``batch_isend_irecv``, the whole pipeline is ONE jitted SPMD program over the
'pp' mesh axis:

* every stage's weights are the same pytree stacked on a leading axis,
  sharded ``P('pp')`` — each device holds its stage's slice;
* a ``lax.scan`` over ``n_micro + n_stages - 1`` ticks runs the classic
  skewed schedule: at tick ``t`` the device at stage ``s`` works on
  microbatch ``t - s`` (masked during the bubble), then hands its activation
  to stage ``s+1`` with ``lax.ppermute`` — the ICI neighbor exchange;
* the backward pass is ``jax.grad`` through the scan: the transpose of
  ``ppermute`` is the reverse rotation, so XLA derives the cooldown
  backward schedule and overlaps transfers with compute automatically.

Constraint (same as the reference's p2p tensor-meta contract): every stage
maps activations to ONE pytree of shapes/dtypes — any pytree (tuples/dicts
of arrays), but uniform across stages; per-stage shape variance must be
padded by the caller (lockstep SPMD rotates one buffer structure). Bubble
fraction matches 1F1B: ``(S-1) / (M + S-1)`` for S stages, M microbatches.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _chunk_key(base_key, micro_idx, chunk_id):
    """Deterministic per-(microbatch, chunk) PRNG key — the reference's
    ``RNGStatesTracker`` contract (``fleet/layers/mpu/random.py``): each
    microbatch × pipeline chunk draws an independent, schedule-invariant
    stream, so a pipelined run with dropout reproduces the sequential
    run bit-for-bit given the same base key."""
    import jax.random as jrandom
    return jrandom.fold_in(jrandom.fold_in(base_key, micro_idx), chunk_id)


def pipeline_spmd(stage_fn, n_stages, n_micro, axis_name="pp",
                  with_keys=False):
    """Per-device pipelined runner (call inside shard_map over ``axis_name``).

    ``stage_fn(stage_params, x) -> y`` applies ONE stage (y.shape == x.shape).
    The returned ``run(stacked_params, micro_inputs)`` expects the local pp
    shard of the [S, ...]-stacked params (leading dim 1) and replicated
    ``micro_inputs`` [M, mb, ...]; it returns the last stage's outputs
    [M, mb, ...], broadcast to every pp rank.

    ``with_keys=True`` changes the contracts to
    ``stage_fn(stage_params, x, key)`` / ``run(..., base_key)`` —
    each tick's call receives the deterministic per-(microbatch, stage)
    key, so stochastic blocks (dropout) are supported.
    """

    def run(stacked_params, micro_inputs, base_key=None):
        params = jax.tree.map(lambda a: a[0], stacked_params)
        stage = jax.lax.axis_index(axis_name)
        m = jax.tree.leaves(micro_inputs)[0].shape[0]
        ticks = m + n_stages - 1
        perm = [(i, i + 1) for i in range(n_stages - 1)]
        is_last = stage == n_stages - 1
        tmap = jax.tree.map

        def tick(carry, t):
            recv, out_buf = carry
            idx = t - stage                     # my microbatch this tick
            active = jnp.logical_and(idx >= 0, idx < m)
            feed = tmap(lambda a: a[jnp.clip(t, 0, m - 1)], micro_inputs)
            x = tmap(lambda f, r: jnp.where(stage == 0, f, r), feed, recv)
            if with_keys:
                key = _chunk_key(base_key, jnp.clip(idx, 0, m - 1), stage)
                y = stage_fn(params, x, key)
            else:
                y = stage_fn(params, x)
            y = tmap(lambda a: jnp.where(active, a, jnp.zeros_like(a)), y)
            slot = jnp.clip(idx, 0, m - 1)
            write = jnp.logical_and(active, is_last)
            out_buf = tmap(lambda b, a: jnp.where(write, b.at[slot].set(a),
                                                  b), out_buf, y)
            recv_next = tmap(lambda a: jax.lax.ppermute(a, axis_name, perm),
                             y)
            return (recv_next, out_buf), None

        out_buf = tmap(jnp.zeros_like, micro_inputs)
        recv0 = tmap(lambda a: jnp.zeros(a.shape[1:], a.dtype), micro_inputs)
        (_, out_buf), _ = jax.lax.scan(tick, (recv0, out_buf),
                                       jnp.arange(ticks))
        # only the last stage wrote non-zeros; broadcast across pp ranks
        return tmap(lambda a: jax.lax.psum(a, axis_name), out_buf)

    return run


def pipeline_spmd_1f1b_bwd(stage_fn, n_stages, n_micro, axis_name="pp",
                           with_keys=False):
    """Per-device interleaved fwd-recompute/backward runner — the memory
    half of the reference's 1F1B schedule
    (``fleet/meta_parallel/pipeline_parallel.py``: steady state holds at
    most S in-flight activations per rank, vs GPipe's M).

    Differentiating :func:`pipeline_spmd` with ``jax.grad`` reproduces
    1F1B's *bubble* but not its *memory*: the scan saves every tick's
    stage residuals, so peak activation memory is O(M·S). This runner is
    the explicit alternative used as the backward of a ``custom_vjp``
    (see :func:`_forward_1f1b`): ONE scan of ``M + 2(S-1)`` ticks where
    every tick recomputes one microbatch's forward (rematerialisation —
    the TPU-native trade of FLOPs for HBM) and back-propagates another,
    keeping stage-input activations in a ``2S-1``-slot ring buffer that
    forward writes and backward releases. Peak memory is
    O(S)·microbatch + one tick's residuals, independent of M.

    Tick math (stage ``s``, microbatch ``j``): forward fires at tick
    ``j + s`` (same skew as the forward scan), backward at tick
    ``j + 2(S-1) - s`` — cotangents enter at the last stage and ride
    the reverse ``ppermute`` one hop per tick. A ring slot is reused
    only after ``2S-1`` microbatches, strictly after its release.
    """

    def run(stacked_params, micro_inputs, d_out, base_key=None):
        import jax.random as jrandom
        params = jax.tree.map(lambda a: a[0], stacked_params)
        stage = jax.lax.axis_index(axis_name)
        m = jax.tree.leaves(micro_inputs)[0].shape[0]
        s_n = n_stages
        ring_n = 2 * s_n - 1
        ticks = m + 2 * (s_n - 1)
        perm_up = [(i, i + 1) for i in range(s_n - 1)]
        perm_dn = [(i + 1, i) for i in range(s_n - 1)]
        is_last = stage == s_n - 1
        const_key = jrandom.PRNGKey(0)
        tmap = jax.tree.map

        def apply(p, x, key):
            return stage_fn(p, x, key) if with_keys else stage_fn(p, x)

        def tick(carry, t):
            recv_f, recv_b, ring, dparams, dx_buf = carry
            # -- forward (recompute) half: microbatch t - stage ----------
            fi = t - stage
            f_act = jnp.logical_and(fi >= 0, fi < m)
            fi_c = jnp.clip(fi, 0, m - 1)
            x_in = tmap(lambda mi, r: jnp.where(stage == 0, mi[fi_c], r),
                        micro_inputs, recv_f)
            kf = (_chunk_key(base_key, fi_c, stage) if with_keys
                  else const_key)
            y = apply(params, x_in, kf)
            y = tmap(lambda a: jnp.where(f_act, a, jnp.zeros_like(a)), y)
            ring = tmap(lambda rg, xa: jnp.where(
                f_act, rg.at[fi_c % ring_n].set(xa), rg), ring, x_in)
            # -- backward half: microbatch t - (2(S-1) - stage) ----------
            bi = t - (2 * s_n - 2 - stage)
            b_act = jnp.logical_and(bi >= 0, bi < m)
            bi_c = jnp.clip(bi, 0, m - 1)
            g_in = tmap(lambda d, r: jnp.where(is_last, d[bi_c], r),
                        d_out, recv_b)
            x_sav = tmap(lambda rg: rg[bi_c % ring_n], ring)
            kb = (_chunk_key(base_key, bi_c, stage) if with_keys
                  else const_key)
            _, vjp = jax.vjp(lambda p, x: apply(p, x, kb), params, x_sav)
            dp, dx = vjp(g_in)
            dparams = tmap(
                lambda acc, g: acc + jnp.where(b_act, g, jnp.zeros_like(g)),
                dparams, dp)
            dx = tmap(lambda a: jnp.where(b_act, a, jnp.zeros_like(a)), dx)
            dx_buf = tmap(lambda b, a: jnp.where(
                jnp.logical_and(b_act, stage == 0), b.at[bi_c].set(a), b),
                dx_buf, dx)
            recv_f = tmap(lambda a: jax.lax.ppermute(a, axis_name, perm_up),
                          y)
            recv_b = tmap(lambda a: jax.lax.ppermute(a, axis_name, perm_dn),
                          dx)
            return (recv_f, recv_b, ring, dparams, dx_buf), None

        def act0(a):
            return jnp.zeros(a.shape[1:], a.dtype)

        carry0 = (tmap(act0, micro_inputs),
                  tmap(act0, micro_inputs),
                  tmap(lambda a: jnp.zeros((ring_n,) + a.shape[1:], a.dtype),
                       micro_inputs),
                  jax.tree.map(jnp.zeros_like, params),
                  tmap(jnp.zeros_like, micro_inputs))
        (_, _, _, dparams, dx_buf), _ = jax.lax.scan(
            tick, carry0, jnp.arange(ticks))
        dstacked = jax.tree.map(lambda a: a[None], dparams)
        return dstacked, tmap(lambda a: jax.lax.psum(a, axis_name), dx_buf)

    return run


def pipeline_spmd_zb_bwd(stage_fn, n_stages, n_micro, axis_name="pp",
                         with_keys=False):
    """Per-device ZB-H1 backward runner (reference:
    ``pipeline_scheduler_pass`` ZBH1 — SURVEY.md §2.3 "Distributed
    passes"): the backward splits into **B** (activation grad — the only
    part the ppermute chain waits on) and **W** (weight grad — no
    inter-stage dependency), with W deferred one tick so it fills slots
    off the wire chain.

    TPU-native split: the tick linearizes its microbatch ONCE
    (``jax.vjp``), evaluates only the dx cotangent in that tick (XLA
    dead-code-eliminates the dW transpose half), and carries the vjp
    closure — a ``jax.tree_util.Partial`` whose leaves are the
    linearization residuals — to the NEXT tick, which evaluates only the
    dp half. Same total FLOPs as the 1F1B-memory scan (one forward
    recompute + one full transpose per microbatch), but the dW matmuls
    sit outside the recv→B→ppermute dependency chain, giving XLA's
    scheduler slack to overlap them with the inter-stage transfers —
    ZBH1's defining property under lockstep SPMD. One extra tick drains
    the last W; one extra (residuals, cotangent) slot per stage is the
    memory cost (ZBH1 ≈ 1F1B memory, unlike ZB-V's 2×).
    """

    def run(stacked_params, micro_inputs, d_out, base_key=None):
        import jax.random as jrandom
        import jax.tree_util as jtu
        params = jax.tree.map(lambda a: a[0], stacked_params)
        stage = jax.lax.axis_index(axis_name)
        m = jax.tree.leaves(micro_inputs)[0].shape[0]
        s_n = n_stages
        ring_n = 2 * s_n - 1
        ticks = m + 2 * (s_n - 1) + 1      # +1: W trails B by one tick
        perm_up = [(i, i + 1) for i in range(s_n - 1)]
        perm_dn = [(i + 1, i) for i in range(s_n - 1)]
        is_last = stage == s_n - 1
        const_key = jrandom.PRNGKey(0)
        tmap = jax.tree.map

        def apply(p, x, key):
            return stage_fn(p, x, key) if with_keys else stage_fn(p, x)

        def lin(p, x, key):
            _, vjp = jax.vjp(lambda pp, xx: apply(pp, xx, key), p, x)
            return vjp

        # The VJP closure is a pytree whose LEAVES are the linearization
        # residuals but whose treedef embeds trace-local metadata — it
        # cannot ride the scan carry as-is. Carry the residual leaves;
        # each tick re-flattens ITS OWN (structurally identical) vjp and
        # unflattens the carried leaves with that tick's treedef to
        # evaluate the previous microbatch's W half.
        def tick(carry, t):
            (recv_f, recv_b, ring, res_prev, g_prev, dparams,
             dx_buf) = carry
            # -- forward (recompute) half: microbatch t - stage ----------
            fi = t - stage
            f_act = jnp.logical_and(fi >= 0, fi < m)
            fi_c = jnp.clip(fi, 0, m - 1)
            x_in = tmap(lambda mi, r: jnp.where(stage == 0, mi[fi_c], r),
                        micro_inputs, recv_f)
            kf = (_chunk_key(base_key, fi_c, stage) if with_keys
                  else const_key)
            y = apply(params, x_in, kf)
            y = tmap(lambda a: jnp.where(f_act, a, jnp.zeros_like(a)), y)
            ring = tmap(lambda rg, xa: jnp.where(
                f_act, rg.at[fi_c % ring_n].set(xa), rg), ring, x_in)
            # -- B half: activation grad of microbatch t - (2(S-1) - s).
            # Linearize once; evaluate ONLY dx (the dW transpose half has
            # no consumer this tick — XLA DCEs it off the wire chain).
            bi = t - (2 * s_n - 2 - stage)
            b_act = jnp.logical_and(bi >= 0, bi < m)
            bi_c = jnp.clip(bi, 0, m - 1)
            g_in = tmap(lambda d, r: jnp.where(is_last, d[bi_c], r),
                        d_out, recv_b)
            x_sav = tmap(lambda rg: rg[bi_c % ring_n], ring)
            kb = (_chunk_key(base_key, bi_c, stage) if with_keys
                  else const_key)
            vjp_now = lin(params, x_sav, kb)
            leaves_now, treedef = jtu.tree_flatten(vjp_now)
            _dp_dead, dx = vjp_now(g_in)       # dW half DCE'd here
            dx = tmap(lambda a: jnp.where(b_act, a, jnp.zeros_like(a)), dx)
            dx_buf = tmap(lambda b, a: jnp.where(
                jnp.logical_and(b_act, stage == 0), b.at[bi_c].set(a), b),
                dx_buf, dx)
            # -- W half: weight grad of the PREVIOUS tick's B microbatch.
            # No wire dependency — only the carried residuals/cotangent.
            wi = t - 1 - (2 * s_n - 2 - stage)
            w_act = jnp.logical_and(wi >= 0, wi < m)
            vjp_prev = jtu.tree_unflatten(treedef, res_prev)
            dp, _dx_dead = vjp_prev(g_prev)    # dx half DCE'd here
            dparams = tmap(
                lambda acc, g: acc + jnp.where(w_act, g, jnp.zeros_like(g)),
                dparams, dp)
            recv_f = tmap(lambda a: jax.lax.ppermute(a, axis_name, perm_up),
                          y)
            recv_b = tmap(lambda a: jax.lax.ppermute(a, axis_name, perm_dn),
                          dx)
            return (recv_f, recv_b, ring, leaves_now, g_in, dparams,
                    dx_buf), None

        def act0(a):
            return jnp.zeros(a.shape[1:], a.dtype)

        zero_x = tmap(act0, micro_inputs)
        res0_shapes = jax.eval_shape(
            lambda p, x: jtu.tree_flatten(lin(p, x, const_key))[0],
            params, zero_x)
        res0 = [jnp.zeros(s.shape, s.dtype) for s in res0_shapes]
        carry0 = (zero_x,
                  tmap(act0, micro_inputs),
                  tmap(lambda a: jnp.zeros((ring_n,) + a.shape[1:], a.dtype),
                       micro_inputs),
                  res0,
                  tmap(act0, micro_inputs),
                  jax.tree.map(jnp.zeros_like, params),
                  tmap(jnp.zeros_like, micro_inputs))
        (_, _, _, _, _, dparams, dx_buf), _ = jax.lax.scan(
            tick, carry0, jnp.arange(ticks))
        dstacked = jax.tree.map(lambda a: a[None], dparams)
        return dstacked, tmap(lambda a: jax.lax.psum(a, axis_name), dx_buf)

    return run


def _forward_1f1b(stage_fn, mesh, n_stages, n_micro, axis_name, with_keys,
                  schedule="1f1b"):
    """Differentiable pipelined forward whose VJP is the interleaved
    1F1B-memory scan (:func:`pipeline_spmd_1f1b_bwd`) — or its ZB-H1
    B/W-split variant (:func:`pipeline_spmd_zb_bwd`) — instead of
    ``jax.grad``-through-scan. Forward results are bit-identical to the
    default schedule (it IS the same forward runner); only the backward's
    schedule/memory differ — gradients remain exact (rematerialised)."""
    import numpy as np

    fwd_run = pipeline_spmd(stage_fn, n_stages, n_micro, axis_name,
                            with_keys=with_keys)
    bwd_maker = (pipeline_spmd_zb_bwd if schedule == "zb"
                 else pipeline_spmd_1f1b_bwd)
    bwd_run = bwd_maker(stage_fn, n_stages, n_micro, axis_name,
                        with_keys=with_keys)

    def _p_specs(tree):
        return jax.tree.map(lambda a: P(axis_name), tree)

    @jax.custom_vjp
    def call(stacked_params, micro_inputs, rng_key):
        mapped = jax.shard_map(
            fwd_run, mesh=mesh,
            in_specs=(_p_specs(stacked_params), P(), P()), out_specs=P(),
            axis_names={axis_name}, check_vma=False)
        return jax.jit(mapped)(stacked_params, micro_inputs, rng_key)

    def fwd(stacked_params, micro_inputs, rng_key):
        return (call(stacked_params, micro_inputs, rng_key),
                (stacked_params, micro_inputs, rng_key))

    def bwd(res, d_out):
        stacked_params, micro_inputs, rng_key = res
        specs = _p_specs(stacked_params)
        mapped = jax.shard_map(
            bwd_run, mesh=mesh, in_specs=(specs, P(), P(), P()),
            out_specs=(specs, P()), axis_names={axis_name}, check_vma=False)
        dstacked, dmicro = jax.jit(mapped)(stacked_params, micro_inputs,
                                           d_out, rng_key)
        dkey = np.zeros(rng_key.shape, dtype=jax.dtypes.float0)
        return dstacked, dmicro, dkey

    call.defvjp(fwd, bwd)
    return call


def pipeline_spmd_interleaved(stage_fn, n_stages, n_micro, vpp,
                              axis_name="pp", with_keys=False):
    """Interleaved (VPP) per-device runner — the reference
    ``PipelineParallelWithInterleave``: L = S·v chunks, chunk c on device
    c mod S; each tick every device runs its v chunks and the ring wraps
    (S-1 → 0) carrying activations to the next virtual stage. Expects the
    local param shard with leading dim v in *slot* order (slot k = chunk
    ``stage + k·S``) — ``pipeline_forward`` pre-permutes.
    ``with_keys`` as in :func:`pipeline_spmd` (chunk id = stage + k·S).
    """

    def run(stacked_params, micro_inputs, base_key=None):
        stage = jax.lax.axis_index(axis_name)
        m = micro_inputs.shape[0]
        chunks = n_stages * vpp
        ticks = m + chunks - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        act_shape = micro_inputs.shape[1:]
        act_dtype = micro_inputs.dtype
        is_last = stage == n_stages - 1

        def tick(carry, t):
            recv, out_buf = carry          # recv [v, ...]
            outs = []
            for k in range(vpp):
                params_k = jax.tree.map(lambda a: a[k], stacked_params)
                c = stage + k * n_stages   # my chunk id at slot k
                idx = t - c
                active = jnp.logical_and(idx >= 0, idx < m)
                if k == 0:
                    feed = micro_inputs[jnp.clip(t, 0, m - 1)]
                    x = jnp.where(stage == 0, feed, recv[0])
                else:
                    x = recv[k]
                if with_keys:
                    key = _chunk_key(base_key, jnp.clip(idx, 0, m - 1), c)
                    y = stage_fn(params_k, x, key)
                else:
                    y = stage_fn(params_k, x)
                y = jnp.where(active, y, jnp.zeros_like(y))
                if k == vpp - 1:
                    slot = jnp.clip(idx, 0, m - 1)
                    write = jnp.logical_and(active, is_last)
                    out_buf = jnp.where(write, out_buf.at[slot].set(y),
                                        out_buf)
                outs.append(y)
            sent = jax.lax.ppermute(jnp.stack(outs), axis_name, perm)
            # ring wrap S-1 → 0 advances the virtual stage: on device 0,
            # incoming slot k feeds chunk (k+1)·S, i.e. local slot k+1
            shifted = jnp.concatenate(
                [jnp.zeros((1,) + act_shape, act_dtype), sent[:-1]], axis=0)
            recv_next = jnp.where(stage == 0, shifted, sent)
            return (recv_next, out_buf), None

        out_buf = jnp.zeros((m,) + act_shape, act_dtype)
        recv0 = jnp.zeros((vpp,) + act_shape, act_dtype)
        (_, out_buf), _ = jax.lax.scan(tick, (recv0, out_buf),
                                       jnp.arange(ticks))
        return jax.lax.psum(out_buf, axis_name)

    return run


def pipeline_seq_forward(block_fn, stacked_params, micro_inputs, *, pre=None,
                         post=None, mesh=None, axis_name="pp",
                         n_stages=None, vpp_degree=1, rng_key=None,
                         schedule="fthenb"):
    """Full-model pipelined forward for stage-heterogeneous LMs (reference:
    ``pp_layers.py`` stage partition with embedding on stage 0, head on
    stage S-1, ``SharedLayerDesc`` tied weights).

    TPU-native stage heterogeneity: on GPU pipelines the embedding/head
    live on the first/last rank because weights are pinned to processes.
    Under SPMD there is no pinning — so ``pre`` (embedding) and ``post``
    (final norm + head) run as plain sharded compute over the WHOLE mesh
    (every chip's MXU works on the vocab matmul instead of 1/S of them),
    and only the homogeneous decoder-block run is scheduled through the
    ppermute pipeline. Tied embeddings need no ``allreduce_shared_weight``:
    reference (``pipeline_parallel.py`` shared-weight sync) — here the tied
    array simply appears in both ``pre`` and ``post`` closures and
    ``jax.grad`` sums the two contributions.

    ``pre``/``post``: batched callables ``x -> y`` applied to the
    microbatches flattened to ONE [M·mb, ...] batch (bigger MXU matmuls
    than per-micro application, and activation sharding constraints see
    their canonical [B, T, H] rank); ``block_fn(chunk_params, x)`` applies
    one pipeline chunk. ``micro_inputs``: [M, mb, ...]. With ``rng_key``
    set, ``block_fn(chunk_params, x, key)`` gets per-(micro, chunk) keys
    and ``pre``/``post`` become ``fn(x, key)`` with their own derived
    keys (they run once over the flat batch, outside the schedule, so a
    single key each keeps them schedule-invariant too).
    """
    def _flat_apply(fn, x, key=None):
        m, mb = x.shape[:2]
        flat = x.reshape((m * mb,) + tuple(x.shape[2:]))
        y = fn(flat) if key is None else fn(flat, key)
        return y.reshape((m, mb) + tuple(y.shape[1:]))

    import jax.random as jrandom
    h = micro_inputs
    if pre is not None:
        h = _flat_apply(pre, h, None if rng_key is None
                        else jrandom.fold_in(rng_key, 0x5e90))
    h = pipeline_forward(block_fn, stacked_params, h, mesh=mesh,
                         axis_name=axis_name, n_stages=n_stages,
                         vpp_degree=vpp_degree, rng_key=rng_key,
                         schedule=schedule)
    if post is not None:
        h = _flat_apply(post, h, None if rng_key is None
                        else jrandom.fold_in(rng_key, 0x5e91))
    return h


class PipelinedModule:
    """Functionalize a ``PipelineLayer`` for the jitted SPMD engine —
    the bridge that lets a REAL stage-heterogeneous LM (embedding stage,
    N decoder blocks, norm+head stage, optionally tied embeddings) train
    through ``pipeline_forward`` (reference:
    ``fleet/meta_parallel/pipeline_parallel.py`` 1F1B over the stage
    modules built by ``pp_layers.py``).

    Split: ``PipelineLayer.homogeneous_run()`` finds the longest run of
    identical-signature layers (the decoder blocks); everything before is
    the *pre* segment (embedding), everything after the *post* segment
    (final norm + lm head). Pre/post params stay unstacked ("edge"
    params, sharded by the caller's TP/fsdp rules); block params are
    stacked ``[S·vpp, layers_per_chunk, ...]`` and sharded ``P('pp')``.
    Tied embeddings (``SharedLayerDesc``) need no shared-weight allreduce:
    the tied Parameter is deduped into ONE edge array consumed by both
    segments, so ``jax.grad`` sums the two contributions.

    Stochastic blocks (dropout): pass ``rng_key`` to ``__call__`` — the
    engine threads deterministic per-(microbatch, chunk) keys through
    the scan (reference ``RNGStatesTracker`` semantics), so a pipelined
    run reproduces the sequential run given the same base key. Without
    a key the blocks run with a constant key (dropout degenerates to a
    fixed mask — fine for the dropout-free pretrain configs).

    Mutable buffers (BN running stats) remain unsupported by design:
    under the skewed schedule each stage sees microbatches at different
    ticks, so a buffer update order would be schedule-dependent — the
    reference has the same constraint in spirit (per-stage BN is local
    to a rank there; here weights are stacked across stages).

    Usage::

        pm = PipelinedModule(pipe_layer, mesh=mesh)
        out = pm(pm.edge_arrays(), pm.stacked_arrays(), micro_x)  # [M, ...]
    """

    def __init__(self, pipe_layer, mesh=None, axis_name="pp", n_stages=None,
                 vpp_degree=None, schedule="fthenb"):
        from . import mesh as mesh_mod
        from ..framework.functional import FunctionalModule

        self.schedule = schedule
        self.axis_name = axis_name
        self.mesh = mesh or (mesh_mod.get_mesh() if mesh_mod.has_mesh()
                             else None)
        if n_stages is None:
            n_stages = (int(self.mesh.shape[axis_name])
                        if self.mesh is not None and
                        axis_name in self.mesh.shape else pipe_layer._num_stages)
        self.n_stages = n_stages
        self.vpp = int(vpp_degree if vpp_degree is not None
                       else getattr(pipe_layer, "_vpp", 1))
        n_chunks = self.n_stages * self.vpp

        lo, hi = pipe_layer.homogeneous_run()
        if hi - lo < n_chunks:
            raise ValueError(
                f"homogeneous block run has {hi - lo} layers < "
                f"{n_chunks} pipeline chunks (stages {self.n_stages} × vpp "
                f"{self.vpp})")
        # trailing blocks that don't fill a chunk fold into the post segment
        hi -= (hi - lo) % n_chunks
        self.blocks = pipe_layer.run_function[lo:hi]
        self.lpc = len(self.blocks) // n_chunks          # layers per chunk
        self.n_chunks = n_chunks

        self._edge = _EdgeSegments(pipe_layer.run_function[:lo],
                                   pipe_layer.run_function[hi:])
        self._fm_pre = FunctionalModule(self._edge, method=self._edge.run_pre)
        self._fm_post = FunctionalModule(self._edge, method=self._edge.run_post)
        self._fm_blk = FunctionalModule(self.blocks[0])
        self._blk_params = [list(b.parameters()) for b in self.blocks]
        for ps in self._blk_params:
            assert len(ps) == len(self._fm_blk.params), \
                "pipeline blocks must share one parameter signature"
        if any(b for blk in self.blocks for b in blk.buffers()):
            raise ValueError("pipelined blocks with mutable buffers are not "
                             "supported (BN stats can't thread the schedule)")
        self.edge_params = self._fm_pre.params           # deduped, tied once

    # -- state ---------------------------------------------------------------
    def edge_arrays(self):
        return [p._data for p in self.edge_params]

    def stacked_arrays(self):
        """Stack each block-param leaf [n_chunks, lpc, ...] in chunk order
        (chunk c = blocks [c·lpc, (c+1)·lpc))."""
        outs = []
        for j in range(len(self._fm_blk.params)):
            leaf = jnp.stack([ps[j]._data for ps in self._blk_params])
            outs.append(leaf.reshape((self.n_chunks, self.lpc)
                                     + tuple(leaf.shape[1:])))
        return outs

    def write_back(self, edge_arrs, stacked_arrs):
        """Write updated arrays back into the eager Parameters."""
        for p, a in zip(self.edge_params, edge_arrs):
            p._data = a
        for j, a in enumerate(stacked_arrs):
            flat = a.reshape((-1,) + tuple(a.shape[2:]))
            for i, ps in enumerate(self._blk_params):
                ps[j]._data = flat[i]

    def unstack_grads(self, stacked_grads):
        """Per-block grad list (parallel to ``self.blocks``) from stacked
        grads — for eager ``.grad`` write-back in train_batch."""
        per_block = [[] for _ in self.blocks]
        for g in stacked_grads:
            flat = g.reshape((-1,) + tuple(g.shape[2:]))
            for i in range(len(self.blocks)):
                per_block[i].append(flat[i])
        return per_block

    # -- the pure pipelined forward -----------------------------------------
    def __call__(self, edge_arrs, stacked_arrs, micro_inputs, rng_key=None):
        import jax.random as jrandom
        const_key = jrandom.PRNGKey(0)
        threaded = rng_key is not None

        if threaded:
            def chunk_fn(chunk_arrs, x, key):
                for l in range(self.lpc):
                    arrs = [a[l] for a in chunk_arrs]
                    x, _ = self._fm_blk(arrs, [],
                                        jrandom.fold_in(key, l), x)
                return x

            pre = post = None
            if self._edge.has_pre:
                def pre(x, key):
                    return self._fm_pre(edge_arrs, [], key, x)[0]
            if self._edge.has_post:
                def post(x, key):
                    return self._fm_post(edge_arrs, [], key, x)[0]
        else:
            def chunk_fn(chunk_arrs, x):
                for l in range(self.lpc):
                    arrs = [a[l] for a in chunk_arrs]
                    x, _ = self._fm_blk(arrs, [], const_key, x)
                return x

            pre = post = None
            if self._edge.has_pre:
                def pre(x):
                    return self._fm_pre(edge_arrs, [], const_key, x)[0]
            if self._edge.has_post:
                def post(x):
                    return self._fm_post(edge_arrs, [], const_key, x)[0]
        return pipeline_seq_forward(chunk_fn, stacked_arrs, micro_inputs,
                                    pre=pre, post=post, mesh=self.mesh,
                                    axis_name=self.axis_name,
                                    n_stages=self.n_stages,
                                    vpp_degree=self.vpp, rng_key=rng_key,
                                    schedule=self.schedule)


class _EdgeSegments:
    """Container for the pre/post (embedding / norm+head) segments with
    tied parameters deduped across both (``Layer.named_parameters`` memo)."""

    def __init__(self, pre_layers, post_layers):
        from ..nn.layer import Layer

        class _Holder(Layer):
            pass

        holder = _Holder()
        for i, l in enumerate(pre_layers):
            holder.add_sublayer(f"pre_{i}", l)
        for i, l in enumerate(post_layers):
            holder.add_sublayer(f"post_{i}", l)
        self._holder = holder
        self._pre = list(pre_layers)
        self._post = list(post_layers)
        self.has_pre = bool(pre_layers)
        self.has_post = bool(post_layers)

    # FunctionalModule protocol: parameters()/buffers()/sublayers()
    def parameters(self):
        return self._holder.parameters()

    def named_parameters(self):
        return self._holder.named_parameters()

    def buffers(self):
        return self._holder.buffers()

    def sublayers(self, include_self=False):
        return self._holder.sublayers(include_self=False)

    @staticmethod
    def _run(layers, x):
        for l in layers:
            fwd = getattr(l, "_shared_forward", None)
            x = fwd(l, x) if fwd is not None else l(x)
        return x

    def run_pre(self, x):
        return self._run(self._pre, x)

    def run_post(self, x):
        return self._run(self._post, x)


def _pad_to(a, shape):
    pads = [(0, t - s) for s, t in zip(a.shape, shape)]
    return jnp.pad(a, pads) if any(p[1] for p in pads) else a


def pipeline_forward_hetero(stage_fns, per_stage_params, micro_inputs, *,
                            mesh=None, axis_name="pp", rng_key=None,
                            schedule="fthenb"):
    """Pipelined forward over stages with DIFFERENT bodies, parameter
    pytrees, and activation widths (reference: per-microbatch tensor-meta
    exchange in ``pp_utils/p2p_communication.py`` — recv shapes are
    negotiated per stage, so heterogeneous stages work; VERDICT round-4
    item 7 asks for the same freedom here).

    TPU-native handling: lockstep SPMD rotates ONE wire buffer, so the
    engine (not the caller) absorbs the heterogeneity —

    * per-stage param leaves are zero-padded to the positionwise max
      shape and stacked ``[S, ...]`` (shardable ``P('pp')`` like the
      homogeneous path; the padding is dead weight only on the stages
      that don't use it);
    * activations ride the wire padded to the elementwise max of every
      stage's in/out shape; each stage statically slices its true input
      shape and re-pads its output (pad/slice transpose cleanly, so all
      three backward schedules work unchanged);
    * the per-stage body is picked by ``lax.switch`` on a stage-id leaf
      threaded through the stacked params (each device evaluates only
      its own branch).

    ``stage_fns``: list of S callables ``fn(params_s, x)`` (or
    ``fn(params_s, x, key)`` with ``rng_key``); ``per_stage_params``:
    list of S pytrees; ``micro_inputs``: [M, mb, *in_shape_0] single
    array. Returns the last stage's outputs [M, mb, *out_shape_last],
    exactly as a sequential apply would.
    """
    from . import mesh as mesh_mod
    mesh = mesh or mesh_mod.get_mesh()
    n_stages_ = len(stage_fns)
    if len(per_stage_params) != n_stages_:
        raise ValueError(f"{n_stages_} stage_fns but "
                         f"{len(per_stage_params)} param trees")
    with_keys = rng_key is not None
    m, mb = micro_inputs.shape[0], micro_inputs.shape[1]

    # per-stage activation shapes by abstract evaluation of the chain
    flat_stages = [list(jax.tree.leaves(p)) for p in per_stage_params]
    treedefs = [jax.tree.structure(p) for p in per_stage_params]
    x_shape = tuple(micro_inputs.shape[2:])
    in_shapes, out_shapes = [], []
    x_sds = jax.ShapeDtypeStruct((mb,) + x_shape, micro_inputs.dtype)
    key0 = jax.random.PRNGKey(0) if with_keys else None
    for s in range(n_stages_):
        in_shapes.append(tuple(x_sds.shape[1:]))
        x_sds = jax.eval_shape(
            lambda p, x, fn=stage_fns[s]: (fn(p, x, key0) if with_keys
                                           else fn(p, x)),
            per_stage_params[s], x_sds)
        out_shapes.append(tuple(x_sds.shape[1:]))
    if len(set(len(sh) for sh in in_shapes + out_shapes)) != 1:
        raise ValueError("heterogeneous stages must agree on activation "
                         f"RANK (got in={in_shapes}, out={out_shapes})")
    wire_shape = tuple(max(sh[i] for sh in in_shapes + out_shapes)
                       for i in range(len(x_shape)))

    # Storage slots: stages may have entirely different leaf counts and
    # orders, so leaves are binned by (rank, dtype) — the j-th rank-R
    # dtype-D leaf of any stage shares a stacked slot with the j-th such
    # leaf of every other stage, zero-padded to the slot's max shape.
    slots = []                       # slot id -> (rank, dtype)
    slot_of = []                     # per stage: leaf index -> slot id
    for f in flat_stages:
        seen = {}
        ids = []
        for leaf in f:
            kkey = (jnp.ndim(leaf), jnp.asarray(leaf).dtype)
            occ = seen.get(kkey, 0)
            seen[kkey] = occ + 1
            have = [i for i, sk in enumerate(slots) if sk == kkey]
            if occ < len(have):
                ids.append(have[occ])
            else:
                slots.append(kkey)
                ids.append(len(slots) - 1)
        slot_of.append(ids)
    max_shapes = []
    for sid, (rk, dt) in enumerate(slots):
        shs = [jnp.shape(f[j]) for f, ids in zip(flat_stages, slot_of)
               for j, s_id in enumerate(ids) if s_id == sid]
        max_shapes.append(tuple(max(sh[i] for sh in shs)
                                for i in range(rk)))
    stacked = []
    for sid, (rk, dt) in enumerate(slots):
        per_stage = []
        for f, ids in zip(flat_stages, slot_of):
            js = [j for j, s_id in enumerate(ids) if s_id == sid]
            per_stage.append(_pad_to(jnp.asarray(f[js[0]]), max_shapes[sid])
                             if js else jnp.zeros(max_shapes[sid], dt))
        stacked.append(jnp.stack(per_stage))
    # stage-id leaf: [S] — the switch index each device reads from its
    # shard. Stored as float32 so the backward schedules can form its
    # (discarded) cotangent; int leaves would yield float0 grads the
    # scan accumulators cannot add.
    stacked_all = {"leaves": stacked,
                   "sid": jnp.arange(n_stages_, dtype=jnp.float32)}

    def uni_stage(params_slice, x, key=None):
        sid = params_slice["sid"].astype(jnp.int32)
        leaves = params_slice["leaves"]

        def make_branch(s):
            def branch(leaves_x):
                lvs, xx = leaves_x
                f_leaves = [lvs[slot_of[s][j]][tuple(
                                slice(0, d) for d in
                                jnp.shape(flat_stages[s][j]))]
                            for j in range(len(flat_stages[s]))]
                p_s = jax.tree.unflatten(treedefs[s], f_leaves)
                x_s = xx[(slice(None),) + tuple(slice(0, d)
                                                for d in in_shapes[s])]
                y = (stage_fns[s](p_s, x_s, key) if with_keys
                     else stage_fns[s](p_s, x_s))
                return _pad_to(y, (y.shape[0],) + wire_shape)
            return branch

        return jax.lax.switch(sid, [make_branch(s)
                                    for s in range(n_stages_)], (leaves, x))

    micro_padded = _pad_to(micro_inputs, micro_inputs.shape[:2] + wire_shape)
    out = pipeline_forward(uni_stage, stacked_all, micro_padded,
                           mesh=mesh, axis_name=axis_name,
                           n_stages=n_stages_, vpp_degree=1,
                           rng_key=rng_key, schedule=schedule)
    last = out_shapes[-1]
    return out[(slice(None), slice(None))
               + tuple(slice(0, d) for d in last)]


def stacked_fsdp_spec(arr, pp_axis="pp", fsdp_axis="sharding"):
    """PartitionSpec for a ``[n_chunks, lpc, *param]`` stacked block leaf:
    pp on dim 0, ZeRO-3 ``fsdp_axis`` on the first weight dim of 2-D
    weights when divisible (params-sharded-at-rest; GSPMD all-gathers on
    use and reduce-scatters grads). Shared by the config-4 dryrun and the
    hybrid tests so the placement rule lives in one place."""
    from . import mesh as mesh_mod
    n = mesh_mod.axis_size(fsdp_axis)
    if n > 1 and arr.ndim >= 4 and arr.shape[2] % n == 0:
        return P(pp_axis, None, fsdp_axis)
    return P(pp_axis)


def stacked_hybrid_spec(arr, pp_axis="pp", fsdp_axis="sharding",
                        mp_axis="mp"):
    """Full config-4 placement for a ``[n_chunks, lpc, *param]`` stacked
    block leaf: pp on dim 0, ZeRO-3 ``fsdp_axis`` on the input dim and
    Megatron ``mp_axis`` (column parallel) on the output dim of 2-D
    weights, each applied when the mesh axis exists >1 and divides the
    dim (reference: the GPT-1.3B dp×mp×pp×sharding hybrid —
    ``fleet/meta_parallel`` HybridParallelClipGrad world; SURVEY.md §2.4
    config 4, §3.4)."""
    from . import mesh as mesh_mod
    n_f = mesh_mod.axis_size(fsdp_axis)
    n_m = mesh_mod.axis_size(mp_axis)
    fsdp_ok = n_f > 1 and arr.ndim >= 4 and arr.shape[2] % n_f == 0
    mp_ok = n_m > 1 and arr.ndim == 4 and arr.shape[3] % n_m == 0
    if fsdp_ok and mp_ok:
        return P(pp_axis, None, fsdp_axis, mp_axis)
    if fsdp_ok:
        return P(pp_axis, None, fsdp_axis)
    if mp_ok:
        return P(pp_axis, None, None, mp_axis)
    return P(pp_axis)


def pipeline_forward(stage_fn, stacked_params, micro_inputs, *, mesh=None,
                     axis_name="pp", n_stages=None, vpp_degree=1,
                     rng_key=None, schedule="fthenb"):
    """Pipelined forward over the global mesh's pp axis (differentiable,
    jit-compatible).

    ``stacked_params``: pytree, leaves stacked [S·vpp, ...] in chunk order
    (chunk = consecutive layer group). ``micro_inputs``: [M, mb, ...].
    ``vpp_degree`` > 1 selects the interleaved (VPP) schedule.
    With ``rng_key`` set, ``stage_fn(params, x, key)`` receives a
    deterministic per-(microbatch, chunk) key — stochastic stages
    (dropout) produce the same result as a sequential run with the same
    base key, regardless of schedule or pp size.

    ``schedule`` picks the *backward* memory profile (reference:
    ``pipeline_scheduler_pass`` FThenB/1F1B — SURVEY.md §2.3):

    * ``"fthenb"`` (default): ``jax.grad`` through the forward scan —
      1F1B-like bubble, GPipe-like memory (O(M) saved residual sets).
    * ``"1f1b"``: ``custom_vjp`` with the interleaved recompute/backward
      scan — O(S) in-flight activations independent of M, one extra
      forward of FLOPs (remat). Requires ``vpp_degree == 1``.
    * ``"zb"``: ZB-H1 — like ``"1f1b"`` but the backward splits into B
      (activation grad, on the ppermute chain) and W (weight grad,
      deferred one tick off the chain). Same FLOPs and O(S) memory;
      the dW matmuls gain scheduling slack against the transfers.
    """
    from . import mesh as mesh_mod
    mesh = mesh or mesh_mod.get_mesh()
    mesh_pp = int(mesh.shape[axis_name]) if axis_name in mesh.shape else 1
    if n_stages is not None and mesh_pp > 1 and n_stages != mesh_pp:
        raise ValueError(f"n_stages={n_stages} != mesh '{axis_name}' size "
                         f"{mesh_pp}: chunks would be silently dropped")
    n_stages = mesh_pp
    if schedule not in ("fthenb", "1f1b", "zb"):
        raise ValueError(f"unknown pipeline schedule {schedule!r} "
                         "(expected 'fthenb', '1f1b' or 'zb')")
    with_keys = rng_key is not None
    if n_stages == 1:
        n_chunks = jax.tree.leaves(stacked_params)[0].shape[0]

        def seq_all(x, micro_idx):
            for c in range(n_chunks):
                p = jax.tree.map(lambda a: a[c], stacked_params)
                if with_keys:
                    x = stage_fn(p, x, _chunk_key(rng_key, micro_idx, c))
                else:
                    x = stage_fn(p, x)
            return x
        m = jax.tree.leaves(micro_inputs)[0].shape[0]
        return jax.vmap(seq_all)(micro_inputs, jnp.arange(m))
    n_micro = int(jax.tree.leaves(micro_inputs)[0].shape[0])
    if schedule in ("1f1b", "zb"):
        if vpp_degree > 1:
            raise ValueError(f"schedule={schedule!r} supports vpp_degree == "
                             "1 only (interleaved-VPP keeps the default "
                             "backward)")
        import jax.random as jrandom
        key = rng_key if with_keys else jrandom.PRNGKey(0)
        call = _forward_1f1b(stage_fn, mesh, n_stages, n_micro, axis_name,
                             with_keys, schedule=schedule)
        return call(stacked_params, micro_inputs, key)
    if vpp_degree > 1:
        if not hasattr(micro_inputs, "shape"):
            raise ValueError("the interleaved (VPP) schedule supports a "
                             "single-array activation; pack pytree "
                             "activations into one array or use vpp=1")
        # chunk-major [c] → slot-major [(k, d) → d*v + k ... ]: device d's
        # slot k must hold chunk d + k·S, and P('pp') splits contiguously,
        # so global order becomes [d=0: chunks 0, S, 2S…; d=1: 1, S+1, …]
        order = jnp.asarray([d + k * n_stages
                             for d in range(n_stages)
                             for k in range(vpp_degree)])
        stacked_params = jax.tree.map(
            lambda a: jnp.take(a, order, axis=0), stacked_params)
        run = pipeline_spmd_interleaved(stage_fn, n_stages, n_micro,
                                        vpp_degree, axis_name,
                                        with_keys=with_keys)
    else:
        run = pipeline_spmd(stage_fn, n_stages, n_micro, axis_name,
                            with_keys=with_keys)
    p_specs = jax.tree.map(lambda a: P(axis_name), stacked_params)
    # bare P() is a pytree-prefix spec: replicates every activation leaf
    in_specs = (p_specs, P()) + ((P(),) if with_keys else ())
    mapped = jax.shard_map(
        run, mesh=mesh, in_specs=in_specs, out_specs=P(),
        axis_names={axis_name}, check_vma=False)
    args = (stacked_params, micro_inputs) + ((rng_key,) if with_keys else ())
    # axes outside axis_name stay in "auto" sharding mode, which shard_map
    # only supports under jit — so compile here; callers' outer jit still
    # fuses through (nested jit is inlined)
    return jax.jit(mapped)(*args)

"""Overlapped backward (ISSUE 5): tape grad-ready hooks, ready-bucket
async gradient exchange, fused donated optimizer step, persistent jit
cache, and the hapi trailing-partial-batch fix."""
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu import nn
from paddle_tpu.autograd import tape
from paddle_tpu.distributed.comm import GradientBucketer


# ---------------------------------------------------------------------------
# tape grad-ready hooks
# ---------------------------------------------------------------------------


class TestGradReadyHooks:
    def _net(self):
        net = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 2))
        wr = np.random.default_rng(0)
        for p in net.parameters():
            p.set_value(paddle.to_tensor(
                (wr.normal(size=p.shape) * 0.1).astype(np.float32)))
        return net

    def test_fires_once_per_leaf_in_finality_order(self):
        """Every trainable leaf fires exactly once per backward, and a
        leaf fires only when its grad is FINAL — the last layer's weight
        (whose consumers finish first in reverse traversal) fires before
        the first layer's."""
        net = self._net()
        fired = []
        cb = tape.register_grad_ready_callback(fired.append)
        try:
            x = paddle.to_tensor(np.ones((3, 4), np.float32))
            (net(x) ** 2).mean().backward()
        finally:
            tape.unregister_grad_ready_callback(cb)
        ids = [id(t) for t in fired]
        assert len(ids) == len(set(ids)), "a leaf fired twice"
        params = list(net.parameters())
        assert set(ids) == {id(p) for p in params}
        # grads were readable (final) inside the hook
        assert all(t.grad is not None for t in fired)
        w_first, w_last = params[0], params[2]
        assert ids.index(id(w_last)) < ids.index(id(w_first))

    def test_retain_graph_fires_per_backward(self):
        """retain_graph=True + a second backward: hooks fire once per
        leaf in EACH backward (the comm scheduler's stale-round discard
        keys on exactly this re-fire)."""
        net = self._net()
        fired = []
        cb = tape.register_grad_ready_callback(fired.append)
        try:
            x = paddle.to_tensor(np.ones((3, 4), np.float32))
            loss = (net(x) ** 2).mean()
            loss.backward(retain_graph=True)
            n1 = len(fired)
            loss.backward()
        finally:
            tape.unregister_grad_ready_callback(cb)
        nparams = len(list(net.parameters()))
        assert n1 == nparams
        assert len(fired) == 2 * nparams

    def test_double_backward_capture_does_not_fire(self):
        """paddle.grad (capture mode, accumulate=False) never owns .grad
        finality, so grad-ready must not fire there — only the final
        accumulate-mode backward over the second-order graph fires."""
        net = self._net()
        fired = []
        cb = tape.register_grad_ready_callback(fired.append)
        try:
            x = paddle.to_tensor(np.ones((3, 4), np.float32))
            loss = (net(x) ** 2).mean()
            (g,) = tape.grad(loss, [net[0].weight], create_graph=True)
            assert not fired, "capture-mode grad fired ready hooks"
            (g ** 2).sum().backward()
        finally:
            tape.unregister_grad_ready_callback(cb)
        assert fired, "double-backward's accumulate pass did not fire"
        assert all(t.grad is not None for t in fired)

    def test_unused_leaf_does_not_fire(self):
        """A parameter outside the backward graph must not fire (its
        bucket is the scheduler's at-barrier leftover path)."""
        used = paddle.create_parameter([4, 2])
        unused = paddle.create_parameter([4, 2])
        fired = []
        cb = tape.register_grad_ready_callback(fired.append)
        try:
            x = paddle.to_tensor(np.ones((3, 4), np.float32))
            paddle.matmul(x, used).sum().backward()
        finally:
            tape.unregister_grad_ready_callback(cb)
        assert id(unused) not in [id(t) for t in fired]
        assert id(used) in [id(t) for t in fired]


# ---------------------------------------------------------------------------
# single-tensor bucket fast path (satellite)
# ---------------------------------------------------------------------------


class TestSingleTensorBucket:
    def test_flatten_skips_assembly_with_identical_layout(self):
        """fuse 0 → every tensor its own bucket; the no-copy fast path
        must produce byte-identical flat vectors to the generic assembly
        loop (offset 0, no padding possible)."""
        params = [paddle.create_parameter([64, 32]),
                  paddle.create_parameter([32])]
        b = GradientBucketer(params, fuse_grad_size_in_MB=0)
        assert b.num_buckets == 2
        rng = np.random.default_rng(3)
        arrays = [rng.normal(size=(64, 32)).astype(np.float32),
                  rng.normal(size=(32,)).astype(np.float32)]
        for bi, bucket in enumerate(b._buckets):
            assert len(bucket.items) == 1
            fast = b._flatten(bucket, arrays)
            # generic path: force the assembly loop by temporarily
            # removing the single-item precondition
            (i, off, numel, shape) = bucket.items[0]
            ref = np.zeros(bucket.numel, bucket.dtype)
            ref[off:off + numel] = np.asarray(
                arrays[i], bucket.dtype).reshape(-1)
            np.testing.assert_array_equal(fast, ref)

    def test_fused_bucket_still_assembles(self):
        """A multi-tensor bucket keeps the generic layout (offsets in
        rank-deterministic parameter order)."""
        params = [paddle.create_parameter([8, 4]),
                  paddle.create_parameter([4])]
        b = GradientBucketer(params, fuse_grad_size_in_MB=32)
        assert b.num_buckets == 1
        rng = np.random.default_rng(4)
        arrays = [rng.normal(size=(8, 4)).astype(np.float32),
                  rng.normal(size=(4,)).astype(np.float32)]
        flat = b._flatten(b._buckets[0], arrays)
        np.testing.assert_array_equal(flat[:32], arrays[0].reshape(-1))
        np.testing.assert_array_equal(flat[32:36], arrays[1])


# ---------------------------------------------------------------------------
# dp-4 overlap parity (acceptance)
# ---------------------------------------------------------------------------


def _train_dp4(overlap, quant=None, fused_step=False, env=None, steps=3):
    """3-step dp-4 sim run through HybridParallelOptimizer; returns the
    per-rank parameter arrays."""
    saved = {}
    for k, v in (env or {}).items():
        saved[k] = os.environ.get(k)
        os.environ[k] = v

    def worker():
        r = dist.get_rank()
        model = nn.Sequential(nn.Linear(16, 32), nn.Tanh(),
                              nn.Linear(32, 4))
        wr = np.random.default_rng(0)
        for p in model.parameters():
            p.set_value(paddle.to_tensor(
                (wr.normal(size=p.shape) * 0.1).astype(np.float32)))
        strat = dist.fleet.DistributedStrategy()
        strat.hybrid_configs = {"dp_degree": 4}
        strat.comm_overlap = overlap
        strat.fuse_grad_size_in_MB = 0.0001     # several buckets in flight
        strat.comm_quantization = quant
        inner = paddle.optimizer.SGD(learning_rate=0.05,
                                     parameters=model.parameters())
        inner.fuse_step = fused_step
        opt = dist.fleet.HybridParallelOptimizer(inner, strategy=strat)
        loss_fn = nn.MSELoss()
        rngX = np.random.default_rng(7)
        X = rngX.normal(size=(4 * 8 * steps, 16)).astype(np.float32)
        Y = (X @ rngX.normal(size=(16, 4)).astype(np.float32)
             ).astype(np.float32)
        for s in range(steps):
            lo = (s * 4 + r) * 8
            loss = loss_fn(model(paddle.to_tensor(X[lo:lo + 8])),
                           paddle.to_tensor(Y[lo:lo + 8]))
            loss.backward()
            opt.step()
            opt.clear_grad()
        return [np.asarray(p.numpy()).copy() for p in model.parameters()]

    try:
        return dist.spawn(worker, nprocs=4).results
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


class TestOverlapParity:
    def test_dp4_bit_parity_on_off_and_env(self):
        """ISSUE 5 acceptance: after 3 dp-4 SGD steps the parameters are
        BIT-identical across (a) ready-bucket overlap, (b) strategy
        comm_overlap=False, and (c) PADDLE_COMM_OVERLAP=0 — the PR-1
        barrier path."""
        on = _train_dp4(True)
        off = _train_dp4(False)
        legacy = _train_dp4(True, env={"PADDLE_COMM_OVERLAP": "0"})
        for variant in (off, legacy):
            for rank_on, rank_v in zip(on, variant):
                for a, b in zip(rank_on, rank_v):
                    np.testing.assert_array_equal(a, b)
        # replicas agree with each other too
        for r in range(1, 4):
            for a, b in zip(on[0], on[r]):
                np.testing.assert_array_equal(a, b)

    def test_dp4_bit_parity_quantized(self):
        """Same exchange math (incl. int8 codec + error feedback) runs on
        the worker lanes — overlap on/off stays bit-identical."""
        on = _train_dp4(True, quant="int8")
        off = _train_dp4(False, quant="int8")
        for a, b in zip(on[0], off[0]):
            np.testing.assert_array_equal(a, b)

    def test_dp4_fused_step_bit_parity(self):
        """Fused donated SGD step under overlap == eager per-param loop,
        bit for bit (acceptance)."""
        eager = _train_dp4(True, fused_step=False)
        fused = _train_dp4(True, fused_step=True)
        for a, b in zip(eager[0], fused[0]):
            np.testing.assert_array_equal(a, b)

    def test_overlap_dispatches_in_backward(self):
        """The overlap run actually dispatches buckets DURING backward
        (telemetry `paddle_comm_overlap_buckets_total{where=in_backward}`
        grows)."""
        from paddle_tpu.distributed.comm.bucketer import _overlap_telemetry
        c = _overlap_telemetry()["buckets"]
        before = c.value(where="in_backward")
        _train_dp4(True)
        assert c.value(where="in_backward") > before


# ---------------------------------------------------------------------------
# fused step oracle (single process)
# ---------------------------------------------------------------------------


def _mk_params(shapes, seed=0):
    rng = np.random.default_rng(seed)
    params = []
    for s in shapes:
        p = paddle.create_parameter(list(s))
        p.set_value(paddle.to_tensor(
            rng.normal(size=s).astype(np.float32) * 0.1))
        params.append(p)
    return params


def _run_opt(opt_cls, fused, steps=3, seed=5, **kw):
    shapes = [(32, 16), (16,), (16, 8), (8,)] * 5      # 20 params >= min
    params = _mk_params(shapes)
    opt = opt_cls(learning_rate=0.05, parameters=params, **kw)
    opt.fuse_step = fused
    rng = np.random.default_rng(seed)
    grads = [[rng.normal(size=s).astype(np.float32) * 0.01 for s in shapes]
             for _ in range(steps)]
    for gs in grads:
        for p, g in zip(params, gs):
            p.grad = paddle.to_tensor(g)
        opt.step()
        opt.clear_grad()
    return [np.asarray(p.numpy()) for p in params]


class TestFusedStep:
    def test_sgd_bit_identical(self):
        """Plain-SGD fused step (two-phase delta/combine, no FMA across
        the final subtract) is bit-identical to the eager loop."""
        for a, b in zip(_run_opt(paddle.optimizer.SGD, False),
                        _run_opt(paddle.optimizer.SGD, True)):
            np.testing.assert_array_equal(a, b)

    def test_sgd_weight_decay_bit_identical(self):
        for a, b in zip(
                _run_opt(paddle.optimizer.SGD, False, weight_decay=0.01),
                _run_opt(paddle.optimizer.SGD, True, weight_decay=0.01)):
            np.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("opt_cls", ["Momentum", "Adam", "AdamW"])
    def test_slotted_optimizers_match_eager(self, opt_cls):
        """Slot-carrying optimizers run the generic one-call fused
        program — same math at f32 rounding level: the compiled program
        FMA-contracts the moment updates and evaluates bias-correction
        powers in f32 where the eager loop rounds per-op with f64
        python-float scalars, so updates agree to ~1e-6 absolute (params
        are O(0.1); near-zero elements make pure rtol meaningless)."""
        cls = getattr(paddle.optimizer, opt_cls)
        for a, b in zip(_run_opt(cls, False), _run_opt(cls, True)):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=5e-6)

    def test_fused_collapses_dispatches(self):
        """The telemetry counters show the O(params)->O(1) collapse: one
        eager dispatch per parameter per step vs O(1) fused calls."""
        from paddle_tpu.optimizer.fused import opt_telemetry
        c = opt_telemetry()["dispatches"]
        e0, f0 = c.value(mode="eager"), c.value(mode="fused")
        _run_opt(paddle.optimizer.SGD, False, steps=1)
        e1 = c.value(mode="eager")
        _run_opt(paddle.optimizer.SGD, True, steps=1)
        f1, e2 = c.value(mode="fused"), c.value(mode="eager")
        assert e1 - e0 == 20                    # one per param
        assert 0 < f1 - f0 <= 4                 # O(1) group calls
        assert e2 == e1                         # no eager leftovers
        assert (e1 - e0) / (f1 - f0) >= 10      # >= 10x collapse

    def test_l1_regularizer_falls_back_to_eager(self):
        """L1-regularized params are exotic: they must leave the fused
        path and still match the pure-eager result exactly."""
        from paddle_tpu.regularizer import L1Decay

        def run(fused):
            params = _mk_params([(8, 4)] * 20, seed=2)
            for p in params[:3]:
                p.regularizer = L1Decay(0.01)
            opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=params)
            opt.fuse_step = fused
            rng = np.random.default_rng(9)
            for p in params:
                p.grad = paddle.to_tensor(
                    rng.normal(size=(8, 4)).astype(np.float32) * 0.01)
            opt.step()
            return [np.asarray(p.numpy()) for p in params]

        for a, b in zip(run(False), run(True)):
            np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# overlap never deadlocks when a rank skips a step
# ---------------------------------------------------------------------------


class TestOverlapTimeout:
    def test_skipped_rank_times_out_not_deadlocks(self):
        """Rank 1 skips its backward+step; rank 0's in-flight bucket can
        never pair. The step boundary must surface a TimeoutError within
        the configured bound — not hang."""
        os.environ["PADDLE_COMM_OVERLAP_TIMEOUT_S"] = "3"
        try:
            def worker():
                r = dist.get_rank()
                model = nn.Linear(8, 4)
                model.weight.set_value(paddle.to_tensor(
                    np.ones((8, 4), np.float32) * 0.1))
                strat = dist.fleet.DistributedStrategy()
                strat.hybrid_configs = {"dp_degree": 2}
                strat.comm_overlap = True
                opt = dist.fleet.HybridParallelOptimizer(
                    paddle.optimizer.SGD(learning_rate=0.1,
                                         parameters=model.parameters()),
                    strategy=strat)
                if r == 1:
                    return "skipped"
                x = paddle.to_tensor(np.ones((2, 8), np.float32))
                model(x).sum().backward()
                opt.step()
                return "stepped"

            t0 = time.monotonic()
            # spawn wraps the rank's TimeoutError in its per-rank report
            with pytest.raises(RuntimeError, match="did not complete"):
                dist.spawn(worker, nprocs=2)
            assert time.monotonic() - t0 < 30
        finally:
            os.environ.pop("PADDLE_COMM_OVERLAP_TIMEOUT_S", None)


# ---------------------------------------------------------------------------
# persistent jit compilation cache (satellite)
# ---------------------------------------------------------------------------


class TestPersistentJitCache:
    def test_disk_hit_counted(self, tmp_path, monkeypatch):
        import jax
        import jax.numpy as jnp

        from paddle_tpu.jit import api as jit_api

        # an outside placement would (rightly) win over cache_dir
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        cache_dir = str(tmp_path / "jitcache")
        prev = jit_api._PERSISTENT_CACHE[0]
        assert jit_api.enable_persistent_cache(cache_dir)
        try:
            c = jit_api._jit_metrics()["cache"]
            before = c.value(event="disk_hit")
            f = jax.jit(lambda x: x * 3 + 2)
            f(jnp.ones((4, 4))).block_until_ready()
            assert os.listdir(cache_dir), "no executables persisted"
            # drop the in-memory caches: the next call must restore the
            # compiled executable from disk, not recompile
            jax.clear_caches()
            f(jnp.ones((4, 4))).block_until_ready()
            assert c.value(event="disk_hit") > before
        finally:
            # restore the suite-wide cache (conftest enables one) rather
            # than leaving the plane disabled for every later test
            if isinstance(prev, str):
                jit_api._PERSISTENT_CACHE[0] = None
                jit_api.enable_persistent_cache(prev)
            else:
                jax.config.update("jax_compilation_cache_dir", None)
                jit_api._PERSISTENT_CACHE[0] = False

    def test_disabled_without_env(self, monkeypatch):
        from paddle_tpu.jit import api as jit_api
        monkeypatch.delenv("PADDLE_JIT_CACHE_DIR", raising=False)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        jit_api._PERSISTENT_CACHE[0] = None
        assert jit_api.enable_persistent_cache() is False
        jit_api._PERSISTENT_CACHE[0] = None


# ---------------------------------------------------------------------------
# hapi trailing-partial-batch recompile fix (satellite)
# ---------------------------------------------------------------------------


class _Toy(paddle.io.Dataset):
    def __init__(self, n=20):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        rng = np.random.default_rng(i)
        return (rng.normal(size=(8,)).astype(np.float32),
                rng.normal(size=(2,)).astype(np.float32))


class TestPartialBatchPad:
    def test_no_recompile_on_trailing_batch(self):
        """20 samples / batch 8 -> 8, 8, 4: the 4-row tail is padded to
        the compiled spec, so the jit cache records exactly ONE miss
        across three epochs (the old behavior traced a second program
        every epoch)."""
        from paddle_tpu.jit.api import _jit_metrics
        net = paddle.jit.to_static(nn.Sequential(
            nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 2)))
        model = paddle.Model(net)
        model.prepare(
            optimizer=paddle.optimizer.SGD(
                learning_rate=0.01, parameters=net.parameters()),
            loss=nn.MSELoss())
        loader = paddle.io.DataLoader(_Toy(), batch_size=8, shuffle=False)
        c = _jit_metrics()["cache"]
        m0 = c.value(event="miss")
        model.fit(loader, epochs=3, verbose=0)
        assert c.value(event="miss") - m0 == 1

    def test_padded_gradients_match_unpadded(self):
        """Pad rows get a zero cotangent (outputs sliced before the
        loss), so the step on a padded tail equals the eager unpadded
        step."""
        def run(static):
            net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(),
                                nn.Linear(16, 2))
            wr = np.random.default_rng(0)
            for p in net.parameters():
                p.set_value(paddle.to_tensor(
                    (wr.normal(size=p.shape) * 0.1).astype(np.float32)))
            if static:
                net = paddle.jit.to_static(net)
            model = paddle.Model(net)
            model.prepare(
                optimizer=paddle.optimizer.SGD(
                    learning_rate=0.05, parameters=net.parameters()),
                loss=nn.MSELoss())
            loader = paddle.io.DataLoader(_Toy(12), batch_size=8,
                                          shuffle=False)
            model.fit(loader, epochs=1, verbose=0)
            return [np.asarray(p.numpy()) for p in net.parameters()]

        for a, b in zip(run(False), run(True)):
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6)

    def test_batchnorm_disables_padding(self):
        """Batch-coupled normalization would see the pad rows in its
        statistics — the safety gate must keep such nets on the legacy
        per-shape trace."""
        net = paddle.jit.to_static(nn.Sequential(
            nn.Linear(8, 16), nn.BatchNorm1D(16), nn.Linear(16, 2)))
        model = paddle.Model(net)
        assert model._pad_partial_enabled() is False

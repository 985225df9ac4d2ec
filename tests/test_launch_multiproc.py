"""REAL multi-process distributed path (VERDICT.md round-1 item 7;
reference: the ``TestDistBase`` shell-out pattern of
``test/legacy_test/test_dist_base.py`` — spawn trainers via the launch CLI,
compare losses against a single-process oracle).

Two local processes rendezvous through ``jax.distributed.initialize``
(driven by the PADDLE_* env the launcher sets), each drives 2 virtual CPU
devices, and one jitted SPMD step trains over the global 4-device dp mesh —
collectives ride Gloo across the processes."""
import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ.get("LOCAL_DEVICES", "2"))
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_default_matmul_precision", "highest")
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.framework.functional import FunctionalModule
    from jax.sharding import NamedSharding, PartitionSpec as P
    import jax.numpy as jnp

    dist.init_parallel_env()
    world = jax.process_count()
    n_dev = len(jax.devices())
    assert n_dev == 4, f"expected 4 global devices, got {n_dev}"
    mesh = mesh_mod.init_mesh({"dp": n_dev})

    paddle.seed(11)
    model = paddle.nn.Sequential(paddle.nn.Linear(8, 16), paddle.nn.Tanh(),
                                 paddle.nn.Linear(16, 1))
    fm = FunctionalModule(model, training=True)
    p_arrs = fm.param_arrays()
    rng = np.random.RandomState(5)
    X = rng.randn(16, 8).astype(np.float32)
    W = rng.randn(8, 1).astype(np.float32)
    Y = (X @ W).astype(np.float32)

    data_sh = NamedSharding(mesh, P("dp"))
    repl = NamedSharding(mesh, P())
    gx = jax.make_array_from_callback(X.shape, data_sh, lambda i: X[i])
    gy = jax.make_array_from_callback(Y.shape, data_sh, lambda i: Y[i])
    key = fm.next_key()

    @jax.jit
    def step(p_arrs, x, y):
        def loss_fn(ps):
            out, _ = fm(ps, [], key, x)
            return ((out - y) ** 2).mean()
        loss, g = jax.value_and_grad(loss_fn)(p_arrs)
        return loss, [p - 0.1 * gg for p, gg in zip(p_arrs, g)]

    losses = []
    for _ in range(5):
        loss, p_arrs = step(p_arrs, gx, gy)
        losses.append(float(jax.device_get(
            jax.jit(lambda l: l, out_shardings=repl)(loss))))
    if jax.process_index() == 0:
        print("LOSSES:", ",".join(f"{l:.6f}" for l in losses), flush=True)

    # eager collective over the device tier (one jitted reduction across
    # processes instead of a host allgather)
    if world > 1:
        from paddle_tpu.framework.core import Tensor
        me = jax.process_index()
        t = Tensor(jnp.full((4,), float(me + 1)))
        dist.all_reduce(t)
        expect = sum(range(1, world + 1))
        assert np.allclose(np.asarray(t._data), expect), np.asarray(t._data)
        if me == 0:
            print("ALLREDUCE_OK", flush=True)

        # reduce_scatter device tier: rank r gets sum_p(p-th input of
        # each process); inputs are (proc+1)*(slot+1) -> slice r sums to
        # (slot r+1) * sum(proc+1)
        outs = Tensor(jnp.zeros((2,), jnp.float32))
        ins = [Tensor(jnp.full((2,), float((me + 1) * (s + 1)), jnp.float32))
               for s in range(world)]
        dist.reduce_scatter(outs, ins)
        want_rs = (me + 1) * sum(p + 1 for p in range(world))
        assert np.allclose(np.asarray(outs._data), want_rs), \
            (np.asarray(outs._data), want_rs)

        # alltoall device tier: slot s of my inputs goes to rank s
        a2a_out = []
        a2a_in = [Tensor(jnp.full((2,), float(me * 10 + s), jnp.float32))
                  for s in range(world)]
        dist.alltoall(a2a_out, a2a_in)
        got = [float(np.asarray(t_._data)[0]) for t_ in a2a_out]
        assert got == [p * 10 + me for p in range(world)], got

        # real cross-process send/recv through the TCPStore p2p channel
        if me == 0:
            msg = Tensor(jnp.arange(6, dtype=jnp.float32).reshape(2, 3))
            dist.send(msg, dst=1)
            back = Tensor(jnp.zeros((2, 3), jnp.float32))
            dist.recv(back, src=1)
            assert np.allclose(np.asarray(back._data),
                               np.arange(6).reshape(2, 3) * 2), \
                np.asarray(back._data)
            print("P2P_OK", flush=True)
        else:
            got_t = Tensor(jnp.zeros((2, 3), jnp.float32))
            dist.recv(got_t, src=0)
            reply = Tensor(jnp.asarray(np.asarray(got_t._data) * 2))
            dist.send(reply, dst=0)
        if me == 0:
            print("RS_A2A_OK", flush=True)
    print("WORKER_DONE rank", jax.process_index(), flush=True)
""")


def _cpu_env(extra):
    env = dict(os.environ)
    parts = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    env["PYTHONPATH"] = os.pathsep.join([REPO] + parts)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.update(extra)
    return env


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _parse_losses(text):
    for line in text.splitlines():
        if line.startswith("LOSSES:"):
            return [float(v) for v in line.split(":", 1)[1].split(",")]
    raise AssertionError(f"no LOSSES line in output:\n{text[-2000:]}")


def test_launch_two_process_dp_parity(tmp_path):
    worker = tmp_path / "worker.py"
    worker.write_text(WORKER)

    # ---- oracle: one process, 4 local devices, same global mesh
    out = subprocess.run(
        [sys.executable, str(worker)],
        env=_cpu_env({"LOCAL_DEVICES": "4"}),
        capture_output=True, text=True, timeout=420, cwd=str(tmp_path))
    assert out.returncode == 0, out.stderr[-2000:]
    oracle = _parse_losses(out.stdout)
    assert oracle[-1] < oracle[0], oracle

    # ---- 2 processes x 2 local devices through the launch CLI
    port = _free_port()
    logdir = tmp_path / "logs"
    procs = []
    for rank in range(2):
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nnodes", "2", "--rank", str(rank),
             "--master", f"127.0.0.1:{port}",
             "--log_dir", str(logdir), str(worker)],
            env=_cpu_env({"LOCAL_DEVICES": "2"}),
            cwd=str(tmp_path)))
    for p in procs:
        try:
            assert p.wait(timeout=420) == 0
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            logs = "\n".join(f.read_text()[-1500:]
                             for f in sorted(logdir.glob("workerlog.*")))
            pytest.fail(f"multi-process launch timed out; logs:\n{logs}")

    log0 = (logdir / "workerlog.0").read_text()
    dist_losses = _parse_losses(log0)
    np.testing.assert_allclose(dist_losses, oracle, rtol=1e-5, atol=1e-6)
    assert "ALLREDUCE_OK" in log0
    assert "RS_A2A_OK" in log0
    assert "P2P_OK" in log0
    assert "WORKER_DONE rank 0" in log0
    assert "WORKER_DONE rank 1" in (logdir / "workerlog.1").read_text()

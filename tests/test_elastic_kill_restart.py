"""Real-process elastic recovery (VERDICT.md round-2 weak #9): a worker
launched through the launch CLI is SIGKILLed mid-training; the
supervisor restarts it, it re-rendezvouses through the C++ TCPStore and
resumes from its checkpoint to completion (reference semantics: the
launch controllers + elastic manager, SURVEY.md §5.3)."""
import os
import signal
import subprocess
import sys
import textwrap
import time

import pytest

from paddle_tpu.distributed import native

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = textwrap.dedent("""
    import os, sys, time
    from paddle_tpu.distributed.native import TCPStore

    store = TCPStore("127.0.0.1", int(os.environ["TEST_STORE_PORT"]),
                     is_master=False, world_size=1)
    attempt = store.add("attempts", 1)
    ckpt = os.environ["TEST_CKPT"]
    start = int(open(ckpt).read()) if os.path.exists(ckpt) else 0
    print(f"RESUMED_AT {start} attempt {attempt}", flush=True)
    for step in range(start, 10):
        with open(ckpt, "w") as f:       # checkpoint every step
            f.write(str(step + 1))
        if attempt == 1 and step == 4:
            # advertise ourselves and wait for the external SIGKILL —
            # a hard process death, not a clean python exception
            store.set("pid", str(os.getpid()))
            time.sleep(120)
    print("TRAINING_DONE", open(ckpt).read(), flush=True)
""")


@pytest.mark.skipif(not native.available(), reason="native TCPStore needed")
def test_sigkill_worker_recovers_through_supervisor(tmp_path):
    worker = tmp_path / "worker.py"
    worker.write_text(WORKER)
    ckpt = tmp_path / "step.ckpt"
    logdir = tmp_path / "logs"

    # the test owns the rendezvous store (survives the worker's death,
    # like a real multi-host master)
    store = native.TCPStore("127.0.0.1", 0, is_master=True, world_size=1)

    env = dict(os.environ)
    parts = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    env["PYTHONPATH"] = os.pathsep.join([REPO] + parts)
    env["JAX_PLATFORMS"] = "cpu"
    env["TEST_STORE_PORT"] = str(store.port)
    env["TEST_CKPT"] = str(ckpt)

    sup = subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nnodes", "1", "--rank", "0", "--run_mode", "elastic",
         "--max_restarts", "2", "--log_dir", str(logdir), str(worker)],
        env=env, cwd=str(tmp_path), stderr=subprocess.PIPE, text=True)

    # wait for the first attempt to advertise its pid, then SIGKILL it
    deadline = time.monotonic() + 120
    pid = None
    while time.monotonic() < deadline:
        try:
            pid = int(store.get("pid", wait=False))
            break
        except KeyError:
            time.sleep(0.2)
        except RuntimeError:
            time.sleep(0.2)
    assert pid is not None, "worker never reached the kill point"
    os.kill(pid, signal.SIGKILL)

    rc = sup.wait(timeout=180)
    err = sup.stderr.read()
    assert rc == 0, err[-2000:]
    assert "[elastic] worker failed" in err          # supervisor observed it
    log = (logdir / "workerlog.0").read_text()
    assert "RESUMED_AT 0 attempt 1" in log           # first life
    assert "RESUMED_AT 5 attempt 2" in log           # resumed mid-training
    assert "TRAINING_DONE 10" in log                 # completed after restart
    # add() counters are stored as little-endian int64 bytes
    assert int.from_bytes(store.get("attempts", wait=False),
                          "little") == 2
"""Elastic shrink/regrow chaos matrix (ISSUE 6 acceptance) plus the
checkpoint-hygiene / deterministic-resume satellites.

Acceptance: under the dp-4 thread-rank simulator a FaultPlan kills a
rank mid-run; survivors detect it (structured RankFailure — no hang, no
leaked overlap lanes), shrink to dp-3, restore the latest complete
checkpoint, and the post-resume loss trajectory is BIT-identical to a
fresh from-checkpoint restart on 3 ranks at the same step. A delay-only
fault produces a straggler report and no shrink.
"""
import os
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu import nn
from paddle_tpu.distributed import fault
from paddle_tpu.distributed.fault import elastic_telemetry
from paddle_tpu.distributed.fleet.elastic import (
    CheckpointManager, ElasticTrainLoop, ElasticWorld, MemKVStore,
)
from paddle_tpu.profiler import flight_recorder as fr


@pytest.fixture(autouse=True)
def _clean_fault_plan():
    fault.clear()
    yield
    fault.clear()


# ---------------------------------------------------------------------------
# harness
# ---------------------------------------------------------------------------

_STEPS = 24


def _build():
    net = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 4))
    wr = np.random.default_rng(0)
    for p in net.parameters():
        p.set_value(paddle.to_tensor(
            (wr.normal(size=p.shape) * 0.1).astype(np.float32)))
    opt = paddle.optimizer.SGD(learning_rate=0.05,
                               parameters=net.parameters())
    return net, opt, nn.MSELoss()


_rng = np.random.default_rng(7)
_X = _rng.normal(size=(_STEPS + 8, 12, 8)).astype(np.float32)
_W = _rng.normal(size=(8, 4)).astype(np.float32)


def _data(step):
    # global batch of 12 rows: splits evenly over 4 AND 3 ranks
    return _X[step], (_X[step] @ _W).astype(np.float32)


def _run_world(ckpt_dir, nprocs, total_steps, plan=None, ckpt_interval=3,
               job_id="job", restore_step=None, sharded=False,
               rejoin_after=None, ttl=30.0):
    """Spawn an elastic dp-N run; returns per-rank result dicts. A killed
    rank marks itself dead and survivors name it from the collective's
    ``RankFailure``, so no test here waits for ``ttl`` to expire: it only
    bounds FALSE deaths, and 30 s keeps a heartbeat thread starved under
    six busy xdist workers from faking one (a live rank dropped from the
    agreed world fails every ``world ==`` assertion below)."""
    store = MemKVStore()
    # every rank is a member before any of them meets in the first
    # barrier: ``agree`` takes whoever is a member once the acks have held
    # for three 5 ms polls, so a rank thread that starts 20 ms late on a
    # loaded host found a world of three agreed without it (seen under
    # ``-n 6``: the first all_reduce of a 4-rank run over ranks (0, 1, 2))
    for r in range(nprocs):
        store.put(f"{job_id}/member/{r}", r)
    if plan:
        fault.install(plan)

    def worker():
        r = dist.get_rank()
        loop = ElasticTrainLoop(str(ckpt_dir), store=store, job_id=job_id,
                                ckpt_interval=ckpt_interval, ttl=ttl,
                                barrier_timeout=60.0,
                                sharded_checkpoint=sharded)
        res = loop.run(_build, _data, total_steps,
                       restore_step=restore_step)
        if res["status"] == "killed" and rejoin_after is not None:
            # regrow: wait until every survivor has advanced past the
            # shrink (the progress counter, not a time), then rejoin
            # through the same loop; the deadline only ends a hung run
            ew = ElasticWorld(store, job_id, rank=r, ttl=ttl)
            deadline = time.monotonic() + 300
            while time.monotonic() < deadline:
                alive = [v for k, v in ew.progress().items() if k != r]
                if alive and min(alive) >= rejoin_after:
                    break
                time.sleep(0.05)
            res = loop.run(_build, _data, total_steps)
            res["rejoined"] = True
        return res

    try:
        return dist.spawn(worker, nprocs=nprocs).results
    finally:
        fault.clear()


def _overlap_threads():
    return {t.ident: t.name for t in threading.enumerate()
            if t.name.startswith("comm-overlap:")}


def _lane_snapshot():
    """Idents of overlap lanes alive right now — earlier test files park
    idle lanes (their schedulers are never closed), so leak checks must
    be DELTAS against this baseline, not absolute."""
    return set(_overlap_threads())


def _assert_no_leaked_lanes(baseline=frozenset()):
    deadline = time.monotonic() + 60         # returns as soon as none is left
    while time.monotonic() < deadline:
        new = {i: n for i, n in _overlap_threads().items()
               if i not in baseline}
        if not new:
            return
        time.sleep(0.05)
    raise AssertionError(f"leaked overlap lanes: {sorted(new.values())}")


# ---------------------------------------------------------------------------
# chaos matrix
# ---------------------------------------------------------------------------


class TestKillAtStep:
    def test_shrink_and_bit_match_fresh_restart(self, tmp_path):
        """THE acceptance test: kill rank 2 at step 5; survivors shrink
        to [0, 1, 3], restore the step-3 checkpoint, and every step >= 3
        of the post-resume trajectory bit-matches a fresh 3-rank restart
        from the same checkpoint, position for position."""
        ck = tmp_path / "ck"
        base = _lane_snapshot()
        res = _run_world(ck, 4, 10, plan="kill:rank=2,step=5",
                         job_id="kill-step")
        by_rank = {r["rank"]: r for r in res}
        assert by_rank[2]["status"] == "killed"
        survivors = [by_rank[r] for r in (0, 1, 3)]
        for s in survivors:
            assert s["status"] == "done"
            assert s["world"] == [0, 1, 3]
            assert sorted(s["losses"]) == list(range(10))
        _assert_no_leaked_lanes(base)

        # fresh from-checkpoint restart on 3 ranks at the same step
        fresh = _run_world(ck, 3, 10, job_id="fresh", ckpt_interval=1000,
                           restore_step=3)
        fresh.sort(key=lambda r: r["rank"])
        for pos in range(3):
            a = survivors[pos]["losses"]
            b = fresh[pos]["losses"]
            for s in range(3, 10):
                assert a[s] == b[s], (pos, s, a[s], b[s])

    def test_kill_counts_and_events(self, tmp_path):
        c = elastic_telemetry()["events"]
        before = {k: c.value(kind=k)
                  for k in ("kill", "failure_detected", "shrink", "restore")}
        _run_world(tmp_path / "ck", 4, 8, plan="kill:rank=1,step=4",
                   job_id="kill-tel")
        assert c.value(kind="kill") == before["kill"] + 1
        assert c.value(kind="failure_detected") > before["failure_detected"]
        assert c.value(kind="shrink") > before["shrink"]
        assert c.value(kind="restore") > before["restore"]


class TestKillMidCollective:
    def test_seq_kill_shrinks_without_hang_or_leak(self, tmp_path):
        """Kill rank 2 before one of its collectives (mid-backward, on an
        overlap lane): survivors get RankFailure out of the scheduler's
        finish(), release their lanes, shrink and finish."""
        base = _lane_snapshot()
        t0 = time.monotonic()
        res = _run_world(tmp_path / "ck", 4, 10,
                         plan="kill:rank=2,seq=9", job_id="kill-seq")
        assert time.monotonic() - t0 < 120       # detection, not timeout
        by_rank = {r["rank"]: r for r in res}
        assert by_rank[2]["status"] == "killed"
        for r in (0, 1, 3):
            assert by_rank[r]["status"] == "done"
            assert by_rank[r]["world"] == [0, 1, 3]
            assert sorted(by_rank[r]["losses"]) == list(range(10))
        _assert_no_leaked_lanes(base)


class TestKillDuringCheckpoint:
    def test_writer_death_leaves_no_tmp_and_survivors_resume(self, tmp_path):
        """Kill the checkpoint WRITER (world position 0 = rank 0) on the
        step right after a checkpoint boundary. Survivors must restore a
        COMPLETE checkpoint (the atomic rename guarantees no torn read)
        and the rebuild barrier's orphan sweep must leave no step_*.tmp
        behind."""
        ck = tmp_path / "ck"
        res = _run_world(ck, 4, 10, plan="kill:rank=0,step=4",
                         job_id="kill-writer", ckpt_interval=2)
        by_rank = {r["rank"]: r for r in res}
        assert by_rank[0]["status"] == "killed"
        for r in (1, 2, 3):
            assert by_rank[r]["status"] == "done"
            assert by_rank[r]["world"] == [1, 2, 3]
            assert sorted(by_rank[r]["losses"]) == list(range(10))
        # the simulator cannot kill the dead writer's background save
        # thread: let it end before the directory is read
        for t in threading.enumerate():
            if t.name.startswith("paddle-ckpt-"):
                t.join(60)
        leftovers = [n for n in os.listdir(ck) if n.endswith(".tmp")]
        assert not leftovers, leftovers
        assert CheckpointManager(str(ck)).steps()       # checkpoints exist

    def test_sharded_checkpoint_mode_shrinks_too(self, tmp_path):
        """Same chaos with sharded (distributed.checkpoint) async saves:
        restore-and-reshard onto the smaller world rides the
        re-shard-on-load path."""
        ck = tmp_path / "ck"
        res = _run_world(ck, 4, 10, plan="kill:rank=1,step=5",
                         job_id="kill-sharded", sharded=True)
        by_rank = {r["rank"]: r for r in res}
        assert by_rank[1]["status"] == "killed"
        for r in (0, 2, 3):
            assert by_rank[r]["status"] == "done"
            assert by_rank[r]["world"] == [0, 2, 3]
        steps = CheckpointManager(str(ck)).steps()
        assert steps
        assert os.path.exists(os.path.join(ck, f"step_{steps[-1]}",
                                           "metadata.json"))


class TestSlowRank:
    def test_delay_only_reports_straggler_no_shrink(self, tmp_path):
        """A 0.5 s delay on rank 3 is a straggler, not a failure: the
        world must NOT shrink, and the flight recorder's straggler
        report must name rank 3."""
        fr.reset()
        fr.enable()
        c = elastic_telemetry()["events"]
        shrinks0 = c.value(kind="shrink")
        try:
            res = _run_world(tmp_path / "ck", 4, 8,
                             plan="delay:rank=3,step=4,seconds=0.5",
                             job_id="slow")
            by_rank = {r["rank"]: r for r in res}
            for r in range(4):
                assert by_rank[r]["status"] == "done"
                assert by_rank[r]["world"] == [0, 1, 2, 3]
            assert c.value(kind="shrink") == shrinks0
            rep = fr.straggler_report(
                fr.get_flight_recorder().collective_events(by_rank=True))
            assert rep["slowest_rank"] == 3
            assert rep["per_rank_lag"][3]["max_s"] >= 0.2
        finally:
            fr.disable()
            fr.reset()


class TestRegrow:
    def test_killed_rank_readmitted_at_checkpoint_boundary(self, tmp_path):
        base = _lane_snapshot()
        c = elastic_telemetry()["events"]
        regrow0 = c.value(kind="regrow")
        res = _run_world(tmp_path / "ck", 4, 20,
                         plan="kill:rank=2,step=5", job_id="regrow",
                         ckpt_interval=2, rejoin_after=10)
        by_rank = {r["rank"]: r for r in res}
        assert by_rank[2].get("rejoined") is True
        for r in range(4):
            assert by_rank[r]["status"] == "done"
            assert by_rank[r]["world"] == [0, 1, 2, 3]   # regrown world
            assert max(by_rank[r]["losses"]) == 19
        assert c.value(kind="regrow") > regrow0
        # the rejoiner resumed from a checkpoint, not from step 0
        assert min(by_rank[2]["losses"]) >= 2
        _assert_no_leaked_lanes(base)


# ---------------------------------------------------------------------------
# satellites: checkpoint hygiene, atomic io.save, overlap-timeout
# diagnosis, DataLoader deterministic resume
# ---------------------------------------------------------------------------


class TestCheckpointHygiene:
    def test_retention_sweeps_stale_orphan_tmp(self, tmp_path):
        cm = CheckpointManager(str(tmp_path), keep=2)
        orphan = tmp_path / "step_5.tmp"
        orphan.mkdir()
        (orphan / "state.pdz").write_bytes(b"torn")
        cm.save(10, {"w": paddle.to_tensor(np.ones(3, np.float32))})
        assert not orphan.exists()          # swept: 5 <= newest complete 10
        assert cm.steps() == [10]

    def test_sweep_orphans_removes_everything_staged(self, tmp_path):
        cm = CheckpointManager(str(tmp_path))
        (tmp_path / "step_99.tmp").mkdir()
        removed = cm.sweep_orphans()
        assert removed == ["step_99.tmp"]
        assert not (tmp_path / "step_99.tmp").exists()

    def test_resave_over_complete_checkpoint(self, tmp_path):
        # a run restored from an earlier step re-writes later steps:
        # publishing over an existing COMPLETE step dir must not
        # ENOTEMPTY (os.replace can't overwrite a non-empty directory)
        cm = CheckpointManager(str(tmp_path))
        cm.save(6, {"w": paddle.to_tensor(np.zeros(3, np.float32))})
        cm.save(6, {"w": paddle.to_tensor(np.ones(3, np.float32))})
        step, state = cm.load()
        assert step == 6
        np.testing.assert_allclose(state["w"].numpy(), 1.0)
        assert not [n for n in os.listdir(tmp_path) if n.endswith(".tmp")]

    def test_save_async_is_durable_and_counted(self, tmp_path):
        h = elastic_telemetry()["ckpt_async"]
        n0 = h.labels().count
        cm = CheckpointManager(str(tmp_path))
        handle = cm.save_async(3, {"w": paddle.to_tensor(
            np.arange(4, dtype=np.float32))})
        handle.wait()
        step, state = cm.load()
        assert step == 3
        np.testing.assert_array_equal(state["w"].numpy(),
                                      np.arange(4, dtype=np.float32))
        assert h.labels().count == n0 + 1

    def test_load_waits_pending_async_save(self, tmp_path):
        cm = CheckpointManager(str(tmp_path))
        cm.save_async(7, {"w": paddle.to_tensor(np.full(2, 7, np.float32))})
        step, state = cm.load()             # no explicit wait
        assert step == 7
        np.testing.assert_allclose(state["w"].numpy(), 7.0)

    def test_sharded_roundtrip_reuses_reshard_on_load(self, tmp_path):
        cm = CheckpointManager(str(tmp_path))
        src = {"model": {"w": paddle.to_tensor(
            np.arange(12, dtype=np.float32).reshape(3, 4))}, "step": 4}
        cm.save_sharded(4, src)
        tmpl = {"model": {"w": paddle.to_tensor(
            np.zeros((3, 4), np.float32))}, "step": 0}
        step, loaded = cm.load_sharded(tmpl)
        assert step == 4
        np.testing.assert_array_equal(
            loaded["model"]["w"].numpy(),
            np.arange(12, dtype=np.float32).reshape(3, 4))

    def test_pickle_load_rejects_sharded_dir(self, tmp_path):
        cm = CheckpointManager(str(tmp_path))
        cm.save_sharded(2, {"w": paddle.to_tensor(np.ones(2, np.float32))})
        with pytest.raises(ValueError, match="load_sharded"):
            cm.load()


class TestAtomicIoSave:
    def test_failed_save_leaves_no_partial_target(self, tmp_path):
        from paddle_tpu.framework import io as fio
        path = tmp_path / "state.pdz"
        fio.save({"ok": paddle.to_tensor(np.ones(2, np.float32))}, str(path))
        good = path.read_bytes()

        class Unpicklable:
            def __reduce__(self):
                raise RuntimeError("cannot pickle me")

        with pytest.raises(Exception):
            fio.save({"bad": Unpicklable()}, str(path))
        # target untouched, no tmp litter
        assert path.read_bytes() == good
        assert [n for n in os.listdir(tmp_path) if ".tmp" in n] == []

    def test_save_then_load_roundtrip(self, tmp_path):
        from paddle_tpu.framework import io as fio
        path = str(tmp_path / "x.pdz")
        fio.save({"a": paddle.to_tensor(np.eye(3, dtype=np.float32))}, path)
        out = fio.load(path)
        np.testing.assert_array_equal(out["a"].numpy(), np.eye(3))


class TestOverlapTimeoutDiagnosis:
    def test_timeout_carries_desync_report_and_releases_lanes(self):
        """Rank 1 skips its step: rank 0's in-flight bucket can never
        pair. The TimeoutError must (a) arrive within the bound, (b)
        carry the flight-recorder desync report naming the rank/seq that
        never entered, (c) leave no _RankWorker lanes behind."""
        base = _lane_snapshot()
        os.environ["PADDLE_COMM_OVERLAP_TIMEOUT_S"] = "3"
        fr.reset()
        fr.enable()
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

            with pytest.raises(RuntimeError) as ei:
                dist.spawn(worker, nprocs=2)
            msg = str(ei.value)
            assert "did not complete" in msg
            assert "desync report" in msg
            assert "never entered" in msg
            _assert_no_leaked_lanes(base)
        finally:
            os.environ.pop("PADDLE_COMM_OVERLAP_TIMEOUT_S", None)
            fr.disable()
            fr.reset()


class _Rows(paddle.io.Dataset):
    def __init__(self, n=20):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        return np.full((2,), i, np.float32)


class TestDataLoaderResume:
    @staticmethod
    def _ids(batches):
        return [sorted(int(v) for v in np.asarray(b.numpy())[:, 0])
                for b in batches]

    def test_seeded_shuffle_resume_skips_exactly_consumed(self):
        loader = paddle.io.DataLoader(_Rows(), batch_size=4, shuffle=True,
                                      seed=11, num_workers=0)
        it = iter(loader)
        consumed = [next(it) for _ in range(2)]
        state = loader.state_dict()
        assert state["consumed_batches"] == 2 and state["seed"] == 11
        # abandon mid-epoch; a NEW loader resumes from the state
        resumed = paddle.io.DataLoader(_Rows(), batch_size=4, shuffle=True,
                                       seed=11, num_workers=0)
        resumed.set_state_dict(state)
        rest = list(resumed)
        # reference: the full epoch order is a pure fn of (seed, epoch)
        full = list(paddle.io.DataLoader(_Rows(), batch_size=4, shuffle=True,
                                         seed=11, num_workers=0))
        assert self._ids(consumed) + self._ids(rest) == self._ids(full)
        assert len(rest) == 3

    def test_resume_epoch_keeps_shuffle_order(self):
        a = paddle.io.DataLoader(_Rows(), batch_size=5, shuffle=True, seed=3)
        a.batch_sampler.set_epoch(2)
        order_a = self._ids(list(a))
        b = paddle.io.DataLoader(_Rows(), batch_size=5, shuffle=True, seed=3)
        b.set_state_dict({"epoch": 2, "consumed_batches": 0, "seed": 3})
        assert self._ids(list(b)) == order_a
        # different epoch -> different order
        c = paddle.io.DataLoader(_Rows(), batch_size=5, shuffle=True, seed=3)
        c.batch_sampler.set_epoch(3)
        assert self._ids(list(c)) != order_a

    def test_unseeded_shuffle_resume_rejected(self):
        loader = paddle.io.DataLoader(_Rows(), batch_size=4, shuffle=True)
        loader.set_state_dict({"epoch": 0, "consumed_batches": 2})
        with pytest.raises(ValueError, match="needs a seed"):
            list(loader)

    def test_next_epoch_after_resume_is_fresh(self):
        loader = paddle.io.DataLoader(_Rows(12), batch_size=4, shuffle=True,
                                      seed=5)
        loader.set_state_dict({"epoch": 0, "consumed_batches": 1, "seed": 5})
        assert len(list(loader)) == 2       # skipped one
        assert len(list(loader)) == 3       # fresh epoch, no skip

    def test_overskip_raises(self):
        loader = paddle.io.DataLoader(_Rows(8), batch_size=4, shuffle=True,
                                      seed=5)
        loader.set_state_dict({"epoch": 0, "consumed_batches": 9, "seed": 5})
        with pytest.raises(ValueError, match="only"):
            list(loader)

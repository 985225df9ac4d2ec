"""Determinism observatory (ISSUE 13): digest ledger unit tier, the
``bitflip:`` fault directive, dp-4 cross-rank divergence acceptance,
warn-mode bit-parity, KV publish/gather/compare, requeue + disagg
token-stream attestation, handoff blob digests, golden-ledger
roundtrip and the stdlib-only ``tools/ledger_diff.py`` CLI.

Acceptance here: dp-4 sim with ``PADDLE_FAULT_PLAN="bitflip:rank=2,
step=5"`` — the ledger's cross-rank comparator raises a structured
``DivergenceError`` at step 5 naming rank 2 and the exact parameter,
the built-in ``numerics_divergence`` alert fires, and the watchdog
dump's ``ledger`` state provider carries the latched divergence; the
identical run without the fault plan exports a golden ledger that is
byte-identical across two same-seed runs; a hard-killed replica's
requeued request passes token-stream attestation with ledger-on
outputs bit-identical to ledger-off."""
import hashlib
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu import nn
from paddle_tpu.autograd import tape
from paddle_tpu.distributed import fault, simulator
from paddle_tpu.distributed.fleet.elastic.tcp_kv import MemKVStore
from paddle_tpu.inference import ContinuousServingEngine, ServingRouter
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.profiler import (alerts, flight_recorder as flight,
                                 ledger, request_trace as rt, timeseries)
from paddle_tpu.profiler.ledger import DivergenceError
from paddle_tpu.profiler.telemetry import get_registry

REPO = os.path.join(os.path.dirname(__file__), "..")
ENGINE_KW = dict(max_batch_size=4, max_len=160, page_size=16,
                 prefill_chunk_tokens=32)


@pytest.fixture(autouse=True)
def _clean_ledger():
    rt.enable()
    rt.get_trace_store().clear()
    yield
    ledger.disable()
    ledger.reset()
    fault.clear()
    alerts.reset_alert_engine()
    timeseries.reset()
    flight.disable()
    flight.reset()


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    return LlamaForCausalLM(llama_tiny(num_hidden_layers=1,
                                       max_position_embeddings=256))


def _mlp(seed=0, din=16, dh=16, dout=4):
    """Deterministic per-rank init: explicit numpy values, NOT the
    process-global paddle generator (whose draw counter interleaves
    across simulated rank threads)."""
    net = nn.Sequential(nn.Linear(din, dh), nn.Tanh(), nn.Linear(dh, dout))
    wr = np.random.default_rng(seed)
    for p in net.parameters():
        p.set_value(paddle.to_tensor(
            (wr.normal(size=p.shape) * 0.1).astype(np.float32)))
    return net


def _oracle(model, p, n):
    return np.asarray(model.generate(paddle.to_tensor(p),
                                     max_new_tokens=n)._data)


def _shared_prompts(n_req=4, sys_len=32, tail=8, seed=0):
    rng = np.random.RandomState(seed)
    sys_prompt = rng.randint(0, 128, sys_len)
    return [np.concatenate([sys_prompt, rng.randint(0, 128, tail)])
            .astype(np.int64)[None] for _ in range(n_req)]


# ---------------------------------------------------------------------------
# unit tier: digests + comparator
# ---------------------------------------------------------------------------


class TestDigestOracle:
    def test_digest_stable_and_bit_sensitive(self):
        a = np.arange(6, dtype=np.float32).reshape(2, 3)
        assert ledger.tensor_digest(a) == ledger.tensor_digest(a.copy())
        # dtype- and shape-tagged
        assert ledger.tensor_digest(a) != \
            ledger.tensor_digest(a.astype(np.float64))
        assert ledger.tensor_digest(a) != \
            ledger.tensor_digest(a.reshape(3, 2))
        # raw BIT patterns, not values: -0.0 != 0.0, NaN payloads count
        z, z2 = np.zeros(3, np.float32), np.zeros(3, np.float32)
        z2[0] = -0.0
        assert ledger.tensor_digest(z) != ledger.tensor_digest(z2)
        # one flipped mantissa bit changes the digest
        b = a.copy()
        b.view(np.uint32)[0] ^= 1
        assert ledger.tensor_digest(a) != ledger.tensor_digest(b)

    def test_insertion_order_independent(self, tmp_path):
        """Same tensors => same exported ledger, regardless of the
        order entries were recorded in (ISSUE 13 stability oracle)."""
        rows = {"grad:p0000": "aa", "param:p0000": "bb",
                "grad:p0001": "cc", "param:p0001": "dd"}
        led1 = ledger.StepLedger(mode="warn")
        led1._commit(0, 0, dict(rows))
        led2 = ledger.StepLedger(mode="warn")
        led2._commit(0, 0, dict(reversed(list(rows.items()))))
        p1 = led1.export_golden(str(tmp_path / "a.jsonl"))
        p2 = led2.export_golden(str(tmp_path / "b.jsonl"))
        with open(p1, "rb") as f1, open(p2, "rb") as f2:
            assert f1.read() == f2.read()

    def test_first_divergence_majority_and_order(self):
        base = {"grad:p0000": "g0", "grad:p0001": "g1",
                "param:p0000": "w0", "param:p0001": "w1"}
        # rank 2 outvoted 3:1 on BOTH a grad and a param entry: the
        # grad is named (canonical order: cause before effect)
        bad = dict(base, **{"grad:p0001": "XX", "param:p0001": "YY"})
        div = ledger.first_divergence(
            {0: base, 1: base, 2: bad, 3: base})
        assert div["rank"] == 2 and div["tensor"] == "grad:p0001"
        # grad.local entries are never compared cross-rank
        div = ledger.first_divergence(
            {0: dict(base, **{"grad.local:w": "a"}),
             1: dict(base, **{"grad.local:w": "b"})})
        assert div is None
        # a rank missing a tensor the others have IS divergence
        short = {k: v for k, v in base.items() if k != "param:p0001"}
        div = ledger.first_divergence({0: base, 1: base, 2: short})
        assert div["rank"] == 2 and div["tensor"] == "param:p0001"
        # two-rank tie sides with the lowest rank
        div = ledger.first_divergence(
            {0: base, 1: dict(base, **{"param:p0000": "zz"})})
        assert div["rank"] == 1 and div["tensor"] == "param:p0000"

    def test_env_knobs(self, monkeypatch):
        monkeypatch.setenv("PADDLE_LEDGER_MODE", "warn")
        monkeypatch.setenv("PADDLE_LEDGER_INTERVAL", "4")
        monkeypatch.setenv("PADDLE_LEDGER_CAPACITY", "32")
        monkeypatch.setenv("PADDLE_LEDGER_STREAMS", "16")
        led = ledger.StepLedger()
        assert (led.mode, led.interval, led.capacity,
                led.stream_capacity) == ("warn", 4, 32, 16)
        monkeypatch.setenv("PADDLE_LEDGER_MODE", "explode")
        with pytest.raises(ValueError):
            ledger.StepLedger()

    def test_disabled_layer_is_inert(self):
        assert not ledger.is_enabled()
        ledger.note_stream_token("t", 0, 1)      # all no-ops
        assert ledger.stream_digest("t") is None
        assert ledger.attest_delivery("t") is None
        assert ledger.seal_handoff({}) is None
        net = _mlp()
        opt = paddle.optimizer.SGD(learning_rate=0.01,
                                   parameters=net.parameters())
        loss = (net(paddle.to_tensor(
            np.ones((2, 16), np.float32))) ** 2).mean()
        loss.backward()
        opt.step()
        assert ledger.get_ledger().rows() == []

    def test_import_time_enable_knob(self):
        code = ("import jax; jax.config.update('jax_platforms', 'cpu')\n"
                "from paddle_tpu.profiler import ledger\n"
                "assert ledger.is_enabled()\n"
                "assert ledger.get_ledger().mode == 'warn'\n")
        env = dict(os.environ, PADDLE_LEDGER="1",
                   PADDLE_LEDGER_MODE="warn", JAX_PLATFORMS="cpu")
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True, timeout=120,
                              cwd=REPO)
        assert proc.returncode == 0, proc.stderr


# ---------------------------------------------------------------------------
# bitflip fault directive
# ---------------------------------------------------------------------------


class TestBitflipFault:
    def test_parse_bitflip_directive(self):
        plan = fault.FaultPlan.parse("bitflip:rank=2,step=5")
        f = plan.faults[0]
        assert (f.kind, f.rank, f.step) == ("bitflip", 2, 5)
        with pytest.raises(ValueError):
            fault.FaultPlan.parse("bitflip:rank=0")     # needs a trigger
        with pytest.raises(ValueError):
            fault.FaultPlan.parse("gamma:rank=0,step=1")

    def test_flip_is_single_bit_once_only(self):
        net = _mlp(3)
        x = paddle.to_tensor(np.random.default_rng(1)
                             .normal(size=(4, 16)).astype(np.float32))

        def grads():
            for p in net.parameters():
                p.grad = None
            loss = (net(x) ** 2).mean()
            loss.backward()
            return {p.name: np.asarray(p.grad.numpy()).copy()
                    for p in net.parameters()}

        clean = grads()
        tape.flip_bit_next_leaf_grad()
        flipped = grads()
        diffs = [k for k in clean
                 if not np.array_equal(clean[k], flipped[k])]
        assert len(diffs) == 1, diffs
        xor = clean[diffs[0]].view(np.uint32) ^ \
            flipped[diffs[0]].view(np.uint32)
        assert sum(bin(v).count("1") for v in xor.ravel()) == 1
        # once-only: the next backward is clean again
        again = grads()
        for k in clean:
            np.testing.assert_array_equal(clean[k], again[k])

    def test_fault_fire_arms_flip_and_counts(self):
        fault.install("bitflip:rank=0,step=1")
        fault.check_step(0)                      # not due
        fault.check_step(1)                      # arms the tape poison
        c = get_registry().get("paddle_elastic_events_total")
        assert c.value(kind="bitflip") >= 1
        net = _mlp(4)
        x = paddle.to_tensor(np.ones((2, 16), np.float32))
        loss = (net(x) ** 2).mean()
        loss.backward()                          # consumes the poison
        fault.check_step(1)                      # once-only: no re-fire
        assert fault.active_plan().faults[0].fired


# ---------------------------------------------------------------------------
# optimizer-step digests (single rank)
# ---------------------------------------------------------------------------


class TestOptimizerCommits:
    def test_step_rows_and_local_grad_entries(self):
        ledger.enable(mode="warn", grad_ready=True)
        net = _mlp(0)
        opt = paddle.optimizer.SGD(learning_rate=0.05,
                                   parameters=net.parameters())
        x = paddle.to_tensor(np.random.default_rng(2)
                             .normal(size=(4, 16)).astype(np.float32))
        for _ in range(2):
            loss = (net(x) ** 2).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
        rows = ledger.get_ledger().rows(rank=0)
        assert [r["step"] for r in rows] == [0, 1]
        names = set(rows[0]["entries"])
        n_params = len(list(net.parameters()))
        assert sum(1 for n in names if n.startswith("grad:")) == n_params
        assert sum(1 for n in names if n.startswith("param:")) == n_params
        # tape-attached local digests ride in the same row
        assert sum(1 for n in names
                   if n.startswith("grad.local:")) == n_params
        # the human name map covers every positional key
        assert set(rows[0]["names"]) == \
            {n.split(":")[1] for n in names if n.startswith("grad:")}
        c = get_registry().get("paddle_ledger_digests_total")
        assert c.value(kind="grad") >= n_params
        assert c.value(kind="param") >= n_params
        assert c.value(kind="grad_local") >= n_params

    def test_interval_skips_steps(self):
        ledger.enable(mode="warn", interval=2)
        net = _mlp(1)
        opt = paddle.optimizer.SGD(learning_rate=0.05,
                                   parameters=net.parameters())
        x = paddle.to_tensor(np.ones((2, 16), np.float32))
        for _ in range(4):
            loss = (net(x) ** 2).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
        rows = ledger.get_ledger().rows(rank=0)
        assert [r["step"] for r in rows] == [0, 1, 2, 3]
        assert [bool(r["entries"]) for r in rows] == [
            True, False, True, False]


# ---------------------------------------------------------------------------
# dp-4 acceptance + parity
# ---------------------------------------------------------------------------


def _dp4_worker(steps=7):
    r = dist.get_rank()
    net = _mlp(seed=0)
    strat = dist.fleet.DistributedStrategy()
    strat.hybrid_configs = {"dp_degree": 4}
    dp = dist.parallel.DataParallel(net, strategy=strat)
    opt = paddle.optimizer.SGD(learning_rate=0.05,
                               parameters=net.parameters())
    ledger.attach()                      # per-rank: tape hooks are TLS
    rngX = np.random.default_rng(7)
    X = rngX.normal(size=(4 * 4 * steps, 16)).astype(np.float32)
    names = [p.name for p in net.parameters()]
    s = -1
    try:
        losses = []
        for s in range(steps):
            fault.check_step(s)
            lo = (s * 4 + r) * 4
            loss = (dp(paddle.to_tensor(X[lo:lo + 4])) ** 2).mean()
            loss.backward()
            losses.append(np.asarray(loss.numpy()).copy())
            opt.step()
            opt.clear_grad()
        return ("done", losses,
                [np.asarray(p.numpy()).copy() for p in net.parameters()],
                names)
    except DivergenceError as e:
        w = simulator.active_world()
        if w is not None:
            w.mark_dead(r)               # unblock the survivors
        return ("divergence", e, None, names)
    except simulator.RankFailure as e:
        return ("peer_failure", s, e.rank, names)
    finally:
        dp.shutdown()
        ledger.detach()


class TestAcceptanceDp4:
    def test_bitflip_raises_naming_rank_and_param(self, monkeypatch,
                                                  tmp_path):
        """ISSUE 13 acceptance: dp-4 sim with
        PADDLE_FAULT_PLAN="bitflip:rank=2,step=5" — the comparator
        raises DivergenceError at step 5 naming rank 2 and the exact
        parameter, survivors surface structured RankFailures, the
        built-in numerics_divergence alert fires, and the watchdog
        dump's ledger state provider carries the latched divergence."""
        monkeypatch.setenv("PADDLE_FAULT_PLAN", "bitflip:rank=2,step=5")
        monkeypatch.setenv("PADDLE_COMM_OVERLAP_TIMEOUT_S", "60")
        fault.clear()                    # re-arm lazy env parsing
        flight.enable()
        ledger.enable(mode="raise")
        results = dist.spawn(_dp4_worker, nprocs=4).results
        by_kind = {}
        for i, res in enumerate(results):
            by_kind.setdefault(res[0], []).append((i, res))
        divs = by_kind.get("divergence", [])
        assert divs, results
        detector, (_, err, _, _) = divs[0]
        assert err.kind == "cross_rank"
        assert err.step == 5, "detection must land at step 5"
        assert err.rank == 2, "majority vote must name rank 2"
        # the error names the exact parameter — the DIVERGENT rank's
        # human name substituted back into the positional entry key
        # (every rank's worker returns its own name list at index 3)
        rank2_names = results[2][3]
        assert err.tensor.split(":", 1)[1] in rank2_names, \
            (err.tensor, rank2_names)
        assert err.tensor.startswith(("grad:", "param:"))
        # rank 2's digest is the odd one out in the error payload
        assert err.digests[2] != err.digests[(set(err.digests) - {2}).pop()]
        for _i, res in by_kind.get("peer_failure", []):
            assert res[2] == detector    # failures name the dead rank
        # telemetry + latch + flight event
        c = get_registry().get("paddle_ledger_divergence_total")
        assert c.value(kind="cross_rank") >= 1
        g = get_registry().get("paddle_ledger_divergent_steps")
        assert g.value() >= 1            # the alert rule's signal
        latched = ledger.get_ledger().divergences()
        assert any(d["step"] == 5 and d["rank"] == 2 for d in latched)
        fr = flight.get_flight_recorder()
        assert any(e.get("divergence") == "cross_rank" and e.get("step") == 5
                   for e in fr.events(kind="ledger"))
        # alert: one history tick evaluates the built-in threshold rule
        eng = alerts.get_alert_engine()
        assert "numerics_divergence" in eng.rules
        timeseries.get_history().tick()
        active = alerts.active_alerts()
        assert "numerics_divergence" in active
        assert active["numerics_divergence"]["severity"] == "page"
        # watchdog dump carries the ledger provider with the latch
        out = fr.dump(reason="test", directory=str(tmp_path))
        with open(next(iter(out["ranks"].values()))) as f:
            dumped = json.load(f)
        led_state = dumped["state"]["ledger"]
        assert any(d["step"] == 5 and d["rank"] == 2
                   for d in led_state["divergences"])
        assert led_state["mode"] == "raise"

    def test_warn_mode_records_and_continues(self, monkeypatch):
        """Same bitflip, PADDLE_LEDGER_MODE=warn: every rank completes,
        the divergence is latched (step 5, rank 2) instead of raised."""
        monkeypatch.setenv("PADDLE_FAULT_PLAN", "bitflip:rank=2,step=5")
        monkeypatch.setenv("PADDLE_COMM_OVERLAP_TIMEOUT_S", "60")
        fault.clear()
        ledger.enable(mode="warn")
        results = dist.spawn(_dp4_worker, nprocs=4).results
        assert all(res[0] == "done" for res in results), \
            [res[0] for res in results]
        latched = ledger.get_ledger().divergences()
        assert any(d["kind"] == "cross_rank" and d["step"] == 5
                   and d["rank"] == 2 for d in latched)

    def test_warn_mode_is_bit_identical_to_disabled(self):
        """With the ledger in warn mode and no fault, the dp-4 loss
        trajectory AND final params are bit-identical to ledger-off
        (the sensing layer is read-only), and no divergence latches."""

        def run(sense):
            if sense:
                ledger.enable(mode="warn")
            else:
                ledger.disable()
                ledger.reset()
            results = dist.spawn(_dp4_worker, nprocs=4).results
            assert all(res[0] == "done" for res in results)
            return results

        sensed = run(True)
        assert ledger.get_ledger().divergences() == []
        plain = run(False)
        for (_, l_a, p_a, _), (_, l_b, p_b, _) in zip(sensed, plain):
            for a, b in zip(l_a, l_b):
                np.testing.assert_array_equal(a, b)
            for a, b in zip(p_a, p_b):
                np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# cross-process tier: publish / gather / compare over the KV path
# ---------------------------------------------------------------------------


def test_publish_gather_compare_store():
    ledger.enable(mode="warn")
    led = ledger.get_ledger()
    base = {"grad:p0000": "gg", "param:p0000": "w0"}
    led._commit(0, 0, dict(base), {"p0000": "w"})
    led._commit(1, 0, dict(base, **{"param:p0000": "w1"}), {"p0000": "w"})
    store = MemKVStore()
    assert ledger.publish_ledger(store, rank=0) == 1
    assert ledger.publish_ledger(store, rank=1) == 1
    got = ledger.gather_ledgers(store)
    assert set(got) == {0, 1} and set(got[0]) == {0}
    div = ledger.compare_store(store)
    assert div is not None
    assert (div["step"], div["tensor"]) == (0, "param:p0000")
    assert div["rank"] == 1              # two-way tie sides with rank 0
    # identical ledgers compare clean
    store2 = MemKVStore()
    led2 = ledger.StepLedger(mode="warn")
    led2._commit(0, 0, dict(base))
    led2._commit(1, 0, dict(base))
    for row in led2.rows():
        flight.publish_component_state(
            store2, f"{ledger.KV_LEDGER_PREFIX}{row['rank']}/{row['step']}",
            row)
    assert ledger.compare_store(store2) is None


def test_store_attached_commit_publishes():
    store = MemKVStore()
    ledger.enable(mode="warn", store=store)
    net = _mlp(5)
    opt = paddle.optimizer.SGD(learning_rate=0.05,
                               parameters=net.parameters())
    loss = (net(paddle.to_tensor(np.ones((2, 16), np.float32))) ** 2).mean()
    loss.backward()
    opt.step()
    got = ledger.gather_ledgers(store)
    assert 0 in got and 0 in got[0]
    assert any(k.startswith("grad:") for k in got[0][0])


# ---------------------------------------------------------------------------
# serving: token streams, attestation, handoff digests
# ---------------------------------------------------------------------------


class TestAttestationUnit:
    def test_chain_and_matching_streams_pass(self):
        led = ledger.enable(mode="raise")
        toks = [5, 6, 7]
        for t in toks:
            led.note_stream_token("tr", 1, t)
        for t in toks + [8]:
            led.note_stream_token("tr", 2, t)
        # the chain digest is the documented recurrence
        want = ledger.STREAM_SEED
        for t in toks:
            want = ledger.chain_update(want, t)
        assert led.streams("tr")[1]["digest"] == want
        dg = led.attest_delivery("tr", attempt=2)
        assert dg == led.streams("tr")[2]["digest"]
        c = get_registry().get("paddle_ledger_attestations_total")
        assert c.value(result="pass") >= 1

    def test_tampered_stream_fails_attestation(self):
        led = ledger.enable(mode="raise")
        for t in [5, 6, 7]:
            led.note_stream_token("trx", 1, t)
        for t in [5, 9, 7, 8]:                 # diverges at position 1
            led.note_stream_token("trx", 2, t)
        with pytest.raises(DivergenceError) as ei:
            led.attest_delivery("trx", attempt=2)
        assert ei.value.kind == "attestation"
        assert ei.value.tensor == "tokens:trx"
        assert ei.value.rank == 1              # the non-delivering attempt
        c = get_registry().get("paddle_ledger_attestations_total")
        assert c.value(result="fail") >= 1
        # warn mode records and returns the digest
        led2 = ledger.enable(mode="warn")
        for t in [1, 2]:
            led2.note_stream_token("trw", 1, t)
        for t in [1, 3]:
            led2.note_stream_token("trw", 2, t)
        assert led2.attest_delivery("trw", attempt=2) is not None
        assert any(d["kind"] == "attestation"
                   for d in led2.divergences())

    def test_handoff_blob_seal_and_tamper(self):
        led = ledger.enable(mode="raise")
        blob = {"page_size": 16, "kv_dtype": "native",
                "native_dtype": "float32",
                "digests": [b"\x01" * 20, b"\x02" * 20],
                "layers": [(np.ones((2, 2, 16, 4), np.float32),
                            np.zeros((2, 2, 16, 4), np.float32))],
                "scales": None}
        blob["ledger_digest"] = led.seal_handoff(blob)
        # sealing is idempotent: the digest ignores itself
        assert ledger.blob_digest(blob) == blob["ledger_digest"]
        led.check_handoff(blob)                # bit-exact: passes
        blob["layers"][0][0][0, 0, 0, 0] = 2.0
        with pytest.raises(DivergenceError) as ei:
            led.check_handoff(blob)
        assert ei.value.kind == "handoff"
        c = get_registry().get("paddle_ledger_digests_total")
        assert c.value(kind="handoff") >= 3


class TestServingAttestation:
    def test_engine_outputs_bit_identical_and_trace_digest(self, model):
        """Ledger-on serving outputs are bit-identical to ledger-off,
        and the trace's terminal span carries the stream digest that
        matches a hand-computed chain over the generated tokens."""
        p = _shared_prompts(n_req=1, seed=3)[0]

        def run():
            eng = ContinuousServingEngine(model, **ENGINE_KW)
            with eng:
                return np.asarray(eng.generate(
                    p, max_new_tokens=6, timeout=600).numpy())

        off = run()
        ledger.enable(mode="raise")
        on = run()
        np.testing.assert_array_equal(on, off)
        # trace terminal span carries token_digest
        store = rt.get_trace_store()
        tid = store.trace_ids()[-1]
        rec = store.timeline(tid)
        done = [s for s in rec["spans"] if s["name"] == "done"][0]
        dg = (done.get("tags") or {}).get("token_digest")
        assert dg, rec["spans"]
        want = ledger.STREAM_SEED
        for t in on[0, p.shape[1]:p.shape[1] + 6]:
            want = ledger.chain_update(want, int(t))
        assert dg == want

    def test_requeue_attestation_parity(self, model):
        """ISSUE 13 acceptance (serving): hard-kill a replica
        mid-decode; the requeued request's regenerated stream passes
        attestation against the dead attempt's partial stream (digest
        equal over the common prefix), the delivered event carries the
        token digest, and outputs stay bit-identical to the oracle."""
        ledger.enable(mode="raise")      # attestation failure would raise
        prompts = _shared_prompts(n_req=4, sys_len=32, seed=2)
        want = [_oracle(model, p, 12) for p in prompts]
        router = ServingRouter(model, num_replicas=2, policy="balance",
                               engine_kwargs=ENGINE_KW, store=MemKVStore(),
                               heartbeat_ttl=60.0)
        results, errors = [None] * 4, [None] * 4

        def call(i):
            try:
                results[i] = np.asarray(router.generate(
                    prompts[i], max_new_tokens=12, tenant=f"t{i}",
                    timeout=600).numpy())
            except Exception as e:      # noqa: BLE001 — asserted below
                errors[i] = e

        led = ledger.get_ledger()
        store_rt = rt.get_trace_store()
        with router:
            threads = [threading.Thread(target=call, args=(i,))
                       for i in range(4)]
            for t in threads:
                t.start()
            # kill only once some first attempt has DELIVERED tokens —
            # attestation needs a non-empty attempt-1 stream to check
            # the regenerated attempt-2 stream against
            deadline = time.monotonic() + 60    # cold engines, loaded host
            victim = None
            while victim is None and time.monotonic() < deadline:
                for tid in store_rt.trace_ids():
                    st = led.streams(tid)
                    if st and max(st) == 1 and st[1]["count"] >= 2:
                        rec = store_rt.timeline(tid)
                        reps = [s.get("replica") for s in rec["spans"]
                                if s.get("replica")]
                        if not reps:
                            continue
                        r = router._replica(reps[-1])
                        if r.alive and r.inflight:
                            victim = r
                            break
                time.sleep(0.01)
            assert victim is not None, "no mid-decode work to kill under"
            router.kill_replica(victim.id)
            for t in threads:
                t.join()
            stats = router.stats()
        assert not [e for e in errors if e], errors
        for g, w in zip(results, want):
            np.testing.assert_array_equal(g, w)
        assert stats["requeues_total"] >= 1, stats
        # find the requeued trace: it has streams from >= 2 attempts,
        # all digest-consistent, and a delivered token_digest tag
        led = ledger.get_ledger()
        store = rt.get_trace_store()
        requeued = [tid for tid in store.trace_ids()
                    if len(led.streams(tid)) >= 2]
        assert requeued, "no request recorded streams from two attempts"
        for tid in requeued:
            streams = led.streams(tid)
            final = streams[max(streams)]
            rec = store.timeline(tid)
            delivered = [s for s in rec["spans"]
                         if s["name"] == "delivered"][0]
            assert (delivered.get("tags") or {}).get("token_digest") \
                == final["digest"]
        c = get_registry().get("paddle_ledger_attestations_total")
        assert c.value(result="pass") >= 4
        assert ledger.get_ledger().divergences() == []

    def test_disagg_attestation_and_handoff_digests(self, model):
        """Disagg fleet with the ledger on: the prefill replica's
        1-token stream attests against the decode replica's full
        stream, the export blob is sealed and verified bit-exact at
        import, outputs bit-identical to the colocated oracle."""
        ledger.enable(mode="raise")
        prompts = _shared_prompts(n_req=3, sys_len=48, seed=4)
        want = [_oracle(model, p, 4) for p in prompts]
        router = ServingRouter(model, num_replicas=2, disagg=True,
                               engine_kwargs=ENGINE_KW, store=MemKVStore(),
                               heartbeat_ttl=60.0)
        with router:
            results = [np.asarray(router.generate(
                p, max_new_tokens=4, timeout=600).numpy())
                for p in prompts]
            dec = router.replicas[1]
            assert dec.engine._cache.pages_imported > 0
        for g, w in zip(results, want):
            np.testing.assert_array_equal(g, w)
        led = ledger.get_ledger()
        # at least one request produced tokens on BOTH replicas
        # (prefill attempt = 1 token, decode attempt = the full stream)
        multi = [tid for tid in rt.get_trace_store().trace_ids()
                 if len(led.streams(tid)) >= 2]
        assert multi, "no trace recorded prefill AND decode streams"
        for tid in multi:
            counts = sorted(s["count"]
                            for s in led.streams(tid).values())
            assert counts[0] == 1        # the prefill replica's token
        # the export was sealed, the import verified, nothing diverged
        st = led.state()
        dirs = [h["direction"] for h in st["handoffs"]]
        assert "export" in dirs and "import" in dirs
        assert led.divergences() == []
        c = get_registry().get("paddle_ledger_digests_total")
        assert c.value(kind="handoff") >= 2


# ---------------------------------------------------------------------------
# golden ledger + ledger_diff CLI
# ---------------------------------------------------------------------------


def _seeded_train(tmp_path, tag, flip_step=None, steps=4):
    """One seeded single-rank training run with a fresh ledger; exports
    and returns the golden path."""
    ledger.reset()
    fault.clear()
    if flip_step is not None:
        fault.install(f"bitflip:rank=0,step={flip_step}")
    ledger.enable(mode="warn")
    net = _mlp(0)
    # deterministic parameter names: the auto-assigned ones come from a
    # process-global counter, which would differ between two in-process
    # runs (two real processes get identical names for free)
    for i, p in enumerate(net.parameters()):
        p.name = f"w{i}"
    opt = paddle.optimizer.SGD(learning_rate=0.05,
                               parameters=net.parameters())
    rngX = np.random.default_rng(7)
    X = rngX.normal(size=(4 * steps, 16)).astype(np.float32)
    for s in range(steps):
        fault.check_step(s)
        loss = (net(paddle.to_tensor(X[s * 4:(s + 1) * 4])) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
    path = ledger.export_golden(str(tmp_path / f"{tag}.jsonl"))
    ledger.disable()
    fault.clear()
    return path


def _run_ledger_diff(argv):
    """Run tools/ledger_diff.py in a jax/numpy-poisoned subprocess
    (laptop-vs-fleet-ledgers discipline)."""
    tool = os.path.join(REPO, "tools", "ledger_diff.py")
    code = (
        "import sys\n"
        "sys.modules['jax'] = None\n"
        "sys.modules['numpy'] = None\n"
        f"sys.argv = {argv!r}\n"
        "import runpy\n"
        "try:\n"
        f"    runpy.run_path({tool!r}, run_name='__main__')\n"
        "except SystemExit as e:\n"
        "    raise SystemExit(e.code or 0)\n")
    return subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=60)


class TestGoldenLedger:
    def test_same_seed_runs_are_byte_identical(self, tmp_path):
        """ISSUE 13 acceptance: two same-seed runs export byte-identical
        golden ledgers, and ledger_diff reports them identical (exit 0)
        with jax AND numpy poisoned out of the interpreter."""
        a = _seeded_train(tmp_path, "a")
        b = _seeded_train(tmp_path, "b")
        with open(a, "rb") as fa, open(b, "rb") as fb:
            assert fa.read() == fb.read()
        proc = _run_ledger_diff(["ledger_diff.py", a, b])
        assert proc.returncode == 0, proc.stderr
        assert "identical" in proc.stdout

    def test_diff_names_first_divergent_step_and_tensor(self, tmp_path):
        """A bitflipped run diverges from the golden; the CLI names the
        first divergent step (the flip step) and the tensor, exit 1."""
        golden = _seeded_train(tmp_path, "golden")
        bad = _seeded_train(tmp_path, "bad", flip_step=2)
        proc = _run_ledger_diff(["ledger_diff.py", golden, bad])
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "FIRST DIVERGENCE: step 2 rank 0" in proc.stdout
        assert "grad:" in proc.stdout
        # steps before the flip agree — step 2 is the FIRST divergence
        assert "step 0" not in proc.stdout and "step 1" not in proc.stdout
        # --json mode round-trips
        proc = _run_ledger_diff(["ledger_diff.py", "--json", golden, bad])
        out = json.loads(proc.stdout)
        assert not out["identical"]
        assert out["divergences"][0]["step"] == 2

    def test_diff_reports_stream_divergence(self, tmp_path):
        led = ledger.enable(mode="warn")
        for t in [1, 2, 3]:
            led.note_stream_token("req-a", 1, t)
        a = ledger.export_golden(str(tmp_path / "sa.jsonl"))
        ledger.reset()
        led = ledger.enable(mode="warn")
        for t in [1, 9, 3]:
            led.note_stream_token("req-a", 1, t)
        b = ledger.export_golden(str(tmp_path / "sb.jsonl"))
        proc = _run_ledger_diff(["ledger_diff.py", a, b])
        assert proc.returncode == 1
        assert "FIRST DIVERGENCE: request req-a" in proc.stdout

    def test_cli_bad_input_exit_2(self, tmp_path):
        good = _seeded_train(tmp_path, "g")
        missing = str(tmp_path / "nope.jsonl")
        assert _run_ledger_diff(
            ["ledger_diff.py", good, missing]).returncode == 2
        notjson = tmp_path / "bad.jsonl"
        notjson.write_text("this is not a ledger\n")
        assert _run_ledger_diff(
            ["ledger_diff.py", good, str(notjson)]).returncode == 2

    def test_golden_env_default_path(self, tmp_path, monkeypatch):
        monkeypatch.setenv("PADDLE_LEDGER_GOLDEN",
                           str(tmp_path / "env_golden.jsonl"))
        ledger.enable(mode="warn")
        ledger.get_ledger()._commit(0, 0, {"grad:p0000": "x"})
        path = ledger.export_golden()
        assert path == str(tmp_path / "env_golden.jsonl")
        assert os.path.exists(path)

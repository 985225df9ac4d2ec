"""The benchmark's own tests (CPU, tiny sizes, no TPU topology, no chip):
that ``BENCHMARK.json`` and the files it names hang together, that the
yardstick's arithmetic is right, that a run without a TPU refuses, and that
both drivers run end to end through their functions with interpret-mode
kernels. Nothing seen here is a device result.
"""
import copy
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import flops, harness, trace_reduce  # noqa: E402
from benchmark import run as runmod  # noqa: E402
from benchmark.traffic import generate  # noqa: E402

MANIFEST = harness.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in MANIFEST["workloads"]]
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
MISTRAL = dict(hidden_size=4096, intermediate_size=14336, head_dim=128,
               num_attention_heads=32, num_key_value_heads=8,
               vocab_size=32768, num_hidden_layers=4)
TINY = dict(hidden_size=64, intermediate_size=176, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            vocab_size=128, max_position_embeddings=128)


# -- the manifest and what it names -----------------------------------------

@pytest.mark.parametrize("cell", CELLS)
def test_cell_is_found_by_name(cell):
    entry, config, traffic = harness.find_cell(MANIFEST, cell)
    assert config["source"].startswith("https://huggingface.co/mistralai/")
    for key in ("reduced", "assumed", "deployment"):
        assert key in config, key
    cfg_entry = next(c for c in MANIFEST["configs"]
                     if c["name"] == entry["config"])
    assert sorted(cfg_entry["reduced"]) == sorted(config["reduced"])
    assert os.path.exists(os.path.join(REPO, "benchmark", "drivers",
                                       traffic["driver"] + ".py"))
    limits = harness.load_json(os.path.join(REPO, "benchmark", "limits",
                                            cell + ".json"))
    assert limits
    assert entry["chips"] in (1, 4) and len(entry["why"]) <= 200


@pytest.mark.parametrize("config", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_config_keeps_published_widths(config):
    """No width is cut: only depth and the positions kept."""
    body = harness.load_json(os.path.join(REPO, config["file"]))
    for key, value in dict(MISTRAL, num_hidden_layers=None).items():
        if value is not None:
            assert body[key] == value, key
    assert set(config["reduced"]) <= {"num_hidden_layers",
                                      "max_position_embeddings"}
    assert any(w["config"] == config["name"]
               for w in MANIFEST["workloads"])


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_layer_metric_has_a_reader_and_moves_what_its_cells_report(metric):
    assert callable(harness.load_reader(metric["name"]))
    assert harness.load_reader(metric["name"])({}) is None   # nothing to read
    moved = next(m for m in MANIFEST["end_to_end"]
                 if m["name"] == metric["moves"])
    for cell in metric["workloads"]:
        assert cell in CELLS
        assert "workloads" not in moved or cell in moved["workloads"]


@pytest.mark.parametrize("entry", METRICS + MANIFEST["workloads"]
                         + MANIFEST["configs"], ids=lambda e: e["name"])
def test_names_and_units_use_allowed_characters(entry):
    assert NAME.match(entry["name"])
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
        assert "workloads" in entry or entry["name"] == "setup_s"


def test_manifest_shape():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert any(m["name"] == "setup_s" for m in MANIFEST["end_to_end"])
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
    for cell in CELLS:
        e2e = harness.metrics_of(MANIFEST, "end_to_end", cell)
        assert len(e2e) >= 2
        assert harness.metrics_of(MANIFEST, "per_layer", cell)


def test_a_cell_a_config_and_a_metric_are_added_with_files_only(
        tmp_path, monkeypatch):
    """New files plus new entries: nothing that is there is edited."""
    here = tmp_path / "benchmark"
    shutil.copytree(os.path.join(REPO, "benchmark"), here,
                    ignore=shutil.ignore_patterns("__pycache__"))
    config = harness.load_json(here / "configs" / "mistral-7b-train-4l.json")
    config["num_hidden_layers"] = 2
    (here / "configs" / "dummy-2l.json").write_text(json.dumps(config))
    (here / "traffic" / "dummy_rows.json").write_text(json.dumps(
        {"driver": "train", "kind": "token_rows", "seq": 64}))
    (here / "limits" / "dummy_cell.json").write_text(json.dumps({"x": 1.0}))
    (here / "layer_metrics" / "dummy.metric.py").write_text(
        "def read(run):\n    return run.get('tokens')\n")
    manifest = copy.deepcopy(MANIFEST)
    manifest["configs"].append({
        "name": "dummy-2l", "source": "s", "why": "w", "reduced": [],
        "file": "benchmark/configs/dummy-2l.json"})
    manifest["workloads"].append({
        "name": "dummy_cell", "config": "dummy-2l", "traffic": "dummy_rows",
        "chips": 1, "why": "w"})
    manifest["per_layer"].append({
        "name": "dummy.metric", "unit": "tokens", "better": "higher",
        "source": "program_counter", "layer": "model step",
        "moves": "train_tok_s", "workloads": ["dummy_cell"]})
    monkeypatch.setattr(harness, "HERE", str(here))
    cell, cfg, traffic = harness.find_cell(manifest, "dummy_cell",
                                           root=str(tmp_path))
    assert cfg["num_hidden_layers"] == 2 and traffic["seq"] == 64
    names = [m["name"] for m in harness.metrics_of(manifest, "per_layer",
                                                   "dummy_cell")]
    assert names == ["dummy.metric"]
    assert harness.load_reader("dummy.metric")({"tokens": 7}) == 7
    with pytest.raises(harness.Refused):
        harness.find_cell(manifest, "no_such_cell")
    with pytest.raises(harness.Refused):
        harness.load_reader("no_such_metric")


# -- traffic ----------------------------------------------------------------

def chat():
    return harness.load_json(os.path.join(REPO, "benchmark", "traffic",
                                          "chat_closed_32.json"))


def test_closed_loop_traffic_is_seeded_with_one_length_grid():
    a = generate.closed_loop_requests(chat(), 5, 32768)
    b = generate.closed_loop_requests(chat(), 5, 32768)
    c = generate.closed_loop_requests(chat(), 2**31 + 9, 32768)
    assert all((x[0] == y[0]).all() and x[1] == y[1]
               for ra, rb in zip(a, b) for x, y in zip(ra, rb))
    rounds = chat()["requests_per_client"]
    assert all(len(reqs) == 1 + rounds for reqs in a)

    def round_lens(plan, r):
        return (sorted(len(reqs[1 + r][0]) for reqs in plan),
                sorted(reqs[1 + r][1] for reqs in plan))

    # every round holds the same lengths, under every seed
    for r in range(rounds):
        assert round_lens(a, r) == round_lens(c, r) == round_lens(a, 0)
    # the mix's deal_seed deals the lengths, anew each round and the same
    # under every seed; another deal_seed is another schedule
    assert [len(p) for p, _ in a[0][1:]] == [len(p) for p, _ in c[0][1:]]
    assert [len(r[1][0]) for r in a] != [len(r[2][0]) for r in a]
    d = generate.closed_loop_requests(dict(chat(), deal_seed=25), 5, 32768)
    assert [len(p) for p, _ in a[0][1:]] != [len(p) for p, _ in d[0][1:]]
    assert round_lens(d, 0) == round_lens(a, 0)
    with pytest.raises(KeyError):
        generate.closed_loop_requests(
            {k: v for k, v in chat().items() if k != "deal_seed"}, 5, 32768)
    pl, ol = round_lens(a, 0)
    assert min(pl) >= 32 and max(pl) <= 1536
    assert 230 <= pl[len(pl) // 2] <= 280 and 8 <= min(ol) <= max(ol) <= 192
    # the ramp staggers the clients the same way whatever the seed
    assert [r[0][1] for r in a] == [r[0][1] for r in c]
    assert not (a[0][1][0][:8] == c[0][1][0][:8]).all()     # other token ids


def test_open_loop_arrivals_are_seeded_and_burst():
    mix = {"kind": "open_loop", "arrivals": "poisson", "duration_s": 200.0,
           "rate_rps": 2.0}
    a, b = generate.arrivals(mix, 2**31 + 3), generate.arrivals(mix, 2**31 + 3)
    assert a == b and a == sorted(a) and a[-1] < 200.0
    assert 330 <= len(a) <= 470 and a != generate.arrivals(mix, 4)
    burst = dict(mix, arrivals="bursty", burst_factor=10.0,
                 burst_start_frac=0.35, burst_dur_frac=0.25)
    t = generate.arrivals(burst, 9)
    inside = sum(1 for x in t if 70.0 <= x < 120.0)
    assert inside > 3 * (len(t) - inside) / 3    # 50 s at 10x vs 150 s at 1x
    with pytest.raises(ValueError):
        generate.arrivals(dict(mix, arrivals="adversarial"), 1)
    with pytest.raises(ValueError):
        generate.arrivals(chat(), 1)


def test_training_feed_is_seeded_and_rows_differ():
    t = {"kind": "token_rows", "seq": 16}
    a, b = (generate.token_rows(t, 2**31 + 5, 100, 2) for _ in range(2))
    x, y = next(a), next(b)
    assert (x == y).all() and x.shape == (2, 17)
    assert not (x[0] == x[1]).all() and not (next(a) == x).all()
    with pytest.raises(ValueError):
        next(generate.token_rows({"kind": "closed_loop"}, 0, 100, 2))


# -- the yardstick's arithmetic ---------------------------------------------

def test_flops_against_hand_counts_at_mistral_widths():
    assert flops.layer_matmul_params(MISTRAL) == 218_103_808
    assert flops.head_params(MISTRAL) == 134_217_728
    # one layer's causal attention over 4096 tokens: 4 * 32 * 128 * s(s+1)/2
    assert flops.attention_flops(MISTRAL, 4096, 4096) == \
        4 * 4096 * (4096 * 4097 // 2)
    assert flops.attention_flops(MISTRAL, 1, 100) == 4 * 4096 * 100
    per_token = flops.train_flops_per_token(MISTRAL, 4096)
    hand = 3 * (2 * (4 * 218_103_808 + 134_217_728)
                + 4 * 4 * 4096 * 4097 / 2)
    assert per_token == pytest.approx(hand) and 6.4e9 < per_token < 6.5e9
    spans = [(1, 100), (256, 256)]
    assert flops.serve_flops(MISTRAL, spans, 2) == (
        2 * 4 * 218_103_808 * 257
        + 4 * (4 * 4096 * 100 + 4 * 4096 * (256 * 257 // 2))
        + 2 * 134_217_728 * 2)
    assert flops.flash_flops(MISTRAL, 2, 4096, True) == \
        2 * flops.flash_flops(MISTRAL, 2, 4096, False)
    peaks = harness.load_peaks("TPU v5 lite")
    t, bound = flops.roofline_seconds(
        flops.flash_flops(MISTRAL, 1, 4096, False),
        flops.flash_bytes(MISTRAL, 1, 4096, False), peaks)
    assert bound == "compute" and t == pytest.approx(
        4 * 4096 * (4096 * 4097 // 2) / 197e12)


def test_percentile_rate_and_mfu():
    assert harness.percentile([1, 2, 3, 4, 5], 50) == 3
    assert harness.percentile(range(101), 95) == pytest.approx(95)
    assert harness.percentile([10], 95) == 10
    with pytest.raises(ValueError):
        harness.percentile([], 95)
    assert harness.rate(300, 20.0) == 15.0
    assert harness.mfu_pct(197e12 * 10, 20.0, 1, 197e12) == 50.0
    assert harness.mfu_pct(197e12 * 10, 20.0, 4, 197e12) == 12.5


def _records(stall):
    """Two requests, a token every 0.1 s; ``stall`` holds tokens back for
    that long in the middle of the window."""
    from benchmark.drivers import serve
    recs = []
    for c in range(2):
        r = serve.Record(c, 1, False, np.arange(10), 50)
        r.t_send = 1.0
        times = [1.5 + 0.1 * i for i in range(50)]
        r.tokens = [t + (stall if t > 3.0 else 0.0) for t in times]
        r.output, r.t_done = np.zeros(50, int), r.tokens[-1]
        recs.append(r)
    return recs


def test_a_stall_in_the_window_moves_every_serving_metric():
    from benchmark.drivers import serve
    smooth = serve.end_to_end(serve.window_metrics(_records(0.0), 0.95, 5.95))
    stalled = serve.end_to_end(serve.window_metrics(_records(2.0), 0.95,
                                                    5.95))
    assert smooth["serve_tok_s"] == pytest.approx(90 / 5.0)  # 45 each
    assert stalled["serve_tok_s"] < 0.7 * smooth["serve_tok_s"]
    assert smooth["itl_mean_ms"] == pytest.approx(100.0)
    assert smooth["itl_p95_ms"] == pytest.approx(100.0)
    # 2 of the 48 gaps in the window are the stall: the mean gap and the
    # tokens per second see them, the 95th percentile (per-layer) does not
    assert stalled["itl_mean_ms"] == pytest.approx((46 * 100 + 2 * 2100) / 48)
    assert stalled["itl_p95_ms"] >= smooth["itl_p95_ms"]
    assert smooth["ttft_p95_ms"] == pytest.approx(500.0)
    late = _records(0.0)
    late[1].tokens = []                       # never answered: failed
    wm = serve.window_metrics(late, 0.95, 5.95, cut_at=9.0)
    assert wm["failed"] == 1 and wm["attempted"] == 2
    assert max(wm["ttft"]) == pytest.approx(8.0)


def test_the_serving_window_is_made_of_whole_ticks():
    """Tokens come in bursts, one a tick. The window runs between the first
    deliveries after its nominal ends, so however those fall against the
    ticks it holds whole ticks, and the rate does not swing by a burst."""
    from benchmark.drivers import serve
    recs = []
    for c in range(4):                      # 4 slots, a tick every 0.5 s
        r = serve.Record(c, 1, False, np.arange(10), 100)
        r.t_send = 0.0
        r.tokens = [1.0 + 0.5 * i + 0.001 * c for i in range(100)]
        r.output, r.t_done = np.zeros(100, int), r.tokens[-1]
        recs.append(r)
    rates = []
    for open_at in (3.05, 3.26, 3.49):      # anywhere inside a tick
        s0 = serve.first_delivery(recs, open_at)
        s1 = serve.first_delivery(recs, open_at + 10.0)
        assert s0 == pytest.approx(3.5) and s1 == pytest.approx(13.5)
        wm = serve.window_metrics(recs, s0, s1)
        assert wm["delivered"] == 80 and wm["window_s"] == pytest.approx(10.0)
        rates.append(serve.end_to_end(wm)["serve_tok_s"])
    assert rates == [pytest.approx(8.0)] * 3
    # no delivery after the close: the nominal end stands
    assert serve.first_delivery(recs, 99.0) == 99.0


def test_train_rate_is_over_the_whole_window():
    assert harness.rate(40 * 4096, 20.0) == 8192.0
    assert harness.rate(40 * 4096, 22.0) < 8192.0    # a 2 s stall shows


# -- the trace reduction, on the recorded fixture ---------------------------

FIXTURE = os.path.join(REPO, "benchmark", "fixtures", "trace_train_small.json")


def test_trace_reduce_on_the_recorded_fixture():
    trace = harness.load_json(FIXTURE)
    planes = trace_reduce.device_planes(trace)
    assert [p["name"] for p in planes] == ["/device:TPU:0"]
    events = trace_reduce.op_events(planes[0])
    assert events == sorted(events, key=lambda e: (e[1], -e[2]))
    red = trace_reduce.reduce_trace(trace)
    span = (max(e[1] + e[2] for e in events) - events[0][1]) / 1e9
    assert 0 < red["busy_s"] <= span
    by_name = trace_reduce.self_seconds_by_name(events)
    assert sum(by_name.values()) == pytest.approx(red["busy_s"], rel=1e-6)
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10
    assert red["device_ops"][0][1] >= red["device_ops"][-1][1]
    gaps = [g for _, g in red["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert red["busy_s"] + sum(
        b[0] - a[1] for a, b in zip(trace_reduce.busy_intervals(events),
                                    trace_reduce.busy_intervals(events)[1:])
    ) / 1e9 == pytest.approx(span, rel=1e-9)


def test_trace_reduce_by_hand():
    ev = [["fusion", 0, 10], ["inner", 2, 3], ["copy", 20, 5],
          ["fusion", 40, 10], ["all-gather.1", 45, 20]]
    assert trace_reduce.busy_seconds(ev) == pytest.approx(40e-9)
    by_name = trace_reduce.self_seconds_by_name(ev)
    # the first fusion without its child, the second without the part the
    # overlapping collective covers: names add up to busy time
    assert by_name["fusion"] == pytest.approx(12e-9)
    assert sum(by_name.values()) == pytest.approx(40e-9)
    gaps = trace_reduce.idle_gaps(ev, [["bench:tick", 24, 20]])
    assert gaps[0] == ["bench:tick", pytest.approx(15e-9)]
    assert gaps[1] == ["unattributed", pytest.approx(10e-9)]
    assert trace_reduce.exposed_seconds(ev, r"^all-gather") == \
        pytest.approx(15e-9)
    assert trace_reduce.seconds_matching(ev, "^fusion") == \
        (pytest.approx(20e-9), 2)
    with pytest.raises(KeyError):
        trace_reduce.op_events({"name": "/device:TPU:0", "lines": []})
    with pytest.raises(KeyError):
        trace_reduce.reduce_trace({"planes": [{"name": "/host:CPU",
                                               "lines": []}]})


# -- what never falls back ---------------------------------------------------

def test_peaks_table_refuses_an_unknown_device_kind():
    assert harness.load_peaks("TPU v5 lite")["bf16_flops"] == 197e12
    for kind in ("cpu", "TPU v4", "source"):
        with pytest.raises(harness.Refused):
            harness.load_peaks(kind)


def test_command_refuses_a_cpu_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        MANIFEST["command"] + ["--workload", CELLS[0], "--seed", "1",
                               "--seconds", "1", "--trace", "0"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_judge_and_the_printed_comparison(capsys):
    checks = [("a", 0.5, 1.0), ("b", float("nan"), 1.0)]
    assert harness.judge(checks[:1]) and not harness.judge(checks)
    harness.emit({"correct": False}, checks)
    out, err = capsys.readouterr()
    last = json.loads(out.strip().splitlines()[-1])
    assert list(last)[-1] == "checks" and last["checks"]["a"]["limit"] == 1.0
    assert err.strip().splitlines()[-1].startswith("check b:")
    assert "FAILED" in err


def test_setup_s_counts_from_the_start_of_the_process(monkeypatch):
    """``setup_s`` is the process's age as the window opens; the part before
    the chip is in hand and the part after it are per-layer readings."""
    age = harness._process_age()
    assert 0 < age < 3600
    # /proc/uptime ticks in hundredths of a second
    assert age - 0.05 <= harness.since_start() <= age + 5.0
    monkeypatch.setattr(harness, "DEVICE_AT", None)
    assert harness.pre_device_seconds() is None
    monkeypatch.setattr(harness, "DEVICE_AT", harness.START + 9.5)
    assert harness.pre_device_seconds() == pytest.approx(9.5)
    before = harness.load_reader("pre_device_s")
    after = harness.load_reader("device_setup_s")
    run = {"pre_device_s": 9.5, "end_to_end": {"setup_s": 41.0}}
    assert before(run) == 9.5 and after(run) == pytest.approx(31.5)
    assert before({}) is None and after({"end_to_end": {}}) is None
    for name in ("pre_device_s", "device_setup_s"):
        m = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
        assert m["moves"] == "setup_s" and sorted(m["workloads"]) == \
            sorted(CELLS)


@pytest.mark.parametrize("who,numbers,correct", [
    ("program", {"served_logit_gap_max": 0.1}, True),
    ("control_int8", {"served_logit_gap_max": 0.7}, False),
    ("altered_token", {"served_logit_gap_max": 1.3}, False),
    ("control_int8", {"served_logit_gap_max": 0.1}, True),
])
def test_calibrate_judges_every_row_by_the_committed_limits(
        who, numbers, correct, tmp_path, capsys):
    """A stand-in row that passes the limits is counted as wrong."""
    from benchmark import calibrate
    limit = harness.load_json(os.path.join(
        REPO, "benchmark", "limits", "serve_chat_closed.json"))[
            "served_logit_gap_max"]
    r = calibrate.row("serve_chat_closed", 1, who,
                      [(n, v, limit) for n, v in numbers.items()])
    assert r["correct"] is correct and r["expected"] is (who == "program")
    rec = tmp_path / "rows.jsonl"
    rec.write_text(json.dumps({"cell": "serve_chat_closed", "seed": 1,
                               "who": who, "numbers": numbers}) + "\n")
    wrong = calibrate.rejudge(str(rec))
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is correct and out["limits"] == {
        "served_logit_gap_max": limit}
    assert wrong == (correct != (who == "program"))


# -- both drivers, end to end at a tiny size ---------------------------------

FAKE_DEVICE = {"platform": "cpu-rehearsal", "kind": "TPU v5 lite"}


def tiny_ctx(cell, seed, **over):
    entry, config, traffic = harness.find_cell(MANIFEST, cell)
    config.update(TINY)
    ctx = {"cell": entry, "config": config, "traffic": traffic, "seed": seed,
           "seconds": 1.0, "trace": False,
           "chips": 1, "peaks": harness.load_peaks("TPU v5 lite"),
           "watch": harness.CompileWatch()}
    ctx.update(over)
    return ctx


def last_line(ctx, run, capsys):
    run["peaks"], run["chips"], run["config"] = ctx["peaks"], 1, ctx["config"]
    harness.emit(runmod.result_line(ctx, MANIFEST, run, FAKE_DEVICE),
                 run["checks"])
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


# the tiny size's own limits, from its own readings on the CPU over five
# seeds: the program's change gap read 2.7e-4..5.6e-4, the int8 control's
# 8.2e-4..1.2e-3 (the chip's limits are in benchmark/limits/)
TRAIN_LIMITS = {"grad_norm_worst_leaf_gap": 0.02,
                "grad_norm_median_leaf_gap": 0.02,
                "change_norm_worst_leaf_gap": 7e-4}


def train_ctx(seed, **over):
    ctx = tiny_ctx("train_dense_1chip", seed, limits=TRAIN_LIMITS, **over)
    ctx["config"]["trainer"]["batch"] = 2
    ctx["traffic"]["seq"] = 128
    return ctx


def test_train_driver_end_to_end(capsys):
    from benchmark.drivers import train
    ctx = train_ctx(2**31 + 11)
    line = last_line(ctx, train.run(ctx), capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tok_s", "setup_s"}
    assert line["metrics"]["train_tok_s"]["unit"] == "tokens/s"
    assert line["attempted"] >= 1 and line["device"]["count"] == 1
    assert list(line)[-1] == "checks" and len(line["checks"]) == 3
    assert line["compiles_in_window"]["backend_compiles"] == 0


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_a_broken_train_step_comes_out_not_correct(fault, capsys):
    """The timed path broken underneath, the rest of the run as it is."""
    from benchmark.drivers import train
    ctx = train_ctx(7, fault=fault)
    line = last_line(ctx, train.run(ctx), capsys)
    assert line["correct"] is False
    failing = [k for k, v in line["checks"].items()
               if not v["value"] <= v["limit"]]
    assert failing
    if fault == "state_unchanged":
        # a leaf that did not move reads 1 by the comparison's measure
        assert line["checks"]["change_norm_worst_leaf_gap"]["value"] == \
            pytest.approx(1.0, abs=1e-3)


SERVE_LIMITS = {"sample_requests": 3, "served_logit_gap_max": 0.02}


def serve_ctx(seed, **over):
    ctx = tiny_ctx("serve_chat_closed", seed, limits=SERVE_LIMITS,
                   seconds=4.0, **over)
    ctx["config"]["engine"] = {"max_batch_size": 4, "max_len": 128,
                               "prefill_chunk_tokens": 16,
                               "token_budget": 16}
    ctx["traffic"].update(
        clients=4, requests_per_client=6,
        prompt_len={"median": 24, "sigma": 0.5, "min": 8, "max": 60},
        output_len={"median": 6, "sigma": 0.5, "min": 2, "max": 12},
        ramp={"prompt_len": 8, "output_min": 2, "output_max": 5,
              "open_after_clients": 2},
        warm={"token_buckets": [4, 8], "job_buckets": [1, 4]},
        reference_width=96, trace_seconds=3)
    return ctx


@pytest.mark.skipif("serve_chat_closed" not in CELLS,
                    reason="the serving cell is not in BENCHMARK.json")
def test_serve_driver_end_to_end_and_an_altered_token(capsys, monkeypatch):
    from benchmark.drivers import serve
    from paddle_tpu.inference import ContinuousServingEngine
    ctx = serve_ctx(2**31 + 13)
    run = serve.run(ctx)
    line = last_line(ctx, run, capsys)
    assert line["correct"] is True, line["checks"]
    assert {"serve_tok_s", "setup_s"} <= set(line["metrics"])
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert run["counters"]["ragged_steps"] >= 1 and run["pages"]
    assert harness.load_reader("sched_tick_ms")(run) > 0
    assert 0 <= harness.load_reader("sched_padded_pct")(run) < 100

    # a token altered where it is produced: the same run, the engine's
    # answer changed underneath, comes out not correct
    real = ContinuousServingEngine.generate

    def altered(self, input_ids, **kw):
        out = real(self, input_ids, **kw)
        out._data = out._data.at[0, -1].set((out._data[0, -1] + 1) % 128)
        return out

    monkeypatch.setattr(ContinuousServingEngine, "generate", altered)
    ctx = serve_ctx(2**31 + 13, control="int8")      # as calibrate runs it
    run = serve.run(ctx)
    line = last_line(ctx, run, capsys)
    assert line["correct"] is False
    assert line["checks"]["served_logit_gap_max"]["value"] > \
        SERVE_LIMITS["served_logit_gap_max"]
    # the rows that calibrate judges beside the program's
    assert set(run["stand_ins"]) == {"control_int8", "altered_token"}
    assert not harness.judge(run["stand_ins"]["altered_token"])


# -- the control: the reference a precision lower, in the program's place ----

@pytest.mark.parametrize("seed", [3, 2**31 + 17, 3000000021])
def test_the_int8_control_comes_out_not_correct_for_training(seed):
    """At a size a test run can hold. On the chip the same control was read
    at the cell's own size (PERF.md, limits)."""
    from benchmark.drivers import train
    ctx = train_ctx(seed)
    config = ctx["config"]
    feed = generate.token_rows(ctx["traffic"], seed, config["vocab_size"], 2)
    batches = [(t[:, :-1], t[:, 1:]) for t in (next(feed) for _ in range(3))]
    ref = train.reference_readings(config, seed, batches)
    control = train.reference_readings(config, seed, batches, quant="int8")
    checks = train.compare(control, ref, TRAIN_LIMITS)
    assert not harness.judge(checks), checks
    assert harness.judge(train.compare(ref, ref, TRAIN_LIMITS))


def test_calibrate_drives_program_control_and_fault_through_the_judge(
        tmp_path, capsys):
    """The rows calibrate writes on the chip, at a size a test can hold:
    the program correct, the int8 control and the planted fault not."""
    from benchmark import calibrate
    ctx = train_ctx(2**31 + 17)
    with open(tmp_path / "rows.jsonl", "w") as out:
        wrong = calibrate.train_seed(ctx, ctx["seed"], True, ["half_batch"],
                                     out)
    capsys.readouterr()
    rows = [json.loads(x) for x in
            (tmp_path / "rows.jsonl").read_text().splitlines()]
    assert {r["who"]: r["correct"] for r in rows} == {
        "program": True, "control_int8": False, "control_bf16_int8": False,
        "fault_half_batch": False}
    assert wrong == 0 and all(r["limits"] == TRAIN_LIMITS for r in rows)


def test_the_int8_control_picks_worse_tokens_when_serving():
    """The control need not decode: at each position of the same prompts and
    tokens, the gap of the token that int8 puts first."""
    from benchmark.reference import llama as ref
    config = dict(harness.find_cell(MANIFEST, CELLS[0])[1], **TINY)
    rng = np.random.default_rng(5)
    seqs = []
    for n, t in ((20, 6), (33, 9), (12, 4)):
        seqs.append((rng.integers(1, 128, n), rng.integers(1, 128, t)))
    gaps = ref.served_gaps(config, 11, seqs, 64, quant="int8")
    assert len(gaps["served"]) == len(gaps["control"]) == 19
    assert min(gaps["served"]) >= 0 and min(gaps["control"]) >= 0
    # random served tokens lie far below the best; the control's picks are
    # the reference's own best or a near-tie
    assert max(gaps["control"]) < max(gaps["served"])
    exact = ref.served_gaps(config, 11, seqs, 64)
    assert exact["control"] is None and exact["served"] == gaps["served"]

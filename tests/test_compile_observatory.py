"""Compile observatory (ISSUE 18): retrace-cause attribution unit tier,
the PADDLE_COMPILE_OBSERVATORY gate, paddle_compile_* metric rollups,
recompile-storm / family-drift alert rules (+ env grammar), the
``/compile`` exporter route and fleet merge, zero post-warmup misses on
mixed / speculative / q-block serving replays, cold-request TTFT
decomposition through log_query, and the compile_report CLI."""
import json
import os
import sys
import threading
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import ContinuousServingEngine
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.profiler import alerts, eventlog, scrape
from paddle_tpu.profiler import compile_observatory as co
from paddle_tpu.profiler import request_trace as rt
from paddle_tpu.profiler.exporter import TelemetryServer
from paddle_tpu.profiler.telemetry import MetricRegistry, get_registry
from paddle_tpu.profiler.timeseries import MetricsHistory

REPO = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, os.path.join(REPO, "tools"))

ENGINE_KW = dict(max_batch_size=2, max_len=48, token_budget=16,
                 prefill_chunk_tokens=16)


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    return LlamaForCausalLM(llama_tiny(num_hidden_layers=1))


@pytest.fixture(autouse=True)
def _fresh_observatory():
    co.reset()
    co.enable()
    yield
    co.reset()
    eventlog.reset()


def _prompts(sizes, seed=1):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 128, (1, n)).astype(np.int64) for n in sizes]


def _drive(eng, prompts, new_tokens):
    results = [None] * len(prompts)
    with eng:
        threads = [threading.Thread(
            target=lambda i=i, p=p: results.__setitem__(
                i, np.asarray(eng.generate(p, max_new_tokens=new_tokens,
                                           timeout=300).numpy())))
            for i, p in enumerate(prompts)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    return results


def _tok(n, dtype="int64"):
    return {"tokens": co.tensor_arg((n,), dtype)}


# ---------------------------------------------------------------------------
# unit tier: cause attribution
# ---------------------------------------------------------------------------

def test_cause_new_family_then_hit():
    r = co.observe("unit.a", _tok(8), seconds=0.5)
    assert r["miss"] and r["cause"] == "new family (family undeclared)"
    r = co.observe("unit.a", _tok(8))
    assert not r["miss"] and r["cause"] is None
    snap = co.snapshot()["families"]["unit.a"]
    assert (snap["hits"], snap["misses"]) == (1, 1)
    assert snap["compile_s"] == pytest.approx(0.5)
    assert snap["signatures"] == 1


def test_cause_bucket_miss_names_argument_and_dim():
    """The acceptance-bar cause string: a shape outside the declared
    bucket set must name the exact argument, dimension, offending value
    and the declared set."""
    co.declare_family("unit.buckets", buckets={"tokens": [128, 256]})
    co.observe("unit.buckets", _tok(128))
    r = co.observe("unit.buckets", _tok(136))
    assert r["cause"] == "arg `tokens` dim0 136∉{128,256}: bucket miss"
    # a declared-but-cold bucket is a "new bucket", not a bucket miss
    r = co.observe("unit.buckets", _tok(256))
    assert r["cause"] == "arg `tokens` dim0 136→256: new bucket"


def test_cause_static_dtype_rank_and_removed_args():
    fam = "unit.static"
    co.declare_family(fam)
    base = {"tokens": co.tensor_arg((8,), "int64"),
            "weight_dtype": co.static_arg("int8")}
    co.observe(fam, base)
    r = co.observe(fam, {"tokens": co.tensor_arg((8,), "int64"),
                         "weight_dtype": co.static_arg("bf16")})
    assert r["cause"] == "static arg `weight_dtype` int8→bf16"
    r = co.observe(fam, {"tokens": co.tensor_arg((8,), "int32"),
                         "weight_dtype": co.static_arg("bf16")})
    assert r["cause"] == "arg `tokens` dtype int64→int32"
    r = co.observe(fam, {"tokens": co.tensor_arg((2, 8), "int32"),
                         "weight_dtype": co.static_arg("bf16")})
    assert r["cause"] == "arg `tokens` rank 1→2"
    r = co.observe(fam, {"tokens": co.tensor_arg((2, 8), "int32")})
    assert r["cause"] == "arg `weight_dtype` removed"
    # undeclared dims diff without bucket vocabulary
    co.observe("unit.free", _tok(4))
    r = co.observe("unit.free", _tok(6))
    assert "arg `tokens` dim0 4→6" in r["cause"]


def test_signature_formatting():
    sig = {"tokens": co.tensor_arg((2, 16), "int64"),
           "weight_dtype": co.static_arg("int8")}
    assert (co.format_signature(sorted(sig.items()))
            == "tokens=int64[2x16], weight_dtype='int8'")


# ---------------------------------------------------------------------------
# gate + snapshot + cost table
# ---------------------------------------------------------------------------

def test_env_knob_gates_observation(monkeypatch):
    """PADDLE_COMPILE_OBSERVATORY=0 turns the plane off: the facade
    returns None and records nothing."""
    monkeypatch.setenv("PADDLE_COMPILE_OBSERVATORY", "0")
    co.reset()
    assert not co.is_enabled()
    assert co.observe("unit.off", _tok(8)) is None
    snap = co.snapshot()
    assert snap["enabled"] is False and snap["families"] == {}
    monkeypatch.setenv("PADDLE_COMPILE_OBSERVATORY", "1")
    co.reset()
    assert co.is_enabled()
    assert co.observe("unit.on", _tok(8))["miss"]


def test_snapshot_drift_and_warmup_accounting():
    co.declare_family("unit.declared", buckets={"tokens": [8]},
                      warmup=lambda: "warm")
    co.declare_family("unit.cold")
    co.observe("unit.declared", _tok(8))
    co.observe("unit.rogue", _tok(3))
    snap = co.snapshot()
    assert snap["schema"] == co.SCHEMA
    assert snap["undeclared"] == ["unit.rogue"]
    assert snap["declared_unobserved"] == ["unit.cold"]
    fam = snap["families"]["unit.declared"]
    assert fam["declared"] and fam["warmup"]
    assert not snap["families"]["unit.rogue"]["declared"]
    assert snap["families"]["unit.rogue"]["last_causes"][-1]["cause"] \
        .endswith("(family undeclared)")
    assert co.undeclared_families() == ["unit.rogue"]
    assert co.run_warmup(families=["unit.declared"]) \
        == {"unit.declared": "warm"}


def test_cost_table_compile_section():
    co.observe("unit.cost", _tok(8), seconds=0.25)
    co.observe("unit.cost", _tok(16), seconds=0.75)
    co.observe("unit.cost", _tok(8))                 # hit: no cost
    sect = co.cost_section()
    assert sect["unit.cost"]["compiles"] == 2
    assert sect["unit.cost"]["compile_s"] == pytest.approx(1.0)
    assert sect["unit.cost"]["mean_compile_s"] == pytest.approx(0.5)
    table = rt.cost_table()
    assert table["schema"] == "paddle_cost_table/2"   # additive key only
    assert table["compile"]["unit.cost"]["compiles"] == 2


def test_metrics_rollup_and_all_series():
    """Every observe lands on the per-family series AND the family="all"
    rollup the recompile-storm burn rate consumes."""
    reg = get_registry()
    hits = reg.counter("paddle_compile_hits_total", labels=("family",))
    misses = reg.counter("paddle_compile_misses_total",
                         labels=("family",))
    h0, m0 = hits.value(family="all"), misses.value(family="all")
    co.observe("unit.metrics", _tok(8), seconds=0.1)
    co.observe("unit.metrics", _tok(8))
    co.observe("unit.metrics", _tok(8))
    assert misses.value(family="unit.metrics") == 1.0
    assert hits.value(family="unit.metrics") == 2.0
    assert misses.value(family="all") - m0 == 1.0
    assert hits.value(family="all") - h0 == 2.0
    seconds = reg.get("paddle_compile_seconds")
    assert seconds.labels(family="unit.metrics").count == 1
    gauge = reg.get("paddle_compile_undeclared_families")
    assert gauge.value() >= 1.0          # unit.metrics was never declared
    co.declare_family("unit.metrics")
    co.observe("unit.metrics", _tok(8))
    assert gauge.value() == 0.0


# ---------------------------------------------------------------------------
# alert rules: recompile storm + family drift (+ env grammar)
# ---------------------------------------------------------------------------

def _compile_registry():
    reg = MetricRegistry()
    hits = reg.counter("paddle_compile_hits_total", labels=("family",))
    misses = reg.counter("paddle_compile_misses_total",
                         labels=("family",))
    return reg, hits, misses


def test_shape_churn_fires_recompile_storm_with_cause():
    """Acceptance bar: a shape-churn workload fires the recompile-storm
    page and the attribution names the exact argument and dimension."""
    co.declare_family("serving.ragged", buckets={"tokens": [8, 16]})
    co.observe("serving.ragged", _tok(8))
    reg, hits, misses = _compile_registry()
    h = MetricsHistory(capacity=256, registry=reg)
    rule = alerts.recompile_storm_rule(budget=0.1, fast_window_s=3.0,
                                       slow_window_s=9.0)
    assert rule.severity == "page" and rule.name == "recompile_storm"
    eng = alerts.AlertEngine(history=h, rules=[rule])
    # warm steady state: pure hits, no alert
    for t in range(10):
        hits.inc(family="all")
        h.tick(now=float(t))
        eng.evaluate(now=float(t))
    assert not eng.active
    # shape churn: every tick a fresh padded size outside {8,16}
    fired = []
    for t in range(10, 24):
        ev = co.observe("serving.ragged", _tok(16 + t), seconds=0.01)
        assert ev["miss"]
        misses.inc(family="all")
        h.tick(now=float(t))
        fired += eng.evaluate(now=float(t))
    assert any(tr["rule"] == "recompile_storm" and tr["action"] == "fired"
               for tr in fired), fired
    causes = [c["cause"] for c in
              co.snapshot()["families"]["serving.ragged"]["last_causes"]]
    assert any("`tokens`" in c and "dim0" in c and "bucket miss" in c
               for c in causes), causes


def test_family_drift_rule_fires_and_clears():
    reg = MetricRegistry()
    g = reg.gauge("paddle_compile_undeclared_families")
    h = MetricsHistory(capacity=64, registry=reg)
    rule = alerts.family_drift_rule()
    assert isinstance(rule, alerts.ThresholdRule)
    assert rule.name == "compile_family_drift" and rule.above == 0.0
    eng = alerts.AlertEngine(history=h, rules=[rule])
    g.set(0.0)
    h.tick(now=0.0)
    assert eng.evaluate(now=0.0) == []
    g.set(2.0)
    h.tick(now=1.0)
    trs = eng.evaluate(now=1.0)
    assert trs and trs[0]["action"] == "fired"
    g.set(0.0)
    h.tick(now=2.0)
    trs = eng.evaluate(now=2.0)
    assert trs and trs[0]["action"] == "cleared"


def test_parse_rules_compile_kinds():
    rules = alerts.parse_rules(
        "recompile_storm:budget=0.05,fast=30,slow=120,factor=2;"
        "family_drift:severity=page,for=5")
    storm, drift = rules
    assert isinstance(storm, alerts.BurnRateRule)
    assert storm.good_metric == "paddle_compile_hits_total"
    assert storm.bad_metric == "paddle_compile_misses_total"
    assert storm.slo == "all"
    assert (storm.budget, storm.fast_window_s, storm.slow_window_s,
            storm.factor) == (0.05, 30.0, 120.0, 2.0)
    assert isinstance(drift, alerts.ThresholdRule)
    assert drift.severity == "page" and drift.for_s == 5.0
    # defaults: the storm budget is the documented 2%
    assert alerts.recompile_storm_rule().budget \
        == alerts.DEFAULT_RECOMPILE_BUDGET == 0.02


# ---------------------------------------------------------------------------
# /compile route + fleet scrape/merge
# ---------------------------------------------------------------------------

def test_compile_endpoint_and_fleet_merge():
    co.declare_family("serving.ragged", buckets={"tokens": [8]})
    co.observe("serving.ragged", _tok(8), seconds=0.02)
    co.observe("serving.ragged", _tok(8))
    with TelemetryServer(instance="c0", port=0) as srv:
        with urllib.request.urlopen(
                f"http://{srv.address}/compile", timeout=10) as resp:
            assert resp.status == 200
            snap = json.loads(resp.read())
        assert snap["instance"] == "c0"
        assert snap["schema"] == co.SCHEMA
        fam = snap["families"]["serving.ragged"]
        assert (fam["hits"], fam["misses"]) == (1, 1)
        # scrape-module fetch agrees with the raw GET
        fetched = scrape.fetch_compile(srv.address)
        assert fetched["families"] == snap["families"]
        # FleetScraper static tier folds the instance in
        fs = scrape.FleetScraper(endpoints={"c0": srv.address})
        merged = fs.compile_merged()
        assert merged["instances"] == ["c0"]
        assert merged["families"]["serving.ragged"]["misses"] == 1
        assert merged["totals"]["hits"] == 1


def test_merge_compile_snapshots_attribution():
    """The fleet rollup sums counts but keeps per-instance attribution
    on causes and undeclared families — drift on ONE replica must stay
    visible."""
    a = {"families": {"serving.ragged": {
             "hits": 10, "misses": 1, "compile_s": 0.5, "signatures": 2,
             "last_causes": [{"cause": "new family"}]}},
         "undeclared": [], "totals": {"hits": 10, "misses": 1,
                                      "compile_s": 0.5}}
    b = {"families": {"serving.ragged": {
             "hits": 4, "misses": 3, "compile_s": 1.5, "signatures": 4,
             "last_causes": [{"cause": "arg `tokens` dim0 9∉{8}: "
                                       "bucket miss"}]},
         "spec.rogue": {"hits": 0, "misses": 2, "compile_s": 0.1,
                        "signatures": 2, "last_causes": []}},
         "undeclared": ["spec.rogue"],
         "totals": {"hits": 4, "misses": 5, "compile_s": 1.6}}
    m = scrape.merge_compile_snapshots({"r0": a, "r1": b})
    assert m["instances"] == ["r0", "r1"]
    fam = m["families"]["serving.ragged"]
    assert (fam["hits"], fam["misses"]) == (14, 4)
    assert fam["compile_s"] == pytest.approx(2.0)
    assert fam["instances"] == ["r0", "r1"]
    assert {c["instance"] for c in fam["last_causes"]} == {"r0", "r1"}
    assert m["undeclared"] == {"spec.rogue": ["r1"]}
    assert m["totals"] == {"hits": 14, "misses": 6,
                           "compile_s": pytest.approx(2.1)}


# ---------------------------------------------------------------------------
# engine tier: warmup covers the declared inventory, steady state is
# miss-free
# ---------------------------------------------------------------------------

def _zero_miss_replay(eng, prompts, new_tokens):
    warm = eng.warmup_programs()
    assert warm, "warmup compiled nothing"
    snap = co.snapshot()
    base = snap["totals"]["misses"]
    assert base > 0, "warmup should pay the compiles up front"
    assert snap["undeclared"] == [], snap["undeclared"]
    _drive(eng, prompts, new_tokens)
    snap = co.snapshot()
    causes = {n: [c["cause"] for c in f["last_causes"]]
              for n, f in snap["families"].items() if f["last_causes"]}
    assert snap["totals"]["misses"] == base, causes
    assert snap["totals"]["hits"] > 0
    assert snap["undeclared"] == [], snap["undeclared"]
    # every declared family carries a warmup entry (inventory contract)
    missing = set(co.declared_families()) - set(co.warmup_entries())
    assert not missing, missing
    return snap


def test_mixed_replay_zero_post_warmup_misses(model):
    """Acceptance bar: after warmup_programs() a mixed prefill+decode
    replay re-enters warm programs only — zero observatory misses."""
    eng = ContinuousServingEngine(model, **ENGINE_KW)
    snap = _zero_miss_replay(eng, _prompts((13, 3, 21)), 3)
    assert snap["families"]["serving.ragged"]["hits"] > 0
    # a second warmup run is pure hits too (idempotent warm state)
    co.run_warmup()
    assert co.snapshot()["totals"]["misses"] \
        == snap["totals"]["misses"]


def test_spec_draft_replay_zero_post_warmup_misses(model):
    """Speculative decode with batched drafting stays inside the
    declared pow2 (rows, width) draft family after warmup."""
    eng = ContinuousServingEngine(
        model, max_batch_size=2, max_len=64, token_budget=16,
        prefill_chunk_tokens=16, spec_decode=True, spec_k=3,
        draft_model=model, draft_batch=True)
    snap = _zero_miss_replay(eng, _prompts((19, 9), seed=6), 6)
    assert eng.spec_drafted_tokens > 0
    assert snap["families"]["spec.draft_batch"]["hits"] > 0


def test_qblock_replay_zero_post_warmup_misses(model):
    """The q-block ragged grid serves the declared token-bucket family:
    warm replay is miss-free."""
    eng = ContinuousServingEngine(model, **ENGINE_KW)
    snap = _zero_miss_replay(eng, _prompts((23, 5), seed=2), 3)
    assert eng.ragged_steps > 0
    assert snap["families"]["serving.ragged"]["hits"] > 0


def test_cold_request_ttft_decomposition(model, tmp_path):
    """Acceptance bar: a COLD request's TTFT decomposes into queue /
    compile / prefill spans, joined by trace id through log_query."""
    import log_query as lq

    rt.enable()
    rt.get_trace_store().clear()
    path = tmp_path / "events.jsonl"
    eventlog.enable(str(path))
    try:
        eng = ContinuousServingEngine(model, max_batch_size=2, max_len=48,
                                      prefill_chunk_tokens=16)
        with eng:                    # deliberately NO warmup: cold start
            eng.generate(_prompts((13,))[0], max_new_tokens=2,
                         timeout=300)
    finally:
        eventlog.disable()
    ids = rt.get_trace_store().trace_ids()
    assert len(ids) == 1
    rows = lq.query([str(path)], trace=ids[0])
    kinds = [r["kind"] for r in rows]
    for need in ("queue_wait", "compile", "prefill_chunk"):
        assert need in kinds, kinds
    # the compile span carries the observatory's attribution
    sp = next(r for r in rows if r["kind"] == "compile")
    assert sp["family"].startswith("serving.")
    assert sp["cause"]
    # the CLI join works on the same file
    assert lq.main([str(path), "--trace", ids[0],
                    "--kind", "queue_wait,compile,prefill_chunk"]) == 0
    # warm spans never emit compile records: warmup removes the tax
    co.reset()
    eventlog.enable(str(tmp_path / "warm.jsonl"))
    try:
        eng2 = ContinuousServingEngine(model, max_batch_size=2,
                                       max_len=48,
                                       prefill_chunk_tokens=16)
        eng2.warmup_programs()
        rt.get_trace_store().clear()
        with eng2:
            eng2.generate(_prompts((13,))[0], max_new_tokens=2,
                          timeout=300)
    finally:
        eventlog.disable()
    tid = rt.get_trace_store().trace_ids()[0]
    warm_rows = lq.query([str(tmp_path / "warm.jsonl")], trace=tid)
    assert "compile" not in [r["kind"] for r in warm_rows]


# ---------------------------------------------------------------------------
# compile_report CLI
# ---------------------------------------------------------------------------

def _write_events(path, rows):
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")


def _miss(fam, cause, seconds=0.1, src="compile_observatory"):
    return {"ts": 1.0, "kind": "compile", "src": src, "family": fam,
            "cause": cause, "seconds": seconds, "signature": "x"}


def test_compile_report_fold_filters_and_render(tmp_path, capsys):
    import compile_report as cr

    path = tmp_path / "e.jsonl"
    _write_events(path, [
        _miss("serving.ragged", "new family"),
        _miss("serving.ragged", "arg `tokens` dim0 9∉{8,16}: bucket miss",
              seconds=0.4),
        # the request tracer's teed span copy must NOT double-count
        _miss("serving.ragged", "new family", src="trace"),
        {"ts": 1.0, "kind": "delivered", "trace_id": "t"},
    ])
    fams = cr.fold(cr.load_events(str(path)))
    assert fams["serving.ragged"]["compiles"] == 2
    assert fams["serving.ragged"]["compile_s"] == pytest.approx(0.5)
    assert fams["serving.ragged"]["causes"]["new family"] == 1
    assert cr.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "serving.ragged" in out and "bucket miss" in out
    # usage / unreadable-input errors exit 2
    assert cr.main([str(tmp_path / "missing.jsonl")]) == 2
    bad = tmp_path / "bad.jsonl"
    bad.write_text("{not json\n")
    assert cr.main([str(bad)]) == 2
    assert cr.main([]) == 2


def test_compile_report_diff_exit_codes(tmp_path, capsys):
    import compile_report as cr

    old = tmp_path / "old.jsonl"
    new = tmp_path / "new.jsonl"
    _write_events(old, [_miss("serving.ragged", "new family")])
    _write_events(new, [
        _miss("serving.ragged", "new family"),
        _miss("serving.ragged",
              "arg `tokens` dim0 17∉{8,16}: bucket miss"),
        _miss("serving.ragged",
              "arg `tokens` dim0 33∉{8,16}: bucket miss"),
    ])
    assert cr.main(["--diff", str(old), str(new)]) == 1
    out = capsys.readouterr().out
    assert "REGRESSED" in out and "bucket miss" in out
    # no growth -> clean exit; regressions list the NEW causes
    assert cr.main(["--diff", str(new), str(new)]) == 0
    regs = cr.diff_folds(cr.fold(cr.load_events(str(old))),
                         cr.fold(cr.load_events(str(new))))
    assert regs[0]["family"] == "serving.ragged"
    assert regs[0]["delta"] == 2
    assert any("bucket miss" in c for c in regs[0]["causes"])
    assert cr.main(["--diff", str(old)]) == 2


def test_compile_report_fleet_scrape(tmp_path, capsys):
    import compile_report as cr

    co.declare_family("serving.ragged", buckets={"tokens": [8]})
    co.observe("serving.ragged", _tok(8), seconds=0.01)
    co.observe("unit.rogue", _tok(3))
    with TelemetryServer(instance="f0", port=0) as srv:
        rc = cr.main(["--fleet", f"{srv.address},127.0.0.1:1"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "serving.ragged" in out
    assert "DRIFT" in out and "unit.rogue" in out
    assert "UNREACHABLE: 127.0.0.1:1" in out
    # --fleet composes with neither log paths nor --diff
    assert cr.main(["--fleet", "h:1", "x.jsonl"]) == 2


def test_bench_compare_compile_directions():
    """serving_recompiles_per_1k_ticks / post-warmup misses / warmup
    compile seconds are all lower-better in the bench comparator."""
    import bench_compare as bc

    assert bc.direction_of("serving_recompiles_per_1k_ticks") == "lower"
    assert bc.direction_of("compile_post_warmup_misses") == "lower"
    assert bc.direction_of("serving_warmup_compile_s") == "lower"
    assert bc.direction_of("compile_observatory_overhead_pct") == "lower"


# a hand-made optimized module: one product with an exponential in a NESTED
# producer of its operand, one with a divide as its output epilogue, one
# plain product, and an elementwise fusion that holds no product
_HLO = """HloModule jit_step, entry_computation_layout={()->f32[8,8]}

%fused_computation.9 (param_0.1: f32[8,8]) -> f32[8,8] {
  %param_0.1 = f32[8,8]{1,0} parameter(0)
  ROOT %exp.1 = f32[8,8]{1,0} exponential(%param_0.1)
}

%fused_computation.1 (param_0: f32[8,8], param_1: bf16[8,8]) -> (bf16[8,8], bf16[8,8]) {
  %param_0 = f32[8,8]{1,0} parameter(0)
  %param_1 = bf16[8,8]{1,0} parameter(1)
  %fusion.9.clone = f32[8,8]{1,0} fusion(%param_0), kind=kLoop, calls=%fused_computation.9
  %convolution.1 = f32[8,8]{1,0} convolution(%fusion.9.clone, %param_1), dim_labels=bf_io->bf
  %convert.1 = bf16[8,8]{1,0} convert(%convolution.1)
  ROOT %tuple.1 = (bf16[8,8]{1,0}, bf16[8,8]{1,0}) tuple(%convert.1, %convert.1)
}

%fused_computation.2 (param_0.2: bf16[8,8], param_1.2: bf16[8,8]) -> bf16[8,8] {
  %param_0.2 = bf16[8,8]{1,0} parameter(0)
  %param_1.2 = bf16[8,8]{1,0} parameter(1)
  %convolution.2 = f32[8,8]{1,0} convolution(%param_0.2, %param_1.2), dim_labels=bf_io->bf
  %div.2 = f32[8,8]{1,0} divide(%convolution.2, %convolution.2)
  ROOT %convert.2 = bf16[8,8]{1,0} convert(%div.2)
}

%fused_computation.3 (param_0.3: bf16[8,8]) -> f32[8,8] {
  %param_0.3 = bf16[8,8]{1,0} parameter(0)
  ROOT %convolution.3 = f32[8,8]{1,0} convolution(%param_0.3, %param_0.3), dim_labels=bf_io->bf
}

%fused_computation.4 (param_0.4: f32[8,8]) -> f32[8,8] {
  %param_0.4 = f32[8,8]{1,0} parameter(0)
  ROOT %log.4 = f32[8,8]{1,0} log(%param_0.4)
}

ENTRY %main.1 (a: f32[8,8], b: bf16[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0)
  %b = bf16[8,8]{1,0} parameter(1)
  %fusion.1 = (bf16[8,8]{1,0}, bf16[8,8]{1,0}) fusion(%a, %b), kind=kOutput, calls=%fused_computation.1, backend_config={"window_config":{"estimated_cycles":"700"}}
  %gte.1 = bf16[8,8]{1,0} get-tuple-element(%fusion.1), index=0
  %fusion.2 = bf16[8,8]{1,0} fusion(%gte.1, %b), kind=kOutput, calls=%fused_computation.2, backend_config={"window_config":{"estimated_cycles":"200"}}
  %fusion.3 = f32[8,8]{1,0} fusion(%fusion.2), kind=kOutput, calls=%fused_computation.3, backend_config={"window_config":{"estimated_cycles":"100"}}
  ROOT %fusion.4 = f32[8,8]{1,0} fusion(%fusion.3), kind=kLoop, calls=%fused_computation.4, backend_config={"window_config":{"estimated_cycles":"50"}}
}
"""


def test_what_a_compiled_program_fused_into_its_products():
    """``hlo_fusions``: which side of each product a transcendental chain
    sits on, read from the compiled text; the observatory keeps the
    counts with a program family."""
    from paddle_tpu.profiler import hlo_fusions

    recs = {r["name"]: r for r in hlo_fusions.product_fusions(_HLO)}
    assert sorted(recs) == ["fusion.1", "fusion.2", "fusion.3"]
    assert recs["fusion.1"]["operand_side"] == ["exponential"]
    assert recs["fusion.1"]["estimated_cycles"] == 700
    assert recs["fusion.1"]["result"].startswith("(bf16[8,8]")
    assert recs["fusion.1"]["inputs"] == ["f32[8,8]{1,0}", "bf16[8,8]{1,0}"]
    assert recs["fusion.2"]["operand_side"] == []
    assert recs["fusion.2"]["anywhere"] == ["divide"]       # an epilogue
    assert recs["fusion.3"]["anywhere"] == []
    seen = co.record_program("train.unit", _HLO)
    assert seen == {"product_fusions": 3, "operand_side_transcendental": 1,
                    "product_cycles": 1000, "operand_side_cycles": 700,
                    "program_cycles": 1050, "grouped_products": []}
    assert co.snapshot()["programs"] == {"train.unit": seen}
    json.dumps(co.snapshot())
    co.reset()
    assert co.snapshot()["programs"] == {}


_GROUPED_HLO = """HloModule jit_fn, is_scheduled=true

ENTRY %main.9 (x.1: bf16[4096,2560], w.1: bf16[128,2560,768], s.1: s32[128]) -> bf16[4096,768] {
  %x.1 = bf16[4096,2560]{1,0:T(8,128)(2,1)} parameter(0)
  %w.1 = bf16[128,2560,768]{2,1,0:T(8,128)(2,1)} parameter(1)
  %s.1 = s32[128]{0:T(128)} parameter(2)
  %ragged-dot-metadata = (s32[129]{0:T(256)S(1)}, s32[159]{0:T(256)S(1)}, s32[159]{0:T(256)S(1)}, s32[1]{0:T(128)}) custom-call(%s.1), custom_call_target="tpu_custom_call", operand_layout_constraints={s32[128]{0}}, metadata={op_name="ragged-dot-metadata"}, backend_config={"custom_call_config":{"body":"QUJD"}}
  %get-tuple-element = s32[1]{0:T(128)} get-tuple-element(%ragged-dot-metadata), index=3
  %get-tuple-element.1 = s32[129]{0:T(256)S(1)} get-tuple-element(%ragged-dot-metadata), index=0
  %get-tuple-element.2 = s32[159]{0:T(256)S(1)} get-tuple-element(%ragged-dot-metadata), index=1
  %get-tuple-element.3 = s32[159]{0:T(256)S(1)} get-tuple-element(%ragged-dot-metadata), index=2
  ROOT %ragged-dot-none = bf16[4096,768]{1,0:T(8,128)(2,1)} custom-call(%get-tuple-element, %get-tuple-element.1, %get-tuple-element.2, %get-tuple-element.3, %get-tuple-element, /*index=5*/%x.1, %w.1), custom_call_target="tpu_custom_call", operand_layout_constraints={s32[1]{0}, s32[129]{0}, s32[159]{0}, s32[159]{0}, s32[1]{0}, bf16[4096,2560]{1,0}, bf16[128,2560,768]{2,1,0}}, frontend_attributes={mosaic_fusion_entry_point="true"ATTR}, metadata={op_name="ragged-dot-none"}, backend_config={"custom_call_config":{"body":"QUJD"}}
}
"""


@pytest.mark.parametrize("attr,tiling", [
    (',ragged_dot_tiling="128,2560,768"', [128, 2560, 768]), ("", None)])
def test_what_tile_a_compiled_grouped_product_got(attr, tiling):
    """``hlo_fusions.grouped_products``: each ``ragged-dot`` call's name,
    operand shapes and ``ragged_dot_tiling`` (None where the instruction
    carries none), not the call that builds its tile lists; ``summary``
    and the observatory carry it."""
    from paddle_tpu.profiler import hlo_fusions

    text = _GROUPED_HLO.replace("ATTR", attr)
    want = [{"name": "ragged-dot-none", "lhs": "bf16[4096,2560]",
             "rhs": "bf16[128,2560,768]", "result": "bf16[4096,768]",
             "tiling": tiling}]
    assert hlo_fusions.grouped_products(text) == want
    assert hlo_fusions.grouped_products(_HLO) == []
    seen = co.record_program("serving.ragged", text)
    assert seen["grouped_products"] == want and seen["product_fusions"] == 0
    assert co.snapshot()["programs"]["serving.ragged"] == seen
    json.dumps(co.snapshot())
    co.reset()

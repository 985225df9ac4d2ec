"""CI gate: every SURVEY.md §2 inventory item resolves to real symbols."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))


def test_inventory_complete():
    from check_inventory import check
    failures = check(verbose=False)
    assert not failures, failures


def test_strategy_fields_documented():
    """Every public DistributedStrategy field is mentioned in
    docs/PERF.md, so future knobs stay documented."""
    from check_inventory import check_strategy_docs
    missing = check_strategy_docs(verbose=False)
    assert not missing, f"undocumented DistributedStrategy fields: {missing}"


def test_env_knobs_documented():
    """Every PADDLE_* env knob referenced in paddle_tpu/ is mentioned in
    a docs/*.md file (same discoverability rule as the strategy fields)."""
    from check_inventory import check_env_docs
    missing = check_env_docs(verbose=False)
    assert not missing, f"undocumented PADDLE_* env knobs: {missing}"


def test_fleet_knobs_covered():
    """Every PADDLE_FLEET_* knob is documented in docs/SERVING.md and
    every router policy string is exercised by a test (and documented)."""
    from check_inventory import check_fleet_knobs
    violations = check_fleet_knobs(verbose=False)
    assert not violations, violations


def test_observability_catalog():
    """Every paddle_request_*/paddle_slo_* metric and PADDLE_SLO_*/
    PADDLE_REQUEST_TRACE* knob referenced in paddle_tpu/ is cataloged in
    docs/OBSERVABILITY.md."""
    from check_inventory import check_observability_catalog
    violations = check_observability_catalog(verbose=False)
    assert not violations, violations


def test_alert_catalog():
    """Every PADDLE_HISTORY_*/PADDLE_ALERT_*/PADDLE_REPLAY_*/
    PADDLE_TELEMETRY_* knob and paddle_history_*/paddle_alert* metric
    is cataloged in docs/OBSERVABILITY.md AND exercised by a test, and
    every replay preset appears in a test."""
    from check_inventory import check_alert_catalog
    violations = check_alert_catalog(verbose=False)
    assert not violations, violations


def test_training_observability_catalog():
    """Every PADDLE_NUMERICS_*/PADDLE_MEMORY_*/PADDLE_STEP_PHASE* knob
    and paddle_numerics_*/paddle_memory_*/paddle_step_phase_* metric is
    cataloged in docs/OBSERVABILITY.md AND exercised by a test."""
    from check_inventory import check_training_observability
    violations = check_training_observability(verbose=False)
    assert not violations, violations


def test_ledger_catalog():
    """Every PADDLE_LEDGER* knob and paddle_ledger_* metric is cataloged
    in docs/OBSERVABILITY.md AND exercised by a test."""
    from check_inventory import check_ledger_catalog
    violations = check_ledger_catalog(verbose=False)
    assert not violations, violations


def test_controller_catalog():
    """Every PADDLE_CONTROLLER_* knob, paddle_controller_* metric,
    controller action string, fleet fault directive and structured
    rejection reason is documented AND exercised by a test."""
    from check_inventory import check_controller_catalog
    violations = check_controller_catalog(verbose=False)
    assert not violations, violations


def test_telemetry_plane_catalog():
    """Every PADDLE_TELEMETRY_*/PADDLE_EVENTLOG* knob,
    paddle_telemetry_*/paddle_eventlog_* metric and exporter HTTP route
    is cataloged in docs/OBSERVABILITY.md AND exercised by a test."""
    from check_inventory import check_telemetry_plane
    violations = check_telemetry_plane(verbose=False)
    assert not violations, violations


def test_serving_program_budget():
    """Compiled-program guard: a mixed prefill+decode load stays inside
    the ragged scheduler's declared token-bucket family (no per-request
    shapes / unbounded recompiles) and exercises both token kinds; the
    speculative pass proves verify spans (q_len = 1+k) stay inside the
    SAME family — spec decode must not explode the program set."""
    from check_inventory import check_serving_programs
    violations = check_serving_programs(verbose=False)
    assert not violations, violations


def test_quantized_config_catalog():
    """Quantized-config guard (ISSUE 16): every device-tier decode-speed
    knob (PADDLE_WEIGHT_DTYPE / PADDLE_SPEC_DRAFT_BATCH /
    PADDLE_KV_DTYPE) is documented in docs/*.md AND exercised by a test,
    and the fully-int8 serving config (int8 weights + int8 KV pages on the
    q-block ragged grid) is bit-stable across two same-seed runs with a
    matching token digest."""
    from check_inventory import check_quantized_config
    violations = check_quantized_config(verbose=False)
    assert not violations, violations


def test_compile_observatory_catalog():
    """Compile-observatory guard (ISSUE 18): every PADDLE_COMPILE* knob
    and paddle_compile_* metric is cataloged in docs/OBSERVABILITY.md
    AND exercised by a test; a warmed engine's mixed replay observes
    only declared program families, every declared family has a warmup
    entry, and zero post-warmup trace-cache misses occur."""
    from check_inventory import check_compile_observatory
    violations = check_compile_observatory(verbose=False)
    assert not violations, violations


def test_kv_tier_catalog():
    """Tiered-KV guard (ISSUE 19): every PADDLE_KV_HOST_* / PADDLE_SEP_*
    knob is documented in docs/SERVING.md AND exercised by a test, and
    every paddle_kv_* metric (plus the tier-labelled prefix-eviction
    counter) is cataloged in docs/OBSERVABILITY.md AND exercised by a
    test."""
    from check_inventory import check_kv_tier
    violations = check_kv_tier(verbose=False)
    assert not violations, violations


def test_paddle_flops():
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import nn

    net = nn.Sequential(nn.Conv2D(3, 8, 3, padding=1), nn.ReLU(),
                        nn.Flatten(), nn.Linear(8 * 16, 5))
    total = paddle.flops(net, (2, 3, 4, 4))
    # reference MAC convention with bias: out_numel * (Cin*K + 1)
    conv = 2 * 4 * 4 * 8 * (3 * 9 + 1)
    relu = 2 * 8 * 4 * 4
    lin = 2 * 5 * (128 + 1)
    assert total == conv + relu + lin, (total, conv + relu + lin)
    # bare leaf layer counts too
    leaf = paddle.flops(nn.Linear(10, 20, bias_attr=False), (4, 10))
    assert leaf == 4 * 20 * 10, leaf


def test_compat_namespaces():
    import numpy as np
    import paddle_tpu as paddle

    assert paddle.iinfo("int8").max == 127
    assert abs(paddle.finfo("float16").eps - 0.000977) < 1e-5
    x = paddle.to_tensor(np.zeros((4, 6), np.float32))
    c = paddle.crop(x, shape=[2, -1], offsets=[1, 2])
    assert tuple(c.shape) == (2, 4)
    assert paddle.version.cuda() == "False"
    assert paddle.tensor.matmul is paddle.matmul
    p = paddle.create_parameter([2, 2], is_bias=True)
    assert float(np.abs(np.asarray(p.numpy())).sum()) == 0.0
    v = paddle.view(paddle.to_tensor(np.zeros((2, 6), np.float32)), [3, 4])
    assert tuple(v.shape) == (3, 4)
    tl = np.asarray(paddle.tril_indices(3).numpy())
    want_r, want_c = np.tril_indices(3)
    np.testing.assert_array_equal(tl, np.stack([want_r, want_c]))
    hist = paddle.histogramdd(paddle.to_tensor(
        np.random.rand(20, 2).astype(np.float32)), bins=4)
    assert np.asarray(hist[0].numpy()).sum() == 20

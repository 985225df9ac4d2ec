"""The benchmark's files for the window-and-full-attention / routed-expert
family (CPU, tiny sizes, no chip): the configuration against the catalog's
published numbers, the deal of short turns and long documents, the
operations-and-bytes arithmetic against hand counts, the new readers on
hand-made run records, the seeded leaf table, and the new driver end to end
through its functions. Nothing seen here is a device result."""
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import flops_smallthinker as flops  # noqa: E402
from benchmark import harness  # noqa: E402
from benchmark import weights_smallthinker as weights  # noqa: E402
from benchmark.traffic import mixed_len  # noqa: E402

MANIFEST = harness.load_manifest()
CELL = "serve_mixed_window_closed"
LAYOUT = [0, 1, 1, 1] * 13
#: ``config`` of the catalog's row SmallThinker-21BA3B-Instruct (its
#: source_url's config.json)
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_layout": LAYOUT,
    "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": LAYOUT, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936}
#: the accepted readers that read this driver's records as they are, listed
#: for this cell through copies of their own (``<name>.window``, a reader
#: file that loads the accepted one)
COPIED_READERS = ("moe_device_pct", "moe_load_max_over_mean",
                  "prefix_hit_pct")
NEW_READERS = ("serve_mfu_pct.window_moe", "qblock_roofline.windowed",
               "qblock_window_skip_pct", "kv_window_held_pct") + tuple(
                   name + ".window" for name in COPIED_READERS)
JOINED = ("sched_tick_ms", "sched_padded_pct", "kv_pages_peak_pct",
          "device_idle_pct.serve", "pre_device_s", "device_setup_s",
          "ttft_p95_ms.closed")


@pytest.fixture(scope="module")
def found():
    return harness.find_cell(MANIFEST, CELL)


def test_the_cell_is_found_by_name_with_its_files(found):
    entry, config, traffic = found
    assert entry == {"name": CELL, "config": "smallthinker-21b-serve-12l",
                     "traffic": "mixed_len_closed_24", "chips": 1,
                     "why": entry["why"]}
    assert len(entry["why"]) <= 200
    cfg_entry = next(c for c in MANIFEST["configs"]
                     if c["name"] == entry["config"])
    assert cfg_entry["source"] == config["source"] == (
        "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/"
        "blob/main/config.json")
    assert cfg_entry["reduced"] == sorted(config["reduced"]) == [
        "num_hidden_layers"]
    for key in ("reduced", "assumed", "deployment", "engine"):
        assert key in config, key
    assert traffic["driver"] == "serve_window_moe"
    for name in ("drivers/serve_window_moe.py", "reference/smallthinker.py",
                 "weights_smallthinker.py", "flops_smallthinker.py",
                 "traffic/mixed_len.py", "limits/" + CELL + ".json"):
        assert os.path.exists(os.path.join(REPO, "benchmark", name)), name
    listed = {m["name"] for g in ("end_to_end", "per_layer")
              for m in harness.metrics_of(MANIFEST, g, CELL)}
    assert listed >= {"serve_tok_s", "setup_s"} | set(NEW_READERS) | set(
        JOINED)
    # ``itl_mean_ms``: the driver's check read one set of six runs at
    # 3.6 % against the bound's 2.5 (PERF.md, section 6), so the cell does
    # not list it, as ISSUE 31 says of a spread over half the bound
    assert not listed & ({"qblock_roofline", "moe_unheld_pct", "itl_mean_ms",
                          "tick_attn_host_ms", "serve_mfu_pct"}
                         | set(COPIED_READERS))
    for name in NEW_READERS:
        m = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
        assert CELL in m["workloads"] and m["moves"] == "serve_tok_s"


def test_the_configuration_keeps_every_published_number(found):
    """Every number of the catalog's entry under its own key; only the
    depth is cut, to a whole number of periods, and the two layouts keep
    their published entries (a layer reads its own)."""
    _, config, _ = found
    for key, value in PUBLISHED.items():
        if key != "num_hidden_layers":
            assert config[key] == value, key
    assert config["num_hidden_layers"] == 12 == 3 * 4
    assert config["rope_layout"][:12] == [0, 1, 1, 1] * 3
    assert config["sliding_window_layout"][:12] == [0, 1, 1, 1] * 3
    assert "held_experts" not in config          # all 64 are held
    engine = config["engine"]
    assert engine["max_batch_size"] == 24 and engine["max_len"] == 16384
    assert engine["page_size"] in (64, 128)
    assert engine["token_budget"] == engine["prefill_chunk_tokens"] == 512
    for key in ("router_input", "attention", "window", "router",
                "initializer_range", "weights"):
        assert key in config["assumed"], key
    # the program's config class takes the file's keys as they are
    from benchmark.drivers.serve_window_moe import MODEL_KEYS
    from paddle_tpu.models.smallthinker import SmallThinkerConfig
    cfg = SmallThinkerConfig(**{k: config[k] for k in MODEL_KEYS})
    assert [cfg.window_of(i) for i in range(4)] == [None, 4096, 4096, 4096]


def test_parameter_count_and_page_bytes_are_the_files_arithmetic(found):
    _, config, _ = found
    attn = 2560 * 3584 * 2 + 2 * 2560 * 512
    expert = 3 * 2560 * 768
    layer = attn + 2560 * 64 + 64 * expert + 2 * 2560
    assert attn == flops.attention_proj_params(config) == 20_971_520
    assert expert == flops.expert_params(config) == 5_898_240
    assert weights.param_count(config) == (
        12 * layer + 2 * 151936 * 2560 + 2560) == 5_561_448_960
    # bf16 pages of 128 tokens: 2,048 B a token a layer
    engine = config["engine"]
    a_token = 2 * 4 * 128 * 2
    full = (engine["num_pages"] - 1) * engine["page_size"] * 3 * a_token
    window = (engine["window_num_pages"] - 1) * engine["page_size"] \
        * 9 * a_token
    assert 1.0e9 < full < 1.6e9 and 1.4e9 < window < 2.2e9
    assert full + window + 2 * weights.param_count(config) < 14.5e9


def test_the_deal_of_turns_and_documents(found):
    _, config, traffic = found
    plan, asks = mixed_len.mixed_len_requests(traffic, 7, 151936)
    again, _ = mixed_len.mixed_len_requests(traffic, 8, 151936)
    assert len(plan) == 24 == traffic["clients"]
    assert traffic["long"]["clients"] == 8
    assert traffic["short"]["clients"] == 16 and traffic["deal_seed"] == 31
    for c in range(24):       # the lengths are the file's, the ids the seed's
        assert [(len(p), n) for p, n in plan[c]] == [
            (len(p), n) for p, n in again[c]]
    assert not np.array_equal(plan[0][0][0], again[0][0][0])
    assert max(len(p) + n for reqs in plan for p, n in reqs) <= 16384
    for c in range(8):
        docs = {}
        for (p, n), a in zip(plan[c], asks[c]):
            assert 5120 + 32 <= len(p) <= 14336 + 256 and 64 <= n <= 384
            docs.setdefault(p[:5120].tobytes(), []).append(a)
        # each document twice in a row; an odd client starts one ask in
        for i, got in enumerate(docs.values()):
            assert got == ([0] if i == 0 and c % 2 else [0, 1])
        first, second = plan[c][1 if c % 2 else 0], plan[c][2 if c % 2 else 1]
        doc = (len(first[0]) - 32) // 256 * 256
        assert np.array_equal(first[0][:doc], second[0][:doc])
        assert (doc % 256, doc >= 5120) == (0, True)
    for c in range(8, 24):
        assert set(asks[c]) == {-1}
        assert all(64 <= len(p) <= 2048 and 16 <= n <= 256
                   for p, n in plan[c])
    short = [len(p) for c in range(8, 24) for p, _ in plan[c]]
    assert 330 <= np.median(short) <= 440


def test_flops_and_bytes_against_hand_counts(found):
    _, config, _ = found
    # a decode row at context 10,000: every key in a full layer, the last
    # 4,096 in a window layer
    assert flops.keys_seen(1, 10000) == 10000
    assert flops.keys_seen(1, 10000, 4096) == 4096
    assert flops.keys_seen(1, 300, 4096) == 300
    # a 512-token chunk ending at 4,200: its tokens see 3689..4200 keys, the
    # last 105 of them (bounds 4096..4200) the window's 4,096
    want = sum(min(c, 4096) for c in range(3689, 4201))
    assert flops.keys_seen(512, 4200, 4096) == want
    assert flops.keys_seen(512, 4200) == sum(range(3689, 4201))
    assert flops.keys_seen(512, 9000, 4096) == 512 * 4096
    assert flops.keys_read(512, 9000, 4096) == 4096 + 511
    assert flops.keys_read(1, 300, 4096) == 300
    assert flops.attention_flops(config, 1, 10000, 4096) == \
        4 * 28 * 128 * 4096
    assert flops.layer_windows(config) == [None, 4096, 4096, 4096] * 3
    spans = [(1, 10000), (512, 4200)]
    proj = 12 * (20_971_520 + 2560 * 64)
    attn = 4 * 28 * 128 * (
        3 * (10000 + sum(range(3689, 4201))) + 9 * (4096 + want))
    assert flops.serve_flops(config, spans, 2, 513 * 6 * 12) == (
        2 * proj * 513 + attn + 2 * 5_898_240 * 513 * 72
        + 2 * 2560 * 151936 * 2)
    # bytes: 513 tokens of 28 x 128 queries in and out, K and V of 4 x 128
    assert flops.attention_bytes(config, spans, 4096) == 2 * (
        2 * 513 * 28 * 128 + 2 * (4096 + 4200) * 4 * 128)
    assert flops.attention_bytes(config, spans) == 2 * (
        2 * 513 * 28 * 128 + 2 * (10000 + 4200) * 4 * 128)


def test_the_new_readers_on_a_hand_made_run(found):
    _, config, _ = found
    for name in NEW_READERS:
        assert harness.load_reader(name)({}) is None
        assert harness.load_reader(name)({"config": config}) is None
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    calls = [(0.0, [1, 512], [10000, 4200], w)
             for w in [None, 4096, 4096, 4096] * 3]
    run = {"config": config, "peaks": peaks, "chips": 1, "window_s": 2.0,
           "kernel_calls": calls, "window": {"delivered": 2},
           "counters": {"moe_expert_tokens": np.full(64, 513 * 72 // 64)},
           # (name, start ns, duration ns): 4 ms a call
           "trace": {"events": {"d0": [["%_qblock_device.1 = tpu_custom_call",
                                        i * 5_000_000, 4_000_000]
                                       for i in range(12)]}},
           "window_span_args": [
               {"jobs": 30, "jobs_without_window": 100, "window": 4096},
               {"jobs": 45, "jobs_without_window": 50, "window": 4096}],
           "kv_samples": [(0.0, [("full", 5, 9), ("window4096", 3, 9)],
                           1000, [600]),
                          (0.25, [("full", 5, 9), ("window4096", 3, 9)],
                           3000, [1400])]}
    mfu = harness.load_reader("serve_mfu_pct.window_moe")(run)
    total = flops.serve_flops(config, [(1, 10000), (512, 4200)], 2,
                              513 * 72 // 64 * 64)
    assert mfu == pytest.approx(100 * total / (2.0 * 197e12))
    assert harness.load_reader("qblock_window_skip_pct")(run) == \
        pytest.approx(50.0)
    assert harness.load_reader("kv_window_held_pct")(run) == \
        pytest.approx(50.0)
    from benchmark import flops as base
    least = sum(base.roofline_seconds(
        sum(flops.attention_flops(config, q, c, w)
            for q, c in [(1, 10000), (512, 4200)]),
        flops.attention_bytes(config, [(1, 10000), (512, 4200)], w),
        peaks)[0] for w in [None, 4096, 4096, 4096] * 3)
    roof = harness.load_reader("qblock_roofline.windowed")(run)
    assert roof == pytest.approx(100 * least / 0.048) and 0 < roof < 100
    # the accepted reader counts whole contexts for the window layers too
    assert harness.load_reader("qblock_roofline")(dict(
        run, kernel_calls=[c[:3] for c in calls])) > roof
    # the accepted readers the cell joins read this driver's records
    run["pages"] = [(0.0, 5, 9), (0.25, 3, 9)]
    assert harness.load_reader("kv_pages_peak_pct")(run) == \
        pytest.approx(100 * 5 / 9)
    assert harness.load_reader("moe_load_max_over_mean.window")(run) == 1.0
    assert harness.load_reader("prefix_hit_pct.window")(dict(run, counters={
        "prompt_tokens_admitted": 400, "prompt_tokens_cached": 100})) == 25.0
    run["trace"] = {"window_s": 2.0, "events": {"d0": [
        ["%ragged-dot-none.1 = tpu_custom_call", i * 5_000_000, 4_000_000]
        for i in range(36)]}}
    assert harness.load_reader("moe_device_pct.window")(run) == \
        pytest.approx(100 * 36 * 0.004 / 2.0)


def test_seeded_leaves_by_group_equal_the_whole_table():
    cfg = dict(vocab_size=64, hidden_size=16, num_hidden_layers=2,
               num_attention_heads=2, num_key_value_heads=1, head_dim=8,
               moe_ffn_hidden_size=8, moe_num_primary_experts=4,
               initializer_range=0.02)
    table = weights.leaf_table(cfg)
    whole = weights.make_weights(cfg, 2**31 + 5, "float32")
    assert len(table) == len(whole) == 1 + 2 * 10 + 2
    by_name = {n: a for (n, _, _), a in zip(table, whole)}
    for prefix in ("model.embed_tokens.", weights.layer_prefix(1),
                   "lm_head."):
        for short, a in weights.make_group(cfg, 2**31 + 5, prefix,
                                           "float32").items():
            assert np.array_equal(np.asarray(a),
                                  np.asarray(by_name[prefix + short]))
    router = by_name["model.layers.0.experts.router"]
    assert str(router.dtype) == "float32" and router.shape == (16, 4)
    # a share of the experts draws its own leaves at its own shapes
    held = dict(cfg, held_experts=[2, 2])
    assert dict((n, s) for n, s, _ in weights.leaf_table(held))[
        "model.layers.0.experts.w_gate"] == (2, 16, 8)


def test_the_sample_holds_the_four_kinds_or_nothing():
    from benchmark.drivers.serve import Record
    from benchmark.drivers.serve_window_moe import sample_kinds

    def rec(client, index, prompt, new):
        r = Record(client, index, index == 0, np.zeros(prompt, np.int64), new)
        r.output = np.zeros(new, np.int64)
        return r

    asks = [[0, 1, 0, 1], [0, 0, 1], [-1] * 4]
    records = [rec(0, 1, 9000, 100), rec(0, 2, 5200, 64),
               rec(1, 1, 13000, 200), rec(1, 2, 13010, 80),
               rec(2, 1, 300, 20), rec(2, 2, 90, 16)]
    got = sample_kinds(records, asks, 5, 4, 4096, 2048)
    assert len(got) == 4 and got[0] is records[2]        # the longest
    assert asks[got[1].client][got[1].index] > 0         # a second ask
    assert len(got[2].prompt) + got[2].new >= 6144 and \
        asks[got[2].client][got[2].index] >= 0
    assert asks[got[3].client][got[3].index] == -1       # a short one
    assert len({id(r) for r in got}) == 4
    assert sample_kinds(records[:4], asks, 5, 4, 4096, 2048) == []   # no short


def tiny(config, traffic):
    """The cell at a size the CPU serves: one period of layers, a window of
    8 tokens, pages of 4, two long and two short clients."""
    config = dict(
        config, vocab_size=128, hidden_size=64, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        moe_ffn_hidden_size=32, moe_num_primary_experts=8,
        moe_num_active_primary_experts=3, sliding_window_size=8,
        rope_theta=10000.0, max_position_embeddings=128,
        engine_dtype="float32",
        engine=dict(max_batch_size=4, max_len=128, page_size=4,
                    num_pages=100, window_num_pages=40, token_budget=16,
                    prefill_chunk_tokens=16))
    traffic = dict(
        traffic, clients=4, reference_width=[64, 128], passed_window_by=8,
        long=dict(clients=2, docs_per_client=6, asks_per_doc=2,
                  doc_len=dict(median=24, sigma=0.3, min=16, max=40, grid=8),
                  question_len=dict(median=4, sigma=0.3, min=3, max=6),
                  answer_len=dict(median=3, sigma=0.3, min=2, max=4)),
        short=dict(clients=2, requests_per_client=40,
                   prompt_len=dict(median=8, sigma=0.5, min=4, max=14),
                   answer_len=dict(median=3, sigma=0.3, min=2, max=4)))
    return config, traffic


def test_the_driver_end_to_end_at_a_small_size(found):
    """The new driver through its functions (interpret-mode kernel,
    float32, the CPU): every request answered, the four kinds of request in
    the sample, the program's served tokens the reference's own first
    choices, the window groups' counters and samples there, and an altered
    token told apart."""
    from benchmark.drivers import serve_window_moe as drv
    entry, config, traffic = found
    config, traffic = tiny(config, traffic)
    ctx = {"cell": entry, "config": config, "traffic": traffic,
           "limits": {"sample_requests": 4, "router_margin_min": 1e-3,
                      "decided_logit_gap_max": 1e-4,
                      "served_logit_gap_mean": 1e-5,
                      "decided_logit_rms_median": 1e-5},
           "seed": 2**31 + 77, "seconds": 45.0, "trace": False, "chips": 1,
           "watch": harness.CompileWatch(), "control": "int8"}
    run = drv.run(ctx)
    assert run["failed"] == 0 and run["finished"] >= 4
    assert harness.judge(run["checks"]), run["checks"]
    assert [name for name, _, _ in run["checks"]] == list(drv.CHECKS) + [
        drv.UNHELD]
    assert run["checks"][-1][1:] == (0, 0)
    c = run["counters"]
    assert c["compiled_layer_calls"] == 4 * c["ragged_steps"] > 0
    assert c["moe_expert_tokens"].shape == (8,)
    assert c["moe_unheld_tokens"] == 0
    assert 0 < c["prompt_tokens_cached"] < c["prompt_tokens_admitted"]
    assert c["window_blocks_released"] > 0
    gaps = run["gaps"]
    n = len(gaps["served"])
    assert n == len(gaps["margin"]) == len(gaps["altered"]) == len(
        gaps["int8"]) > 0
    assert min(gaps["margin"]) >= 0
    assert [name for name, _, _ in run["stand_ins"]["altered_token"]] == \
        list(drv.CHECKS)
    assert not harness.judge(run["stand_ins"]["altered_token"])
    run["config"] = config
    held = harness.load_reader("kv_window_held_pct")(run)
    assert 0 < held < 100
    assert 0 < harness.load_reader("kv_pages_peak_pct")(run) <= 100
    assert 0 < harness.load_reader("prefix_hit_pct.window")(run) < 100
    assert harness.load_reader("moe_load_max_over_mean.window")(run) >= 1
    assert len(run["window"]["ttft"]) > 0
    assert harness.load_reader("qblock_window_skip_pct")(run) is None


def test_the_sampler_sees_a_window_block_released_early(monkeypatch):
    """``kv_sample`` on a cache alone (no model), by the tables and the
    refcounts the kernel reads: contexts past their window hold what their
    next query sees, so no slot is counted; with the release one block
    early, every sample of such a slot is; so is a slot whose needed block
    maps a page that went back to the free list, or one that two slots map
    and one owns."""
    from benchmark.calibrate_window import released_early
    from benchmark.drivers.serve_window_moe import kv_sample
    from paddle_tpu.models.generation import SlotPagedKVCache

    def fill():
        cache = SlotPagedKVCache(2, page_size=4, max_len=64, num_pages=40,
                                 window_groups={8: 20})
        cache.assign(0, np.arange(1, 31))
        cache.assign(1, np.arange(101, 119))
        for n0, n1 in ((8, 8), (8, 8), (8, 1), (6, 1)):
            cache.begin_ragged([(0, 0, n0), (1, n0, n1)])
            cache.advance(n0 + n1)
        return cache

    groups, live, held, unheld = kv_sample(fill())
    assert live == 30 + 18 and unheld == 0
    # slot 0 holds blocks 5..7, slot 1 blocks 2..4
    assert held == [(30 - 5 * 4) + (18 - 2 * 4)]
    assert groups[1][0] == "window8"
    undo = released_early()
    try:
        assert kv_sample(fill())[3] == 2       # both slots lost a block
    finally:
        undo()
    cache = fill()
    assert kv_sample(cache)[3] == 0
    window = cache._groups[1]
    page = int(window.tables[0, 6])
    window.ref[page] = 0                       # freed under the slot
    assert kv_sample(cache)[3] == 1
    window.ref[page] = 1
    window.tables[1, 3] = page                 # two slots, one owner
    assert kv_sample(cache)[3] == 2
    window.tables[1, 3] = 0                    # a needed block unmapped
    assert kv_sample(cache)[3] == 1


def test_the_recorded_readings_judged_again_under_the_committed_limits():
    """Every recorded line (my chip runs, PR 31) through ``rows_of`` under
    the limits as committed: each program row correct; each altered-token
    row not; the int8 control not, by its own logits' distance, on every
    line that read it (nothing is expected of the four lines recorded
    before: the tokens' numbers alone do not tell it); each fault's row
    not (nothing is expected of the one released-early line recorded
    before the runs read either number that tells it)."""
    import json
    from benchmark import calibrate_window as cw
    limits = harness.load_json(os.path.join(
        REPO, "benchmark", "limits", CELL + ".json"))
    driver = harness.load_driver("serve_window_moe")
    rows = []
    with open(os.path.join(REPO, "benchmark", "limits",
                           CELL + ".readings.jsonl")) as f:
        for line in f:
            rows += cw.rows_of(json.loads(line), driver, limits)
    by_who = {}
    for r in rows:
        by_who.setdefault(r["who"], []).append((r["correct"], r["expected"]))
    assert by_who["program"] == [(True, True)] * 10
    assert by_who["altered_token"] == [(False, False)] * 10
    assert by_who["fault_window_ignored"] == [(False, False)] * 2
    assert sorted(by_who["fault_released_early"], key=str) == \
        [(False, False)] * 3 + [(True, None)]
    assert sorted(by_who["control_int8"], key=str) == \
        [(False, False)] * 3 + [(True, None)] * 4
    told = [r for r in rows if r["who"] == "control_int8"
            and r["expected"] is False]
    for r in told:                      # by the logits' distance alone
        n = r["numbers"]
        assert n[driver.RMS] > limits[driver.RMS] > 0
        assert n["decided_logit_gap_max"] < limits["decided_logit_gap_max"]
        assert n["served_logit_gap_mean"] < limits["served_logit_gap_mean"]
    assert not cw.rejudge(os.path.join(REPO, "benchmark", "limits",
                                       CELL + ".readings.jsonl"))


def test_the_logits_distance_is_read_where_the_router_decided():
    """``rms_check``: the median over the positions whose router margin is
    at or over the limit and whose logits were read; ``logit_rms`` of the
    reference says NaN for a row that was not."""
    from benchmark.drivers.serve_window_moe import RMS, rms_check
    from benchmark.reference.smallthinker import logit_rms
    limits = {"router_margin_min": 0.005, RMS: 0.015}
    rms = [0.008, 0.09, 0.01, float("nan"), 0.012, 0.5]
    margins = [0.01, 0.001, 0.02, 0.03, 0.005, 0.0]
    assert rms_check(rms, margins, limits) == [(RMS, 0.01, 0.015)]
    (name, value, _), = rms_check(rms, [0.0] * 6, limits)
    assert value != value               # nothing decided: not correct
    assert not harness.judge([(name, value, 0.015)])
    got = np.full((3, 2), np.nan, np.float32)
    got[np.array([0, 2])] = np.array([[1.0, 2.0], [3.0, 5.0]], np.float32)
    ref = np.array([[1.0, 2.0], [0.0, 0.0], [3.0, 4.0]], np.float32)
    out = logit_rms(got, ref)
    assert out[0] == 0 and out[1] != out[1]
    assert out[2] == pytest.approx(np.sqrt(0.5))


def test_the_limits_file_states_its_readings():
    limits = harness.load_json(os.path.join(
        REPO, "benchmark", "limits", CELL + ".json"))
    assert limits["sample_requests"] == 4
    for key in ("router_margin_min", "decided_logit_gap_max",
                "served_logit_gap_mean", "decided_logit_rms_median"):
        assert limits[key] > 0
    assert limits["window_keys_unheld_samples"] == 0
    for word in ("int8", "altered", "window ignored", "released one block "
                 "early"):
        assert word in limits["readings"], word

"""The benchmark's files for the latent-attention / routed-expert family
(CPU, tiny sizes, no chip): the configuration against the published
numbers, the deal of documents, the operations-and-bytes arithmetic against
hand counts, the new readers on hand-made run records, the seeded leaf
table, and the new driver end to end through its functions. Nothing seen
here is a device result."""
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import flops_deepseek_v3 as flops  # noqa: E402
from benchmark import harness  # noqa: E402
from benchmark import weights_deepseek_v3 as weights  # noqa: E402
from benchmark.traffic import docs_reask  # noqa: E402

MANIFEST = harness.load_manifest()
CELL = "serve_docs_latent_closed"
#: ``config`` of the catalog's row GigaChat3.1-702B-A36B (its source_url's
#: config.json, model_type deepseek_v3)
PUBLISHED = {
    "vocab_size": 128256, "max_position_embeddings": 262144,
    "hidden_size": 7168, "intermediate_size": 18432,
    "moe_intermediate_size": 2048, "num_hidden_layers": 64,
    "num_nextn_predict_layers": 1, "num_attention_heads": 64,
    "n_shared_experts": 1, "n_routed_experts": 256, "ep_size": 1,
    "routed_scaling_factor": 2.5, "kv_lora_rank": 512, "q_lora_rank": 1536,
    "qk_rope_head_dim": 64, "v_head_dim": 192, "qk_nope_head_dim": 128,
    "topk_method": "noaux_tc", "n_group": 8, "topk_group": 4,
    "num_experts_per_tok": 8, "moe_layer_freq": 1,
    "first_k_dense_replace": 3, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "num_key_value_heads": 64,
    "hidden_act": "silu", "rms_norm_eps": 1e-06, "rope_theta": 100000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "rope_type": "yarn"},
    "attention_bias": False, "tie_word_embeddings": False,
    "model_type": "deepseek_v3"}
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "kv_lora_rank", "q_lora_rank", "qk_rope_head_dim", "v_head_dim",
          "qk_nope_head_dim", "num_experts_per_tok", "num_attention_heads")


@pytest.fixture(scope="module")
def found():
    return harness.find_cell(MANIFEST, CELL)


def traffic_file():
    return harness.load_json(os.path.join(
        REPO, "benchmark", "traffic", "docs_reask_closed_16.json"))


def test_the_cell_is_found_by_name_with_its_files(found):
    entry, config, traffic = found
    cfg_entry = next(c for c in MANIFEST["configs"]
                     if c["name"] == entry["config"])
    assert cfg_entry["source"] == config["source"] == (
        "https://huggingface.co/ai-sage/GigaChat3.1-702B-A36B/blob/main/"
        "config.json")
    for key in ("reduced", "assumed", "deployment"):
        assert key in config, key
    assert sorted(cfg_entry["reduced"]) == sorted(config["reduced"])
    assert traffic["driver"] == "serve_latent_moe"
    for path in ("drivers/serve_latent_moe.py", "limits/" + CELL + ".json",
                 "reference/deepseek_v3.py", "flops_deepseek_v3.py",
                 "weights_deepseek_v3.py"):
        assert os.path.exists(os.path.join(REPO, "benchmark", path)), path
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    # ``itl_mean_ms`` is listed since the clients pass the turnstile: four
    # seeds then spread it 0.6 %, under half its bound (PERF.md, PR 27)
    assert [m["name"] for m in harness.metrics_of(
        MANIFEST, "end_to_end", CELL)] == ["serve_tok_s", "itl_mean_ms",
                                           "setup_s"]


def test_the_configuration_keeps_every_published_number(found):
    """Every key of the published config is there with its value, but for
    the keys under ``reduced``; no width is among those."""
    _, config, _ = found
    for key, value in PUBLISHED.items():
        if key in config["reduced"]:
            continue
        assert config[key] == value, key
    assert not set(config["reduced"]) & set(WIDTHS)
    assert set(config["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "max_position_embeddings", "num_nextn_predict_layers"}
    # the router keeps its width; the cut is in what is held
    assert config["n_routed_experts"] == 256
    assert config["held_experts"] == [0, 16]
    assert config["n_routed_experts_held"] == 16
    # the guide's floors: a period + 4 expert layers, 8 experts, 1/8 vocab
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert config["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    assert config["engine"]["max_len"] == config["max_position_embeddings"]


def test_parameter_count_and_pool_bytes_are_the_files_arithmetic(found):
    _, config, _ = found
    attn = flops.attention_proj_params(config)
    assert attn == 132_579_328                       # 132.58 M a layer
    assert flops.kv_b_params(config) == 10_485_760
    assert flops.expert_params(config) == 44_040_192
    assert flops.dense_mlp_params(config) == 396_361_728
    count = weights.param_count(config)
    norms = 5 * (1536 + 512 + 2 * 7168) + 7168
    want = (5 * attn + 396_361_728
            + 4 * (17 * 44_040_192 + 7168 * 256 + 256)
            + 2 * 16032 * 7168 + norms)
    assert count == want and round(count / 1e9, 2) == 4.29
    page = config["engine"]["page_size"]
    per_page = 5 * page * 576 * 2
    assert per_page == 737_280
    assert (config["engine"]["num_pages"] - 1) * per_page == 3_774_873_600


def test_the_deal_of_documents():
    traffic = traffic_file()
    plan, asks = docs_reask.docs_reask_requests(traffic, 1, 16032)
    again, asks2 = docs_reask.docs_reask_requests(traffic, 2**31 + 5, 16032)
    assert len(plan) == 16 and asks == asks2
    q = traffic["question_len"]
    for c, (reqs, nums) in enumerate(zip(plan, asks)):
        # the same lengths under every seed, other ids
        assert [(len(p), n) for p, n in reqs] == [
            (len(p), n) for p, n in again[c]]
        assert not np.array_equal(reqs[0][0], again[c][0][0])
        # client c starts c % 4 asks into its first document
        assert len(reqs) == 12 * 4 - c % 4
        assert nums[0] == 0 and nums[1:4 - c % 4] == list(
            range(c % 4 + 1, 4))
        assert nums[4 - c % 4:] == [0, 1, 2, 3] * 11
        i = 0
        while i < len(reqs):
            run = 4 - c % 4 if i == 0 else 4
            for prompt, new in reqs[i:i + run]:
                assert 32 <= new <= 128
                lens = [n for n in range(4096, 16385, 256)
                        if q["min"] <= len(prompt) - n <= q["max"]]
                assert lens, len(prompt)
            # the asks of one document share it, token for token
            shared = min(len(p) for p, _ in reqs[i:i + run]) - q["max"]
            for prompt, _ in reqs[i + 1:i + run]:
                np.testing.assert_array_equal(prompt[:shared],
                                              reqs[i][0][:shared])
            i += run
        assert max(len(p) + n for p, n in reqs) <= 17408
    docs = sorted({len(p) - len(p) % 256 for reqs in plan for p, _ in reqs})
    assert docs[0] >= 4096 and docs[-1] <= 16384 + 256


def test_flops_and_bytes_against_hand_counts(found):
    _, config, _ = found
    assert flops.pairs(1, 8192) == 8192
    assert flops.pairs(512, 512) == 512 * 513 // 2
    # decode at 8 k: the absorbed core, 64 heads x (576 + 512) a pair
    assert flops.absorbed_attention_flops(config, 1, 8192) == (
        2 * 64 * 1088 * 8192)
    assert flops.attention_flops(config, 1, 8192) == 2 * 64 * 1088 * 8192
    # a 512-token chunk on 7680 cached tokens: expanding is cheaper
    p = 512 * 7680 + 512 * 513 // 2
    expanded = 2 * 64 * 384 * p + 2 * 10_485_760 * 7680
    assert flops.expanded_attention_flops(config, 512, 8192) == expanded
    assert expanded < flops.absorbed_attention_flops(config, 512, 8192)
    assert flops.attention_flops(config, 512, 8192) == expanded
    # a whole step: one 512-token chunk, 10 sampled, 300 held pairs
    per_token = (5 * 132_579_328 + 396_361_728
                 + 4 * (7168 * 256 + 44_040_192))
    want = (2 * per_token * 512 + 5 * expanded + 2 * 44_040_192 * 300
            + 2 * 7168 * 16032 * 10)
    assert flops.serve_flops(config, [(512, 8192)], 10, 300) == want
    # bytes of one call: 16 decode rows at 8 k read 16 x 8192 rows once
    spans = [(1, 8192)] * 16
    assert flops.latent_attention_bytes(config, spans) == 2 * (
        16 * 64 * 1088 + 16 * 8192 * 576)
    assert 8192 * 576 * 2 == 8192 * 1152              # 1,152 B a token


def hand_run(found, **over):
    _, config, _ = found
    calls = [(float(t), [1, 1, 510], [100, 200, 8192])
             for t in (1.0, 1.1, 1.2, 1.3, 1.4, 2.0, 2.1, 2.2, 2.3, 2.4)]
    kernel = ("%_latent_qblock_device.1 = bf16[64,512,512] custom-call(), "
              "custom_call_target=\"tpu_custom_call\"")
    gmm = "%ragged-dot-none.2 = bf16[4096,2048] custom-call()"
    events = [[kernel, i * 1000, 400] for i in range(10)] + [
        [gmm, 20_000 + i * 1000, 250] for i in range(24)] + [
        ["%_qblock_device.1 = f32[] custom-call(), custom_call_target="
         "\"tpu_custom_call\"", 90_000, 7]]
    run = {"config": config, "chips": 1, "window_s": 2.0,
           "peaks": harness.load_peaks("TPU v5 lite"),
           "kernel_calls": calls, "window": {"delivered": 4},
           "trace": {"events": {"/device:TPU:0": events},
                     "window_s": 1e-4, "busy_s": 1e-5},
           "counters": {"useful_tokens_total": 1024,
                        "moe_expert_tokens": np.array([40, 10] + [5] * 14),
                        "moe_unheld_tokens": 2048,
                        "prompt_tokens_admitted": 4000,
                        "prompt_tokens_cached": 3000}}
    run.update(over)
    return run


def test_the_new_readers_on_a_hand_made_run(found):
    run = hand_run(found)
    read = {m["name"]: harness.load_reader(m["name"])
            for m in MANIFEST["per_layer"] if m["workloads"] == [CELL]}
    assert sorted(read) == [
        "latent_attn_roofline", "moe_device_pct", "moe_load_max_over_mean",
        "moe_unheld_pct", "prefix_hit_pct", "serve_mfu_pct.latent_moe"]
    assert read["prefix_hit_pct"](run) == 75.0
    assert read["moe_load_max_over_mean"](run) == 40 * 16 / 120
    assert read["moe_unheld_pct"](run) == 100 * 2048 / (1024 * 4)
    # the grouped products: 24 x 250 ns of a 1e-4 s window
    assert read["moe_device_pct"](run) == pytest.approx(6.0)
    config = run["config"]
    spans = [(1, 100), (1, 200), (510, 8192)]
    total = flops.serve_flops(config, spans * 2, 4, 120)
    assert read["serve_mfu_pct.latent_moe"](run) == pytest.approx(
        100 * total / (2.0 * 197e12))
    ops = sum(flops.absorbed_attention_flops(config, q, c)
              for q, c in spans)
    nbytes = flops.latent_attention_bytes(config, spans)
    least = 10 * max(ops / 197e12, nbytes / 819e9)
    assert read["latent_attn_roofline"](run) == pytest.approx(
        100 * least / (10 * 400e-9))
    # Llama's kernel is not counted, and the latent kernel's events do not
    # reach Llama's roofline
    import re
    from benchmark.layer_metrics import qblock_roofline
    assert not re.search(qblock_roofline.KERNEL,
                         run["trace"]["events"]["/device:TPU:0"][0][0])
    # a program without the counters or the kernel: nothing, not an error
    bare = hand_run(found, counters={}, trace={"events": {"p": []},
                                               "window_s": 1.0,
                                               "busy_s": 0.0})
    for name, fn in read.items():
        assert fn(bare) is None, name
        assert fn({}) is None, name


def test_seeded_leaves_by_group_equal_the_whole_table():
    cfg = dict(vocab_size=64, hidden_size=32, intermediate_size=48,
               moe_intermediate_size=16, num_hidden_layers=2,
               num_nextn_predict_layers=1, num_attention_heads=2,
               n_shared_experts=1, n_routed_experts=8, held_experts=[2, 4],
               kv_lora_rank=8, q_lora_rank=16, qk_rope_head_dim=4,
               v_head_dim=12, qk_nope_head_dim=8, first_k_dense_replace=1,
               initializer_range=0.02, router_bias_std=0.05)
    table = weights.leaf_table(cfg)
    whole = dict(zip([n for n, _, _ in table],
                     weights.make_weights(cfg, 2**31 + 9, "bfloat16")))
    for prefix in ("model.layers.1.", "mtp.0.", "lm_head."):
        group = weights.make_group(cfg, 2**31 + 9, prefix, "bfloat16")
        assert group and all(
            np.array_equal(np.asarray(a, np.float32),
                           np.asarray(whole[prefix + n], np.float32))
            for n, a in group.items())
    layer = weights.make_group(cfg, 5, "model.layers.1.")
    assert layer["mlp.experts.w_gate"].shape == (4, 32, 16)
    assert layer["mlp.experts.router"].shape == (32, 8)
    assert str(layer["mlp.experts.router"].dtype) == "float32"
    bias = np.asarray(layer["mlp.experts.router_bias"])
    assert np.abs(bias).min() > 0 and np.abs(bias).max() < 0.1
    assert float(np.asarray(layer["input_layernorm.weight"],
                            np.float32).min()) == 1.0


@pytest.mark.parametrize("seed", [7, 2**31 + 27])
def test_the_seeded_router_loads_every_held_expert_under_any_seed(found, seed):
    """The configuration's own router and bias (``router_bias_std``), on
    unit-norm random rows in the hidden state's place (which reproduce the
    chip's ``moe_unheld_pct`` seed by seed: PERF.md section 6): in every
    expert layer each held expert gets its share of the tokens. A bias wide
    beside the gaps between a token's best scores picks the experts itself:
    at 0.05 a third of the held experts never got a token, which ones by the
    seed, and the seed set the tick's length."""
    import jax
    import jax.numpy as jnp
    from benchmark.reference import deepseek_v3 as ref
    config = found[1]
    lo, n = config["held_experts"]
    rows = jax.random.normal(jax.random.key(seed % 1000),
                             (2048, config["hidden_size"]), jnp.float32)
    rows = rows / jnp.sqrt(jnp.mean(rows * rows, -1, keepdims=True))
    unheld = []
    for i in range(config["first_k_dense_replace"],
                   config["num_hidden_layers"]):
        prefix = weights.layer_prefix(i) + "mlp.experts.router"
        group = weights.make_group(config, seed, prefix, "float32")
        idx = np.asarray(ref.route(rows, group[""], group["_bias"],
                                   config)[0])
        held = (idx >= lo) & (idx < lo + n)
        load = np.bincount(idx[held] - lo, minlength=n)
        assert load.min() > 0.5 * load.mean(), (i, load)
        assert load.max() < 1.7 * load.mean(), (i, load)
        unheld.append(1 - held.any(-1).mean())
    # an even router leaves out group 0 in half of the draws, and holds none
    # of the 8 picks in a third of the others: two thirds of the tokens
    assert 0.60 < np.mean(unheld) < 0.70, unheld


def test_the_turnstile_sends_one_ticks_requests_in_the_clients_order():
    """Requests whose replies came together reach the engine in the
    clients' order, a turnaround after the reply, whatever order the
    threads woke in; a reply that came later does not wait for them."""
    import threading
    import time
    from benchmark.drivers import serve_latent_moe as drv

    class Engine:
        def __init__(self):
            self.arrived, self.reply = [], threading.Event()

        def generate(self, prompt, **kw):
            self.arrived.append((int(prompt[0]), time.perf_counter()))
            self.reply.wait(5)

    engine = Engine()
    gate = drv.Turnstile(engine, 0.3, 0.2)
    started = {}

    def client(c, delay):
        time.sleep(delay)
        started[c] = time.perf_counter()
        gate.generate(np.asarray([c]))
        if c != 5:                        # one tick's replies: all at once
            gate.generate(np.asarray([10 + c]))

    # the threads wake in the reverse of the clients' order, 10 ms apart;
    # client 5's reply comes 0.7 s later: another tick's
    delays = {0: 0.04, 1: 0.03, 2: 0.02, 3: 0.01, 4: 0.0, 5: 0.74}
    threads = [threading.Thread(target=client, args=(c, delays[c]))
               for c in range(6)]
    gate.bind(threads)
    for t in threads:
        t.start()
    while len(engine.arrived) < 6:
        time.sleep(0.005)
    first = engine.arrived[:6]
    assert [c for c, _ in first] == [0, 1, 2, 3, 4, 5]
    assert all(t - started[c] >= 0.3 for c, t in first)
    engine.reply.set()                    # five replies in one tick
    for t in threads:
        t.join(10)
    assert [c for c, _ in engine.arrived[6:]] == [10, 11, 12, 13, 14]
    assert not gate.waiting


def test_the_simulated_loop_is_steady_in_order_and_not_in_a_race():
    """``traffic/docs_reask_sim.py`` on the cell's own deal: with one tick's
    requests in the clients' order the window's count is the same under a
    jitter of every tick; with the order and the admitting tick left to a
    race it spreads by percents (what the chip read: PERF.md section 6)."""
    from benchmark.traffic import docs_reask_sim as sim
    lengths = sim.plan_lengths(traffic_file())
    assert len(lengths) == 16 and all(r[0][2] for r in lengths)

    def rates(**kw):
        return [sim.window_rate(sim.simulate(
            lengths, tick_jitter=0.02, horizon_s=52, seed=s, **kw)[1], 0, 50)
            for s in range(8)]

    in_order, raced = rates(), rates(order_noise_s=0.02)
    assert 120 < min(in_order) and max(in_order) < 260
    assert max(in_order) - min(in_order) < 0.01 * min(in_order)
    assert max(raced) - min(raced) > 0.02 * min(raced)
    counts, factor = sim.speed_response(lengths, np.asarray([0.9, 1.0, 1.1]))
    assert counts[0] > counts[1] > counts[2] and factor[1] > 0


def test_warm_shapes_meet_every_job_bucket_a_tick_can_reach():
    import importlib
    from benchmark.drivers import serve_latent_moe as drv
    rpa = importlib.import_module(
        "paddle_tpu.ops.pallas.ragged_paged_attention")
    shapes = drv.warm_shapes([16, 32, 64, 128, 256, 512], 136, 128, 16, 8,
                             rpa.latent_job_list)
    by_tokens = {}
    for t, rows, pages in shapes:
        assert rows <= t and pages <= 136
        jobs = rpa.latent_job_list(
            t, np.arange(rows), np.arange(rows), np.ones(rows, np.int32),
            np.full(rows, pages * 128), np.zeros((512, 136), np.int32), 8,
            128)[2].shape[1]
        by_tokens.setdefault(t, set()).add(jobs)
    # a 16-token tick of 16 rows at full length: 2,176 jobs -> 4096
    assert by_tokens[16] == {64, 128, 256, 512, 1024, 2048, 4096}
    # a 512-token tick: a chunk at full length + 16 rows: 80 x 136 jobs
    assert by_tokens[512] == {64, 128, 256, 512, 1024, 2048, 4096, 8192,
                              16384}


@pytest.mark.parametrize("control", [None, "int8"])
def test_the_driver_end_to_end_at_a_small_size(found, control):
    """The new driver through its functions (interpret-mode kernel, float32,
    the CPU): every request answered, the program's served tokens are the
    reference's own first choices, the counters arrive."""
    from benchmark.drivers import serve_latent_moe as drv
    entry, config, traffic = found
    config = dict(
        config, vocab_size=128, hidden_size=64, intermediate_size=160,
        moe_intermediate_size=32, num_hidden_layers=3,
        num_attention_heads=4, n_routed_experts=16, held_experts=[4, 4],
        kv_lora_rank=16, q_lora_rank=32, qk_rope_head_dim=8, v_head_dim=24,
        qk_nope_head_dim=16, n_group=4, topk_group=2, num_experts_per_tok=4,
        max_position_embeddings=128, engine_dtype="float32",
        rope_scaling=dict(config["rope_scaling"], factor=4,
                          original_max_position_embeddings=32),
        engine=dict(max_batch_size=2, max_len=128, page_size=8,
                    num_pages=60, token_budget=16, prefill_chunk_tokens=16))
    traffic = dict(
        traffic, clients=2, docs_per_client=6, reference_width=128,
        doc_len=dict(median=48, sigma=0.5, min=32, max=80, grid=16),
        question_len=dict(median=8, sigma=0.6, min=4, max=16),
        answer_len=dict(median=6, sigma=0.4, min=4, max=8))
    ctx = {"cell": entry, "config": config, "traffic": traffic,
           "limits": {"sample_requests": 3, "router_margin_min": 1e-3,
                      "decided_logit_gap_max": 1e-4,
                      "served_logit_gap_mean": 1e-5},
           # a window long enough for a request on a loaded host
           "seed": 2**31 + 77, "seconds": 20.0, "trace": False, "chips": 1,
           "watch": harness.CompileWatch(), "control": control}
    run = drv.run(ctx)
    assert run["failed"] == 0 and run["finished"] >= 1
    assert harness.judge(run["checks"]), run["checks"]
    c = run["counters"]
    assert c["compiled_layer_calls"] == 3 * c["ragged_steps"] > 0
    assert c["moe_expert_tokens"].shape == (4,)
    assert 0 < c["prompt_tokens_cached"] < c["prompt_tokens_admitted"]
    gaps = run["gaps"]
    n = len(gaps["served"])
    assert n == len(gaps["margin"]) == len(gaps["altered"]) > 0
    # two expert layers, a group margin and an expert margin each
    assert np.asarray(gaps["margins"]).shape == (n, 2, 2)
    assert min(gaps["margin"]) >= 0
    assert any(m >= 1e-3 for m in gaps["margin"])
    assert any(len(r) > 1 for r in run["token_stamps"])
    if control:
        # an altered token is told apart at this limit, on every number
        assert [n for n, _, _ in run["stand_ins"]["altered_token"]] == list(
            drv.CHECKS)
        assert not harness.judge(run["stand_ins"]["altered_token"])
        assert len(gaps["int8"]) == n


def test_the_widest_gap_is_read_where_the_routing_is_decided():
    """Hand-made readings: a wide gap on a token that the reference's
    router decides by less than the limit's margin does not count, the
    same gap on a decided token does; an altered token goes where it costs
    least among the decided positions and is judged on every number; the
    control fails by the mean alone."""
    from benchmark.drivers import serve_latent_moe as drv
    limits = {"router_margin_min": 0.01, "decided_logit_gap_max": 0.3,
              "served_logit_gap_mean": 0.05}
    gaps = {"served": [0.0, 0.9, 0.1, 0.0],
            "margin": [0.05, 0.001, 0.02, 0.0],
            "altered": [2.0, 0.2, 1.5, 0.1],
            "int8": [0.2, 0.0, 0.25, 0.0]}
    rows = drv.judged_rows(gaps, limits)
    assert [n for n, _, _ in rows["program"]] == list(drv.CHECKS)
    assert dict((n, v) for n, v, _ in rows["program"]) == {
        "decided_logit_gap_max": 0.1, "served_logit_gap_mean": 0.25}
    assert not harness.judge(rows["program"])         # the mean: 0.25
    assert harness.judge(drv.gap_checks([0.0, 0.9, 0.1, 0.0],
                                        gaps["margin"],
                                        dict(limits,
                                             served_logit_gap_mean=0.3)))
    # the same wide gap on a decided token fails
    assert not harness.judge(drv.gap_checks(
        [0.9, 0.0, 0.1, 0.0], gaps["margin"],
        dict(limits, served_logit_gap_mean=0.3)))
    # altered: position 2 (1.5), not 1 or 3 (undecided) and not 0 (2.0)
    assert drv.altered_token_row(gaps, limits) == [0.0, 0.9, 1.5, 0.0]
    altered = dict((n, v) for n, v, _ in rows["altered_token"])
    assert altered["decided_logit_gap_max"] == 1.5
    control = dict((n, v) for n, v, _ in rows["control_int8"])
    assert control == {"decided_logit_gap_max": 0.25,
                       "served_logit_gap_mean": 0.1125}
    assert not harness.judge(rows["control_int8"])
    # no decided token at all: nothing to read is not a pass
    none = drv.gap_checks([0.0], [0.0], limits)
    assert not harness.judge(none)


def test_calibrate_serve_judges_recorded_readings_again(tmp_path, found):
    """``calibrate_serve``: the rows of a recorded seed come from the
    driver's own ``judged_rows`` under the limits as they are now; a
    variant's rows are expected to be nothing; other windows are read out
    of one run's token stamps; the committed readings of this cell, judged
    again under the committed limits, all come out as they have to."""
    import json
    from benchmark import calibrate_serve as cal
    from benchmark.drivers import serve_latent_moe as drv
    limits = {"router_margin_min": 0.01, "decided_logit_gap_max": 0.3,
              "served_logit_gap_mean": 0.05}
    rec = {"cell": CELL, "seed": 7, "variant": None,
           "gaps": {"served": [0.0, 0.9, 0.1], "margin": [0.05, 0.001, 0.02],
                    "altered": [2.0, 0.2, 1.5], "int8": [0.4, 0.0, 0.25]},
           "numbers": {}}
    rows = {r["who"]: r for r in cal.rows_of(rec, drv, limits)}
    assert rows["program"]["correct"] is False        # the mean: 0.333
    limits["served_logit_gap_mean"] = 0.4
    rows = {r["who"]: r for r in cal.rows_of(rec, drv, limits)}
    assert rows["program"]["correct"] and rows["program"]["expected"]
    for who in ("control_int8", "altered_token"):
        assert not rows[who]["correct"] and not rows[who]["expected"]
    assert cal.wrong_rows(list(rows.values())) == 0
    variant = cal.rows_of(dict(rec, variant="router_bf16"), drv, limits)
    assert {r["who"] for r in variant} == {
        "program.router_bf16", "control_int8.router_bf16",
        "altered_token.router_bf16"}
    assert all(r["expected"] is None for r in variant)
    assert cal.wrong_rows(variant) == 0
    # a driver without ``judged_rows``: the recorded numbers by name
    plain = cal.rows_of({"cell": "serve_chat_closed", "seed": 1,
                         "numbers": {"program": {"served_logit_gap_max": .1},
                                     "altered_token":
                                     {"served_logit_gap_max": 2.0}}},
                        object(), {"served_logit_gap_max": 0.4})
    assert [r["correct"] for r in plain] == [True, False]
    # windows out of one stretch: 4 tokens a second for two requests
    stamps = [[0.25 * i for i in range(1, 41)], [0.5 * i for i in range(21)]]
    w = cal.window_numbers(stamps, 2.0, 5.0)
    assert w["serve_tok_s"] == pytest.approx((20 + 10) / 5.0)
    assert w["itl_mean_ms"] == pytest.approx(1e3 * (20 * .25 + 10 * .5) / 30)
    assert cal.window_numbers(stamps, 50.0, 5.0)["itl_mean_ms"] is None
    # the committed readings under the committed limits
    path = os.path.join(REPO, "benchmark", "limits", CELL + ".readings.jsonl")
    with open(path) as f:
        recs = [json.loads(line) for line in f]
    assert len({r["seed"] for r in recs if not r.get("variant")}) >= 6
    assert cal.rejudge(path, MANIFEST) == 0
    committed = harness.load_json(os.path.join(REPO, "benchmark", "limits",
                                               CELL + ".json"))
    judged = [r for rec in recs if not rec.get("variant")
              for r in cal.rows_of(rec, drv, committed)]
    for who in ("program", "control_int8", "altered_token"):
        assert sum(r["who"] == who for r in judged) >= 6, who

"""The benchmark's files for the linear-attention (a recurrent state a slot)
/ latent-attention / routed-expert family (CPU, tiny sizes, no chip): the
configuration against the catalog's published numbers, its parameter and
byte arithmetic, the deal of reasoning requests and long documents, the
operations-and-bytes arithmetic against hand counts, the new readers on
hand-made run records, the seeded leaf table, the tap that reads the
program's own logits from the timed ticks, and the new driver end to end
through its functions, with each fault of the mechanism. Nothing seen here
is a device result."""
import json
import os
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import flops_bailing_hybrid as flops  # noqa: E402
from benchmark import harness  # noqa: E402
from benchmark import weights_bailing_hybrid as weights  # noqa: E402
from benchmark.traffic import reason_tail  # noqa: E402

MANIFEST = harness.load_manifest()
CELL = "serve_reason_state_closed"
CONFIG = "ling-3.0-flash-serve-ep4-7l"
SOURCE = "https://huggingface.co/inclusionAI/Ling-3.0-flash/blob/main/" \
    "config.json"
#: ``config`` of the catalog's row Ling-3.0-flash (its source_url's
#: config.json)
PUBLISHED = {
    "expert_swiglu_limit_list": [0] * 35 + [4] * 7,
    "first_k_dense_replace": 2,
    "gated_attention_proj_granularity_type": "head_wise",
    "group_norm_size": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2560, "intermediate_size": 6144, "kda_lower_bound": -5,
    "kda_safe_gate": True, "kv_lora_rank": 512, "layer_group_size": 6,
    "linear_silu": True, "max_position_embeddings": 262144,
    "max_window_layers": 20, "moe_intermediate_size": 768,
    "moe_router_enable_expert_bias": True,
    "moe_shared_expert_intermediate_size": 768, "mtp_loss_scaling_factor": 0,
    "mtp_use_kda": False, "n_group": 8, "no_kda_lora": True,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 512,
    "num_experts_per_tok": 8, "num_hidden_layers": 42,
    "num_key_value_heads": 32, "num_kv_heads_for_linear_attn": 0,
    "num_nextn_predict_layers": 1, "num_shared_experts": 1,
    "partial_rotary_factor": 0.5, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 6000000,
    "rotary_dim": 64, "routed_scaling_factor": 2.5,
    "scale_router_input": False, "score_function": "sigmoid",
    "scoring_func": "sigmoid", "seq_aux": True,
    "share_expert_swiglu_limit_list": [0] * 34 + [5] * 6 + [7] * 2,
    "short_conv_kernel_size": 4, "tie_word_embeddings": False,
    "topk_group": 4, "topk_method": "noaux_tc", "up_proj_norm": False,
    "use_bias": False, "use_kda_lora": False, "use_mla_nope": False,
    "use_nGPT": False, "use_qk_norm": True, "use_qkv_bias": False,
    "v_head_dim": 128, "value_norm": False, "vocab_size": 157184,
    "model_type": "bailing_hybrid"}
REDUCED = {"num_hidden_layers": 7, "first_k_dense_replace": 1,
           "num_experts": 512, "vocab_size": 39296,
           "max_position_embeddings": 34048, "num_nextn_predict_layers": 0}
#: the accepted readers that read this driver's records as they are, listed
#: for this cell through copies of their own (``<name>.linear``)
COPIED_READERS = ("latent_attn_roofline", "moe_device_pct",
                  "moe_load_max_over_mean", "moe_unheld_pct")
NEW_READERS = ("serve_mfu_pct.linear_latent", "kda_step_roofline",
               "kda_chunk_roofline", "linear_attn_device_pct",
               "kda_chunk_fill_pct", "state_host_ms") + tuple(
                   name + ".linear" for name in COPIED_READERS)
JOINED = ("sched_tick_ms", "sched_padded_pct", "kv_pages_peak_pct",
          "device_idle_pct.serve", "pre_device_s", "device_setup_s",
          "ttft_p95_ms.closed")
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def found():
    return harness.find_cell(MANIFEST, CELL)


def test_the_cell_is_found_by_name_with_its_files(found):
    entry, config, traffic = found
    assert entry == {"name": CELL, "config": CONFIG,
                     "traffic": "reason_tail_closed_96", "chips": 1,
                     "why": entry["why"]}
    assert len(entry["why"]) <= 200
    cfg_entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert cfg_entry["source"] == config["source"] == SOURCE
    assert sorted(cfg_entry["reduced"]) == sorted(config["reduced"]) == \
        sorted(REDUCED)
    for key in ("reduced", "assumed", "deployment", "engine", "parameters",
                "state", "pages"):
        assert key in config, key
    assert traffic["driver"] == "serve_linear_latent"
    for name in ("drivers/serve_linear_latent.py",
                 "reference/bailing_hybrid.py", "weights_bailing_hybrid.py",
                 "flops_bailing_hybrid.py", "traffic/reason_tail.py",
                 "calibrate_state.py", "kda_spans.py",
                 "limits/" + CELL + ".json"):
        assert os.path.exists(os.path.join(REPO, "benchmark", name)), name
    listed = {m["name"] for g in ("end_to_end", "per_layer")
              for m in harness.metrics_of(MANIFEST, g, CELL)}
    assert listed >= {"serve_tok_s", "setup_s"} | set(NEW_READERS) | set(
        JOINED)
    # ``itl_mean_ms``: refused for PR 31's cell at this bound (ledger), and
    # 96 clients behind 32 k documents will not spread less
    assert not listed & ({"itl_mean_ms", "serve_mfu_pct", "qblock_roofline",
                          "tick_attn_host_ms", "prefix_hit_pct"}
                         | set(COPIED_READERS))
    for name in NEW_READERS:
        m = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
        assert CELL in m["workloads"] and m["moves"] == "serve_tok_s"


def test_the_configuration_keeps_every_published_number(found):
    """Every number of the catalog's entry under its own key; what is cut
    is listed in ``reduced``, and no width is."""
    _, config, _ = found
    for key, value in PUBLISHED.items():
        assert config[key] == REDUCED.get(key, value), key
    assert config["held_experts"] == [0, 128] and \
        config["num_experts_held"] == 128
    assert config["layer_kinds"] == ["kda"] * 6 + ["mla"]
    # the kept layers by their published indices: the published rule
    assert config["layer_indices"] == [0, 6, 7, 8, 9, 10, 11]
    assert [("mla" if (i + 1) % 6 == 0 else "kda")
            for i in config["layer_indices"]] == config["layer_kinds"]
    assert not any(config[k][i] for i in config["layer_indices"]
                   for k in ("expert_swiglu_limit_list",
                             "share_expert_swiglu_limit_list"))
    engine = config["engine"]
    assert engine == {"max_batch_size": 96, "max_len": 34048,
                      "page_size": 128, "num_pages": 8193,
                      "token_budget": 512, "prefill_chunk_tokens": 512}
    assert engine["max_len"] == 266 * 128 == \
        config["max_position_embeddings"]
    for key in ("use_qk_norm", "no_kda_lora", "kda_safe_gate",
                "num_kv_heads_for_linear_attn", "max_window_layers",
                "router_bias_std", "initializer_range", "A_log / dt_bias",
                "conv_weight", "expert_swiglu_limit_list"):
        assert key in config["assumed"], key
    # the program's config class takes the file's keys as they are
    from benchmark.drivers.serve_linear_latent import MODEL_KEYS
    from paddle_tpu.models.bailing_hybrid import BailingHybridConfig
    cfg = BailingHybridConfig(held_experts=tuple(config["held_experts"]),
                              **{k: config[k] for k in MODEL_KEYS})
    assert cfg.layer_kinds == config["layer_kinds"]
    assert cfg.q_lora_rank is None and cfg.kda_lower_bound == -5.0


def test_parameter_and_byte_counts_are_the_files_arithmetic(found):
    _, config, _ = found
    stated = config["parameters"]
    kda = 5 * 2560 * 4096 + 4096 * 2560 + 2560 * 32 + 4 * 12288 + 32 \
        + 4096 + 128
    mla = 2560 * 6144 + 2560 * 576 + 512 + 512 * 8192 + 2560 * 32 \
        + 4096 * 2560
    expert = 3 * 2560 * 768
    router = 2560 * 512 + 512
    dense = kda + 3 * 2560 * 6144 + 2 * 2560
    moe = 128 * expert + expert + router + 2 * 2560
    assert (kda, mla, expert) == (63_049_888, 31_965_696, 5_898_240)
    assert stated["kda_mixer"] == kda and stated["mla_mixer"] == mla
    assert stated["expert"] == expert and stated["router"] == router
    assert stated["dense_layer"] == dense
    assert stated["kda_expert_layer"] == kda + moe
    assert stated["mla_expert_layer"] == mla + moe
    total = dense + 5 * (kda + moe) + mla + moe + 2 * 39296 * 2560 + 2560
    assert weights.param_count(config) == stated["total"] == total
    assert round(total / 1e9, 2) == 5.23
    assert stated["bytes_bf16"] == 2 * total and \
        round(2 * total / 1e9, 2) == 10.46
    assert kda - 4 * 12288 - 32 - 4096 - 128 == \
        flops.kda_proj_params(config)
    assert mla - 512 == flops.mla_proj_params(config)
    # the state: S float32 + the convolution's last 3 rows in bf16, a slot a
    # KDA layer, 96 slots + the scratch slot, 6 layers
    state = config["state"]
    a_slot = 32 * 128 * 128 * 4 + 3 * 12288 * 2
    assert state["bytes_a_slot_a_layer"] == a_slot == 2_097_152 + 73_728
    assert state["bytes"] == 97 * 6 * a_slot
    assert round(96 * 6 * a_slot / 1e9, 2) == 1.25
    assert flops.state_bytes(config) == 2_097_152
    # the one MLA layer's latent pool
    pages = config["pages"]
    assert pages["bytes_a_token"] == 2 * (512 + 64) == 1152
    assert pages["bytes"] == 8193 * 128 * 1152
    assert round(pages["bytes"] / 1e9, 2) == 1.21
    assert stated["bytes_bf16"] + state["bytes"] + pages["bytes"] < 13.0e9


def test_the_deal_of_reasoning_requests_and_documents(found):
    _, config, traffic = found
    plan, kinds = reason_tail.reason_tail_requests(traffic, 7, 39296)
    again, _ = reason_tail.reason_tail_requests(traffic, 8, 39296)
    assert len(plan) == 96 == traffic["clients"]
    assert kinds == ["long"] * 8 + ["short"] * 88
    assert traffic["deal_seed"] == 33 and traffic["turnaround_ms"] == 12
    for c in range(96):       # the lengths are the file's, the ids the seed's
        assert [(len(p), n) for p, n in plan[c]] == [
            (len(p), n) for p, n in again[c]]
    assert not np.array_equal(plan[0][0][0], again[0][0][0])
    assert max(len(p) + n for reqs in plan for p, n in reqs) <= \
        config["engine"]["max_len"]
    for c in range(8):
        for i, (p, n) in enumerate(plan[c]):
            assert 8192 + 32 <= len(p) <= 32768 + 256
            # the first answer is cut to (c % 8 + 1) / 8 of its length
            assert (256 if i else 256 * (c + 1) // 8) <= n <= 1024
        assert len(plan[c]) == 6 and len(
            {p[:64].tobytes() for p, _ in plan[c]}) == 6     # each once
    for c in range(8, 96):
        assert all(256 <= len(p) <= 4096 for p, _ in plan[c])
        assert all(512 <= n <= 4096 for _, n in plan[c][1:])
        assert 512 * (c % 8 + 1) // 8 <= plan[c][0][1] <= 4096
    docs = [len(p) for c in range(8) for p, _ in plan[c]]
    prompts = [len(p) for c in range(8, 96) for p, _ in plan[c]]
    answers = [n for c in range(8, 96) for _, n in plan[c][1:]]
    assert 15000 <= np.median(docs) <= 18000
    assert 950 <= np.median(prompts) <= 1100
    assert 1450 <= np.median(answers) <= 1650
    # the watched requests: documents whose prefill crosses 16 chunks
    watch = traffic["watch"]
    assert set(watch["long_clients"]) <= set(range(8))
    assert set(watch["short_clients"]) <= set(range(8, 96))
    assert all(c % 8 == 0 for c in watch["short_clients"])
    # a watched client gives a row a tick at the most: ~1,100 ticks of ramp
    # and window (PERF.md section 5) fit the tap's buffer
    watched = len(watch["long_clients"]) + len(watch["short_clients"])
    assert watch["pool_rows"] >= watched * 1100
    assert traffic["crossed_chunks"] * 512 <= 8192
    assert max(traffic["reference_width"]) == config["engine"]["max_len"]


def test_flops_and_bytes_against_hand_counts(found):
    _, config, _ = found
    assert flops.layer_counts(config) == (6, 1, 1, 6)
    # the recurrence: three passes over a head's 128 x 128 state a token
    assert flops.recurrence_flops(config, 10) == 2 * 3 * 128 * 128 * 32 * 10
    assert flops.conv_flops(config, 10) == 2 * 4 * 12288 * 10
    row = 4 * 4096 * 2 + 4 * 4096 + 4 * 32       # q k v o bf16, g, beta
    assert flops.kda_step_bytes(config, 96) == 96 * (2 * 2_097_152 + row)
    assert flops.kda_chunk_bytes(config, 420, 2) == \
        2 * 2 * 2_097_152 + 420 * row
    # the counts read the work, never a chunk size: 420 tokens in one span
    # or in two cost the same operations
    spans = [(1, 3000), (420, 9000)]
    from benchmark import flops_deepseek_v3 as ds
    per_token = (6 * flops.kda_proj_params(config)
                 + flops.mla_proj_params(config) + 3 * 2560 * 6144
                 + 6 * (2560 * 512 + 3 * 2560 * 768))
    attn = sum(ds.attention_flops(config, q, c) for q, c in spans)
    assert flops.serve_flops(config, spans, 5, 1000) == (
        2 * per_token * 421
        + 6 * (2 * 3 * 128 * 128 * 32 * 421 + 2 * 4 * 12288 * 421)
        + attn + 2 * 5_898_240 * 1000 + 2 * 2560 * 39296 * 5)
    # the MLA layer in the cheaper of its two forms: absorbed for a decode
    # row at a long context
    assert ds.attention_flops(config, 1, 3000) == \
        ds.absorbed_attention_flops(config, 1, 3000) == \
        2 * 32 * (2 * 512 + 64) * 3000


def hand_made_run(config):
    """A traced run's records, two ticks: a decode-only one and one with a
    420-token chunk in two spans."""
    from benchmark import kda_spans
    step = [({"rows": 96}, 2e-4)] * 6 + [({"rows": 90}, 2e-4)] * 6
    chunk = [({"spans": 2, "tokens": 420, "padded_tokens": 640,
               "chunks": 28}, 3e-4)] * 6
    events = ([["%kda_step.1 = custom-call tpu_custom_call",
                i * 10_000_000, 1_000_000] for i in range(12)]
              + [["%kda_chunk.1 = custom-call tpu_custom_call",
                  200_000_000 + i * 10_000_000, 4_000_000]
                 for i in range(6)]
              + [["%_latent_qblock_device.2 = custom-call tpu_custom_call",
                  400_000_000 + i * 10_000_000, 2_000_000]
                 for i in range(2)]
              + [["%ragged-dot.3 = custom-call tpu_custom_call",
                  500_000_000 + i * 1_000_000, 500_000] for i in range(36)])
    return {"config": config, "peaks": PEAKS, "chips": 1, "window_s": 2.0,
            "kernel_calls": [(0.0, [1] * 96, [3000] * 96),
                             (0.1, [1] * 90 + [400, 20],
                              [3000] * 90 + [9000, 20])],
            "window": {"delivered": 188},
            "counters": {"moe_expert_tokens": np.full(128, 30),
                         "moe_unheld_tokens": 606,
                         "useful_tokens_total": 606,
                         "kda_chunk_tokens": 420,
                         "kda_chunk_padded_tokens": 640},
            "trace": {"window_s": 2.0, "events": {"d0": events}},
            "pages": [(0.0, 2000, 8192), (0.25, 2100, 8192)]}, {
                kda_spans.STEP: step, kda_spans.CHUNK: chunk}


def test_the_new_readers_on_a_hand_made_run(found, monkeypatch):
    from benchmark import flops as base, kda_spans
    _, config, _ = found
    for name in NEW_READERS:
        assert harness.load_reader(name)({}) is None
        assert harness.load_reader(name)({"config": config}) is None
    run, spans = hand_made_run(config)
    monkeypatch.setattr(kda_spans, "kept", lambda run: spans)
    # each call's least time: the state's bytes bind the one-token kernel
    least = sum(base.roofline_seconds(
        flops.recurrence_flops(config, r), flops.kda_step_bytes(config, r),
        PEAKS)[0] for r in [96] * 6 + [90] * 6)
    assert {b for _, b in [base.roofline_seconds(
        flops.recurrence_flops(config, 96),
        flops.kda_step_bytes(config, 96), PEAKS)]} == {"memory"}
    got = harness.load_reader("kda_step_roofline")(run)
    assert got == pytest.approx(100 * least / 0.012) and 0 < got < 100
    least = 6 * base.roofline_seconds(
        flops.recurrence_flops(config, 420),
        flops.kda_chunk_bytes(config, 420, 2), PEAKS)[0]
    got = harness.load_reader("kda_chunk_roofline")(run)
    assert got == pytest.approx(100 * least / 0.024) and 0 < got < 100
    assert harness.load_reader("linear_attn_device_pct")(run) == \
        pytest.approx(100 * 0.036 / 2.0)
    assert harness.load_reader("kda_chunk_fill_pct")(run) == \
        pytest.approx(100 * 420 / 640)
    total = flops.serve_flops(
        config, [(1, 3000)] * 186 + [(400, 9000), (20, 20)], 188, 128 * 30)
    assert harness.load_reader("serve_mfu_pct.linear_latent")(run) == \
        pytest.approx(100 * total / (2.0 * 197e12))
    # the accepted readers on this cell's records
    assert harness.load_reader("moe_load_max_over_mean.linear")(run) == 1.0
    assert harness.load_reader("moe_unheld_pct.linear")(run) == \
        pytest.approx(100 / 6)
    assert harness.load_reader("moe_device_pct.linear")(run) == \
        pytest.approx(100 * 36 * 0.0005 / 2.0)
    assert 0 < harness.load_reader("latent_attn_roofline.linear")(run) < 100
    assert harness.load_reader("kv_pages_peak_pct")(run) == \
        pytest.approx(100 * 2100 / 8192)
    # a program without the spans (the parent) reads as nothing
    monkeypatch.setattr(kda_spans, "kept", lambda run: None)
    assert harness.load_reader("kda_step_roofline")(run) is None
    assert harness.load_reader("kda_chunk_roofline")(run) is None


def test_the_kernels_spans_are_read_from_the_programs_tracer():
    """``kda_spans.kept`` on the program's own tracer: the spans inside the
    window's stamps, by name, with their args."""
    from benchmark import kda_spans
    from paddle_tpu.profiler import get_tracer
    from paddle_tpu.profiler import spans
    import time
    tracer = get_tracer()
    tracer.enable()
    try:
        t0 = time.perf_counter()
        spans.latch()
        with spans.span("attn/kda_step", rows=7):
            pass
        with spans.span("attn/kda_chunk", spans=1, tokens=20,
                        padded_tokens=32, chunks=2):
            pass
        t1 = time.perf_counter()
    finally:
        tracer.disable()
        spans.latch()
    got = kda_spans.kept({"kernel_calls": [(t0,), (t1,)]})
    assert [a for a, _ in got[kda_spans.STEP]][-1] == {"rows": 7}
    assert got[kda_spans.CHUNK][-1][0]["tokens"] == 20
    assert kda_spans.kept({}) is None


def test_seeded_leaves_by_group_equal_the_whole_table():
    cfg = dict(vocab_size=64, hidden_size=16, intermediate_size=24,
               moe_intermediate_size=8, num_attention_heads=2, head_dim=8,
               kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
               v_head_dim=8, num_experts=8, num_shared_experts=1,
               moe_shared_expert_intermediate_size=8,
               first_k_dense_replace=1, short_conv_kernel_size=4,
               layer_kinds=["kda", "kda", "mla"], initializer_range=0.02,
               router_bias_std=0.005, held_experts=[2, 4])
    table = weights.leaf_table(cfg)
    whole = weights.make_weights(cfg, 2**31 + 5, "float32")
    assert len(table) == len(whole) == 1 + 16 + 21 + 16 + 2
    by_name = {n: a for (n, _, _), a in zip(table, whole)}
    for prefix in ("model.embed_tokens.", weights.layer_prefix(1),
                   weights.layer_prefix(2), "lm_head."):
        for short, a in weights.make_group(cfg, 2**31 + 5, prefix,
                                           "float32").items():
            assert np.array_equal(np.asarray(a),
                                  np.asarray(by_name[prefix + short]))
    assert by_name["model.layers.1.experts.w_gate"].shape == (4, 16, 8)
    assert by_name["model.layers.1.experts.router"].shape == (16, 8)
    # the decay gate's draws: spread, pinned to neither end
    a_log = np.asarray(by_name["model.layers.0.linear_attn.A_log"])
    dt = np.asarray(by_name["model.layers.0.linear_attn.dt_bias"])
    assert (np.log(0.5) <= a_log).all() and (a_log <= np.log(2)).all()
    assert (-8 <= dt).all() and (dt <= 1).all() and dt.std() > 1.5
    conv = np.asarray(by_name["model.layers.0.linear_attn.conv_weight"])
    assert conv.shape == (4, 48) and 0.3 < conv.std() < 0.7


def test_the_sample_holds_a_document_or_nothing():
    from benchmark.drivers.serve_linear_latent import Served, sample_watched

    def rec(client, index, prompt, new, done=True):
        return Served(client, index, np.zeros(prompt, np.int64),
                      np.zeros(new, np.int64), done)

    kinds = ["long", "long", "short", "short", "short"]
    watch = {"long_clients": [0, 1], "short_clients": [2, 3]}
    served = [rec(0, 0, 9000, 60), rec(0, 1, 30000, 640),
              rec(1, 0, 12000, 100), rec(2, 0, 900, 100),
              rec(2, 1, 1200, 500, done=False), rec(3, 1, 700, 300, False),
              rec(4, 1, 500, 600)]
    got = sample_watched(served, kinds, watch, 8192, 4)
    # a second document before a first; then second requests first (in
    # flight or not), and no request of a client that is not watched
    assert got[0] is served[1] and len(got) == 4
    assert [(r.client, r.index) for r in got[1:]] == [(3, 1), (2, 1), (2, 0)]
    assert sample_watched(served[2:], kinds, watch, 8192, 4)[0] is served[2]
    assert sample_watched(served[3:], kinds, watch, 8192, 4) == []
    assert sample_watched(served, kinds, watch, 40000, 4) == []


def test_a_watched_request_counts_from_enough_rows_on():
    """``watched_served``: a request in flight at the cut is taken as far
    as the tap read it, a finished one's rows have to be its client's
    tokens, and one with too few rows is left out."""
    from benchmark.drivers.serve import Record
    from benchmark.drivers.serve_linear_latent import watched_served

    class Tap:
        prompts = {(0, 0): np.arange(5), (0, 1): np.arange(7),
                   (1, 0): np.arange(9)}
        read = {(0, 0): [3, 4, 5], (0, 1): [6, 7], (1, 0): [1]}

        def served(self, key):
            return np.asarray(self.read[key], np.int64)

    def rec(client, index, new, output):
        r = Record(client, index, index == 0, Tap.prompts[client, index], new)
        r.output = None if output is None else np.asarray(output)
        return r

    records = [rec(0, 0, 3, [3, 4, 5]), rec(0, 1, 9, None),
               rec(1, 0, 4, None)]
    got = watched_served(Tap(), records, 2)
    assert [(r.client, r.index, r.finished, r.output.tolist())
            for r in got] == [(0, 0, True, [3, 4, 5]), (0, 1, False, [6, 7])]
    records[0].output = np.asarray([3, 4, 6])
    with pytest.raises(RuntimeError, match="not the tokens"):
        watched_served(Tap(), records, 2)


def test_the_tap_reads_the_watched_requests_rows():
    """``LogitTap`` on a stand-in model: a request is known by its prompt,
    read are its prefill's last row and its decode rows, a slot's next
    request is told apart, an unwatched request is left alone."""
    import jax.numpy as jnp
    from benchmark.drivers.serve_linear_latent import LogitTap

    class Ids:
        def __init__(self, a):
            self._data = jnp.asarray(np.asarray(a)[None])

    class Out:
        def __init__(self, t):
            # row r of a tick's logits holds r everywhere
            self._data = jnp.broadcast_to(
                jnp.arange(t, dtype=jnp.float32)[None, :, None], (1, t, 5))

    class Cache:
        ragged_armed = True

        def ragged_spans(self):
            return self.spans

    class Model:
        def forward(self, ids, cache=None):
            return Out(ids._data.shape[1])

    model, cache = Model(), Cache()
    mine, other = np.arange(10, 30), np.arange(50, 60)
    tap = LogitTap(model, [[(mine, 3)], [(other, 2)]], [0], cap=2)
    tap.install()
    for spans, flat in (
            ([(1, 0, 12, 0), (0, 12, 4, 0)], list(mine[:12]) + list(other[:4])),
            ([(1, 0, 8, 12), (0, 8, 6, 4)], list(mine[12:]) + list(other[4:])),
            ([(0, 0, 1, 10), (1, 1, 1, 20)], [7, 8]),
            ([(1, 0, 1, 21)], [9]),                  # past the cap
            ([(1, 0, 10, 0)], list(other))):         # the slot's next request
        cache.spans = spans
        model.forward(Ids(flat), cache=cache)
    tap.remove()
    # row r of a tick's logits holds r everywhere: the greedy choice is 0
    assert tap.served((0, 0)).tolist() == [0, 0]         # the cap is 2
    got = tap.logits((0, 0), 2)
    assert got[0].tolist() == [7.0] * 5          # the last prefill row
    assert got[1].tolist() == [1.0] * 5          # its decode row
    assert tap.slot_of == {} and "forward" not in vars(model)


def test_the_taps_rows_stand_in_one_buffer_made_before_the_window():
    """The tap's host buffer: made and written by ``warm`` in the logits'
    own type (bfloat16 on the chip), filled row by row, read back as
    float32; a row past its end is not read, so the request counts as far
    as the rows go without a hole."""
    import jax.numpy as jnp
    from benchmark.drivers.serve_linear_latent import TAP_ROWS, LogitTap

    class Model:
        pass

    tap = LogitTap(Model(), [[(np.arange(4), 9)]], [0], cap=9, pool_rows=3)
    tap.warm([8], 5, jnp.bfloat16)
    pool = tap.pool
    assert pool.shape == (3, 5) and pool.dtype == jnp.bfloat16
    assert tap.used == 0 and not pool.any()
    for served in range(4):
        rows = jnp.full((TAP_ROWS, 5), served, jnp.bfloat16).at[0, 2].set(9)
        tap.pending.append(([((0, 0), served, 0)], rows))
    tap.drain(0)
    assert tap.pool is pool and tap.used == 3         # the fourth is left
    assert tap.served((0, 0)).tolist() == [2, 2, 2]
    got = tap.logits((0, 0), 3)
    assert got.dtype == np.float32 and got[:, 0].tolist() == [0.0, 1.0, 2.0]


def test_what_stopped_the_engines_thread_is_in_the_log():
    """``Stalls``: full collections are timed through ``gc.callbacks``, the
    watcher times ticks from ``ragged_steps`` and says where the engine's
    thread stood when one took ``SLOW_S`` or more."""
    import gc
    import threading
    from benchmark.drivers.serve_linear_latent import Stalls

    class Engine:
        ragged_steps = 0
        _thread = threading.current_thread()

    engine = Engine()
    stalls = Stalls(engine)
    stalls.SLOW_S = 0.2
    t0 = time.perf_counter()
    stalls.start()
    try:
        gc.collect()                                  # a full collection
        for _ in range(3):
            time.sleep(0.06)
            engine.ragged_steps += 1
        time.sleep(0.45)                              # a tick that hangs
        engine.ragged_steps += 1
        time.sleep(0.12)
    finally:
        stalls.stop()
    assert stalls.collected not in gc.callbacks
    line = stalls.between(t0, time.perf_counter())
    assert "1 full collections" in line, line
    assert "the engine's thread at" in line and "sleep" not in line.split(
        "thread at")[0], line
    longest = float(line.split("longest tick ")[1].split(" ms")[0])
    assert 400 <= longest <= 700, line
    assert stalls.between(t0 - 2, t0 - 1).startswith("longest tick 0 ms")


def tiny(config, traffic):
    """The cell at a size the CPU serves."""
    config = dict(
        config, vocab_size=128, hidden_size=64, intermediate_size=160,
        moe_intermediate_size=32, num_attention_heads=4, head_dim=16,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, num_experts=16, held_experts=[0, 8], n_group=4,
        topk_group=2, num_experts_per_tok=4,
        moe_shared_expert_intermediate_size=32, rope_theta=10000.0,
        max_position_embeddings=256, engine_dtype="float32",
        engine=dict(max_batch_size=6, max_len=256, page_size=8,
                    num_pages=200, token_budget=32, prefill_chunk_tokens=32))
    traffic = dict(
        traffic, clients=6, reference_width=[64, 128, 256], crossed_chunks=2,
        watch=dict(long_clients=[0, 1], short_clients=[2, 3],
                   rows_a_request=12, min_rows=3, pool_rows=512),
        long=dict(clients=2, docs_per_client=4,
                  doc_len=dict(median=96, sigma=0.3, min=72, max=160, grid=8),
                  question_len=dict(median=4, sigma=0.3, min=3, max=6),
                  answer_len=dict(median=6, sigma=0.3, min=4, max=8)),
        short=dict(clients=4, requests_per_client=20,
                   prompt_len=dict(median=12, sigma=0.5, min=6, max=24),
                   answer_len=dict(median=10, sigma=0.3, min=6, max=16)),
        ramp=dict(phases=2))
    return config, traffic


LIMITS = {"sample_requests": 4, "router_margin_min": 1e-3,
          "decided_logit_gap_max": 1e-4, "served_logit_gap_mean": 1e-5,
          "decided_logit_rms_median": 1e-5}


def small_run(found, seconds, control=None):
    from benchmark.drivers import serve_linear_latent as drv
    entry, config, traffic = found
    config, traffic = tiny(config, traffic)
    ctx = {"cell": entry, "config": config, "traffic": traffic,
           "limits": LIMITS, "seed": 2**31 + 77, "seconds": seconds,
           "trace": False, "chips": 1, "watch": harness.CompileWatch(),
           "control": control}
    return drv.run(ctx), config


def test_the_driver_end_to_end_at_a_small_size(found):
    """The new driver through its functions (plain-XLA kernels, float32,
    the CPU): every request answered, a document whose prefill crossed the
    chunks and reasoning requests in the sample, the program's own logits
    read from the timed ticks at DECODE positions too and the reference's
    own to rounding, the state's counters there, the int8 control and an
    altered token told apart."""
    from benchmark.drivers import serve_linear_latent as drv
    run, config = small_run(found, 15.0, control="int8")
    assert run["failed"] == 0 and run["finished"] >= 4
    assert harness.judge(run["checks"]), run["checks"]
    assert [name for name, _, _ in run["checks"]] == list(drv.CHECKS)
    c = run["counters"]
    assert c["compiled_layer_calls"] == 7 * c["ragged_steps"] > 0
    assert c["moe_expert_tokens"].shape == (8,)
    assert c["prompt_tokens_cached"] == 0        # the prefix cache is off
    assert c["kda_steps"] == c["ragged_steps"]
    assert c["kda_step_rows"] > 0 and c["state_resets"] > 0
    assert 0 < c["kda_chunk_tokens"] < c["kda_chunk_padded_tokens"]
    gaps = run["gaps"]
    n = len(gaps["served"])
    assert n == len(gaps["margin"]) == len(gaps["altered"]) == len(
        gaps["int8"]) == len(gaps["rms"]) == len(gaps["rms_int8"]) > 0
    # every served position of the sample was read, decode rows among them
    assert not np.isnan(gaps["rms"]).any() and n > 4
    assert not harness.judge(run["stand_ins"]["altered_token"])
    assert not harness.judge(run["stand_ins"]["control_int8"])
    run["config"] = config
    assert 0 < harness.load_reader("kv_pages_peak_pct")(run) <= 100
    assert harness.load_reader("moe_load_max_over_mean.linear")(run) >= 1
    assert 0 < harness.load_reader("moe_unheld_pct.linear")(run) < 100
    assert 0 < harness.load_reader("kda_chunk_fill_pct")(run) < 100
    assert len(run["window"]["ttft"]) > 0 and max(run["state_slots_live"]) > 1
    # untraced: what reads a trace or the program's spans finds nothing
    for name in ("kda_step_roofline", "kda_chunk_roofline", "state_host_ms",
                 "linear_attn_device_pct", "serve_mfu_pct.linear_latent"):
        assert harness.load_reader(name)(run) is None


@pytest.mark.parametrize("fault", ["state_not_reset", "chunk_state_dropped"])
def test_each_fault_of_the_mechanism_comes_out_not_correct(found, fault):
    from benchmark import calibrate_state
    undo = calibrate_state.FAULTS[fault]()
    try:
        run, _ = small_run(found, 8.0)
    finally:
        undo()
    assert run["failed"] == 0
    assert not harness.judge(run["checks"]), run["checks"]
    rec = {"cell": CELL, "seed": 1, "fault": fault, "gaps": run["gaps"],
           "numbers": {"program": {n: v for n, v, _ in run["checks"]}}}
    from benchmark.drivers import serve_linear_latent as drv
    (row,) = calibrate_state.rows_of(rec, drv, LIMITS)
    assert row["who"] == "fault_" + fault
    assert row["expected"] is False and row["correct"] is False


def test_the_recorded_readings_judged_again_under_the_committed_limits():
    """The chip's recorded lines (one a seed: the reference's per-token
    readings and the program's own logits' distances) judged again under
    the limits as they are committed: the program correct, the int8
    control, an altered token and each fault not."""
    from benchmark import calibrate_state
    path = os.path.join(REPO, "benchmark", "limits",
                        CELL + ".readings.jsonl")
    limits = harness.load_json(os.path.join(REPO, "benchmark", "limits",
                                            CELL + ".json"))
    driver = harness.load_driver("serve_linear_latent")
    whos = set()
    with open(path) as f:
        for line in f:
            for row in calibrate_state.rows_of(json.loads(line), driver,
                                               limits):
                whos.add(row["who"])
                assert row["correct"] == row["expected"], row
    assert whos >= {"program", "control_int8", "altered_token",
                    "fault_state_not_reset", "fault_chunk_state_dropped"}


def test_the_limits_file_states_its_readings():
    limits = harness.load_json(os.path.join(REPO, "benchmark", "limits",
                                            CELL + ".json"))
    from benchmark.drivers.serve_linear_latent import CHECKS
    for name in CHECKS + ("router_margin_min", "sample_requests"):
        assert isinstance(limits[name], (int, float)), name
    text = limits["readings"]
    for word in ("state_not_reset", "chunk_state_dropped", "int8",
                 "altered", "decode", "PR 33"):
        assert word in text, word

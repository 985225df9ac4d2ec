"""Self-audit: SURVEY.md §2 component inventory → paddle_tpu modules.

Run: python tools/check_inventory.py
Prints one line per inventory item with the implementing module(s) and
whether every listed symbol resolves. Used by CI (tests/test_inventory.py)
to keep the map honest as the build grows.
"""
from __future__ import annotations

import importlib
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# (SURVEY §2 item, module path, symbols that must resolve)
INVENTORY = [
    ("Phi kernels / op layer", "paddle_tpu.ops",
     ["add", "matmul", "einsum", "topk", "cumsum"]),
    ("Flash attention (FA2 kernels)", "paddle_tpu.ops.pallas",
     ["flash_attention", "flash_attention_with_lse", "mha_reference"]),
    ("Ring attention / CP", "paddle_tpu.ops.pallas",
     ["ring_flash_attention"]),
    ("Int8 GEMM (quant inference)", "paddle_tpu.ops.pallas",
     ["int8_matmul", "quantize_weight"]),
    ("Fused ops (phi fusion tier)", "paddle_tpu.incubate.nn.functional",
     ["fused_rotary_position_embedding", "fused_rms_norm", "swiglu"]),
    ("Eager autograd engine", "paddle_tpu.autograd.tape",
     ["apply", "run_backward", "no_grad"]),
    ("PyLayer (custom op autograd)", "paddle_tpu.autograd.pylayer",
     ["PyLayer"]),
    ("to_static / SOT tracer", "paddle_tpu.jit",
     ["to_static", "save", "load", "InputSpec"]),
    ("Static Program/Executor", "paddle_tpu.static",
     ["Program", "Executor", "BuildStrategy", "program_guard"]),
    ("Inference predictor", "paddle_tpu.inference",
     ["Config", "create_predictor"]),
    ("nn layers", "paddle_tpu.nn",
     ["Linear", "Conv2D", "LayerNorm", "BatchNorm2D", "MultiHeadAttention",
      "TransformerEncoder", "LSTM", "Embedding"]),
    ("Optimizers", "paddle_tpu.optimizer",
     ["SGD", "Momentum", "Adam", "AdamW", "Lamb", "Adagrad", "RMSProp",
      "Adadelta"]),
    ("LR schedulers", "paddle_tpu.optimizer.lr",
     ["NoamDecay", "LinearWarmup", "CosineAnnealingDecay", "OneCycleLR",
      "ReduceOnPlateau"]),
    ("AMP", "paddle_tpu.amp",
     ["auto_cast", "GradScaler", "decorate"]),
    ("AMP debugging / nan checker", "paddle_tpu.amp.debugging",
     ["check_numerics", "enable_tensor_checker", "TensorCheckerConfig"]),
    ("DataLoader / io", "paddle_tpu.io",
     ["Dataset", "IterableDataset", "DataLoader", "BatchSampler",
      "DistributedBatchSampler", "WeightedRandomSampler"]),
    ("Native shm queue (C++)", "paddle_tpu.io.native",
     ["ShmQueue", "available"]),
    ("Profiler", "paddle_tpu.profiler",
     ["Profiler", "make_scheduler", "RecordEvent", "export_chrome_tracing"]),
    ("Checkpoint save/load", "paddle_tpu.framework.io",
     ["save", "load"]),
    ("Distributed checkpoint", "paddle_tpu.distributed.checkpoint",
     ["save_state_dict", "load_state_dict", "save_group_sharded_model"]),
    ("Collectives API", "paddle_tpu.distributed",
     ["all_reduce", "all_gather", "reduce_scatter", "alltoall", "send",
      "recv", "new_group", "batch_isend_irecv"]),
    ("Mesh / topology", "paddle_tpu.distributed.mesh",
     ["init_mesh", "get_mesh", "HYBRID_AXES"]),
    ("HybridCommunicateGroup", "paddle_tpu.distributed.fleet",
     ["HybridCommunicateGroup", "CommunicateTopology"]),
    ("Fleet facade", "paddle_tpu.distributed.fleet",
     ["init", "distributed_model", "distributed_optimizer",
      "DistributedStrategy"]),
    ("TP/MP layers", "paddle_tpu.distributed.fleet.meta_parallel",
     ["ColumnParallelLinear", "RowParallelLinear", "VocabParallelEmbedding",
      "ParallelCrossEntropy", "get_rng_state_tracker"]),
    ("Pipeline (1F1B + layers)", "paddle_tpu.distributed.fleet.meta_parallel",
     ["PipelineLayer", "LayerDesc", "SharedLayerDesc", "PipelineParallel"]),
    ("SPMD pipeline engine (+VPP)", "paddle_tpu.distributed.engine",
     ["pipeline_forward", "pipeline_spmd", "pipeline_spmd_interleaved"]),
    ("Sharding stages 1-3", "paddle_tpu.distributed.sharding",
     ["group_sharded_parallel", "save_group_sharded_model"]),
    ("Sequence parallel utils",
     "paddle_tpu.distributed.fleet.utils.sequence_parallel_utils",
     ["ScatterOp", "GatherOp", "AllGatherOp", "ReduceScatterOp",
      "mark_as_sequence_parallel_parameter"]),
    ("Ring attention facade", "paddle_tpu.distributed.fleet.utils",
     ["ring_attention", "RingFlashAttention"]),
    ("Recompute", "paddle_tpu.distributed.fleet.utils", ["recompute"]),
    ("MoE / EP", "paddle_tpu.incubate.distributed.models.moe",
     ["MoELayer", "GShardGate", "SwitchGate", "NaiveGate",
      "dispatch_combine"]),
    ("Sparse-MoE LM family (Mixtral)", "paddle_tpu.models",
     ["MixtralConfig", "MixtralForCausalLM", "MixtralSparseMoeBlock",
      "mixtral_8x7b", "mixtral_tiny"]),
    ("Auto-parallel API", "paddle_tpu.distributed.auto_parallel",
     ["ProcessMesh", "Shard", "Replicate", "Partial", "shard_tensor",
      "reshard", "shard_optimizer", "Engine"]),
    ("Distributed passes", "paddle_tpu.distributed.passes",
     ["new_pass", "PassManager", "register_pass"]),
    ("Pipeline schedules (FThenB/1F1B/VPP/ZBH1)",
     "paddle_tpu.distributed.engine",
     ["pipeline_forward", "pipeline_spmd_1f1b_bwd", "pipeline_spmd_zb_bwd",
      "pipeline_spmd_interleaved", "pipeline_forward_hetero"]),
    ("DGC / LocalSGD meta-optimizers",
     "paddle_tpu.distributed.fleet.meta_optimizers",
     ["DGCMomentumOptimizer", "LocalSGDOptimizer"]),
    ("Launch CLI", "paddle_tpu.distributed.launch", ["launch_main"]),
    ("Elastic", "paddle_tpu.distributed.fleet.elastic",
     ["ElasticManager", "TrainingSupervisor", "CheckpointManager"]),
    ("Flags system", "paddle_tpu.flags",
     ["set_flags", "get_flags"]),
    ("Sparse tensors", "paddle_tpu.sparse",
     ["sparse_coo_tensor", "sparse_csr_tensor", "matmul", "masked_matmul"]),
    ("Quantization", "paddle_tpu.quantization",
     ["QuantConfig", "QAT", "PTQ", "convert"]),
    ("ASP 2:4 sparsity", "paddle_tpu.incubate.asp",
     ["prune_model", "decorate", "calculate_density"]),
    ("Higher-order AD", "paddle_tpu.incubate.autograd",
     ["jvp", "vjp", "Jacobian", "Hessian"]),
    ("hapi Model", "paddle_tpu.hapi", ["Model", "summary"]),
    ("Callbacks", "paddle_tpu.callbacks",
     ["ModelCheckpoint", "EarlyStopping", "LRScheduler"]),
    ("Metrics", "paddle_tpu.metric",
     ["Accuracy", "Precision", "Recall", "Auc"]),
    ("Vision models", "paddle_tpu.vision.models",
     ["resnet50", "vgg16", "mobilenet_v2", "LeNet"]),
    ("Vision ops (detection)", "paddle_tpu.vision.ops",
     ["nms", "roi_align", "box_iou", "distance2bbox", "yolo_box"]),
    ("Detection model (PP-YOLOE)", "paddle_tpu.models",
     ["PPYOLOE", "DetectionLoss", "ppyoloe_lite"]),
    ("LM zoo", "paddle_tpu.models",
     ["LlamaForCausalLM", "GPTForCausalLM", "BertModel", "ErnieModel"]),
    ("Generation", "paddle_tpu.models.generation",
     ["GenerationMixin", "KVCache"]),
    ("fft", "paddle_tpu.fft", ["fft", "rfft", "irfft", "fft2", "fftshift"]),
    ("signal", "paddle_tpu.signal", ["stft", "istft", "frame"]),
    ("text", "paddle_tpu.text", ["ViterbiDecoder", "viterbi_decode"]),
    ("audio", "paddle_tpu.audio",
     ["MelSpectrogram", "LogMelSpectrogram", "MFCC"]),
    ("Device API", "paddle_tpu.device",
     ["set_device", "synchronize", "Stream", "Event", "cuda"]),
    ("Profiler benchmark timer", "paddle_tpu.profiler", ["benchmark"]),
    ("utils", "paddle_tpu.utils",
     ["run_check", "get_weights_path_from_url", "try_import"]),
    ("Paged attention (serving KV)", "paddle_tpu.ops.pallas.paged_attention",
     ["paged_attention", "paged_attention_reference"]),
    ("Serving engine (batched decode)", "paddle_tpu.inference.serving",
     ["ServingEngine"]),
    ("FusedMultiTransformer (serving block)", "paddle_tpu.incubate.nn",
     ["FusedMultiTransformer"]),
    ("TCPStore rendezvous (C++)", "paddle_tpu.distributed.native",
     ["TCPStore", "available"]),
    ("paddle.distribution", "paddle_tpu.distribution",
     ["Normal", "Gamma", "Dirichlet", "MultivariateNormal",
      "TransformedDistribution", "kl_divergence", "register_kl"]),
    ("Pretrained weights (zoo cache + HF interop)", "paddle_tpu.models.pretrained",
     ["load_llama_from_hf", "load_gpt_from_hf", "llama_config_from_hf"]),
    ("nn breadth batch 2 (unpool/3d/losses)", "paddle_tpu.nn",
     ["MaxUnPool2D", "Conv3DTranspose", "HSigmoidLoss", "Fold",
      "PixelUnshuffle", "TripletMarginWithDistanceLoss"]),
    ("paddle.geometric (GNN ops)", "paddle_tpu.geometric",
     ["segment_sum", "send_u_recv", "send_ue_recv", "send_uv"]),
    ("Optimizer breadth (LBFGS tier)", "paddle_tpu.optimizer",
     ["LBFGS", "RAdam", "NAdam", "Rprop", "ASGD"]),
    ("Vision zoo batch 2", "paddle_tpu.vision.models",
     ["AlexNet", "SqueezeNet", "MobileNetV3Small", "ShuffleNetV2",
      "DenseNet", "wide_resnet50_2", "GoogLeNet", "InceptionV3"]),
    ("Compat namespaces", "paddle_tpu",
     ["iinfo", "finfo", "is_tensor", "create_parameter", "flops",
      "LazyGuard"]),
    ("Fused functional shims", "paddle_tpu.incubate.nn.functional",
     ["fused_linear", "fused_dropout_add",
      "fused_bias_dropout_residual_layer_norm"]),
    ("Text datasets (cache-gated)", "paddle_tpu.text",
     ["UCIHousing", "Imdb", "Imikolov"]),
    # -- round 3 additions ---------------------------------------------------
    ("Ulysses all-to-all context parallel", "paddle_tpu.distributed.fleet.utils",
     ["ulysses_attention", "UlyssesAttention"]),
    ("Continuous-batching serving", "paddle_tpu.inference",
     ["ContinuousServingEngine"]),
    ("Slot-paged KV cache", "paddle_tpu.models.generation",
     ["SlotPagedKVCache"]),
    ("Donation/aliasing sanitizers", "paddle_tpu.utils.donation",
     ["donated_jit", "assert_no_aliases"]),
    ("Device memory runtime", "paddle_tpu.device.memory",
     ["memory_stats", "live_tensor_report", "memory_summary"]),
    ("Auto-search mesh tuner wiring", "paddle_tpu.distributed.fleet",
     ["_apply_auto_search"]),
    ("Auto-parallel Engine (fit/eval/cost)", "paddle_tpu.distributed.auto_parallel",
     ["Engine"]),
    ("Static inference IO (save/load_inference_model)", "paddle_tpu.static",
     ["save_inference_model", "load_inference_model"]),
    ("GPT pipeline model", "paddle_tpu.models",
     ["GPTForCausalLMPipe"]),
    ("T5 encoder-decoder family", "paddle_tpu.models",
     ["T5ForConditionalGeneration", "T5Config", "t5_tiny"]),
    ("ViT family", "paddle_tpu.vision.models",
     ["VisionTransformer", "vit_base_patch16_224"]),
    ("Sparse op breadth", "paddle_tpu.sparse",
     ["tanh", "transpose", "coalesce", "mask_as", "addmm"]),
    ("Parameter-server mode (ps tables/RPC)", "paddle_tpu.distributed.ps",
     ["SparseTable", "PSServer", "PSClient", "DistributedEmbedding"]),
    ("PIR pass infra (StableHLO rewriter)", "paddle_tpu.static.pir",
     ["ProgramIR", "Pass", "PassRegistry", "PatternRewritePass",
      "MLIRPipelinePass", "optimize_exported"]),
    ("Auto-parallel completion (dist-attr)", "paddle_tpu.distributed.auto_parallel",
     ["Completer", "completion"]),
    ("dy2static control-flow conversion", "paddle_tpu.jit.dy2static",
     ["convert_function", "ConversionUnsupported"]),
    ("1F1B/SPMD pipeline engine", "paddle_tpu.distributed.engine",
     ["pipeline_spmd", "pipeline_spmd_1f1b_bwd", "pipeline_spmd_interleaved",
      "PipelinedModule"]),
    ("Generation (beam search, paged KV)", "paddle_tpu.models.generation",
     ["GenerationMixin", "KVCache", "PagedKVCache", "SlotPagedKVCache"]),
    ("Detection op surface", "paddle_tpu.vision.ops",
     ["matrix_nms", "roi_pool", "roi_align", "deform_conv2d", "nms"]),
    ("Hermitian FFT family", "paddle_tpu.fft",
     ["hfft2", "ihfft2", "hfftn", "ihfftn"]),
    # -- gradient communication layer (EQuARX-style) -------------------------
    ("Bucketed/quantized gradient comm", "paddle_tpu.distributed.comm",
     ["GradientBucketer", "CommStats", "get_comm_stats", "reset_comm_stats",
      "all_reduce_quantized", "reduce_scatter_quantized",
      "quantize_blockwise", "dequantize_blockwise",
      "comm_config_from_strategy"]),
    ("Comm stats via profiler", "paddle_tpu.profiler", ["comm_stats"]),
    # -- unified runtime telemetry (ISSUE 2) ---------------------------------
    ("Telemetry registry + span tracer", "paddle_tpu.profiler.telemetry",
     ["MetricRegistry", "Counter", "Gauge", "Histogram", "SpanTracer",
      "get_registry", "get_tracer", "metrics", "metrics_text",
      "enable_op_telemetry", "disable_op_telemetry"]),
    ("Telemetry facade via profiler", "paddle_tpu.profiler",
     ["metrics", "metrics_text", "get_registry", "get_tracer"]),
    ("Training telemetry callback", "paddle_tpu.callbacks",
     ["TelemetryCallback"]),
    # -- distributed flight recorder (ISSUE 3) -------------------------------
    ("Flight recorder (hang/straggler diagnosis)",
     "paddle_tpu.profiler.flight_recorder",
     ["FlightRecorder", "Watchdog", "get_flight_recorder", "enable",
      "disable", "is_enabled", "record_event", "heartbeat",
      "collective_begin", "collective_end", "register_state_provider",
      "desync_report", "straggler_report", "merge_chrome_traces",
      "merge_rank_snapshots", "publish_snapshot", "gather_metrics"]),
    ("Flight recorder facade via profiler", "paddle_tpu.profiler",
     ["get_flight_recorder", "gather_metrics", "merge_chrome_traces",
      "straggler_report", "desync_report"]),
    ("Elastic KV aggregation stores",
     "paddle_tpu.distributed.fleet.elastic.tcp_kv",
     ["TcpKVStore", "MemKVStore"]),
    # -- serving fast path (ISSUE 4) -----------------------------------------
    ("Prefix-cached shared KV page pool", "paddle_tpu.models.generation",
     ["SlotPagedKVCache", "block_hash_chain"]),
    ("Chunked-prefill continuous scheduler", "paddle_tpu.inference.serving",
     ["ContinuousServingEngine", "DEFAULT_PREFILL_CHUNK_TOKENS"]),
    ("Serving bench (prefix cache on/off)", "bench",
     ["bench_serving", "bench_llama_decode"]),
    # -- overlapped backward + fused step (ISSUE 5) --------------------------
    ("Ready-bucket comm overlap", "paddle_tpu.distributed.comm",
     ["ReadyBucketScheduler", "GradientBucketer"]),
    ("Grad-ready tape hooks", "paddle_tpu.autograd.tape",
     ["register_grad_ready_callback", "unregister_grad_ready_callback"]),
    ("Fused donated optimizer step", "paddle_tpu.optimizer.fused",
     ["FusedStepEngine", "opt_telemetry"]),
    ("Persistent jit compilation cache", "paddle_tpu.jit.api",
     ["enable_persistent_cache"]),
    # -- elastic fault tolerance (ISSUE 6) -----------------------------------
    ("Fault injection harness", "paddle_tpu.distributed.fault",
     ["Fault", "FaultPlan", "install", "clear", "active_plan", "check_step",
      "SimulatedRankKill", "RankFailure", "elastic_telemetry"]),
    ("Structured failure detection (simulator)",
     "paddle_tpu.distributed.simulator",
     ["RankFailure", "SimulatedRankKill", "reset_seqs"]),
    ("Elastic shrink/regrow train loop",
     "paddle_tpu.distributed.fleet.elastic",
     ["ElasticTrainLoop", "ElasticWorld", "WorldChanged", "RankFailure",
      "TrainingSupervisor", "CheckpointManager"]),
    ("Async/sharded checkpoint manager",
     "paddle_tpu.distributed.fleet.elastic.supervisor",
     ["CheckpointManager", "ElasticTrainLoop", "ElasticWorld"]),
    # -- ragged paged attention + token-budget scheduler (ISSUE 7) -----------
    ("Ragged paged attention (mixed prefill+decode kernel)",
     "paddle_tpu.ops.pallas.ragged_paged_attention",
     ["ragged_paged_attention", "ragged_paged_attention_reference"]),
    ("Token-budget continuous batching",
     "paddle_tpu.inference.serving",
     ["ContinuousServingEngine", "DEFAULT_SERVING_TOKEN_BUDGET"]),
    ("Ragged cache step (slot-paged pool)",
     "paddle_tpu.models.generation",
     ["SlotPagedKVCache"]),
    # -- serving fleet (ISSUE 8) ---------------------------------------------
    ("Serving fleet router (affinity/disagg/quotas/health)",
     "paddle_tpu.inference.fleet",
     ["ServingRouter", "Replica", "Rejected", "TenantQuotaManager",
      "ROUTER_POLICIES", "DEFAULT_FLEET_AFFINITY"]),
    ("Fleet KV atomic counters + component-state publish",
     "paddle_tpu.distributed.fleet.elastic.tcp_kv",
     ["MemKVStore", "TcpKVStore"]),
    ("Fleet heartbeat publish path (flight recorder)",
     "paddle_tpu.profiler.flight_recorder",
     ["publish_component_state", "gather_component_states"]),
    # -- per-request tracing + SLO monitor (ISSUE 9) -------------------------
    ("Per-request trace store + SLO monitor",
     "paddle_tpu.profiler.request_trace",
     ["TraceContext", "RequestTraceStore", "SLOMonitor", "start_request",
      "add_span", "add_event", "note_token", "finish_request",
      "request_timeline", "recent_timelines", "timeline_to_chrome",
      "get_slo_monitor", "reset_slo_monitor", "slo_report", "cost_table"]),
    ("Request-trace facade via profiler", "paddle_tpu.profiler",
     ["request_timeline", "slo_report", "cost_table", "get_slo_monitor",
      "timeline_to_chrome", "get_trace_store"]),
    ("Request-flow chrome merge (flow events)",
     "paddle_tpu.profiler.flight_recorder",
     ["merge_chrome_traces"]),
    # -- speculative decoding + int8 KV pages (ISSUE 10) ---------------------
    ("Speculative decoding (drafter tiers + verify path)",
     "paddle_tpu.inference.speculative",
     ["NGramDrafter", "DraftModelDrafter", "make_drafter",
      "DEFAULT_SPEC_K"]),
    ("Slot-paged KV rollback + int8 page codec",
     "paddle_tpu.models.generation",
     ["SlotPagedKVCache", "quantize_kv_rows", "dequantize_kv_rows",
      "kv_page_nbytes"]),
    ("Quantized paged-attention gather tiers",
     "paddle_tpu.ops.pallas.ragged_paged_attention",
     ["ragged_paged_attention"]),
    # -- fleet load observatory (ISSUE 11) -----------------------------------
    ("Metric time-series history (sampler + queries)",
     "paddle_tpu.profiler.timeseries",
     ["MetricsHistory", "get_history", "history", "history_tick",
      "HISTORY_SCHEMA"]),
    ("Alert rules + SLO burn-rate engine",
     "paddle_tpu.profiler.alerts",
     ["AlertEngine", "AlertRule", "ThresholdRule", "BurnRateRule",
      "parse_rules", "get_alert_engine", "active_alerts"]),
    ("Workload replay harness (seeded load generator)",
     "paddle_tpu.inference.fleet.replay",
     ["ReplayHarness", "ReplayReport", "ReplayTrace", "ReplayRequest",
      "make_trace", "load_trace", "time_to_recover", "REPLAY_PRESETS"]),
    # -- training observatory (ISSUE 12) -------------------------------------
    ("Numerics sentinel (per-layer grad stats)",
     "paddle_tpu.profiler.tensor_stats",
     ["NumericsSentinel", "NonFiniteGradError", "get_sentinel", "enable",
      "disable", "attach", "detach", "is_enabled"]),
    ("Step memory timeline + module breakdown",
     "paddle_tpu.profiler.memory",
     ["MemoryTimeline", "get_timeline", "module_breakdown",
      "register_model_breakdown", "phase_sample", "last_breakdown"]),
    ("Step-phase spans (fwd/bwd/comm/opt)",
     "paddle_tpu.profiler.step_phase",
     ["PHASES", "record_phase", "span", "breakdown", "clock",
      "step_begin", "step_end"]),
    # -- determinism observatory (ISSUE 13) ----------------------------------
    ("Determinism ledger (digest sensing + comparator)",
     "paddle_tpu.profiler.ledger",
     ["StepLedger", "DivergenceError", "get_ledger", "enable", "disable",
      "attach", "detach", "is_enabled", "tensor_digest",
      "first_divergence", "record_optimizer_step"]),
    ("Golden ledger export + cross-process publish",
     "paddle_tpu.profiler.ledger",
     ["export_golden", "publish_ledger", "gather_ledgers",
      "compare_store", "LEDGER_SCHEMA", "KV_LEDGER_PREFIX"]),
    ("Token-stream attestation + handoff digests",
     "paddle_tpu.profiler.ledger",
     ["note_stream_token", "stream_digest", "attest_delivery",
      "seal_handoff", "check_handoff", "chain_update", "blob_digest"]),
    # -- self-healing fleet control plane (ISSUE 14) -------------------------
    ("Fleet controller (SLO-driven reconcile loop)",
     "paddle_tpu.inference.fleet.controller",
     ["FleetController", "ControllerAction", "CONTROLLER_ACTIONS"]),
    ("Fleet actuators (scale/flip/shed/supervise surface)",
     "paddle_tpu.inference.fleet",
     ["ServingRouter", "TenantQuotaManager", "REJECTION_REASONS",
      "DEFAULT_FLEET_MAX_ATTEMPTS"]),
    ("Fleet fault directives (kill/stall by routed request)",
     "paddle_tpu.distributed.fault",
     ["FLEET_FAULT_KINDS", "check_fleet_route", "Fault", "FaultPlan"]),
    # -- telemetry plane (ISSUE 15) ------------------------------------------
    ("Per-process telemetry exporter (HTTP endpoints + KV discovery)",
     "paddle_tpu.profiler.exporter",
     ["TelemetryServer", "maybe_start_exporter", "exporter_enabled",
      "ROUTES", "KV_TELEMETRY_PREFIX", "MAX_HISTORY_WINDOW_S",
      "MAX_POST_BYTES"]),
    ("Fleet scrape aggregation (strict parser + merged view)",
     "paddle_tpu.profiler.scrape",
     ["FleetScraper", "parse_metrics_text", "render_metrics_text",
      "merge_instances", "fleet_metrics", "fleet_metrics_text",
      "start_fleet_scraper", "stop_fleet_scraper"]),
    ("Correlated structured event log (JSONL + rotation)",
     "paddle_tpu.profiler.eventlog",
     ["EventLog", "get_event_log", "log_event", "enable", "disable",
      "is_enabled", "EVENTLOG_SCHEMA"]),
    # -- device-tier decode speed (ISSUE 16) ---------------------------------
    ("Q-block ragged attention (fixed-q-block grid)",
     "paddle_tpu.ops.pallas.ragged_paged_attention",
     ["qblock_job_list", "job_buckets", "DEFAULT_QBLOCK",
      "ragged_paged_attention"]),
    ("Int8 weight serving path (quantize + fused forward)",
     "paddle_tpu.quantization",
     ["quantize_linears", "int8_linear"]),
    ("Batched drafting (one padded draft forward per tick)",
     "paddle_tpu.inference.speculative",
     ["DraftModelDrafter", "NGramDrafter"]),
    # -- compile observatory (ISSUE 18) --------------------------------------
    ("Compile observatory (retrace-cause attribution)",
     "paddle_tpu.profiler.compile_observatory",
     ["CompileObservatory", "get_observatory", "observe",
      "declare_family", "register_warmup", "run_warmup",
      "declared_families", "undeclared_families", "snapshot",
      "cost_section", "tensor_arg", "static_arg", "format_signature",
      "SCHEMA"]),
    ("Recompile-storm + family-drift alert rules",
     "paddle_tpu.profiler.alerts",
     ["recompile_storm_rule", "family_drift_rule",
      "DEFAULT_RECOMPILE_BUDGET"]),
    ("Fleet compile scrape (/compile merge)",
     "paddle_tpu.profiler.scrape",
     ["fetch_compile", "merge_compile_snapshots"]),
    # -- tiered KV + long-context sep prefill (ISSUE 19) ---------------------
    ("Host-RAM KV tier (prefix spill pool)",
     "paddle_tpu.models.generation",
     ["HostKVPool", "SlotPagedKVCache"]),
    ("Sep-ring blockwise prefill kernel tier",
     "paddle_tpu.ops.pallas.ring_attention",
     ["blockwise_causal_attention", "ring_partial", "sep_ring_impl",
      "SEP_RING_IMPLS"]),
]

# DistributedStrategy fields exempt from the docs/PERF.md mention rule
# (none today — add a field here only with a reason it cannot matter to
# performance tuning).
STRATEGY_DOC_EXEMPT: set = set()


def check_strategy_docs(verbose=True):
    """Every public ``DistributedStrategy`` field must be mentioned in
    docs/PERF.md — a knob nobody can discover is a knob nobody tunes.
    Returns the list of undocumented fields (empty = pass)."""
    from paddle_tpu.distributed.fleet.distributed_strategy import (
        DistributedStrategy)
    perf_path = os.path.join(os.path.dirname(__file__), "..", "docs",
                             "PERF.md")
    with open(perf_path) as f:
        perf = f.read()
    fields = sorted(k for k in vars(DistributedStrategy())
                    if not k.startswith("_") and k not in STRATEGY_DOC_EXEMPT)
    missing = [f for f in fields if f not in perf]
    if verbose:
        for f in missing:
            print(f"FAIL DistributedStrategy.{f} has no docs/PERF.md mention")
        print(f"{len(fields) - len(missing)}/{len(fields)} strategy fields "
              f"documented")
    return missing


# PADDLE_* env knobs exempt from the docs-mention rule. Add a knob here
# only with a reason it cannot matter to a user tuning or operating the
# system (none today).
ENV_DOC_EXEMPT: set = set()


def check_env_docs(verbose=True):
    """Every ``PADDLE_*`` env knob referenced anywhere in ``paddle_tpu/``
    must be mentioned in at least one ``docs/*.md`` file — an env knob
    nobody can discover is a knob nobody tunes (the PR-5
    DistributedStrategy-field rule, applied to the env surface). Returns
    the list of undocumented knobs (empty = pass)."""
    import re

    root = os.path.join(os.path.dirname(__file__), "..")
    pat = re.compile(r"PADDLE_[A-Z0-9_]*[A-Z0-9]")
    found = set()
    for dirpath, dirnames, filenames in os.walk(
            os.path.join(root, "paddle_tpu")):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for name in filenames:
            if not name.endswith(".py"):
                continue
            with open(os.path.join(dirpath, name), errors="replace") as f:
                found.update(pat.findall(f.read()))
    docs_text = ""
    docs_dir = os.path.join(root, "docs")
    for name in sorted(os.listdir(docs_dir)):
        if name.endswith(".md"):
            with open(os.path.join(docs_dir, name), errors="replace") as f:
                docs_text += f.read()
    missing = sorted(k for k in found
                     if k not in docs_text and k not in ENV_DOC_EXEMPT)
    if verbose:
        for k in missing:
            print(f"FAIL env knob {k} has no docs/*.md mention")
        print(f"{len(found) - len(missing)}/{len(found)} env knobs "
              f"documented")
    return missing


def check_serving_programs(verbose=True):
    """Compiled-program-count guard for the serving tier: drive a short
    MIXED prefill+decode load through the ragged scheduler and fail if
    any forward ran a shape outside the engine's declared token-bucket
    family — per-request shapes mean unbounded recompiles in production.
    Also proves both token kinds actually flowed through the single
    ragged program family, and (second pass) that speculative-decode
    verify spans (q_len = 1 + k drafted tokens) stay inside the SAME
    declared family — spec decode must not explode the compiled-program
    set. Last, the decoder layer's two compiled programs hold exactly
    one executable a bucket met. Returns a list of violation strings."""
    import threading

    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.inference import ContinuousServingEngine
    from paddle_tpu.models import LlamaForCausalLM, llama_tiny

    paddle.seed(0)
    model = LlamaForCausalLM(llama_tiny(num_hidden_layers=1))
    rng = np.random.RandomState(0)
    # deliberately awkward prompt lengths: none is a bucket size
    prompts = [rng.randint(0, 128, (1, n)).astype(np.int64)
               for n in (13, 3, 21)]

    def drive(eng, reqs, new_tokens=3):
        with eng:
            threads = [threading.Thread(
                target=lambda p=p: eng.generate(p, max_new_tokens=new_tokens,
                                                timeout=300))
                for p in reqs]
            for t in threads:
                t.start()
            for t in threads:
                t.join()

    eng = ContinuousServingEngine(model, max_batch_size=2, max_len=48,
                                  token_budget=16, prefill_chunk_tokens=16)
    drive(eng, prompts)
    declared = eng.declared_token_buckets()
    violations = []
    stray = eng.ragged_buckets_used - declared
    if stray:
        violations.append(
            f"serving ran shapes outside the declared bucket set: "
            f"{sorted(stray)} (declared {sorted(declared)})")
    if not eng.ragged_steps:
        violations.append("mixed load never reached the ragged scheduler")
    if not (eng.ragged_prefill_tokens and eng.ragged_decode_tokens):
        violations.append(
            f"ragged program family missed a token kind: prefill="
            f"{eng.ragged_prefill_tokens} decode={eng.ragged_decode_tokens}")
    # speculative pass: self-draft (acceptance ~1) maximizes verify-span
    # lengths, the worst case for bucket growth
    spec = ContinuousServingEngine(model, max_batch_size=2, max_len=48,
                                   token_budget=16, prefill_chunk_tokens=16,
                                   spec_decode=True, spec_k=3,
                                   draft_model=model)
    drive(spec, prompts[:2], new_tokens=6)
    spec_stray = spec.ragged_buckets_used - spec.declared_token_buckets()
    if spec_stray:
        violations.append(
            f"speculative verify spans ran shapes outside the declared "
            f"bucket set: {sorted(spec_stray)} "
            f"(declared {sorted(spec.declared_token_buckets())})")
    if not spec.spec_drafted_tokens:
        violations.append("speculative pass drafted no tokens")
    # the decoder layer's two compiled programs (models/llama.py): one
    # executable a token bucket met, shared by every layer, tick and
    # engine of this geometry — a recompile a tick or a layer shows here
    met = eng.ragged_buckets_used | spec.ragged_buckets_used
    programs = model.llama._programs
    pieces = programs.program_counts() if programs is not None else {}
    for e, name in ((eng, "mixed"), (spec, "speculative")):
        if e.compiled_layer_calls != e.ragged_steps:     # one layer
            violations.append(
                f"{name} pass ran {e.compiled_layer_calls} compiled layers "
                f"in {e.ragged_steps} ticks of a one-layer model")
    for piece, n in sorted(pieces.items()):
        if n != len(met):
            violations.append(
                f"layer program `{piece}` holds {n} executables for "
                f"{len(met)} token bucket(s) met {sorted(met)}")
    if verbose:
        for v in violations:
            print(f"FAIL {v}")
        print(f"layer programs: {pieces} for buckets {sorted(met)}")
        print(f"serving programs: {len(eng.ragged_buckets_used)} bucket(s) "
              f"{sorted(eng.ragged_buckets_used)} within declared "
              f"{sorted(declared)}; prefill={eng.ragged_prefill_tokens} "
              f"decode={eng.ragged_decode_tokens} tokens; spec buckets "
              f"{sorted(spec.ragged_buckets_used)} drafted="
              f"{spec.spec_drafted_tokens} accepted="
              f"{spec.spec_accepted_tokens}")
    return violations


def check_quantized_config(verbose=True):
    """Quantized-config inventory guard (ISSUE 16): every device-tier
    decode-speed knob (int8 weights, q-block ragged grid, batched
    drafting) must be documented in ``docs/*.md`` AND exercised by at
    least one test, and the fully-quantized serving config
    (``weight_dtype="int8"`` + ``kv_dtype="int8"`` under the default
    q-block ragged grid) must be BIT-STABLE: two same-seed runs produce
    byte-identical token streams (sha1 attestation) while staying
    inside the declared bucket family. A quantized path that drifts
    run-to-run is a silent-accuracy incident, not a speed win. Returns
    a list of violation strings."""
    import hashlib
    import threading

    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.inference import ContinuousServingEngine
    from paddle_tpu.models import LlamaForCausalLM, llama_tiny

    root = os.path.join(os.path.dirname(__file__), "..")
    docs_text = ""
    docs_dir = os.path.join(root, "docs")
    for name in sorted(os.listdir(docs_dir)):
        if name.endswith(".md"):
            with open(os.path.join(docs_dir, name), errors="replace") as f:
                docs_text += f.read()
    tests_text = ""
    tests_dir = os.path.join(root, "tests")
    for name in sorted(os.listdir(tests_dir)):
        if name.startswith("test_") and name.endswith(".py"):
            with open(os.path.join(tests_dir, name), errors="replace") as f:
                tests_text += f.read()
    violations = []
    knobs = ["PADDLE_WEIGHT_DTYPE", "PADDLE_SPEC_DRAFT_BATCH",
             "PADDLE_KV_DTYPE"]
    for k in knobs:
        if k not in docs_text:
            violations.append(
                f"quantized-config knob {k} missing from docs/*.md")
        if k not in tests_text:
            violations.append(
                f"quantized-config knob {k} not exercised by any test")

    def run_once():
        paddle.seed(0)
        model = LlamaForCausalLM(llama_tiny(num_hidden_layers=1))
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, 128, (1, n)).astype(np.int64)
                   for n in (13, 3, 21)]
        eng = ContinuousServingEngine(
            model, max_batch_size=2, max_len=48, token_budget=16,
            prefill_chunk_tokens=16, weight_dtype="int8", kv_dtype="int8")
        outs = [None] * len(prompts)

        def gen(i, p):
            outs[i] = np.asarray(
                eng.generate(p, max_new_tokens=3, timeout=300).numpy())

        with eng:
            threads = [threading.Thread(target=gen, args=(i, p))
                       for i, p in enumerate(prompts)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        h = hashlib.sha1()
        for o in outs:
            if o is not None:
                h.update(np.ascontiguousarray(o).tobytes())
        return eng, outs, h.hexdigest()

    eng_a, outs_a, dig_a = run_once()
    eng_b, outs_b, dig_b = run_once()
    if not eng_a.quantized_linears:
        violations.append("fully-int8 config quantized no Linear layers")
    stray = eng_a.ragged_buckets_used - eng_a.declared_token_buckets()
    if stray:
        violations.append(
            f"fully-int8 serving ran shapes outside the declared bucket "
            f"set: {sorted(stray)} "
            f"(declared {sorted(eng_a.declared_token_buckets())})")
    for i, (a, b) in enumerate(zip(outs_a, outs_b)):
        if a is None or b is None:
            violations.append(f"fully-int8 request {i} produced no output")
        elif a.shape != b.shape or not (a == b).all():
            violations.append(
                f"fully-int8 config is not bit-stable: request {i} "
                f"diverged between two same-seed runs")
    if dig_a != dig_b:
        violations.append(
            f"fully-int8 token digests differ across same-seed runs: "
            f"{dig_a} vs {dig_b}")
    if verbose:
        for v in violations:
            print(f"FAIL {v}")
        print(f"quantized config: {len(knobs)} knobs checked, "
              f"{eng_a.quantized_linears} Linear(s) quantized, "
              f"token digest {dig_a[:12]} stable across 2 runs")
    return violations


def check_fleet_knobs(verbose=True):
    """Serving-fleet inventory guard: every ``PADDLE_FLEET_*`` env knob
    referenced in ``paddle_tpu/`` must be documented in docs/SERVING.md's
    fleet knob table, and every router policy string
    (``inference.fleet.ROUTER_POLICIES``) must appear in at least one
    test — a routing mode no test exercises is a routing mode that
    silently rots. Returns a list of violation strings."""
    import re

    root = os.path.join(os.path.dirname(__file__), "..")
    pat = re.compile(r"PADDLE_FLEET_[A-Z0-9_]*[A-Z0-9]")
    knobs = set()
    for dirpath, dirnames, filenames in os.walk(
            os.path.join(root, "paddle_tpu")):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for name in filenames:
            if name.endswith(".py"):
                with open(os.path.join(dirpath, name),
                          errors="replace") as f:
                    knobs.update(pat.findall(f.read()))
    with open(os.path.join(root, "docs", "SERVING.md"),
              errors="replace") as f:
        serving_doc = f.read()
    violations = [f"fleet knob {k} missing from docs/SERVING.md"
                  for k in sorted(knobs) if k not in serving_doc]
    tests_text = ""
    tests_dir = os.path.join(root, "tests")
    for name in sorted(os.listdir(tests_dir)):
        if name.startswith("test_") and name.endswith(".py"):
            with open(os.path.join(tests_dir, name), errors="replace") as f:
                tests_text += f.read()
    from paddle_tpu.inference.fleet import ROUTER_POLICIES
    for policy in ROUTER_POLICIES:
        if f'"{policy}"' not in tests_text:
            violations.append(
                f"router policy {policy!r} not exercised by any test")
        if policy not in serving_doc:
            violations.append(
                f"router policy {policy!r} missing from docs/SERVING.md")
    if verbose:
        for v in violations:
            print(f"FAIL {v}")
        print(f"fleet knobs: {len(knobs)} found, "
              f"{len(ROUTER_POLICIES)} policies checked")
    return violations


def check_observability_catalog(verbose=True):
    """Request-trace/SLO/spec-decode inventory guard: every
    ``paddle_request_*`` / ``paddle_slo_*`` / ``paddle_spec_*`` metric
    name and every ``PADDLE_SLO_*`` / ``PADDLE_REQUEST_TRACE*`` /
    ``PADDLE_SPEC_*`` / ``PADDLE_KV_*`` env knob referenced in
    ``paddle_tpu/`` must be cataloged in docs/OBSERVABILITY.md (knobs
    may live in any docs/*.md via check_env_docs, but the metric names
    must be in the catalog) — these layers exist so operators can SEE;
    an uncataloged signal defeats it. Returns a list of violation
    strings."""
    import re

    root = os.path.join(os.path.dirname(__file__), "..")
    metric_pat = re.compile(
        r"paddle_(?:request|slo|spec)_[a-z0-9_]*[a-z0-9]")
    knob_pat = re.compile(
        r"PADDLE_(?:SLO|REQUEST_TRACE)[A-Z0-9_]*")
    metrics, knobs = set(), set()
    for dirpath, dirnames, filenames in os.walk(
            os.path.join(root, "paddle_tpu")):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for name in filenames:
            if name.endswith(".py"):
                with open(os.path.join(dirpath, name),
                          errors="replace") as f:
                    text = f.read()
                metrics.update(metric_pat.findall(text))
                knobs.update(knob_pat.findall(text))
    with open(os.path.join(root, "docs", "OBSERVABILITY.md"),
              errors="replace") as f:
        doc = f.read()
    violations = [f"request/SLO metric {m} missing from "
                  f"docs/OBSERVABILITY.md"
                  for m in sorted(metrics) if m not in doc]
    violations += [f"request-trace knob {k} missing from "
                   f"docs/OBSERVABILITY.md"
                   for k in sorted(knobs) if k not in doc]
    if verbose:
        for v in violations:
            print(f"FAIL {v}")
        print(f"observability catalog: {len(metrics)} request/SLO "
              f"metrics, {len(knobs)} knobs checked")
    return violations


def check_alert_catalog(verbose=True):
    """Fleet-observatory inventory guard: every ``PADDLE_HISTORY_*`` /
    ``PADDLE_ALERT_*`` / ``PADDLE_REPLAY_*`` / ``PADDLE_TELEMETRY_*``
    env knob and every ``paddle_history_*`` / ``paddle_alert*_*``
    metric referenced in ``paddle_tpu/`` must be (a) cataloged in
    docs/OBSERVABILITY.md and (b) exercised by at least one test —
    an alerting signal nobody documents or tests is a pager that lies.
    Every replay preset string must appear in a test too (same rule as
    the router policies). Returns a list of violation strings."""
    import re

    root = os.path.join(os.path.dirname(__file__), "..")
    knob_pat = re.compile(
        r"PADDLE_(?:HISTORY|ALERT|REPLAY|TELEMETRY)[A-Z0-9_]*")
    metric_pat = re.compile(r"paddle_(?:history|alerts?)_[a-z0-9_]*[a-z0-9]")
    knobs, metrics = set(), set()
    for dirpath, dirnames, filenames in os.walk(
            os.path.join(root, "paddle_tpu")):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for name in filenames:
            if name.endswith(".py"):
                with open(os.path.join(dirpath, name),
                          errors="replace") as f:
                    text = f.read()
                knobs.update(knob_pat.findall(text))
                metrics.update(metric_pat.findall(text))
    with open(os.path.join(root, "docs", "OBSERVABILITY.md"),
              errors="replace") as f:
        doc = f.read()
    tests_text = ""
    tests_dir = os.path.join(root, "tests")
    for name in sorted(os.listdir(tests_dir)):
        if name.startswith("test_") and name.endswith(".py"):
            with open(os.path.join(tests_dir, name), errors="replace") as f:
                tests_text += f.read()
    violations = []
    for k in sorted(knobs):
        if k not in doc:
            violations.append(
                f"observatory knob {k} missing from docs/OBSERVABILITY.md")
        if k not in tests_text:
            violations.append(
                f"observatory knob {k} not exercised by any test")
    for m in sorted(metrics):
        if m not in doc:
            violations.append(
                f"observatory metric {m} missing from "
                f"docs/OBSERVABILITY.md")
        if m not in tests_text:
            violations.append(
                f"observatory metric {m} not exercised by any test")
    from paddle_tpu.inference.fleet import REPLAY_PRESETS
    for preset in REPLAY_PRESETS:
        if f'"{preset}"' not in tests_text:
            violations.append(
                f"replay preset {preset!r} not exercised by any test")
        if preset not in doc:
            violations.append(
                f"replay preset {preset!r} missing from "
                f"docs/OBSERVABILITY.md")
    if verbose:
        for v in violations:
            print(f"FAIL {v}")
        print(f"alert catalog: {len(knobs)} knobs, {len(metrics)} "
              f"metrics, {len(REPLAY_PRESETS)} presets checked")
    return violations


def check_training_observability(verbose=True):
    """Training-observatory inventory guard: every ``PADDLE_NUMERICS_*``
    / ``PADDLE_MEMORY_*`` / ``PADDLE_STEP_PHASE*`` env knob and every
    ``paddle_numerics_*`` / ``paddle_memory_*`` / ``paddle_step_phase_*``
    metric referenced in ``paddle_tpu/`` must be (a) cataloged in
    docs/OBSERVABILITY.md and (b) exercised by at least one test — the
    same rule the fleet observatory lives under (check_alert_catalog):
    a numerics guard nobody documents or tests is a guard that lies.
    Returns a list of violation strings."""
    import re

    root = os.path.join(os.path.dirname(__file__), "..")
    knob_pat = re.compile(
        r"PADDLE_(?:NUMERICS|MEMORY|STEP_PHASE)[A-Z0-9_]*")
    metric_pat = re.compile(
        r"paddle_(?:numerics|memory|step_phase)_[a-z0-9_]*[a-z0-9]")
    knobs, metrics = set(), set()
    for dirpath, dirnames, filenames in os.walk(
            os.path.join(root, "paddle_tpu")):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for name in filenames:
            if name.endswith(".py"):
                with open(os.path.join(dirpath, name),
                          errors="replace") as f:
                    text = f.read()
                knobs.update(knob_pat.findall(text))
                metrics.update(metric_pat.findall(text))
    with open(os.path.join(root, "docs", "OBSERVABILITY.md"),
              errors="replace") as f:
        doc = f.read()
    tests_text = ""
    tests_dir = os.path.join(root, "tests")
    for name in sorted(os.listdir(tests_dir)):
        if name.startswith("test_") and name.endswith(".py"):
            with open(os.path.join(tests_dir, name), errors="replace") as f:
                tests_text += f.read()
    violations = []
    for k in sorted(knobs):
        if k not in doc:
            violations.append(
                f"training-observability knob {k} missing from "
                f"docs/OBSERVABILITY.md")
        if k not in tests_text:
            violations.append(
                f"training-observability knob {k} not exercised by any "
                f"test")
    for m in sorted(metrics):
        if m not in doc:
            violations.append(
                f"training-observability metric {m} missing from "
                f"docs/OBSERVABILITY.md")
        if m not in tests_text:
            violations.append(
                f"training-observability metric {m} not exercised by "
                f"any test")
    if verbose:
        for v in violations:
            print(f"FAIL {v}")
        print(f"training observability: {len(knobs)} knobs, "
              f"{len(metrics)} metrics checked")
    return violations


def check_ledger_catalog(verbose=True):
    """Determinism-observatory inventory guard: every ``PADDLE_LEDGER*``
    env knob and every ``paddle_ledger_*`` metric referenced in
    ``paddle_tpu/`` must be (a) cataloged in docs/OBSERVABILITY.md and
    (b) exercised by at least one test — same rule as the fleet and
    training observatories: a divergence sensor nobody documents or
    tests is a sensor that lies. Returns a list of violation strings."""
    import re

    root = os.path.join(os.path.dirname(__file__), "..")
    knob_pat = re.compile(r"PADDLE_LEDGER[A-Z0-9_]*")
    metric_pat = re.compile(r"paddle_ledger_[a-z0-9_]*[a-z0-9]")
    knobs, metrics = set(), set()
    for dirpath, dirnames, filenames in os.walk(
            os.path.join(root, "paddle_tpu")):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for name in filenames:
            if name.endswith(".py"):
                with open(os.path.join(dirpath, name),
                          errors="replace") as f:
                    text = f.read()
                knobs.update(knob_pat.findall(text))
                metrics.update(metric_pat.findall(text))
    with open(os.path.join(root, "docs", "OBSERVABILITY.md"),
              errors="replace") as f:
        doc = f.read()
    tests_text = ""
    tests_dir = os.path.join(root, "tests")
    for name in sorted(os.listdir(tests_dir)):
        if name.startswith("test_") and name.endswith(".py"):
            with open(os.path.join(tests_dir, name), errors="replace") as f:
                tests_text += f.read()
    violations = []
    for k in sorted(knobs):
        if k not in doc:
            violations.append(
                f"ledger knob {k} missing from docs/OBSERVABILITY.md")
        if k not in tests_text:
            violations.append(
                f"ledger knob {k} not exercised by any test")
    for m in sorted(metrics):
        if m not in doc:
            violations.append(
                f"ledger metric {m} missing from docs/OBSERVABILITY.md")
        if m not in tests_text:
            violations.append(
                f"ledger metric {m} not exercised by any test")
    if verbose:
        for v in violations:
            print(f"FAIL {v}")
        print(f"ledger catalog: {len(knobs)} knobs, {len(metrics)} "
              f"metrics checked")
    return violations


def check_controller_catalog(verbose=True):
    """Fleet-control-plane inventory guard (ISSUE 14): every
    ``PADDLE_CONTROLLER_*`` env knob and ``paddle_controller_*`` metric
    referenced in ``paddle_tpu/`` must be documented (knobs in
    docs/SERVING.md's controller table, metrics in
    docs/OBSERVABILITY.md) AND exercised by at least one test; every
    controller action string (``CONTROLLER_ACTIONS``), fleet fault
    directive (``kill:replica`` / ``stall:replica``) and structured
    rejection reason (``REJECTION_REASONS``) must be documented and
    tested too — a self-healing loop nobody can audit is a loop nobody
    will trust. Returns a list of violation strings."""
    import re

    root = os.path.join(os.path.dirname(__file__), "..")
    knob_pat = re.compile(r"PADDLE_CONTROLLER_[A-Z0-9_]*[A-Z0-9]")
    metric_pat = re.compile(r"paddle_controller_[a-z0-9_]*[a-z0-9]")
    knobs, metrics = set(), set()
    for dirpath, dirnames, filenames in os.walk(
            os.path.join(root, "paddle_tpu")):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for name in filenames:
            if name.endswith(".py"):
                with open(os.path.join(dirpath, name),
                          errors="replace") as f:
                    text = f.read()
                knobs.update(knob_pat.findall(text))
                metrics.update(metric_pat.findall(text))
    with open(os.path.join(root, "docs", "SERVING.md"),
              errors="replace") as f:
        serving_doc = f.read()
    with open(os.path.join(root, "docs", "OBSERVABILITY.md"),
              errors="replace") as f:
        obs_doc = f.read()
    with open(os.path.join(root, "docs", "ROBUSTNESS.md"),
              errors="replace") as f:
        robust_doc = f.read()
    tests_text = ""
    tests_dir = os.path.join(root, "tests")
    for name in sorted(os.listdir(tests_dir)):
        if name.startswith("test_") and name.endswith(".py"):
            with open(os.path.join(tests_dir, name), errors="replace") as f:
                tests_text += f.read()
    violations = []
    for k in sorted(knobs):
        if k not in serving_doc:
            violations.append(
                f"controller knob {k} missing from docs/SERVING.md")
        if k not in tests_text:
            violations.append(
                f"controller knob {k} not exercised by any test")
    for m in sorted(metrics):
        if m not in obs_doc:
            violations.append(
                f"controller metric {m} missing from "
                f"docs/OBSERVABILITY.md")
        if m not in tests_text:
            violations.append(
                f"controller metric {m} not exercised by any test")
    from paddle_tpu.distributed.fault import FLEET_FAULT_KINDS
    from paddle_tpu.inference.fleet import (CONTROLLER_ACTIONS,
                                            REJECTION_REASONS)
    for action in CONTROLLER_ACTIONS:
        if f'"{action}"' not in tests_text:
            violations.append(
                f"controller action {action!r} not exercised by any test")
        if f"`{action}`" not in serving_doc:
            violations.append(
                f"controller action {action!r} missing from "
                f"docs/SERVING.md")
    for kind in FLEET_FAULT_KINDS:
        directive = f"{kind}:replica"
        if directive not in tests_text:
            violations.append(
                f"fleet fault directive {directive!r} not exercised by "
                f"any test")
        if directive not in robust_doc:
            violations.append(
                f"fleet fault directive {directive!r} missing from "
                f"docs/ROBUSTNESS.md")
    for reason in REJECTION_REASONS:
        if f'"{reason}"' not in tests_text:
            violations.append(
                f"rejection reason {reason!r} not exercised by any test")
        if f"`{reason}`" not in serving_doc:
            violations.append(
                f"rejection reason {reason!r} missing from "
                f"docs/SERVING.md")
    if verbose:
        for v in violations:
            print(f"FAIL {v}")
        print(f"controller catalog: {len(knobs)} knobs, {len(metrics)} "
              f"metrics, {len(CONTROLLER_ACTIONS)} actions, "
              f"{len(FLEET_FAULT_KINDS)} fleet fault kinds, "
              f"{len(REJECTION_REASONS)} rejection reasons checked")
    return violations


def check_telemetry_plane(verbose=True):
    """Telemetry-plane inventory guard (ISSUE 15): every
    ``PADDLE_TELEMETRY_*`` / ``PADDLE_EVENTLOG*`` env knob, every
    ``paddle_telemetry_*`` / ``paddle_eventlog_*`` metric referenced in
    ``paddle_tpu/`` AND every exporter HTTP route
    (``profiler.exporter.ROUTES``) must be cataloged in
    docs/OBSERVABILITY.md and exercised by at least one test — a remote
    diagnosis surface nobody documents or tests is a dashboard that
    404s during the incident. Returns a list of violation strings."""
    import re

    root = os.path.join(os.path.dirname(__file__), "..")
    knob_pat = re.compile(r"PADDLE_(?:TELEMETRY|EVENTLOG)[A-Z0-9_]*")
    metric_pat = re.compile(
        r"paddle_(?:telemetry|eventlog)_[a-z0-9_]*[a-z0-9]")
    knobs, metrics = set(), set()
    for dirpath, dirnames, filenames in os.walk(
            os.path.join(root, "paddle_tpu")):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for name in filenames:
            if name.endswith(".py"):
                with open(os.path.join(dirpath, name),
                          errors="replace") as f:
                    text = f.read()
                knobs.update(knob_pat.findall(text))
                metrics.update(metric_pat.findall(text))
    with open(os.path.join(root, "docs", "OBSERVABILITY.md"),
              errors="replace") as f:
        doc = f.read()
    tests_text = ""
    tests_dir = os.path.join(root, "tests")
    for name in sorted(os.listdir(tests_dir)):
        if name.startswith("test_") and name.endswith(".py"):
            with open(os.path.join(tests_dir, name), errors="replace") as f:
                tests_text += f.read()
    violations = []
    for k in sorted(knobs):
        if k not in doc:
            violations.append(
                f"telemetry-plane knob {k} missing from "
                f"docs/OBSERVABILITY.md")
        if k not in tests_text:
            violations.append(
                f"telemetry-plane knob {k} not exercised by any test")
    for m in sorted(metrics):
        if m not in doc:
            violations.append(
                f"telemetry-plane metric {m} missing from "
                f"docs/OBSERVABILITY.md")
        if m not in tests_text:
            violations.append(
                f"telemetry-plane metric {m} not exercised by any test")
    from paddle_tpu.profiler.exporter import ROUTES
    for route in ROUTES:
        # backtick-prefix match: `/timeline/<trace_id>` documents the
        # /timeline route
        if f"`{route}" not in doc:
            violations.append(
                f"exporter route {route!r} missing from "
                f"docs/OBSERVABILITY.md")
        if route not in tests_text:
            violations.append(
                f"exporter route {route!r} not exercised by any test")
    if verbose:
        for v in violations:
            print(f"FAIL {v}")
        print(f"telemetry plane: {len(knobs)} knobs, {len(metrics)} "
              f"metrics, {len(ROUTES)} routes checked")
    return violations


def check_kv_tier(verbose=True):
    """Tiered-KV / long-context inventory guard (ISSUE 19): every
    ``PADDLE_KV_HOST_*`` and ``PADDLE_SEP_*`` env knob referenced in
    ``paddle_tpu/`` must be documented in docs/SERVING.md's tiered-KV
    knob table AND exercised by at least one test, and every
    ``paddle_kv_*`` metric (plus the tier-labelled prefix-eviction
    counter) must be cataloged in docs/OBSERVABILITY.md AND exercised
    by a test — eviction was silent before this layer existed; an
    undocumented spill knob or counter would make it silent again.
    Returns a list of violation strings."""
    import re

    root = os.path.join(os.path.dirname(__file__), "..")
    knob_pat = re.compile(r"PADDLE_(?:KV_HOST|SEP)_[A-Z0-9_]*")
    metric_pat = re.compile(r"paddle_kv_[a-z0-9_]*[a-z0-9]")
    knobs, metrics = set(), set()
    for dirpath, dirnames, filenames in os.walk(
            os.path.join(root, "paddle_tpu")):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for name in filenames:
            if name.endswith(".py"):
                with open(os.path.join(dirpath, name),
                          errors="replace") as f:
                    text = f.read()
                knobs.update(knob_pat.findall(text))
                metrics.update(metric_pat.findall(text))
    metrics.add("paddle_serving_prefix_evictions_total")
    with open(os.path.join(root, "docs", "SERVING.md"),
              errors="replace") as f:
        serving_doc = f.read()
    with open(os.path.join(root, "docs", "OBSERVABILITY.md"),
              errors="replace") as f:
        obs_doc = f.read()
    tests_text = ""
    tests_dir = os.path.join(root, "tests")
    for name in sorted(os.listdir(tests_dir)):
        if name.startswith("test_") and name.endswith(".py"):
            with open(os.path.join(tests_dir, name),
                      errors="replace") as f:
                tests_text += f.read()
    violations = []
    for k in sorted(knobs):
        if k not in serving_doc:
            violations.append(
                f"kv-tier knob {k} missing from docs/SERVING.md")
        if k not in tests_text:
            violations.append(
                f"kv-tier knob {k} not exercised by any test")
    for m in sorted(metrics):
        if m not in obs_doc:
            violations.append(
                f"kv-tier metric {m} missing from docs/OBSERVABILITY.md")
        if m not in tests_text:
            violations.append(
                f"kv-tier metric {m} not exercised by any test")
    if verbose:
        for v in violations:
            print(f"FAIL {v}")
        print(f"kv tier: {len(knobs)} knobs, {len(metrics)} metrics "
              f"checked")
    return violations


def check_compile_observatory(verbose=True):
    """Compile-observatory inventory guard (ISSUE 18). Two halves:

    Catalog: every ``PADDLE_COMPILE*`` env knob and every
    ``paddle_compile_*`` metric referenced in ``paddle_tpu/`` must be
    documented in docs/OBSERVABILITY.md AND exercised by at least one
    test — the same contract every other observability layer lives
    under.

    Runtime drift: a short mixed prefill+decode replay through a warmed
    engine must (a) observe ONLY program families that were declared in
    the inventory (a serve-time family the fleet doesn't account for is
    drift), (b) find a registered warmup entry for every declared
    family, and (c) record ZERO trace-cache misses after
    ``warmup_programs()`` — steady-state serving must never recompile.
    Returns a list of violation strings."""
    import re
    import threading

    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.inference import ContinuousServingEngine
    from paddle_tpu.models import LlamaForCausalLM, llama_tiny
    from paddle_tpu.profiler import compile_observatory as co

    root = os.path.join(os.path.dirname(__file__), "..")
    knob_pat = re.compile(r"PADDLE_COMPILE[A-Z0-9_]*")
    metric_pat = re.compile(r"paddle_compile_[a-z0-9_]*[a-z0-9]")
    knobs, metrics = set(), set()
    for dirpath, dirnames, filenames in os.walk(
            os.path.join(root, "paddle_tpu")):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for name in filenames:
            if name.endswith(".py"):
                with open(os.path.join(dirpath, name),
                          errors="replace") as f:
                    text = f.read()
                knobs.update(knob_pat.findall(text))
                metrics.update(metric_pat.findall(text))
    # the snapshot schema token ("paddle_compile_observatory/1") matches
    # the metric pattern but is not a metric family
    metrics.discard("paddle_compile_observatory")
    with open(os.path.join(root, "docs", "OBSERVABILITY.md"),
              errors="replace") as f:
        doc = f.read()
    tests_text = ""
    tests_dir = os.path.join(root, "tests")
    for name in sorted(os.listdir(tests_dir)):
        if name.startswith("test_") and name.endswith(".py"):
            with open(os.path.join(tests_dir, name), errors="replace") as f:
                tests_text += f.read()
    violations = []
    for k in sorted(knobs):
        if k not in doc:
            violations.append(
                f"compile-observatory knob {k} missing from "
                f"docs/OBSERVABILITY.md")
        if k not in tests_text:
            violations.append(
                f"compile-observatory knob {k} not exercised by any test")
    for m in sorted(metrics):
        if m not in doc:
            violations.append(
                f"compile-observatory metric {m} missing from "
                f"docs/OBSERVABILITY.md")
        if m not in tests_text:
            violations.append(
                f"compile-observatory metric {m} not exercised by any "
                f"test")
    # runtime drift pass: warmed engine + mixed replay, observed ⊆
    # declared, warmup entry per declared family, zero post-warmup misses
    co.reset()
    co.enable()
    try:
        paddle.seed(0)
        model = LlamaForCausalLM(llama_tiny(num_hidden_layers=1))
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, 128, (1, n)).astype(np.int64)
                   for n in (13, 3, 21)]
        eng = ContinuousServingEngine(model, max_batch_size=2, max_len=48,
                                      token_budget=16,
                                      prefill_chunk_tokens=16)
        with eng:
            eng.warmup_programs()
            base = co.snapshot()["totals"]["misses"]
            threads = [threading.Thread(
                target=lambda p=p: eng.generate(p, max_new_tokens=3,
                                                timeout=300))
                for p in prompts]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        snap = co.snapshot()
        if snap["undeclared"]:
            violations.append(
                f"runtime-observed program families never declared: "
                f"{snap['undeclared']} (declared "
                f"{sorted(co.declared_families())})")
        missing_warmup = sorted(set(co.declared_families())
                                - set(co.warmup_entries()))
        if missing_warmup:
            violations.append(
                f"declared families without a registered warmup entry: "
                f"{missing_warmup}")
        post = snap["totals"]["misses"] - base
        if post:
            causes = [c["cause"]
                      for f in snap["families"].values()
                      for c in f.get("last_causes", [])]
            violations.append(
                f"{post} post-warmup trace-cache miss(es) in the mixed "
                f"replay (steady state must be 0); causes: "
                f"{causes[-int(post):]}")
        if verbose:
            for v in violations:
                print(f"FAIL {v}")
            print(f"compile observatory: {len(knobs)} knobs, "
                  f"{len(metrics)} metrics checked; families "
                  f"{sorted(snap['families'])} warmed, "
                  f"{post} post-warmup misses")
    finally:
        co.reset()
    return violations


def check(verbose=True):
    failures = []
    for item, mod_path, symbols in INVENTORY:
        try:
            mod = importlib.import_module(mod_path)
        except Exception as e:
            failures.append((item, mod_path, f"import failed: {e}"))
            continue
        missing = [s for s in symbols if not hasattr(mod, s)]
        if missing:
            failures.append((item, mod_path, f"missing {missing}"))
        elif verbose:
            print(f"  OK {item:<42} {mod_path}")
    if failures:
        for item, mod, why in failures:
            print(f"FAIL {item:<42} {mod}: {why}")
    if verbose:
        print(f"{len(INVENTORY) - len(failures)}/{len(INVENTORY)} "
              f"inventory items resolved")
    return failures


if __name__ == "__main__":
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.exit(1 if (check() or check_strategy_docs() or check_env_docs()
                   or check_fleet_knobs() or check_observability_catalog()
                   or check_alert_catalog() or check_training_observability()
                   or check_ledger_catalog() or check_controller_catalog()
                   or check_telemetry_plane() or check_serving_programs()
                   or check_quantized_config()
                   or check_compile_observatory() or check_kv_tier())
             else 0)

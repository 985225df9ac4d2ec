"""Kernels: the latent ragged q-block attention kernel's share of its
roofline in the traced window. Each call's work comes from the (q_lens,
context_lens) recorded at the kernel's Python entry: the absorbed form's
operations (what the kernel computes: every head against the one latent
row a token, values its prefix) over the bf16 peak, or the least bytes
(absorbed queries in, outputs out, each context's rows ONCE at 1,152 B a
token in bf16) over the HBM peak, whichever is larger; over the device time
of the Mosaic calls named ``_latent_qblock_device`` (the jitted wrapper in
``ops/pallas/ragged_paged_attention.py``; pinned by
``tests/test_tpu_compile.py``)."""
from benchmark import flops, flops_deepseek_v3 as ds, trace_reduce

KERNEL = r"^%_latent_qblock_device.*tpu_custom_call"


def read(run):
    trace, calls = run.get("trace"), run.get("kernel_calls")
    if not trace or not calls:
        return None
    config, peaks = run["config"], run["peaks"]
    if "kv_lora_rank" not in config:
        return None
    total = sum(trace_reduce.seconds_matching(ev, KERNEL)[0]
                for ev in trace["events"].values())
    if not total:
        return None
    least = 0.0
    for _, q_lens, ctx_lens in calls:
        spans = list(zip(q_lens, ctx_lens))
        least += flops.roofline_seconds(
            sum(ds.absorbed_attention_flops(config, q, c) for q, c in spans),
            ds.latent_attention_bytes(config, spans), peaks)[0]
    return 100.0 * least / total

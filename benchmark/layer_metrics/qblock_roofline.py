"""Kernels: the ragged q-block attention kernel's share of its roofline in
the traced window. The work of each call comes from the (q_lens,
context_lens) the benchmark recorded at the kernel's Python entry (traced
runs only); least time is the larger of required operations over the bf16
peak and least bytes (each span's queries and outputs, each context's keys
and values once, at the configuration's 2 bytes) over the HBM peak; the
device time is that of the Mosaic calls named ``_qblock_device`` in the
trace (the jitted wrapper's name in ``ops/pallas/ragged_paged_attention.py``;
a stable kernel ``name`` is on PERF.md's ``tracing`` list)."""
from benchmark import flops, trace_reduce

KERNEL = r"^%_qblock_device.*tpu_custom_call"


def read(run):
    trace, calls = run.get("trace"), run.get("kernel_calls")
    if not trace or not calls:
        return None
    config, peaks = run["config"], run["peaks"]
    least = 0.0
    for _, q_lens, ctx_lens in calls:
        spans = list(zip(q_lens, ctx_lens))
        least += flops.roofline_seconds(
            sum(flops.attention_flops(config, q, c) for q, c in spans),
            flops.ragged_attention_bytes(config, spans), peaks)[0]
    total = sum(trace_reduce.seconds_matching(ev, KERNEL)[0]
                for ev in trace["events"].values())
    if not total:
        return None
    return 100.0 * least / total

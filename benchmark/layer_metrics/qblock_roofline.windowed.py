"""Kernels: the ragged q-block attention kernel's share of its roofline in
the traced window of a model whose layers differ in what they see. Each
call's work comes from the (q_lens, context_lens, window) recorded at the
kernel's Python entry: a full layer's call (``window`` None) counts every
earlier key of each query, a window layer's the last ``window`` of them,
and reads each span's visible keys and values once
(``flops_smallthinker``); least time is the larger of operations over the
bf16 peak and bytes over the HBM peak; over the device time of the Mosaic
calls named ``_qblock_device`` (both kinds of layer run that wrapper).
``qblock_roofline`` counts whole contexts for every call and would read a
windowed call over 100 %, so this cell does not list it."""
from benchmark import flops, flops_smallthinker as st, trace_reduce

KERNEL = r"^%_qblock_device.*tpu_custom_call"


def read(run):
    trace, calls = run.get("trace"), run.get("kernel_calls")
    config = run.get("config") or {}
    if not trace or not calls or "sliding_window_layout" not in config \
            or len(calls[0]) < 4:
        return None
    total = sum(trace_reduce.seconds_matching(ev, KERNEL)[0]
                for ev in trace["events"].values())
    if not total:
        return None
    peaks, least = run["peaks"], 0.0
    for _, q_lens, ctx_lens, window in calls:
        spans = list(zip(q_lens, ctx_lens))
        least += flops.roofline_seconds(
            sum(st.attention_flops(config, q, c, window) for q, c in spans),
            st.attention_bytes(config, spans, window), peaks)[0]
    return 100.0 * least / total

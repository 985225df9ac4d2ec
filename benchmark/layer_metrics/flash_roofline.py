"""Kernels: the flash-attention kernels' share of their roofline in the
traced window. Least time by the roofline (the larger of required operations
over the bf16 peak and least bytes over the HBM peak; ``flops.py``) of every
forward call and of every backward (two kernels, dq and dkv, share one
backward's required work) at the cell's static shapes, over the kernels'
summed device time.

The kernels are told by the names today's trace shows: Mosaic custom calls
whose HLO name comes from the jitted wrappers ``_fwd`` / ``_bwd`` of
``ops/pallas/flash_attention.py``. A stable kernel ``name`` is on the
``tracing`` list in PERF.md.
"""
from benchmark import flops, trace_reduce

FWD = r"^%jvp_jit__fwd_.*tpu_custom_call"
BWD = r"^%transpose_jvp_jit__bwd_.*tpu_custom_call"


def read(run):
    trace = run.get("trace")
    if not trace or "batch" not in run:
        return None
    config, peaks = run["config"], run["peaks"]
    rows = run["batch"] // run["chips"] or 1
    least = total = 0.0
    for events in trace["events"].values():
        fwd_s, n_fwd = trace_reduce.seconds_matching(events, FWD)
        bwd_s, n_bwd = trace_reduce.seconds_matching(events, BWD)
        t_fwd, _ = flops.roofline_seconds(
            flops.flash_flops(config, rows, run["seq"], False),
            flops.flash_bytes(config, rows, run["seq"], False), peaks)
        t_bwd, _ = flops.roofline_seconds(
            flops.flash_flops(config, rows, run["seq"], True),
            flops.flash_bytes(config, rows, run["seq"], True), peaks)
        least += n_fwd * t_fwd + (n_bwd / 2) * t_bwd
        total += fwd_s + bwd_s
    if not total:
        return None
    return 100.0 * least / total

"""Kernels: the one-token KDA kernel's share of its roofline in the traced
window. Each call's work comes from its ``attn/kda_step`` span's ``rows``
(the decode rows of the tick, a call a KDA layer): the least bytes (each
row's state read and written once, its rows in and out) over the HBM peak,
or the recurrence's operations over the bf16 peak, whichever is longer;
over the device time of the Mosaic calls named ``kda_step`` (the Pallas
kernel of ``ops/pallas/kda.py::_kda_step_device``). A call's padding rows
are no work."""
from benchmark import flops, flops_bailing_hybrid as bh, kda_spans, \
    trace_reduce

KERNEL = r"^%kda_step.*tpu_custom_call"


def read(run):
    trace, config = run.get("trace"), run.get("config") or {}
    kept = kda_spans.kept(run)
    if not trace or not kept or "kda_lower_bound" not in config:
        return None
    total = sum(trace_reduce.seconds_matching(ev, KERNEL)[0]
                for ev in trace["events"].values())
    if not total:
        return None
    least = sum(flops.roofline_seconds(
        bh.recurrence_flops(config, a["rows"]),
        bh.kda_step_bytes(config, a["rows"]), run["peaks"])[0]
        for a, _ in kept[kda_spans.STEP])
    return 100.0 * least / total

"""Kernels: the chunked-scan KDA kernel's share of its roofline in the
traced window. Each call's work comes from its ``attn/kda_chunk`` span's
``tokens`` and ``spans`` (the tick's prefill spans, a call a KDA layer): the
recurrence's required operations (``2 x 3 x d x d`` a head a token, not the
chunkwise form's) over the bf16 peak, or the least bytes (each span's state
read and written once, each token's rows in and out) over the HBM peak,
whichever is longer; over the device time of the Mosaic calls named
``kda_chunk`` (``ops/pallas/kda.py::_kda_chunk_device``). The kernel's
chunk size and its padding are no work."""
from benchmark import flops, flops_bailing_hybrid as bh, kda_spans, \
    trace_reduce

KERNEL = r"^%kda_chunk.*tpu_custom_call"


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
        bh.recurrence_flops(config, a["tokens"]),
        bh.kda_chunk_bytes(config, a["tokens"], a["spans"]),
        run["peaks"])[0] for a, _ in kept[kda_spans.CHUNK])
    return 100.0 * least / total

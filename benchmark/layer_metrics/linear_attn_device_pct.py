"""Kernels: how much of the step the linear-attention mechanism is: device
time of the two KDA kernels (the Mosaic calls named ``kda_step`` and
``kda_chunk``) over the traced window. The convolution, the packing of the
chunk kernel's rows and the gates run as XLA fusions of the compiled
pre-state program and of the kernels' jitted wrappers, without a name of
their own in the trace, and are not in it."""
from benchmark import trace_reduce

KERNEL = r"^%kda_(step|chunk).*tpu_custom_call"


def read(run):
    trace = run.get("trace")
    if not trace:
        return None
    total = sum(trace_reduce.seconds_matching(ev, KERNEL)[0]
                for ev in trace["events"].values())
    if not total:
        return None
    return 100.0 * total / (len(trace["events"]) * trace["window_s"])

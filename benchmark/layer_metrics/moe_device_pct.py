"""Kernels: the expert sum's grouped matrix products' share of the traced
window: device time of the ``ragged-dot`` custom calls (``jax.lax.
ragged_dot``: three a layer, gate / up / down, inside the compiled
post-attention program under ``named_scope("moe/experts")``; the name is
pinned by ``tests/test_tpu_compile.py``) over the window. The sort, the
gathers and the shared expert are XLA fusions without a name of their own
and are not in it."""
from benchmark import trace_reduce

KERNEL = r"^%ragged-dot"


def read(run):
    trace = run.get("trace")
    if not trace:
        return None
    total = sum(trace_reduce.seconds_matching(ev, KERNEL)[0]
                for ev in trace["events"].values())
    if not total:
        return None
    return 100.0 * total / (len(trace["events"]) * trace["window_s"])

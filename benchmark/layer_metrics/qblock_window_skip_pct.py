"""Kernels: how much of the walk the window takes away. Over the window
layers' ``attn/qblock`` spans of the traced window (the program's own
spans, whose args say ``jobs``, the (q-block, KV page) jobs the call
walked, and ``jobs_without_window``, what the same call would have walked
with no lower bound): 100 x (1 - jobs / jobs_without_window). 0 where no
context has outgrown its window; a program whose spans say neither (one
without windowed layers) reads as nothing."""


def read(run):
    args = run.get("window_span_args")
    if not args:
        return None
    walked = sum(a["jobs"] for a in args)
    unbounded = sum(a["jobs_without_window"] for a in args)
    return 100.0 * (1.0 - walked / unbounded) if unbounded else None

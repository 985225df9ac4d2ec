"""Kernels: how far the q-block kernel's grid is made of jobs that exist.
Over the ``attn/qblock`` spans of the traced window's ticks (the program's
own, with their args): the real (job, KV head) pairs, ``real_jobs`` x the
configuration's KV heads, over the pairs the grids walk, ``steps`` x the KV
heads a step covers. ``jobs`` is the walk of one KV head, so a step covers
``KV heads x jobs / steps`` of them: all of them where the grid has the one
axis ``(jobs,)``, one where it has a head axis too. A job is one (q-block,
KV page) pair; one without an owner (``real_jobs`` leaves it out) pads a
list to a static grid's length or stands in a q-block of padding rows, and
costs a grid step that computes nothing.

Read from ``paddle_tpu.profiler.get_tracer().completed()``:
``benchmark/tick_spans.program_spans`` drops the args. Kept are the spans
that begin between the first and the last stamp of ``run["kernel_calls"]``
(inside the window, as ``tick_spans`` keeps its ticks) and carry both
``real_jobs`` and ``steps``; a program whose spans lack them (before PR 28:
a grid of ``blocks x the longest block's jobs``, which said neither) reads
as nothing. A latent pool's calls (``latent=1``) are another kernel's.
"""
from __future__ import annotations

SPAN = "attn/qblock"


def kept_args(run):
    """The args of the window's ``attn/qblock`` spans that say both
    counts, or None where the run or the program has nothing to read."""
    calls = run.get("kernel_calls")
    if not calls:
        return None
    try:
        from paddle_tpu.profiler import get_tracer
    except ImportError:
        return None
    tracer = get_tracer()
    origin = getattr(tracer, "origin", None)
    completed = getattr(tracer, "completed", None)
    if origin is None or completed is None:
        return None
    first, last = min(c[0] for c in calls), max(c[0] for c in calls)
    return [s.args for s in completed()
            if s.name == SPAN and first <= origin + s.ts <= last
            and s.args and "real_jobs" in s.args and "steps" in s.args
            and not s.args.get("latent")] or None


def fill_pct(args, kv_heads):
    real = walked = 0.0
    for a in args:
        heads_a_step = kv_heads * a["jobs"] / a["steps"]
        real += a["real_jobs"] * kv_heads
        walked += a["steps"] * heads_a_step
    return 100.0 * real / walked if walked else None


def read(run):
    args = kept_args(run)
    if args is None:
        return None
    return fill_pct(args, run["config"]["num_key_value_heads"])

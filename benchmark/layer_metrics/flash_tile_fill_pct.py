"""Kernels: how far the products inside the flash kernels' computed tiles
are ones the attention asked for. A tile that crosses the causal diagonal
computes its whole ``block_q x block_k`` square and masks the half above
the diagonal away, so a larger tile (fewer grid steps, each a fuller MXU
product) pays with a lower share: 97 % at 128 x 128, 89 % at 512 x 512,
80 % at 1,024 x 1,024 for a causal call of 4,096 tokens.

Read from the program's own arithmetic, ``grid_plan`` of
``paddle_tpu/ops/pallas/flash_attention.py``, at the cell's static shapes
(the sequence length of the run, the configuration's head_dim and dtype,
causal, no offsets): the (query, key) pairs the attention needs, summed over
the three kernels a layer calls once each (forward, dq, dkv), over the pairs
inside the tiles their grids compute. A program without ``grid_plan``
(before PR 30: tiles of 128 x 128 from two environment variables, which said
nothing) reads as nothing.
"""
from __future__ import annotations

import importlib


def plan_of(run):
    """``grid_plan`` at the run's shapes, or None where the run or the
    program has nothing to read."""
    if "seq" not in run or "config" not in run:
        return None
    try:
        program = importlib.import_module(
            "paddle_tpu.ops.pallas.flash_attention")
    except ImportError:
        return None
    grid_plan = getattr(program, "grid_plan", None)
    if grid_plan is None:
        return None
    config = run["config"]
    dtype = config.get("trainer", {}).get("dtype", "bfloat16")
    return grid_plan(run["seq"], run["seq"], config["head_dim"], dtype,
                     causal=True, q_offset=0, kv_offset=0)


def fill_pct(plan):
    needed = sum(k["pairs_needed"] for k in plan.values())
    computed = sum(k["pairs_computed"] for k in plan.values())
    return 100.0 * needed / computed if computed else None


def read(run):
    plan = plan_of(run)
    return None if plan is None else fill_pct(plan)

"""Whole-step share of the chips' bf16 peak: required forward and backward
operations per token (no recomputation, no embedding lookup) times the tokens
trained in the window, over window x chips x peak."""
from benchmark import harness


def read(run):
    if "flops_per_token" not in run:
        return None
    return harness.mfu_pct(run["flops_per_token"] * run["tokens"],
                           run["window_s"], run["chips"],
                           run["peaks"]["bf16_flops"])

"""Experts: the busiest held expert's tokens over the held experts' mean,
over the window (the program's device counter ``moe_expert_tokens``, summed
over expert layers and ticks, read back inside each tick's one sync): 1 is
an even load."""


def read(run):
    per_expert = (run.get("counters") or {}).get("moe_expert_tokens")
    if per_expert is None or not sum(per_expert):
        return None
    return float(max(per_expert)) * len(per_expert) / float(sum(per_expert))

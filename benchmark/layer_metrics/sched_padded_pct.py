"""Scheduler: share of the token positions the ticks' programs processed
that were bucket padding (``padded_tokens_total`` / ``useful_tokens_total``
over the window)."""


def read(run):
    c = run.get("counters") or {}
    if not c.get("padded_tokens_total"):
        return None
    return 100.0 * (1.0 - c["useful_tokens_total"] / c["padded_tokens_total"])

"""Scheduler: 95th percentile of send-to-first-token over the requests sent
in the window, in the closed loop (a few tens of requests a window today:
too few for an end-to-end bound, so it is reported here)."""
from benchmark import harness


def read(run):
    ttft = (run.get("window") or {}).get("ttft")
    if not ttft:
        return None
    return 1e3 * harness.percentile(ttft, 95)

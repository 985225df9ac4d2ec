"""Scheduler: 95th percentile over all gaps between consecutive tokens of
all requests in the window, in the closed loop. A gap is a tick, and a window
holds some 55 ticks today: the 95th percentile is the third slowest of them,
and flips between 1.0 and 1.4 s from run to run (PERF.md, PR 24), too few
samples for an end-to-end bound, so it is reported here."""
from benchmark import harness


def read(run):
    gaps = (run.get("window") or {}).get("gaps")
    if not gaps:
        return None
    return 1e3 * harness.percentile(gaps, 95)

"""Scheduler: the window's seconds over the ragged ticks the engine ran in
it (its own ``ragged_steps`` counter, read at both ends of the window)."""


def read(run):
    ticks = (run.get("counters") or {}).get("ragged_steps")
    if not ticks:
        return None
    return 1e3 * run["window_s"] / ticks

"""KV cache: what the window group still holds of the live contexts. Four
times a second the benchmark's own thread reads, over the slots in use,
the tokens of their contexts and those of them whose blocks the window
group has not released (``engine._cache``: ``lens`` and the group's first
live block a slot); the metric is the tokens held over the tokens live,
summed over the window's samples. 100: nothing was released; a long context
past its window holds ``window + a chunk`` of it."""


def read(run):
    samples = run.get("kv_samples")
    if not samples:
        return None
    live = sum(s[2] for s in samples)
    held = sum(s[3][0] for s in samples if s[3])
    if not live or not any(s[3] for s in samples):
        return None
    return 100.0 * held / live

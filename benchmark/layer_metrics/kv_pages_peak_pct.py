"""KV cache: peak share of the page pool in use during the window, sampled
four times a second from the benchmark's own thread (``engine._cache`` is
the engine's introspection handle; no program change was needed)."""


def read(run):
    pages = run.get("pages")
    if not pages:
        return None
    return 100.0 * max(used / cap for _, used, cap in pages)

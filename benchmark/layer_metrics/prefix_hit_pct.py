"""KV cache: share of the prompt tokens of the requests admitted in the
window that the prefix cache served (the program's host counters
``prompt_tokens_cached`` / ``prompt_tokens_admitted``)."""


def read(run):
    c = run.get("counters") or {}
    if not c.get("prompt_tokens_admitted"):
        return None
    return 100.0 * c["prompt_tokens_cached"] / c["prompt_tokens_admitted"]

"""Kernels: prefill tokens over the tokens the chunk kernel's grid covered
(the program's counters ``kda_chunk_tokens`` and
``kda_chunk_padded_tokens``, a tick's plan counted once, over the window):
a job of 16 rows that a span's tail fills in part, and the jobs that pad
the list to its static length, cost a grid step a head each."""


def read(run):
    c = run.get("counters") or {}
    if not c.get("kda_chunk_padded_tokens"):
        return None
    return 100.0 * c["kda_chunk_tokens"] / c["kda_chunk_padded_tokens"]

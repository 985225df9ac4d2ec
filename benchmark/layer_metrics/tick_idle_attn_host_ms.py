"""Kernels: mean per traced tick of the device's idle time while the host
was in an attention kernel's host half: ``attn/qblock``,
``attn/qblock_schedule``, ``attn/kda_step`` or ``attn/kda_chunk``
(``benchmark/idle_spans.py``)."""
from benchmark import idle_spans


def read(run):
    return idle_spans.idle_ms(run, "tick_idle_attn_host_ms")

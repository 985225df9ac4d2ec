"""Kernels: mean per traced tick of the self times of ``attn/qblock`` and
``attn/qblock_schedule``, summed over the layers: the q-block kernel's host
half (reading the descriptors back, the job schedule's Python loop, the
row broadcast, the kernel's dispatch)."""
from benchmark import tick_spans


def read(run):
    return tick_spans.phase_ms(run, "tick_attn_host_ms")

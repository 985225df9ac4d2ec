"""KV cache: mean per traced tick of the self times of ``kv/admit`` (slot and
page assignment, prefix hashing and lookup) and ``kv/begin_ragged`` (page
allocation and copy-on-write for the tick's spans). The device is idle
meanwhile."""
from benchmark import tick_spans


def read(run):
    return tick_spans.phase_ms(run, "tick_kv_host_ms")

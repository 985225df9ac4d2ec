"""Scheduler: mean per traced tick of the self time of ``serve/schedule``: queue
drain, cancellation sweep, drafting, packing the tick's spans and its flat
token batch, the telemetry gauges. Nothing is queued on the device
meanwhile, so this is idle the scheduler owns."""
from benchmark import tick_spans


def read(run):
    return tick_spans.phase_ms(run, "tick_schedule_ms")

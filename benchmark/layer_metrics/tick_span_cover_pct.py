"""Scheduler: the six ``tick_*_ms`` phases' self times over the traced ticks'
extent (first tick's start to last tick's end). Work that moves outside the
spans, or between two ticks, shows here as a falling share."""
from benchmark.tick_spans import cover_pct as read  # noqa: F401

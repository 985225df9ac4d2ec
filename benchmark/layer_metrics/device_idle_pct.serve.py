"""Device: share of the traced window in which no operation ran on the
device, in the cells that report ``serve_tok_s``."""
from benchmark.trace_reduce import idle_pct as read  # noqa: F401

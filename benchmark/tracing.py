"""The traced window: ``jax.profiler`` around it, the trace reduced once the
run's load has stopped, and the files removed."""
from __future__ import annotations

import glob
import json
import os
import shutil
import time

from benchmark import harness, trace_reduce


class Tracer:
    def __init__(self, ctx):
        self.dir = os.path.join(harness.ROOT, ".bench_trace",
                                ctx["cell"]["name"])
        self.dump = ctx.get("dump_trace")
        self.t0 = None

    def start(self):
        """Device events and the benchmark's own host spans only: the
        profiler's Python tracer (on by default) and its host events made a
        serving tick 1.6 s where an untraced one takes 0.9 s, and a 50 s
        trace 184 MB (my chip run, PR 24)."""
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.t0 = time.perf_counter()

    def stop(self):
        """Ends the traced window; ``reduce`` reads what it wrote."""
        import jax
        self.window_s = time.perf_counter() - self.t0
        jax.profiler.stop_trace()

    def reduce(self):
        """-> the reduced trace, with ``window_s`` by the host's clock. The
        files are removed: a trace is large, and the host keeps what was
        written."""
        try:
            files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if len(files) != 1:
                raise FileNotFoundError(
                    f"expected one .xplane.pb under {self.dir}, found "
                    f"{files}")
            harness.log(f"trace: {os.path.getsize(files[0]) / 1e6:.1f} MB "
                        f"written, read and removed")
            trace = trace_reduce.from_xplane(files[0])
            if self.dump:
                os.makedirs(os.path.dirname(self.dump) or ".", exist_ok=True)
                with open(self.dump, "w") as f:
                    json.dump(trace_reduce.summary_for_dump(trace), f)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        reduced = trace_reduce.reduce_trace(trace)
        reduced["window_s"] = self.window_s
        return reduced


def span(name):
    """A host span on the profiler's clock, from the benchmark's own files."""
    import jax
    return jax.profiler.TraceAnnotation(trace_reduce.HOST_SPAN_PREFIX + name)

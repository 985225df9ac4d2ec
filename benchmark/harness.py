"""What every driver shares: the manifest and its files found by name, the
device and its peaks, compile counting, the statistics of the end-to-end
metrics, the traced window, the comparison's printout and the result line.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")


class Refused(Exception):
    """The run cannot give a device result; nothing is printed on stdout."""


# -- the manifest and the files it names ------------------------------------

def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_manifest(root=ROOT):
    return load_json(os.path.join(root, "BENCHMARK.json"))


def find_cell(manifest, name, root=ROOT):
    """-> (cell, configuration file's contents, traffic file's contents)."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json "
                      f"(has: {sorted(cells)})")
    cell = cells[name]
    cfg_entry = next(c for c in manifest["configs"]
                     if c["name"] == cell["config"])
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))
    return cell, config, traffic


def metrics_of(manifest, group, cell_name):
    """The metrics of ``group`` ('end_to_end' / 'per_layer') this cell
    reports: those without a ``workloads`` key, and those that list it."""
    return [m for m in manifest[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def load_reader(metric_name):
    """The per-layer metric's own file: ``layer_metrics/<name>.py`` with a
    ``read(run)`` that returns a number, or None where it finds nothing."""
    path = os.path.join(HERE, "layer_metrics", metric_name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_layer_metric_" + metric_name.replace(".", "_"), path)
    if spec is None or not os.path.exists(path):
        raise Refused(f"per-layer metric {metric_name!r} has no reader at "
                      f"{path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_driver(name):
    return importlib.import_module(f"benchmark.drivers.{name}")


# -- the device -------------------------------------------------------------

def load_peaks(kind):
    table = load_json(os.path.join(HERE, "peaks.json"))
    if kind not in table or kind == "source":
        raise Refused(f"device kind {kind!r} is not in benchmark/peaks.json "
                      f"(has: {sorted(k for k in table if k != 'source')}); "
                      f"a device that is not in the table is an error")
    return table[kind]


def require_chips(chips):
    """The device record and its peaks, or Refused: any platform but a TPU,
    fewer chips than the cell asks for, a kind with no peaks."""
    import jax
    devs = jax.devices()
    rec = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if rec["platform"] != "tpu":
        raise Refused(f"needs a TPU, jax reports {rec}; nothing ran")
    if rec["count"] < chips:
        raise Refused(f"the cell needs {chips} chips, jax reports {rec}")
    global DEVICE_AT
    DEVICE_AT = time.perf_counter()
    return rec, load_peaks(rec["kind"])


def memory_peak_bytes(chips):
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def enable_compile_cache():
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` where that is
    set, else at one fixed path inside the checkout (the path is part of the
    cache's key). Every program is cached, however quick its compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileWatch:
    """Counts jax's own compile events (after chip_smoke's): programs built
    or loaded (``backend_compiles``: jax times a load from the persistent
    cache under the same event) with their seconds, persistent-cache hits
    and misses (a miss is a real compile). While ``names`` is a list, the
    name of each such program is kept too (jax passes ``fun_name``)."""

    def __init__(self):
        from jax import monitoring
        self.names = None
        self.compiles = 0
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += seconds
            if self.names is not None:
                self.names.append(str(kw.get("fun_name")))

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return {"backend_compiles": self.compiles,
                "compile_s": round(self.compile_s, 3),
                "cache_hits": self.hits, "cache_misses": self.misses}

    @staticmethod
    def between(a, b):
        return {k: round(b[k] - a[k], 3) for k in a}


# -- statistics of the end-to-end metrics -----------------------------------

def percentile(values, p):
    """Linear interpolation between closest ranks (numpy's default)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    k = (len(xs) - 1) * p / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def rate(count, seconds):
    return count / seconds


def mfu_pct(flops, seconds, chips, peak_flops):
    return 100.0 * flops / (seconds * chips * peak_flops)


# -- the comparison that decides ``correct`` --------------------------------

def judge(checks):
    """``checks`` is [(short name, number, limit)]; a number passes at or
    under its limit. NaN fails."""
    return all(isinstance(v, (int, float)) and v == v and v <= lim
               for _, v, lim in checks)


# -- output ------------------------------------------------------------------

def log(msg):
    """One line of the run's log on standard error (standard output
    carries the result line and nothing else), stamped with the process's
    age: the stamps say which part of set-up a slow run spent its time in."""
    print(f"benchmark: [{since_start():7.2f} s] {msg}", file=sys.stderr,
          flush=True)


def emit(result, checks):
    """The comparison on stderr's last lines and, as the last key of the
    last line of stdout, in the result."""
    sys.stdout.flush()
    for name, v, lim in checks:
        print(f"check {name}: {v!r} (limit {lim!r}) "
              f"{'ok' if judge([(name, v, lim)]) else 'FAILED'}",
              file=sys.stderr)
    sys.stderr.flush()
    result = dict(result)
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in checks}
    print(json.dumps(result), flush=True)


def metric_values(entries, values):
    """{name: {"value", "unit"}} for the manifest's entries that have a
    value; a reader that found nothing leaves its metric out."""
    out = {}
    for m in entries:
        v = values.get(m["name"])
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def _process_age():
    """Seconds since the kernel started this process (0 where /proc cannot
    say), so that ``setup_s`` counts the interpreter's own start too."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(uptime - start_ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return 0.0


#: ``perf_counter`` at the start of the process
START = time.perf_counter() - _process_age()


#: ``perf_counter`` when jax handed over the chips (``require_chips``)
DEVICE_AT = None


def since_start():
    """Seconds since the start of the process: the stamp of every log line,
    and ``setup_s`` where a driver reads it as its window opens: the
    interpreter's start, ``import jax``, the TPU runtime's start, the
    program's import, the model, the weights, the warm-up and, in a cold
    run, compilation."""
    return time.perf_counter() - START


def pre_device_seconds():
    """The part of ``setup_s`` before jax handed over the chips (None where
    no chip was asked for): no code of the repo runs in it."""
    return None if DEVICE_AT is None else DEVICE_AT - START

"""Compile observatory: runtime program-cache accounting with
retrace-**cause** attribution (ISSUE 18).

The paper's ``to_static``/Program-IR heritage makes *compiled program
identity* the unit of TPU performance: the ragged token buckets, pow2
draft-batch buckets and q-block grids exist precisely so steady-state
traffic re-enters warm programs. But until this module, compiles were a
static ``check_inventory`` concept — nothing at serve time recorded
whether a forward actually hit a warm signature, and a bucket
off-by-one showed up only as mysterious p99s. The observatory makes
every jit/compile boundary a first-class observed event:

* each instrumented call site (ragged tick, legacy prefill chunk,
  fixed-shape decode, batched draft forward, donated training steps)
  reports its **program family** plus a full
  **argument signature** (array shapes/dtypes and static args) via
  :func:`observe`;
* a signature seen before for its family is a cache **hit**; an unseen
  one is a **miss** (a trace/compile), and the observatory diffs it
  against the *last signature seen* for that family to emit a
  structured retrace cause — ``arg `tokens` dim0 136∉{8,16}: bucket
  miss``, ``static arg `weight_dtype` int8→bf16``, ``new family`` —
  naming the exact argument and offending dimension;
* hits/misses/compile-seconds surface as ``paddle_compile_*`` metrics
  (with a ``family="all"`` rollup series so
  :func:`paddle_tpu.profiler.alerts.recompile_storm_rule` can burn-rate
  them), every miss is appended to the correlated eventlog (kind
  ``compile``), a bounded :func:`snapshot` backs the ``/compile``
  exporter route and flight-recorder dumps, and per-family compile
  seconds fold into ``profiler.cost_table()``;
* engines *declare* their program families up front
  (:func:`declare_family`, with per-arg bucket sets and a registered
  warmup entry) so the observatory can distinguish "legitimate warmup
  of a declared bucket" from "undeclared shape churn" — a family
  observed at serve time that CI never declared raises the
  ``paddle_compile_undeclared_families`` gauge (alertable via
  :func:`paddle_tpu.profiler.alerts.family_drift_rule`).

``PADDLE_COMPILE_OBSERVATORY=0`` disables the whole plane (call sites
are one bool check away from free); the module is stdlib-only so the
eventlog/report tooling can consume its records anywhere.
"""
from __future__ import annotations

import os
import threading

__all__ = [
    "CompileObservatory", "get_observatory", "observe", "declare_family",
    "register_warmup", "declared_families", "warmup_entries", "run_warmup",
    "undeclared_families", "record_program", "snapshot", "cost_section",
    "tensor_arg",
    "static_arg", "format_signature", "enable", "disable", "reset",
    "is_enabled",
]

SCHEMA = "paddle_compile_observatory/1"

#: retained cause records per family (newest kept) and total distinct
#: signatures tracked per family — bounds memory under pathological churn
MAX_CAUSES_PER_FAMILY = 64
MAX_SIGNATURES_PER_FAMILY = 4096


def _env_truthy(v) -> bool:
    return str(v).lower() not in ("", "0", "false", "none")


_ENABLED = _env_truthy(os.environ.get("PADDLE_COMPILE_OBSERVATORY", "1"))


def is_enabled() -> bool:
    return _ENABLED


def enable():
    global _ENABLED
    _ENABLED = True


def disable():
    global _ENABLED
    _ENABLED = False


# ---------------------------------------------------------------------------
# signature descriptors


def tensor_arg(shape, dtype):
    """Signature descriptor for an array argument: shape + dtype. Any
    shape-like (tuple/list/np shape) and any dtype-like accepted."""
    return ("array", tuple(int(d) for d in shape), str(dtype))


def static_arg(value):
    """Signature descriptor for a static (non-array) argument. Values
    must be hashable; anything exotic is stringified."""
    if isinstance(value, (int, float, bool, str, bytes, type(None))):
        return ("static", value)
    return ("static", str(value))


def _fmt_desc(desc):
    if desc[0] == "array":
        shape = "x".join(str(d) for d in desc[1])
        return f"{desc[2]}[{shape}]"
    return repr(desc[1])


def format_signature(sig) -> str:
    """Human form of a canonical signature, e.g.
    ``tokens=int64[16], weight_dtype='int8'``."""
    return ", ".join(f"{k}={_fmt_desc(v)}" for k, v in sig)


def _canonical(signature):
    """dict name -> descriptor  =>  hashable, order-stable tuple."""
    return tuple(sorted((str(k), v) for k, v in signature.items()))


def _bucket_set(buckets, arg, dim):
    """Declared bucket values for (arg, dim), or None if undeclared.
    ``buckets`` maps arg name -> iterable of ints (dim 0) or
    dict dim -> iterable of ints."""
    if not buckets:
        return None
    per_arg = buckets.get(arg)
    if per_arg is None:
        return None
    if isinstance(per_arg, dict):
        vals = per_arg.get(dim)
        return None if vals is None else set(int(v) for v in vals)
    return set(int(v) for v in per_arg) if dim == 0 else None


def _diff_cause(prev, sig, buckets) -> str:
    """The structured retrace cause: diff the missing signature against
    the last one seen for its family."""
    if prev is None:
        return "new family"
    prev_d, sig_d = dict(prev), dict(sig)
    causes = []
    for k, v in sig_d.items():
        pv = prev_d.get(k)
        if pv == v:
            continue
        if pv is None:
            causes.append(f"new arg `{k}` {_fmt_desc(v)}")
            continue
        if v[0] == "array" and pv[0] == "array":
            pshape, shape = pv[1], v[1]
            if len(pshape) != len(shape):
                causes.append(
                    f"arg `{k}` rank {len(pshape)}→{len(shape)}")
            else:
                for d, (a, b) in enumerate(zip(pshape, shape)):
                    if a == b:
                        continue
                    declared = _bucket_set(buckets, k, d)
                    if declared is not None and b not in declared:
                        decl = ",".join(str(x) for x in sorted(declared))
                        causes.append(f"arg `{k}` dim{d} "
                                      f"{b}∉{{{decl}}}: bucket miss")
                    elif declared is not None:
                        causes.append(
                            f"arg `{k}` dim{d} {a}→{b}: new bucket")
                    else:
                        causes.append(f"arg `{k}` dim{d} {a}→{b}")
            if pv[2] != v[2]:
                causes.append(f"arg `{k}` dtype {pv[2]}→{v[2]}")
        elif v[0] == "static" and pv[0] == "static":
            causes.append(f"static arg `{k}` {pv[1]}→{v[1]}")
        else:
            causes.append(f"arg `{k}` kind {pv[0]}→{v[0]}")
    for k, pv in prev_d.items():
        if k not in sig_d:
            causes.append(f"arg `{k}` removed")
    return "; ".join(causes) or "signature churn"


# ---------------------------------------------------------------------------
# observatory


class _Family:
    __slots__ = ("signatures", "last_sig", "hits", "misses",
                 "compile_s", "causes", "overflowed")

    def __init__(self):
        self.signatures = {}     # canonical sig -> observation count
        self.last_sig = None
        self.hits = 0
        self.misses = 0
        self.compile_s = 0.0
        self.causes = []         # newest-last [{cause, signature, seconds}]
        self.overflowed = False


class CompileObservatory:
    """Process-wide program-cache model: per-family signature tables,
    hit/miss accounting, cause attribution, declared-inventory drift."""

    def __init__(self):
        self._lock = threading.RLock()
        self._families = {}      # name -> _Family
        self._declared = {}      # name -> {"buckets": ..., "static": ...}
        self._warmups = {}       # name -> callable
        self._programs = {}      # name -> hlo_fusions.summary of its text
        self._tele = None
        self._provider = False

    # -- declaration -------------------------------------------------------

    def declare_family(self, name, buckets=None, warmup=None, static=None):
        """Declare a program family: its per-arg bucket sets (arg name ->
        iterable of dim-0 sizes, or dict dim -> iterable) and optionally
        a warmup entry — a zero-arg callable that compiles every
        declared signature of the family up front. Idempotent; the
        latest declaration wins (one serving config per process)."""
        name = str(name)
        with self._lock:
            self._declared[name] = {
                "buckets": dict(buckets) if buckets else {},
                "static": dict(static) if static else {},
            }
            if warmup is not None:
                self._warmups[name] = warmup
        return name

    def register_warmup(self, name, fn):
        with self._lock:
            self._warmups[str(name)] = fn

    def declared_families(self):
        with self._lock:
            return dict(self._declared)

    def warmup_entries(self):
        with self._lock:
            return dict(self._warmups)

    def undeclared_families(self):
        """Families observed at runtime that were never declared — the
        drift the inventory guard exists to prevent."""
        with self._lock:
            return sorted(set(self._families) - set(self._declared))

    def run_warmup(self, families=None):
        """Execute registered warmup entries (all, or the named subset);
        returns {family: result} — each entry pre-compiles its family's
        declared signatures so steady-state traffic sees zero misses."""
        with self._lock:
            entries = [(n, fn) for n, fn in sorted(self._warmups.items())
                       if families is None or n in families]
        return {n: fn() for n, fn in entries}

    # -- observation -------------------------------------------------------

    def observe(self, family, signature, seconds=None, trace_id=None):
        """Record one program-boundary execution. ``signature`` maps arg
        name -> :func:`tensor_arg`/:func:`static_arg` descriptor;
        ``seconds`` is the call's wall time (attributed as compile cost
        on a miss — the first execution of a shape pays trace+compile).
        Returns ``{"family", "miss", "cause", "seconds"}``."""
        family = str(family)
        sig = _canonical(signature)
        with self._lock:
            fam = self._families.get(family)
            if fam is None:
                fam = self._families[family] = _Family()
            known = sig in fam.signatures
            declared = self._declared.get(family)
            if known:
                fam.hits += 1
                fam.signatures[sig] += 1
                cause = None
            else:
                fam.misses += 1
                fam.compile_s += float(seconds or 0.0)
                buckets = declared["buckets"] if declared else None
                cause = _diff_cause(fam.last_sig, sig, buckets)
                if declared is None:
                    cause = f"{cause} (family undeclared)"
                if len(fam.signatures) < MAX_SIGNATURES_PER_FAMILY:
                    fam.signatures[sig] = 1
                else:
                    fam.overflowed = True
                fam.causes.append({"cause": cause,
                                   "signature": format_signature(sig),
                                   "seconds": float(seconds or 0.0)})
                del fam.causes[:-MAX_CAUSES_PER_FAMILY]
            fam.last_sig = sig
            n_undeclared = len(set(self._families) - set(self._declared))
        self._record_metrics(family, known, seconds, n_undeclared)
        if not known:
            self._record_event(family, cause, seconds, trace_id, sig)
        return {"family": family, "miss": not known, "cause": cause,
                "seconds": float(seconds or 0.0)}

    def record_program(self, family, text):
        """Keep, with a program family, what its COMPILED text says of
        its matrix products (``hlo_fusions.summary`` of
        ``compiled.as_text()``): how many fusions hold a product, how many
        of them recompute a transcendental chain on an operand side, and
        the compiler's estimated cycles of each group. Called by whoever
        holds the compiled program (a sandbox compile for a described
        chip, a test); shown under ``programs`` in :meth:`snapshot`."""
        from . import hlo_fusions
        seen = hlo_fusions.summary(text)
        with self._lock:
            self._programs[str(family)] = seen
        return seen

    def _telemetry(self):
        if self._tele is None:
            from .telemetry import get_registry
            reg = get_registry()
            self._tele = {
                "hits": reg.counter(
                    "paddle_compile_hits_total",
                    "program-cache hits per family (signature seen "
                    "before; family=\"all\" is the cross-family rollup "
                    "the recompile-storm burn-rate rule consumes)",
                    labels=("family",)),
                "misses": reg.counter(
                    "paddle_compile_misses_total",
                    "trace/compile events per family (unseen signature; "
                    "family=\"all\" rollup)", labels=("family",)),
                "seconds": reg.histogram(
                    "paddle_compile_seconds",
                    "wall seconds of compile (miss) executions per "
                    "program family", labels=("family",)),
                "undeclared": reg.gauge(
                    "paddle_compile_undeclared_families",
                    "program families observed at runtime that the "
                    "declared inventory does not contain (drift)"),
            }
        if not self._provider:
            self._provider = True
            try:
                from . import flight_recorder
                flight_recorder.register_state_provider(
                    "compile_observatory", self.snapshot)
            except Exception:
                pass
        return self._tele

    def _record_metrics(self, family, known, seconds, n_undeclared):
        try:
            tele = self._telemetry()
            kind = "hits" if known else "misses"
            tele[kind].inc(family=family)
            tele[kind].inc(family="all")
            if not known and seconds:
                tele["seconds"].observe(float(seconds), family=family)
            tele["undeclared"].set(float(n_undeclared))
        except Exception:
            pass

    def _record_event(self, family, cause, seconds, trace_id, sig):
        try:
            from . import eventlog
            eventlog.log_event("compile", trace_id=trace_id,
                               src="compile_observatory", family=family,
                               cause=cause,
                               seconds=round(float(seconds or 0.0), 6),
                               signature=format_signature(sig))
        except Exception:
            pass

    # -- introspection -----------------------------------------------------

    def snapshot(self):
        """Bounded JSON-safe view: the ``/compile`` exporter route, the
        flight-recorder state provider, and ``compile_report --fleet``
        all serve this."""
        with self._lock:
            families = {}
            for name, fam in sorted(self._families.items()):
                families[name] = {
                    "hits": fam.hits,
                    "misses": fam.misses,
                    "compile_s": round(fam.compile_s, 6),
                    "signatures": len(fam.signatures),
                    "declared": name in self._declared,
                    "warmup": name in self._warmups,
                    "overflowed": fam.overflowed,
                    "last_causes": list(fam.causes[-8:]),
                }
            declared_only = sorted(set(self._declared) -
                                   set(self._families))
            undeclared = sorted(set(self._families) - set(self._declared))
            return {
                "schema": SCHEMA,
                "enabled": _ENABLED,
                "families": families,
                "declared_unobserved": declared_only,
                "undeclared": undeclared,
                "programs": dict(self._programs),
                "totals": {
                    "hits": sum(f.hits for f in self._families.values()),
                    "misses": sum(f.misses
                                  for f in self._families.values()),
                    "compile_s": round(sum(
                        f.compile_s for f in self._families.values()), 6),
                },
            }

    def cost_section(self):
        """Per-family compile cost for ``profiler.cost_table()``: the
        planner weighs warmup/compile seconds against steady-state
        gains when picking bucket sets."""
        with self._lock:
            out = {}
            for name, fam in sorted(self._families.items()):
                if not fam.misses:
                    continue
                out[name] = {
                    "compiles": fam.misses,
                    "compile_s": round(fam.compile_s, 6),
                    "mean_compile_s": round(fam.compile_s / fam.misses, 6),
                }
            return out

    def reset(self):
        with self._lock:
            self._families.clear()
            self._declared.clear()
            self._warmups.clear()
            self._programs.clear()


# ---------------------------------------------------------------------------
# module facade (the wired call-site surface: one bool check when off)

_OBSERVATORY = CompileObservatory()


def get_observatory() -> CompileObservatory:
    return _OBSERVATORY


def observe(family, signature, seconds=None, trace_id=None):
    """Gate-checked :meth:`CompileObservatory.observe`; returns None
    when the observatory is disabled (call sites branch on that)."""
    if not _ENABLED:
        return None
    return _OBSERVATORY.observe(family, signature, seconds=seconds,
                                trace_id=trace_id)


def declare_family(name, buckets=None, warmup=None, static=None):
    return _OBSERVATORY.declare_family(name, buckets=buckets,
                                       warmup=warmup, static=static)


def register_warmup(name, fn):
    _OBSERVATORY.register_warmup(name, fn)


def declared_families():
    return _OBSERVATORY.declared_families()


def warmup_entries():
    return _OBSERVATORY.warmup_entries()


def run_warmup(families=None):
    return _OBSERVATORY.run_warmup(families=families)


def undeclared_families():
    return _OBSERVATORY.undeclared_families()


def record_program(family, text):
    return _OBSERVATORY.record_program(family, text)


def snapshot():
    return _OBSERVATORY.snapshot()


def cost_section():
    return _OBSERVATORY.cost_section()


def reset():
    """Clear all observed/declared state and re-read the env gate."""
    global _ENABLED
    _OBSERVATORY.reset()
    _ENABLED = _env_truthy(os.environ.get("PADDLE_COMPILE_OBSERVATORY",
                                          "1"))

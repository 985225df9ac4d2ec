"""The program's one span call: ``span(name, **args)``.

A span is a ``jax.profiler.TraceAnnotation`` named ``"paddle_tpu:" + name``
(so it lies in the ``.xplane.pb`` on the clock of the device's "XLA Ops"
line) and a nested span of the same name and ``args`` in the process-global
:class:`~paddle_tpu.profiler.telemetry.SpanTracer` (in memory, with parent
linkage; whoever reads the tracer at the end writes it out).

Spans are on when, and only when, a profiler is recording: the
:class:`~paddle_tpu.profiler.Profiler` facade (which enables the tracer for
its recording window) or any ``jax.profiler`` session. A hot loop asks
:func:`latch` once per iteration (the serving engine: once a tick); the span
sites below it read the latched flag, so with no profiler a site costs one
branch. A site reached while jax traces a jitted function records nothing:
its wall time would be the tracing's, not the program's.

While the latch is on, a ``gc.callbacks`` hook records the collector as a
``host/gc`` span on the thread that triggered it (args ``generation``,
``collected``, ``uncollectable``; no parent in the tracer, so the spans it
interrupts keep their self times): every collection of generation 1 or 2,
and one of generation 0 that took at least :data:`GC_MIN_S`; the shorter
ones are counted on the tracer (``gc_short``, ``gc_short_s``). The
collector stops every Python thread, so such a span is what each thread
was doing. ``latch`` installs the hook on the tick where tracing turns on
and removes it on the tick where it turns off; with no profiler
``gc.callbacks`` is untouched.
"""
from __future__ import annotations

import gc
import threading
import time

import jax

from .telemetry import get_tracer

__all__ = ["PREFIX", "NULL", "span", "open_span", "latch", "tracing_active"]

#: every annotation of the program in a device trace starts with this
PREFIX = "paddle_tpu:"
#: the collector's span, and the shortest generation-0 pass it records
GC_SPAN = "host/gc"
GC_MIN_S = 1e-3

try:
    from jax._src.core import trace_state_clean as _trace_state_clean
except ImportError:                      # a jax without it: never skip
    def _trace_state_clean():
        return True

try:
    from jax._src import profiler as _jax_profiler
except ImportError:
    _jax_profiler = None

#: the latched answer of ``tracing_active()`` that ``span`` reads
_latched = False


def _jax_session_open():
    """Is a ``jax.profiler`` session open? jax keeps that private
    (``jax._src.profiler._profile_state.profile_session``); a jax without
    the attribute reads as "no session"."""
    state = getattr(_jax_profiler, "_profile_state", None)
    return getattr(state, "profile_session", None) is not None


def tracing_active():
    """True while the ``Profiler`` facade records or a ``jax.profiler``
    session is open."""
    return get_tracer().enabled or _jax_session_open()


def latch():
    """Ask ``tracing_active()`` and latch the answer for the span sites
    reached until the next ``latch``; returns it. Where the answer
    changes, the collector's hook goes in or out with it."""
    global _latched
    on = tracing_active()
    if on != _latched:
        _watch_gc(on)
    _latched = on
    return on


#: thread ident -> (the ``host/gc`` annotation, its perf_counter start)
_gc_open = {}


def _on_gc(phase, info):
    """``gc.callbacks`` hook: a ``host/gc`` span around each collection,
    on the thread that runs it; in the tracer it has no parent, so the
    spans it interrupted keep their self times."""
    if phase == "start":
        if tracing_active():
            ann = jax.profiler.TraceAnnotation(
                PREFIX + GC_SPAN, generation=info["generation"])
            ann.__enter__()
            _gc_open[threading.get_ident()] = (ann, time.perf_counter())
        return
    opened = _gc_open.pop(threading.get_ident(), None)
    if opened is None:
        return
    ann, t0 = opened
    seconds = time.perf_counter() - t0
    if info["generation"] == 0 and seconds < GC_MIN_S:
        ann.set_metadata(discarded=1)
        ann.__exit__(None, None, None)
        get_tracer().count_short_gc(seconds)
        return
    args = {"generation": info["generation"],
            "collected": info["collected"],
            "uncollectable": info["uncollectable"]}
    ann.set_metadata(**args)
    ann.__exit__(None, None, None)
    get_tracer().add_interruption(GC_SPAN, seconds, args)


def _watch_gc(on):
    if on:
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)
    else:
        if _on_gc in gc.callbacks:
            gc.callbacks.remove(_on_gc)
        _gc_open.clear()


class _NullSpan:
    """What ``span`` returns while nothing records."""
    __slots__ = ()

    def begin(self):
        return self

    def set(self, **args):
        pass

    def end(self, **args):
        pass

    def discard(self):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL = _NullSpan()


class _Span:
    """One open span: context manager, or ``begin()`` ... ``end()`` where
    a ``with`` block would re-indent a loop body. ``set`` / ``end`` take
    args that are known only once the work is done; ``discard`` closes the
    span without recording it in the tracer (the annotation, which cannot
    be taken back, is marked ``discarded``). ``end`` and ``discard`` after
    the first are no-ops."""
    __slots__ = ("name", "args", "_ann", "_sp")

    def __init__(self, name, args):
        self.name, self.args = name, args
        self._ann = self._sp = None

    def begin(self):
        self._ann = jax.profiler.TraceAnnotation(PREFIX + self.name,
                                                 **self.args)
        self._ann.__enter__()
        self._sp = get_tracer().open(self.name, self.args or None)
        return self

    def set(self, **args):
        if self._sp is None:
            return
        self._ann.set_metadata(**args)
        self._sp.args = {**(self._sp.args or {}), **args}

    def end(self, **args):
        if self._sp is None:
            return
        if args:
            self.set(**args)
        self._ann.__exit__(None, None, None)
        get_tracer().end(self._sp)
        self._sp = None

    def discard(self):
        if self._sp is None:
            return
        self._ann.set_metadata(discarded=1)
        self._ann.__exit__(None, None, None)
        get_tracer().end(self._sp, record=False)
        self._sp = None

    def __enter__(self):
        return self.begin()

    def __exit__(self, *exc):
        self.end()
        return False


def span(name, **args):
    """``with span("serve/forward"): ...`` at a layer boundary. The no-op
    ``NULL`` unless the last ``latch()`` found a profiler recording."""
    if not _latched or not _trace_state_clean():
        return NULL
    return _Span(name, args)


def open_span(name, **args):
    """An already-begun span whenever a profiler records, whatever the
    latch says: for ``RecordEvent``, which users place outside any loop
    that latches."""
    if not tracing_active() or not _trace_state_clean():
        return NULL
    return _Span(name, args).begin()

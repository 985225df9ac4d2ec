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
"""
from __future__ import annotations

import jax

from .telemetry import get_tracer

__all__ = ["PREFIX", "NULL", "span", "open_span", "latch", "tracing_active"]

#: every annotation of the program in a device trace starts with this
PREFIX = "paddle_tpu:"

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
    reached until the next ``latch``; returns it."""
    global _latched
    _latched = tracing_active()
    return _latched


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

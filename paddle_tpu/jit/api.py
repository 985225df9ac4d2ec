"""@paddle.jit.to_static — the dynamic-to-static tracer (reference: the SOT/AST
dual path in ``python/paddle/jit/`` lowering Program IR through CINN; SURVEY.md
§3.2). TPU-native design (SURVEY.md §7.0): **jax.jit IS the tracer** — we trace
the eager op layer with jax tracers by swapping each Parameter/buffer's backing
array, cache the compiled program per input-spec (shape/dtype/stop_gradient +
training flag), and splice ONE GradNode for the whole compiled region into the
imperative tape (via ``tape.apply``) so ``loss.backward()`` keeps working.
Buffer mutation (BN running stats) threads through the trace as extra outputs.
Python branching on tensor values raises under tracing → graph break → eager
fallback, matching SOT's fallback semantics.
"""
from __future__ import annotations

import functools
import os
import time
import warnings

import numpy as np
import jax

from ..framework.core import Tensor
from ..framework import dtype as dtypes
from ..framework import random as prandom
from ..autograd.tape import apply, no_grad
from ..nn.layer import Layer
from ..profiler import compile_observatory as _co

_static_mode = [False]  # paddle.enable_static (legacy static-graph mode flag)
_TRACING = [False]
_STATIC_ACTIVE = [False]   # inside StaticFunction.__call__'s trace (the only
                           # context with an InTraceAutogradNeeded handler)

_JIT_METRICS = None        # lazily bound registry families


def _jit_metrics():
    global _JIT_METRICS
    if _JIT_METRICS is None:
        from ..profiler.telemetry import get_registry
        r = get_registry()
        _JIT_METRICS = {
            "cache": r.counter(
                "paddle_jit_cache_total",
                "to_static program-cache lookups", labels=("event",)),
            "compile": r.histogram(
                "paddle_jit_compile_seconds",
                "trace+compile+first-run seconds per to_static cache miss"),
            "breaks": r.counter(
                "paddle_jit_graph_breaks_total",
                "tracer graph breaks (data-dependent Python control flow)"),
            "fallback": r.counter(
                "paddle_jit_eager_fallback_total",
                "to_static calls served eager by a latched dy2static "
                "fallback"),
            "converted": r.counter(
                "paddle_jit_dy2static_conversions_total",
                "specs rebuilt through dy2static control-flow conversion"),
        }
    return _JIT_METRICS

_GRAPH_BREAK_ERRORS = (
    jax.errors.TracerBoolConversionError,
    jax.errors.ConcretizationTypeError,
    jax.errors.TracerArrayConversionError,
    jax.errors.TracerIntegerConversionError,
)

# persistent (disk) compilation cache state: None = not yet attempted,
# False = unavailable/disabled, str = active cache dir
_PERSISTENT_CACHE = [None]
_DISK_HIT_LISTENER = [False]


def _install_disk_hit_listener():
    """Count disk-cache restores into the existing jit cache metric
    (``paddle_jit_cache_total{event="disk_hit"}``): jax records a
    monitoring event on every compilation-cache read hit."""
    if _DISK_HIT_LISTENER[0]:
        return
    try:
        from jax import monitoring as _monitoring

        def _on_event(event, *a, **k):
            if event == "/jax/compilation_cache/cache_hits":
                _jit_metrics()["cache"].inc(event="disk_hit")

        _monitoring.register_event_listener(_on_event)
        _DISK_HIT_LISTENER[0] = True
    except Exception:
        pass


def enable_persistent_cache(path=None):
    """Wire jax's persistent compilation cache so repeated runs skip XLA
    recompiles entirely (the training/serving cold-start lever): compiled
    executables are keyed on HLO+flags and restored across processes.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the cache lives THERE and no
    directory is ever set in code (whoever runs the program places the
    cache; the path is part of the cache key, so a second opinion in code
    would only make it miss). Otherwise ``path`` (default
    ``PADDLE_JIT_CACHE_DIR``) is used. Returns True when active. Restores
    are counted as ``paddle_jit_cache_total{event="disk_hit"}``."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        path = env_dir
    elif path is None:
        path = os.environ.get("PADDLE_JIT_CACHE_DIR")
    if not path:
        _PERSISTENT_CACHE[0] = False
        return False
    path = str(path)
    if _PERSISTENT_CACHE[0] == path:
        return True
    if not env_dir:
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # default thresholds skip tiny/fast programs — a framework whose
    # eager tier jits small regions wants everything cached
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # the cache latches DISABLED at the first compile of the process
    # (lazy _initialize_cache); a reset re-reads the (now set) dir so
    # late wiring — after paddle's import-time jits — still engages
    from jax.experimental.compilation_cache import (
        compilation_cache as _jax_cc)
    _jax_cc.reset_cache()
    _install_disk_hit_listener()
    _PERSISTENT_CACHE[0] = path
    return True


def enable_static():
    _static_mode[0] = True


def disable_static():
    _static_mode[0] = False


def in_dynamic_mode():
    return not _static_mode[0]


def in_to_static_mode():
    return _TRACING[0]


class InputSpec:
    def __init__(self, shape=None, dtype="float32", name=None, stop_gradient=True):
        self.shape = list(shape) if shape is not None else None
        self.dtype = dtypes.convert_dtype(dtype) if dtype is not None else None
        self.name = name
        self.stop_gradient = stop_gradient

    def __repr__(self):
        return f"InputSpec(shape={self.shape}, dtype={self.dtype}, name={self.name})"

    @classmethod
    def from_tensor(cls, tensor, name=None):
        return cls(tensor.shape, tensor.dtype, name or tensor.name)


def _is_tensor(x):
    return isinstance(x, Tensor)


def _spec_key(args, kwargs, training):
    """Cache key + list of objects to pin. Unhashable objects key on id()
    — the caller must keep the returned ``pinned`` refs alive with the
    cache entry, else a freed object's recycled id() could wrongly hit."""
    parts = [bool(training)]
    pinned = []
    for a in jax.tree.leaves((args, kwargs), is_leaf=_is_tensor):
        if isinstance(a, Tensor):
            parts.append(("T", tuple(a._data.shape), str(a.dtype), a.stop_gradient))
        elif isinstance(a, (int, float, str, bool, bytes, type(None))):
            parts.append(a)
        elif isinstance(a, np.ndarray):
            parts.append(("A", a.shape, str(a.dtype), a.tobytes()))
        else:
            try:
                hash(a)
            except TypeError:
                parts.append(("O", id(a)))
                pinned.append(a)
            else:
                # key on (type, object): the key tuple holds a strong ref
                # (no id recycling), dict equality uses the object's own
                # __eq__, and the type tag keeps value-equal cross-type
                # args (2 vs 2.0 vs True) from aliasing one trace
                parts.append(("H", type(a).__qualname__, a))
    return tuple(parts), pinned


class StaticFunction:
    """Callable produced by @to_static. One compiled program per input spec."""

    def __init__(self, function, input_spec=None, instance=None, **unused):
        self._orig_fn = function
        self._input_spec = input_spec
        self._instance = instance  # set when decorating an unbound method
        self._cache = {}
        self._bound = {}
        self._converted = "unset"  # dy2static-converted fn, lazily built
        if not isinstance(function, Layer):
            functools.update_wrapper(self, function)

    def __get__(self, instance, owner):
        if instance is None:
            return self
        key = id(instance)
        if key not in self._bound:
            self._bound[key] = StaticFunction(self._orig_fn, self._input_spec,
                                              instance=instance)
        return self._bound[key]

    # -- helpers ------------------------------------------------------------
    def _layer(self):
        if isinstance(self._instance, Layer):
            return self._instance
        if isinstance(self._orig_fn, Layer):
            return self._orig_fn
        own = getattr(self._orig_fn, "__self__", None)
        return own if isinstance(own, Layer) else None

    def _call_eager(self, *args, **kwargs):
        if isinstance(self._orig_fn, Layer):
            return self._orig_fn.forward(*args, **kwargs)
        if self._instance is not None:
            return self._orig_fn(self._instance, *args, **kwargs)
        return self._orig_fn(*args, **kwargs)

    def _state(self):
        layer = self._layer()
        if layer is None:
            return [], []
        return ([p for p in layer.parameters() if p is not None],
                [b for b in layer.buffers() if b is not None])

    # -- trace + compile ----------------------------------------------------
    def _make_core(self, treedef, leaves, kwargs_static, params, bufs, sg_flags,
                   tape_in_trace=False, call_fn=None):
        """Returns jitted core(p_arrs, b_arrs, key, t_arrs) -> (out, new_bufs).

        ``leaves`` gives the static (non-Tensor) leaves; Tensor slots are None
        and filled from t_arrs at call time. ``tape_in_trace`` keeps the tape
        recording during the trace (needed when the function calls
        paddle.grad — see autograd.tape.InTraceAutogradNeeded).
        ``call_fn`` overrides the traced callable — used to swap in the
        dy2static control-flow-converted function after a graph break.
        """
        static_leaves = [None if isinstance(l, Tensor) else l for l in leaves]
        tensor_slots = [i for i, l in enumerate(leaves) if isinstance(l, Tensor)]

        def core(p_arrs, b_arrs, key, t_arrs):
            from ..framework.functional import swap_state
            with swap_state(params, bufs, p_arrs, b_arrs, key,
                            enable_grad=tape_in_trace):
                new_leaves = list(static_leaves)
                for slot, arr, sg in zip(tensor_slots, t_arrs, sg_flags):
                    tt = Tensor(arr)
                    tt.stop_gradient = sg
                    new_leaves[slot] = tt
                new_args, new_kwargs = jax.tree.unflatten(treedef, new_leaves)
                if call_fn is not None:
                    out = call_fn(*new_args, **new_kwargs)
                else:
                    out = self._call_eager(*new_args, **new_kwargs)
                out_arrays = jax.tree.map(
                    lambda t: t._data if isinstance(t, Tensor) else t, out,
                    is_leaf=_is_tensor)
                new_bufs = [t._data for t in bufs]
                return out_arrays, new_bufs

        return jax.jit(core)

    # -- dy2static control-flow conversion ----------------------------------
    def _conversion_target(self):
        """(plain function, bound instance or None) for the AST converter."""
        fn, inst = self._orig_fn, self._instance
        if isinstance(fn, Layer):
            fn = type(fn).forward
            inst = self._orig_fn
        if hasattr(fn, "__func__"):          # bound method
            inst = fn.__self__
            fn = fn.__func__
        return fn, inst

    def _get_converted(self):
        """Control-flow-converted callable (reference ``convert_ifelse`` /
        ``convert_while`` — SURVEY.md §3.2), or None when the function has
        no convertible construct. Built lazily on the first graph break."""
        if self._converted == "unset":
            from . import dy2static
            fn, inst = self._conversion_target()
            try:
                cfn = dy2static.convert_function(fn)
            except dy2static.ConversionUnsupported:
                self._converted = None
            else:
                if inst is not None:
                    self._converted = functools.partial(cfn, inst)
                else:
                    self._converted = cfn
        return self._converted

    def __call__(self, *args, **kwargs):
        if _PERSISTENT_CACHE[0] is None:     # PADDLE_JIT_CACHE_DIR, once
            enable_persistent_cache()
        params, bufs = self._state()
        layer = self._layer()
        training = layer.training if layer is not None else True
        leaves, treedef = jax.tree.flatten((args, kwargs), is_leaf=_is_tensor)
        tensor_leaves = [l for l in leaves if isinstance(l, Tensor)]
        key, pinned = _spec_key(args, kwargs, training)
        tm = _jit_metrics()
        entry = self._cache.get(key)
        tm["cache"].inc(event="hit" if entry is not None else "miss")
        t_miss = None if entry is not None else time.perf_counter()
        # compile observatory: to_static IS a training-step jit boundary;
        # record the full input spec as a program signature so a retrace
        # gets a cause string ("arg `arg0` dim0 13→16", "static arg
        # `training` True→False") instead of a silent cache miss
        co_sig = None
        if _co.is_enabled():
            fam = f"jit.{getattr(self._orig_fn, '__name__', 'fn')}"
            if t_miss is not None:
                _co.declare_family(
                    fam, warmup=lambda: "warmed by first traced call")
            co_sig = {"training": _co.static_arg(training)}
            for i, l in enumerate(leaves):
                if isinstance(l, Tensor):
                    co_sig[f"arg{i}"] = _co.tensor_arg(
                        l._data.shape, l.dtype)
                elif isinstance(l, np.ndarray):
                    co_sig[f"arg{i}"] = _co.tensor_arg(l.shape, l.dtype)
                elif isinstance(l, (int, float, str, bool, bytes,
                                    type(None))):
                    co_sig[f"arg{i}"] = _co.static_arg(l)
        if entry is None:
            sg_flags = [t.stop_gradient for t in tensor_leaves]
            # a spec that already needed control-flow conversion tells us
            # the next spec will too — skip the doomed plain trace
            conv = self._converted if callable(self._converted) else None
            core = self._make_core(treedef, leaves, kwargs, params, bufs,
                                   sg_flags, call_fn=conv)
            entry = {"core": core, "fallback": False, "breaks": 0,
                     "pinned": pinned, "converted": conv is not None,
                     "call_fn": conv}
            self._cache[key] = entry
        if entry["fallback"]:
            tm["fallback"].inc()
            return self._call_eager(*args, **kwargs)

        rng_key = prandom.next_key()
        np_, nb_ = len(params), len(bufs)

        def runner(*xs):
            p_arrs = list(xs[:np_])
            b_arrs = list(xs[np_:np_ + nb_])
            t_arrs = list(xs[np_ + nb_:])
            return entry["core"](p_arrs, b_arrs, rng_key, t_arrs)

        from ..autograd.tape import InTraceAutogradNeeded

        def attempt(call_fn):
            try:
                return apply(runner, *params, *bufs, *tensor_leaves,
                             op_name="to_static")
            except InTraceAutogradNeeded:
                # the traced fn calls paddle.grad: re-trace with the tape
                # recording over tracers (unused vjps are DCE'd by XLA)
                sg_flags = [t.stop_gradient for t in tensor_leaves]
                entry["core"] = self._make_core(treedef, leaves, kwargs,
                                                params, bufs, sg_flags,
                                                tape_in_trace=True,
                                                call_fn=call_fn)
                return apply(runner, *params, *bufs, *tensor_leaves,
                             op_name="to_static")

        prev_static = _STATIC_ACTIVE[0]
        _STATIC_ACTIVE[0] = True
        try:
            try:
                out_vals, new_bufs = attempt(entry.get("call_fn"))
            except _GRAPH_BREAK_ERRORS as e:
                # a data-dependent branch: convert Python if/while on
                # tensor values into lax.cond/while_loop (reference
                # convert_ifelse/convert_while) and stay compiled
                tm["breaks"].inc()
                conv = (self._get_converted()
                        if not entry.get("converted") else None)
                if conv is None:
                    raise
                tm["converted"].inc()
                sg_flags = [t.stop_gradient for t in tensor_leaves]
                entry["core"] = self._make_core(treedef, leaves, kwargs,
                                                params, bufs, sg_flags,
                                                call_fn=conv)
                entry["converted"] = True
                entry["call_fn"] = conv
                out_vals, new_bufs = attempt(conv)
        except _GRAPH_BREAK_ERRORS as e:
            # latch the eager fallback only after a SECOND break, so one
            # transient tracer error doesn't permanently degrade the spec;
            # genuinely dynamic code (use static.nn.cond/while_loop to stay
            # compiled) latches on the next call
            tm["breaks"].inc()
            tm["fallback"].inc()
            entry["breaks"] += 1
            entry["fallback"] = entry["breaks"] >= 2
            warnings.warn(
                f"to_static: graph break ({type(e).__name__}) — falling back "
                f"to eager for "
                f"{getattr(self._orig_fn, '__name__', self._orig_fn)}"
                + (" (latched)" if entry["fallback"] else "; will retry once"))
            return self._call_eager(*args, **kwargs)
        finally:
            _STATIC_ACTIVE[0] = prev_static

        entry["breaks"] = 0     # a clean traced call re-arms the retry
        if t_miss is not None:
            # a miss pays trace + XLA compile + first run; later hits on
            # this spec are pure cache dispatch — the spread between this
            # histogram and steady-state step time IS the compile cost
            tm["compile"].observe(time.perf_counter() - t_miss)
        if co_sig is not None:
            _co.observe(f"jit.{getattr(self._orig_fn, '__name__', 'fn')}",
                        co_sig,
                        seconds=(time.perf_counter() - t_miss
                                 if t_miss is not None else None))
        with no_grad():
            for b, nb in zip(bufs, new_bufs):
                b._data = nb._data if isinstance(nb, Tensor) else nb
        return out_vals

    # -- introspection / export --------------------------------------------
    @property
    def code(self):
        import inspect
        try:
            return inspect.getsource(self._orig_fn)
        except (OSError, TypeError):
            return "<source unavailable>"

    def get_concrete_program(self, *args, **kwargs):
        """Lower to StableHLO for the given example inputs (Program analogue)."""
        from ..autograd.tape import InTraceAutogradNeeded
        params, bufs = self._state()
        leaves, treedef = jax.tree.flatten((args, kwargs), is_leaf=_is_tensor)
        tensor_leaves = [l for l in leaves if isinstance(l, Tensor)]
        sg = [t.stop_gradient for t in tensor_leaves]
        prev_static = _STATIC_ACTIVE[0]
        _STATIC_ACTIVE[0] = True
        last_break = None
        try:
            conv = self._converted if callable(self._converted) else None
            for call_fn in ((conv,) if conv is not None else (None, "conv")):
                if call_fn == "conv":
                    call_fn = self._get_converted()
                    if call_fn is None:
                        break
                for tape_in_trace in (False, True):
                    core = self._make_core(treedef, leaves, kwargs, params,
                                           bufs, sg,
                                           tape_in_trace=tape_in_trace,
                                           call_fn=call_fn)
                    try:
                        return core.lower([p._data for p in params],
                                          [b._data for b in bufs],
                                          prandom.next_key(),
                                          [t._data for t in tensor_leaves])
                    except InTraceAutogradNeeded:
                        continue   # retry with the tape recording in-trace
                    except _GRAPH_BREAK_ERRORS as e:
                        if call_fn is not None:
                            raise
                        last_break = e
                        break      # retry with control-flow conversion
        finally:
            _STATIC_ACTIVE[0] = prev_static
        raise (last_break if last_break is not None else RuntimeError(
            "get_concrete_program: could not lower (in-trace autograd "
            "retries exhausted)"))

    def rollback(self):
        if isinstance(self._orig_fn, Layer):
            return self._orig_fn
        return self._orig_fn


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, full_graph=None, **kwargs):
    """@paddle.jit.to_static — decorator or functional form; accepts a Layer,
    a function, or a bound method."""

    def decorate(fn):
        if isinstance(fn, Layer):
            orig_forward = fn.forward
            sf = StaticFunction(orig_forward, input_spec)
            fn._static_forward = sf
            fn._dygraph_forward = orig_forward
            fn.forward = sf
            return fn
        return StaticFunction(fn, input_spec)

    if function is not None:
        return decorate(function)
    return decorate


def not_to_static(fn):
    fn._not_to_static = True
    return fn


def ignore_module(modules):
    pass


def enable_to_static(flag=True):
    pass

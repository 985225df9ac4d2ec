"""Tensor facade over jax.Array + device/place management.

Design (SURVEY.md §7.0): Paddle's eager ``Tensor`` is mutable, carries
``stop_gradient`` (default True — only Parameters default to False, reference
``python/paddle/autograd`` notes in SURVEY.md §2.2), an accumulated ``.grad``,
and supports in-place ops. We wrap an immutable ``jax.Array`` and swap it on
in-place mutation; autograd is an imperative tape recorded per-op (see
``paddle_tpu/autograd/tape.py``).

Most tensor *methods* (``reshape``, ``sum``, …) are monkey-patched onto this
class from the ops layer by ``paddle_tpu/framework/tensor_patch.py`` — the same
scheme upstream uses (``python/paddle/tensor/__init__.py`` monkey_patch).
"""
from __future__ import annotations

import os

import numpy as np
import jax
import jax.numpy as jnp

from . import dtype as dtypes

# ---------------------------------------------------------------------------
# Place / device
# ---------------------------------------------------------------------------


class Place:
    """Device place: 'cpu', 'tpu' (the accelerator), 'gpu' aliases to 'tpu'."""

    def __init__(self, kind: str, index: int = 0):
        self.kind = kind
        self.index = index

    def __repr__(self):
        return f"Place({self.kind}:{self.index})"

    def __eq__(self, other):
        return isinstance(other, Place) and (self.kind, self.index) == (other.kind, other.index)

    def jax_device(self):
        """The jax device behind this place. An accelerator place needs a
        TPU backend and an index inside its device count: a missing chip
        is an error here, never a quiet CPU (or device 0) stand-in."""
        if self.kind == "cpu":
            devs = jax.devices("cpu")
        else:
            devs = jax.devices()
            if devs[0].platform != "tpu":
                raise RuntimeError(
                    f"{self!r} needs a TPU backend, but jax's default "
                    f"backend is {devs[0].platform!r} "
                    f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})")
        if not 0 <= self.index < len(devs):
            raise ValueError(f"{self!r}: device index out of range, the "
                             f"backend has {len(devs)} device(s)")
        return devs[self.index]


class CPUPlace(Place):
    def __init__(self):
        super().__init__("cpu")


class TPUPlace(Place):
    def __init__(self, index=0):
        super().__init__("tpu", index)


CUDAPlace = TPUPlace  # API-compat alias: 'gpu' means 'the accelerator' here.
XPUPlace = TPUPlace   # same alias: any accelerator place maps to the TPU.

_current_place: Place | None = None


def set_device(device: str) -> Place:
    """paddle.set_device('tpu'|'cpu'|'gpu:0'). 'gpu'/'xpu' alias the
    accelerator; raises when that device does not exist."""
    global _current_place
    kind, _, idx = device.partition(":")
    kind = {"gpu": "tpu", "xpu": "tpu"}.get(kind, kind)
    if kind not in ("cpu", "tpu"):
        raise ValueError(f"unknown device {device!r}")
    place = Place(kind, int(idx) if idx else 0)
    jax.config.update("jax_default_device", place.jax_device())
    _current_place = place
    return place


def get_device() -> str:
    p = current_place()
    return f"{p.kind}:{p.index}"


def current_place() -> Place:
    global _current_place
    if _current_place is None:
        # default: accelerator if present else cpu
        kind = "cpu" if jax.default_backend() == "cpu" else "tpu"
        _current_place = Place(kind, 0)
    return _current_place


def is_compiled_with_cuda():
    return False


def is_compiled_with_xpu():
    return False


def device_count():
    return jax.local_device_count()


# ---------------------------------------------------------------------------
# Tensor
# ---------------------------------------------------------------------------

_name_counter = [0]


def _auto_name(prefix="tensor"):
    _name_counter[0] += 1
    return f"{prefix}_{_name_counter[0]}"


class Tensor:
    """Eager tensor over a jax.Array.

    Attributes mirror Paddle: ``stop_gradient`` (True by default), ``grad``
    (a Tensor or None), ``name``, ``persistable``.
    """

    __array_priority__ = 100.0

    __slots__ = (
        "_data", "stop_gradient", "grad", "name", "persistable",
        "_grad_node", "_out_idx", "_retain_grads", "_grad_hooks", "_weak_pp",
        "process_mesh", "placements",   # auto-parallel dist-tensor attrs
        "__weakref__",
    )

    def __init__(self, data, dtype=None, stop_gradient=True, name=None, place=None):
        if isinstance(data, Tensor):
            data = data._data
        if not isinstance(data, (jax.Array, jax.core.Tracer)):
            dt = dtypes.convert_dtype(dtype) if dtype is not None else None
            arr = np.asarray(data)
            if dt is None and arr.dtype == np.float64:
                dt = dtypes.convert_dtype(dtypes.get_default_dtype())
            data = jnp.asarray(arr, dtype=dt)
        elif dtype is not None and data.dtype != np.dtype(dtypes.convert_dtype(dtype)):
            data = data.astype(dtypes.convert_dtype(dtype))
        self._data = data
        self.stop_gradient = stop_gradient
        self.grad = None
        self.name = name or _auto_name()
        self.persistable = False
        self._grad_node = None
        self._out_idx = 0
        self._retain_grads = False
        self._grad_hooks = None
        self._weak_pp = None
        self.process_mesh = None
        self.placements = None

    # -- basic properties ---------------------------------------------------
    @property
    def shape(self):
        return list(self._data.shape)

    @property
    def dtype(self):
        return self._data.dtype

    @property
    def ndim(self):
        return self._data.ndim

    dim = ndim

    @property
    def size(self):
        return int(np.prod(self._data.shape)) if self._data.shape else 1

    @property
    def place(self):
        return current_place()

    @property
    def is_leaf(self):
        return self._grad_node is None

    @property
    def T(self):
        from ..ops import manipulation
        return manipulation.transpose(self, list(range(self.ndim))[::-1])

    def numel(self):
        return self.size

    # -- conversion ---------------------------------------------------------
    def numpy(self):
        try:
            return np.asarray(jax.device_get(self._data))
        except RuntimeError as e:
            if type(e).__name__ == "DonatedTensorError":
                raise          # already the clear guard diagnostic
            if "deleted" in str(e).lower() or "donated" in str(e).lower():
                # donation/aliasing misuse guard (SURVEY.md §5.2 TPU
                # equivalent of StreamSafeCUDAAllocator's reuse guard)
                raise RuntimeError(
                    "Tensor used after its device buffer was donated to a "
                    "jitted call (donate_argnums) — keep the returned "
                    "tensor instead of the donated input") from e
            raise

    def item(self, *args):
        return self.numpy().item(*args)

    def tolist(self):
        return self.numpy().tolist()

    def __float__(self):
        return float(self.item())

    def __int__(self):
        return int(self.item())

    def __bool__(self):
        if self.size != 1:
            raise ValueError("The truth value of a Tensor with more than one element is ambiguous")
        return bool(self.item())

    def __index__(self):
        return int(self.item())

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-D tensor")
        return self._data.shape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __hash__(self):
        return id(self)

    # -- autograd -----------------------------------------------------------
    def backward(self, grad_tensor=None, retain_graph=False):
        from ..autograd import tape
        from ..profiler import step_phase as _step_phase
        _t0 = _step_phase.clock()
        tape.run_backward([self], [grad_tensor], retain_graph=retain_graph)
        if _t0 is not None:
            import time as _time
            _step_phase.record_phase("backward", _time.perf_counter() - _t0)

    def retain_grads(self):
        self._retain_grads = True

    def register_hook(self, hook):
        if self._grad_hooks is None:
            self._grad_hooks = []
        self._grad_hooks.append(hook)

        class _Removable:
            def __init__(self, hooks, h):
                self._hooks, self._h = hooks, h

            def remove(self):
                if self._h in self._hooks:
                    self._hooks.remove(self._h)

        return _Removable(self._grad_hooks, hook)

    def clear_grad(self):
        self.grad = None

    clear_gradient = clear_grad

    def detach(self):
        t = Tensor(self._data, stop_gradient=True, name=self.name + "_detached")
        return t

    def detach_(self):
        self._grad_node = None
        self.stop_gradient = True
        return self

    def clone(self):
        from ..autograd.tape import apply
        return apply(lambda x: x + 0, self, op_name="clone")

    # -- mutation -----------------------------------------------------------
    def _replace_(self, new_data, node=None, out_idx=0):
        """In-place: swap underlying array (and autograd provenance)."""
        self._data = new_data
        self._grad_node = node
        self._out_idx = out_idx
        return self

    def set_value(self, value):
        if isinstance(value, Tensor):
            value = value._data
        value = jnp.asarray(value, dtype=self.dtype)
        if tuple(value.shape) != tuple(self._data.shape):
            raise ValueError(f"set_value shape mismatch {value.shape} vs {self._data.shape}")
        self._data = value
        return self

    def copy_(self, other, *a):
        return self.set_value(other)

    # -- device / dtype movement -------------------------------------------
    def astype(self, dtype):
        from ..autograd.tape import apply
        dt = dtypes.convert_dtype(dtype)
        return apply(lambda x: x.astype(dt), self, op_name="cast")

    def cast(self, dtype):
        return self.astype(dtype)

    def cpu(self):
        return Tensor(jax.device_put(self._data, jax.devices("cpu")[0]),
                      stop_gradient=self.stop_gradient)

    def cuda(self, device_id=None, blocking=True):
        """API-compat: 'cuda' means 'the accelerator' in this build."""
        devs = jax.devices()
        return Tensor(jax.device_put(self._data,
                                     devs[(device_id or 0) % len(devs)]),
                      stop_gradient=self.stop_gradient)

    def element_size(self):
        return self._data.dtype.itemsize

    @property
    def nbytes(self):
        return self._data.dtype.itemsize * int(self.size)

    def data_ptr(self):
        """Opaque buffer identity (reference returns the device pointer).
        Uses the device buffer's real address when the backend exposes it,
        so two Tensor wrappers over ONE jax buffer compare equal and ids
        recycled by GC can't alias; falls back to id() where the runtime
        hides the pointer (meaningful only for same-object comparison
        within a live scope there)."""
        try:
            return self._data.unsafe_buffer_pointer()
        except (AttributeError, NotImplementedError, RuntimeError,
                ValueError):   # ValueError: sharded/multi-device arrays
            return id(self._data)

    def is_sparse(self):
        return False

    def coalesce(self):
        """Dense tensors are their own coalesced form; sparse COO
        tensors override this in paddle_tpu.sparse."""
        return self

    def apply_(self, func):
        """In-place elementwise python function (reference
        ``Tensor.apply_`` — host-side, eager only)."""
        import numpy as np
        arr = np.vectorize(func)(self.numpy()).astype(
            np.asarray(self.numpy()).dtype)
        self._replace_(jnp.asarray(arr))
        return self

    def apply(self, func):
        return Tensor(jnp.asarray(self.clone().apply_(func)._data),
                      stop_gradient=self.stop_gradient)

    def exponential_(self, lam=1.0):
        """In-place exponential sampling (reference
        ``Tensor.exponential_``)."""
        from . import random as prandom
        u = jax.random.uniform(prandom.next_key(), self._data.shape,
                               minval=1e-7, maxval=1.0)
        self._replace_((-jnp.log(u) / lam).astype(self._data.dtype))
        return self

    def floor_divide_(self, y):
        y = y._data if isinstance(y, Tensor) else y
        self._replace_(jnp.floor_divide(self._data, y))
        return self

    def to(self, *args, **kwargs):
        t = self
        for a in list(args) + list(kwargs.values()):
            if isinstance(a, str) and a.lower() in dtypes._STR2DTYPE:
                t = t.astype(a)
            elif isinstance(a, str):  # device string
                kind, _, idx = a.partition(":")
                place = Place({"gpu": "tpu", "xpu": "tpu"}.get(kind, kind),
                              int(idx) if idx else 0)
                t = Tensor(jax.device_put(t._data, place.jax_device()),
                           stop_gradient=t.stop_gradient)
            elif a is not None and not isinstance(a, bool):
                t = t.astype(a)
        return t

    def pin_memory(self):
        return self

    def contiguous(self):
        return self

    def is_contiguous(self):
        return True

    # -- repr ---------------------------------------------------------------
    def __repr__(self):
        grad_info = f", stop_gradient={self.stop_gradient}"
        return (f"Tensor(shape={self.shape}, dtype={dtypes.dtype_name(self.dtype)}"
                f"{grad_info},\n       {np.array2string(self.numpy(), prefix='       ')})")

    __str__ = __repr__

    # -- numpy interop ------------------------------------------------------
    def __array__(self, dtype=None):
        a = self.numpy()
        return a.astype(dtype) if dtype is not None else a


class EagerParamBase(Tensor):
    """A trainable parameter: stop_gradient defaults to False."""

    __slots__ = ("trainable", "optimize_attr", "regularizer", "is_distributed",
                 "need_clip", "initializer", "_sharding_spec")

    def __init__(self, data, dtype=None, name=None, trainable=True, **kw):
        super().__init__(data, dtype=dtype, stop_gradient=not trainable,
                         name=name or _auto_name("param"))
        self.trainable = trainable
        self.persistable = True
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = None
        self.is_distributed = False
        self.need_clip = True
        self.initializer = None
        # PartitionSpec-like tuple for distributed placement (parallel/ layer code sets it)
        self._sharding_spec = None

    def __repr__(self):
        return "Parameter containing:\n" + super().__repr__()


Parameter = EagerParamBase


def to_tensor(data, dtype=None, place=None, stop_gradient=True):
    """paddle.to_tensor"""
    if isinstance(data, Tensor):
        if dtype is not None and np.dtype(dtypes.convert_dtype(dtype)) != data.dtype:
            data = data.astype(dtype)
        t = Tensor(data._data)
        t.stop_gradient = stop_gradient
        return t
    return Tensor(data, dtype=dtype, stop_gradient=stop_gradient, place=place)

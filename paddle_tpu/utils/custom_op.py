"""Custom-op extension API — the TPU-native `PD_BUILD_OP`
(reference: ``paddle/phi/api/ext/op_meta_info.h`` macros +
``python/paddle/utils/cpp_extension/`` JIT loader; SURVEY.md §2.1
"Custom-op ext API").

On GPU the reference compiles user CUDA kernels against the `paddle::Tensor`
stable ABI and registers them into the op registry. The TPU analogue has two
tiers:

* **Device tier** — :func:`register_op`: any pure-jax callable (jnp/lax or a
  Pallas ``pallas_call`` kernel) becomes a first-class op: Tensor in/out,
  recorded on the autograd tape, jit/`to_static`-compatible, AMP-visible by
  its registered name, optional custom VJP (``jax.custom_vjp`` under the
  hood, so it also works under ``paddle.grad(create_graph=True)``).
* **Host tier** — :func:`paddle_tpu.utils.cpp_extension.load`: compile C++
  sources with the system toolchain into a shared library (ctypes), then lift
  a host function into the op layer with ``register_op(...,
  host_callback=True)`` (``jax.pure_callback`` under jit).

Worked in-tree example: ``paddle_tpu.ops.fused.fused_swiglu`` is registered
through this API with a hand-written VJP.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..autograd.tape import apply
from ..framework.core import Tensor

# name -> {fn, has_vjp, doc} (reference: OpMetaInfoMap singleton)
REGISTRY: dict = {}


def _as_array(x):
    return x._data if isinstance(x, Tensor) else x


def register_op(fwd=None, *, name=None, vjp=None, primal=None,
                nondiff_argnums=(), host_callback=False, out_shape=None,
                override=False):
    """Register a custom op (decorator or functional form).

    ``fwd(*arrays, **static_kwargs)`` is a pure function of jax arrays.

    Without ``vjp``: gradients come from jax's autodiff of ``fwd``.

    With ``vjp``: ``fwd`` must return ``(out, residuals)`` and
    ``vjp(residuals, *out_cotangents) -> tuple`` must return one cotangent
    per differentiable positional input (``jax.custom_vjp`` convention;
    reference: the ``SetBackwardFn`` half of PD_BUILD_OP).

    ``primal`` (with ``vjp``): the function an UNDIFFERENTIATED call runs,
    ``primal(*arrays, **static_kwargs) -> out``; default ``fwd(...)[0]``.
    ``jax.custom_vjp`` runs ``fwd`` / ``vjp`` only under differentiation,
    so an op whose rules materialise or fence arrays for the backward pass
    keeps a plain expression for inference programs.

    ``nondiff_argnums``: positional args treated as static (hashable)
    configuration, not tensors.

    ``host_callback=True``: ``fwd`` runs on host (a ctypes call into a
    cpp_extension, numpy code, ...); it is wrapped in ``jax.pure_callback``
    so the op stays jit-compatible. ``out_shape(*inputs)`` must return the
    output ShapeDtypeStruct (or a pytree of them); host ops have no autodiff
    unless ``vjp`` is also given.
    """
    if fwd is None:
        return functools.partial(register_op, name=name, vjp=vjp,
                                 primal=primal,
                                 nondiff_argnums=nondiff_argnums,
                                 host_callback=host_callback,
                                 out_shape=out_shape, override=override)

    op_name = name or fwd.__name__
    if primal is not None and (vjp is None or host_callback):
        raise ValueError(f"custom op '{op_name}': primal= goes with vjp= "
                         "on a device op")
    if op_name in REGISTRY and not override:
        raise ValueError(f"custom op '{op_name}' is already registered "
                         "(pass override=True to replace)")

    if host_callback:
        if out_shape is None:
            raise ValueError("host_callback ops need out_shape")
        inner = fwd

        def device_fn(*args, **kwargs):
            shapes = out_shape(*args, **kwargs)
            return jax.pure_callback(
                lambda *a: inner(*a, **kwargs), shapes, *args,
                vmap_method="sequential")
        base = device_fn
    else:
        base = fwd

    if vjp is not None:
        # static kwargs bind by CLOSURE (cached per combination) so they
        # never become custom_vjp primal args needing cotangents
        @functools.lru_cache(maxsize=64)
        def _bound(kw_items):
            kw = dict(kw_items)
            wrapped = jax.custom_vjp(
                (lambda *a: base(*a, **kw)[0]) if primal is None
                else (lambda *a: primal(*a, **kw)),
                nondiff_argnums=tuple(nondiff_argnums))

            def _fwd(*a):
                return base(*a, **kw)

            def _bwd(*res_and_cot):
                # custom_vjp passes (nondiff..., residuals, cotangent)
                *nd, res, cot = res_and_cot
                cots = cot if isinstance(cot, tuple) else (cot,)
                grads = vjp(res, *cots) if not nd else vjp(*nd, res, *cots)
                return tuple(grads)

            wrapped.defvjp(_fwd, _bwd)
            return wrapped

        jfn = _bound(())
    else:
        jfn = base

    @functools.wraps(fwd)
    def op(*args, **kwargs):
        if vjp is not None and kwargs:
            if any(isinstance(v, Tensor) for v in kwargs.values()):
                raise TypeError(
                    f"custom op '{op_name}': Tensors must be passed "
                    "positionally when a vjp is registered (keyword args "
                    "are static configuration bound by closure)")
            try:
                fn = _bound(tuple(sorted(kwargs.items())))
            except TypeError:
                raise TypeError(
                    f"custom op '{op_name}': static kwargs must be hashable "
                    f"(got {kwargs})") from None
            return apply(fn, *args, op_name=op_name)
        return apply(jfn, *args, op_name=op_name, **kwargs)

    op.raw = jfn
    op.op_name = op_name
    REGISTRY[op_name] = {"fn": jfn, "has_vjp": vjp is not None,
                         "host": host_callback, "doc": fwd.__doc__}
    return op


def get_op(name):
    """Look up a registered custom op's raw jax callable."""
    return REGISTRY[name]["fn"]

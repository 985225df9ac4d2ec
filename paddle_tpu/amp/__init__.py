"""paddle.amp (reference: ``python/paddle/amp/`` — SURVEY.md §2.2: auto_cast
O1 white/black lists, O2 pure-fp16/bf16; GradScaler dynamic loss scaling;
amp.decorate master weights).

Integration point: ``tape.apply`` consults :func:`amp_cast_inputs` before
running each op — the TPU-native analogue of the reference's
``eager_amp_auto_cast.h`` hooks in generated forwards (SURVEY.md §3.1).
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit)
def _check_finite_and_unscale(grads, inv):
    """Fused multi-tensor unscale + global finite check (reference:
    ``check_finite_and_unscale`` CUDA kernel) — one compiled program, one
    host sync per optimizer step."""
    outs = [(g.astype(jnp.float32) * inv).astype(g.dtype) for g in grads]
    finite = jnp.all(jnp.stack(
        [jnp.all(jnp.isfinite(g.astype(jnp.float32))) for g in grads]))
    return outs, jnp.logical_not(finite)

from ..framework.core import Tensor
from ..framework import dtype as dtypes
from ..autograd import tape as _tape
from ..autograd.tape import no_grad

# fp16/bf16-safe ops (matmul-class: MXU-friendly)
WHITE_LIST = {
    "matmul", "mm", "bmm", "linear", "conv1d", "conv2d", "conv3d",
    "conv2d_transpose", "einsum", "sdpa", "addmm",
}
# numerically sensitive: force fp32
BLACK_LIST = {
    "exp", "log", "log2", "log10", "log1p", "logsumexp", "softmax",
    "log_softmax", "cross_entropy", "bce", "bce_with_logits", "kl_div",
    "mse_loss", "l1_loss", "smooth_l1_loss", "sum", "mean", "norm", "cumsum",
    "pow", "square", "rsqrt", "sigmoid_focal_loss", "cosine_similarity",
    "softmax_with_cross_entropy", "layer_norm", "batch_norm", "group_norm",
    "instance_norm", "rms_norm",
}


class _AmpState:
    enabled = False
    level = "O1"
    dtype = jnp.float16
    white = WHITE_LIST
    black = BLACK_LIST


_state = _AmpState()


def amp_state():
    return _state


def _cast_tensors(args, dt):
    out = []
    changed = False
    for a in args:
        if isinstance(a, Tensor) and a.dtype in (jnp.float32, jnp.float16, jnp.bfloat16) \
                and a.dtype != jnp.dtype(dt):
            t = a.astype(dt)
            t.stop_gradient = a.stop_gradient
            # preserve autograd linkage: astype goes through the tape, so t
            # carries a cast node back to a. Good.
            out.append(t)
            changed = True
        else:
            out.append(a)
    return out, changed


def amp_cast_inputs(op_name, args):
    """Called by tape.apply: maybe cast Tensor args per AMP policy."""
    if not _state.enabled:
        return args
    if op_name == "cast":
        # the cast op IS the policy's tool — recasting its input would
        # recurse forever (cast -> amp cast -> cast ...)
        return args
    if _state.level == "O2":
        if op_name in _state.black:
            return _cast_tensors(args, jnp.float32)[0]
        return _cast_tensors(args, _state.dtype)[0]
    # O1
    if op_name in _state.white:
        return _cast_tensors(args, _state.dtype)[0]
    if op_name in _state.black:
        return _cast_tensors(args, jnp.float32)[0]
    return args


_tape._amp_cast_inputs = amp_cast_inputs


@contextlib.contextmanager
def auto_cast(enable=True, custom_white_list=None, custom_black_list=None,
              level="O1", dtype="float16", use_promote=True):
    prev = (_state.enabled, _state.level, _state.dtype, _state.white, _state.black)
    _state.enabled = enable
    _state.level = level
    _state.dtype = dtypes.convert_dtype(dtype)
    _state.white = WHITE_LIST | set(custom_white_list or ())
    _state.black = (BLACK_LIST | set(custom_black_list or ())) - set(custom_white_list or ())
    try:
        yield
    finally:
        (_state.enabled, _state.level, _state.dtype, _state.white,
         _state.black) = prev


amp_guard = auto_cast  # legacy alias


def decorate(models, optimizers=None, level="O1", dtype="float16",
             master_weight=None, save_dtype=None, master_grad=False,
             excluded_layers=None):
    """O2: cast model params to fp16/bf16; optimizer keeps fp32 master weights."""
    single_model = not isinstance(models, (list, tuple))
    model_list = [models] if single_model else list(models)
    if level == "O2":
        excluded = set()
        from ..nn.layers.norm import _BatchNormBase, LayerNorm, GroupNorm
        for m in model_list:
            for lyr in m.sublayers(include_self=True):
                skip = isinstance(lyr, (_BatchNormBase, LayerNorm, GroupNorm))
                if excluded_layers and isinstance(lyr, tuple(excluded_layers)):
                    skip = True
                if skip:
                    continue
                for p in lyr._parameters.values():
                    if p is not None and p.dtype == jnp.float32:
                        p._data = p._data.astype(dtypes.convert_dtype(dtype))
        if optimizers is not None:
            opt_list = optimizers if isinstance(optimizers, (list, tuple)) else [optimizers]
            for opt in opt_list:
                opt._multi_precision = True
    if optimizers is None:
        return models
    return models, optimizers


class GradScaler:
    """Dynamic loss scaling (reference: ``python/paddle/amp/grad_scaler.py`` —
    scale/unscale/inf-check via ``check_finite_and_unscale``, SURVEY.md §2.2)."""

    def __init__(self, enable=True, init_loss_scaling=2.0 ** 16, incr_ratio=2.0,
                 decr_ratio=0.5, incr_every_n_steps=2000,
                 decr_every_n_nan_or_inf=1, use_dynamic_loss_scaling=True):
        self._enable = enable
        self._scale = float(init_loss_scaling)
        self._incr_ratio = incr_ratio
        self._decr_ratio = decr_ratio
        self._incr_every = incr_every_n_steps
        self._decr_every = decr_every_n_nan_or_inf
        self._dynamic = use_dynamic_loss_scaling
        self._good_steps = 0
        self._bad_steps = 0
        self._found_inf = False
        self._unscaled = False

    def scale(self, loss):
        if not self._enable:
            return loss
        return loss * self._scale

    def _unscale(self, optimizer):
        """ONE fused jitted unscale+finite-check over all grads (reference:
        the ``check_finite_and_unscale`` multi-tensor kernel) — a single
        device sync for the whole step instead of one blocking round-trip
        per parameter."""
        if not self._enable or self._unscaled:
            return
        grads = [p.grad._data for p in optimizer._parameter_list
                 if p.grad is not None]
        if grads:
            new_grads, found = _check_finite_and_unscale(
                grads, jnp.asarray(1.0 / self._scale, jnp.float32))
            i = 0
            for p in optimizer._parameter_list:
                if p.grad is None:
                    continue
                p.grad._data = new_grads[i]
                i += 1
            self._found_inf = bool(found)
        else:
            self._found_inf = False
        self._unscaled = True

    def unscale_(self, optimizer):
        self._unscale(optimizer)

    def step(self, optimizer):
        if not self._enable:
            optimizer.step()
            return
        self._unscale(optimizer)
        if not self._found_inf:
            optimizer.step()
        self._update()
        self._unscaled = False

    def update(self):
        pass  # paddle's step() already updates; kept for torch-style loops

    def _update(self):
        if not self._dynamic:
            return
        if self._found_inf:
            self._bad_steps += 1
            self._good_steps = 0
            if self._bad_steps >= self._decr_every:
                self._scale = max(self._scale * self._decr_ratio, 1.0)
                self._bad_steps = 0
        else:
            self._good_steps += 1
            self._bad_steps = 0
            if self._good_steps >= self._incr_every:
                self._scale *= self._incr_ratio
                self._good_steps = 0

    def minimize(self, optimizer, scaled_loss):
        scaled_loss.backward()
        self.step(optimizer)
        optimizer.clear_grad()

    def is_enable(self):
        return self._enable

    def is_use_dynamic_loss_scaling(self):
        return self._dynamic

    def get_scale_ratio(self):
        return self._scale

    def state_dict(self):
        return {"scale": self._scale, "incr_ratio": self._incr_ratio,
                "decr_ratio": self._decr_ratio, "good_steps": self._good_steps,
                "bad_steps": self._bad_steps}

    def load_state_dict(self, state):
        self._scale = state["scale"]
        self._good_steps = state.get("good_steps", 0)
        self._bad_steps = state.get("bad_steps", 0)


from . import debugging  # noqa: F401,E402  (full module: paddle.amp.debugging)


def _device_platform(device=None):
    import jax
    if device is None:
        return jax.devices()[0].platform.lower()
    s = str(device).lower()
    for p in ("tpu", "xpu", "gpu", "cuda", "cpu"):
        if p in s:
            # this build aliases every accelerator place to the TPU
            return {"cuda": "gpu", "xpu": "tpu"}.get(p, p)
    return s


def is_bfloat16_supported(device=None):
    """bf16 is the MXU-native dtype on TPU and runs everywhere XLA does."""
    return _device_platform(device) in ("tpu", "gpu", "cpu")


def is_float16_supported(device=None):
    return _device_platform(device) in ("tpu", "gpu")

"""paddle.inference (reference: ``paddle/fluid/inference/`` —
``AnalysisPredictor``: load pdmodel → IR fusion passes → run; Python surface
``Config``/``create_predictor``/zero-copy handles; SURVEY.md §2.1 "Inference
engine", §3.6).

TPU-native: the saved artifact is serialized StableHLO (paddle.jit.save) —
already fused/optimized by XLA at export; the predictor deserializes and
executes the AOT program. The reference's IR-fusion pass pipeline and
TensorRT engine have no role: XLA is both. Zero-copy IO maps to device
arrays held on the handle until copy_to_cpu().
"""
from __future__ import annotations

import os
import time

import numpy as np
import jax

from ..framework.core import Tensor


class Config:
    """paddle_infer.Config(prog_file, params_file) or Config(model_dir)."""

    def __init__(self, prog_file=None, params_file=None):
        if prog_file is not None and params_file is None \
                and os.path.isdir(prog_file):
            # model_dir flavor: find the single prefix inside
            cands = [f[: -len(".pdmodel.stablehlo")]
                     for f in os.listdir(prog_file)
                     if f.endswith(".pdmodel.stablehlo")]
            if not cands:
                raise FileNotFoundError(
                    f"no .pdmodel.stablehlo in {prog_file}")
            self.prefix = os.path.join(prog_file, cands[0])
        else:
            # accept either the exported prefix or the model file path
            p = prog_file or ""
            for suf in (".pdmodel.stablehlo", ".pdmodel"):
                if p.endswith(suf):
                    p = p[: -len(suf)]
            self.prefix = p
        self._use_tpu = True
        self.mem_opt = True
        self.ir_debug = False
        self.ir_optim = False
        self.profile = False

    # knobs kept for API compat (XLA supersedes them)
    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0):
        self._use_tpu = True

    def disable_gpu(self):
        self._use_tpu = False

    def enable_memory_optim(self):
        self.mem_opt = True

    def switch_ir_optim(self, flag=True):
        """Run the program-level pass pipeline (canonicalize+cse via
        ``static.pir``) on the loaded StableHLO before execution. XLA
        optimizes again at compile time regardless; this knob exercises
        the PIR-analogue pass infra and slims the program pre-compile."""
        self.ir_optim = bool(flag)

    def switch_ir_debug(self, flag=True):
        """Dump the loaded program's StableHLO text next to the model
        (``<prefix>.hlo.txt``) — the IR-inspection knob made real."""
        self.ir_debug = bool(flag)

    def enable_profile(self):
        """Collect per-run wall times; read via Predictor.get_profile()."""
        self.profile = True

    def set_optim_cache_dir(self, path):
        """Persistent compilation cache (reference: the optimization
        cache dir) — compiled executables survive process restarts.
        ``JAX_COMPILATION_CACHE_DIR``, where set, wins over ``path``
        (see ``paddle.jit.enable_persistent_cache``)."""
        from ..jit.api import enable_persistent_cache
        enable_persistent_cache(path)

    def enable_tensorrt_engine(self, *a, **kw):
        raise NotImplementedError(
            "TensorRT is CUDA-only; the TPU build runs XLA-compiled "
            "StableHLO (already fused)")

    def set_cpu_math_library_num_threads(self, n):
        pass                        # XLA's host runtime sizes its own pool


class _IOHandle:
    """Zero-copy style IO handle (reference ZeroCopyTensor)."""

    def __init__(self, name):
        self.name = name
        self._value = None

    def reshape(self, shape):
        pass

    def copy_from_cpu(self, arr):
        self._value = np.ascontiguousarray(arr)

    def copy_to_cpu(self):
        v = self._value
        if isinstance(v, jax.Array):
            return np.asarray(jax.device_get(v))
        return np.asarray(v)

    def share_external_data(self, arr):
        self.copy_from_cpu(np.asarray(arr))


class Predictor:
    def __init__(self, config: Config):
        from ..jit import load as jit_load
        self._layer = jit_load(config.prefix)
        if getattr(config, "ir_optim", False):
            # best-effort: the knob's old contract was a no-op ("XLA always
            # optimizes") — a pass-infra failure must degrade, not brick
            # model load
            try:
                from ..static.pir import optimize_exported
                self._layer._exported = optimize_exported(
                    self._layer._exported)
            except Exception as e:
                import warnings
                warnings.warn(f"ir_optim: pass pipeline unavailable "
                              f"({e!r}); serving the unoptimized program",
                              RuntimeWarning)
        specs = self._layer._meta.get("input_specs", [])
        names = []
        for i, s in enumerate(specs):
            n = s[2] if len(s) > 2 and s[2] else f"input_{i}"
            while n in names:            # spec names may collide with
                n += "_"                 # positional fallbacks — dedupe
            names.append(n)
        self._inputs = [_IOHandle(n) for n in (names or ["input_0"])]
        self._outputs = []
        self._profile = [] if getattr(config, "profile", False) else None
        if getattr(config, "ir_debug", False):
            # IR debug dump is best-effort diagnostics: an unwritable
            # model dir must not take down predictor construction
            try:
                try:
                    text = self._layer._exported.mlir_module()
                except Exception:
                    text = str(self._layer._exported)
                with open(config.prefix + ".hlo.txt", "w") as f:
                    f.write(text)
            except OSError as e:
                import warnings
                warnings.warn(f"ir_debug: cannot write HLO dump next to "
                              f"the model ({e})", RuntimeWarning)

    def get_profile(self):
        """Per-run wall times (s) collected under Config.enable_profile."""
        if self._profile is None:
            raise RuntimeError("call Config.enable_profile() before "
                               "create_predictor")
        t = np.asarray(self._profile)
        return {"runs": len(t),
                "total_s": float(t.sum()) if len(t) else 0.0,
                "mean_s": float(t.mean()) if len(t) else 0.0,
                "p50_s": float(np.percentile(t, 50)) if len(t) else 0.0,
                "p99_s": float(np.percentile(t, 99)) if len(t) else 0.0}

    def get_input_names(self):
        return [h.name for h in self._inputs]

    def get_input_handle(self, name):
        for h in self._inputs:
            if h.name == name:
                return h
        raise KeyError(name)

    def run(self, inputs=None):
        t0 = time.perf_counter() if self._profile is not None else None
        if inputs is not None:          # list-of-arrays convenience form
            for h, a in zip(self._inputs, inputs):
                h.copy_from_cpu(np.asarray(a))
        args = [Tensor(h._value) for h in self._inputs]
        out = self._layer(*args)
        outs = out if isinstance(out, (list, tuple)) else [out]
        self._outputs = []
        for i, o in enumerate(outs):
            h = _IOHandle(f"output_{i}")
            h._value = o._data if isinstance(o, Tensor) else o
            self._outputs.append(h)
        if self._profile is not None:
            jax.block_until_ready([h._value for h in self._outputs])
            self._profile.append(time.perf_counter() - t0)
        if inputs is not None:
            return [h.copy_to_cpu() for h in self._outputs]
        return True

    def get_output_names(self):
        return [h.name for h in self._outputs] or ["output_0"]

    def get_output_handle(self, name):
        for h in self._outputs:
            if h.name == name:
                return h
        raise KeyError(name)


def create_predictor(config: Config) -> Predictor:
    return Predictor(config)


def get_version():
    import paddle_tpu
    return paddle_tpu.__version__


class PrecisionType:
    Float32 = 0
    Half = 1
    Int8 = 2

from .serving import ServingEngine, ContinuousServingEngine  # noqa: E402,F401
from .speculative import (NGramDrafter, DraftModelDrafter,   # noqa: E402,F401
                          make_drafter)
from .fleet import (ServingRouter, Rejected,                 # noqa: E402,F401
                    TenantQuotaManager, ROUTER_POLICIES,
                    FleetController, ControllerAction,
                    ReplayHarness, ReplayTrace, make_trace)

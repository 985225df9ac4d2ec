"""Distributed passes (reference: ``python/paddle/distributed/passes/`` — a
registry of program-rewriting passes for the auto-parallel static engine:
``auto_parallel_amp``, ``auto_parallel_recompute``, ``auto_parallel_sharding``,
``pipeline_scheduler_pass`` (FThenB/1F1B/VPP/ZBH1), fuse-allreduce;
SURVEY.md §2.3 "Distributed passes" + "Static-mode meta-optimizers").

TPU-native framing: the reference's passes rewrite a serialized Program's op
list (insert cast ops, recompute subgraphs, comm ops). Here compilation is
XLA's job, so a "pass" transforms the declarative *plan* — the strategy/
sharding decisions a train step is built from — and the XLA lowering
realizes it. Several reference passes are XLA built-ins and their pass
objects document that (apply = no-op with a note): fused allreduce ≡ XLA
collective combining; fuse-adamw ≡ XLA op fusion.
"""
from __future__ import annotations

_PASS_REGISTRY = {}


def register_pass(name):
    def deco(cls):
        cls.name = name
        _PASS_REGISTRY[name] = cls
        return cls
    return deco


def new_pass(name, attrs=None):
    try:
        cls = _PASS_REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown pass {name!r}; available: "
                         f"{sorted(_PASS_REGISTRY)}")
    return cls(attrs or {})


class PassBase:
    """A pass transforms a plan dict (strategy + shardings + step options).
    ``apply(plan)`` returns the updated plan; ``check`` validates."""

    name = "base"

    def __init__(self, attrs=None):
        self.attrs = dict(attrs or {})

    def check(self, plan):
        return True

    def apply(self, plan, *a, **kw):
        return plan


class PassManager:
    def __init__(self, passes=None):
        self.passes = list(passes or [])

    def append(self, p):
        self.passes.append(p)

    def apply(self, plan=None, *a, **kw):
        plan = dict(plan or {})
        for p in self.passes:
            if p.check(plan):
                plan = p.apply(plan)
        return plan

    @property
    def names(self):
        return [p.name for p in self.passes]


@register_pass("auto_parallel_amp")
class AMPPass(PassBase):
    """Sets the step's compute dtype policy (O1 lists / O2 bf16 + master
    weights) — realized by the amp cast hook, not inserted cast ops."""

    def apply(self, plan, *a, **kw):
        # merge, don't clobber: MasterGradPass may have recorded
        # master_grad in plan['amp'] already (pass order is free)
        plan.setdefault("amp", {}).update(
            {"level": self.attrs.get("level", "O2"),
             "dtype": self.attrs.get("dtype", "bfloat16"),
             "master_weights": True})
        return plan


@register_pass("auto_parallel_fp16")
class FP16Pass(AMPPass):
    def apply(self, plan, *a, **kw):
        plan = super().apply(plan)
        plan["amp"]["dtype"] = "float16"
        return plan


@register_pass("auto_parallel_recompute")
class RecomputePass(PassBase):
    """Marks layer groups for jax.checkpoint (the reference rewrites the
    backward block; XLA rematerializes instead)."""

    def apply(self, plan, *a, **kw):
        plan["recompute"] = {
            "enable": True,
            "granularity": self.attrs.get("granularity", "full"),
            "no_recompute_segments": self.attrs.get(
                "no_recompute_segments", []),
        }
        return plan


@register_pass("auto_parallel_sharding")
class ShardingPass(PassBase):
    """Sets the ZeRO stage realized as parameter/opt-state PartitionSpecs on
    the 'sharding' mesh axis."""

    def apply(self, plan, *a, **kw):
        plan["sharding"] = {"stage": int(self.attrs.get("stage", 2)),
                            "degree": self.attrs.get("degree", None)}
        return plan


@register_pass("pipeline_scheduler")
class PipelineSchedulerPass(PassBase):
    """Selects the microbatch schedule, all realized by the SPMD engine
    (distributed/engine.py): FThenB (grad-through-scan), 1F1B
    (recompute/backward custom_vjp, O(S) memory), VPP (interleaved
    virtual stages), ZBH1 (1F1B with the backward split into B on the
    wire chain and W deferred one tick off it)."""

    SCHEDULES = ("FThenB", "1F1B", "VPP", "ZBH1")

    def check(self, plan):
        mode = self.attrs.get("schedule_mode", "1F1B")
        if mode not in self.SCHEDULES:
            raise ValueError(f"unknown pipeline schedule {mode}")
        return True

    def apply(self, plan, *a, **kw):
        plan["pipeline"] = {
            "schedule_mode": self.attrs.get("schedule_mode", "1F1B"),
            "accumulate_steps": int(self.attrs.get("accumulate_steps", 1)),
            "vpp_degree": int(self.attrs.get("vpp_degree", 1)),
        }
        return plan


@register_pass("fuse_all_reduce")
class FuseAllReducePass(PassBase):
    """Collective combining — realized by XLA; applying the pass pins
    the responsible compiler flags into the plan so
    ``install_xla_flags`` can arm them explicitly."""

    def apply(self, plan, *a, **kw):
        plan.setdefault("xla_flags", []).extend([
            "--xla_tpu_enable_async_collective_fusion=true",
            "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
        ])
        plan.setdefault("notes", []).append(
            "fuse_all_reduce: XLA collective combining (flags pinned)")
        return plan


@register_pass("fused_adamw")
class FusedAdamWPass(PassBase):
    """XLA built-in (op fusion of the update chain); kept for API parity."""

    def apply(self, plan, *a, **kw):
        plan.setdefault("notes", []).append(
            "fused_adamw: XLA fuses the elementwise update chain")
        return plan


@register_pass("auto_parallel_gradient_merge")
class GradientMergePass(PassBase):
    """Gradient merge / large-batch accumulation (reference:
    ``auto_parallel_gradient_merge.py`` rewrites the program to accumulate
    grads over k steps before the optimizer update). Here it is REAL eager
    behavior: ``wrap(optimizer)`` returns an optimizer whose ``step()``
    applies only every ``k_steps``-th call (grads keep accumulating on the
    tape's ``.grad`` between applies — reference avg=True divides)."""

    def apply(self, plan, *a, **kw):
        plan["gradient_merge"] = {
            "k_steps": int(self.attrs.get("k_steps", 1)),
            "avg": bool(self.attrs.get("avg", True)),
        }
        return plan

    def wrap(self, optimizer):
        return _GradientMergeOptimizer(optimizer,
                                       int(self.attrs.get("k_steps", 1)),
                                       bool(self.attrs.get("avg", True)))


class _GradientMergeOptimizer:
    def __init__(self, inner, k_steps, avg):
        self._inner = inner
        self._k = max(1, k_steps)
        self._avg = avg
        self._calls = 0

    def __getattr__(self, item):
        return getattr(self._inner, item)

    def step(self):
        self._calls += 1
        if self._calls % self._k:
            return              # keep accumulating into .grad
        if self._avg and self._k > 1:
            for p in self._inner._parameter_list:
                if p.grad is not None:
                    p.grad._data = p.grad._data / self._k
        self._inner.step()

    def minimize(self, loss, *a, **kw):
        # must route through the merge window, not the inner minimize
        loss.backward()
        self.step()
        self.clear_grad()
        return None, None

    def clear_grad(self, *a, **kw):
        # grads persist across the merge window; clear only after an apply
        if self._calls % self._k == 0:
            self._inner.clear_grad(*a, **kw)

    clear_gradients = clear_grad


@register_pass("auto_parallel_master_grad")
class MasterGradPass(PassBase):
    """fp32 master gradients under bf16 compute — realized by the AMP
    layer's master-weight path; the pass records the policy."""

    def apply(self, plan, *a, **kw):
        plan.setdefault("amp", {})["master_grad"] = True
        return plan


@register_pass("fuse_gemm_epilogue")
class FuseGemmEpiloguePass(PassBase):
    """XLA built-in (bias/activation fused into the matmul); API parity."""

    def apply(self, plan, *a, **kw):
        plan.setdefault("notes", []).append(
            "fuse_gemm_epilogue: XLA fuses bias+activation epilogues")
        return plan


@register_pass("allreduce_matmul_grad_overlapping")
class AllreduceOverlapPass(PassBase):
    """Grad-collective/compute overlap — realized by XLA's latency-hiding
    scheduler; applying the pass pins the flag into the plan."""

    def apply(self, plan, *a, **kw):
        plan.setdefault("xla_flags", []).append(
            "--xla_tpu_enable_latency_hiding_scheduler=true")
        plan.setdefault("notes", []).append(
            "allreduce overlap: XLA latency-hiding scheduler overlaps "
            "grad collectives with the backward matmuls (flag pinned)")
        return plan


def build_strategy_from_plan(plan):
    """Execute a pass plan: fold the dict the passes produced into a
    concrete ``DistributedStrategy`` (+ model-config knobs via
    :func:`apply_plan_to_config`) that ``fleet.init`` /
    ``distributed_model`` actually run with — the reference's
    program-rewrite step collapsed onto strategy/config space (on TPU the
    rewrites themselves are XLA sharding/fusion passes)."""
    from ..fleet.distributed_strategy import DistributedStrategy

    strat = DistributedStrategy()
    if "amp" in plan:
        strat.amp = True
        amp = dict(plan["amp"])
        strat.amp_configs = {
            "level": amp.get("level", "O2"),
            "dtype": amp.get("dtype", "bfloat16"),
            "use_master_weights": amp.get("master_weights", True),
            "use_master_grad": amp.get("master_grad", False),
        }
    if "recompute" in plan and plan["recompute"].get("enable", True):
        strat.recompute = True
        strat.recompute_configs = dict(plan["recompute"])
    h = dict(strat.hybrid_configs)          # accumulate; assign once at
    if "sharding" in plan:                  # the end (the setter merges
        strat.sharding = True               # from DEFAULTS, not current)
        strat.sharding_configs = dict(plan["sharding"])
        h["sharding_degree"] = int(plan["sharding"].get("degree", 1) or 1)
        # the stage HybridParallelOptimizer actually reads lives under
        # hybrid_configs["sharding_configs"]
        sc = dict(h.get("sharding_configs", {}))
        sc["stage"] = int(plan["sharding"].get("stage", 1))
        h["sharding_configs"] = sc
    if "pipeline" in plan:
        pp = plan["pipeline"]
        h["pp_degree"] = int(pp.get("pp_degree", pp.get("degree", 1)) or 1)
        ppc = dict(h.get("pp_configs", {}))
        ppc["schedule_mode"] = pp.get("schedule_mode", "1F1B")
        ppc["accumulate_steps"] = int(pp.get("accumulate_steps", 1))
        ppc["vpp_degree"] = int(pp.get("vpp_degree", 1))
        h["pp_configs"] = ppc               # the runtime reads pp_configs
    strat.hybrid_configs = h
    if "gradient_merge" in plan:
        strat.gradient_merge = True
        strat.gradient_merge_configs = dict(plan["gradient_merge"])
    return strat


def install_xla_flags(plan, env=None, platform=None):
    """Arm the plan's pinned XLA compiler flags (collective fusion,
    latency-hiding scheduler, ...) in ``env`` — the executable half of
    the XLA-builtin passes. TPU-only flags are only installed when the
    backend is a TPU (XLA rejects unknown flags at init), and flags must
    be set BEFORE the first backend initialization to take effect in
    this process (they always apply to spawned children).

    Returns the list of flags installed."""
    import os
    flags = list(dict.fromkeys(plan.get("xla_flags", [])))  # dedup, ordered
    if not flags:
        return []
    if platform is None:
        # Must not call jax.default_backend() here: that would perform
        # the very backend initialization the flags need to precede,
        # rendering them inert for this process. Probe initialized
        # state / env only.
        try:
            from jax._src import xla_bridge as xb
            initialized = bool(getattr(xb, "backends_are_initialized",
                                       lambda: getattr(xb, "_backends",
                                                       None))())
        except Exception:
            initialized = False
        if initialized:
            import jax
            platform = jax.default_backend()
        else:
            envs = (os.environ.get("JAX_PLATFORMS", "")
                    + os.environ.get("PJRT_DEVICE", "")).lower()
            platform = "tpu" if "tpu" in envs else "unknown"
    if platform != "tpu":
        return []            # tpu-only flags would crash other backends
    env = os.environ if env is None else env
    current = env.get("XLA_FLAGS", "").split()
    merged = current + [f for f in flags if f not in current]
    env["XLA_FLAGS"] = " ".join(merged)
    return [f for f in flags if f not in current]


def apply_plan_to_config(plan, model_config):
    """Push plan knobs that live on the MODEL into its config (recompute
    granularity, sequence parallel) — returns the same config object."""
    rc = plan.get("recompute")
    if rc and rc.get("enable", True) \
            and hasattr(model_config, "use_recompute"):
        model_config.use_recompute = True
        gran = rc.get("granularity")
        if gran and hasattr(model_config, "recompute_granularity"):
            model_config.recompute_granularity = gran
    if plan.get("sequence_parallel") \
            and hasattr(model_config, "sequence_parallel"):
        model_config.sequence_parallel = True
    return model_config

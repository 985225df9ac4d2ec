"""Benchmark driver. Default: ResNet-50 / CIFAR-10 training throughput
(BASELINE.json config 1). ``BENCH_MODEL=llama`` benches the flagship
Llama train step (tokens/sec).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} plus
``platform``/``device_kind``/``device_count``. ``vs_baseline`` is
null — the reference mount is empty and BASELINE.json records no
published numbers (SURVEY.md §6); this run IS the baseline.

One process, one device owner: ``main`` runs the mode ``BENCH_MODEL``
selects in THIS process and nothing else — no probe, no child, no retry,
no rerun at another size or on another backend. The record names the
platform, ``device_kind`` and device count it ran on; a number from a CPU
run says ``"platform": "cpu"`` and is never a device metric. Any exception
is a non-zero exit. Run it on the chip through the chip tool
(``chiprun -- python bench.py``); ``JAX_PLATFORMS=cpu python bench.py`` is a
rehearsal of paths, not a measurement.

``BENCH_AMP=1`` (default on TPU) uses the reference's AMP-O2 recipe
mapped to TPU: fp32 master params, bf16 compute — the MXU's native dtype.
"""
from __future__ import annotations

import json
import os
import sys
import time


def _amp_enabled():
    import jax
    plat = jax.devices()[0].platform.lower()
    default = "1" if plat == "tpu" else "0"
    return os.environ.get("BENCH_AMP", default) == "1"


def _loader_batches(batch, image_shape=(3, 32, 32), min_workers=0):
    """Config-1's input path as specified: CIFAR-10 (local cache) or the
    deterministic FakeData stand-in (zero-egress), through
    ``paddle.io.DataLoader`` with worker processes + C++ shm queue +
    prefetch (reference ``buffered_reader.cc`` double buffering).
    Returns ``(workers, generator)``; the generator yields forever and
    callers bound consumption themselves. ``workers`` goes into the
    emitted JSON so 0-worker and 4-worker records are distinguishable."""
    from paddle_tpu.io import DataLoader
    from paddle_tpu.vision.datasets import Cifar10, FakeData
    # Default worker count depends on where COMPUTE runs. On an
    # accelerator the host idles during device steps, so workers overlap
    # with compute even on a 1-core host — keep the reference's 4-worker
    # shape. With CPU compute, workers STEAL the training process's
    # cores (the round-4 loader-fed collapse: 11.77 vs 24.3 img/s was
    # contention, not pipeline cost — the loader itself runs at ~21k
    # img/s on this host); spawn only what spare cores allow. Cores =
    # the scheduling affinity mask (cgroup/cpuset aware), not the
    # machine's nominal count. ``min_workers`` lets the goodput bench
    # keep the worker+shm transport it exists to measure.
    import jax as _jax
    try:
        n_cores = len(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        n_cores = os.cpu_count() or 1
    if _jax.default_backend() == "cpu":
        default_workers = min(4, max(min_workers, n_cores - 1))
    else:
        default_workers = 4
    workers = int(os.environ.get("BENCH_WORKERS", str(default_workers)))
    ds = None
    if tuple(image_shape) == (3, 32, 32):   # CIFAR only at its own shape
        try:
            ds = Cifar10(mode="train")
        except Exception:
            ds = None
    if ds is None:
        ds = FakeData(size=max(2048, batch * 4), image_shape=image_shape)
    loader = DataLoader(ds, batch_size=batch, shuffle=True, drop_last=True,
                        num_workers=workers, use_shared_memory=True,
                        prefetch_factor=2)

    def gen():
        while True:
            for xb, yb in loader:
                yield xb, yb

    return workers, gen()


def bench_resnet():
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.vision.models import resnet50
    from paddle_tpu.framework.functional import FunctionalModule

    batch = int(os.environ.get("BENCH_BATCH", "256"))
    steps = int(os.environ.get("BENCH_STEPS", "20"))
    amp = _amp_enabled()
    # BENCH_DATA=loader feeds real batches through the DataLoader stack
    # (worker procs + shm queue + prefetch) instead of a constant array —
    # config 1 as specified in BASELINE.json
    use_loader = os.environ.get("BENCH_DATA", "synthetic") == "loader"

    paddle.seed(0)
    model = resnet50(num_classes=10)
    model.train()
    fm = FunctionalModule(model, training=True)
    p_arrs = fm.param_arrays()
    b_arrs = fm.buffer_arrays()
    key = fm.next_key()

    x = jnp.ones((batch, 3, 32, 32),
                 jnp.bfloat16 if amp else jnp.float32)
    y = jnp.zeros((batch,), jnp.int32)

    def _loss_fn(ps, b_arrs, key, x, y):
        cps = [a.astype(jnp.bfloat16) if amp and a.dtype == jnp.float32
               else a for a in ps]
        logits, new_b = fm(cps, b_arrs, key, x)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        loss = -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()
        return loss, new_b

    def train_step(p_arrs, b_arrs, key, x, y):
        def loss_fn(ps):
            return _loss_fn(ps, b_arrs, key, x, y)

        (loss, new_b), grads = jax.value_and_grad(loss_fn, has_aux=True)(p_arrs)
        new_p = [p - 0.05 * g.astype(p.dtype) for p, g in zip(p_arrs, grads)]
        return loss, new_p, new_b

    step = jax.jit(train_step, donate_argnums=(0, 1))
    loss, p_arrs, b_arrs = step(p_arrs, b_arrs, key, x, y)   # compile
    loss.block_until_ready()

    comp_dtype = x.dtype
    n_workers = None
    if use_loader:
        import numpy as np
        n_workers, batches = _loader_batches(batch)

        def feed():
            xb, yb = next(batches)
            return (jnp.asarray(np.asarray(xb.numpy()), comp_dtype),
                    jnp.asarray(np.asarray(yb.numpy()).reshape(-1),
                                jnp.int32))
        x, y = feed()                                        # warm loader
        t0 = time.perf_counter()
        for _ in range(steps):
            loss, p_arrs, b_arrs = step(p_arrs, b_arrs, key, x, y)
            x, y = feed()          # overlaps with the async device step
        loss.block_until_ready()
        dt = time.perf_counter() - t0
    else:
        t0 = time.perf_counter()
        for _ in range(steps):
            loss, p_arrs, b_arrs = step(p_arrs, b_arrs, key, x, y)
        loss.block_until_ready()
        dt = time.perf_counter() - t0
    out = {
        "metric": ("resnet50_cifar10_train_throughput_loader" if use_loader
                   else "resnet50_cifar10_train_throughput"),
        "value": round(batch * steps / dt, 2),
        "unit": "images/sec",
        "vs_baseline": None,
    }
    if n_workers is not None:
        out["workers"] = n_workers
    if not use_loader:
        # step donates (p, b): thread them through the probe's closure
        st = [p_arrs, b_arrs]

        def _probe_step():
            loss, st[0], st[1] = step(st[0], st[1], key, x, y)
            return loss

        out["telemetry_overhead_pct"] = _telemetry_overhead_pct(
            _probe_step, lambda r: r.block_until_ready(),
            steps=min(steps, 10))
        p_arrs, b_arrs = st
    # -- training observatory (ISSUE 12): memory peak, phase split,
    # numerics-sentinel cost — the first training-side memory/phase
    # entries in the bench trajectory
    out["train_peak_bytes"] = _train_peak_bytes()
    if os.environ.get("BENCH_PHASES", "1") == "1":
        def _fwd(ps):
            return _loss_fn(ps, b_arrs, key, x, y)[0]

        def _grads(ps):
            return jax.value_and_grad(
                lambda q: _loss_fn(q, b_arrs, key, x, y)[0])(ps)[1]

        def _opt(ps, gs):
            return [p - 0.05 * g.astype(p.dtype) for p, g in zip(ps, gs)]

        out["train_phase_breakdown"] = _phase_breakdown_probe(
            p_arrs, _fwd, _grads, _opt)
    out["numerics_overhead_pct"] = _numerics_overhead_pct()
    out["ledger_overhead_pct"] = _ledger_overhead_pct()
    out["compile_observatory_overhead_pct"] = \
        _compile_observatory_overhead_pct()
    _emit_observatory_aux(out)
    return out


def _telemetry_overhead_pct(run_step, sync, steps=10, instrumented_step=None,
                            setup=None, teardown=None):
    """Cost of the observability layer itself, measured in-situ: the same
    jitted step with the full per-step telemetry surface in the loop
    (span begin/end + step-time histogram + counter + gauge) vs bare.
    Emitted with every resnet bench so a regression in the telemetry hot
    path shows up as a perf delta, not as silent slow training.

    ``instrumented_step`` overrides the default full-telemetry step —
    callers (the flight-recorder overhead guard) time their own
    instrumentation surface against the same bare loop; ``setup`` /
    ``teardown`` bracket the instrumented timing window."""
    if instrumented_step is None:
        from paddle_tpu.profiler.telemetry import get_registry, get_tracer

        reg = get_registry()
        hist = reg.histogram("bench_step_seconds", "bench overhead probe")
        ctr = reg.counter("bench_steps_total", "bench overhead probe")
        gauge = reg.gauge("bench_last_step_seconds", "bench overhead probe")
        tracer = get_tracer()

        def instrumented_step():
            sp = tracer.begin("bench_step")
            t1 = time.perf_counter()
            r = run_step()
            d = time.perf_counter() - t1
            tracer.end(sp)
            hist.observe(d)
            ctr.inc()
            gauge.set(d)
            return r

        setup = tracer.enable

        def teardown():
            tracer.disable()
            tracer.drain()             # don't leak probe spans to exports

    def timed(fn):
        t0 = time.perf_counter()
        r = None
        for _ in range(steps):
            r = fn()
        sync(r)
        return time.perf_counter() - t0

    timed(run_step)                    # warm both paths
    t_plain = timed(run_step)
    if setup is not None:
        setup()
    try:
        t_instr = timed(instrumented_step)
    finally:
        if teardown is not None:
            teardown()
    return round((t_instr - t_plain) / max(t_plain, 1e-9) * 100, 3)


def _train_peak_bytes():
    """Peak device bytes of the training run so far (PJRT allocator
    lifetime peak; 0 on backends without allocator stats)."""
    try:
        from paddle_tpu.device.memory import max_memory_allocated
        return int(max_memory_allocated())
    except Exception:
        return 0


def _phase_breakdown_probe(p_arrs, fwd_fn, grads_fn, opt_fn, steps=None):
    """Split-timed step-phase decomposition of a jitted train step:
    forward = t(loss-only program), backward = t(loss+grads) - forward,
    optimizer = t(update-only program); comm_wait is 0 on one chip. The
    measured durations are ALSO recorded through
    ``profiler.step_phase`` so the ``paddle_step_phase_seconds``
    histogram and ``cost_table()['phases']`` carry the same numbers the
    record reports. Returns {phase: fraction} plus the per-phase
    seconds under ``*_s`` keys."""
    import jax

    from paddle_tpu.profiler import step_phase

    steps = steps or int(os.environ.get("BENCH_PHASE_STEPS", "2"))

    def timed(fn, *args):
        r = fn(*args)                       # compile/warm
        jax.block_until_ready(r)
        t0 = time.perf_counter()
        for _ in range(steps):
            r = fn(*args)
        jax.block_until_ready(r)
        return (time.perf_counter() - t0) / steps, r

    was = step_phase.is_enabled()
    step_phase.enable()
    try:
        t_fwd, _ = timed(jax.jit(fwd_fn), p_arrs)
        t_fwdbwd, grads = timed(jax.jit(grads_fn), p_arrs)
        t_opt, _ = timed(jax.jit(opt_fn), p_arrs, grads)
        t_bwd = max(t_fwdbwd - t_fwd, 0.0)
        for ph, dt in (("forward", t_fwd), ("backward", t_bwd),
                       ("optimizer", t_opt)):
            step_phase.record_phase(ph, dt)
        total = max(t_fwd + t_bwd + t_opt, 1e-12)
        return {
            "forward": round(t_fwd / total, 4),
            "backward": round(t_bwd / total, 4),
            "comm_wait": 0.0,
            "optimizer": round(t_opt / total, 4),
            "forward_s": round(t_fwd, 5),
            "backward_s": round(t_bwd, 5),
            "optimizer_s": round(t_opt, 5),
        }
    finally:
        if not was:
            step_phase.disable()


def _numerics_overhead_pct():
    """Per-step cost of the numerics sentinel (grad L2/abs-max/
    nonfinite stats for every parameter, interval 1) vs sentinel-off,
    measured on an eager 2-layer MLP train step — the sentinel
    instruments the eager tape's grad-ready hooks, which a jitted
    whole-step program never fires, so the eager loop IS the worst
    case."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.profiler import tensor_stats

    # sized so compute dominates the way a real model's does — the
    # sentinel's per-param cost is fixed, so a toy step would report
    # a uselessly inflated percentage
    net = nn.Sequential(nn.Linear(256, 256), nn.Tanh(),
                        nn.Linear(256, 64))
    opt = paddle.optimizer.SGD(learning_rate=0.01,
                               parameters=net.parameters())
    x = paddle.to_tensor(np.random.default_rng(0)
                         .normal(size=(64, 256)).astype(np.float32))

    def step():
        loss = (net(x) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    def setup():
        tensor_stats.enable(interval=1, mode="warn")

    def teardown():
        tensor_stats.disable()
        tensor_stats.reset()

    return _telemetry_overhead_pct(step, lambda r: None, steps=10,
                                   instrumented_step=step,
                                   setup=setup, teardown=teardown)


def _ledger_overhead_pct():
    """Per-step cost of the determinism ledger (sha1 param/grad digests
    at every optimizer step, interval 1, warn mode) vs ledger-off, on
    the same eager MLP step the numerics-sentinel probe uses — the
    digest path pulls every parameter and gradient to host, so the
    eager loop is the honest worst case for the sensing layer."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.profiler import ledger

    net = nn.Sequential(nn.Linear(256, 256), nn.Tanh(),
                        nn.Linear(256, 64))
    opt = paddle.optimizer.SGD(learning_rate=0.01,
                               parameters=net.parameters())
    x = paddle.to_tensor(np.random.default_rng(0)
                         .normal(size=(64, 256)).astype(np.float32))

    def step():
        loss = (net(x) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    def setup():
        ledger.enable(mode="warn", interval=1)

    def teardown():
        ledger.disable()
        ledger.reset()

    return _telemetry_overhead_pct(step, lambda r: None, steps=10,
                                   instrumented_step=step,
                                   setup=setup, teardown=teardown)


def _compile_observatory_overhead_pct():
    """Per-call cost of the compile observatory (signature build +
    trace-cache accounting at every ``to_static`` entry) vs
    observatory-off, on a jitted MLP forward — the to_static entry
    builds a full per-leaf signature on every call when the observatory
    is on, so the cached-program hot loop is the honest worst case for
    the sensing layer. Disabled must cost one bool check."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.profiler import compile_observatory as co

    net = nn.Sequential(nn.Linear(256, 256), nn.Tanh(),
                        nn.Linear(256, 64))
    static_net = paddle.jit.to_static(net)
    x = paddle.to_tensor(np.random.default_rng(0)
                         .normal(size=(64, 256)).astype(np.float32))

    def step():
        return static_net(x)

    co.disable()                       # bare path: observatory off
    try:
        return _telemetry_overhead_pct(step, lambda r: None, steps=10,
                                       instrumented_step=step,
                                       setup=co.enable,
                                       teardown=co.disable)
    finally:
        co.reset()                     # back to the env-gated default


def _emit_observatory_aux(out):
    """stderr aux lines for the training-observatory record fields."""
    for name in ("train_peak_bytes", "numerics_overhead_pct",
                 "ledger_overhead_pct",
                 "compile_observatory_overhead_pct"):
        if name in out:
            print(json.dumps({"aux_metric": name, "value": out[name]}),
                  file=sys.stderr)
    if "train_phase_breakdown" in out:
        print(json.dumps({"aux_metric": "train_phase_breakdown",
                          **{k: v for k, v in
                             out["train_phase_breakdown"].items()
                             if not k.endswith("_s")}}), file=sys.stderr)


def bench_data():
    """Config-3 goodput: DataLoader (worker procs + C++ shm queue +
    prefetch) → HBM transfer rate on detection-sized images (reference:
    ``buffered_reader.cc`` double-buffered H2D prefetch)."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    batch = int(os.environ.get("BENCH_BATCH", "32"))
    steps = int(os.environ.get("BENCH_STEPS", "30"))
    shape = (3, int(os.environ.get("BENCH_IMG", "320")),
             int(os.environ.get("BENCH_IMG", "320")))
    # the goodput metric EXISTS to measure the worker+shm transport —
    # never let the spare-core default degrade it to single-process
    n_workers, batches = _loader_batches(batch, image_shape=shape,
                                         min_workers=2)
    dev = jax.devices()[0]

    next(batches)                                            # warm workers
    n_bytes = 0
    t0 = time.perf_counter()
    for _ in range(steps):
        xb, yb = next(batches)
        xa = jax.device_put(np.asarray(xb.numpy()), dev)
        n_bytes += xa.size * xa.dtype.itemsize
    xa.block_until_ready()
    dt = time.perf_counter() - t0
    print(json.dumps({"aux_metric": "loader_hbm_goodput",
                      "value": round(n_bytes / dt / 2**20, 2),
                      "unit": "MiB/s"}), file=sys.stderr)
    return {
        "metric": "dataloader_hbm_samples_per_sec",
        "value": round(batch * steps / dt, 2),
        "unit": "samples/sec",
        "vs_baseline": None,
        "workers": n_workers,
    }


def bench_llama():
    """Flagship single-chip Llama train-step bench (tokens/sec); exercises
    the Pallas flash-attention path + AMP master weights."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.framework.functional import FunctionalModule

    if jax.devices()[0].platform == "cpu":
        # the peak table's nominal "cpu" entry is for the CPU tests'
        # callbacks; a utilization is a device metric, a CPU run has none
        raise RuntimeError("BENCH_MODEL=llama reports MFU and needs an "
                           "accelerator; jax reports a cpu backend")
    batch = int(os.environ.get("BENCH_BATCH", "4"))
    seq = int(os.environ.get("BENCH_SEQ", "1024"))
    steps = int(os.environ.get("BENCH_STEPS", "10"))
    amp = _amp_enabled()
    # MFU sweep knobs (BENCH_REMAT=1/full -> full activation recompute per
    # layer — trades FLOPs for HBM so bigger BENCH_BATCH/BENCH_SEQ fit;
    # BENCH_REMAT=dots -> dots-saveable policy: matmul outputs kept,
    # elementwise recomputed — much cheaper recompute, the usual TPU
    # MFU-vs-memory sweet spot)
    remat_mode = os.environ.get("BENCH_REMAT", "0")
    remat = remat_mode not in ("0", "")
    if remat_mode not in ("0", "", "1", "full"):
        import paddle_tpu as _p
        # any other value is a recompute policy name (dots/dots_batch/
        # everything); fleet.utils.recompute raises on unknown names
        _p.set_flags({"FLAGS_recompute_policy": remat_mode})
    # BENCH_PRESET=1b: a genuinely 1B-class config (TinyLlama-1.1B
    # shape) — the sub-1B default can't saturate the MXU (round-2 MFU
    # was measured at h1024/L8; VERDICT item 2 asks for 1B+)
    preset = os.environ.get("BENCH_PRESET", "")
    if preset == "1b":
        dims = dict(hidden_size=2048, intermediate_size=5632,
                    num_hidden_layers=22, num_attention_heads=32,
                    num_key_value_heads=4)
    else:
        dims = dict(hidden_size=int(os.environ.get("BENCH_HIDDEN", "1024")),
                    intermediate_size=int(os.environ.get("BENCH_INTER",
                                                         "2816")),
                    num_hidden_layers=int(os.environ.get("BENCH_LAYERS",
                                                         "8")),
                    num_attention_heads=16, num_key_value_heads=8)

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=32000,
                      max_position_embeddings=max(2048, seq),
                      use_recompute=remat, **dims)
    model = LlamaForCausalLM(cfg)
    model.train()
    fm = FunctionalModule(model, training=True)
    p_arrs = fm.param_arrays()
    # BENCH_PARAM_DTYPE=bf16: pure-bf16 state — params AND grads live in
    # bf16 (no fp32 master, no per-step cast). On a 16 GB v5e at the 1b
    # preset this frees ~6.6 GB (fp32 params 4.4 + fp32 grads 4.4 +
    # bf16 copies 2.2 → bf16 params 2.2 + bf16 grads 2.2), buying
    # no-remat arithmetic at batches that otherwise need recompute —
    # a throughput-measurement mode (production training keeps the
    # AMP-O2 master-weight path for convergence)
    pure_bf16 = os.environ.get("BENCH_PARAM_DTYPE", "") == "bf16"
    if pure_bf16:
        p_arrs = [a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a
                  for a in p_arrs]
        # rebind the module's Tensors to the bf16 arrays: they would
        # otherwise keep the fp32 originals alive for the whole run
        # (unlike the baseline path, which donates them to the jitted
        # step), stranding 4.4 GB at the 1b preset and defeating the
        # mode's point
        for t, a in zip(fm.params, p_arrs):
            t._data = a
        amp = False            # params are already compute-dtype
    key = fm.next_key()
    import numpy as np
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                         jnp.int32)

    # BENCH_ACCUM=n: gradient accumulation over n microbatches — an
    # activation-memory lever for the 1b preset on a 16 GB chip (the
    # microbatch fwd+bwd serialize on the grad-sum dependency, so peak
    # activation memory is that of batch/n, at full arithmetic)
    accum = max(int(os.environ.get("BENCH_ACCUM", "1")), 1)
    assert batch % accum == 0, "BENCH_ACCUM must divide BENCH_BATCH"

    def _loss_fn(ps, mb_ids, mb_labels):
        cps = [a.astype(jnp.bfloat16) if amp and a.dtype == jnp.float32
               else a for a in ps]
        (loss, _), _ = fm(cps, [], key, mb_ids, labels=mb_labels)
        return loss

    def train_step(p_arrs, key, ids, labels):
        loss_fn = _loss_fn

        if accum == 1:
            loss, grads = jax.value_and_grad(loss_fn)(p_arrs, ids, labels)
        else:
            # lax.scan carrying the accumulator: the carry dependency
            # forces microbatches to run strictly one after another, so
            # the peak-memory property holds by construction (an
            # unrolled Python loop would let XLA overlap forwards)
            mb = batch // accum
            ids_mb = ids.reshape(accum, mb, ids.shape[1])
            labels_mb = labels.reshape(accum, mb, labels.shape[1])

            def acc_step(carry, xs):
                loss_acc, grads_acc = carry
                l_i, g_i = jax.value_and_grad(loss_fn)(p_arrs, *xs)
                return (loss_acc + l_i,
                        [a + b for a, b in zip(grads_acc, g_i)]), None

            zeros = (jnp.zeros((), jnp.float32),
                     [jnp.zeros_like(p) for p in p_arrs])
            (loss, grads), _ = jax.lax.scan(acc_step, zeros,
                                            (ids_mb, labels_mb))
            loss = loss / accum
            grads = [g / accum for g in grads]
        new_p = [p - 1e-4 * g.astype(p.dtype) for p, g in zip(p_arrs, grads)]
        return loss, new_p

    step = jax.jit(train_step, donate_argnums=(0,))
    if os.environ.get("BENCH_ANALYZE", "1") == "1":
        # compiled-program introspection: XLA's own flop/byte counts +
        # peak memory — tells compute- vs HBM-bound without trace tooling.
        # The AOT executable then REPLACES the jit wrapper for the run
        # (the jit call cache doesn't reuse an AOT compile; calling step()
        # afterwards would compile the whole model twice)
        try:
            comp = step.lower(p_arrs, key, ids, labels).compile()
            ca = comp.cost_analysis() or {}
            ma = comp.memory_analysis()
            print(json.dumps({
                "aux_metric": "compiled_analysis",
                "xla_gflops": round(ca.get("flops", 0) / 1e9, 1),
                "xla_gbytes": round(ca.get("bytes accessed", 0) / 1e9, 2),
                "temp_mb": round(
                    getattr(ma, "temp_size_in_bytes", 0) / 1e6, 1),
                "argument_mb": round(
                    getattr(ma, "argument_size_in_bytes", 0) / 1e6, 1),
            }), file=sys.stderr)
            step = comp
        except Exception as e:
            print(f"bench: compiled analysis skipped: {e}", file=sys.stderr)
    loss, p_arrs = step(p_arrs, key, ids, labels)
    loss.block_until_ready()

    t0 = time.perf_counter()
    for _ in range(steps):
        loss, p_arrs = step(p_arrs, key, ids, labels)
    loss.block_until_ready()
    dt = time.perf_counter() - t0

    from paddle_tpu.profiler.mfu import llama_train_flops, PEAK_FLOPS, chip_kind
    flops = llama_train_flops(cfg, batch, seq)
    chip = os.environ.get("BENCH_CHIP") or chip_kind(jax.devices()[0])
    mfu = flops * steps / dt / PEAK_FLOPS[chip]
    print(json.dumps({"aux_metric": "mfu_" + chip,
                      "value": round(mfu * 100, 2), "unit": "%"}),
          file=sys.stderr)
    out = {
        "metric": "llama_1b_train_tokens_per_sec",
        "value": round(batch * seq * steps / dt, 2),
        "unit": "tokens/sec",
        "vs_baseline": None,
        "mfu_pct": round(mfu * 100, 2),
        "chip": chip,
        "config": {"batch": batch, "seq": seq, "remat": remat_mode,
                   "accum": accum,
                   "param_dtype": ("bf16" if pure_bf16
                                   else "fp32+amp" if amp else "fp32"),
                   **{k: v for k, v in dims.items()}},
    }
    # -- training observatory (ISSUE 12): memory peak, phase split,
    # numerics-sentinel cost
    out["train_peak_bytes"] = _train_peak_bytes()
    if os.environ.get("BENCH_PHASES", "1") == "1":
        def _fwd(ps):
            return _loss_fn(ps, ids, labels)

        def _grads(ps):
            return jax.value_and_grad(_loss_fn)(ps, ids, labels)[1]

        def _opt(ps, gs):
            return [p - 1e-4 * g.astype(p.dtype) for p, g in zip(ps, gs)]

        out["train_phase_breakdown"] = _phase_breakdown_probe(
            p_arrs, _fwd, _grads, _opt)
    out["numerics_overhead_pct"] = _numerics_overhead_pct()
    out["ledger_overhead_pct"] = _ledger_overhead_pct()
    out["compile_observatory_overhead_pct"] = \
        _compile_observatory_overhead_pct()
    _emit_observatory_aux(out)
    return out


def bench_bert():
    """Config-2 (BASELINE.json configs[1]): BERT/ERNIE-base fine-tune
    step time through the @to_static → HLO path on one device."""
    import time

    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models.bert import (BertConfig,
                                        BertForSequenceClassification)

    batch = int(os.environ.get("BENCH_BATCH", "32"))
    seq = int(os.environ.get("BENCH_SEQ", "128"))
    steps = int(os.environ.get("BENCH_STEPS", "10"))
    paddle.seed(0)
    cfg = BertConfig()                    # base size: L12 H768 A12
    model = BertForSequenceClassification(cfg)
    model.eval()                          # deterministic step timing
    static = paddle.jit.to_static(model)
    opt = paddle.optimizer.AdamW(learning_rate=5e-5,
                                 parameters=model.parameters())
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (batch, seq)))
    labels = paddle.to_tensor(rng.integers(0, cfg.num_labels, (batch,)))
    mask = paddle.to_tensor(
        (rng.random((batch, seq)) < 0.9).astype(np.int64))

    def step():
        loss, _ = static(ids, attention_mask=mask, labels=labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    loss = step()                          # compile
    jax.block_until_ready(loss._data)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step()
    jax.block_until_ready(loss._data)
    dt = (time.perf_counter() - t0) / steps
    return {
        "metric": "bert_base_finetune_step_ms",
        "value": round(dt * 1e3, 2),
        "unit": "ms/step",
        "vs_baseline": None,
        "config": {"batch": batch, "seq": seq},
        "samples_per_sec": round(batch / dt, 2),
    }


def bench_comm():
    """Gradient-communication bench (BENCH_MODEL=comm): a simulated dp-N
    bucketed+quantized gradient all-reduce over a synthetic parameter set,
    vs the per-tensor fp32 baseline. Emits ``dp_allreduce_wire_bytes``
    (the quantized wire volume) with the fp32 baseline, compression
    ratio, call counts and max quantization error riding along — the
    CommStats counters the distributed.comm layer maintains."""
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.comm import (GradientBucketer, get_comm_stats,
                                             reset_comm_stats)

    nprocs = int(os.environ.get("BENCH_DP", "4"))
    steps = int(os.environ.get("BENCH_STEPS", "5"))
    # synthetic grad set shaped like a small model: 16 weight matrices +
    # 16 vectors, ~4.3 MB fp32 per rank
    shapes = [(256, 256)] * 16 + [(1024,)] * 16

    def run(quant, fuse_mb):
        reset_comm_stats()

        def worker():
            r = dist.get_rank()
            rng = np.random.default_rng(r)
            params = [paddle.to_tensor(np.zeros(s, np.float32))
                      for s in shapes]
            for p in params:
                p.grad = paddle.to_tensor(
                    rng.normal(size=p.shape).astype(np.float32))
            b = GradientBucketer(params, fuse_grad_size_in_MB=fuse_mb,
                                 quantization=quant, error_feedback=True)
            t0 = time.perf_counter()
            for _ in range(steps):
                b.sync_grads()
            return time.perf_counter() - t0

        times = dist.spawn(worker, nprocs=nprocs).results
        return get_comm_stats().as_dict(), max(times)

    base, t_base = run(None, 0)        # per-tensor fp32 (the legacy path)
    quant, t_quant = run("int8", 32)   # bucketed blockwise-int8
    overlap = _bench_comm_overlap(nprocs)
    fused = _bench_fused_step()
    for name, val in (("comm_overlap_step_ratio",
                       overlap["comm_overlap_step_ratio"]),
                      ("fused_step_dispatch_ratio",
                       fused["fused_step_dispatch_ratio"])):
        print(json.dumps({"aux_metric": name, "value": val}),
              file=sys.stderr)
    return {
        "metric": "dp_allreduce_wire_bytes",
        "value": quant["wire_bytes"],
        "unit": "bytes",
        "vs_baseline": None,
        "fp32_wire_bytes": base["wire_bytes"],
        "compression_ratio": round(base["wire_bytes"]
                                   / max(quant["wire_bytes"], 1), 3),
        "calls_fp32": base["calls"],
        "calls_int8": quant["calls"],
        "max_quant_error": quant["quant_max_error"],
        "sync_seconds_fp32": round(t_base, 3),
        "sync_seconds_int8": round(t_quant, 3),
        "dp": nprocs,
        "steps": steps,
        **overlap,
        **fused,
    }


def _bench_comm_overlap(nprocs):
    """Overlapped (ready-bucket, in-backward dispatch) vs barrier-at-step
    dp step time on a simulated dp-N MLP train loop. Same bucketer, same
    quantized wire — the delta is purely WHEN the collectives run.

    Runs under the simulator's wire-cost model
    (``PADDLE_SIM_WIRE_LAT_US``/``GBPS``, applied to BOTH variants): the
    in-memory rendezvous is otherwise instantaneous, leaving no wire time
    for overlap to hide — exactly the cost that dominates a real
    multi-chip interconnect."""
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu import nn
    from paddle_tpu.distributed import collective as _collective
    from paddle_tpu.distributed import fleet

    steps = int(os.environ.get("BENCH_OVERLAP_STEPS", "8"))
    repeats = int(os.environ.get("BENCH_OVERLAP_REPEATS", "2"))
    # pure-latency wire by default: latency is propagation (it pipelines
    # across in-flight buckets, the thing overlap exploits); bandwidth
    # would add per-byte occupancy on top — opt in via BENCH_SIM_WIRE_GBPS
    wire_env = {"PADDLE_SIM_WIRE_LAT_US":
                os.environ.get("BENCH_SIM_WIRE_LAT_US", "10000"),
                "PADDLE_SIM_WIRE_GBPS":
                os.environ.get("BENCH_SIM_WIRE_GBPS", "0")}

    def run(overlap):
        strat = fleet.DistributedStrategy()
        strat.comm_overlap = overlap
        strat.fuse_grad_size_in_MB = 0.0625    # one bucket per layer weight
        strat.comm_quantization = "int8"
        strat.comm_configs = {"error_feedback": True}

        def worker():
            r = dist.get_rank()
            net = nn.Sequential(*[layer
                                  for _ in range(8)
                                  for layer in (nn.Linear(128, 128),
                                                nn.ReLU())])
            for k, p in enumerate(net.parameters()):
                rng = np.random.default_rng(100 + k)
                p.set_value(rng.normal(size=p.shape).astype(np.float32)
                            * 0.05)
            dp = dist.parallel.DataParallel(net, strategy=strat)
            opt = paddle.optimizer.SGD(learning_rate=0.01,
                                       parameters=net.parameters())
            rng = np.random.default_rng(r)
            xs = [paddle.to_tensor(rng.normal(size=(8, 128))
                                   .astype(np.float32))
                  for _ in range(steps + 2)]
            ts = []
            for i, x in enumerate(xs):           # first 2 = warmup/compile
                t0 = time.perf_counter()
                loss = (dp(x) ** 2).mean()
                loss.backward()
                opt.step()
                opt.clear_grad()
                if i >= 2:
                    ts.append(time.perf_counter() - t0)
            return ts

        def once():
            # per-step slowest rank, then the median step: robust to the
            # single-core scheduler noise that min/total-time is not
            res = dist.spawn(worker, nprocs=nprocs).results
            return float(np.median([max(col) for col in zip(*res)]))

        return min(once() for _ in range(repeats))

    saved = {k: os.environ.get(k) for k in wire_env}
    os.environ.update(wire_env)
    _collective._SIM_WIRE[0] = None      # re-read the knobs
    try:
        t_barrier = run(False)
        t_overlap = run(True)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        _collective._SIM_WIRE[0] = None
    return {
        "comm_overlap_step_ratio": round(t_overlap / t_barrier, 3),
        "overlap_step_seconds": round(t_overlap, 4),
        "barrier_step_seconds": round(t_barrier, 4),
        "overlap_dp": nprocs,
    }


def _bench_fused_step():
    """Host-dispatch collapse of the fused donated optimizer step on the
    llama config's parameter set: eager = one update dispatch per
    parameter per step, fused = O(1) compiled calls per step."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.optimizer.fused import opt_telemetry

    cfg = LlamaConfig(vocab_size=1000, hidden_size=128,
                      intermediate_size=256, num_hidden_layers=4,
                      num_attention_heads=4, num_key_value_heads=4,
                      max_position_embeddings=128)
    model = LlamaForCausalLM(cfg)
    params = [p for p in model.parameters() if p is not None]
    rng = np.random.default_rng(0)
    grads = [rng.normal(size=tuple(p.shape)).astype(np.float32) * 0.01
             for p in params]

    def dispatches(fused, steps=3):
        opt = paddle.optimizer.SGD(learning_rate=0.01, parameters=params)
        opt.fuse_step = fused
        counter = opt_telemetry()["dispatches"]
        mode = "fused" if fused else "eager"
        before = counter.value(mode=mode)
        for _ in range(steps):
            for p, g in zip(params, grads):
                p.grad = paddle.to_tensor(g)
            opt.step()
        return (counter.value(mode=mode) - before) / steps

    eager = dispatches(False)
    fused = dispatches(True)
    return {
        "fused_step_dispatches_eager": round(eager, 1),
        "fused_step_dispatches_fused": round(fused, 1),
        "fused_step_dispatch_ratio": round(eager / max(fused, 1e-9), 1),
        "fused_step_params": len(params),
    }


def bench_dispatch():
    """Eager (dygraph) per-op dispatch overhead vs raw jax — SURVEY §7.3
    item 1's top risk, measured. Reports µs/op for a no-grad elementwise
    add (the pure dispatch path) plus the grad-enabled ratio and the
    comparable raw-jax eager-vjp cost as aux lines."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle

    def clock(fn, n=2000, warmup=200):
        for _ in range(warmup):
            r = fn()
        jax.block_until_ready([getattr(r, "_data", r)])
        t0 = time.perf_counter()
        for _ in range(n):
            r = fn()
        jax.block_until_ready([getattr(r, "_data", r)])
        return (time.perf_counter() - t0) / n * 1e6

    xj = jnp.ones((256, 256))
    yj = jnp.ones((256, 256))
    xp = paddle.to_tensor(np.ones((256, 256), np.float32))
    yp = paddle.to_tensor(np.ones((256, 256), np.float32))
    xg = paddle.to_tensor(np.ones((256, 256), np.float32),
                          stop_gradient=False)

    raw = clock(lambda: jnp.add(xj, yj))
    nograd = clock(lambda: xp + yp)
    grad_on = clock(lambda: xg + yp)
    raw_vjp = clock(lambda: jax.vjp(jnp.add, xj, yj)[0], n=500, warmup=50)

    for name, val in (("raw_jnp_add_us", raw),
                      ("eager_grad_add_us", grad_on),
                      ("raw_jax_eager_vjp_us", raw_vjp),
                      ("grad_vs_rawvjp_ratio", grad_on / raw_vjp)):
        print(json.dumps({"aux_metric": name, "value": round(val, 2)}),
              file=sys.stderr)
    return {
        "metric": "eager_dispatch_overhead_vs_jax",
        "value": round(nograd / raw, 3),
        "unit": "x (add, 256x256; paddle eager / raw jnp)",
        "vs_baseline": None,
    }


def bench_llama_decode():
    """Serving-tier decode bench: batched autoregressive decode through the
    paged KV cache + Pallas paged_attention kernel (tokens/sec).

    ``BENCH_SHARED_PREFIX=1`` switches to the engine-level variant: the
    batch shares a common system prompt served through
    ``ContinuousServingEngine``'s prefix cache (one warm-up request fills
    the index; the timed requests prefill only their unique tails), and
    the record carries the measured prefix hit rate."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    batch = int(os.environ.get("BENCH_BATCH", "8"))
    prompt = int(os.environ.get("BENCH_PROMPT", "128"))
    new = int(os.environ.get("BENCH_NEW_TOKENS", "128"))
    shared_prefix = os.environ.get("BENCH_SHARED_PREFIX", "0") == "1"

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=32000, hidden_size=1024,
                      intermediate_size=2816, num_hidden_layers=8,
                      num_attention_heads=16, num_key_value_heads=8,
                      max_position_embeddings=max(2048, prompt + new))
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(0)

    if shared_prefix:
        import threading
        from paddle_tpu.inference import ContinuousServingEngine
        tail = int(os.environ.get("BENCH_TAIL", "16"))
        sys_prompt = rng.integers(0, cfg.vocab_size, prompt - tail)
        prompts = [np.concatenate([sys_prompt,
                                   rng.integers(0, cfg.vocab_size, tail)])
                   .astype(np.int64)[None] for _ in range(batch)]
        eng = ContinuousServingEngine(
            model, max_batch_size=batch, max_len=prompt + new,
            enable_prefix_cache=True)
        with eng:
            # first request prefills + registers the shared blocks
            eng.generate(prompts[0], max_new_tokens=new, timeout=1800)
            t0 = time.perf_counter()
            threads = [threading.Thread(
                target=lambda p=p: eng.generate(p, max_new_tokens=new,
                                                timeout=1800))
                for p in prompts[1:]]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
            cache = eng._cache
            lookups = max(cache.prefix_hits + cache.prefix_misses, 1)
            hit_rate = round(cache.prefix_hits / lookups, 3)
            cached = cache.cached_tokens_total
        return {
            "metric": "llama_paged_decode_tokens_per_sec",
            "value": round((batch - 1) * new / dt, 2),
            "unit": "tokens/sec",
            "vs_baseline": None,
            "shared_prefix": True,
            "prefix_hit_rate": hit_rate,
            "prefix_cached_tokens": int(cached),
        }

    ids = paddle.to_tensor(rng.integers(0, cfg.vocab_size,
                                        (batch, prompt)).astype(np.int64))
    model.generate(ids, max_new_tokens=4, use_paged_cache=True)  # warmup
    t0 = time.perf_counter()
    out = model.generate(ids, max_new_tokens=new, use_paged_cache=True)
    assert out.shape[1] == prompt + new
    dt = time.perf_counter() - t0
    return {
        "metric": "llama_paged_decode_tokens_per_sec",
        "value": round(batch * new / dt, 2),
        "unit": "tokens/sec",
        "vs_baseline": None,
    }


def bench_serving():
    """Engine-level serving fast-path bench (``BENCH_MODEL=serving``):
    TTFT and decode throughput through ``ContinuousServingEngine`` with a
    shared system prompt, prefix cache ON vs OFF in the same run — the
    paper's production story (millions of users share system prompts /
    few-shot templates; arxiv 2605.25645 shows prefix reuse is the
    dominant TTFT lever on TPU)."""
    import threading

    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.inference import ContinuousServingEngine
    from paddle_tpu.profiler import request_trace as rt

    # fresh sliding window: the SLO percentiles below cover THIS run
    rt.reset_slo_monitor()
    n_req = int(os.environ.get("BENCH_REQUESTS", "8"))
    sys_len = int(os.environ.get("BENCH_SYS_PROMPT", "128"))
    tail = int(os.environ.get("BENCH_TAIL", "8"))
    new = int(os.environ.get("BENCH_NEW_TOKENS", "8"))
    chunk = int(os.environ.get("BENCH_CHUNK_TOKENS", "64"))

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=32000, hidden_size=256,
                      intermediate_size=704, num_hidden_layers=4,
                      num_attention_heads=8, num_key_value_heads=4,
                      max_position_embeddings=max(2048, sys_len + tail + new))
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(0)
    sys_prompt = rng.integers(0, cfg.vocab_size, sys_len)
    prompts = [np.concatenate([sys_prompt,
                               rng.integers(0, cfg.vocab_size, tail)])
               .astype(np.int64)[None] for _ in range(n_req)]

    def run(prefix_cache):
        eng = ContinuousServingEngine(
            model, max_batch_size=4, max_len=sys_len + tail + new + 16,
            enable_prefix_cache=prefix_cache, prefill_chunk_tokens=chunk)
        stats = {}
        with eng:
            # request 0 warms compiled programs AND (when enabled) fills
            # the prefix index with the shared system-prompt blocks
            eng.generate(prompts[0], max_new_tokens=new, timeout=1800)
            ttfts = []
            for p in prompts[1:]:
                t0 = time.perf_counter()
                eng.generate(p, max_new_tokens=1, timeout=1800)
                ttfts.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            outs = [None] * (n_req - 1)

            def _gen(i, p):
                outs[i] = np.asarray(
                    eng.generate(p, max_new_tokens=new,
                                 timeout=1800).numpy())

            threads = [threading.Thread(target=_gen, args=(i, p))
                       for i, p in enumerate(prompts[1:])]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
            # content digest of every delivered stream, in prompt order
            # (greedy decode is deterministic, so this is stable across
            # runs — bench_compare flags any drift as output-content
            # regression, not just perf regression)
            import hashlib
            h = hashlib.sha1()
            for o in outs:
                h.update(np.ascontiguousarray(o).tobytes())
            cache = eng._cache
            stats = {
                "ttft_ms": round(float(np.mean(ttfts)) * 1e3, 2),
                "tokens_per_sec": round((n_req - 1) * new / dt, 2),
                "prefix_hits": int(cache.prefix_hits),
                "prefix_misses": int(cache.prefix_misses),
                "cached_tokens": int(cache.cached_tokens_total),
                "token_digest": h.hexdigest(),
            }
        return stats

    def run_mixed():
        """MIXED concurrent load (varied prompt lengths, staggered
        arrivals) so prefill and decode contend for
        every tick — the regime the one-kernel token-budget scheduler
        (Ragged Paged Attention, arxiv 2604.15464) exists for."""
        mix_rng = np.random.default_rng(1)
        lens = [sys_len // 2 + int(mix_rng.integers(1, sys_len // 2 + 8))
                for _ in range(n_req)]
        mix = [mix_rng.integers(0, cfg.vocab_size, n).astype(np.int64)[None]
               for n in lens]
        eng = ContinuousServingEngine(
            model, max_batch_size=4, max_len=max(lens) + new + 16,
            enable_prefix_cache=False, prefill_chunk_tokens=chunk,
            token_budget=chunk)
        with eng:
            eng.generate(mix[0], max_new_tokens=new, timeout=1800)  # warmup
            t0 = time.perf_counter()
            threads = [threading.Thread(
                target=lambda p=p, i=i: (time.sleep(0.002 * i),
                                         eng.generate(p, max_new_tokens=new,
                                                      timeout=1800)))
                for i, p in enumerate(mix[1:])]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
        waste = 1.0 - (eng.useful_tokens_total
                       / max(eng.padded_tokens_total, 1))
        return {"tokens_per_sec": (n_req - 1) * new / dt,
                "waste_ratio": round(waste, 3),
                "buckets": sorted(eng.ragged_buckets_used)}

    def run_spec(spec_on):
        """Speculative-decode on-vs-off variant: short prompts + long
        decodes so TPOT dominates, tier-2 self-draft drafter (acceptance
        ~1.0) as the upper bound. Interpret-tier wall clock understates
        the win (a verify span costs k+1 attention grid steps there, and
        the draft forwards are full model runs), so the target-forwards-
        per-token ratio is emitted alongside as the device-tier proxy —
        the same convention as the ragged tokens/s ratio."""
        sp_rng = np.random.default_rng(2)
        sp_new = max(new, 8)
        sp = [sp_rng.integers(0, cfg.vocab_size, 24).astype(np.int64)[None]
              for _ in range(4)]
        eng = ContinuousServingEngine(
            model, max_batch_size=4, max_len=24 + sp_new + 16,
            enable_prefix_cache=False, token_budget=64,
            spec_decode=spec_on, spec_k=4,
            draft_model=model if spec_on else None)
        with eng:
            eng.generate(sp[0], max_new_tokens=2, timeout=1800)  # warmup
            t0 = time.perf_counter()
            threads = [threading.Thread(
                target=lambda p=p: eng.generate(p, max_new_tokens=sp_new,
                                                timeout=1800))
                for p in sp]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
        tokens = len(sp) * sp_new
        return {
            "tokens_per_sec": tokens / dt,
            "decode_ticks": eng.decode_steps,
            "tokens": tokens,
            "forwards_per_token": eng.decode_steps / max(tokens, 1),
            "acceptance": (eng.spec_accepted_tokens
                           / max(eng.spec_drafted_tokens, 1)),
            "drafted": eng.spec_drafted_tokens,
            # batched drafting win: draft-model forwards per drafting
            # tick (the per-slot path pays ~slots*k forwards per tick,
            # the batched path pays ~k)
            "draft_forwards_per_tick": round(
                eng.spec_draft_forwards / max(eng.spec_draft_ticks, 1),
                3),
        }

    def compile_probe():
        """Compile-observatory steady-state probe: warm every declared
        program bucket via ``warmup_programs()``, then replay the mixed
        prefill+decode workload — post-warmup trace-cache misses must
        be ZERO (``serving_recompiles_per_1k_ticks == 0`` is the
        recompile-storm acceptance gate), and the warmup wall seconds
        are the cold-start compile budget a fleet pays per process."""
        from paddle_tpu.profiler import compile_observatory as co
        co.reset()
        co.enable()
        mix_rng = np.random.default_rng(3)
        lens = [sys_len // 2 + int(mix_rng.integers(1, sys_len // 2 + 8))
                for _ in range(n_req)]
        mix = [mix_rng.integers(0, cfg.vocab_size, n)
               .astype(np.int64)[None] for n in lens]
        eng = ContinuousServingEngine(
            model, max_batch_size=4, max_len=max(lens) + new + 16,
            enable_prefix_cache=False, prefill_chunk_tokens=chunk,
            token_budget=chunk)
        warmup_s = sum(eng.warmup_programs().values())
        base = co.snapshot()["totals"]["misses"]
        with eng:
            ticks0 = eng.ragged_steps
            threads = [threading.Thread(
                target=lambda p=p, i=i: (time.sleep(0.002 * i),
                                         eng.generate(p,
                                                      max_new_tokens=new,
                                                      timeout=1800)))
                for i, p in enumerate(mix)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            misses = co.snapshot()["totals"]["misses"] - base
            ticks = max(eng.ragged_steps - ticks0, 1)
        return {"warmup_compile_s": round(warmup_s, 3),
                "post_warmup_misses": int(misses),
                "recompiles_per_1k_ticks": round(misses / ticks * 1e3,
                                                 3)}

    def qblock_step_probe():
        """Q-block vs per-token ragged grid at a representative mixed
        prefill+decode tick: the per-token kernel runs one grid step per
        (token, kv_head, page); the q-block kernel runs one per job of
        its flat (q_block, page) list, every KV head in it. The step
        ratio is the device-tier
        speed lever (fewer, fatter MXU launches for the same math) and
        is exact from the schedules — no timing noise."""
        from paddle_tpu.ops.pallas.ragged_paged_attention import (
            qblock_job_list, _qblock_rows)
        page, pps = 16, 8
        # 3 decode slots mid-stream + a chunked-prefill tail + a fresh
        # prefill: 64 packed tokens, the run_mixed regime
        seq_slots = np.asarray([0, 1, 2, 3, 4], np.int32)
        q_starts = np.asarray([0, 1, 2, 3, 32], np.int32)
        q_lens = np.asarray([1, 1, 1, 29, 32], np.int32)
        ctx = np.asarray([97, 54, 21, 29, 32], np.int32)
        tbl = np.zeros((8, pps), np.int32)
        tokens = 64
        kv_heads = cfg.num_key_value_heads
        _, _, jobs = qblock_job_list(
            tokens, seq_slots, q_starts, q_lens, ctx, tbl,
            _qblock_rows(), page)
        q_steps = jobs.shape[1]
        t_steps = tokens * kv_heads * pps
        return {"qblock_grid_steps": int(q_steps),
                "token_grid_steps": int(t_steps),
                "step_ratio": round(q_steps / t_steps, 4)}

    def run_int8_weights():
        """Fully-quantized serving config: int8 weights end-to-end
        (``quantize_linears`` routes every Linear through the Pallas
        int8 GEMM) + int8 KV pages, on a fresh same-seed model so the
        shared float model above stays untouched. Emits the tokens/s
        ratio vs the float engine and the weight-footprint win."""
        import hashlib

        from paddle_tpu.nn.layers.common import Linear

        paddle.seed(0)
        qmodel = LlamaForCausalLM(cfg)
        eng = ContinuousServingEngine(
            qmodel, max_batch_size=4, max_len=sys_len + tail + new + 16,
            enable_prefix_cache=False, prefill_chunk_tokens=chunk,
            weight_dtype="int8", kv_dtype="int8")
        with eng:
            eng.generate(prompts[0], max_new_tokens=new, timeout=1800)
            t0 = time.perf_counter()
            outs = [None] * (n_req - 1)

            def _gen(i, p):
                outs[i] = np.asarray(
                    eng.generate(p, max_new_tokens=new,
                                 timeout=1800).numpy())

            threads = [threading.Thread(target=_gen, args=(i, p))
                       for i, p in enumerate(prompts[1:])]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
        int8_bytes = float_bytes = 0

        def visit(layer):
            nonlocal int8_bytes, float_bytes
            if isinstance(layer, Linear) and layer._w_int8 is not None:
                int8_bytes += (layer._w_int8.nbytes
                               + layer._w_scale.nbytes)
                float_bytes += layer._w_int8.size * 4
            for sub in layer._sub_layers.values():
                if sub is not None:
                    visit(sub)

        visit(qmodel)
        h = hashlib.sha1()
        for o in outs:
            h.update(np.ascontiguousarray(o).tobytes())
        return {
            "tokens_per_sec": (n_req - 1) * new / dt,
            "quantized_linears": int(eng.quantized_linears),
            "weight_bytes_ratio": round(int8_bytes
                                        / max(float_bytes, 1), 4),
            "token_digest": h.hexdigest(),
        }

    def kv_capacity_probe():
        """``BENCH_KV_DTYPE=int8`` capacity probe: max concurrent
        full-length sessions a fixed pool byte budget holds, int8 vs
        native pages (analytic from the page codec's byte layout,
        cross-checked against a live int8 engine's measured
        ``page_nbytes``)."""
        from paddle_tpu.models.generation import kv_page_nbytes
        pool_mb = float(os.environ.get("BENCH_KV_POOL_MB", "64"))
        budget = int(pool_mb * 2 ** 20)
        page = 16
        seq_len = sys_len + tail + new + 16
        pages_per_seq = -(-seq_len // page)
        head_dim = cfg.hidden_size // cfg.num_attention_heads
        kv_heads = cfg.num_key_value_heads
        native_pb = kv_page_nbytes(kv_heads, head_dim, page, "native",
                                   "float32", cfg.num_hidden_layers)
        int8_pb = kv_page_nbytes(kv_heads, head_dim, page, "int8",
                                 "float32", cfg.num_hidden_layers)
        native_sessions = budget // (pages_per_seq * native_pb)
        int8_sessions = budget // (pages_per_seq * int8_pb)
        # prove the int8 pool serves real traffic + measured page bytes
        eng = ContinuousServingEngine(model, max_batch_size=2,
                                      max_len=seq_len, kv_dtype="int8")
        with eng:
            eng.generate(prompts[0], max_new_tokens=new, timeout=1800)
            measured_pb = eng._cache.page_nbytes
        return {
            "pool_mb": pool_mb,
            "native_sessions": int(native_sessions),
            "int8_sessions": int(int8_sessions),
            "capacity_ratio": round(int8_sessions
                                    / max(native_sessions, 1), 2),
            "int8_page_nbytes": int(int8_pb),
            "int8_page_nbytes_measured": int(measured_pb),
        }

    def run_kv_tier():
        """Tiered-KV probe (ISSUE 19): a distinct-prefix working set
        several times the device page pool, served twice — the second
        pass re-admits every prefix AFTER its pages were evicted from
        device. With ``host_pool_mb=0`` that is a full re-prefill; with
        the host tier on, eviction demoted the pages to host RAM and
        re-admission promotes them back (a memcpy, not a forward pass).
        TTFT ratio off/on is ``serving_kv_tier_hit_speedup``; the
        delivered streams must be bit-identical either way. Small
        prefill chunks keep the comparison honest off-TPU: a full
        re-prefill pays ceil(prompt/chunk) chunk ticks where a
        host-tier hit pays one, so the ratio survives the
        interpret-mode per-forward floor that would otherwise mask
        the prefill-token savings."""
        import hashlib
        kvt_pref, kvt_tail_n = 96, 8
        kvt_n = int(os.environ.get("BENCH_KV_TIER_REQS", "10"))
        kvt_len = kvt_pref + kvt_tail_n + new + 16
        kvt_rng = np.random.default_rng(7)
        kvt_prompts = [
            np.concatenate([kvt_rng.integers(0, cfg.vocab_size, kvt_pref),
                            kvt_rng.integers(0, cfg.vocab_size, kvt_tail_n)])
            .astype(np.int64)[None] for _ in range(kvt_n)]

        def one(pool_mb):
            eng = ContinuousServingEngine(
                model, max_batch_size=2, max_len=kvt_len,
                enable_prefix_cache=True, num_pages=10,
                host_pool_mb=pool_mb, prefill_chunk_tokens=32)
            with eng:
                # pass 1: populate the prefix index; the working set
                # (60 prefix pages at the default 10 requests) dwarfs
                # the 9-page device pool, so every prefix is evicted
                # (and, with the tier on, demoted) before its
                # re-admission below
                for p in kvt_prompts:
                    eng.generate(p, max_new_tokens=1, timeout=1800)
                ttfts = []
                for p in kvt_prompts:
                    t0 = time.perf_counter()
                    eng.generate(p, max_new_tokens=1, timeout=1800)
                    ttfts.append(time.perf_counter() - t0)
                h = hashlib.sha1()
                for p in kvt_prompts:
                    o = np.asarray(eng.generate(
                        p, max_new_tokens=new, timeout=1800).numpy())
                    h.update(np.ascontiguousarray(o).tobytes())
                pool = eng._host_pool
                return {"ttft_ms": round(float(np.mean(ttfts)) * 1e3, 2),
                        "promotions": int(pool.promotions),
                        "demotions": int(pool.demotions),
                        "token_digest": h.hexdigest()}

        t_off = one(0)
        t_on = one(64)
        assert t_on["token_digest"] == t_off["token_digest"], \
            "host-tier promotion changed delivered tokens"
        return {
            "speedup": round(t_off["ttft_ms"]
                             / max(t_on["ttft_ms"], 1e-6), 2),
            "ttft_host_ms": t_on["ttft_ms"],
            "ttft_reprefill_ms": t_off["ttft_ms"],
            "promotions": t_on["promotions"],
            "demotions": t_on["demotions"],
            "token_digest": t_on["token_digest"],
        }

    def run_long_context():
        """Long-context probe (ISSUE 19): a prompt larger than the
        device page pool, chunk-prefilled through the sep ring-attention
        schedule (host-striped KV, pow2 decode tail). Emits prompt
        tokens per prefill-wall-second and cross-checks the delivered
        stream against a single-device oracle engine whose pool DOES
        hold the whole prompt."""
        import hashlib
        lc_len = 512
        lc_rng = np.random.default_rng(9)
        lc_prompt = lc_rng.integers(0, cfg.vocab_size,
                                    lc_len).astype(np.int64)[None]
        lc_max = lc_len + new + 16
        eng = ContinuousServingEngine(
            model, max_batch_size=2, max_len=lc_max,
            enable_prefix_cache=False, num_pages=16,  # 240-token pool
            sep_prefill=True, sep_stripe_tokens=64,
            sep_threshold_tokens=256)
        with eng:
            eng.generate(lc_prompt, max_new_tokens=1, timeout=1800)
            t0 = time.perf_counter()
            eng.generate(lc_prompt, max_new_tokens=1, timeout=1800)
            dt = time.perf_counter() - t0
            out = np.asarray(eng.generate(
                lc_prompt, max_new_tokens=new, timeout=1800).numpy())
            sep_reqs = int(eng.sep_requests)
            chunks = int(eng._cache.sep_chunks)
        oracle = ContinuousServingEngine(
            model, max_batch_size=2, max_len=lc_max,
            enable_prefix_cache=False)
        with oracle:
            want = np.asarray(oracle.generate(
                lc_prompt, max_new_tokens=new, timeout=1800).numpy())
        assert np.array_equal(out, want), \
            "sep long-context decode diverged from single-device oracle"
        h = hashlib.sha1(np.ascontiguousarray(out).tobytes())
        return {
            "tokens_per_s": round((lc_len + 1) / dt, 2),
            "prompt_tokens": lc_len,
            "sep_requests": sep_reqs,
            "sep_prefill_chunks": chunks,
            "oracle_match": True,
            "token_digest": h.hexdigest(),
        }

    off = run(False)
    on = run(True)
    mixed_ragged = run_mixed()
    spec_on = run_spec(True)
    spec_off = run_spec(False)
    qblock = qblock_step_probe()
    compile_obs = compile_probe()
    int8w = run_int8_weights()
    int8w_ratio = round(int8w["tokens_per_sec"]
                        / max(off["tokens_per_sec"], 1e-9), 2)
    spec_speedup = round(spec_on["tokens_per_sec"]
                         / max(spec_off["tokens_per_sec"], 1e-9), 2)
    kv_probe = (kv_capacity_probe()
                if os.environ.get("BENCH_KV_DTYPE", "").lower() == "int8"
                else None)
    kv_tier = run_kv_tier()
    long_ctx = run_long_context()
    # latency percentiles + goodput from the request-trace SLO monitor
    # (every engine generate above fed it) — the bench trajectory's
    # first latency-percentile entries
    slo = rt.slo_report()
    aux = [
        ("serving_ragged_waste_ratio", mixed_ragged["waste_ratio"]),
        ("serving_p95_ttft_ms", round(slo["ttft"]["p95_s"] * 1e3, 2)),
        ("serving_p95_tpot_ms", round(slo["tpot"]["p95_s"] * 1e3, 2)),
        ("serving_goodput_ratio", round(slo["goodput_ratio"], 3)),
        ("serving_spec_tpot_speedup", spec_speedup),
        ("serving_spec_acceptance_rate",
         round(spec_on["acceptance"], 3)),
        ("serving_spec_forwards_per_token",
         round(spec_on["forwards_per_token"], 3)),
        ("serving_qblock_step_ratio", qblock["step_ratio"]),
        ("serving_int8_weight_tokens_per_s_ratio", int8w_ratio),
        ("serving_int8_weight_bytes_ratio",
         int8w["weight_bytes_ratio"]),
        ("spec_draft_forwards_per_tick",
         spec_on["draft_forwards_per_tick"]),
        ("serving_recompiles_per_1k_ticks",
         compile_obs["recompiles_per_1k_ticks"]),
        ("serving_warmup_compile_s", compile_obs["warmup_compile_s"]),
        ("serving_kv_tier_hit_speedup", kv_tier["speedup"]),
        ("serving_long_context_tokens_per_s", long_ctx["tokens_per_s"]),
    ]
    if kv_probe is not None:
        aux.append(("serving_kv_capacity_ratio",
                    kv_probe["capacity_ratio"]))
    # delivered-token-stream content digest (determinism ledger's
    # cross-run story at bench granularity): bench_compare treats
    # *_digest fields as exact-match metrics, so output-content drift
    # between two bench runs fails the comparison like a perf
    # regression would
    aux.append(("serving_token_digest", on["token_digest"]))
    for name, val in aux:
        print(json.dumps({"aux_metric": name, "value": val}),
              file=sys.stderr)
    return {
        "p95_ttft_ms": round(slo["ttft"]["p95_s"] * 1e3, 2),
        "p95_tpot_ms": round(slo["tpot"]["p95_s"] * 1e3, 2),
        "p95_queue_wait_ms": round(slo["queue_wait"]["p95_s"] * 1e3, 2),
        "goodput_ratio": round(slo["goodput_ratio"], 3),
        "metric": "serving_prefix_ttft_speedup",
        "value": round(off["ttft_ms"] / max(on["ttft_ms"], 1e-6), 2),
        "unit": "x (mean TTFT, prefix cache off / on, shared sys prompt)",
        "vs_baseline": None,
        "ttft_cached_ms": on["ttft_ms"],
        "ttft_nocache_ms": off["ttft_ms"],
        "tokens_per_sec_cached": on["tokens_per_sec"],
        "tokens_per_sec_nocache": off["tokens_per_sec"],
        "prefix_hits": on["prefix_hits"],
        "prefix_cached_tokens": on["cached_tokens"],
        "serving_token_digest": on["token_digest"],
        # mixed concurrent prefill+decode load
        "ragged_tokens_per_sec": round(mixed_ragged["tokens_per_sec"], 2),
        "ragged_waste_ratio": mixed_ragged["waste_ratio"],
        "ragged_buckets": mixed_ragged["buckets"],
        # speculative decode on-vs-off (self-draft upper bound)
        "serving_spec_tpot_speedup": spec_speedup,
        "spec_acceptance_rate": round(spec_on["acceptance"], 3),
        "spec_drafted_tokens": spec_on["drafted"],
        "spec_forwards_per_token": round(spec_on["forwards_per_token"], 3),
        "nospec_forwards_per_token": round(spec_off["forwards_per_token"],
                                           3),
        "spec_draft_forwards_per_tick": spec_on["draft_forwards_per_tick"],
        # compile observatory: cold-start warmup cost + steady-state
        # recompile rate (must be 0 — misses after warmup mean shapes
        # are churning past the declared buckets)
        "serving_recompiles_per_1k_ticks":
            compile_obs["recompiles_per_1k_ticks"],
        "serving_warmup_compile_s": compile_obs["warmup_compile_s"],
        "compile_post_warmup_misses": compile_obs["post_warmup_misses"],
        # q-block vs per-token ragged grid (exact step counts)
        "serving_qblock_step_ratio": qblock["step_ratio"],
        "qblock_grid_steps": qblock["qblock_grid_steps"],
        "token_grid_steps": qblock["token_grid_steps"],
        # fully-quantized config: int8 weights + int8 KV pages
        "serving_int8_weight_tokens_per_s_ratio": int8w_ratio,
        "serving_int8_weight_bytes_ratio": int8w["weight_bytes_ratio"],
        "int8_weight_token_digest": int8w["token_digest"],
        "quantized_linears": int8w["quantized_linears"],
        "kv_capacity_probe": kv_probe,
        # tiered KV: host-RAM prefix spill (TTFT on re-admission, host
        # tier vs full re-prefill, identical token streams enforced)
        "serving_kv_tier_hit_speedup": kv_tier["speedup"],
        "kv_tier_ttft_host_ms": kv_tier["ttft_host_ms"],
        "kv_tier_ttft_reprefill_ms": kv_tier["ttft_reprefill_ms"],
        "kv_tier_promotions": kv_tier["promotions"],
        "kv_tier_token_digest": kv_tier["token_digest"],
        # long-context sep-parallel prefill (prompt > device page pool,
        # bit-identical to the single-device oracle)
        "serving_long_context_tokens_per_s": long_ctx["tokens_per_s"],
        "long_context_prompt_tokens": long_ctx["prompt_tokens"],
        "long_context_sep_chunks": long_ctx["sep_prefill_chunks"],
        "long_context_token_digest": long_ctx["token_digest"],
        "config": {"requests": n_req, "sys_prompt": sys_len, "tail": tail,
                   "new_tokens": new, "chunk_tokens": chunk},
    }


def bench_fleet():
    """Fleet-router bench (``BENCH_MODEL=fleet``): shared-system-prompt
    mixed-tenant workload over 2 engine replicas, prefix-affinity
    routing vs round-robin — the PR-4 ``serving_prefix_ttft_speedup``
    methodology applied at the orchestration layer (the Gemma-on-TPU
    serving study, arxiv 2605.25645: replica routing + cache locality
    decide TPU serving economics)."""
    import threading

    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.elastic.tcp_kv import MemKVStore
    from paddle_tpu.inference import ServingRouter
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.profiler import request_trace as rt

    # fresh sliding window: the SLO percentiles below cover THIS run
    rt.reset_slo_monitor()
    n_req = int(os.environ.get("BENCH_REQUESTS", "8"))
    sys_len = int(os.environ.get("BENCH_SYS_PROMPT", "128"))
    tail = int(os.environ.get("BENCH_TAIL", "8"))
    new = int(os.environ.get("BENCH_NEW_TOKENS", "8"))

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=32000, hidden_size=256,
                      intermediate_size=704, num_hidden_layers=4,
                      num_attention_heads=8, num_key_value_heads=4,
                      max_position_embeddings=max(2048, sys_len + tail + new))
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(0)
    sys_prompt = rng.integers(0, cfg.vocab_size, sys_len)
    prompts = [np.concatenate([sys_prompt,
                               rng.integers(0, cfg.vocab_size, tail)])
               .astype(np.int64)[None] for _ in range(n_req)]

    def run(policy):
        router = ServingRouter(
            model, num_replicas=2, policy=policy, store=MemKVStore(),
            heartbeat_ttl=600.0,
            engine_kwargs=dict(max_batch_size=4,
                               max_len=sys_len + tail + new + 16))
        with router:
            # request 0 warms compiled programs on ONE replica and (under
            # affinity) pins the shared chain there; round-robin then
            # pays the prefill again on the other replica
            router.generate(prompts[0], max_new_tokens=new,
                            tenant="tenant0", timeout=1800)
            ttfts = []
            for i, p in enumerate(prompts[1:], start=1):
                t0 = time.perf_counter()
                router.generate(p, max_new_tokens=1,
                                tenant=f"tenant{i % 3}", timeout=1800)
                ttfts.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            threads = [threading.Thread(
                target=lambda p=p, i=i: router.generate(
                    p, max_new_tokens=new, tenant=f"tenant{i % 3}",
                    timeout=1800))
                for i, p in enumerate(prompts[1:], start=1)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
            cached = sum(r.engine._cache.cached_tokens_total
                         for r in router.replicas)
            stats = router.stats()
        return {
            "ttft_ms": round(float(np.mean(ttfts)) * 1e3, 2),
            "tokens_per_sec": round((n_req - 1) * new / dt, 2),
            "cached_tokens": int(cached),
            "affinity_hits": stats["affinity_hits"],
            "affinity_matchable": stats["affinity_matchable"],
        }

    rr = run("round_robin")
    aff = run("affinity")
    speedup = round(rr["ttft_ms"] / max(aff["ttft_ms"], 1e-6), 2)
    # fleet-level SLO percentiles + goodput: every routed request above
    # fed the request-trace SLO monitor (TTFT measured at the ROUTER,
    # queue wait and per-token gaps from the engine spans)
    slo = rt.slo_report()
    replay_rep = _bench_fleet_replay(model, sys_len, tail, new)
    # chaos pair: the SAME seeded burst with a replica killed mid-run,
    # controller-off vs controller-on — the ISSUE-14 acceptance numbers
    # (recover_ratio > 1 means the controller recovered faster)
    kill_spec = os.environ.get("BENCH_FLEET_FAULT",
                               "kill:replica=r1,request=4")
    ctl_off = _bench_fleet_replay(model, sys_len, tail, new,
                                  fault_spec=kill_spec)
    ctl_on = _bench_fleet_replay(model, sys_len, tail, new,
                                 fault_spec=kill_spec, controller=True)
    export_pct, scrape_age = _bench_telemetry_plane(model, sys_len, new)
    ttr_on = ctl_on.get("time_to_recover_s")
    ttr_off = ctl_off.get("time_to_recover_s")
    if ttr_on is None:
        recover_ratio = None
    elif ttr_off is None:
        # controller-off never recovered inside its observation window:
        # credit the whole window (a floor, not a fabrication)
        window = max(ctl_off.get("observed_s") or 0.0, ttr_on)
        recover_ratio = round(window / max(ttr_on, 1e-6), 2)
    else:
        recover_ratio = round(ttr_off / max(ttr_on, 1e-6), 2)
    n_actions = ctl_on.get("controller_actions_total", 0)
    for name, val in (
            ("fleet_affinity_ttft_speedup", speedup),
            ("fleet_affinity_cached_tokens", aff["cached_tokens"]),
            ("fleet_rr_cached_tokens", rr["cached_tokens"]),
            ("fleet_p95_ttft_ms", round(slo["ttft"]["p95_s"] * 1e3, 2)),
            ("fleet_p95_tpot_ms", round(slo["tpot"]["p95_s"] * 1e3, 2)),
            ("fleet_goodput_ratio", round(slo["goodput_ratio"], 3)),
            ("fleet_goodput_under_burst",
             replay_rep.get("goodput_under_burst")),
            ("fleet_time_to_recover_s",
             replay_rep.get("time_to_recover_s")),
            ("fleet_controller_recover_ratio", recover_ratio),
            ("fleet_controller_actions", n_actions),
            ("telemetry_export_overhead_pct", export_pct),
            ("telemetry_scrape_age_s", scrape_age)):
        print(json.dumps({"aux_metric": name, "value": val}),
              file=sys.stderr)
    return {
        "p95_ttft_ms": round(slo["ttft"]["p95_s"] * 1e3, 2),
        "p95_tpot_ms": round(slo["tpot"]["p95_s"] * 1e3, 2),
        "p95_queue_wait_ms": round(slo["queue_wait"]["p95_s"] * 1e3, 2),
        "goodput_ratio": round(slo["goodput_ratio"], 3),
        "metric": "fleet_affinity_ttft_speedup",
        "value": speedup,
        "unit": "x (mean TTFT, round-robin / affinity, 2 replicas, "
                "shared sys prompt)",
        "vs_baseline": None,
        "ttft_affinity_ms": aff["ttft_ms"],
        "ttft_round_robin_ms": rr["ttft_ms"],
        "tokens_per_sec_affinity": aff["tokens_per_sec"],
        "tokens_per_sec_round_robin": rr["tokens_per_sec"],
        "cached_tokens_affinity": aff["cached_tokens"],
        "cached_tokens_round_robin": rr["cached_tokens"],
        "affinity_hit_rate": round(
            aff["affinity_hits"] / max(aff["affinity_matchable"], 1), 3),
        "replay": replay_rep,
        "fleet_controller_recover_ratio": recover_ratio,
        "fleet_controller_actions": n_actions,
        "telemetry_export_overhead_pct": export_pct,
        "telemetry_scrape_age_s": scrape_age,
        "controller_replay": {"on": ctl_on, "off": ctl_off,
                              "fault": kill_spec},
        "config": {"requests": n_req, "sys_prompt": sys_len, "tail": tail,
                   "new_tokens": new, "replicas": 2},
    }


def _bench_telemetry_plane(model, sys_len, new):
    """(telemetry_export_overhead_pct, telemetry_scrape_age_s): the
    serving-step cost of having a live HTTP exporter + an active
    scraper against it (ISSUE 15), measured with the standard
    ``_telemetry_overhead_pct`` machinery — the same engine step runs
    bare and then with the plane fully on (server thread + 20 Hz
    scrape), so a regression in the exporter hot path shows up as a
    perf delta. The scrape age is the freshness of the last successful
    scrape at teardown — a scraper that cannot keep up shows a growing
    age long before it shows wrong numbers."""
    import numpy as np
    from paddle_tpu.inference import ContinuousServingEngine
    from paddle_tpu.profiler.exporter import TelemetryServer
    from paddle_tpu.profiler.scrape import FleetScraper

    eng = ContinuousServingEngine(
        model, max_batch_size=2, max_len=max(sys_len // 4, 16) + new + 8)
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, 1000,
                          (1, max(sys_len // 8, 4))).astype(np.int64)
    state = {"server": None, "scraper": None, "age": None}
    with eng:
        eng.generate(prompt, max_new_tokens=2, timeout=1800)   # warm

        def step():
            return eng.generate(prompt, max_new_tokens=2, timeout=1800)

        def setup():
            srv = TelemetryServer(instance="bench", port=0).start()
            sc = FleetScraper(endpoints={"bench": srv.address},
                              interval_s=0.05, stale_s=60.0)
            sc.start()
            state["server"], state["scraper"] = srv, sc

        def teardown():
            sc, srv = state["scraper"], state["server"]
            if sc is not None:
                sc.scrape_once()
                state["age"] = sc.last_scrape_age()
                sc.stop()
            if srv is not None:
                srv.stop()

        pct = _telemetry_overhead_pct(step, lambda r: None, steps=5,
                                      instrumented_step=step,
                                      setup=setup, teardown=teardown)
    age = state["age"]
    return pct, None if age is None else round(age, 4)


def _bench_fleet_replay(model, sys_len, tail, new, fault_spec=None,
                        controller=False):
    """Seeded bursty replay against a fresh 2-replica fleet: the
    goodput-under-burst / time-to-recover measurement rig (ISSUE 11;
    ROADMAP 4's controller gets judged by exactly these numbers). SLO
    TTFT target is adaptive — 2x a measured warm-path request — so the
    burst (not host speed) decides the violation story. ``fault_spec``
    installs a fleet fault plan (e.g. ``kill:replica=r1,request=4``)
    for the run; ``controller=True`` runs a ``FleetController`` beside
    the replay — the ISSUE-14 chaos pair compares the same seed with
    the controller off vs on."""
    import numpy as np
    from paddle_tpu.distributed import fault as flt
    from paddle_tpu.distributed.fleet.elastic.tcp_kv import MemKVStore
    from paddle_tpu.inference import FleetController, ServingRouter
    from paddle_tpu.inference.fleet import replay as rp
    from paddle_tpu.profiler import alerts, request_trace as rt
    from paddle_tpu.profiler import timeseries

    seed = int(os.environ.get("BENCH_REPLAY_SEED", "11"))
    duration = float(os.environ.get("BENCH_REPLAY_DURATION_S", "6"))
    trace = rp.make_trace(
        preset="bursty", seed=seed, duration_s=duration, rate_rps=0.7,
        burst_factor=float(os.environ.get("BENCH_REPLAY_BURST", "10")),
        burst_start_frac=0.35, burst_dur_frac=0.2,
        prompt_len=(8, min(sys_len, 24)), new_tokens=(2, max(new // 2, 2)))
    router = ServingRouter(
        model, num_replicas=2, store=MemKVStore(), heartbeat_ttl=600.0,
        engine_kwargs=dict(max_batch_size=2,
                           max_len=sys_len + tail + new + 16))
    hist = timeseries.MetricsHistory(capacity=4096)
    engine = alerts.AlertEngine(history=hist)
    engine.add_rule(alerts.BurnRateRule(
        budget=0.2, fast_window_s=1.5, slow_window_s=4.5, factor=1.0))
    engine.attach(hist)
    old_ttft = os.environ.get("PADDLE_SLO_TTFT_MS")
    ctl = None
    try:
        with router:
            warm = np.arange(16, dtype=np.int64)[None]
            router.generate(warm, max_new_tokens=2, timeout=1800)
            t0 = time.perf_counter()
            router.generate(warm + 16, max_new_tokens=2, timeout=1800)
            warm_s = time.perf_counter() - t0
            os.environ["PADDLE_SLO_TTFT_MS"] = str(
                round(max(2.0 * warm_s, 0.2) * 1e3, 1))
            rt.reset_slo_monitor()
            if fault_spec:
                flt.install(fault_spec)
            if controller:
                ctl = FleetController(
                    router, history=hist, alert_engine=engine,
                    cooldown_s=1.0, restart_backoff_s=0.2,
                    interval_s=0.1, degraded_max_new=0)
                ctl.start()
            harness = rp.ReplayHarness(
                router, trace, vocab_size=256, history=hist,
                alert_engine=engine, tick_interval_s=0.25,
                recover_window_s=1.5, budget=0.2, factor=1.0)
            rep = harness.run().as_dict()
            if ctl is not None:
                ctl.stop()
                rep["controller_actions_total"] = len(ctl.actions)
                rep["controller_actions_by_kind"] = {}
                for a in ctl.actions:
                    k = rep["controller_actions_by_kind"]
                    k[a.action] = k.get(a.action, 0) + 1
            if rep.get("burst_t") and rep.get("t_end") is not None:
                rep["observed_s"] = rep["t_end"] - rep["burst_t"][1]
    finally:
        if ctl is not None:
            ctl.stop()
        if fault_spec:
            flt.clear()
        engine.detach()
        if old_ttft is None:
            os.environ.pop("PADDLE_SLO_TTFT_MS", None)
        else:
            os.environ["PADDLE_SLO_TTFT_MS"] = old_ttft
        rt.reset_slo_monitor()
    keep = ("preset", "seed", "schedule_digest", "requests", "ok",
            "statuses", "goodput_under_burst", "p99_ttft_under_burst_s",
            "p99_latency_s", "time_to_recover_s", "burst_requests",
            "burst_ok", "alerts", "observed_s", "controller_actions_total",
            "controller_actions_by_kind")
    return {k: rep.get(k) for k in keep if k in rep}


# --------------------------------------------------------------------------
# Orchestration: never hang, never exit without a JSON line.
# --------------------------------------------------------------------------

def _emit_telemetry_snapshot(out):
    """Every bench run ships its telemetry: a one-line per-family summary
    on stderr plus a full JSONL snapshot (BENCH_TELEMETRY_JSONL path, or
    bench_telemetry.jsonl next to this file). Regressions in the
    observability layer itself are caught by ``telemetry_overhead_pct``
    riding on the resnet record."""
    try:
        from paddle_tpu.profiler.telemetry import get_registry
        reg = get_registry()
        snap = reg.collect()
        summary = {}
        for name, fam in snap.items():
            if fam["type"] == "histogram":
                summary[name] = {
                    k or "_": {"count": s["count"],
                               "p50_ms": round(s["p50"] * 1e3, 3),
                               "p99_ms": round(s["p99"] * 1e3, 3)}
                    for k, s in fam["series"].items()}
            else:
                summary[name] = {k or "_": v
                                 for k, v in fam["series"].items()}
        aux = {"aux_metric": "telemetry_snapshot"}
        hits = summary.get("paddle_serving_prefix_hits", {}).get("_", 0)
        misses = summary.get("paddle_serving_prefix_misses", {}).get("_", 0)
        if hits or misses:
            # prefix-cache regressions must show up in EVERY bench run
            aux["prefix_hit_rate"] = round(hits / max(hits + misses, 1), 3)
        aux["families"] = summary
        print(json.dumps(aux), file=sys.stderr)
        path = os.environ.get(
            "BENCH_TELEMETRY_JSONL",
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "bench_telemetry.jsonl"))
        reg.export_jsonl(path, extra={"metric": out.get("metric"),
                                      "value": out.get("value")})
    except Exception as e:   # telemetry must never kill a bench record
        print(f"bench: telemetry snapshot skipped: {e}", file=sys.stderr)


def main():
    here = os.path.dirname(os.path.abspath(__file__))
    from paddle_tpu.jit.api import enable_persistent_cache
    # one fixed path inside the checkout (the path is part of the cache
    # key); JAX_COMPILATION_CACHE_DIR, where set, places it instead
    enable_persistent_cache(os.path.join(here, ".jax_cache"))
    mode = os.environ.get("BENCH_MODEL", "resnet")
    out = (bench_llama() if mode == "llama"
           else bench_llama_decode() if mode == "llama_decode"
           else bench_serving() if mode == "serving"
           else bench_fleet() if mode == "fleet"
           else bench_data() if mode == "data"
           else bench_dispatch() if mode == "dispatch"
           else bench_bert() if mode == "bert"
           else bench_comm() if mode == "comm"
           else bench_resnet())
    import jax
    devs = jax.devices()
    out["platform"] = devs[0].platform
    out["device_kind"] = devs[0].device_kind
    out["device_count"] = len(devs)
    _emit_telemetry_snapshot(out)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

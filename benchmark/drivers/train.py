"""Training cells: the jitted, donated AdamW step over ``FunctionalModule``.

Set-up builds ONE object (the compiled step with its state), drives it from
the seed through its first steps on the window's own call and feed, and
hands that same object to the window. The first steps' readings (each loss,
the first gradient's norm by leaf as the optimizer gets it, the parameters'
change by leaf) are compared with the plain reference once the window has
closed and the program's state is freed.

From the program this file takes the model, ``FunctionalModule`` and, across
chips, ``fleet`` and the mesh helpers. The optimizer rule is the trainer's
stated one (``configs/*.json`` ``trainer.optimizer``), written here as
``examples/pretrain_llama.py`` and ``chip_smoke.make_train_step`` write it.
"""
from __future__ import annotations

import gc
import statistics
import time

import numpy as np

from benchmark import flops, harness, weights
from benchmark.traffic import generate

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "max_position_embeddings",
              "rms_norm_eps", "rope_theta", "initializer_range",
              "tie_word_embeddings")
FIRST_STEPS = 3


def build_model(config, dtype):
    """The program's own model object, then its parameters replaced by the
    seed's weights (``load_weights``)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.models.llama import LlamaConfig
    cfg = LlamaConfig(**{k: config[k] for k in MODEL_KEYS})
    if cfg.head_dim != config["head_dim"]:
        raise ValueError(f"head_dim {config['head_dim']} is not hidden/heads")
    paddle.set_default_dtype(dtype)
    try:
        model = LlamaForCausalLM(cfg)
    finally:
        paddle.set_default_dtype("float32")
    harness.log("model object built (the constructor draws weights of its "
                "own, which ``load_weights`` frees)")
    return model


def load_weights(model, config, seed, dtype, shardings=None):
    """Free the constructor's draw, then fill every leaf from the seed in
    one jitted call. The table and the model must agree name by name."""
    named = [(n, p) for n, p in model.named_parameters() if p is not None]
    table = weights.leaf_table(config)
    got = [(n, tuple(p.shape)) for n, p in named]
    want = [(n, tuple(s)) for n, s, _ in table]
    if got != want:
        diff = [(g, w) for g, w in zip(got, want) if g != w][:3]
        raise ValueError(f"the model's parameters are not the table's: "
                         f"{len(got)} vs {len(want)} leaves, first "
                         f"differences {diff}")
    for _, p in named:
        p._data = None
    gc.collect()
    arrs = weights.make_weights(config, seed, dtype, shardings)
    for (_, p), a in zip(named, arrs):
        p._data = a
    return arrs


def make_train_step(fm, opt, specs=None, fault=None):
    """AdamW over ``FunctionalModule`` arrays, state in the parameters' own
    type, arithmetic in float32, no bias correction. ``fault`` plants one of
    the faults a test has to see caught; the benchmark never sets it."""
    import jax
    import jax.numpy as jnp
    b1, b2, eps = opt["beta1"], opt["beta2"], opt["eps"]
    wd, lr = opt["weight_decay"], opt["lr"]

    def train_step(p, m, v, key, ids, labels):
        if fault == "half_batch":
            if ids.shape[0] > 1:
                ids, labels = (a[:a.shape[0] // 2] for a in (ids, labels))
            else:
                ids, labels = (a[:, :a.shape[1] // 2] for a in (ids, labels))

        def loss_fn(ps):
            if specs is not None:
                from paddle_tpu.distributed import mesh as mesh_mod
                ps = mesh_mod.unshard_for_compute(ps, specs, "sharding")
            (loss, _), _ = fm(ps, [], key, ids, labels=labels)
            return loss

        loss, grads = jax.value_and_grad(loss_fn)(p)
        if fault == "state_unchanged":
            return loss, p, m, v
        new_p, new_m, new_v = [], [], []
        for pa, g, mm, vv in zip(p, grads, m, v):
            g = g.astype(jnp.float32)
            mf = b1 * mm.astype(jnp.float32) + (1 - b1) * g
            vf = b2 * vv.astype(jnp.float32) + (1 - b2) * g * g
            pf = pa.astype(jnp.float32)
            pf = pf - lr * (mf / (jnp.sqrt(vf) + eps) + wd * pf)
            new_p.append(pf.astype(pa.dtype))
            new_m.append(mf.astype(mm.dtype))
            new_v.append(vf.astype(vv.dtype))
        return loss, new_p, new_m, new_v

    return train_step


class Trainer:
    """The compiled step with its state: what set-up drives through the
    first steps and the window then drives on."""

    def __init__(self, config, traffic, seed, fault=None):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.framework.functional import FunctionalModule
        self.config, self.traffic, self.seed = config, traffic, seed
        tr = config["trainer"]
        self.dtype = tr["dtype"]
        self.batch, self.seq = tr["batch"], traffic["seq"]
        self.model = build_model(config, self.dtype)
        self.model.train()
        self.fm = FunctionalModule(self.model, training=True)
        self.p = load_weights(self.model, config, seed, self.dtype)
        self.m = [jnp.zeros_like(a) for a in self.p]
        self.v = [jnp.zeros_like(a) for a in self.p]
        self.key = jax.random.key(0)      # no dropout: the key is unused
        self.step = jax.jit(
            make_train_step(self.fm, tr["optimizer"], fault=fault),
            donate_argnums=(0, 1, 2))
        self.feed = generate.token_rows(traffic, seed, config["vocab_size"],
                                        self.batch)
        self.steps_done = 0
        self.first_batches = []
        beta1 = tr["optimizer"]["beta1"]

        @jax.jit
        def grad_norms(m):
            # m after one step from zero moments is (1 - beta1) * g
            return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
                a.astype(jnp.float32)))) for a in m]) / (1 - beta1)

        @jax.jit
        def change_norms(p, key):
            p0 = [weights.leaf(key, i, shape, kind,
                                float(config["initializer_range"]),
                                jnp.dtype(self.dtype))
                  for i, (_, shape, kind)
                  in enumerate(weights.leaf_table(config))]
            return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
                a.astype(jnp.float32) - b.astype(jnp.float32))))
                for a, b in zip(p, p0)])

        self._grad_norms, self._change_norms = grad_norms, change_norms

    def one_step(self):
        """The window's own call and feed; returns the loss (on the device)."""
        import jax.numpy as jnp
        tok = next(self.feed)
        if self.steps_done < FIRST_STEPS:
            self.first_batches.append((tok[:, :-1], tok[:, 1:]))
        ids = jnp.asarray(tok[:, :-1], jnp.int32)
        labels = jnp.asarray(tok[:, 1:], jnp.int32)
        loss, self.p, self.m, self.v = self.step(
            self.p, self.m, self.v, self.key, ids, labels)
        self.steps_done += 1
        return loss

    def first_steps(self):
        """Readings of the first steps, as host numbers."""
        losses, grad = [], None
        for i in range(FIRST_STEPS):
            losses.append(float(self.one_step()))
            if i == 0:
                grad = [float(x) for x in self._grad_norms(self.m)]
        change = [float(x) for x in self._change_norms(
            self.p, weights.seed_key(self.seed))]
        return {"losses": losses, "grad_norms": grad, "change_norms": change}

    def window(self, seconds):
        """Steps for ``seconds``, at most two in flight, closed by
        ``block_until_ready`` on the whole state."""
        import jax
        losses, pending = [], None
        t0 = time.perf_counter()
        while True:
            loss = self.one_step()
            losses.append(loss)
            if pending is not None:
                pending.block_until_ready()
            pending = loss
            if time.perf_counter() - t0 >= seconds:
                break
        jax.block_until_ready((loss, self.p, self.m, self.v))
        window_s = time.perf_counter() - t0
        return window_s, [float(x) for x in losses]

    def free(self):
        for _, p in self.model.named_parameters():
            if p is not None:
                p._data = None
        self.p = self.m = self.v = self.step = self.fm = self.model = None
        gc.collect()


def compare(prog, ref, limits):
    """The numbers compared, each with its limit. By leaf, the gap between
    the program's and the reference's norm of the first gradient and of the
    parameters' change, against the reference's norm of that leaf or of the
    median leaf, whichever is larger: the worst leaf's gap of each, and the
    median leaf's gap of the gradient, which is steady from seed to seed and
    is the number that tells a lower precision (PERF.md, section 2). Leaves
    whose reference gradient is under a thousandth of the median leaf's move
    by round-off alone and are left out of the change. The losses are not
    compared: neither the control nor a fault moves them past what sound
    runs read; ``loss_gaps`` gives them for the log."""
    grad = leaf_gaps(prog, ref, "grad_norms")
    g_med = statistics.median(ref["grad_norms"])
    change = [g for g, r in zip(leaf_gaps(prog, ref, "change_norms"),
                                ref["grad_norms"]) if r >= 1e-3 * g_med]
    numbers = {"grad_norm_median_leaf_gap": statistics.median(grad),
               "grad_norm_worst_leaf_gap": max(grad),
               "change_norm_worst_leaf_gap": max(change)}
    return [(name, v, limits[name]) for name, v in numbers.items()]


def loss_gaps(prog, ref):
    return [abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                                ref["losses"])]


def leaf_gaps(prog, ref, key):
    """By leaf: the gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    med = statistics.median(ref[key])
    return [abs(a - b) / max(b, med) for a, b in zip(prog[key], ref[key])]


def reference_readings(config, seed, batches, quant=None):
    from benchmark.reference import llama as ref
    return ref.train_readings(config, config["trainer"]["optimizer"], seed,
                              batches, quant=quant,
                              dtype=config["trainer"]["dtype"])


def run(ctx):
    """One run of a training cell. ``ctx``: cell, config, traffic, limits,
    seed, seconds, trace, chips, peaks, watch."""
    config, traffic = ctx["config"], ctx["traffic"]
    trainer = Trainer(config, traffic, ctx["seed"],
                      fault=ctx.get("fault"))
    harness.log(f"model {weights.param_count(config) / 1e9:.3f} B "
                f"parameters in {trainer.dtype}, batch {trainer.batch} x "
                f"{trainer.seq} tokens")
    prog = trainer.first_steps()
    harness.log(f"first steps: losses {prog['losses']}")

    seconds = ctx["seconds"]
    tracer = None
    if ctx["trace"]:
        from benchmark import tracing
        seconds = min(seconds, traffic.get("trace_seconds", seconds))
        tracer = tracing.Tracer(ctx)
        tracer.start()
    before = ctx["watch"].snapshot()
    ctx["watch"].names = []
    setup_s = harness.since_start()
    window_s, losses = trainer.window(seconds)
    compiled = harness.CompileWatch.between(before, ctx["watch"].snapshot())
    compiled["programs"], ctx["watch"].names = ctx["watch"].names, None
    if tracer:
        tracer.stop()
    trace = tracer.reduce() if tracer else None
    harness.log(f"compiles inside the window: {compiled}")

    tokens = len(losses) * trainer.batch * trainer.seq
    failed = sum(1 for x in losses if not np.isfinite(x))
    mem_peak = harness.memory_peak_bytes(ctx["chips"])
    batches = trainer.first_batches
    trainer.free()

    t_ref = time.perf_counter()
    ref = reference_readings(config, ctx["seed"], batches)
    harness.log(f"reference: losses {ref['losses']} in "
                f"{time.perf_counter() - t_ref:.1f} s; relative gaps (not "
                f"compared) {[float(f'{g:.3g}') for g in loss_gaps(prog, ref)]}")
    checks = compare(prog, ref, ctx["limits"])
    return {
        "attempted": len(losses), "failed": failed, "checks": checks,
        "memory_peak_bytes": mem_peak, "window_s": window_s,
        "end_to_end": {"train_tok_s": harness.rate(tokens, window_s),
                       "setup_s": setup_s},
        "compiles_in_window": compiled, "trace": trace, "tokens": tokens,
        "flops_per_token": flops.train_flops_per_token(config, trainer.seq),
        "batch": trainer.batch, "seq": trainer.seq,
    }

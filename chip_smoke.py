"""chip_smoke.py — does the system still start on the chip?

Drives the two main paths once, through the entry points a user calls, on
ONE TPU chip at the full width of Llama-3-8B (depth cut to what 16 GB
holds, random weights from ``--seed``):

* ``serve``: a ``ContinuousServingEngine`` with its default scheduler and
  knobs answers a few concurrent requests of mixed prompt lengths; every
  emitted token is checked against a plain forward of the same model, and
  the streams are compared with ``model.generate``.
* ``train``: a few AdamW steps of the jitted, donated train step
  (``FunctionalModule``, flash attention forward and backward) on one
  repeated batch: losses finite and falling.

``--chips 4`` runs ONLY the several-chip path and what it is compared
with: the same train step under ``fleet.init(sharding_degree=2,
mp_degree=2)`` over four devices, then on one of them from the same seed
and batch.

This process is the only one that touches JAX. It refuses any platform
but a TPU before doing anything else, a phase that raises ends the run
with a non-zero exit code, and the last line of stdout is the result
object. Rehearse it on the CPU through ``tests/test_chip_smoke.py``; a
device result comes only from ``python chip_smoke.py`` on the chip.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib
import json
import math
import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))

#: serving: prompt lengths chosen so chunked prefill (256-token chunks) and
#: decode share ticks under the engine's default 256-token budget
SERVE_PROMPT_LENS = (100, 210, 330, 480, 700)
SERVE_NEW_TOKENS = 16
#: layers kept of Llama-3-8B's 32. Weights are 0.44 GB/layer in bf16 on
#: top of 2.1 GB of embeddings + head, the default page pool 0.07 GB/layer:
#: 16 layers is 9.1 GB of weights, which leaves room for the logits and
#: the oracle's forwards; 32 layers (16 GB) cannot fit a 16 GB chip at all
SERVE_LAYERS = 16
#: an emitted token must score within this many standard deviations (of
#: that position's logits) of the plain forward's best token. bf16
#: rounding can flip an argmax between near-ties; a wrong kernel or cache
#: puts the token ~4-5 sigma down (the max of 128k random logits)
SERVE_REGRET_TOL = 0.25

#: training: pure-bf16 AdamW state is 8 B/param with the gradients; the
#: 128256-token embedding and head alone are 1.05 B params (8.4 GB), each
#: 8B-width layer 0.218 B (1.75 GB), so 2 layers (11.9 GB + ~1.5 GB of
#: logits at batch 1 x 1024) is what one 16 GB chip holds. fp32-master
#: AMP-O2 (16-18 B/param) does not fit these widths at any depth.
TRAIN_LAYERS = 2
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 1, 1024, 4
#: sharded vs single-device losses: same program, different reduction
#: order in bf16 (mp all-reduces, fsdp gathers)
MULTICHIP_RTOL = 2e-2


def log(msg):
    print(f"chip_smoke: {msg}", flush=True)


def device_record():
    import jax
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


class CompileWatch:
    """Sums jax's own compile events: backend compile seconds, and
    persistent-cache hits/misses."""

    def __init__(self):
        from jax import monitoring
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += seconds

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return {"compile_s": round(self.compile_s, 1),
                "cache_hits": self.hits, "cache_misses": self.misses}


#: attention implementations by (module, function): every tier a call site
#: can take, kernel or not, so a run that took an XLA tier says so
_ATTENTION_IMPLS = {
    "ragged q-block (Pallas)": ("ragged_paged_attention",
                                "_ragged_paged_attention_pallas_qblock"),
    "ragged XLA tier": ("ragged_paged_attention",
                        "_ragged_paged_attention_xla"),
    "paged decode (Pallas)": ("paged_attention", "_paged_attention_pallas"),
    "paged XLA tier": ("paged_attention", "_paged_attention_xla"),
    "flash fwd (Pallas)": ("flash_attention", "_fwd"),
    "flash bwd (Pallas)": ("flash_attention", "_bwd"),
    "XLA attention tier": ("flash_attention", "xla_attention"),
}


@contextlib.contextmanager
def count_attention_calls():
    """Count calls (eager) or traces (under jit) of each attention
    implementation, plus the dense ``sdpa`` op of the tape."""
    from paddle_tpu.autograd import tape
    counts = {}
    saved = []

    def wrap(label, fn):
        def counted(*a, **k):
            counts[label] = counts.get(label, 0) + 1
            return fn(*a, **k)
        return counted

    for label, (mod, name) in _ATTENTION_IMPLS.items():
        m = importlib.import_module(f"paddle_tpu.ops.pallas.{mod}")
        saved.append((m, name, getattr(m, name)))
        setattr(m, name, wrap(label, getattr(m, name)))

    def on_op(name, _dt):
        if name in ("sdpa", "sdpa_chunked"):
            label = f"dense {name} op (XLA)"
            counts[label] = counts.get(label, 0) + 1

    tape._op_observers.append(on_op)
    try:
        yield counts
    finally:
        tape._op_observers.remove(on_op)
        for m, name, fn in saved:
            setattr(m, name, fn)


def build_model(cfg, seed):
    """LlamaForCausalLM with bf16 weights initialised directly in bf16 (an
    fp32 copy of these widths does not fit beside the bf16 one)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM
    paddle.seed(seed)
    paddle.set_default_dtype("bfloat16")
    try:
        model = LlamaForCausalLM(cfg)
    finally:
        paddle.set_default_dtype("float32")
    n = sum(math.prod(p.shape) for p in model.parameters())
    log(f"model: hidden {cfg.hidden_size}, intermediate "
        f"{cfg.intermediate_size}, heads {cfg.num_attention_heads}/"
        f"{cfg.num_key_value_heads}, head_dim {cfg.head_dim}, vocab "
        f"{cfg.vocab_size}, layers {cfg.num_hidden_layers}, "
        f"{n / 1e9:.3f} B params in {model.parameters()[0].dtype}")
    return model


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def serve_phase(cfg, prompt_lens=SERVE_PROMPT_LENS,
                new_tokens=SERVE_NEW_TOKENS, seed=0,
                regret_tol=SERVE_REGRET_TOL, engine_kwargs=None,
                watch=None):
    """``engine_kwargs`` is for the CPU rehearsal only (tiny prompts need a
    tiny chunk to be chunked at all); the chip run passes none. ``watch``
    (a CompileWatch) adds cumulative compile counts to the log lines."""
    compiled = (lambda: f" [{watch.snapshot()}]") if watch else (lambda: "")
    import numpy as np
    import jax.numpy as jnp
    from paddle_tpu.autograd.tape import no_grad
    from paddle_tpu.framework.core import Tensor
    from paddle_tpu.inference import ContinuousServingEngine

    model = build_model(cfg, seed)
    model.eval()
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab_size, (n,)).astype(np.int64)
               for n in prompt_lens]

    # --- the engine, default scheduler and knobs, concurrent clients -----
    outs = [None] * len(prompts)
    errors = []

    def client(i):
        try:
            outs[i] = engine.generate(prompts[i][None],
                                      max_new_tokens=new_tokens,
                                      timeout=900).numpy()[0]
        except BaseException as e:          # re-raised on the main thread
            errors.append(e)

    t0 = time.perf_counter()
    with count_attention_calls() as engine_calls:
        engine = ContinuousServingEngine(model, **(engine_kwargs or {}))
        engine.start()
        try:
            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(len(prompts))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        finally:
            engine.stop()
    if errors:
        raise errors[0]
    log(f"serve: engine answered {len(prompts)} requests (prompts "
        f"{list(prompt_lens)}, {new_tokens} new tokens each) in "
        f"{time.perf_counter() - t0:.1f} s incl. compiles; ticks "
        f"{engine.ragged_steps}, prefill chunks {engine.prefill_chunks}, "
        f"decode steps {engine.decode_steps}, token buckets "
        f"{sorted(engine.ragged_buckets_used)}{compiled()}")
    log(f"serve: engine attention (prefill chunks AND decode tokens share "
        f"the ragged tick): {engine_calls}")
    for i, (p, o) in enumerate(zip(prompts, outs)):
        assert o.shape == (len(p) + new_tokens,), (i, o.shape)
        assert (o[:len(p)] == p).all(), f"request {i}: prompt not echoed"
    assert any(e[0] == "decode" for e in engine.events) and \
        engine.prefill_chunks > len(prompts), \
        "chunked prefill and decode never shared the schedule"

    # --- oracle 1: model.generate (paged decode kernel; a 100-token
    # prefill sits under SDPA's 128-token flash threshold, dense sdpa) ----
    # for the SHORTEST request only: eager, every prompt length is ~60 new
    # XLA programs (the first chip run spent 9 of its 21 minutes here on
    # five), and the decisive check below covers every request
    t0 = time.perf_counter()
    with count_attention_calls() as oracle_calls:
        oracle = [model.generate(Tensor(prompts[0][None]),
                                 max_new_tokens=new_tokens,
                                 use_paged_cache=True).numpy()[0]]
    exact = bool((outs[0] == oracle[0]).all())
    log(f"serve: model.generate oracle (request 0) in "
        f"{time.perf_counter() - t0:.1f} s; attention: {oracle_calls}"
        f"{compiled()}")
    log(f"serve: engine stream == model.generate stream: {exact} (bf16 "
        f"near-ties may flip an argmax; the decisive check is the next one)")

    # --- oracle 2: every emitted token against a plain forward ----------
    # every stream right-padded to ONE width (causal attention: padding
    # cannot reach back) and forwarded one row at a time: the eager model
    # compiles one set of programs instead of one per prompt length, and
    # activations stay those of one sequence (three rows at once peaked at
    # 14.9 GB of the chip's 16)
    streams = [("engine", i, s) for i, s in enumerate(outs)]
    streams.append(("model.generate", 0, oracle[0]))
    width = max(len(s) for _, _, s in streams)
    regrets = {}
    with count_attention_calls() as forward_calls, no_grad():
        for who, i, s in streams:
            row = np.zeros((1, width), np.int64)
            row[0, :len(s)] = s
            logits = model.forward(Tensor(row))._data[0]
            n = len(prompts[i])
            lg = np.asarray(logits[n - 1:len(s) - 1].astype(jnp.float32))
            assert np.isfinite(lg).all(), "non-finite logits"
            took = lg[np.arange(len(lg)), s[n:]]
            regrets.setdefault(who, []).append(
                (lg.max(-1) - took) / lg.std(-1))
    worst = 0.0
    for who, rs in regrets.items():
        r = np.concatenate(rs)
        log(f"serve: {who} tokens vs plain forward: {len(r)} tokens, "
            f"{int((r == 0).sum())} exact argmax, max regret "
            f"{r.max():.4f} sigma (tolerance {regret_tol})")
        worst = max(worst, float(r.max()))
    log(f"serve: plain-forward attention: {forward_calls}{compiled()}")
    assert worst <= regret_tol, \
        f"an emitted token sits {worst:.3f} sigma below the plain " \
        f"forward's best (tolerance {regret_tol})"
    return {"engine": engine_calls, "oracle": oracle_calls,
            "forward": forward_calls, "exact_stream": exact,
            "max_regret": worst}


# ---------------------------------------------------------------------------
# train (one device, or a sharding x mp mesh)
# ---------------------------------------------------------------------------

def make_train_step(fm, specs=None, lr=2e-4):
    """AdamW step over FunctionalModule arrays, state in the params' own
    dtype (pure bf16: no fp32 master), moments updated in fp32. ``specs``
    are the params' PartitionSpecs under a mesh (ZeRO-3 gather at entry)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.distributed import mesh as mesh_mod
    b1, b2, eps, wd = 0.9, 0.999, 1e-8, 0.01

    def train_step(p, m, v, key, ids, labels):
        def loss_fn(ps):
            if specs is not None:
                ps = mesh_mod.unshard_for_compute(ps, specs, "sharding")
            (loss, _), _ = fm(ps, [], key, ids, labels=labels)
            return loss

        loss, grads = jax.value_and_grad(loss_fn)(p)
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


def run_steps(fm, p, batch, seq, steps, seed, data_sharding=None,
              specs=None):
    """``steps`` AdamW steps on one repeated batch. The step DONATES its
    state, ``p`` included: the caller hands the arrays over. Returns the
    losses and the final params."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    vocab = fm.layer.config.vocab_size
    rng = np.random.default_rng(seed)
    tok = rng.integers(0, vocab, (batch, seq + 1))
    ids = jnp.asarray(tok[:, :-1], jnp.int32)
    labels = jnp.asarray(tok[:, 1:], jnp.int32)
    if data_sharding is not None:
        ids, labels = (jax.device_put(a, data_sharding)
                       for a in (ids, labels))
    m = [jnp.zeros_like(a) for a in p]
    v = [jnp.zeros_like(a) for a in p]
    step = jax.jit(make_train_step(fm, specs), donate_argnums=(0, 1, 2))
    key = jax.random.key(seed)
    losses = []
    for i in range(steps):
        t0 = time.perf_counter()
        loss, p, m, v = step(p, m, v, key, ids, labels)
        losses.append(float(loss))
        log(f"train: step {i} loss {losses[-1]:.4f} "
            f"({time.perf_counter() - t0:.1f} s)")
    return losses, p


def check_losses(losses):
    import numpy as np
    assert np.isfinite(losses).all(), f"non-finite loss: {losses}"
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"


def train_phase(cfg, batch=TRAIN_BATCH, seq=TRAIN_SEQ, steps=TRAIN_STEPS,
                seed=0):
    from paddle_tpu.framework.functional import FunctionalModule
    model = build_model(cfg, seed)
    model.train()
    fm = FunctionalModule(model, training=True)
    log(f"train: pure-bf16 AdamW state (params, moments and grads in "
        f"bf16: 8 B/param), batch {batch} x seq {seq}, {steps} steps on "
        f"one repeated batch")
    with count_attention_calls() as calls:
        # the module's own arrays go into the donated step (a second
        # copy of the weights would not fit beside the optimizer state)
        losses, _ = run_steps(fm, fm.param_arrays(), batch, seq, steps, seed)
    log(f"train: attention traced into the step: {calls}")
    check_losses(losses)
    return {"losses": losses, "attention": calls}


def multichip_phase(cfg, batch=2 * TRAIN_BATCH, seq=TRAIN_SEQ,
                    steps=TRAIN_STEPS, seed=0, rtol=MULTICHIP_RTOL):
    """Hybrid-parallel training over four devices (ZeRO-3 'sharding' x
    Megatron 'mp'), then the same steps on one of them."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.framework.functional import FunctionalModule
    from paddle_tpu.models import LlamaForCausalLM

    model = build_model(cfg, seed)
    model.train()
    fm = FunctionalModule(model, training=True)
    p0 = fm.param_arrays()

    strategy = DistributedStrategy()
    # dp -1: whatever sharding x mp leaves (1 on four chips) folds into dp
    strategy.hybrid_configs = {"dp_degree": -1, "mp_degree": 2,
                               "sharding_degree": 2, "sep_degree": 1,
                               "pp_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    mesh = mesh_mod.get_mesh()
    log(f"multichip: mesh {dict(mesh.shape)} over "
        f"{[str(d) for d in mesh.devices.flat]}")
    try:
        specs = fm.param_specs(LlamaForCausalLM.sharding_rules(),
                               fsdp_axis="sharding", fsdp_size=2)
        p_sh = [NamedSharding(mesh, s) for s in specs]
        data_sh = NamedSharding(mesh, P(("dp", "sharding"), "sep"))
        # put COPIES: a replicated norm weight otherwise shares device 0's
        # buffer with p0 (may_alias=False does not prevent it), and the
        # donated step would delete what the single-device run still needs
        p_sharded = [jax.device_put(jnp.copy(a), sh)
                     for a, sh in zip(p0, p_sh)]
        with count_attention_calls() as calls, mesh:
            sharded, p_final = run_steps(fm, p_sharded, batch, seq, steps,
                                         seed, data_sharding=data_sh,
                                         specs=specs)
        # what __graft_entry__'s dry run asserts on virtual devices: the
        # shards of a large weight sit on distinct devices, each smaller
        # than the whole
        big = max(p_final, key=lambda a: a.size)
        shards = big.addressable_shards
        devs = {s.device for s in shards}
        assert len(devs) == mesh.size >= 4, \
            f"largest weight lives on {len(devs)} of {mesh.size} devices"
        assert all(s.data.size < big.size for s in shards), \
            "a shard of the largest weight is as large as the whole"
        log(f"multichip: largest weight {tuple(big.shape)} -> shards "
            f"{[tuple(s.data.shape) for s in shards]} on "
            f"{sorted(str(d) for d in devs)}")
        del p_final, p_sharded, big, shards
    finally:
        mesh_mod.reset_mesh()
    log(f"multichip: attention traced into the sharded step: {calls}")
    check_losses(sharded)

    log("multichip: the same steps on one device, same seed and batch")
    single, _ = run_steps(fm, p0, batch, seq, steps, seed)
    check_losses(single)
    rel = float(np.max(np.abs(np.array(sharded) - np.array(single))
                       / np.abs(single)))
    log(f"multichip: sharded {sharded} vs single {single}: max relative "
        f"difference {rel:.2e} (rtol {rtol})")
    assert rel <= rtol, f"sharded and single-device losses differ: {rel}"
    return {"sharded": sharded, "single": single, "rel": rel}


# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--phase", choices=("serve", "train", "both"),
                    default="both")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    import jax
    dev = device_record()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, jax reports {dev}; nothing ran",
              file=sys.stderr)
        return 1
    if dev["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax reports {dev}",
              file=sys.stderr)
        return 1

    import paddle_tpu as paddle
    from paddle_tpu.jit.api import enable_persistent_cache
    from paddle_tpu.models.llama import llama3_8b
    watch = CompileWatch()
    enable_persistent_cache(os.path.join(REPO, ".jax_cache"))
    log(f"device {dev}; compile cache at "
        f"{jax.config.jax_compilation_cache_dir}")
    paddle.set_device("tpu")

    if args.chips == 4:
        log(f"depth cut: {TRAIN_LAYERS} of 32 layers at Llama-3-8B widths "
            f"(what ONE device holds, so both sides run the same model)")
        multichip_phase(llama3_8b(num_hidden_layers=TRAIN_LAYERS),
                        seed=args.seed)
        log(f"multichip: {watch.snapshot()}, peak device-0 bytes "
            f"{peak_bytes()}")
    else:
        if args.phase in ("serve", "both"):
            log(f"depth cut: serving {SERVE_LAYERS} of 32 layers at "
                f"Llama-3-8B widths, bf16")
            serve_phase(llama3_8b(num_hidden_layers=SERVE_LAYERS),
                        seed=args.seed, watch=watch)
            log(f"serve: {watch.snapshot()}, peak device bytes "
                f"{peak_bytes()}")
            gc.collect()
        if args.phase in ("train", "both"):
            log(f"depth cut: training {TRAIN_LAYERS} of 32 layers at "
                f"Llama-3-8B widths (see TRAIN_LAYERS for the arithmetic)")
            train_phase(llama3_8b(num_hidden_layers=TRAIN_LAYERS),
                        seed=args.seed)
            log(f"train: {watch.snapshot()} (cumulative), peak device "
                f"bytes of the process {peak_bytes()}")
    log(f"done in {time.perf_counter() - t_start:.0f} s")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

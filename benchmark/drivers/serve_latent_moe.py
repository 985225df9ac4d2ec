"""Serving cells of a latent-attention / routed-expert model
(``DeepseekV3ForCausalLM``) behind ``ContinuousServingEngine``: the closed
loop, window, cut and statistics are ``drivers/serve.py``'s; this file
brings the model's build, its warm-up, the deal of documents
(``traffic/docs_reask.py``) and the comparison with
``reference/deepseek_v3.py``.

On a program that lacks the model (the parent of the PR that brought it)
the import below fails and the run exits at once with no result.
"""
from __future__ import annotations

import gc
import threading
import time

import numpy as np

from benchmark import harness, weights_deepseek_v3 as weights
from benchmark.drivers.serve import (
    FIRST_TOKEN_WAIT_S, Clients, KernelSpy, end_to_end, engine_counters,
    first_delivery, sample_finished, window_metrics)
from benchmark.traffic import docs_reask

#: the configuration's keys that the program's config class takes
MODEL_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "num_nextn_predict_layers", "num_attention_heads",
    "n_shared_experts", "n_routed_experts", "routed_scaling_factor",
    "kv_lora_rank", "q_lora_rank", "qk_rope_head_dim", "v_head_dim",
    "qk_nope_head_dim", "n_group", "topk_group", "num_experts_per_tok",
    "first_k_dense_replace", "norm_topk_prob", "scoring_func", "topk_method",
    "rms_norm_eps", "rope_theta", "rope_scaling", "max_position_embeddings",
    "initializer_range")


def build_model(config, dtype):
    import paddle_tpu as paddle
    from paddle_tpu.models.deepseek_v3 import (DeepseekV3Config,
                                               DeepseekV3ForCausalLM)
    cfg = DeepseekV3Config(held_experts=tuple(config["held_experts"]),
                           **{k: config[k] for k in MODEL_KEYS})
    paddle.set_default_dtype(dtype)
    try:
        model = DeepseekV3ForCausalLM(cfg)
    finally:
        paddle.set_default_dtype("float32")
    harness.log("model object built (the constructor draws weights of its "
                "own, which ``load_weights`` frees)")
    return model


def load_weights(model, config, seed, dtype):
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
    for (_, p), a in zip(named, weights.make_weights(config, seed, dtype)):
        p._data = a


def warm_shapes(token_buckets, pages_per_seq, page, slots, q_block,
                schedule):
    """(tokens, rows, pages a row), one for each (token bucket, length of
    the flat job list) a tick can reach. ``rows`` single-token spans of
    ``pages`` pages each; ``schedule`` is the program's own
    ``latent_job_list``, asked how long a list they make. A sequence's span
    is contiguous, so a tick of ``b`` q-blocks and ``slots`` sequences has at
    most ``b + slots`` (block, sequence) pairs, each of at most
    ``pages_per_seq`` jobs."""
    tables = np.zeros((max(token_buckets), pages_per_seq), np.int32)
    out, seen = [], set()
    for t in token_buckets:
        most = min(t, -(-t // q_block) + slots) * pages_per_seq
        rows, counts = 1, []
        while rows <= t:
            counts.append(rows)
            rows *= 2
        pages = sorted({min(2 ** i, pages_per_seq) for i in range(12)})
        for r in counts:
            for p in pages:
                if r * p > most:
                    continue
                jobs = schedule(t, np.arange(r), np.arange(r),
                                np.ones(r, np.int32),
                                np.full(r, p * page), tables, q_block,
                                page)[2].shape[1]
                if (t, jobs) not in seen:
                    seen.add((t, jobs))
                    out.append((t, r, p))
    return out


def warm_kernels(config, engine):
    """Compile the latent kernel for every (token bucket, job bucket) the
    traffic can reach, through the public op, on the engine's own pool (the
    op only reads it). One short request first makes the engine build its
    pool and its logits' type known."""
    import jax
    import jax.numpy as jnp
    import importlib
    rpa = importlib.import_module(
        "paddle_tpu.ops.pallas.ragged_paged_attention")
    interpret = jax.default_backend() != "tpu"
    model, seen = engine.model, {}
    forward = model.forward

    def probe(*a, **kw):
        out = forward(*a, **kw)
        seen["logits_dtype"] = out._data.dtype
        return out

    model.forward = probe
    try:
        engine.generate(np.arange(1, 9)[None], max_new_tokens=2)
    finally:
        del model.forward
    (pool,) = next(iter(engine._cache._pools.values()))
    _, num_pages, d, page = pool.shape      # a page's tokens: its columns
    attn = model.model.layers[0].self_attn
    slots, pages_per_seq = engine.max_batch, -(-engine.max_len // page)
    buckets = sorted(engine.declared_token_buckets())
    tables = np.zeros((max(buckets), pages_per_seq), np.int32)
    outs = []
    for t, rows, pages in warm_shapes(buckets, pages_per_seq, page, slots,
                                      rpa._qblock_rows(),
                                      rpa.latent_job_list):
        q = jnp.zeros((t, config["num_attention_heads"], d), pool.dtype)
        outs.append(rpa.ragged_paged_attention(
            q, pool, None, tables, np.arange(rows, dtype=np.int32),
            np.arange(rows, dtype=np.int32), np.ones(rows, np.int32),
            np.full(rows, pages * page, np.int32), interpret=interpret,
            **attn.ragged_kwargs))
    for o in outs:
        o.block_until_ready()
    # the tick's small eager programs (``drivers/serve.py::warm_glue``)
    for n in range(1, engine.max_batch + 1):
        jnp.asarray(list(range(n)), jnp.int32).block_until_ready()
    for t in sorted(engine.declared_token_buckets()):
        lg = jnp.zeros((1, t, config["vocab_size"]), seen["logits_dtype"])
        jnp.argmax(lg[0].astype(jnp.float32), axis=-1).block_until_ready()
    return len(outs), str(pool.dtype), tuple(pool.shape)


class Turnstile:
    """Stands between ``Clients`` and the engine's queue: a caller's
    turnaround, and an order.

    ``Clients`` sends a client's next request the moment its reply is
    there, from one thread a client. Whether that request reaches the queue
    before the engine drains it for its next tick, and in which order the
    requests go whose replies came in one tick, is then a race between
    threads; and behind a FIFO prefill queue a re-ask of 96 tokens that
    lands behind a 16 k document and not before it moves a 50 s window's
    count by percents (PERF.md section 6: six seeds spread 3.4 %, a
    simulation of the engine's packing with that race alone 1.5-2 %).
    Here a request goes ``turnaround_s`` after its reply (a caller's own
    time, a network round trip), which is while the engine waits for the
    device, so that the tick that admits it is the next but one; and the
    requests whose replies came within ``tie_s`` of each other (one tick's)
    go in the clients' order, ``SETTLE_S`` apart (the time a request takes
    from here into the queue). ``Clients`` sees an engine."""

    SETTLE_S = 0.0005

    def __init__(self, engine, turnaround_s, tie_s):
        self.engine = engine
        self.turnaround_s, self.tie_s = turnaround_s, tie_s
        self.lock = threading.Lock()
        self.client_of = {}               # thread -> client
        self.waiting = {}                 # client -> when its reply came
        self.last_sent = 0.0

    def bind(self, threads):
        self.client_of = {th: c for c, th in enumerate(threads)}

    def generate(self, prompt, **kw):
        c = self.client_of[threading.current_thread()]
        t = time.perf_counter()
        with self.lock:
            self.waiting[c] = t
        time.sleep(self.turnaround_s)
        while True:
            with self.lock:
                now = time.perf_counter()
                if (now - self.last_sent >= self.SETTLE_S and not any(
                        x < c and abs(tx - t) < self.tie_s
                        for x, tx in self.waiting.items())):
                    del self.waiting[c]
                    self.last_sent = now
                    break
            time.sleep(0.0002)
        return self.engine.generate(prompt, **kw)


def sample_asks(records, asks, seed, count):
    """Finished requests for the comparison: the longest, then at least one
    first ask and one re-ask, then others drawn from the seed."""
    picked = sample_finished(records, seed, len(records))
    if not picked:
        return []
    out = [picked[0]]
    for want_first in (True, False):
        hit = next((r for r in picked[1:] if r not in out
                    and (asks[r.client][r.index] == 0) == want_first), None)
        if hit is not None:
            out.append(hit)
    out += [r for r in picked[1:] if r not in out]
    return out[:count]


#: the numbers of the comparison, each with its limit in the cell's file
CHECKS = ("decided_logit_gap_max", "served_logit_gap_mean")


def gap_checks(gaps, margins, limits):
    """``gaps``: for each served token, by how much its reference logit lies
    below the reference's best; ``margins``: the least margin by which the
    reference's router decided that token's position, over the expert
    layers. Two numbers. The widest gap among the DECIDED tokens (margin at
    or over the limits' ``router_margin_min``): a token whose routing a
    rounding can flip takes other experts in the program than in the
    reference, and its gap then says nothing of the program (PERF.md
    section 2 has the readings), so the widest gap is read where the
    routing is not in doubt, and there a single wrong token shows. And the
    mean gap over all served tokens, which a lower precision moves."""
    decided = [g for g, m in zip(gaps, margins)
               if m >= limits["router_margin_min"]]
    values = {"decided_logit_gap_max": max(decided, default=float("nan")),
              "served_logit_gap_mean": sum(gaps) / len(gaps)}
    return [(name, values[name], limits[name]) for name in CHECKS]


def altered_token_row(gaps, limits):
    """The served tokens with ONE of them altered by one id, at the decided
    position where that costs least: what every single altered token that
    the comparison can see reads at the least."""
    decided = [i for i, m in enumerate(gaps["margin"])
               if m >= limits["router_margin_min"]]
    if not decided:
        return list(gaps["served"])
    at = min(decided, key=lambda i: gaps["altered"][i])
    return [gaps["altered"][i] if i == at else g
            for i, g in enumerate(gaps["served"])]


def judged_rows(gaps, limits):
    """{who: checks} from the reference's per-token readings
    (``served_gaps``): the program; ``control_<quant>``, the reference at a
    precision below the configuration's, its first choices in the served
    tokens' place; ``altered_token`` (:func:`altered_token_row`). Each is
    judged on every number, as the program is."""
    rows = {"program": gap_checks(gaps["served"], gaps["margin"], limits)}
    if gaps.get("int8"):
        rows["control_int8"] = gap_checks(gaps["int8"], gaps["margin"],
                                          limits)
    rows["altered_token"] = gap_checks(altered_token_row(gaps, limits),
                                       gaps["margin"], limits)
    return rows


def compare(sample, config, seed, limits, width, quant=None, readings=()):
    """As ``drivers/serve.py::compare``, against the family's reference ->
    (the run's checks, the rows that stand in the program's place, the
    reference's per-token readings). ``readings``: further passes of the
    reference (``served_gaps``), reported and never judged."""
    from benchmark.reference import deepseek_v3 as ref
    seqs = [(np.asarray(r.prompt), np.asarray(r.output)) for r in sample]
    gaps = ref.served_gaps(config, seed, seqs, width, quant=quant,
                           dtype=config["engine_dtype"], readings=readings)
    ends = np.cumsum([len(out) for _, out in seqs])
    decided = sum(m >= limits["router_margin_min"] for m in gaps["margin"])
    harness.log(
        f"widest gap {max(gaps['served']):.3f}; {decided} of "
        f"{len(gaps['served'])} served tokens decided by the reference's "
        f"router by {limits['router_margin_min']} or more; a request "
        "(prompt length: widest gap): " + ", ".join(
            f"{len(p)}: {max(gaps['served'][e - len(o):e]):.3f}"
            for (p, o), e in zip(seqs, ends)))
    rows = judged_rows(gaps, limits)
    return rows.pop("program"), rows if quant else {}, gaps


def program_counters(engine):
    """The engine's counters, with those the model counted on the device
    (``engine.model_counters``, read back inside each tick's one sync)."""
    out = engine_counters(engine)
    for k in ("prompt_tokens_admitted", "prompt_tokens_cached",
              "compiled_layer_calls"):
        out[k] = getattr(engine, k)
    for k, v in engine.model_counters.items():
        out[k] = np.array(v, copy=True)
    return out


def counters_between(c0, c1):
    return {k: (v - c0.get(k, 0)) for k, v in c1.items()}


def run(ctx):
    """One run of the cell."""
    import jax
    from paddle_tpu.inference import ContinuousServingEngine
    config, traffic, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    dtype = config["engine_dtype"]
    model = build_model(config, dtype)
    model.eval()
    load_weights(model, config, seed, dtype)
    harness.log(f"model {weights.param_count(config) / 1e9:.3f} B "
                f"parameters in {dtype}")
    engine = ContinuousServingEngine(model, **config["engine"])
    t = time.perf_counter()
    engine.warmup_programs()
    harness.log(f"warm-up: the engine's declared programs in "
                f"{time.perf_counter() - t:.1f} s {ctx['watch'].snapshot()}")

    plan, asks = docs_reask.docs_reask_requests(traffic, seed,
                                                config["vocab_size"])
    seconds = ctx["seconds"]
    tracer = None
    if ctx["trace"]:
        from benchmark import tracing
        seconds = min(seconds, traffic.get("trace_seconds", seconds))
        tracer = tracing.Tracer(ctx)
    spy = KernelSpy(model) if ctx["trace"] else None
    pages = []                            # (time, used pages, capacity)
    sampling = threading.Event()

    def sample_pages():
        while not sampling.wait(0.25):
            cache = engine._cache
            if cache is not None:
                pages.append((time.perf_counter(), cache.used_page_count,
                              cache.num_pages - 1))

    engine.start()
    t = time.perf_counter()
    n, kv_dtype, pool_shape = warm_kernels(config, engine)
    harness.log(f"warm-up: {n} kernel shapes on a {kv_dtype} latent pool "
                f"{pool_shape} a layer in {time.perf_counter() - t:.1f} s "
                f"{ctx['watch'].snapshot()}")
    turnstile = Turnstile(engine, traffic["turnaround_ms"] / 1e3,
                          traffic["tie_ms"] / 1e3)
    clients = Clients(turnstile, plan, ramp=bool(traffic.get("ramp")))
    turnstile.bind(clients.threads)
    sampler = threading.Thread(target=sample_pages, daemon=True)
    cut = False
    try:
        t_ramp = time.perf_counter()
        clients.start()
        sampler.start()
        # the ramp: every client's first request is a whole document; the
        # window opens once all of them are cached
        while clients.ramps_done < traffic["clients"]:
            if not any(th.is_alive() for th in clients.threads):
                raise RuntimeError("the clients ended during the ramp")
            time.sleep(0.05)
        if spy:
            spy.install()
        if tracer:
            tracer.start()
        before = ctx["watch"].snapshot()
        ctx["watch"].names = []
        counters0 = program_counters(engine)
        setup_s = harness.since_start()
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            if not any(th.is_alive() for th in clients.threads):
                break                     # an error ended every client
            time.sleep(0.01)
        t1 = time.perf_counter()
        clients.stop_sending.set()
        counters1 = program_counters(engine)
        compiled = harness.CompileWatch.between(before,
                                                ctx["watch"].snapshot())
        compiled["programs"], ctx["watch"].names = ctx["watch"].names, None
        if tracer:
            tracer.stop()
        if spy:
            spy.remove()
        harness.log(f"compiles inside the window: {compiled}")
        t_wait = time.perf_counter()
        while (time.perf_counter() - t_wait < FIRST_TOKEN_WAIT_S
               and (engine.ragged_steps < counters1["ragged_steps"] + 2
                    or clients.first_tokens_pending(t0, t1))):
            time.sleep(0.1)
        t_cut = time.perf_counter()
        cut = True
    finally:
        sampling.set()
        engine.abort()                    # fails what is still in flight
        clients.stop_sending.set()
        joined = clients.join(60)
    if not joined:
        raise RuntimeError("client threads did not end after the cut")
    trace = tracer.reduce() if tracer else None
    records = clients.records
    for r in records:
        if r.error is not None and cut and r.t_done >= t_cut:
            r.error = None
    s0, s1 = first_delivery(records, t0), first_delivery(records, t1)
    wm = window_metrics(records, s0, s1, cut_at=t_cut)
    counters = counters_between(counters0, counters1)
    finished = sum(1 for r in records if r.output is not None
                   and r.t_done is not None and t0 < r.t_done <= t1)
    harness.log(f"window {t1 - t0:.2f} s with {counters['ragged_steps']} "
                f"ticks, between deliveries {wm['window_s']:.2f} s; then "
                f"{t_cut - t1:.1f} s to the cut; {finished} requests "
                f"finished in it, {len(wm['gaps'])} gaps, 50th / 95th / "
                "100th percentile "
                + " / ".join(f"{1e3 * harness.percentile(wm['gaps'], p):.0f}"
                             for p in (50, 95, 100) if wm["gaps"]) + " ms, "
                f"mean {1e3 * sum(wm['gaps']) / max(len(wm['gaps']), 1):.2f} "
                f"ms; the ramp before it took {t0 - t_ramp:.2f} s")
    per_expert = counters.get("moe_expert_tokens")
    if per_expert is not None and np.sum(per_expert):
        harness.log("held experts' tokens in the window, least / most over "
                    f"the mean: {np.min(per_expert) / np.mean(per_expert):.2f}"
                    f" / {np.max(per_expert) / np.mean(per_expert):.2f}")
    mem_peak = harness.memory_peak_bytes(ctx["chips"])
    sample = sample_asks(records, asks, seed,
                         ctx["limits"]["sample_requests"])

    # free the program's state before the reference takes the chip
    for _, p in model.named_parameters():
        if p is not None:
            p._data = None
    engine._cache = None
    del engine, model
    gc.collect()
    jax.clear_caches()

    stand_ins, gaps = {}, None
    if sample:
        t_ref = time.perf_counter()
        checks, stand_ins, gaps = compare(
            sample, config, seed, ctx["limits"], traffic["reference_width"],
            quant=ctx.get("control"), readings=ctx.get("readings", ()))
        harness.log(f"reference: {len(gaps['served'])} served tokens of "
                    f"{len(sample)} requests (asks "
                    f"{[asks[r.client][r.index] for r in sample]}, prompts "
                    f"{[len(r.prompt) for r in sample]}) in "
                    f"{time.perf_counter() - t_ref:.1f} s")
    else:
        checks = [(name, float("nan"), ctx["limits"][name])
                  for name in CHECKS]
    return {
        "attempted": wm["attempted"], "failed": wm["failed"],
        "checks": checks, "memory_peak_bytes": mem_peak,
        "window_s": t1 - t0,              # what counters and calls span
        "end_to_end": dict(end_to_end(wm), setup_s=setup_s),
        "compiles_in_window": compiled, "trace": trace,
        "stand_ins": stand_ins, "gaps": gaps, "window": wm,
        "finished": finished, "counters": counters,
        # every request's token stamps from the window's start: other
        # windows of the same run (``calibrate_serve``)
        "token_stamps": [[x - t0 for x in r.tokens] for r in records],
        "kernel_calls": [c for c in spy.calls if t0 <= c[0] <= t1]
        if spy else None,
        "pages": [p for p in pages if t0 <= p[0] <= t1],
    }

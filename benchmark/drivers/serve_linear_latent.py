"""The serving cell of a model whose layers are linear attention with a
recurrent state a slot (KDA) beside latent attention over a page pool, with
routed experts (``BailingHybridForCausalLM``) behind
``ContinuousServingEngine``: the closed loop, window, cut and statistics are
``drivers/serve.py``'s, the turnstile and the tokens' two numbers
``drivers/serve_latent_moe.py``'s, the logits' number
``drivers/serve_window_moe.py``'s; this file brings the model's build, the
deal of reasoning requests and long documents (``traffic/reason_tail.py``),
the program's own logits read from the TIMED ticks' rows (:class:`LogitTap`:
a prefill's last row and every decode row of the watched requests, so the
compared logits went through the one-token kernel as well as the chunked
one; a watched request still in flight at the cut is compared as far as it
got: its served tokens are the greedy choice of the rows read), what the
state counted, and ``reference/bailing_hybrid.py``. Before the clients
start the collector is frozen (what the warm-up built is never walked
again: a full collection stopped the engine for a third of a second), and
the window's line of the log says what stopped the engine's thread
(:class:`Stalls`).

On a program that lacks the model (the parent of the PR that brought it)
the program's import fails and the run exits at once with no result.
"""
from __future__ import annotations

import collections
import gc
import sys
import threading
import time
import traceback

import numpy as np

from benchmark import harness, weights_bailing_hybrid as weights
from benchmark.drivers.serve import (
    FIRST_TOKEN_WAIT_S, Clients, KernelSpy, end_to_end, first_delivery,
    warm_glue, window_metrics)
from benchmark.drivers.serve_latent_moe import (
    Turnstile, counters_between, program_counters)
from benchmark.drivers.serve_window_moe import (
    CHECKS, judged_rows, long_documents_prefilled, memory)
from benchmark.traffic import reason_tail

#: the configuration's keys that the program's config class takes
MODEL_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "num_attention_heads", "head_dim",
    "layer_group_size", "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "num_experts", "num_experts_per_tok",
    "n_group", "topk_group", "num_shared_experts",
    "moe_shared_expert_intermediate_size", "routed_scaling_factor",
    "norm_topk_prob", "first_k_dense_replace", "short_conv_kernel_size",
    "kda_lower_bound", "kda_safe_gate", "no_kda_lora", "use_qk_norm",
    "linear_silu", "group_norm_size", "num_kv_heads_for_linear_attn",
    "gated_attention_proj_granularity_type", "score_function", "topk_method",
    "moe_router_enable_expert_bias", "expert_swiglu_limit_list",
    "share_expert_swiglu_limit_list", "num_nextn_predict_layers",
    "rms_norm_eps", "rope_theta", "rope_scaling", "rope_interleave",
    "max_position_embeddings", "initializer_range", "layer_kinds",
    "layer_indices")

#: the state's counters (``engine.kv_counters()``), the cache's own
STATE_COUNTERS = ("kda_step_rows", "kda_chunk_tokens",
                  "kda_chunk_padded_tokens", "kda_steps", "state_resets")

#: rows a tick's tap reads at the most (the watched requests in flight)
TAP_ROWS = 8


def build_model(config, dtype):
    import paddle_tpu as paddle
    from paddle_tpu.models.bailing_hybrid import (BailingHybridConfig,
                                                  BailingHybridForCausalLM)
    cfg = BailingHybridConfig(held_experts=tuple(config["held_experts"]),
                              **{k: config[k] for k in MODEL_KEYS})
    # the constructor's own draw of 5.2 B normal weights is freed unread by
    # ``load_weights``: for the length of the constructor the program's
    # normal initializer gives zeros (``drivers/serve_window_moe.py``)
    from paddle_tpu.nn import initializer
    drawn = initializer.Normal.__call__
    initializer.Normal.__call__ = lambda self, shape, dtype="float32": \
        initializer.Constant(0.0)(shape, dtype)
    paddle.set_default_dtype(dtype)
    try:
        model = BailingHybridForCausalLM(cfg)
    finally:
        paddle.set_default_dtype("float32")
        initializer.Normal.__call__ = drawn
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


class LogitTap:
    """The program's own logits at the served positions of the WATCHED
    requests (every request of ``clients`` in ``plan``), read from the
    ticks that served them: ``model.forward`` is wrapped; after each tick's
    forward the rows of its logits that are a watched request's sampled
    positions (the last row of its prefill's last chunk, then each decode
    row, up to ``cap`` a request) are gathered on the device (one small
    program a token bucket, at most ``TAP_ROWS`` rows) and copied to the
    host behind the tick. A request is known by its prompt: a span that
    starts a slot's context anew carries the prompt's first tokens, and
    every later prefill span of the slot has to carry the prompt's next.
    The rows read stand in ONE host buffer of ``pool_rows`` rows in the
    logits' own type, made and written once before the window (``warm``):
    a tick inside the window asks the system for no fresh memory, and rows
    past the buffer's end are not read."""

    def __init__(self, model, plan, clients, cap, pool_rows=64):
        import jax
        self.model, self.cap, self.pool_rows = model, cap, pool_rows
        self.prompts = {(c, i): np.asarray(p) for c in clients
                        for i, (p, _) in enumerate(plan[c])}
        self.slot_of = {}                 # slot -> (client, index)
        self.rows = {k: {} for k in self.prompts}   # served index -> pool row
        self.pool, self.used = None, 0
        self.pending = collections.deque()
        self.take = jax.jit(lambda logits, idx: logits[0][idx])

    def install(self):
        forward = self.model.forward

        def tapped(ids, *a, **kw):
            cache = kw.get("cache")
            spans = cache.ragged_spans() if getattr(
                cache, "ragged_armed", False) else ()
            out = forward(ids, *a, **kw)
            if spans:
                self.after(ids, spans, out)
            return out

        self.model.forward = tapped

    def remove(self):
        del self.model.forward
        self.drain(0)

    def after(self, ids, spans, out):
        flat, picks = None, []
        for slot, qs, n, start in spans:
            if start == 0 or slot in self.slot_of:
                if flat is None:
                    flat = np.asarray(ids._data)[0]
                got = flat[qs:qs + n]
            if start == 0:
                self.slot_of.pop(slot, None)
                match = [k for k, p in self.prompts.items()
                         if len(p) >= n and np.array_equal(p[:n], got)]
                if len(match) == 1:
                    self.slot_of[slot] = match[0]
            key = self.slot_of.get(slot)
            if key is None:
                continue
            prompt = self.prompts[key]
            if start < len(prompt):
                if not np.array_equal(prompt[start:start + n], got):
                    del self.slot_of[slot]        # another request's span
                    continue
                if start + n < len(prompt):
                    continue                      # mid-prefill
                served, row = 0, qs + n - 1
            else:
                served, row = start - len(prompt) + 1, qs
            if served < self.cap:
                picks.append((key, served, row))
        if picks:
            picks = picks[:TAP_ROWS]
            idx = np.zeros(TAP_ROWS, np.int32)
            idx[:len(picks)] = [r for _, _, r in picks]
            rows = self.take(out._data, idx)
            rows.copy_to_host_async()
            self.pending.append((picks, rows))
        self.drain(4)

    def drain(self, keep):
        while len(self.pending) > keep:
            picks, rows = self.pending.popleft()
            rows = np.asarray(rows)
            if self.pool is None:
                self.make_pool(rows.shape[-1], rows.dtype)
            for j, (key, served, _) in enumerate(picks[:len(self.pool)
                                                       - self.used]):
                self.pool[self.used] = rows[j]
                self.rows[key][served] = self.used
                self.used += 1

    def make_pool(self, vocab, dtype):
        self.pool = np.empty((self.pool_rows, vocab), dtype)
        self.pool.fill(0)                 # every page written, so there

    def warm(self, buckets, vocab, dtype):
        import jax.numpy as jnp
        for t in sorted(buckets):
            self.take(jnp.zeros((1, t, vocab), dtype),
                      np.zeros(TAP_ROWS, np.int32)).block_until_ready()
        self.make_pool(vocab, np.asarray(jnp.zeros((), dtype)).dtype)

    def served(self, key):
        """The tokens of request ``key`` from its first served position on,
        as far as rows were read without a hole: each the greedy choice of
        its row, which is what the engine served."""
        rows, m = self.rows[key], 0
        while m in rows:
            m += 1
        if not m:
            return np.zeros(0, np.int64)
        return self.logits(key, m).argmax(axis=-1).astype(np.int64)

    def logits(self, key, served):
        """The first ``served`` rows read of request ``key`` [served,
        vocab] float32."""
        at = [self.rows[key][i] for i in range(served)]
        return self.pool[at].astype(np.float32)


class Stalls:
    """What stopped the engine's thread, for the run's log: the collector's
    full passes (``gc.callbacks``: each walks every object the process
    keeps with all threads stopped) and, from a thread that looks at
    ``engine.ragged_steps`` twenty times a second, the longest tick, with
    the engine thread's stack where one took ``SLOW_S`` or more and a note
    where the watcher itself could not run (the interpreter's lock held, or
    the whole process stopped)."""

    SLOW_S = 1.0

    def __init__(self, engine):
        self.engine, self.full, self.ticks, self.notes = engine, [], [], []
        self.done = threading.Event()
        self.thread = threading.Thread(target=self.watch, daemon=True)

    def start(self):
        gc.callbacks.append(self.collected)
        self.thread.start()

    def stop(self):
        self.done.set()
        if self.collected in gc.callbacks:
            gc.callbacks.remove(self.collected)

    def collected(self, phase, info):
        if info["generation"] == 2:
            now = time.perf_counter()
            if phase == "start":
                self.full.append([now, None])
            elif self.full and self.full[-1][1] is None:
                self.full[-1][1] = now - self.full[-1][0]

    def watch(self):
        steps, since = self.engine.ragged_steps, time.perf_counter()
        woke = since
        while not self.done.wait(0.05):
            now = time.perf_counter()
            if now - woke > self.SLOW_S:
                self.notes.append((now, f"the watcher itself slept "
                                        f"{now - woke:.2f} s"))
            woke = now
            if self.engine.ragged_steps != steps:
                self.ticks.append((since, now - since))
                steps, since = self.engine.ragged_steps, now
            elif now - since > self.SLOW_S and not (
                    self.notes and self.notes[-1][0] > since):
                frame = sys._current_frames().get(
                    getattr(self.engine._thread, "ident", None))
                self.notes.append((now, "the engine's thread at " + " < ".join(
                    f"{f.name}:{f.lineno}" for f in reversed(
                        traceback.extract_stack(frame)[-6:]))
                    if frame else "no engine thread"))

    def between(self, t0, t1):
        """-> a line for the log on what lies in (t0, t1]."""
        full = [d for t, d in self.full if t0 < t <= t1 and d is not None]
        ticks = [d for t, d in self.ticks if t0 < t <= t1]
        notes = [n for t, n in self.notes if t0 < t <= t1]
        return (f"longest tick {1e3 * max(ticks, default=0):.0f} ms (to a "
                f"twentieth of a second), {len(full)} full collections of "
                f"{sum(full):.3f} s" + "".join("; " + n for n in notes))


def warm_rest(config, engine, tap):
    """Two short requests, one after the other, make the engine build its
    pool and its states, zero a slot's state at admission and show the type
    of its logits; then the tick's small eager programs
    (``drivers/serve.py::warm_glue``) and the tap's gather. The kernels'
    own programs are the engine's declared families
    (``engine.warmup_programs()``)."""
    model, seen = engine.model, {}
    forward = model.forward

    def probe(*a, **kw):
        out = forward(*a, **kw)
        seen["logits_dtype"] = out._data.dtype
        return out

    model.forward = probe
    try:
        for _ in range(2):
            engine.generate(np.arange(1, 9)[None], max_new_tokens=2)
    finally:
        del model.forward
    warm_glue(engine, config, seen["logits_dtype"])
    tap.warm(engine.declared_token_buckets(), config["vocab_size"],
             seen["logits_dtype"])
    cache = engine._cache
    (pool,) = next(iter(cache._pools.values()))
    states = next(iter(cache._states.values()))
    return (tuple(pool.shape), str(pool.dtype), len(cache._states),
            {k: (tuple(v.shape), str(v.dtype)) for k, v in states.items()})


#: a watched request as the comparison takes it: what the tap read of it
Served = collections.namedtuple(
    "Served", "client index prompt output finished")


def watched_served(tap, records, min_rows):
    """Every watched request of which the tap read ``min_rows`` rows or
    more, finished or in flight at the cut, as :class:`Served`; a finished
    request's tokens have to be the client's own."""
    out = []
    by_key = {(r.client, r.index): r for r in records}
    for key, prompt in tap.prompts.items():
        tokens = tap.served(key)
        rec = by_key.get(key)
        if len(tokens) < min_rows or rec is None:
            continue
        done = rec.output is not None and len(rec.output) == rec.new
        if done and not np.array_equal(tokens,
                                       np.asarray(rec.output)[:len(tokens)]):
            raise RuntimeError(f"the rows read of request {key} are not "
                               "the tokens its client got")
        out.append(Served(key[0], key[1], prompt, tokens, done))
    return out


def sample_watched(served, kinds, watch, crossed_tokens, count):
    """Of the watched requests the tap read: ONE document request whose
    prefill crossed ``crossed_tokens`` (a second or later document where
    there is one, else a first; of those the shortest), then reasoning
    requests, second or later ones first (their slot held another request
    before them: what ``state_not_reset`` breaks); [] without a document."""
    served = sorted(served, key=lambda r: (r.index == 0, len(r.prompt),
                                           r.client, r.index))
    docs = [r for r in served if r.client in watch["long_clients"]
            and kinds[r.client] == "long" and len(r.prompt) > crossed_tokens]
    if not docs:
        return []
    short = [r for r in served if r.client in watch["short_clients"]]
    return [docs[0]] + short[:max(count - 1, 1)]


def compare(sample, config, seed, limits, widths, quant=None, programs=None):
    """As ``drivers/serve_window_moe.py::compare``, against this family's
    reference -> (the run's checks, the rows that stand in the program's
    place, the reference's per-token readings)."""
    from benchmark.reference import bailing_hybrid as ref
    seqs = [(np.asarray(r.prompt), np.asarray(r.output)) for r in sample]
    gaps = ref.served_gaps(config, seed, seqs, widths, quant=quant,
                           dtype=config["engine_dtype"], programs=programs)
    ends = np.cumsum([len(out) for _, out in seqs])
    decided = sum(m >= limits["router_margin_min"] for m in gaps["margin"])
    rms = np.asarray(gaps["rms"])
    harness.log(
        f"widest gap {max(gaps['served']):.3f}; {decided} of "
        f"{len(gaps['served'])} served tokens decided by the reference's "
        f"router by {limits['router_margin_min']} or more; the program's "
        f"own logits read at {int(np.sum(~np.isnan(rms)))} positions; a "
        "request (prompt length: widest gap, mean gap, median / 90th "
        "percentile / largest distance of its logits): " + ", ".join(
            f"{len(p)}: {max(gaps['served'][e - len(o):e]):.3f}, "
            f"{np.mean(gaps['served'][e - len(o):e]):.4f}, "
            + " / ".join(f"{x:.4f}" for x in np.nanpercentile(
                rms[e - len(o):e], (50, 90, 100)))
            for (p, o), e in zip(seqs, ends)))
    rows = judged_rows(gaps, limits)
    return rows.pop("program"), rows if quant else {}, gaps


def state_counters(engine):
    got = engine.kv_counters()
    return {k: got.get(k, 0) for k in STATE_COUNTERS}


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
                f"parameters in {dtype}; {memory()}")
    engine = ContinuousServingEngine(model, **config["engine"])
    t = time.perf_counter()
    engine.warmup_programs()
    harness.log(f"warm-up: the engine's declared programs in "
                f"{time.perf_counter() - t:.1f} s {ctx['watch'].snapshot()}; "
                f"{memory()}")

    plan, kinds = reason_tail.reason_tail_requests(traffic, seed,
                                                   config["vocab_size"])
    n_long, watch = traffic["long"]["clients"], traffic["watch"]
    tap = LogitTap(model, plan,
                   watch["long_clients"] + watch["short_clients"],
                   watch["rows_a_request"], watch["pool_rows"])
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
                              cache.num_pages - 1,
                              int(np.count_nonzero(cache.lens))))

    engine.start()
    t = time.perf_counter()
    warmed = warm_rest(config, engine, tap)
    harness.log(f"warm-up: the tick's small programs in "
                f"{time.perf_counter() - t:.1f} s; latent pool {warmed[0]} "
                f"{warmed[1]}, {warmed[2]} layers with a state a slot "
                f"{warmed[3]} {ctx['watch'].snapshot()}; {memory()}")
    turnstile = Turnstile(engine, traffic["turnaround_ms"] / 1e3,
                          traffic["tie_ms"] / 1e3)
    clients = Clients(turnstile, plan, ramp=True)
    turnstile.bind(clients.threads)
    sampler = threading.Thread(target=sample_pages, daemon=True)
    cut = False
    tap.install()
    # what the warm-up built lives as long as the process (the traced
    # programs of 475 executables: 650 k objects the collector tracks): a
    # full collection walks all of it for a third of a second with the
    # engine's thread stopped, once or twice a window; frozen, the
    # collector passes it by, as a server that has warmed up would have it
    gc.collect()
    gc.freeze()
    stalls = Stalls(engine)
    stalls.start()
    try:
        t_ramp = time.perf_counter()
        clients.start()
        sampler.start()
        # the ramp: the window opens once every document client's first
        # document is prefilled (its first token is out)
        while not long_documents_prefilled(clients, n_long):
            if not any(th.is_alive() for th in clients.threads):
                raise RuntimeError("the clients ended during the ramp")
            time.sleep(0.05)
        if spy:
            spy.install()
        if tracer:
            tracer.start()
        before = ctx["watch"].snapshot()
        ctx["watch"].names = []
        counters0 = dict(program_counters(engine), **state_counters(engine))
        setup_s = harness.since_start()
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            if not any(th.is_alive() for th in clients.threads):
                break                     # an error ended every client
            time.sleep(0.01)
        t1 = time.perf_counter()
        clients.stop_sending.set()
        counters1 = dict(program_counters(engine), **state_counters(engine))
        compiled = harness.CompileWatch.between(before,
                                                ctx["watch"].snapshot())
        compiled["programs"], ctx["watch"].names = ctx["watch"].names, None
        if tracer:
            tracer.stop()
        if spy:
            spy.remove()
            tap.install()                 # the spy's removal took it off
        harness.log(f"compiles inside the window: {compiled}; {memory()}")
        t_wait = time.perf_counter()
        while (time.perf_counter() - t_wait < FIRST_TOKEN_WAIT_S
               and (engine.ragged_steps < counters1["ragged_steps"] + 2
                    or clients.first_tokens_pending(t0, t1))):
            time.sleep(0.1)
        t_cut = time.perf_counter()
        cut = True
    finally:
        sampling.set()
        stalls.stop()
        gc.unfreeze()                     # the model has to go, cycles too
        engine.abort()                    # fails what is still in flight
        clients.stop_sending.set()
        joined = clients.join(60)
    if not joined:
        raise RuntimeError("client threads did not end after the cut")
    tap.remove()
    tap_rows = tap.used
    trace = tracer.reduce() if tracer else None
    records = clients.records
    for r in records:
        if r.error is not None and cut and r.t_done >= t_cut:
            r.error = None
    s0, s1 = first_delivery(records, t0), first_delivery(records, t1)
    wm = window_metrics(records, s0, s1, cut_at=t_cut)
    counters = counters_between(counters0, counters1)
    done = [r for r in records if r.output is not None
            and r.t_done is not None and t0 < r.t_done <= t1]
    harness.log(f"window {t1 - t0:.2f} s with {counters['ragged_steps']} "
                f"ticks, between deliveries {wm['window_s']:.2f} s; then "
                f"{t_cut - t1:.1f} s to the cut; "
                f"{sum(kinds[r.client] == 'long' for r in done)} document "
                f"and {sum(kinds[r.client] == 'short' for r in done)} "
                f"reasoning requests finished in it, {len(wm['gaps'])} "
                "gaps, 50th / 95th / 100th percentile "
                + " / ".join(f"{1e3 * harness.percentile(wm['gaps'], p):.0f}"
                             for p in (50, 95, 100) if wm["gaps"]) + " ms, "
                f"mean {1e3 * sum(wm['gaps']) / max(len(wm['gaps']), 1):.2f} "
                f"ms; the ramp before it took {t0 - t_ramp:.2f} s; the "
                "state: " + ", ".join(f"{k} {counters[k]}"
                                      for k in STATE_COUNTERS)
                + f"; {stalls.between(t0, t1)}; the tap read "
                f"{tap_rows} rows of {watch['pool_rows']}")
    mem_peak = harness.memory_peak_bytes(ctx["chips"])
    sample = sample_watched(
        watched_served(tap, records, watch["min_rows"]), kinds, watch,
        traffic["crossed_chunks"] * config["engine"]["prefill_chunk_tokens"],
        ctx["limits"]["sample_requests"])
    own = [tap.logits((r.client, r.index), len(r.output)) for r in sample]

    # free the program's state before the reference takes the chip
    for _, p in model.named_parameters():
        if p is not None:
            p._data = None
    engine._cache = None
    del engine, model, tap
    gc.collect()
    jax.clear_caches()

    stand_ins, gaps = {}, None
    if sample:
        t_ref = time.perf_counter()
        checks, stand_ins, gaps = compare(
            sample, config, seed, ctx["limits"], traffic["reference_width"],
            quant=ctx.get("control"), programs=own)
        del own
        harness.log(f"reference: {len(gaps['served'])} served tokens of "
                    f"{len(sample)} requests (client, request, finished "
                    f"{[(r.client, r.index, r.finished) for r in sample]}, "
                    "prompts "
                    f"{[len(r.prompt) for r in sample]}) in "
                    f"{time.perf_counter() - t_ref:.1f} s")
    else:
        checks = [(name, float("nan"), ctx["limits"][name])
                  for name in CHECKS]
    in_window = [p for p in pages if t0 <= p[0] <= t1]
    return {
        "attempted": wm["attempted"], "failed": wm["failed"],
        "checks": checks, "memory_peak_bytes": mem_peak,
        "window_s": t1 - t0,              # what counters and calls span
        "end_to_end": dict(end_to_end(wm), setup_s=setup_s),
        "compiles_in_window": compiled, "trace": trace,
        "stand_ins": stand_ins, "gaps": gaps, "window": wm,
        "finished": len(done), "counters": counters,
        "token_stamps": [[x - t0 for x in r.tokens] for r in records],
        "kernel_calls": [c for c in spy.calls if t0 <= c[0] <= t1]
        if spy else None,
        "pages": [p[:3] for p in in_window],
        "state_slots_live": [p[3] for p in in_window],
    }

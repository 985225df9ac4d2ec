"""The serving cell of a model whose layers mix window and full attention
and carry routed experts (``SmallThinkerForCausalLM``) behind
``ContinuousServingEngine``: the closed loop, window, cut and statistics
are ``drivers/serve.py``'s, the turnstile and the two-number comparison
``drivers/serve_latent_moe.py``'s; this file brings the model's build, the
deal of short turns and long documents (``traffic/mixed_len.py``), the
sample the comparison needs and the program's own logits on it
(``replay_logits``), what the window groups counted, and
``reference/smallthinker.py``.

On a program that lacks the model (the parent of the PR that brought it)
the program's import fails and the run exits at once with no result.
"""
from __future__ import annotations

import gc
import threading
import time

import numpy as np

from benchmark import harness, weights_smallthinker as weights
from benchmark.drivers.serve import (
    FIRST_TOKEN_WAIT_S, Clients, KernelSpy, end_to_end, first_delivery,
    sample_finished, warm_glue, window_metrics)
from benchmark.drivers import serve_latent_moe as latent
from benchmark.drivers.serve_latent_moe import (
    Turnstile, counters_between, program_counters)
from benchmark.traffic import mixed_len

#: the configuration's keys that the program's config class takes
MODEL_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "moe_ffn_hidden_size",
    "moe_num_primary_experts", "moe_num_active_primary_experts",
    "moe_primary_router_apply_softmax", "norm_topk_prob", "rope_layout",
    "sliding_window_layout", "sliding_window_size", "rms_norm_eps",
    "rope_theta", "rope_scaling", "max_position_embeddings",
    "tie_word_embeddings", "initializer_range")

#: beside the two numbers of the TOKENS' comparison (``latent.CHECKS``: how
#: far the served tokens lie below the reference's best), one of the
#: program's own LOGITS at the served positions (``replay_logits``): each
#: position's root mean square distance from the reference's over the
#: vocabulary, and of those the median over the positions that the
#: reference's router decided (the ``decided_logit_gap_max`` positions: a
#: flipped expert moves a position's logits by several times what rounding
#: does, and a served sequence that repeats itself carries one near-tie
#: through all its positions). A lower precision moves it at every
#: position, where the tokens' gaps move only where two logits lie close:
#: the int8 control's first choices lie as close to the reference as the
#: bf16 program's tokens, its logits three times as far
RMS = "decided_logit_rms_median"
CHECKS = latent.CHECKS + (RMS,)

#: over every sample the benchmark took of the cache at a tick's boundary:
#: the slots whose next query would see a key in a block that a window
#: group's table does not map to a referenced page (``kv_sample``)
UNHELD = "window_keys_unheld_samples"

#: the cache's counters of the window groups (``engine.kv_counters()``)
KV_COUNTERS = ("prefix_evictions_device", "window_blocks_released",
               "window_blocks_evicted", "prefix_hits_shortened_by_window")


def build_model(config, dtype):
    import paddle_tpu as paddle
    from paddle_tpu.models.smallthinker import (SmallThinkerConfig,
                                                SmallThinkerForCausalLM)
    held = config.get("held_experts")
    cfg = SmallThinkerConfig(held_experts=tuple(held) if held else None,
                             **{k: config[k] for k in MODEL_KEYS})
    # the constructor's own draw of 5.6 B normal weights took 60 s of every
    # set-up (my chip runs, PR 31) and ``load_weights`` frees it unread: for
    # the length of the constructor the program's normal initializer gives
    # zeros
    from paddle_tpu.nn import initializer
    drawn = initializer.Normal.__call__
    initializer.Normal.__call__ = lambda self, shape, dtype="float32": \
        initializer.Constant(0.0)(shape, dtype)
    paddle.set_default_dtype(dtype)
    try:
        model = SmallThinkerForCausalLM(cfg)
    finally:
        paddle.set_default_dtype("float32")
        initializer.Normal.__call__ = drawn
    harness.log("model object built (zeros, which ``load_weights`` frees)")
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


class WindowSpy(KernelSpy):
    """``KernelSpy`` whose records also say the layer's window: (time,
    q_lens, context_lens, window or None)."""

    def install(self):
        super().install()
        inner, calls = getattr(self.mod, self.ENTRY), self.calls

        def spy(*a, **kw):
            out = inner(*a, **kw)
            calls[-1] = calls[-1] + (kw.get("window"),)
            return out

        setattr(self.mod, self.ENTRY, spy)


def warm_rest(config, engine):
    """One short request makes the engine build its pools and shows the
    type of its logits; then the tick's small eager programs
    (``drivers/serve.py::warm_glue``). The kernel's own programs, one a
    (token bucket, job bucket, window or none), are the engine's declared
    family, which ``engine.warmup_programs()`` has compiled."""
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
    warm_glue(engine, config, seen["logits_dtype"])
    cache = engine._cache
    return ([(g.label, g.num_pages) for g in cache._groups],
            sorted({str(p.dtype) for pools in cache._pools.values()
                    for p in pools}))


def long_documents_prefilled(clients, n_long):
    """True once every long client's first request has its first token."""
    from paddle_tpu.profiler import request_trace as rt
    store = rt.get_trace_store()
    with clients.lock:
        first = {r.client: r for r in clients.records if r.index == 0}
        live = dict(clients.live)
    for c in range(n_long):
        rec = first.get(c)
        if rec is None:
            return False
        if rec.t_done is not None:
            continue
        got = live.get(c)
        if got is None or got[0] is not rec \
                or not store.timeline(got[1].trace_id)["tokens"]:
            return False
    return True


def sample_kinds(records, asks, seed, count, window, passed_by):
    """Finished requests for the comparison: the longest, a second ask (a
    prefix hit), a long request whose context passed the window by
    ``passed_by`` tokens at the least, a short one, then others drawn from
    the seed; [] where one of the four kinds has no finished request."""
    picked = sample_finished(records, seed, len(records))
    if not picked:
        return []

    def ask(r):
        return asks[r.client][r.index]

    kinds = (lambda r: ask(r) > 0,
             lambda r: ask(r) >= 0
             and len(r.prompt) + r.new >= window + passed_by,
             lambda r: ask(r) < 0)
    out = [picked[0]]
    for kind in kinds:
        hit = next((r for r in picked[1:] if r not in out and kind(r)), None)
        if hit is None:
            return []
        out.append(hit)
    out += [r for r in picked[1:] if r not in out]
    return out[:max(count, 4)]


def replay_logits(engine, sample, vocab, wait_s=300.0):
    """The program's own logits at the served positions of ``sample``: each
    request's prompt with its served tokens (but the last) goes through the
    engine once more as a prompt, on the normal path (admission, the prefix
    cache, 512-token chunks that share ticks with whatever still decodes),
    and the rows of the tick's logits that are this request's positions
    ``[prompt - 1, prompt + served - 1)`` are read -> for each request
    [served, vocab] float32, NaN where a position was not computed (a
    prefix hit that reached past the prompt's last token).

    The rows are found by what they hold: the request's own ids at its own
    positions, next to a row that holds the neighbouring one (another
    request's decode row stands alone)."""
    model = engine.model
    forward = model.forward
    want = {}

    def capture(ids, *a, **kw):
        out = forward(ids, *a, **kw)
        seq, lo = want.get("seq"), want.get("lo")
        if seq is None:
            return out
        flat = np.asarray(ids._data)[0]
        pos = np.asarray(kw["position_ids"])
        mine = (pos >= lo) & (pos < len(seq)) \
            & (flat == seq[np.minimum(pos, len(seq) - 1)])
        step = np.diff(pos) == 1
        run = np.zeros(len(pos), bool)
        run[1:] |= mine[:-1] & step
        run[:-1] |= mine[1:] & step
        rows = np.flatnonzero(mine & run)
        if len(rows):
            want["got"][pos[rows] - lo] = np.asarray(out._data[0])[rows]
        return out

    model.forward = capture
    logits = []
    try:
        for r in sample:
            seq = np.concatenate([np.asarray(r.prompt),
                                  np.asarray(r.output)[:-1]])
            logits.append(np.full((len(r.output), vocab), np.nan,
                                  np.float32))
            want.update(seq=seq, lo=len(r.prompt) - 1, got=logits[-1])
            engine.generate(seq[None], max_new_tokens=1, timeout=wait_s)
            want.update(seq=None)
    finally:
        del model.forward
    return logits


def rms_check(rms, margins, limits):
    """``RMS`` from each position's distance and router margin; a position
    that was not read (NaN) is left out."""
    read = [r for r, m in zip(rms, margins)
            if m >= limits["router_margin_min"] and r == r]
    return [(RMS, float(np.median(read)) if read else float("nan"),
             limits[RMS])]


def judged_rows(gaps, limits):
    """``drivers/serve_latent_moe.py::judged_rows`` (the program, the int8
    control's first choices and an altered token in the served tokens'
    place), each with the check of its own logits where the run read them:
    the control's are that pass's, an altered token leaves the program's as
    they were."""
    rows = latent.judged_rows(gaps, limits)
    for who, rms in (("program", "rms"), ("altered_token", "rms"),
                     ("control_int8", "rms_int8")):
        if who in rows and gaps.get(rms):
            rows[who] = rows[who] + rms_check(gaps[rms], gaps["margin"],
                                              limits)
    return rows


def compare(sample, config, seed, limits, widths, quant=None, programs=None):
    """As ``drivers/serve_latent_moe.py::compare``, against this family's
    reference -> (the run's checks, the rows that stand in the program's
    place, the reference's per-token readings). ``programs``: the program's
    own logits at the served positions (``replay_logits``)."""
    from benchmark.reference import smallthinker as ref
    seqs = [(np.asarray(r.prompt), np.asarray(r.output)) for r in sample]
    gaps = ref.served_gaps(config, seed, seqs, widths, quant=quant,
                           dtype=config["engine_dtype"], programs=programs)
    ends = np.cumsum([len(out) for _, out in seqs])
    decided = sum(m >= limits["router_margin_min"] for m in gaps["margin"])
    rms = np.asarray(gaps.get("rms", [np.nan]))
    harness.log(
        f"widest gap {max(gaps['served']):.3f}; {decided} of "
        f"{len(gaps['served'])} served tokens decided by the reference's "
        f"router by {limits['router_margin_min']} or more; the program's "
        f"own logits read at {int(np.sum(~np.isnan(rms)))} positions; a "
        "request (prompt length: widest gap, mean gap, median / 90th "
        "percentile / largest distance of its logits over all its "
        "positions): " + ", ".join(
            f"{len(p)}: {max(gaps['served'][e - len(o):e]):.3f}, "
            f"{np.mean(gaps['served'][e - len(o):e]):.4f}, "
            + " / ".join(f"{x:.4f}" for x in np.nanpercentile(
                rms[e - len(o):e] if len(rms) > 1 else rms, (50, 90, 100)))
            for (p, o), e in zip(seqs, ends)))
    rows = judged_rows(gaps, limits)
    return rows.pop("program"), rows if quant else {}, gaps


def kv_counters(engine):
    got = engine.kv_counters()
    return {k: got.get(k, 0) for k in KV_COUNTERS}


def kv_sample(cache):
    """What the sampler reads of the cache, 4x a second at a tick's
    boundary (``engine.run_on_loop``): each group's (label, pages used,
    pages); over the slots in use, the tokens of the live contexts and
    those of them whose blocks each window group's table still maps; and
    the slots whose next query would see a key that a window group does not
    hold. That is the benchmark's own arithmetic on what the kernel reads:
    a query at the slot's filled length ``n`` sees the keys ``j > n -
    window`` (the reference's rule), so every block from ``(n - window + 1)
    // page`` to the last that holds a key has to be mapped to a page other
    than the scratch page 0, that page referenced, and by as many slots as
    map it; 0 in a program that keeps the guarantee."""
    page, lens = cache.page_size, cache.lens
    block = np.arange(cache._groups[0].tables.shape[1])[None]
    filled = block < -(-lens // page)[:, None]   # blocks that hold a key
    held, unheld = [], 0
    for g in cache._groups[1:]:
        mapped = g.tables > 0
        held.append(int(np.sum(lens) - page * np.sum(filled & ~mapped)))
        seen = filled & (
            block >= (np.maximum(lens - g.window + 1, 0) // page)[:, None])
        slots = np.bincount(g.tables[seen & mapped], minlength=g.num_pages)
        lost = seen & ~(mapped & (g.ref[g.tables] >= slots[g.tables]))
        unheld += int(np.sum(np.any(lost, axis=1)))
    return cache.group_usage(), int(lens.sum()), held, unheld


def memory():
    """The device's bytes in use and their peak so far, for the log."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return (f"{stats.get('bytes_in_use', 0) / 1e9:.2f} GB in use, peak "
            f"{stats.get('peak_bytes_in_use', 0) / 1e9:.2f}")


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

    plan, asks = mixed_len.mixed_len_requests(traffic, seed,
                                              config["vocab_size"])
    n_long = traffic["long"]["clients"]
    seconds = ctx["seconds"]
    tracer = None
    if ctx["trace"]:
        from benchmark import tracing
        seconds = min(seconds, traffic.get("trace_seconds", seconds))
        tracer = tracing.Tracer(ctx)
    spy = WindowSpy(model) if ctx["trace"] else None
    samples = []                          # (time, kv_sample)
    sampling = threading.Event()

    def sample_kv():
        while not sampling.wait(0.25):
            try:
                got = engine.run_on_loop(
                    lambda e: None if e._cache is None
                    else kv_sample(e._cache), timeout=5.0)
            except (RuntimeError, TimeoutError):
                continue                  # the engine has stopped
            if got:
                samples.append((time.perf_counter(),) + got)

    engine.start()
    t = time.perf_counter()
    groups, kv_dtypes = warm_rest(config, engine)
    harness.log(f"warm-up: the tick's small programs in "
                f"{time.perf_counter() - t:.1f} s; page groups {groups} "
                f"in {kv_dtypes} {ctx['watch'].snapshot()}; {memory()}")
    turnstile = Turnstile(engine, traffic["turnaround_ms"] / 1e3,
                          traffic["tie_ms"] / 1e3)
    clients = Clients(turnstile, plan, ramp=bool(traffic.get("ramp")))
    turnstile.bind(clients.threads)
    sampler = threading.Thread(target=sample_kv, daemon=True)
    cut = False
    try:
        t_ramp = time.perf_counter()
        clients.start()
        sampler.start()
        # the ramp: the window opens once every long client's first
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
        counters0 = dict(program_counters(engine), **kv_counters(engine))
        setup_s = harness.since_start()
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            if not any(th.is_alive() for th in clients.threads):
                break                     # an error ended every client
            time.sleep(0.01)
        t1 = time.perf_counter()
        clients.stop_sending.set()
        counters1 = dict(program_counters(engine), **kv_counters(engine))
        compiled = harness.CompileWatch.between(before,
                                                ctx["watch"].snapshot())
        compiled["programs"], ctx["watch"].names = ctx["watch"].names, None
        if tracer:
            tracer.stop()
        if spy:
            spy.remove()
        harness.log(f"compiles inside the window: {compiled}; {memory()}")
        t_wait = time.perf_counter()
        while (time.perf_counter() - t_wait < FIRST_TOKEN_WAIT_S
               and (engine.ragged_steps < counters1["ragged_steps"] + 2
                    or clients.first_tokens_pending(t0, t1))):
            time.sleep(0.1)
        t_cut = time.perf_counter()
        cut = True
        # the sample and the program's own logits on it, while the engine
        # still runs (the requests in flight decode beside the replay)
        sample = sample_kinds(list(clients.records), asks, seed,
                              ctx["limits"]["sample_requests"],
                              config["sliding_window_size"],
                              traffic["passed_window_by"])
        own = replay_logits(engine, sample, config["vocab_size"])
        harness.log(f"the sample's {len(sample)} requests once more as "
                    f"prompts, for their logits, in "
                    f"{time.perf_counter() - t_cut:.1f} s; {memory()}")
    finally:
        sampling.set()
        engine.abort()                    # fails what is still in flight
        clients.stop_sending.set()
        joined = clients.join(60)
    if not joined:
        raise RuntimeError("client threads did not end after the cut")
    trace = tracer.reduce() if tracer else None
    window_args = window_span_args(spy) if spy else None
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
                f"{sum(asks[r.client][r.index] >= 0 for r in done)} long and "
                f"{sum(asks[r.client][r.index] < 0 for r in done)} short "
                f"requests finished in it, {len(wm['gaps'])} gaps, 50th / "
                "95th / 100th percentile "
                + " / ".join(f"{1e3 * harness.percentile(wm['gaps'], p):.0f}"
                             for p in (50, 95, 100) if wm["gaps"]) + " ms, "
                f"mean {1e3 * sum(wm['gaps']) / max(len(wm['gaps']), 1):.2f} "
                f"ms; the ramp before it took {t0 - t_ramp:.2f} s; window "
                "groups: " + ", ".join(f"{k} {counters[k]}"
                                       for k in KV_COUNTERS))
    mem_peak = harness.memory_peak_bytes(ctx["chips"])

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
            quant=ctx.get("control"), programs=own)
        del own
        harness.log(f"reference: {len(gaps['served'])} served tokens of "
                    f"{len(sample)} requests (asks "
                    f"{[asks[r.client][r.index] for r in sample]}, prompts "
                    f"{[len(r.prompt) for r in sample]}) in "
                    f"{time.perf_counter() - t_ref:.1f} s")
    else:
        checks = [(name, float("nan"), ctx["limits"][name])
                  for name in CHECKS]
    checks.append((UNHELD, sum(s[4] for s in samples),
                   ctx["limits"].get(UNHELD, 0)))
    in_window = [s for s in samples if t0 <= s[0] <= t1]
    run = {
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
        "window_span_args": window_args,
        # the fuller group's pages, as ``kv_pages_peak_pct`` reads them
        "pages": [max(((s[0], used, cap) for _, used, cap in s[1]),
                      key=lambda p: p[1] / p[2]) for s in in_window],
        "kv_samples": in_window,
    }
    return run


def window_span_args(spy):
    """The args of the traced window's ``attn/qblock`` spans of window
    layers (``window``, ``jobs``, ``jobs_without_window``), or None where
    the program's tracer has nothing of the kind."""
    from benchmark.layer_metrics.qblock_job_fill_pct import kept_args
    args = kept_args({"kernel_calls": spy.calls}) or ()
    return [a for a in args if "jobs_without_window" in a] or None

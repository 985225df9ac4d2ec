"""Serving cells: ``ContinuousServingEngine`` under closed-loop clients.

One client thread a slot; each sends its next request when the last
returns. Per-token times are the program's own request-trace stamps
(``profiler/request_trace.note_token``), read here once a request is over.
After the window has closed the driver waits (at most a minute) for the
first token of every request sent inside it, then cuts what is still in
flight: a request that answers late is late, one that is cut after its first
token is not failed. A sample of the requests the engine finished, drawn
from the seed with the longest in it, is compared with the plain reference
once the engine's state is freed.
"""
from __future__ import annotations

import gc
import statistics
import threading
import time

import numpy as np

from benchmark import harness, weights
from benchmark.drivers import train
from benchmark.traffic import generate

FIRST_TOKEN_WAIT_S = 60.0


class Record:
    """One request as the client saw it."""
    __slots__ = ("client", "index", "ramp", "prompt", "new", "t_send",
                 "t_done", "tokens", "output", "error")

    def __init__(self, client, index, ramp, prompt, new):
        self.client, self.index, self.ramp = client, index, ramp
        self.prompt, self.new = prompt, new
        self.t_send = self.t_done = None
        self.tokens, self.output, self.error = [], None, None


class Clients:
    """The closed loop. ``stop_sending`` ends it: no new request starts."""

    def __init__(self, engine, plan, ramp):
        self.engine, self.plan, self.ramp = engine, plan, ramp
        self.records = []
        self.live = {}                    # client -> (record, trace ctx)
        self.lock = threading.Lock()
        self.stop_sending = threading.Event()
        self.ramps_done = 0
        self.threads = [threading.Thread(target=self._client, args=(c,),
                                         daemon=True)
                        for c in range(len(plan))]

    def start(self):
        for t in self.threads:
            t.start()

    def join(self, timeout):
        deadline = time.monotonic() + timeout
        for t in self.threads:
            t.join(max(deadline - time.monotonic(), 0.1))
        return not any(t.is_alive() for t in self.threads)

    def _client(self, c):
        from paddle_tpu.profiler import request_trace as rt
        store = rt.get_trace_store()
        for i, (prompt, new) in enumerate(self.plan[c]):
            if self.stop_sending.is_set():
                return
            rec = Record(c, i, self.ramp and i == 0, prompt, new)
            ctx = rt.start_request(source="benchmark",
                                   prompt_tokens=len(prompt),
                                   max_new_tokens=new)
            with self.lock:
                self.records.append(rec)
                self.live[c] = (rec, ctx)
            rec.t_send = time.perf_counter()
            try:
                out = self.engine.generate(prompt[None], max_new_tokens=new,
                                           trace=ctx)
                rec.output = out.numpy()[0][len(prompt):]
            except Exception as e:        # the record carries it to the run
                rec.error = e
            rec.t_done = time.perf_counter()
            rec.tokens = list(store.timeline(ctx.trace_id)["tokens"])
            rt.finish_request(ctx, status="ok" if rec.error is None
                              else "error")
            with self.lock:
                self.live.pop(c, None)
                if rec.ramp:
                    self.ramps_done += 1
            if rec.error is not None:
                return

    def first_tokens_pending(self, t0, t1):
        """Requests sent in [t0, t1] that have no token yet."""
        from paddle_tpu.profiler import request_trace as rt
        store = rt.get_trace_store()
        with self.lock:
            live = list(self.live.values())
        return [rec for rec, ctx in live
                if t0 <= rec.t_send <= t1
                and not store.timeline(ctx.trace_id)["tokens"]]


def first_delivery(records, t):
    """The stamp of the first token delivered at or after ``t`` (``t``
    itself where none is)."""
    later = [x for r in records for x in r.tokens if x >= t]
    return min(later) if later else t


def window_metrics(records, t0, t1, cut_at=None):
    """The end-to-end numbers from the clients' records over the window
    (t0, t1]. Tokens and gaps count where their stamp lies in it; TTFT is
    over every request sent in it, and one that never got a token is failed
    and reads as its whole wait (to ``cut_at``)."""
    window = t1 - t0
    delivered = sum(1 for r in records for t in r.tokens if t0 < t <= t1)
    gaps = [b - a for r in records for a, b in zip(r.tokens, r.tokens[1:])
            if t0 < b <= t1]
    sent = [r for r in records if t0 < r.t_send <= t1]
    ttft = [(r.tokens[0] if r.tokens
             else (cut_at if cut_at is not None else t1)) - r.t_send
            for r in sent]
    failed = sum(1 for r in records
                 if r.error is not None or (r in sent and not r.tokens))
    return {"delivered": delivered, "gaps": gaps, "ttft": ttft,
            "attempted": len(sent), "failed": failed, "window_s": window}


def end_to_end(wm):
    """Every statistic of the window that some metric reports: the manifest
    says which are end to end (PERF.md, section 2, on the two tails)."""
    out = {"serve_tok_s": harness.rate(wm["delivered"], wm["window_s"])}
    if wm["gaps"]:
        out["itl_mean_ms"] = 1e3 * statistics.fmean(wm["gaps"])
        out["itl_p95_ms"] = 1e3 * harness.percentile(wm["gaps"], 95)
    if wm["ttft"]:
        out["ttft_p95_ms"] = 1e3 * harness.percentile(wm["ttft"], 95)
    return out


def sample_finished(records, seed, count):
    """Finished requests for the comparison: the longest, then others
    drawn from the seed."""
    done = [r for r in records if r.output is not None and not r.ramp
            and len(r.output) == r.new]
    if not done:
        return []
    done.sort(key=lambda r: (r.client, r.index))
    longest = max(done, key=lambda r: len(r.prompt) + r.new)
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 3])
    picks = rng.permutation(len(rest))[:max(count - 1, 0)]
    return [longest] + [rest[i] for i in picks]


def compare(sample, config, seed, limits, width, quant=None):
    """-> (the run's checks, the rows that stand in the program's place, each
    as checks of its own against the same limit; none without ``quant``).
    ``control``: the token that the reference at ``quant`` puts first at each
    served position, the widest gap. ``altered_token``: a served token
    altered by one id, the narrowest gap, so that the row is judged not
    correct only where every single altered token would be."""
    from benchmark.reference import llama as ref
    seqs = [(np.asarray(r.prompt), np.asarray(r.output)) for r in sample]
    gaps = ref.served_gaps(config, seed, seqs, width, quant=quant,
                           dtype=config["engine_dtype"])
    name, limit = "served_logit_gap_max", limits["served_logit_gap_max"]
    stand_ins = {}
    if quant is not None:
        stand_ins = {"control_" + quant: [(name, max(gaps["control"]), limit)],
                     "altered_token": [(name, min(gaps["altered"]), limit)]}
    return [(name, max(gaps["served"]), limit)], stand_ins, len(gaps["served"])


class KernelSpy:
    """Traced runs only: records each q-block kernel call's (q_lens,
    context_lens) by wrapping the kernel's Python entry. The engine is eager
    and the descriptors are host values, so this reads them at no device
    cost; untraced runs do not install it."""

    MODULE = "paddle_tpu.ops.pallas.ragged_paged_attention"
    ENTRY = "_ragged_paged_attention_pallas_qblock"

    def __init__(self, model=None):
        import importlib
        self.mod = importlib.import_module(self.MODULE)
        self.orig = getattr(self.mod, self.ENTRY)
        self.model = model
        self.calls = []

    def install(self):
        from benchmark import tracing
        orig, calls = self.orig, self.calls

        def spy(q, k_pages, v_pages, block_tables, seq_slots, q_starts,
                q_lens, context_lens, **kw):
            calls.append((time.perf_counter(),
                          np.asarray(q_lens).tolist(),
                          np.asarray(context_lens).tolist()))
            with tracing.span("qblock_attention"):
                return orig(q, k_pages, v_pages, block_tables, seq_slots,
                            q_starts, q_lens, context_lens, **kw)

        setattr(self.mod, self.ENTRY, spy)
        if self.model is not None:
            # a host span around each tick's forward, so that an idle gap
            # of the device falls either inside the model's eager dispatch
            # or in the scheduler's Python between two forwards
            forward = self.model.forward

            def traced_forward(*a, **kw):
                with tracing.span("model_forward"):
                    return forward(*a, **kw)

            self.model.forward = traced_forward

    def remove(self):
        setattr(self.mod, self.ENTRY, self.orig)
        if self.model is not None:
            del self.model.forward        # the class's method again


def warm_kernels(config, engine, warm):
    """Compile the ragged attention kernel for every (token bucket, job
    bucket) the cell's traffic can reach, through the public op: the job
    count of a tick follows the contexts in flight, so no finite warm-up
    traffic is sure to have met each. One short request first makes the
    engine build its page pools; the warm-up pools then take their shape
    and type from those (today float32, whatever the weights' type), so
    that a later change of the program's KV type is followed, not missed."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention)
    interpret = jax.default_backend() != "tpu"     # the cache's own rule
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
        del model.forward                 # the class's method again
    k_pages, _ = next(iter(engine._cache._pools.values()))
    nkv, num_pages, page, d = k_pages.shape
    dtype, nq = k_pages.dtype, config["num_attention_heads"]
    slots, max_len = engine.max_batch, engine.max_len
    pages_per_seq = -(-max_len // page)
    pool = jnp.zeros((nkv, num_pages, page, d), dtype)
    tables = np.zeros((slots, pages_per_seq), np.int32)
    for s in range(slots):
        tables[s] = (1 + s * pages_per_seq
                     + np.arange(pages_per_seq)) % num_pages
    outs = []
    for t in warm["token_buckets"]:
        q = jnp.zeros((t, nq, d), dtype)
        for jobs in warm["job_buckets"]:
            # one q-block of single-token spans whose pages add up to the
            # bucket: min(jobs, 8) rows, jobs/rows pages each
            rows = min(jobs, 8, t)
            ctx = min((jobs // rows) * page, max_len)
            outs.append(ragged_paged_attention(
                q, pool, pool, tables, np.arange(rows, dtype=np.int32),
                np.arange(rows, dtype=np.int32),
                np.ones(rows, np.int32), np.full(rows, ctx, np.int32),
                interpret=interpret))
    for o in outs:
        o.block_until_ready()
    warm_glue(engine, config, seen["logits_dtype"])
    return len(outs), str(dtype)


def warm_glue(engine, config, logits_dtype):
    """The small eager programs of a tick that neither
    ``engine.warmup_programs()`` nor the kernel warm-up meets, found by name
    in the window's compile log (PERF.md, PR 24): the int32 descriptor arrays
    of ``begin_ragged``, one shape for each number of spans in a tick, and
    the tick's logits slice, float32 copy and argmax, one a token bucket, on
    logits of the type the model's forward was seen to return."""
    import jax.numpy as jnp
    for n in range(1, engine.max_batch + 1):
        jnp.asarray(list(range(n)), jnp.int32).block_until_ready()
    for t in sorted(engine.declared_token_buckets()):
        logits = jnp.zeros((1, t, config["vocab_size"]), logits_dtype)
        jnp.argmax(logits[0].astype(jnp.float32), axis=-1).block_until_ready()


def run(ctx):
    """One run of a serving cell."""
    import jax
    from paddle_tpu.inference import ContinuousServingEngine
    config, traffic, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    dtype = config["engine_dtype"]
    model = train.build_model(config, dtype)
    model.eval()
    train.load_weights(model, config, seed, dtype)
    harness.log(f"model {weights.param_count(config) / 1e9:.3f} B "
                f"parameters in {dtype}")
    engine_kw = dict(config["engine"])
    engine = ContinuousServingEngine(model, **engine_kw)
    t = time.perf_counter()
    engine.warmup_programs()
    harness.log(f"warm-up: the engine's declared programs in "
                f"{time.perf_counter() - t:.1f} s {ctx['watch'].snapshot()}")

    plan = generate.closed_loop_requests(traffic, seed, config["vocab_size"])
    seconds = ctx["seconds"]
    tracer = None
    if ctx["trace"]:
        from benchmark import tracing
        seconds = min(seconds, traffic.get("trace_seconds", seconds))
        tracer = tracing.Tracer(ctx)
    spy = KernelSpy(model) if ctx["trace"] else None
    pages = []                            # (time, used pages)
    sampling = threading.Event()

    def sample_pages():
        while not sampling.wait(0.25):
            cache = engine._cache
            if cache is not None:
                pages.append((time.perf_counter(), cache.used_page_count,
                              cache.num_pages - 1))

    engine.start()
    t = time.perf_counter()
    n, kv_dtype = warm_kernels(config, engine, traffic["warm"])
    harness.log(f"warm-up: {n} kernel shapes on {kv_dtype} pools in "
                f"{time.perf_counter() - t:.1f} s {ctx['watch'].snapshot()}")
    clients = Clients(engine, plan, ramp=bool(traffic.get("ramp")))
    sampler = threading.Thread(target=sample_pages, daemon=True)
    cut = False
    try:
        clients.start()
        sampler.start()
        # the ramp: the window opens once this many clients are past their
        # first, short request and the slots' phases are spread
        open_after = traffic["ramp"]["open_after_clients"]
        while clients.ramps_done < open_after:
            if not any(th.is_alive() for th in clients.threads):
                raise RuntimeError("the clients ended during the ramp")
            time.sleep(0.05)
        if spy:
            spy.install()
        if tracer:
            tracer.start()
        before = ctx["watch"].snapshot()
        ctx["watch"].names = []
        counters0 = engine_counters(engine)
        setup_s = harness.since_start()
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            if not any(th.is_alive() for th in clients.threads):
                break                     # an error ended every client
            time.sleep(0.01)
        t1 = time.perf_counter()
        clients.stop_sending.set()
        counters1 = engine_counters(engine)
        compiled = harness.CompileWatch.between(before,
                                                ctx["watch"].snapshot())
        compiled["programs"], ctx["watch"].names = ctx["watch"].names, None
        if tracer:
            tracer.stop()
        if spy:
            spy.remove()
        harness.log(f"compiles inside the window: {compiled}")
        # two further ticks, so that a delivery after ``t1`` closes the
        # window; then the first token of every request sent inside it,
        # waited for from here: ending a trace can take a minute by itself
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
    # read only now: the reduction is Python and would starve the engine
    trace = tracer.reduce() if tracer else None
    records = clients.records
    # a request the cut ended is not an error of the window
    for r in records:
        if r.error is not None and cut and r.t_done >= t_cut:
            r.error = None
    # tokens come in bursts, one a tick: the window runs from the first
    # delivery after it opened to the first after ``seconds`` had passed,
    # a whole number of ticks, so that no burst is cut in two
    s0, s1 = first_delivery(records, t0), first_delivery(records, t1)
    wm = window_metrics(records, s0, s1, cut_at=t_cut)
    harness.log(f"window {t1 - t0:.2f} s with "
                f"{counters1['ragged_steps'] - counters0['ragged_steps']} "
                f"ticks, between deliveries {wm['window_s']:.2f} s; then "
                f"{t_cut - t1:.1f} s to the cut; {len(wm['gaps'])} gaps, "
                "50th / 95th / 100th percentile "
                + " / ".join(f"{1e3 * harness.percentile(wm['gaps'], p):.0f}"
                             for p in (50, 95, 100) if wm["gaps"]) + " ms")
    mem_peak = harness.memory_peak_bytes(ctx["chips"])
    sample = sample_finished(records, seed, ctx["limits"]["sample_requests"])

    # free the program's state before the reference takes the chip
    for _, p in model.named_parameters():
        if p is not None:
            p._data = None
    engine._cache = None
    del engine, model
    gc.collect()
    jax.clear_caches()

    stand_ins = {}
    if sample:
        t_ref = time.perf_counter()
        # ``control`` is calibrate's: the benchmark's own runs never set it
        checks, stand_ins, served = compare(
            sample, config, seed, ctx["limits"], traffic["reference_width"],
            quant=ctx.get("control"))
        harness.log(f"reference: {served} served tokens of {len(sample)} "
                    f"requests in {time.perf_counter() - t_ref:.1f} s")
    else:
        checks = [("served_logit_gap_max", float("nan"),
                   ctx["limits"]["served_logit_gap_max"])]
    return {
        "attempted": wm["attempted"], "failed": wm["failed"],
        "checks": checks, "memory_peak_bytes": mem_peak,
        "window_s": t1 - t0,              # what counters and calls span
        "end_to_end": dict(end_to_end(wm), setup_s=setup_s),
        "compiles_in_window": compiled, "trace": trace,
        "stand_ins": stand_ins, "window": wm,
        "counters": {k: counters1[k] - counters0[k] for k in counters0},
        "kernel_calls": [c for c in spy.calls if t0 <= c[0] <= t1]
        if spy else None,
        "pages": [p for p in pages if t0 <= p[0] <= t1],
    }


def engine_counters(engine):
    return {k: getattr(engine, k) for k in (
        "ragged_steps", "prefill_chunks", "decode_steps",
        "padded_tokens_total", "useful_tokens_total",
        "ragged_prefill_tokens", "ragged_decode_tokens")}

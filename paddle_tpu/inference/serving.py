"""Batched serving engine (reference: the serving tier around
``fused_multi_transformer`` / Paddle Inference's request batching —
SURVEY.md §2.1 "Inference engine", §3.6; VERDICT.md L11 "no serving tier").

TPU-native: requests are micro-batched by prompt length (same-shape
grouping keeps every step a fixed-shape jit-friendly batch), each group
decodes through the paged KV cache + Pallas ``paged_attention`` kernel,
and per-request results are fanned back to the callers. Static batching
with a collect window — the continuous-batching scheduler can replace the
grouping policy without touching the decode path."""
from __future__ import annotations

import os
import queue
import threading
import time
from collections import deque

import numpy as np
import jax
import jax.numpy as jnp

from ..framework.core import Tensor
from ..profiler import request_trace as _rt
from ..profiler import ledger as _ledger
from ..profiler import compile_observatory as _co
from ..profiler import spans as _spans

#: default token budget of one chunked-prefill step (overridable per
#: engine via ``prefill_chunk_tokens=`` or PADDLE_SERVING_CHUNK_TOKENS)
DEFAULT_PREFILL_CHUNK_TOKENS = 256

#: default per-tick token budget of the ragged continuous-batching
#: scheduler (``token_budget=`` / PADDLE_SERVING_TOKEN_BUDGET): every
#: live decode slot contributes 1 token, prefill spans fill the rest
DEFAULT_SERVING_TOKEN_BUDGET = 256

#: default stripe length of the sep-parallel long-context prefill
#: (``sep_stripe_tokens=`` / PADDLE_SEP_STRIPE_TOKENS): every chunk of a
#: long prompt pads to exactly this many tokens, so the ring-prefill
#: program family has ONE chunk shape
DEFAULT_SEP_STRIPE_TOKENS = 512

_TELEMETRY = None      # lazily bound registry families


#: ``SlotPagedKVCache``'s counters of its window groups
_WINDOW_COUNTERS = ("window_blocks_released", "window_blocks_evicted",
                    "prefix_hits_shortened_by_window")


def _token_bucket(n, cap):
    """Pad a ragged tick's packed token batch to the next power of two
    (min 1, capped at the token budget). There is no floor: a
    decode-only tick with two live slots runs a 2-token program — padded-
    token waste on decode-heavy ticks is what the ragged scheduler
    exists to remove."""
    b = 1
    while b < n:
        b *= 2
    return min(b, max(int(cap), 1)) if n <= cap else int(cap)


def _telemetry():
    """Serving latency/occupancy metrics in the unified registry:
    queue-wait (enqueue → admission), TTFT (enqueue → first token),
    per-decode-step and per-token latency histograms, plus active-slot /
    free-slot / free-page gauges for the continuous scheduler."""
    global _TELEMETRY
    if _TELEMETRY is None:
        from ..profiler.telemetry import (get_registry,
                                          DEFAULT_RATIO_BUCKETS)
        r = get_registry()
        _TELEMETRY = {
            "requests": r.counter("paddle_serving_requests_total",
                                  "generate() requests accepted",
                                  labels=("engine",)),
            "queue_wait": r.histogram(
                "paddle_serving_queue_wait_seconds",
                "enqueue -> scheduler admission", labels=("engine",)),
            "ttft": r.histogram("paddle_serving_ttft_seconds",
                                "enqueue -> first generated token",
                                labels=("engine",)),
            "decode_step": r.histogram(
                "paddle_serving_decode_step_seconds",
                "one tick that carried decode tokens (ragged or sep)"),
            "token": r.histogram(
                "paddle_serving_token_latency_seconds",
                "per-token decode latency (step time / active slots)"),
            "tokens": r.counter("paddle_serving_tokens_generated_total",
                                "tokens generated", labels=("engine",)),
            "qdepth": r.gauge("paddle_serving_queue_depth",
                              "requests waiting in the engine queue"),
            "active_reqs": r.gauge(
                "paddle_serving_active_requests",
                "generate() calls currently in flight (queued or "
                "decoding) — the live-load series the metric history "
                "samples", labels=("engine",)),
            "active": r.gauge("paddle_serving_active_slots",
                              "continuous-scheduler slots decoding"),
            "free_slots": r.gauge("paddle_serving_free_slots",
                                  "continuous-scheduler slots free"),
            "free_pages": r.gauge("paddle_serving_free_pages",
                                  "KV-cache pages not backing live context"),
            "prefix_hits": r.counter(
                "paddle_serving_prefix_hits",
                "prompt blocks served from the prefix cache (no prefill)"),
            "prefix_misses": r.counter(
                "paddle_serving_prefix_misses",
                "full prompt blocks that had to prefill"),
            "prefix_cached": r.counter(
                "paddle_serving_prefix_cached_tokens",
                "prompt tokens skipped at prefill via prefix-cache hits"),
            "chunk_util": r.histogram(
                "paddle_serving_chunk_utilization",
                "valid-token fraction of each padded prefill chunk",
                buckets=DEFAULT_RATIO_BUCKETS),
            "pool_occupancy": r.gauge(
                "paddle_serving_page_pool_occupancy",
                "fraction of the shared KV page pool backing live or "
                "prefix-cached context"),
            "budget_util": r.histogram(
                "paddle_serving_token_budget_utilization",
                "useful-token fraction of each padded ragged step "
                "(1 - utilization = padding waste)",
                buckets=DEFAULT_RATIO_BUCKETS),
            "ragged_tokens": r.counter(
                "paddle_serving_ragged_tokens_total",
                "tokens executed through the ragged program family",
                labels=("kind",)),
            "pool_bytes": r.gauge(
                "paddle_serving_page_pool_bytes",
                "dtype-aware KV page-pool bytes (kind=used: pages "
                "backing live or prefix-cached context; kind=capacity: "
                "the whole allocatable pool)", labels=("kind",)),
            "spec_tokens": r.counter(
                "paddle_spec_tokens_total",
                "speculative-decode tokens by fate "
                "(kind=drafted: proposed by the drafter; kind=accepted: "
                "verified equal to the target model's token)",
                labels=("kind",)),
            "spec_accept": r.histogram(
                "paddle_spec_acceptance_ratio",
                "accepted/drafted fraction of each verified span",
                buckets=DEFAULT_RATIO_BUCKETS),
            "prefix_evictions": r.counter(
                "paddle_serving_prefix_evictions_total",
                "prefix-cache evictions by tier (tier=device: LRU "
                "reclaim of an index page, demoted to host when the "
                "tier is on; tier=host: second-level LRU drop — the "
                "prefix is gone and will re-prefill)",
                labels=("tier",)),
            "host_pool_bytes": r.gauge(
                "paddle_kv_host_pool_bytes",
                "host-RAM KV tier bytes (kind=used: resident demoted "
                "pages; kind=capacity: PADDLE_KV_HOST_POOL_MB bound)",
                labels=("kind",)),
            "host_demotions": r.counter(
                "paddle_kv_host_demotions_total",
                "device prefix pages demoted into the host tier"),
            "host_promotions": r.counter(
                "paddle_kv_host_promotions_total",
                "host-tier pages promoted back to device on an "
                "admission hit (prefill work avoided)"),
            "group_pages": r.gauge(
                "paddle_kv_group_pages",
                "pages of each KV page group of a model with window "
                "layers (group=full|window<length>; kind=used|capacity)",
                labels=("group", "kind")),
            "window_events": r.counter(
                "paddle_kv_window_events_total",
                "window page groups: blocks released during a request, "
                "cached window blocks evicted, prefix hits shortened for "
                "want of a window tail (kind=...)", labels=("kind",)),
        }
    return _TELEMETRY


def _engine_state(engine) -> dict:
    """Request-queue / scheduler state snapshot for flight-recorder dumps
    (a post-hang dump must show what the serving tier was doing)."""
    state = {"engine": engine._ENGINE, "running": engine._running,
             "queue_depth": engine._q.qsize()}
    for attr in ("batches_run", "decode_steps", "prefills", "max_batch",
                 "prefill_chunks", "cancelled_rows", "ragged_steps",
                 "compiled_layer_calls", "token_budget", "ragged_prefill_tokens",
                 "ragged_decode_tokens", "padded_tokens_total",
                 "useful_tokens_total", "spec_drafted_tokens",
                 "spec_accepted_tokens", "spec_rounds", "spec_k",
                 "spec_draft_forwards", "spec_draft_ticks",
                 "quantized_linears", "sep_requests"):
        v = getattr(engine, attr, None)
        if v is not None:
            state[attr] = v
    buckets = getattr(engine, "ragged_buckets_used", None)
    if buckets:
        state["ragged_buckets_used"] = sorted(buckets)
    # per-request ages, oldest first: a watchdog dump must NAME the stuck
    # request (trace id + scheduler state), not just the stalled rank
    reqs = list(getattr(engine, "_inflight_reqs", {}).values())
    if reqs:
        now = time.perf_counter()
        ages = []
        for r in reqs:
            rows = getattr(r, "_rows", None)
            ages.append({
                "age_s": round(now - r.t_submit, 3),
                "state": (",".join(sorted({row.state for row in rows}))
                          if rows else "queued"),
                "trace": (r.trace.trace_id if r.trace is not None
                          else None),
                "cancelled": r.cancelled,
            })
        ages.sort(key=lambda a: -a["age_s"])
        state["oldest_request_age_s"] = ages[0]["age_s"]
        state["oldest_request_trace"] = ages[0]["trace"]
        state["request_ages"] = ages[:8]
    else:
        state["oldest_request_age_s"] = 0.0
    if getattr(engine, "enable_spec", None) is not None:
        state["spec_decode"] = engine.enable_spec
    if getattr(engine, "draft_batch", None) is not None:
        state["draft_batch"] = engine.draft_batch
    if getattr(engine, "weight_dtype", None) is not None:
        state["weight_dtype"] = engine.weight_dtype
    cache = getattr(engine, "_cache", None)
    if cache is not None:
        # bytes, not just page counts: the int8-KV capacity win must be
        # visible in a hang dump without arithmetic
        page_nb = cache.page_nbytes
        state["prefix_cache"] = {
            "enabled": cache.enable_prefix_cache,
            "hits": cache.prefix_hits,
            "misses": cache.prefix_misses,
            "cached_tokens": cache.cached_tokens_total,
            "cow_copies": cache.cow_copies,
            "free_pages": cache.free_page_count,
            "used_pages": cache.used_page_count,
            "kv_dtype": cache.kv_dtype,
            "page_nbytes": page_nb,
            "pool_bytes_used": cache.used_page_count * page_nb,
            "pool_bytes_capacity": (cache.num_pages - 1) * page_nb,
            "rollbacks": cache.rollbacks,
            "tokens_rolled_back": cache.tokens_rolled_back,
        }
        hp = getattr(cache, "host_pool", None)
        if hp is not None:
            state["kv_host_tier"] = {
                "enabled": hp.enabled,
                "used_bytes": hp.used_bytes,
                "capacity_bytes": hp.max_bytes,
                "entries": len(hp),
                "demotions": hp.demotions,
                "promotions": hp.promotions,
                "evictions": hp.evictions,
                "device_evictions": cache.prefix_evictions_device,
                "promote_rejects": cache.host_promote_rejects,
            }
        if getattr(cache, "sep_stripes_stored", 0) or \
                getattr(engine, "sep_requests", 0):
            state["sep_prefill"] = {
                "stripes_stored": cache.sep_stripes_stored,
                "chunks": cache.sep_chunks,
                "decode_steps": cache.sep_decode_steps,
            }
    return state


class _Control:
    """A callable posted into the engine queue and executed by the serve
    loop at a tick boundary — the safe point to touch scheduler-owned
    state (the KV cache, slot tables) from another thread. The fleet
    router's disaggregation handoff (export/import of KV pages) rides on
    this."""

    def __init__(self, fn):
        self.fn = fn
        self.done = threading.Event()
        self.result = None
        self.error = None

    def run(self, engine):
        try:
            self.result = self.fn(engine)
        except Exception as e:        # noqa: BLE001 — fanned to the caller
            self.error = e
        finally:
            self.done.set()

    def fail(self, exc):
        if not self.done.is_set():
            self.error = exc
            self.done.set()


class _Request:
    def __init__(self, ids, max_new_tokens, kwargs, trace=None):
        self.ids = np.asarray(ids)
        if self.ids.ndim == 1:
            self.ids = self.ids[None]
        self.max_new_tokens = max_new_tokens
        self.kwargs = kwargs
        self.trace = trace             # request-trace context (or None)
        self.done = threading.Event()
        self.result = None
        self.error = None
        self.cancelled = False         # client gave up (timeout)
        self.t_submit = time.perf_counter()
        self.t_first = None            # first-token time (TTFT)


class ServingEngine:
    """Thread-safe batched ``generate`` front end.

    engine = ServingEngine(model, max_batch_size=8)
    engine.start()
    out = engine.generate(prompt_ids, max_new_tokens=64)   # blocks
    engine.stop()
    """

    _STOP = object()
    _ENGINE = "static"             # telemetry label

    def __init__(self, model, max_batch_size=8, batch_window_s=0.005,
                 use_paged_cache=True, page_size=16):
        # NB: generate() handles eval()/restore per call — constructing an
        # engine must not flip a training model's mode
        self.model = model
        self.max_batch = int(max_batch_size)
        self.window = float(batch_window_s)
        self.use_paged = use_paged_cache
        self.page_size = page_size
        self._q: queue.Queue = queue.Queue()
        self._thread = None
        self._running = False
        self._aborted = False
        self._inflight_reqs: dict = {}   # id(req) -> req (age tracking)
        self.batches_run = 0          # observability/testing

    # -- client API ----------------------------------------------------------
    def run_on_loop(self, fn, timeout=30.0):
        """Run ``fn(engine)`` on the serve-loop thread at the next tick
        boundary and return its result (raising its exception). The only
        safe way to inspect or mutate scheduler-owned state (e.g. the
        slot-paged KV cache) while the engine is serving."""
        if not self._running:
            raise RuntimeError("ServingEngine not started (call start())")
        ctl = _Control(fn)
        self._q.put(ctl)
        if not ctl.done.wait(timeout):
            raise TimeoutError("run_on_loop control not serviced")
        if ctl.error is not None:
            raise ctl.error
        return ctl.result

    def generate(self, input_ids, max_new_tokens=32, timeout=None,
                 trace=None, **kwargs):
        if not self._running:
            raise RuntimeError("ServingEngine not started (call start())")
        ids = input_ids.numpy() if isinstance(input_ids, Tensor) \
            else np.asarray(input_ids)
        # mint a request trace at direct engine admission (fleet-less
        # use); the router passes its own ctx through ``trace=`` and
        # stays the owner (it finishes the trace at delivery)
        trace_owned = False
        if trace is None and _rt.is_enabled():
            trace = _rt.start_request(
                source=self._ENGINE, prompt_tokens=int(ids.shape[-1]),
                max_new_tokens=int(max_new_tokens))
            trace_owned = True
        req = _Request(ids, max_new_tokens, kwargs, trace=trace)
        tele = _telemetry()
        tele["requests"].inc(engine=self._ENGINE)
        self._inflight_reqs[id(req)] = req
        tele["active_reqs"].set(len(self._inflight_reqs),
                                engine=self._ENGINE)
        self._q.put(req)
        tele["qdepth"].set(self._q.qsize())
        deadline = None if timeout is None else time.monotonic() + timeout
        try:
            while not req.done.is_set():
                remaining = (None if deadline is None
                             else deadline - time.monotonic())
                if remaining is not None and remaining <= 0:
                    # the scheduler must not keep decoding for a client
                    # that gave up: pending rows are skipped at admission,
                    # active slots/pages freed at the next step boundary
                    req.cancelled = True
                    _rt.add_event(trace, "timeout", engine=self._ENGINE)
                    if trace_owned:
                        _rt.finish_request(trace, status="timeout")
                    raise TimeoutError("generate timed out")
                th = self._thread
                worker_alive = th is not None and th.is_alive()
                if not self._running and not worker_alive:
                    # raced with stop() AND the worker (whose exit path
                    # fails every still-queued request) is gone: our
                    # request provably missed the drain — fail it here
                    # rather than hang
                    if not req.done.is_set():
                        req.error = RuntimeError("ServingEngine stopped")
                        req.done.set()
                    break
                req.done.wait(0.5 if remaining is None
                              else min(0.5, remaining))
            if req.error is not None:
                _rt.add_event(trace, "engine_error",
                              error=type(req.error).__name__)
                if trace_owned:
                    _rt.finish_request(trace, status="error")
                raise req.error
            if trace_owned:
                # thread the delivered-token-stream digest into the
                # trace's terminal span (fleet-less attestation record)
                dg = (_ledger.stream_digest(trace.trace_id)
                      if _ledger.is_enabled() and trace is not None
                      else None)
                _rt.finish_request(trace, status="ok",
                                   **({"token_digest": dg} if dg else {}))
            return Tensor(req.result)
        finally:
            self._inflight_reqs.pop(id(req), None)
            tele["active_reqs"].set(len(self._inflight_reqs),
                                    engine=self._ENGINE)

    # -- lifecycle -----------------------------------------------------------
    def start(self):
        if self._running:
            return self
        # drain stale stop tokens from a previous stop() so the new
        # worker doesn't die on arrival
        try:
            while True:
                item = self._q.get_nowait()
                if item is not self._STOP and item is not None:
                    self._q.put(item)
                    break
        except queue.Empty:
            pass
        self._running = True
        self._aborted = False
        import weakref
        from ..profiler import flight_recorder as _flight
        self._flight_key = f"serving_{self._ENGINE}_{id(self):x}"
        wr = weakref.ref(self)     # the provider registry must not keep a
        #                            stopped-but-unstopped engine alive
        _flight.register_state_provider(
            self._flight_key,
            lambda: _engine_state(wr()) if wr() is not None else {})
        if not getattr(self, "_exporter_managed", False):
            # standalone engine: its own telemetry endpoint when the
            # plane is on (a router-fronted engine's exporter is owned
            # by the router, named by replica id — see _exporter_managed)
            from ..profiler import exporter as _exp
            self._exporter = _exp.maybe_start_exporter(
                instance=os.environ.get("PADDLE_TELEMETRY_INSTANCE")
                or f"{self._ENGINE}-{os.getpid()}")
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()
        return self

    def stop(self):
        if not self._running and self._thread is None:
            return
        self._running = False
        self._q.put(self._STOP)
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None
        # unregister AFTER the drain: a watchdog dump taken while the
        # engine winds down must still see its state, and repeated
        # start/stop (the fleet router's drain/rejoin cycle) must never
        # accumulate stale providers in dumps
        key = getattr(self, "_flight_key", None)
        if key is not None:
            from ..profiler import flight_recorder as _flight
            _flight.unregister_state_provider(key)
            self._flight_key = None
        exp = getattr(self, "_exporter", None)
        if exp is not None:
            exp.stop()
            self._exporter = None

    def abort(self):
        """Hard stop: fail every queued AND in-flight request instead of
        draining decodes to completion — the fleet tier's simulated
        replica death (a real process kill has no drain either)."""
        self._aborted = True
        self.stop()

    # -- scheduler -----------------------------------------------------------
    def _collect(self):
        """Block for one request, then drain compatible ones within the
        window. Groups by (prompt_len, max_new_tokens, kwargs) — equal
        shapes keep the decode batch fixed-shape."""
        first = self._q.get()
        while isinstance(first, _Control):
            first.run(self)
            first = self._q.get()
        if first is self._STOP or first is None:
            return None
        group = [first]
        key = (first.ids.shape[1], first.max_new_tokens,
               tuple(sorted(first.kwargs.items())))
        deadline = time.monotonic() + self.window
        leftovers = []
        try:
            while sum(r.ids.shape[0] for r in group) < self.max_batch:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                try:
                    nxt = self._q.get(timeout=remaining)
                except queue.Empty:
                    break
                if isinstance(nxt, _Control):
                    nxt.run(self)
                    continue
                if nxt is self._STOP or nxt is None:
                    self._q.put(self._STOP)  # re-post the stop token
                    break
                k = (nxt.ids.shape[1], nxt.max_new_tokens,
                     tuple(sorted(nxt.kwargs.items())))
                if k == key and (sum(r.ids.shape[0] for r in group)
                                 + nxt.ids.shape[0]) <= self.max_batch:
                    group.append(nxt)
                else:
                    leftovers.append(nxt)
        finally:
            for r in leftovers:             # incompatible: next rounds
                self._q.put(r)
        return group

    def _loop(self):
        try:
            self._serve()
        finally:
            # fail any stranded requests (queued behind the stop token /
            # leftovers re-queued after it) instead of blocking callers
            try:
                while True:
                    item = self._q.get_nowait()
                    if isinstance(item, _Request):
                        item.error = RuntimeError("ServingEngine stopped")
                        item.done.set()
                    elif isinstance(item, _Control):
                        item.fail(RuntimeError("ServingEngine stopped"))
            except queue.Empty:
                pass

    def _serve(self):
        tele = _telemetry()
        while self._running:
            group = self._collect()
            if group is None:
                break
            # a timed-out client already raised; don't burn a batch on it
            group = [r for r in group if not r.cancelled]
            if not group:
                continue
            t_admit = time.perf_counter()
            for r in group:
                tele["queue_wait"].observe(t_admit - r.t_submit,
                                           engine=self._ENGINE)
                _rt.add_span(r.trace, "queue_wait", t0=r.t_submit,
                             dur=t_admit - r.t_submit, engine=self._ENGINE)
            try:
                batch = np.concatenate([r.ids for r in group], axis=0)
                kwargs = dict(group[0].kwargs)
                if self.use_paged:
                    kwargs.setdefault("use_paged_cache", True)
                    kwargs.setdefault("page_size", self.page_size)
                out = self.model.generate(
                    Tensor(batch), max_new_tokens=group[0].max_new_tokens,
                    **kwargs)
                arr = np.asarray(out.numpy())
                self.batches_run += 1
                prompt_len = group[0].ids.shape[1]
                # the static window batcher emits the whole completion at
                # once, so first-token time == completion time
                t_done = time.perf_counter()
                for r in group:
                    r.t_first = t_done
                    tele["ttft"].observe(t_done - r.t_submit,
                                         engine=self._ENGINE)
                    # the window batcher emits the whole completion at
                    # once: one batch span + one token mark per request
                    _rt.add_span(r.trace, "batch_generate", t0=t_admit,
                                 dur=t_done - t_admit,
                                 batch=len(group), engine=self._ENGINE)
                    _rt.note_token(r.trace, t_done)
                tele["tokens"].inc(
                    (arr.shape[1] - prompt_len) * arr.shape[0],
                    engine=self._ENGINE)
                eos = kwargs.get("eos_token_id")
                row = 0
                for r in group:
                    n = r.ids.shape[0]
                    res = arr[row:row + n]
                    if eos is not None and arr.shape[1] > prompt_len:
                        # trim co-batch eos padding: a request's output
                        # must not depend on its batch-mates' lengths
                        gen = res[:, prompt_len:]
                        hits = np.argmax(gen == eos, axis=1)
                        has = (gen == eos).any(axis=1)
                        stop = int(np.max(np.where(has, hits + 1,
                                                   gen.shape[1])))
                        res = res[:, :prompt_len + stop]
                    r.result = res
                    row += n
                    r.done.set()
            except Exception as e:          # fan the failure out, keep serving
                for r in group:
                    r.error = e
                    r.done.set()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()


class _Row:
    """One sequence of a request inside the continuous scheduler."""

    def __init__(self, req, ids, row_idx=0):
        self.req = req
        self.row_idx = int(row_idx)          # row within the request
        self.prompt = np.asarray(ids)        # [s]
        self.generated: list = []
        self.done = False
        self.state = "queued"                # queued -> prefill -> decode
        self.sep = False                     # long-context sep-ring row
        self._key_base = None                # seeded-sampling PRNG base


class ContinuousServingEngine:
    """Continuous-batching serving engine (reference: the vLLM-style
    scheduler the serving tier around ``fused_multi_transformer`` targets;
    VERDICT.md round-2 item 8 — per-step admit/evict over the paged KV
    cache, replacing :class:`ServingEngine`'s static same-shape windows).

    TPU-native scheduling: admission is NON-BLOCKING — it only maps a
    request onto a free slot of a :class:`SlotPagedKVCache` (prompt
    blocks that hit the prefix index reuse already-filled pages with no
    model work at all). Each tick then packs up to ``token_budget``
    tokens into ONE flat batch — every live decode slot's single token
    plus as many prefill tokens of the uncached prompt suffixes as fit
    (per-span cap ``prefill_chunk_tokens``) — and runs them through the
    single ragged paged-attention program family (Ragged Paged
    Attention, arxiv 2604.15464), so a long prompt never
    head-of-line-blocks active decodes. Sequences of different prompt
    lengths and decode budgets share every step, a finished sequence's
    slot is reused immediately, and the batch is padded to a bounded
    bucket set, so the whole mixed prefill+decode workload compiles a
    small fixed family of programs.

    engine = ContinuousServingEngine(model, max_batch_size=8)
    engine.start()
    out = engine.generate(prompt_ids, max_new_tokens=64)   # blocks
    engine.stop()

    Prefix caching defaults on; disable with ``enable_prefix_cache=False``
    or ``PADDLE_SERVING_PREFIX_CACHE=0`` (legacy per-request prefill
    behavior, still chunked). ``prefill_chunk_tokens`` >= ``max_len``
    restores monolithic prefill.
    """

    _STOP = ServingEngine._STOP
    _ENGINE = "continuous"         # telemetry label

    def __init__(self, model, max_batch_size=8, page_size=16, max_len=2048,
                 pad_token_id=0, prefill_chunk_tokens=None,
                 enable_prefix_cache=None, num_pages=None,
                 token_budget=None, kv_dtype=None,
                 spec_decode=None, spec_k=None, drafter=None,
                 draft_model=None, weight_dtype=None, draft_batch=None,
                 host_pool_mb=None, sep_prefill=None,
                 sep_stripe_tokens=None, sep_threshold_tokens=None,
                 window_num_pages=None):
        self.model = model
        # end-to-end int8 weights (PADDLE_WEIGHT_DTYPE=int8): every
        # nn.Linear swaps its weight for (int8, per-channel scale) and
        # forwards through the Pallas int8 GEMM — composes with
        # kv_dtype="int8" for a fully-quantized serving config
        if weight_dtype is None:
            weight_dtype = os.environ.get("PADDLE_WEIGHT_DTYPE") or None
        self.weight_dtype = str(weight_dtype).lower() if weight_dtype \
            else None
        if self.weight_dtype not in (None, "int8"):
            raise ValueError(f"unsupported weight_dtype "
                             f"{self.weight_dtype!r} (expected 'int8')")
        if self.weight_dtype == "int8":
            from ..quantization import quantize_linears
            self.quantized_linears = quantize_linears(model)
        else:
            self.quantized_linears = 0
        self.max_batch = int(max_batch_size)
        self.page_size = int(page_size)
        self.max_len = int(max_len)
        self.pad_token_id = int(pad_token_id)
        # layers that keep a state a slot instead of pages
        # (``model.kv_state_layers``: linear attention): the cache holds
        # their arrays beside the page pool, and the prefix cache is off
        # (a hit would need a snapshot of the state; asking for it is
        # refused by the cache)
        self.state_layers = int(getattr(model, "kv_state_layers", 0) or 0)
        if enable_prefix_cache is None and self.state_layers:
            enable_prefix_cache = False
        if enable_prefix_cache is None:
            enable_prefix_cache = os.environ.get(
                "PADDLE_SERVING_PREFIX_CACHE", "1") != "0"
        self.enable_prefix_cache = bool(enable_prefix_cache)
        if prefill_chunk_tokens is None:
            prefill_chunk_tokens = int(os.environ.get(
                "PADDLE_SERVING_CHUNK_TOKENS",
                str(DEFAULT_PREFILL_CHUNK_TOKENS)))
        self.chunk_tokens = max(int(prefill_chunk_tokens), 1)
        if token_budget is None:
            token_budget = int(os.environ.get(
                "PADDLE_SERVING_TOKEN_BUDGET",
                str(DEFAULT_SERVING_TOKEN_BUDGET)))
        # every live decode slot is entitled to its 1 token per tick, so
        # the effective budget never starves decode — clamping here (not
        # per tick) keeps the compiled bucket set fixed for the engine's
        # lifetime
        self.token_budget = max(int(token_budget), self.max_batch, 1)
        self.num_pages = num_pages
        self.kv_dtype = kv_dtype       # None => cache reads PADDLE_KV_DTYPE
        # speculative decoding (PADDLE_SPEC_DECODE=1): a drafter proposes
        # up to spec_k tokens per live decode slot each tick; the ragged
        # forward verifies them as one q_len=k+1 span and the scheduler
        # keeps the longest matching prefix (greedy acceptance => output
        # bit-identical to plain greedy).
        if spec_decode is None:
            spec_decode = os.environ.get("PADDLE_SPEC_DECODE", "0") == "1"
        self.enable_spec = bool(spec_decode)
        if spec_k is None:
            from .speculative import DEFAULT_SPEC_K
            spec_k = int(os.environ.get("PADDLE_SPEC_K",
                                        str(DEFAULT_SPEC_K)))
        self.spec_k = max(int(spec_k), 1)
        self._drafter = None
        if self.enable_spec:
            if drafter is None:
                from .speculative import make_drafter
                drafter = make_drafter(draft_model=draft_model)
            self._drafter = drafter
        # batched drafting (PADDLE_SPEC_DRAFT_BATCH, default on): one
        # padded draft forward per tick for every live decode slot
        # instead of one forward per slot per drafted token — proposals
        # stay bit-identical (greedy + causal right-padding), only the
        # forward count drops
        if draft_batch is None:
            draft_batch = os.environ.get(
                "PADDLE_SPEC_DRAFT_BATCH", "1") != "0"
        self.draft_batch = bool(draft_batch)
        # tiered KV: the engine owns ONE host pool across cache rebuilds
        # (a serve-loop crash must not flush the warm tier); 0 MB keeps
        # the tier off and eviction behavior exactly legacy
        from ..models.generation import HostKVPool
        if host_pool_mb is None:
            host_pool_mb = float(os.environ.get(
                "PADDLE_KV_HOST_POOL_MB", "0") or 0)
        self.host_pool_mb = float(host_pool_mb)
        if self.host_pool_mb < 0:
            raise ValueError(f"host_pool_mb must be >= 0, got "
                             f"{self.host_pool_mb}")
        self._host_pool = HostKVPool(self.host_pool_mb)
        self._kv_tier_seen: dict = {}   # counter baselines for telemetry
        # sep-parallel long-context prefill (PADDLE_SEP_PREFILL=1):
        # prompts past the threshold are chunked into fixed
        # PADDLE_SEP_STRIPE_TOKENS stripes attended with the
        # ring-attention schedule — the device page pool only ever holds
        # the decode tail, so a prompt far larger than the pool serves
        if sep_prefill is None:
            sep_prefill = os.environ.get("PADDLE_SEP_PREFILL", "0") == "1"
        self.sep_prefill_enabled = bool(sep_prefill)
        if sep_stripe_tokens is None:
            sep_stripe_tokens = int(os.environ.get(
                "PADDLE_SEP_STRIPE_TOKENS", str(DEFAULT_SEP_STRIPE_TOKENS)))
        self.sep_stripe = int(sep_stripe_tokens)
        if sep_threshold_tokens is None:
            sep_threshold_tokens = int(os.environ.get(
                "PADDLE_SEP_THRESHOLD_TOKENS", "0"))
        self.sep_threshold = int(sep_threshold_tokens)
        self.sep_requests = 0
        if self.sep_prefill_enabled:
            if self.sep_stripe <= 0 or self.sep_stripe % self.page_size:
                raise ValueError(
                    f"sep_stripe_tokens {self.sep_stripe} must be a "
                    f"positive multiple of page_size {self.page_size}")
            kv = self.kv_dtype
            if kv is None:
                kv = os.environ.get("PADDLE_KV_DTYPE", "auto")
            if str(kv).lower() == "int8":
                raise ValueError("sep prefill requires native KV pages "
                                 "(kv_dtype=int8 is unsupported)")
        # layer groups: a model some of whose layers attend a sliding
        # window (``model.kv_layer_windows``: None or a length a layer)
        # gets one page group a distinct window beside the full layers'
        # (``SlotPagedKVCache``); ``window_num_pages`` sizes it; the default
        # holds every slot's window and one step's chunk
        self.kv_windows = sorted({int(w) for w in getattr(
            model, "kv_layer_windows", ()) if w})
        self.window_groups = self._window_groups(window_num_pages)
        if self.state_layers:
            self._refuse_with("layers that keep a state a slot")
        self.spec_drafted_tokens = 0
        self.spec_accepted_tokens = 0
        self.spec_rounds = 0           # verify spans with >= 1 draft
        self.spec_draft_forwards = 0   # draft-model forwards observed
        self.spec_draft_ticks = 0      # ticks that ran the drafter
        self._q: queue.Queue = queue.Queue()
        self._thread = None
        self._running = False
        self._aborted = False
        self._inflight_reqs = {}       # id(req) -> req (age tracking)
        self._cache = None
        # observability (and the "beats static batching" proof in tests)
        self.decode_steps = 0
        self.prefills = 0              # rows admitted (one per sequence)
        self.prefill_chunks = 0        # chunk forwards run
        self.cancelled_rows = 0
        self.ragged_steps = 0          # ragged packed forwards run
        # decoder layers of those forwards that ran as compiled programs
        # around the kernel entry (models/llama.py); 0 on a model that
        # runs its layers eagerly
        self.compiled_layer_calls = 0
        # prompt tokens of admitted requests, and those of them that the
        # prefix cache served (``cache.assign``): host counts, also on
        # ``serve/tick`` as the tick's deltas
        self.prompt_tokens_admitted = 0
        self.prompt_tokens_cached = 0
        # device counters a model left on the cache during a tick's
        # forward (``cache.add_step_counters``; e.g. tokens routed to each
        # held expert), summed over ticks and layers: name -> numpy array.
        # They come back inside the tick's one sync
        self.model_counters: dict = {}
        self.ragged_prefill_tokens = 0
        self.ragged_decode_tokens = 0
        # padded-vs-useful accounting (the bench's waste-ratio metric):
        # padded counts every token position a compiled program
        # processed, useful only the real ones
        self.padded_tokens_total = 0
        self.useful_tokens_total = 0
        #: bucket sizes actually compiled — the inventory guard asserts
        #: this stays inside :meth:`declared_token_buckets`
        self.ragged_buckets_used: set = set()
        # scheduling trace for liveness tests / debugging: ("chunk",
        # slot, n_valid, done) and ("decode", n_active) events in order,
        # both per packed tick
        self.events: deque = deque(maxlen=4096)
        self._declare_programs()

    def _window_groups(self, window_num_pages):
        """``{window: pages}`` for the cache, or None for a model of full
        layers alone; ``window_num_pages`` is ONE count, given to each
        distinct window's group (no model here has two). What cannot yet
        serve a windowed model refuses it here, loudly (docs/SERVING.md
        lists them)."""
        if not self.kv_windows:
            if window_num_pages is not None:
                raise ValueError("window_num_pages for a model that "
                                 "declares no window layer")
            return None
        self._refuse_with(f"window layers ({self.kv_windows})")
        pages_per_seq = -(-self.max_len // self.page_size)
        out = {}
        for w in self.kv_windows:
            n = window_num_pages
            if n is None:
                a_slot = min(pages_per_seq, -(-(
                    w + min(self.chunk_tokens, self.token_budget))
                    // self.page_size) + 2)
                n = self.max_batch * a_slot + 1
            out[w] = int(n)
        return out

    def _refuse_with(self, what):
        """What cannot yet serve a model with ``what`` refuses it here,
        loudly (docs/SERVING.md lists them)."""
        kv = self.kv_dtype
        if kv is None:
            kv = os.environ.get("PADDLE_KV_DTYPE", "auto")
        refused = [name for name, on in (
            ("speculative decoding (rollback)", self.enable_spec),
            ("sep striping", self.sep_prefill_enabled),
            ("the host KV tier", self.host_pool_mb > 0),
            ("int8 KV pages", str(kv).lower() == "int8")) if on]
        if refused:
            raise NotImplementedError(
                f"a model with {what} is not served with: "
                f"{', '.join(refused)}")

    def declared_token_buckets(self):
        """The ragged scheduler's full compiled-shape family: every tick's
        flat token batch is padded to one of these sizes, so the number
        of compiled programs is bounded for the engine's lifetime
        regardless of traffic mix (enforced by tools/check_inventory.py's
        serving-program guard)."""
        out, b = set(), 1
        while b < self.token_budget:
            out.add(b)
            b *= 2
        out.add(self.token_budget)
        return out

    def declared_kernel_buckets(self, latent=False, window=None):
        """The q-block attention kernel's compiled-shape family: one
        program a (token bucket, job bucket). A tick's flat job list, one
        job a (q-block, KV page) pair
        (``ragged_paged_attention.qblock_job_list``), reaches the device
        in an array of a bucketed length (``job_bucket``: 1,024, 8,192,
        ...), and how long the list is follows the contexts in flight,
        not the tick's token count. Returns ``{token bucket: [job
        buckets]}``: every bucket a tick within this engine's limits can
        reach (``max_batch_size`` sequences of at most ``max_len`` tokens,
        pages shared or not), which :meth:`warmup_programs` compiles.
        ``latent``: the ladder of a latent (one-pool) layer's kernel,
        whose lists are padded to powers of two from 64. ``window``: the
        ladder of a sliding-window layer, whose (q-block, sequence) pairs
        walk the window's pages at the most."""
        from ..ops.pallas.ragged_paged_attention import (
            _qblock_rows, job_buckets, window_pages)
        pages_per_seq = window_pages(window, _qblock_rows(), self.page_size,
                                     -(-self.max_len // self.page_size))
        return {b: job_buckets(b, _qblock_rows(), self.max_batch,
                               pages_per_seq, latent=latent)
                for b in sorted(self.declared_token_buckets())}

    def declared_draft_buckets(self):
        """The batched drafter's compiled-shape family: (rows, width)
        both pow2-bucketed (:func:`speculative._pow2_bucket`), rows up
        to the engine's slot count, width capped at the draft window.
        Returns ``(rows_buckets, width_buckets)`` or None when batched
        drafting is off / the drafter has no batch path."""
        if not (self.enable_spec and self.draft_batch
                and hasattr(self._drafter, "propose_batch")):
            return None
        from .speculative import _pow2_bucket
        rows, b = set(), 1
        while b < _pow2_bucket(self.max_batch):
            rows.add(b)
            b *= 2
        rows.add(_pow2_bucket(self.max_batch))
        window = int(getattr(self._drafter, "window", 64))
        widths, b = set(), 1
        while b < window:
            widths.add(b)
            b *= 2
        widths.add(window)
        return rows, widths

    def _static_args(self):
        """Static (non-shape) parts of every serving program signature:
        a dtype flip recompiles the whole family, and the observatory's
        cause string must say so (``static arg `weight_dtype`
        int8→native``)."""
        kv = self.kv_dtype
        if kv is None:
            kv = os.environ.get("PADDLE_KV_DTYPE", "auto")
        kv = "native" if str(kv).lower() == "auto" else str(kv).lower()
        return {"weight_dtype": _co.static_arg(self.weight_dtype
                                               or "native"),
                "kv_dtype": _co.static_arg(kv)}

    def _ragged_signature(self, padded):
        sig = {"tokens": _co.tensor_arg((int(padded),), "int64")}
        sig.update(self._static_args())
        return sig

    def _kernel_signature(self, padded, jobs):
        sig = {"tokens": _co.tensor_arg((int(padded),), "int64"),
               "jobs": _co.tensor_arg((int(jobs),), "int32")}
        sig.update(self._static_args())
        return sig

    def _sep_max_stripes(self):
        return self.max_len // max(self.sep_stripe, 1)

    def _sep_tail_buckets(self):
        """pow2 tail-page windows a sep decode step can compile with
        (the cache always gathers the pure power of two)."""
        import math as _math
        pages_per_seq = -(-self.max_len // self.page_size)
        out, b = set(), 1
        while b < pages_per_seq:
            out.add(b)
            b *= 2
        out.add(b)
        return out

    def _sep_prefill_signature(self, n_stripes):
        # the chunk shape is fixed at the stripe length; the unrolled
        # ring loop makes the STRIPE COUNT part of the program identity
        sig = {"tokens": _co.tensor_arg((self.sep_stripe,), "int64"),
               "stripes": _co.tensor_arg((int(n_stripes),), "int32")}
        sig.update(self._static_args())
        return sig

    def _sep_decode_signature(self, n_stripes, tail_pages):
        sig = {"tokens": _co.tensor_arg((1,), "int64"),
               "stripes": _co.tensor_arg((int(n_stripes),), "int32"),
               "tail_pages": _co.tensor_arg((int(tail_pages),), "int32")}
        sig.update(self._static_args())
        return sig

    def _host_promote_signature(self):
        # one page's writeback is the compiled unit (fixed page shape)
        sig = {"pages": _co.tensor_arg((1,), "int32")}
        sig.update(self._static_args())
        return sig

    def _declare_programs(self):
        """Declare this engine's program families (bucket sets + warmup
        entries) with the compile observatory, so serve-time observations
        can be checked against the inventory and causes can name the
        offending bucket. Declaration is construction-time bookkeeping —
        the hot-path gate stays :func:`compile_observatory.is_enabled`."""
        import weakref
        ref = weakref.ref(self)

        def warm(names):
            eng = ref()
            return eng.warmup_programs(families=names) if eng else {}

        _co.declare_family(
            "serving.ragged",
            buckets={"tokens": sorted(self.declared_token_buckets())},
            warmup=lambda: warm(("serving.ragged",)))
        # both ladders: which kind of layer the model has shows only once
        # a forward has built its pools
        kernel = self.declared_kernel_buckets()
        ladders = (kernel, self.declared_kernel_buckets(latent=True)) + tuple(
            self.declared_kernel_buckets(window=w) for w in self.kv_windows)
        _co.declare_family(
            "serving.ragged_attention",
            buckets={"tokens": sorted(kernel),
                     "jobs": sorted({j for fam in ladders
                                     for js in fam.values()
                                     for j in js})},
            warmup=lambda: warm(("serving.ragged_attention",)))
        draft = self.declared_draft_buckets()
        if draft is not None:
            rows, widths = draft
            _co.declare_family(
                "spec.draft_batch",
                buckets={"tokens": {0: sorted(rows), 1: sorted(widths)}},
                warmup=lambda: warm(("spec.draft_batch",)))
        if self.sep_prefill_enabled:
            max_stripes = self._sep_max_stripes()
            _co.declare_family(
                "serving.sep_prefill",
                buckets={"tokens": [self.sep_stripe],
                         "stripes": list(range(max_stripes + 1))},
                warmup=lambda: warm(("serving.sep_prefill",)))
            _co.declare_family(
                "serving.sep_decode",
                buckets={"tokens": [1],
                         "stripes": list(range(max_stripes + 1)),
                         "tail_pages": sorted(self._sep_tail_buckets())},
                warmup=lambda: warm(("serving.sep_decode",)))
        if self._host_pool.enabled:
            _co.declare_family(
                "kv.host_promote", buckets={"pages": [1]},
                warmup=lambda: warm(("kv.host_promote",)))

    def warmup_programs(self, families=None):
        """Pre-compile every declared signature of this engine's program
        families and record the observations, so steady-state traffic
        sees ZERO observatory misses (and pays no first-request compile
        tax). Runs each declared bucket shape once through the real
        forward path on a scratch KV cache; call before :meth:`start`
        (or through :meth:`run_on_loop` on a live engine). Returns
        ``{family: wall_seconds}``."""
        from ..autograd.tape import no_grad
        from ..models.generation import SlotPagedKVCache
        names = None if families is None else set(families)

        def want(n):
            return names is None or n in names

        out = {}
        was_training = self.model.training
        self.model.eval()
        try:
            with no_grad():
                cache = SlotPagedKVCache(
                    self.max_batch, page_size=self.page_size,
                    max_len=self.max_len, num_pages=self.num_pages,
                    enable_prefix_cache=False, kv_dtype=self.kv_dtype,
                    allow_page_overcommit=self.sep_prefill_enabled,
                    window_groups=self.window_groups,
                    state_layers=bool(self.state_layers))
                kernel = want("serving.ragged_attention")
                if want("serving.ragged") or kernel:
                    t0 = time.perf_counter()
                    t_kernel = 0.0
                    for b in sorted(self.declared_token_buckets()):
                        flat = np.full(b, self.pad_token_id, np.int64)
                        pos = np.zeros(b, np.int32)
                        cache.begin_ragged([(0, 0, 1)])
                        cache.attention_calls = []
                        t_run = time.perf_counter()
                        self.model.forward(Tensor(flat[None]), cache=cache,
                                           position_ids=pos)
                        if want("serving.ragged"):
                            _co.observe("serving.ragged",
                                        self._ragged_signature(b),
                                        seconds=time.perf_counter() - t_run)
                        calls, cache.attention_calls = \
                            cache.attention_calls, None
                        t_run = time.perf_counter()
                        if kernel:
                            self._warm_attention(cache, b, calls)
                        t_kernel += time.perf_counter() - t_run
                        cache.free(0)
                        if self.state_layers:
                            self._warm_state_spans(cache, b)
                    if want("serving.ragged"):
                        out["serving.ragged"] = \
                            time.perf_counter() - t0 - t_kernel
                    if kernel:
                        out["serving.ragged_attention"] = t_kernel
                draft = self.declared_draft_buckets()
                if draft is not None and want("spec.draft_batch"):
                    rows, widths = draft
                    t0 = time.perf_counter()
                    for r in sorted(rows):
                        for w in sorted(widths):
                            batch = np.zeros((r, w), np.int64)
                            t_run = time.perf_counter()
                            self._drafter.model.forward(Tensor(batch))
                            _co.observe(
                                "spec.draft_batch",
                                {"tokens": _co.tensor_arg((r, w), "int64")},
                                seconds=time.perf_counter() - t_run)
                    out["spec.draft_batch"] = time.perf_counter() - t0
                if self.sep_prefill_enabled and (
                        want("serving.sep_prefill")
                        or want("serving.sep_decode")):
                    # one full long-context span walks the ring-prefill
                    # family through every stripe count, then one decode
                    # step compiles the stripes+tail read
                    t0 = time.perf_counter()
                    sep_cache = SlotPagedKVCache(
                        self.max_batch, page_size=self.page_size,
                        max_len=self.max_len, num_pages=self.num_pages,
                        enable_prefix_cache=False, kv_dtype=self.kv_dtype,
                        allow_page_overcommit=True)
                    stripe = self.sep_stripe
                    n = min(self.max_len - 2,
                            self._sep_max_stripes() * stripe
                            + max(stripe // 2, 1))
                    sep_cache.assign_sep(0, n, stripe)
                    pos0 = 0
                    while pos0 < n:
                        nv = min(stripe, n - pos0)
                        ns = len(sep_cache._sep[0]["stripes"])
                        chunk = np.full(stripe, self.pad_token_id,
                                        np.int64)
                        pos = np.minimum(
                            np.arange(pos0, pos0 + stripe,
                                      dtype=np.int32), pos0 + nv - 1)
                        sep_cache.begin_sep_prefill(0, nv)
                        t_run = time.perf_counter()
                        self.model.forward(Tensor(chunk[None]),
                                           cache=sep_cache,
                                           position_ids=pos)
                        if want("serving.sep_prefill"):
                            _co.observe(
                                "serving.sep_prefill",
                                self._sep_prefill_signature(ns),
                                seconds=time.perf_counter() - t_run)
                        pos0 += nv
                    if want("serving.sep_prefill"):
                        out["serving.sep_prefill"] = \
                            time.perf_counter() - t0
                    if want("serving.sep_decode"):
                        t0 = time.perf_counter()
                        view = sep_cache.sep_view(0)
                        sep_cache.begin_sep_decode(0)
                        cur = np.full((1, 1), self.pad_token_id, np.int64)
                        dpos = np.asarray([[int(sep_cache.lens[0])]],
                                          np.int32)
                        t_run = time.perf_counter()
                        self.model.forward(Tensor(cur), cache=sep_cache,
                                           position_ids=dpos)
                        _co.observe(
                            "serving.sep_decode",
                            self._sep_decode_signature(
                                view["stripes"], view["tail_pages"]),
                            seconds=time.perf_counter() - t_run)
                        out["serving.sep_decode"] = \
                            time.perf_counter() - t0
                    sep_cache.free(0)
                if self._host_pool.enabled and want("kv.host_promote"):
                    # demote -> promote roundtrip on a scratch cache and
                    # a scratch pool (the live tier must stay untouched)
                    from ..models.generation import HostKVPool
                    t0 = time.perf_counter()
                    hcache = SlotPagedKVCache(
                        1, page_size=self.page_size, max_len=self.max_len,
                        enable_prefix_cache=True, kv_dtype=self.kv_dtype,
                        host_pool=HostKVPool(max(self.host_pool_mb, 64)))
                    n = 2 * self.page_size
                    prompt = np.zeros(n, np.int64)
                    hcache.assign(0, prompt)
                    hcache.begin_ragged([(0, 0, n)])
                    self.model.forward(
                        Tensor(prompt[None]), cache=hcache,
                        position_ids=np.arange(n, dtype=np.int32))
                    hcache.commit_prefix(0)
                    hcache.free(0)
                    while hcache._evict_lru():
                        pass
                    t_run = time.perf_counter()
                    hcache.assign(0, prompt)   # host hit -> promotion
                    _co.observe("kv.host_promote",
                                self._host_promote_signature(),
                                seconds=time.perf_counter() - t_run)
                    hcache.free(0)
                    out["kv.host_promote"] = time.perf_counter() - t0
        finally:
            if was_training:
                self.model.train()
        return out

    def _warm_state_spans(self, cache, tokens):
        """A model with a state a slot runs a tick's longer spans through
        another kernel than its one-token rows, over a job list padded to
        one of two lengths: one forward of ONE span of ``tokens`` tokens
        and one of as many two-token spans as a tick can hold reach both
        (the one-token warm-up forward met neither)."""
        if tokens < 2:
            return
        many = min(self.max_batch, tokens // 2)
        for spans in [[(0, 0, tokens)]] + (
                [[(i, 2 * i, 2) for i in range(many)]] if many > 1 else []):
            cache.begin_ragged(spans)
            self.model.forward(
                Tensor(np.full(tokens, self.pad_token_id, np.int64)[None]),
                cache=cache, position_ids=np.zeros(tokens, np.int32))
            for slot, _, _ in spans:
                cache.free(slot)

    def _warm_attention(self, cache, tokens, calls):
        """Compile the attention kernel of a ``tokens``-token tick for
        every job bucket declared for it: the warm-up forward that has just
        run on ``cache`` met the smallest one only. ``calls`` are that
        forward's kernel calls (``cache.attention_calls``); one layer of
        each distinct shape is called again, through the cache and the
        public op, with descriptors that make a list of each length (a
        layer of one pool is a latent one, with that kernel's ladder)."""
        from ..ops.pallas.ragged_paged_attention import (
            _qblock_rows, warm_descriptors)
        pages_per_seq = -(-self.max_len // self.page_size)
        seen = set()
        for layer, shape, dtype, sm_scale, value_dim in calls:
            pools = cache._pools[id(layer)]
            key = (shape, str(dtype), sm_scale, value_dim,
                   getattr(layer, "kv_window", None),
                   tuple((p.shape, str(p.dtype)) for p in pools))
            if key in seen:
                continue
            seen.add(key)
            q = jnp.zeros(shape, dtype)
            window = getattr(layer, "kv_window", None)
            ladder = self.declared_kernel_buckets(latent=len(pools) == 1,
                                                  window=window)
            for jobs in ladder[tokens]:
                t_run = time.perf_counter()
                cache.ragged_attention(
                    layer, q, sm_scale, value_dim,
                    descriptors=warm_descriptors(
                        tokens, jobs, _qblock_rows(), self.page_size,
                        pages_per_seq, window=window)
                ).block_until_ready()
                _co.observe("serving.ragged_attention",
                            self._kernel_signature(tokens, jobs),
                            seconds=time.perf_counter() - t_run)

    def generate(self, input_ids, max_new_tokens=32, max_length=None,
                 timeout=None, trace=None, **kwargs):
        ids = input_ids.numpy() if isinstance(input_ids, Tensor) \
            else np.asarray(input_ids)
        if ids.ndim == 1:
            ids = ids[None]
        if max_length is not None:           # GenerationMixin contract
            max_new_tokens = max(int(max_length) - ids.shape[1], 0)
        if max_new_tokens <= 0:              # zero budget: prompt unchanged
            return Tensor(ids)
        if ids.shape[1] + max_new_tokens > self.max_len:
            # fail THIS request up front — admitted-then-overflowing would
            # poison every co-scheduled request via the batch error path
            raise ValueError(
                f"request needs {ids.shape[1]} + {max_new_tokens} tokens "
                f"> engine max_len {self.max_len}")
        return ServingEngine.generate(self, ids,
                                      max_new_tokens=max_new_tokens,
                                      timeout=timeout, trace=trace,
                                      **kwargs)

    start = ServingEngine.start
    run_on_loop = ServingEngine.run_on_loop
    abort = ServingEngine.abort
    stop = ServingEngine.stop
    _loop = ServingEngine._loop
    __enter__ = ServingEngine.__enter__
    __exit__ = ServingEngine.__exit__

    # -- scheduler ----------------------------------------------------------
    def _admit(self, cache, free, active, pending, prefill_q, sep_q=None):
        """Non-blocking admission: map waiting rows onto free slots and
        match their prompts against the prefix index — NO model work
        happens here (the prefill itself runs chunk-by-chunk in the main
        loop, interleaved with decode steps). Prompts past the sep
        threshold route to the sep-parallel ring-prefill queue instead
        of the paged prefix path."""
        n0 = self.prefills
        with _spans.span("kv/admit") as sp:
            self._admit_rows(cache, free, active, pending, prefill_q, sep_q)
            sp.set(admitted=self.prefills - n0)

    def _admit_rows(self, cache, free, active, pending, prefill_q, sep_q):
        tele = _telemetry()
        while free and pending:
            row = pending.popleft()
            if row.req.cancelled:          # client already gave up
                row.done = True
                self.cancelled_rows += 1
                continue
            slot = free.popleft()
            now = time.perf_counter()
            tele["queue_wait"].observe(now - row.req.t_submit,
                                       engine=self._ENGINE)
            _rt.add_span(row.req.trace, "queue_wait",
                         t0=row.req.t_submit, dur=now - row.req.t_submit,
                         engine=self._ENGINE)
            if row.prompt.shape[0] < 1:
                raise ValueError("cannot serve an empty prompt")
            if sep_q is not None and \
                    self._sep_engaged(cache, row.prompt.shape[0]):
                cache.assign_sep(slot, row.prompt.shape[0],
                                 self.sep_stripe)
                row.sep = True
                row.state = "prefill"
                active[slot] = row
                sep_q.append(slot)
                self.prefills += 1
                self.sep_requests += 1
                _rt.add_event(row.req.trace, "admit_sep", slot=slot,
                              tokens=int(row.prompt.shape[0]),
                              stripe=self.sep_stripe,
                              engine=self._ENGINE)
                continue
            p0 = self._host_pool.promotions
            t_assign = time.perf_counter()
            cached, hits, misses = cache.assign(slot, row.prompt)
            if _co.is_enabled() and self._host_pool.promotions > p0:
                # the promote path stages host blobs onto device pages —
                # a distinct program family (H2D copies + dequant)
                _co.observe("kv.host_promote",
                            self._host_promote_signature(),
                            seconds=time.perf_counter() - t_assign)
            tele["prefix_hits"].inc(hits)
            tele["prefix_misses"].inc(misses)
            tele["prefix_cached"].inc(cached)
            self.prompt_tokens_admitted += int(row.prompt.shape[0])
            self.prompt_tokens_cached += int(cached)
            _rt.add_event(row.req.trace, "admit", slot=slot,
                          cached_tokens=int(cached), prefix_hits=int(hits),
                          prefix_misses=int(misses), engine=self._ENGINE)
            row.state = "prefill"
            active[slot] = row
            prefill_q.append(slot)
            self.prefills += 1

    def _push_token(self, cache, free, active, slot, token):
        row = active[slot]
        row.generated.append(token)
        tele = _telemetry()
        tele["tokens"].inc(engine=self._ENGINE)
        _rt.note_token(row.req.trace)
        if _ledger.is_enabled() and row.req.trace is not None:
            # determinism ledger: advance this (trace, attempt) delivered
            # token-stream chain digest — the attestation input
            _ledger.note_stream_token(
                row.req.trace.trace_id,
                row.req.trace.tags.get("attempt", 0), token)
        if row.req.t_first is None:
            row.req.t_first = time.perf_counter()
            tele["ttft"].observe(row.req.t_first - row.req.t_submit,
                                 engine=self._ENGINE)
        eos = row.req.kwargs.get("eos_token_id")
        if (eos is not None and token == eos) or \
                len(row.generated) >= row.req.max_new_tokens:
            row.done = True
            active[slot] = None
            cache.free(slot)
            free.append(slot)
            self._maybe_finish(row.req)

    def _maybe_finish(self, req):
        rows = req._rows
        if not all(r.done for r in rows):
            return
        if req.cancelled:              # caller already raised TimeoutError
            req.done.set()
            return
        eos = req.kwargs.get("eos_token_id")
        pad = self.pad_token_id if eos is None else eos
        width = req.ids.shape[1] + max(len(r.generated) for r in rows)
        out = np.full((len(rows), width), pad, req.ids.dtype)
        for i, r in enumerate(rows):
            seq = np.concatenate([r.prompt, np.asarray(r.generated,
                                                       req.ids.dtype)])
            out[i, :seq.shape[0]] = seq
        req.result = out
        req.done.set()

    def _serve(self):
        from ..autograd.tape import no_grad
        with no_grad():
            self._serve_ragged()

    def _new_cache(self):
        from ..models.generation import SlotPagedKVCache
        cache = SlotPagedKVCache(self.max_batch, page_size=self.page_size,
                                 max_len=self.max_len,
                                 num_pages=self.num_pages,
                                 enable_prefix_cache=self.enable_prefix_cache,
                                 kv_dtype=self.kv_dtype,
                                 host_pool=self._host_pool,
                                 allow_page_overcommit=(
                                     self.sep_prefill_enabled),
                                 window_groups=self.window_groups,
                                 state_layers=bool(self.state_layers))
        # cache-scoped counter baselines reset with the cache (a rebuilt
        # cache restarts them at 0; pool-scoped baselines persist with
        # the engine-owned host pool)
        for key in ("dev_evict",) + _WINDOW_COUNTERS:
            self._kv_tier_seen.pop(key, None)
        self._cache = cache           # flight-recorder / test introspection
        return cache

    def _mirror_kv_tier(self, tele, cache):
        """Per-tick telemetry mirror for the tiered-KV counters: inc the
        registry by the delta since the last mirror (counters must never
        regress even when the cache — and its counters — rebuild after a
        serve-loop error)."""
        hp = self._host_pool
        bump = self._bump

        bump("dev_evict", cache.prefix_evictions_device,
             tele["prefix_evictions"], tier="device")
        bump("host_evict", hp.evictions,
             tele["prefix_evictions"], tier="host")
        bump("demote", hp.demotions, tele["host_demotions"])
        bump("promote", hp.promotions, tele["host_promotions"])
        tele["host_pool_bytes"].set(hp.used_bytes, kind="used")
        tele["host_pool_bytes"].set(hp.max_bytes, kind="capacity")

    def _bump(self, key, cur, metric, **labels):
        """Inc ``metric`` by what counter ``key`` gained since it was last
        mirrored."""
        prev = self._kv_tier_seen.get(key, 0)
        if cur > prev:
            metric.inc(cur - prev, **labels)
        self._kv_tier_seen[key] = cur

    def _mirror_kv_groups(self, tele, cache):
        """A windowed model's tick: a gauge a page group (pages used /
        pages) and the window groups' three counters, as
        :meth:`_mirror_kv_tier` mirrors the tier's."""
        for label, used, pages in cache.group_usage():
            tele["group_pages"].set(used, group=label, kind="used")
            tele["group_pages"].set(pages, group=label, kind="capacity")
        for key in _WINDOW_COUNTERS:
            self._bump(key, getattr(cache, key), tele["window_events"],
                       kind=key)

    def kv_counters(self):
        """The cache's counters that the engine's own do not carry:
        prefix evictions and, for a windowed model, the window groups'
        three and each group's pages (used, capacity). {} before the
        first tick."""
        cache = self._cache
        if cache is None:
            return {}
        out = {"prefix_evictions_device": cache.prefix_evictions_device}
        if self.state_layers:
            out.update(cache.state_counters,
                       state_resets=cache.state_resets)
        if self.window_groups:
            out.update({key: getattr(cache, key) for key in _WINDOW_COUNTERS},
                       group_pages={label: (used, pages) for label, used,
                                    pages in cache.group_usage()})
        return out

    def _sep_engaged(self, cache, prompt_tokens):
        """Route a prompt to sep-parallel prefill? Explicit threshold
        wins; the 0 default engages when the prompt would consume more
        than half the device page pool (long-context territory — the
        pool may not even hold it)."""
        if not self.sep_prefill_enabled:
            return False
        thr = self.sep_threshold
        if thr <= 0:
            cap = (cache.num_pages - 1) * self.page_size
            thr = max(cap // 2, self.sep_stripe)
        return int(prompt_tokens) >= thr

    @staticmethod
    def _row_key(row, token_idx):
        """Per-token PRNG key for seeded sampling: a request carrying
        ``seed=`` draws token ``i`` of row ``r`` with
        ``fold_in(fold_in(key(seed), r), i)`` — a pure function of the
        request, so sampled decode replays identically across runs,
        schedulers, and speculative verification. Returns None (global
        stateful generator) without a seed."""
        seed = row.req.kwargs.get("seed")
        if seed is None:
            return None
        import jax
        if row._key_base is None:
            row._key_base = jax.random.fold_in(
                jax.random.key(int(seed)), row.row_idx)
        return jax.random.fold_in(row._key_base, int(token_idx))

    def _serve_ragged(self):
        """Token-budget continuous batching: ONE ragged forward per tick
        covering every live decode slot's token plus as many prefill
        tokens as fit in ``token_budget`` (per-span cap
        ``chunk_tokens``), padded to the fixed bucket set — the single
        ragged program family."""
        from ..models.generation import _sample_logits

        was_training = self.model.training
        self.model.eval()
        tick = phase = _spans.NULL
        try:
            cache = self._new_cache()
            free: deque = deque(range(self.max_batch))
            active: list = [None] * self.max_batch
            pending: deque = deque()
            prefill_q: deque = deque()    # slots mid-prefill, FIFO
            sep_q: deque = deque()        # slots mid sep-ring prefill

            def enqueue(item):
                """False = stop token; otherwise split into rows."""
                if item is self._STOP or item is None:
                    return False
                if isinstance(item, _Control):
                    item.run(self)       # tick boundary: scheduler-safe
                    return True
                item._rows = [_Row(item, row, i)
                              for i, row in enumerate(item.ids)]
                pending.extend(item._rows)
                return True

            def drop_slot(i):
                active[i] = None
                cache.free(i)
                if i in prefill_q:
                    prefill_q.remove(i)
                if i in sep_q:
                    sep_q.remove(i)
                free.append(i)

            while True:
                if self._aborted:
                    # replica death (fleet abort()): no drain — every
                    # queued and in-flight request fails NOW so callers
                    # can requeue to a surviving replica
                    err = RuntimeError("ServingEngine aborted")
                    for row in list(pending) + [r for r in active
                                                if r is not None]:
                        _rt.add_event(row.req.trace, "engine_aborted",
                                      engine=self._ENGINE)
                        row.req.error = err
                        row.req.done.set()
                    break
                draining = not self._running
                if draining and all(r is None for r in active):
                    break
                # block only when idle; otherwise drain without waiting
                if not draining and not pending and \
                        all(r is None for r in active):
                    if not enqueue(self._q.get()):
                        self._running = False
                        continue     # drain in-flight rows before exit
                # the tick's spans (docs/OBSERVABILITY.md): schedule,
                # kv/begin_ragged, forward, sync and emit partition
                # serve/tick; ``phase`` is whichever of them is open
                tracing = _spans.latch()
                tick = _spans.span("serve/tick").begin()
                admitted0 = (self.prompt_tokens_admitted,
                             self.prompt_tokens_cached)
                phase = _spans.span("serve/schedule").begin()
                if not draining:
                    try:
                        while True:
                            if not enqueue(self._q.get_nowait()):
                                self._running = False
                                break
                    except queue.Empty:
                        pass
                if not self._running and pending:
                    # stop(): un-admitted rows fail fast — including any
                    # already-admitted SIBLING rows of the same request
                    # (finishing them would be wasted work: the caller
                    # already got the error). Fully-admitted requests
                    # decode to completion (the base engine's contract).
                    dropped = {row.req for row in pending}
                    for row in pending:
                        row.req.error = RuntimeError("ServingEngine stopped")
                        row.req.done.set()
                    pending.clear()
                    for i, r in enumerate(active):
                        if r is not None and r.req in dropped:
                            drop_slot(i)
                # cancellation sweep (step boundary): free slots/pages a
                # timed-out client still holds
                for i, r in enumerate(active):
                    if r is not None and r.req.cancelled:
                        r.done = True
                        self.cancelled_rows += 1
                        _rt.add_event(r.req.trace, "cancelled", slot=i,
                                      engine=self._ENGINE)
                        drop_slot(i)
                tele = _telemetry()
                try:
                    if self._running:
                        self._admit(cache, free, active, pending, prefill_q,
                                    sep_q=sep_q)
                    # ---- pack the tick: decode tokens first (each
                    # optionally extended into a speculative verify span
                    # of 1 current + up to spec_k drafted tokens), then
                    # as many prefill tokens as the budget admits ------
                    # (sep rows run their own stripe-shaped programs in
                    # _sep_tick and never join the ragged pack)
                    decode_slots = [i for i, r in enumerate(active)
                                    if r is not None and r.state == "decode"
                                    and not r.sep]
                    spans = []        # (slot, q_start, start, n, kind)
                    tick_drafts = {}  # slot -> drafted tokens this tick
                    off = 0
                    drafter = self._drafter
                    draft_f0 = getattr(drafter, "forwards", None)
                    # batched drafting prepass: one padded draft forward
                    # per STEP for every decode slot at once. Each slot
                    # is over-asked up to an optimistic cap (>= any room
                    # the sequential packing below can grant, since
                    # every other slot takes at least 1 token) and the
                    # greedy proposal — prefix-stable in k — is trimmed
                    # to the exact sequential room, so packing is
                    # bit-identical to the per-slot propose() path.
                    batch_drafts = None
                    if (drafter is not None and self.draft_batch
                            and decode_slots
                            and hasattr(drafter, "propose_batch")):
                        hists, caps = [], []
                        for i in decode_slots:
                            row = active[i]
                            start = int(cache.lens[i])
                            caps.append(max(0, min(
                                self.token_budget - len(decode_slots),
                                self.spec_k,
                                self.max_len - start - 1,
                                row.req.max_new_tokens
                                - len(row.generated) - 1)))
                            hists.append(np.concatenate(
                                [row.prompt,
                                 np.asarray(row.generated,
                                            row.prompt.dtype)]))
                        batch_drafts = (
                            drafter.propose_batch(hists, caps)
                            if max(caps) > 0 else [[] for _ in caps])
                    for di, i in enumerate(decode_slots):
                        row = active[i]
                        start = int(cache.lens[i])
                        n = 1
                        if drafter is not None:
                            # drafts ride only on leftover budget: every
                            # remaining decode slot keeps its 1 token
                            # (decode liveness stays unconditional), and
                            # a draft never runs past max_len or past
                            # the row's remaining new-token budget
                            room = min(
                                self.token_budget - off - 1
                                - (len(decode_slots) - di - 1),
                                self.spec_k,
                                self.max_len - start - 1,
                                row.req.max_new_tokens
                                - len(row.generated) - 1)
                            if batch_drafts is not None:
                                draft = (batch_drafts[di][:room]
                                         if room > 0 else [])
                            else:
                                draft = (drafter.propose(
                                    np.concatenate(
                                        [row.prompt,
                                         np.asarray(row.generated,
                                                    row.prompt.dtype)]),
                                    room) if room > 0 else [])
                            if draft:
                                tick_drafts[i] = [int(t) for t in draft]
                                n = 1 + len(tick_drafts[i])
                        spans.append((i, off, start, n, "decode"))
                        off += n
                    if drafter is not None and decode_slots:
                        self.spec_draft_ticks += 1
                        if draft_f0 is not None:
                            self.spec_draft_forwards += (
                                drafter.forwards - draft_f0)
                    remaining = self.token_budget - off
                    for slot in list(prefill_q):
                        if remaining <= 0:
                            break
                        row = active[slot]
                        start = int(cache.lens[slot])
                        n = min(self.chunk_tokens,
                                row.prompt.shape[0] - start, remaining)
                        if n <= 0:
                            break
                        spans.append((slot, off, start, n, "prefill"))
                        off += n
                        remaining -= n
                    tele["active"].set(sum(r is not None for r in active))
                    tele["free_slots"].set(len(free))
                    tele["free_pages"].set(cache.free_page_count)
                    tele["pool_occupancy"].set(
                        cache.used_page_count / max(cache.num_pages - 1, 1))
                    page_nb = cache.page_nbytes     # dtype-aware bytes
                    tele["pool_bytes"].set(cache.used_page_count * page_nb,
                                           kind="used")
                    tele["pool_bytes"].set((cache.num_pages - 1) * page_nb,
                                           kind="capacity")
                    self._mirror_kv_tier(tele, cache)
                    if self.window_groups:
                        self._mirror_kv_groups(tele, cache)
                    self._sep_tick(cache, free, active, sep_q)
                    if not spans:
                        phase.discard()
                        tick.discard()
                        continue
                    total = off
                    padded = _token_bucket(total, self.token_budget)
                    flat = np.full(padded, self.pad_token_id, np.int64)
                    pos = np.zeros(padded, np.int32)
                    for slot, qs, start, n, kind in spans:
                        row = active[slot]
                        if kind == "decode":
                            flat[qs] = (row.generated[-1] if row.generated
                                        else row.prompt[-1])
                            draft = tick_drafts.get(slot)
                            if draft:
                                flat[qs + 1:qs + n] = draft
                            pos[qs:qs + n] = np.arange(start, start + n)
                        else:
                            flat[qs:qs + n] = row.prompt[start:start + n]
                            pos[qs:qs + n] = np.arange(start, start + n)
                    ragged = [(slot, qs, n) for slot, qs, _, n, _ in spans]
                    t_step = time.perf_counter()
                    phase.end()
                    cache.begin_ragged(ragged)      # its own span
                    compiled0 = cache.compiled_layer_calls
                    state0 = dict(cache.state_counters)
                    phase = _spans.span("serve/forward").begin()
                    logits = self.model.forward(Tensor(flat[None]),
                                                cache=cache,
                                                position_ids=pos)
                    phase.end()
                    compiled = cache.compiled_layer_calls - compiled0
                    self.compiled_layer_calls += compiled
                    # the tick's one sync: the host waits for the device
                    phase = _spans.span("serve/sync").begin()
                    lg = logits._data[0].astype(jnp.float32)  # [padded, V]
                    # ... and what the model counted on the device during
                    # the forward rides the same read-back
                    greedy, found = jax.device_get(
                        (jnp.argmax(lg, axis=-1),
                         cache.take_step_counters()))
                    step_dt = time.perf_counter() - t_step
                    phase.end()
                    found = {k: np.sum(v, axis=0) for k, v in found.items()}
                    for k, v in found.items():
                        self.model_counters[k] = \
                            self.model_counters.get(k, 0) + v
                    phase = _spans.span("serve/emit").begin()
                    self.ragged_steps += 1
                    self.ragged_buckets_used.add(padded)
                    # compile observatory: one program-boundary record
                    # per packed tick; on a miss every participating
                    # request gets a "compile" span so its TTFT
                    # decomposes into queue/compile/prefill
                    compile_ev = None
                    if _co.is_enabled():
                        ev = _co.observe("serving.ragged",
                                         self._ragged_signature(padded),
                                         seconds=step_dt)
                        if ev is not None and ev["miss"]:
                            compile_ev = ev
                    self.padded_tokens_total += padded
                    self.useful_tokens_total += total
                    tele["budget_util"].observe(total / max(padded, 1))
                    n_decode = sum(n for _, _, _, n, kind in spans
                                   if kind == "decode")
                    n_prefill = total - n_decode
                    self.ragged_decode_tokens += n_decode
                    self.ragged_prefill_tokens += n_prefill
                    if n_decode:
                        tele["ragged_tokens"].inc(n_decode, kind="decode")
                    if n_prefill:
                        tele["ragged_tokens"].inc(n_prefill, kind="prefill")
                    # request-trace: the packed tick lands as one span on
                    # every participating request (its kind/tokens in the
                    # tags — prefill chunks and decode ticks both)
                    for slot, qs, start, n, kind in spans:
                        row = active[slot]
                        if row is None:
                            continue
                        if compile_ev is not None:
                            _rt.add_span(row.req.trace, "compile",
                                         t0=t_step, dur=step_dt,
                                         family="serving.ragged",
                                         cause=compile_ev["cause"],
                                         tick=self.ragged_steps)
                        name = ("prefill_chunk" if kind == "prefill"
                                else "decode")
                        _rt.add_span(
                            row.req.trace, name, t0=t_step, dur=step_dt,
                            slot=slot, tokens=n, start=start,
                            tick=self.ragged_steps,
                            last=(kind == "prefill" and
                                  start + n >= row.prompt.shape[0]))

                    def sample(idx, row, offset=0):
                        """Target token for flat position ``idx``;
                        ``offset`` is the token's index past the row's
                        already-generated count (speculative verify
                        positions), keeping seeded-sampling keys a pure
                        function of the final token index."""
                        kw = row.req.kwargs
                        if kw.get("do_sample", False):
                            key = self._row_key(
                                row, len(row.generated) + offset)
                            return int(np.asarray(_sample_logits(
                                lg[idx:idx + 1], True, kw.get("top_k", 0),
                                kw.get("top_p", 1.0),
                                kw.get("temperature", 1.0), key=key))[0])
                        return int(greedy[idx])

                    # prefill spans: advance, register finished prompts,
                    # hand completed rows to the decode path
                    first_tokens = emitted = 0
                    for slot, qs, start, n, kind in spans:
                        if kind != "prefill":
                            continue
                        row = active[slot]
                        self.prefill_chunks += 1
                        done = start + n >= row.prompt.shape[0]
                        self.events.append(("chunk", slot, n, done))
                        if not done:
                            continue
                        prefill_q.remove(slot)
                        cache.commit_prefix(slot)
                        row.state = "decode"
                        self._push_token(cache, free, active, slot,
                                         sample(qs + n - 1, row))
                        first_tokens += 1
                    # decode spans: verify drafted tokens against the
                    # target model's own choices — the target token at
                    # span offset j is valid iff every draft before it
                    # matched, so the longest matching prefix (plus the
                    # free token after it) is emitted and the rejected
                    # tail's K/V rolls back out of the context
                    if decode_slots:
                        self.decode_steps += 1
                        self.events.append(("decode", len(decode_slots)))
                        tele["decode_step"].observe(step_dt)
                        for slot, qs, start, n, kind in spans:
                            if kind != "decode":
                                continue
                            row = active[slot]
                            if row is None or row.done:
                                continue
                            draft = tick_drafts.get(slot, ())
                            kd = len(draft)
                            targets = [sample(qs + j, row, offset=j)
                                       for j in range(kd + 1)]
                            m = 0
                            while m < kd and draft[m] == targets[m]:
                                m += 1
                            if kd:
                                self.spec_rounds += 1
                                self.spec_drafted_tokens += kd
                                self.spec_accepted_tokens += m
                                tele["spec_tokens"].inc(kd, kind="drafted")
                                if m:
                                    tele["spec_tokens"].inc(
                                        m, kind="accepted")
                                tele["spec_accept"].observe(m / kd)
                                if kd > m:
                                    cache.rollback(slot, kd - m)
                            for t in targets[:m + 1]:
                                self._push_token(cache, free, active,
                                                 slot, t)
                                emitted += 1
                                if active[slot] is None \
                                        or active[slot].done:
                                    break
                        for _ in range(emitted):
                            tele["token"].observe(
                                step_dt / max(emitted, 1))
                    phase.end(emitted=first_tokens + emitted)
                    if tracing:
                        tick.end(tick=self.ragged_steps, useful=total,
                                 padded=padded, n_decode=n_decode,
                                 n_prefill=n_prefill,
                                 compiled_layers=compiled,
                                 prompt_tokens=(self.prompt_tokens_admitted
                                                - admitted0[0]),
                                 cached_tokens=(self.prompt_tokens_cached
                                                - admitted0[1]),
                                 spans=[[n, start + n]
                                        for _, _, start, n, _ in spans],
                                 **({"state_slots_live": int(
                                     np.count_nonzero(cache.lens))}
                                    if self.state_layers else {}),
                                 **{k: v - state0.get(k, 0) for k, v in
                                    cache.state_counters.items()},
                                 **{k: v.tolist()
                                    for k, v in found.items()})
                except Exception as e:      # fail everything in flight
                    phase.end()
                    tick.end(error=type(e).__name__)
                    reqs = {r.req for r in pending}
                    reqs |= {r.req for r in active if r is not None}
                    for req in reqs:
                        req.error = e
                        req.done.set()
                    pending.clear()
                    prefill_q.clear()
                    sep_q.clear()
                    active = [None] * self.max_batch
                    free = deque(range(self.max_batch))
                    cache = self._new_cache()
        finally:
            phase.discard()       # only what an escaping error left open
            tick.discard()
            if was_training:
                self.model.train()

    def _sep_tick(self, cache, free, active, sep_q):
        """One sep-parallel step per tick: a single ring-prefill stripe
        chunk for the longest-waiting sep slot, then one decode token
        for every sep row already decoding. Sep programs are stripe- or
        tail-shaped — never part of the ragged pack — so interleaving
        at tick granularity keeps paged traffic flowing underneath a
        100k-token prefill."""
        if sep_q:
            slot = sep_q[0]
            if self._sep_prefill_chunk(cache, free, active, slot,
                                       active[slot]):
                sep_q.popleft()
        for i, r in enumerate(active):
            if r is not None and r.sep and r.state == "decode":
                self._sep_decode_step(cache, free, active, i)

    def _sep_prefill_chunk(self, cache, free, active, slot, row):
        """Advance one stripe-sized ring-prefill chunk; on the final
        chunk sample the first token and flip the row to sep decode.
        Returns True when the prompt is fully consumed."""
        from ..models.generation import _sample_logits
        tele = _telemetry()
        stripe = self.sep_stripe
        start = int(cache.lens[slot])
        n_valid = min(stripe, row.prompt.shape[0] - start)
        chunk = np.full(stripe, self.pad_token_id, row.prompt.dtype)
        chunk[:n_valid] = row.prompt[start:start + n_valid]
        pos = np.minimum(np.arange(start, start + stripe, dtype=np.int32),
                         start + n_valid - 1)
        n_stripes = cache.sep_view(slot)["stripes"]
        cache.begin_sep_prefill(slot, n_valid)
        t_chunk = time.perf_counter()
        logits = self.model.forward(Tensor(chunk[None]), cache=cache,
                                    position_ids=pos)
        chunk_dt = time.perf_counter() - t_chunk
        self.prefill_chunks += 1
        self.padded_tokens_total += stripe
        self.useful_tokens_total += n_valid
        tele["chunk_util"].observe(n_valid / max(stripe, 1))
        done = start + n_valid >= row.prompt.shape[0]
        self.events.append(("sep_chunk", slot, n_valid, done))
        if _co.is_enabled():
            ev = _co.observe("serving.sep_prefill",
                             self._sep_prefill_signature(n_stripes),
                             seconds=chunk_dt)
            if ev is not None and ev["miss"]:
                _rt.add_span(row.req.trace, "compile", t0=t_chunk,
                             dur=chunk_dt, family="serving.sep_prefill",
                             cause=ev["cause"])
        _rt.add_span(row.req.trace, "sep_prefill_chunk", t0=t_chunk,
                     dur=chunk_dt, slot=slot, tokens=n_valid,
                     start=start, stripes=n_stripes, last=done)
        if not done:
            return False
        kw = row.req.kwargs
        nxt = int(np.asarray(_sample_logits(
            logits._data[:, n_valid - 1].astype(jnp.float32),
            kw.get("do_sample", False), kw.get("top_k", 0),
            kw.get("top_p", 1.0), kw.get("temperature", 1.0),
            key=self._row_key(row, len(row.generated))))[0])
        row.state = "decode"
        self._push_token(cache, free, active, slot, nxt)
        return True

    def _sep_decode_step(self, cache, free, active, slot):
        """One decode token for a sep row: the ring merge reads every
        stored stripe plus the pow2-padded device tail window."""
        from ..models.generation import _sample_logits
        tele = _telemetry()
        row = active[slot]
        view = cache.sep_view(slot)
        cur = np.asarray([[row.generated[-1] if row.generated
                           else row.prompt[-1]]], np.int64)
        pos = np.asarray([[int(cache.lens[slot])]], np.int32)
        cache.begin_sep_decode(slot)
        t_step = time.perf_counter()
        logits = self.model.forward(Tensor(cur), cache=cache,
                                    position_ids=pos)
        step_dt = time.perf_counter() - t_step
        self.decode_steps += 1
        tele["decode_step"].observe(step_dt)
        tele["token"].observe(step_dt)
        if _co.is_enabled():
            ev = _co.observe("serving.sep_decode",
                             self._sep_decode_signature(
                                 view["stripes"], view["tail_pages"]),
                             seconds=step_dt)
            if ev is not None and ev["miss"]:
                _rt.add_span(row.req.trace, "compile", t0=t_step,
                             dur=step_dt, family="serving.sep_decode",
                             cause=ev["cause"])
        _rt.add_span(row.req.trace, "decode", t0=t_step, dur=step_dt,
                     slot=slot, tokens=1, sep=True,
                     tick=self.decode_steps)
        kw = row.req.kwargs
        tok = int(np.asarray(_sample_logits(
            logits._data[:, -1].astype(jnp.float32),
            kw.get("do_sample", False), kw.get("top_k", 0),
            kw.get("top_p", 1.0), kw.get("temperature", 1.0),
            key=self._row_key(row, len(row.generated))))[0])
        self._push_token(cache, free, active, slot, tok)

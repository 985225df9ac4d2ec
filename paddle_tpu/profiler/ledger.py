"""Determinism observatory: cross-rank/cross-run digest ledger (ISSUE 13).

Nearly every headline guarantee in this repo is a *bit-parity* property
— elastic shrink resumes bit-identical (PR 6), spec decode equals plain
greedy (PR 10), disagg handoff and requeue never change tokens (PRs
8/9) — but each is asserted only inside tests. In a running fleet
nothing would *notice* silent numerical divergence: a flipped bit in
one rank's optimizer state, a non-deterministic kernel, a stale KV page
after a handoff. Production TPU serving (arxiv 2605.25645) treats
cross-replica output equivalence as an operational invariant; this
module is the sensor that makes it one here.

The :class:`StepLedger` computes cheap, *stable* content digests (sha1
over the raw float bit patterns, dtype/shape-tagged — a 1-ulp
perturbation changes the digest) of designated tensors at well-defined
barriers:

* **training** — per-step parameter and (post-sync) gradient digests,
  hooked through ``Optimizer.step``; optional per-leaf *local* (pre
  all-reduce) gradient digests through the PR-5 tape grad-ready
  callbacks (:func:`attach`, thread-local per simulated rank). Entry
  names are ``grad:<param>`` / ``param:<param>`` / ``grad.local:<param>``
  — the ``grad.local:`` tier legitimately differs across dp ranks (each
  rank owns a data shard) so only the first two enter the cross-rank
  comparison; all three enter the cross-run golden ledger.
* **serving** — per-request delivered-token-stream *chain* digests
  (``d_i = sha1(d_{i-1} || token_i)``) recorded at the engines' single
  token-append point and threaded through ``RequestTraceStore`` spans
  (the ``delivered``/``done`` span carries ``token_digest``); the
  router attests at delivery that a requeued or disaggregated request's
  stream is digest-consistent across attempts/replicas
  (:func:`attest_delivery`) — the at-most-once resume contract becomes
  a runtime-checked invariant.
* **handoff** — KV-page-blob digests sealed at
  ``SlotPagedKVCache.export_pages`` and verified at ``import_pages``
  (:func:`seal_handoff` / :func:`check_handoff`).

Three consumers wire it end to end:

1. **cross-rank** — each rank's committed step row is compared against
   its peers' (directly under the thread-rank simulator; via
   :func:`publish_ledger`/:func:`gather_ledgers` over the flight-
   recorder KV component-state path for real multi-process jobs). The
   comparator raises a structured :class:`DivergenceError` naming the
   FIRST divergent step/rank/tensor (majority vote across ranks;
   ``PADDLE_LEDGER_MODE=warn`` records-and-continues — the warn path is
   read-only, bit-identical to ledger-off). Detections tick
   ``paddle_ledger_divergence_total{kind}``, set the
   ``paddle_ledger_divergent_steps`` gauge the built-in
   ``numerics_divergence`` alert rule pages on, and ride into watchdog
   dumps through the ``ledger`` state provider.
2. **cross-run** — :func:`export_golden` writes a deterministic
   (timestamp-free, sorted, write-tmp-then-replace) JSONL golden
   ledger; stdlib-only ``tools/ledger_diff.py`` diffs two ledgers and
   reports the first divergent step/tensor/request — CI's
   seeded-run-vs-committed-golden guard.
3. **attestation** — see above; failures are ``kind="attestation"``
   divergences.

Zero overhead disabled (flight-recorder-style module bool): every call
site checks :func:`is_enabled` first, nothing registers on the tape
until :func:`enable`/:func:`attach`, and a disabled ledger never
touches tensor memory. ``PADDLE_LEDGER=1`` enables at import.
"""
from __future__ import annotations

import hashlib
import json
import os
import threading
from collections import Counter, OrderedDict

__all__ = [
    "DivergenceError", "StepLedger", "get_ledger", "enable", "disable",
    "attach", "detach", "is_enabled", "reset", "tensor_digest",
    "chain_update", "blob_digest", "first_divergence",
    "record_optimizer_step", "note_stream_token", "stream_digest",
    "attest_delivery", "seal_handoff", "check_handoff", "export_golden",
    "publish_ledger", "gather_ledgers", "compare_store",
    "LEDGER_SCHEMA", "KV_LEDGER_PREFIX",
    "DEFAULT_LEDGER_CAPACITY", "DEFAULT_STREAM_CAPACITY",
]

LEDGER_SCHEMA = "paddle_ledger/1"
KV_LEDGER_PREFIX = "ledger/rank/"

DEFAULT_LEDGER_CAPACITY = 512      # committed step rows kept (all ranks)
DEFAULT_STREAM_CAPACITY = 512      # per-(trace, attempt) token chains kept
#: chain digests kept per stream; past the cap the rolling digest and
#: count still advance (attestation then compares final prefixes only)
MAX_CHAIN_PER_STREAM = 4096
#: entry-name prefix excluded from the cross-rank comparison (pre-sync
#: local gradients differ across dp ranks by construction)
LOCAL_PREFIX = "grad.local:"

_MODES = ("raise", "warn")

_ENABLED = False
_LEDGER: "StepLedger | None" = None
_MODULE_LOCK = threading.Lock()

#: seed of every token-stream chain (so an empty stream has a defined,
#: non-colliding digest)
STREAM_SEED = hashlib.sha1(b"paddle-ledger-stream").hexdigest()


class DivergenceError(RuntimeError):
    """Two replicas (ranks, attempts or handoff sides) that must be
    bit-identical are not. Carries the comparison ``kind``
    (``cross_rank`` / ``attestation`` / ``handoff``), the first
    divergent ``step`` (token position for attestation), the divergent
    ``rank`` (attempt number for attestation), the exact ``tensor``
    name (``grad:<param>`` / ``param:<param>`` / ``tokens:<trace_id>``
    / ``handoff:<digest-prefix>``) and the per-replica ``digests``."""

    def __init__(self, kind, step, rank, tensor, digests=None):
        self.kind = str(kind)
        self.step = step
        self.rank = rank
        self.tensor = str(tensor)
        self.digests = dict(digests or {})
        super().__init__(
            f"{self.kind} divergence at step {step}: rank {rank} "
            f"diverges on '{self.tensor}' "
            f"(digests {self.digests}) — run tools/ledger_diff.py "
            f"against the golden ledger and see docs/RUNBOOK.md "
            f"'silent divergence'")


def _rank():
    try:
        from ..distributed import simulator
        r = simulator.current_rank()
        if r is not None:
            return r
    except Exception:
        pass
    return 0


# ---------------------------------------------------------------------------
# digest primitives (pure; shared with tools/ledger_diff.py by schema,
# not by import — the tool must stay stdlib-only)
# ---------------------------------------------------------------------------


def tensor_digest(arr) -> str:
    """sha1 over dtype tag + shape tag + the raw (bit-pattern) buffer.
    Stable across runs/processes for bit-identical content; any single
    flipped bit — including ``-0.0`` vs ``0.0`` or a NaN payload —
    changes it. Works for every numpy-convertible dtype incl. bf16."""
    import numpy as np
    a = np.ascontiguousarray(np.asarray(arr))
    h = hashlib.sha1()
    h.update(str(a.dtype).encode())
    h.update(b"|")
    h.update(repr(tuple(a.shape)).encode())
    h.update(b"|")
    h.update(a.tobytes())
    return h.hexdigest()


def chain_update(prev_hex: str, token: int) -> str:
    """One link of a token-stream chain digest: the digest at position
    ``i`` covers every token up to and including ``i``, so two streams
    agree on a prefix iff their chain digests agree at its last
    position."""
    h = hashlib.sha1()
    h.update(bytes.fromhex(prev_hex))
    h.update(int(token).to_bytes(8, "little", signed=True))
    return h.hexdigest()


def blob_digest(blob: dict) -> str:
    """Content digest of a KV-page handoff blob (``export_pages``
    payload): geometry tags + page digests + every layer's raw K/V
    bytes (+ scales for int8 pools). Ignores any already-attached
    ``ledger_digest`` so sealing is idempotent."""
    import numpy as np
    h = hashlib.sha1()
    h.update(str(blob.get("page_size")).encode())
    h.update(str(blob.get("kv_dtype")).encode())
    h.update(str(blob.get("native_dtype")).encode())
    for d in blob.get("digests", ()):
        h.update(bytes(d))
    for parts in blob.get("layers", ()):    # (k, v), or a latent (kv,)
        for part in parts:
            a = np.ascontiguousarray(np.asarray(part))
            h.update(str(a.dtype).encode())
            h.update(repr(tuple(a.shape)).encode())
            h.update(a.tobytes())
    for pair in (blob.get("scales") or ()):
        for part in pair:
            h.update(np.ascontiguousarray(np.asarray(part)).tobytes())
    return h.hexdigest()


def first_divergence(entries_by_rank: dict):
    """Pure comparator over one step's ``{rank: {name: digest}}``.

    Entries are walked in canonical sorted order (``grad:`` sorts
    before ``param:``, so the causal gradient divergence is named
    before the parameter that followed it); ``grad.local:`` entries are
    skipped — local shards differ across dp ranks by design. The
    divergent rank is the one outvoted by the majority digest (ties
    side with the lowest rank). Returns ``None`` or
    ``{"rank", "tensor", "digests"}``."""
    names = sorted(set().union(*[set(e) for e in entries_by_rank.values()])
                   if entries_by_rank else ())
    for name in names:
        if name.startswith(LOCAL_PREFIX):
            continue
        per = {r: e.get(name) for r, e in entries_by_rank.items()}
        present = {r: v for r, v in per.items() if v is not None}
        missing = sorted(r for r, v in per.items() if v is None)
        if missing and present:
            return {"rank": missing[0], "tensor": name, "digests": per}
        if len(set(present.values())) <= 1:
            continue
        top, n = Counter(present.values()).most_common(1)[0]
        majority = (top if n > len(present) // 2
                    else present[min(present)])
        bad = sorted(r for r, v in present.items() if v != majority)
        return {"rank": bad[0] if bad else min(per),
                "tensor": name, "digests": per}
    return None


# ---------------------------------------------------------------------------
# the ledger
# ---------------------------------------------------------------------------


class StepLedger:
    """Process-global digest ledger. One instance; per-rank rows (the
    thread-rank simulator's ranks share it, which is exactly what lets
    the cross-rank comparator run in-process — multi-process jobs go
    through :func:`publish_ledger`/:func:`gather_ledgers` instead)."""

    def __init__(self, mode=None, interval=None, capacity=None,
                 stream_capacity=None):
        if mode is None:
            mode = os.environ.get("PADDLE_LEDGER_MODE", "raise")
        if mode not in _MODES:
            raise ValueError(f"unknown PADDLE_LEDGER_MODE {mode!r} "
                             f"(one of {'/'.join(_MODES)})")
        self.mode = mode
        if interval is None:
            try:
                interval = int(os.environ.get("PADDLE_LEDGER_INTERVAL", "1"))
            except ValueError:
                interval = 1
        self.interval = max(int(interval), 1)
        if capacity is None:
            try:
                capacity = int(os.environ.get(
                    "PADDLE_LEDGER_CAPACITY", str(DEFAULT_LEDGER_CAPACITY)))
            except ValueError:
                capacity = DEFAULT_LEDGER_CAPACITY
        self.capacity = max(int(capacity), 8)
        if stream_capacity is None:
            try:
                stream_capacity = int(os.environ.get(
                    "PADDLE_LEDGER_STREAMS", str(DEFAULT_STREAM_CAPACITY)))
            except ValueError:
                stream_capacity = DEFAULT_STREAM_CAPACITY
        self.stream_capacity = max(int(stream_capacity), 8)
        self._lock = threading.RLock()
        self._rows: OrderedDict = OrderedDict()    # (rank, step) -> row
        self._pending: dict = {}                   # rank -> OrderedDict
        self._counts: dict = {}                    # rank -> committed steps
        self._verified: dict = {}                  # rank -> verified step hw
        self._streams: OrderedDict = OrderedDict()  # (trace, attempt) -> st
        self._handoffs: list = []                  # recent handoff records
        self._divergences: list = []               # latched detections
        self._store = None                         # optional KV publish
        self._tele = None

    # -- telemetry -----------------------------------------------------------
    def _telemetry(self):
        if self._tele is None:
            from .telemetry import get_registry
            r = get_registry()
            self._tele = {
                "digests": r.counter(
                    "paddle_ledger_digests_total",
                    "content digests computed, by tensor kind",
                    labels=("kind",)),
                "divergence": r.counter(
                    "paddle_ledger_divergence_total",
                    "bit-divergence detections, by comparison kind",
                    labels=("kind",)),
                "divergent_steps": r.gauge(
                    "paddle_ledger_divergent_steps",
                    "distinct steps with a latched cross-rank divergence "
                    "(the built-in numerics_divergence alert's signal)"),
                "attest": r.counter(
                    "paddle_ledger_attestations_total",
                    "delivered-token-stream attestations, by result",
                    labels=("result",)),
            }
        return self._tele

    # -- training: tape + optimizer hooks ------------------------------------
    def _sampling(self, rank) -> bool:
        return self._counts.get(rank, 0) % self.interval == 0

    def _on_grad_ready(self, t):
        """Tape grad-ready callback (:func:`attach`): digest the LOCAL
        (pre all-reduce) gradient the moment it is final. Read-only —
        never perturbs the overlapped-backward dispatch order."""
        g = getattr(t, "grad", None)
        if g is None:
            return
        rank = _rank()
        with self._lock:
            if not self._sampling(rank):
                return
        name = getattr(t, "name", None) or f"param{id(t)}"
        d = tensor_digest(g._data)
        with self._lock:
            self._pending.setdefault(rank, OrderedDict())[
                f"{LOCAL_PREFIX}{name}"] = d
        self._telemetry()["digests"].inc(kind="grad_local")

    def record_optimizer_step(self, optimizer):
        """``Optimizer.step`` hook: digest every stepped parameter's
        (post-sync) gradient and updated value, commit the step row and
        run the cross-rank comparator. Raises :class:`DivergenceError`
        in ``raise`` mode when this commit completes a divergent step.

        Entries are keyed by parameter POSITION (``grad:p0003``) — the
        auto-assigned parameter names come from a process-global
        counter, so the thread-simulated ranks' copies of one model
        carry different names; position in the optimizer's parameter
        list is the cross-rank identity (same construction order on
        every rank). The human name rides in the row's ``names`` map
        and is substituted back into :class:`DivergenceError`."""
        rank = _rank()
        with self._lock:
            step = self._counts.get(rank, 0)
            sampled = step % self.interval == 0
            entries = self._pending.pop(rank, OrderedDict())
        names = {}
        if sampled:
            tele = self._telemetry()
            params = [p for p in optimizer._parameter_list
                      if p.grad is not None
                      and getattr(p, "trainable", not p.stop_gradient)]
            for i, p in enumerate(params):
                names[f"p{i:04d}"] = (getattr(p, "name", None)
                                      or f"param{id(p)}")
            for i, p in enumerate(params):
                entries[f"grad:p{i:04d}"] = tensor_digest(p.grad._data)
                tele["digests"].inc(kind="grad")
            for i, p in enumerate(params):
                entries[f"param:p{i:04d}"] = tensor_digest(p._data)
                tele["digests"].inc(kind="param")
        self._commit(rank, step, entries, names)

    def _commit(self, rank, step, entries, names=None):
        row = {"rank": int(rank), "step": int(step),
               "entries": dict(entries), "names": dict(names or {})}
        with self._lock:
            self._rows[(rank, step)] = row
            self._counts[rank] = step + 1
            while len(self._rows) > self.capacity:
                self._rows.popitem(last=False)
        if self._store is not None:
            try:
                from . import flight_recorder
                flight_recorder.publish_component_state(
                    self._store, f"{KV_LEDGER_PREFIX}{rank}/{step}", row)
            except Exception:
                pass            # sensing must never kill the training loop
        self._verify_committed(rank)

    def _verify_committed(self, rank):
        """Advance this rank's verified high-water across every step all
        live peers have committed; first divergence is handled per
        ``mode`` (raise on the committing rank's own thread)."""
        try:
            from ..distributed import simulator
            w = simulator.active_world()
        except Exception:
            w = None
        if w is None:
            return
        live = [r for r in range(w.nprocs) if r not in w.dead_ranks]
        if len(live) < 2 or rank not in live:
            return
        found = None
        with self._lock:
            s = self._verified.get(rank, -1) + 1
            while s < self._counts.get(rank, 0):
                rows = {r: self._rows.get((r, s)) for r in live}
                if any(v is None for v in rows.values()):
                    break                    # peers not there yet
                self._verified[rank] = s
                div = first_divergence(
                    {r: row["entries"] for r, row in rows.items()})
                if div is not None:
                    found = dict(div, step=s)
                    # substitute the divergent rank's human parameter
                    # name back into the positional entry key
                    kind, _, key = found["tensor"].partition(":")
                    name = (rows[found["rank"]] or {}).get(
                        "names", {}).get(key)
                    if name:
                        found["tensor"] = f"{kind}:{name}"
                    break
                s += 1
        if found is not None:
            self._on_divergence("cross_rank", found["step"], found["rank"],
                                found["tensor"], found["digests"])

    # -- serving: token streams + attestation --------------------------------
    def note_stream_token(self, trace_id, attempt, token):
        """Advance the (trace, attempt) chain digest by one delivered
        token — called from the engines' single token-append point."""
        key = (str(trace_id), int(attempt or 0))
        with self._lock:
            st = self._streams.get(key)
            if st is None:
                st = self._streams[key] = {
                    "trace": key[0], "attempt": key[1],
                    "count": 0, "digest": STREAM_SEED, "chain": []}
                self._streams.move_to_end(key)
                while len(self._streams) > self.stream_capacity:
                    self._streams.popitem(last=False)
            st["digest"] = chain_update(st["digest"], token)
            st["count"] += 1
            if len(st["chain"]) < MAX_CHAIN_PER_STREAM:
                st["chain"].append(st["digest"])
        self._telemetry()["digests"].inc(kind="stream")

    def streams(self, trace_id) -> dict:
        """{attempt: {"count", "digest"}} for one trace."""
        tid = str(trace_id)
        with self._lock:
            return {a: {"count": st["count"], "digest": st["digest"]}
                    for (t, a), st in self._streams.items() if t == tid}

    def stream_digest(self, trace_id, attempt=None):
        """Final chain digest of one attempt's stream (highest attempt
        when unspecified), or ``None`` when nothing was recorded."""
        tid = str(trace_id)
        with self._lock:
            cands = [(a, st) for (t, a), st in self._streams.items()
                     if t == tid
                     and (attempt is None or a == int(attempt))]
        if not cands:
            return None
        return max(cands)[1]["digest"]

    def attest_delivery(self, trace_id, attempt=None):
        """Verify every attempt recorded for ``trace_id`` is chain-
        consistent with the delivering attempt over their common prefix
        (a requeued attempt restarted decode; a disagg prefill attempt
        produced the first token on another replica — both must have
        produced the SAME tokens). Returns the delivered stream's final
        digest; mismatch is an ``attestation`` divergence."""
        tid = str(trace_id)
        with self._lock:
            atts = sorted(((a, dict(st, chain=list(st["chain"])))
                           for (t, a), st in self._streams.items()
                           if t == tid))
        if not atts:
            return None
        base = dict(atts[-1][1])
        if attempt is not None:
            for a, st in atts:
                if a == int(attempt):
                    base = st
                    break
        tele = self._telemetry()
        for a, st in atts:
            if st is base or a == base["attempt"]:
                continue
            n = min(st["count"], base["count"])
            if n == 0 or n > len(st["chain"]) or n > len(base["chain"]):
                continue
            if st["chain"][n - 1] != base["chain"][n - 1]:
                tele["attest"].inc(result="fail")
                self._on_divergence(
                    "attestation", n - 1, a, f"tokens:{tid}",
                    {a: st["chain"][n - 1],
                     base["attempt"]: base["chain"][n - 1]})
                return base["digest"]      # warn mode records + continues
        tele["attest"].inc(result="pass")
        return base["digest"]

    # -- KV-page handoff -----------------------------------------------------
    def seal_handoff(self, blob) -> str:
        """Exporter side: compute + record the blob digest (the caller
        attaches it to the blob as ``ledger_digest``)."""
        d = blob_digest(blob)
        with self._lock:
            self._handoffs.append({"direction": "export", "digest": d,
                                   "pages": len(blob.get("digests", ()))})
            del self._handoffs[:-64]
        self._telemetry()["digests"].inc(kind="handoff")
        return d

    def check_handoff(self, blob):
        """Importer side: recompute and verify a sealed blob. An
        unsealed blob (exporter ran ledger-off) records but never
        fails — enabling the ledger must stay a rolling operation."""
        d = blob_digest(blob)
        want = blob.get("ledger_digest")
        with self._lock:
            self._handoffs.append({"direction": "import", "digest": d,
                                   "pages": len(blob.get("digests", ()))})
            del self._handoffs[:-64]
        self._telemetry()["digests"].inc(kind="handoff")
        if want is not None and want != d:
            self._on_divergence("handoff", None, _rank(),
                                f"handoff:{want[:12]}",
                                {"exported": want, "imported": d})
        return d

    # -- divergence handling -------------------------------------------------
    def _on_divergence(self, kind, step, rank, tensor, digests):
        tele = self._telemetry()
        tele["divergence"].inc(kind=kind)
        with self._lock:
            self._divergences.append({
                "kind": kind, "step": step, "rank": rank,
                "tensor": str(tensor), "digests": dict(digests or {})})
            del self._divergences[:-64]
            steps = {d["step"] for d in self._divergences
                     if d["kind"] == "cross_rank"}
        tele["divergent_steps"].set(len(steps))
        from . import flight_recorder
        flight_recorder.record_event("ledger", divergence=kind, step=step,
                                     divergent_rank=rank,
                                     tensor=str(tensor))
        if self.mode == "raise":
            raise DivergenceError(kind, step, rank, tensor, digests)

    def divergences(self) -> list:
        with self._lock:
            return [dict(d) for d in self._divergences]

    # -- read side -----------------------------------------------------------
    def rows(self, rank=None) -> list:
        with self._lock:
            return [dict(r, entries=dict(r["entries"]))
                    for r in self._rows.values()
                    if rank is None or r["rank"] == rank]

    def state(self) -> dict:
        """The ``ledger`` state-provider payload (watchdog dumps)."""
        with self._lock:
            recent = list(self._rows.values())[-8:]
            return {
                "mode": self.mode,
                "interval": self.interval,
                "steps": dict(self._counts),
                "verified": dict(self._verified),
                "recent_rows": [
                    {"rank": r["rank"], "step": r["step"],
                     "entries": dict(sorted(r["entries"].items())[:32])}
                    for r in recent],
                "streams": len(self._streams),
                "handoffs": [dict(h) for h in self._handoffs[-8:]],
                "divergences": [dict(d) for d in self._divergences],
            }

    def attach_store(self, store):
        """Publish every committed row to an elastic KV store under
        ``ledger/rank/<r>/<s>`` (the flight-recorder component-state
        path) so an out-of-process comparator (:func:`compare_store`)
        sees them."""
        self._store = store
        return self

    # -- golden export -------------------------------------------------------
    def export_golden(self, path=None) -> str:
        """Write the deterministic JSONL golden ledger: one ``meta``
        line, then step rows sorted by (rank, step) with sorted
        entries, stream rows sorted by (trace, attempt), handoffs in
        record order. No timestamps — two bit-identical runs produce
        byte-identical files. Write-tmp-then-replace."""
        path = path or os.environ.get("PADDLE_LEDGER_GOLDEN") \
            or "./ledger_golden.jsonl"
        with self._lock:
            rows = sorted(self._rows.values(),
                          key=lambda r: (r["rank"], r["step"]))
            lines = [json.dumps({"kind": "meta", "schema": LEDGER_SCHEMA},
                                sort_keys=True)]
            for r in rows:
                lines.append(json.dumps(
                    {"kind": "step", "rank": r["rank"], "step": r["step"],
                     "entries": dict(sorted(r["entries"].items())),
                     "names": dict(sorted(r.get("names", {}).items()))},
                    sort_keys=True))
            for (t, a) in sorted(self._streams):
                st = self._streams[(t, a)]
                lines.append(json.dumps(
                    {"kind": "stream", "trace": t, "attempt": a,
                     "count": st["count"], "digest": st["digest"]},
                    sort_keys=True))
            for h in self._handoffs:
                lines.append(json.dumps(dict(h, kind="handoff"),
                                        sort_keys=True))
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            f.write("\n".join(lines) + "\n")
        os.replace(tmp, path)
        return path

    def clear(self):
        with self._lock:
            self._rows.clear()
            self._pending.clear()
            self._counts.clear()
            self._verified.clear()
            self._streams.clear()
            del self._handoffs[:]
            del self._divergences[:]


# ---------------------------------------------------------------------------
# module facade (every call is a bool check away from free when disabled)
# ---------------------------------------------------------------------------

_ATTACHED = threading.local()


def get_ledger() -> StepLedger:
    global _LEDGER
    if _LEDGER is None:
        with _MODULE_LOCK:
            if _LEDGER is None:
                _LEDGER = StepLedger()
    return _LEDGER


def is_enabled() -> bool:
    return _ENABLED


def attach() -> StepLedger:
    """Register the ledger's tape grad-ready callback on THIS thread
    (each simulated rank attaches itself — tape hooks are thread-local).
    Optional: the optimizer-step digests need no attachment. Idempotent
    per thread."""
    led = get_ledger()
    if getattr(_ATTACHED, "cb", None) is not None:
        return led
    from ..autograd import tape
    _ATTACHED.cb = tape.register_grad_ready_callback(led._on_grad_ready)
    return led


def detach():
    cb = getattr(_ATTACHED, "cb", None)
    if cb is None:
        return
    from ..autograd import tape
    tape.unregister_grad_ready_callback(cb)
    _ATTACHED.cb = None


def enable(mode=None, interval=None, capacity=None, store=None,
           grad_ready=False) -> StepLedger:
    """Build/replace the global ledger, register the ``ledger`` watchdog
    state provider and the built-in ``numerics_divergence`` alert rule.
    ``grad_ready=True`` also attaches the calling thread's tape hook
    (per-leaf local-grad digests); ``store=`` publishes committed rows
    to an elastic KV store."""
    global _ENABLED, _LEDGER
    with _MODULE_LOCK:
        if (_LEDGER is None or mode is not None or interval is not None
                or capacity is not None):
            _LEDGER = StepLedger(mode=mode, interval=interval,
                                 capacity=capacity)
    _ENABLED = True
    led = get_ledger()
    if store is not None:
        led.attach_store(store)
    if grad_ready:
        attach()
    from . import flight_recorder
    flight_recorder.register_state_provider("ledger", led.state)
    try:
        from .alerts import ThresholdRule, get_alert_engine
        eng = get_alert_engine()
        if "numerics_divergence" not in eng.rules:
            eng.add_rule(ThresholdRule(
                name="numerics_divergence",
                metric="paddle_ledger_divergent_steps",
                above=0, severity="page"))
    except Exception:
        pass           # alerting is optional; detection must still work
    return led


def disable():
    """Detach this thread and drop the module gate + state provider."""
    global _ENABLED
    _ENABLED = False
    detach()
    from . import flight_recorder
    flight_recorder.unregister_state_provider("ledger")


def reset():
    """Drop the ledger and its rows/streams (tests / between jobs)."""
    global _LEDGER
    detach()
    with _MODULE_LOCK:
        _LEDGER = None
    try:
        from .alerts import _ENGINE
        if _ENGINE is not None:
            _ENGINE.remove_rule("numerics_divergence")
    except Exception:
        pass


# -- wired call-site facades (each checks the module gate first) ------------


def record_optimizer_step(optimizer):
    if not _ENABLED:
        return
    get_ledger().record_optimizer_step(optimizer)


def note_stream_token(trace_id, attempt, token):
    if not _ENABLED or trace_id is None:
        return
    get_ledger().note_stream_token(trace_id, attempt, token)


def stream_digest(trace_id, attempt=None):
    if not _ENABLED or trace_id is None:
        return None
    return get_ledger().stream_digest(trace_id, attempt=attempt)


def attest_delivery(trace_id, attempt=None):
    if not _ENABLED or trace_id is None:
        return None
    return get_ledger().attest_delivery(trace_id, attempt=attempt)


def seal_handoff(blob):
    if not _ENABLED:
        return None
    return get_ledger().seal_handoff(blob)


def check_handoff(blob):
    if not _ENABLED:
        return None
    return get_ledger().check_handoff(blob)


def export_golden(path=None) -> str:
    return get_ledger().export_golden(path)


# ---------------------------------------------------------------------------
# cross-process tier: publish/gather over the flight-recorder KV path
# ---------------------------------------------------------------------------


def publish_ledger(store, rank=None) -> int:
    """Deposit every committed row for ``rank`` (caller's rank by
    default) under ``ledger/rank/<r>/<s>`` — same elastic-KV transport
    as ``flight_recorder.publish_snapshot``. Returns the row count."""
    from . import flight_recorder
    r = _rank() if rank is None else int(rank)
    rows = get_ledger().rows(rank=r)
    for row in rows:
        flight_recorder.publish_component_state(
            store, f"{KV_LEDGER_PREFIX}{r}/{row['step']}", row)
    return len(rows)


def gather_ledgers(store) -> dict:
    """{rank: {step: entries}} for every published ledger row."""
    from . import flight_recorder
    out: dict = {}
    for key, row in flight_recorder.gather_component_states(
            store, KV_LEDGER_PREFIX).items():
        if not isinstance(row, dict) or "entries" not in row:
            continue
        out.setdefault(int(row["rank"]), {})[int(row["step"])] = \
            row["entries"]
    return out


def compare_store(store):
    """Out-of-process comparator: gather every rank's published rows
    and return the first divergence (``{"step", "rank", "tensor",
    "digests"}``) across the steps every rank has published, else
    ``None``. Pure read — raising/alerting policy belongs to the
    caller (this is the multi-process analogue of the in-process
    comparator the thread simulator gets for free)."""
    by_rank = gather_ledgers(store)
    if len(by_rank) < 2:
        return None
    common = sorted(set.intersection(
        *[set(steps) for steps in by_rank.values()]))
    for s in common:
        div = first_divergence({r: by_rank[r][s] for r in by_rank})
        if div is not None:
            return dict(div, step=s)
    return None


def _env_truthy(v) -> bool:
    return v not in (None, "", "0", "false", "False", "no")


if _env_truthy(os.environ.get("PADDLE_LEDGER")):   # pragma: no cover
    enable()

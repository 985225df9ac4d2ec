"""Distributed flight recorder: per-rank post-mortem ring buffer,
collective sequence tracking, and hang/straggler diagnosis (ISSUE 3).

A hung collective or a straggling rank dies silently today — the wedged
chip hangs documented in ``ops/pallas/flash_attention.py`` leave no
trail. This module is the PyTorch-NCCL-flight-recorder analogue on the
PR 2 telemetry substrate:

* :class:`FlightRecorder` — a bounded ring buffer of recent spans, op
  dispatches and collective events, each stamped with wall time and the
  issuing rank (thread-rank simulator aware). Every collective gets a
  monotonically increasing per-rank **seq id** with entry/exit
  timestamps, so desync ("rank 3 never entered seq 41") is detectable
  after the fact instead of presenting as a bare hang.
* :class:`Watchdog` — a daemon thread that watches per-rank heartbeats
  (fed by ``TelemetryCallback`` and by every tracked collective); when a
  rank misses its deadline it dumps all-thread stacks, the ring buffer,
  a ``metrics()`` snapshot, in-flight collective state and registered
  subsystem state (e.g. the serving request queue) to one JSON debug
  file per rank, plus a cross-rank desync/straggler report when it can
  see more than one rank.
* cross-rank aggregation — :func:`publish_snapshot` /
  :func:`gather_metrics` ride any elastic KV store
  (``fleet/elastic/tcp_kv.py`` ``TcpKVStore`` or the in-process
  ``MemKVStore``) to merge per-rank snapshots, rank-labeled, into one
  registry view; :func:`merge_chrome_traces` unions per-rank span dumps
  into a single Chrome trace with one pid per rank; and
  :func:`straggler_report` computes per-collective entry-time skew.

Everything is stdlib-only and **zero overhead when disabled**: the
module-level gate (:func:`is_enabled`) is a plain bool check, and every
wired call site (collectives, the train-step heartbeat, the DataLoader
failure path) goes through a module function that returns immediately
when the gate is off.

Env flags: ``PADDLE_FLIGHT_RECORDER=1`` enables at import (with the
watchdog unless ``PADDLE_FLIGHT_WATCHDOG=0``);
``PADDLE_FLIGHT_DEADLINE_S`` (default 300), ``PADDLE_FLIGHT_CAPACITY``
(default 2048), ``PADDLE_FLIGHT_DIR`` (dump directory, default
``./flight_recorder``), ``PADDLE_METRICS_TEXT_PATH`` (the watchdog
periodically rewrites ``metrics_text()`` there for a scraper to tail).
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time
import traceback
from collections import deque

__all__ = [
    "FlightRecorder", "Watchdog", "get_flight_recorder", "enable",
    "disable", "is_enabled", "reset", "record_event", "heartbeat",
    "collective_begin", "collective_end", "register_state_provider",
    "unregister_state_provider", "desync_report", "straggler_report",
    "merge_rank_snapshots", "merge_chrome_traces", "publish_snapshot",
    "gather_snapshots", "gather_metrics", "KV_PREFIX",
    "DUMP_SCHEMA", "REPORT_SCHEMA",
]

DUMP_SCHEMA = "paddle_flight_recorder/1"
REPORT_SCHEMA = "paddle_flight_cross_report/1"
KV_PREFIX = "flight/rank/"

_ENABLED = False
_RECORDER: "FlightRecorder | None" = None
_WATCHDOG: "Watchdog | None" = None
_MODULE_LOCK = threading.Lock()
# subsystem state captured into every dump (name -> zero-arg callable);
# registration is independent of the recorder lifecycle so a serving
# engine started before enable() still shows up in the dump
_STATE_PROVIDERS: dict = {}


def _rank() -> int:
    """Issuing rank: thread-simulator rank when inside a simulated world,
    else the launch env's trainer id (0 for single-process)."""
    try:
        from ..distributed import simulator
        r = simulator.current_rank()
        if r is not None:
            return r
    except Exception:
        pass
    try:
        return int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    except ValueError:
        return 0


def _thread_stacks() -> dict:
    """Formatted stacks of every live thread (the post-hang 'where is
    everyone' view)."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out = {}
    for ident, frame in sys._current_frames().items():
        key = f"{names.get(ident, 'thread')}-{ident}"
        out[key] = traceback.format_stack(frame)
    return out


class FlightRecorder:
    """Bounded ring of recent events plus live collective-sequence and
    heartbeat state. All methods are thread-safe; events are plain dicts
    (JSON-ready) stamped with ``t`` (wall clock) and ``rank``."""

    def __init__(self, capacity: int = 2048):
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=self.capacity)
        self._seq: dict = {}          # rank -> last issued collective seq
        self._inflight: dict = {}     # (rank, seq) -> entry event (not exited)
        self._heartbeats: dict = {}   # rank -> monotonic ts of last liveness

    # -- generic events ------------------------------------------------------
    def record(self, kind: str, rank=None, **fields) -> dict:
        ev = {"t": time.time(), "rank": _rank() if rank is None else rank,
              "kind": kind}
        ev.update(fields)
        with self._lock:
            self._ring.append(ev)
        return ev

    def events(self, rank=None, kind=None) -> list:
        with self._lock:
            evs = list(self._ring)
        return [dict(e) for e in evs
                if (rank is None or e.get("rank") == rank)
                and (kind is None or e.get("kind") == kind)]

    def collective_events(self, by_rank: bool = False):
        evs = self.events(kind="collective")
        if not by_rank:
            return evs
        out: dict = {}
        for e in evs:
            out.setdefault(e["rank"], []).append(e)
        return out

    # -- liveness ------------------------------------------------------------
    def heartbeat(self, rank=None):
        self._heartbeats[_rank() if rank is None else rank] = time.monotonic()

    # -- collective sequence tracking ---------------------------------------
    def collective_begin(self, op: str, nbytes: int, group_ranks) -> dict:
        rank = _rank()
        now = time.time()
        with self._lock:
            seq = self._seq.get(rank, 0) + 1
            self._seq[rank] = seq
            ev = {"t": now, "rank": rank, "kind": "collective", "seq": seq,
                  "op": op, "bytes": int(nbytes),
                  "group": list(group_ranks), "t_enter": now, "t_exit": None}
            self._ring.append(ev)
            self._inflight[(rank, seq)] = ev
        self._heartbeats[rank] = time.monotonic()
        return ev

    def collective_end(self, ev: dict):
        if ev is None:
            return
        ev["t_exit"] = time.time()
        with self._lock:
            self._inflight.pop((ev["rank"], ev["seq"]), None)
        self._heartbeats[ev["rank"]] = time.monotonic()

    # -- snapshots / dumps ---------------------------------------------------
    def known_ranks(self) -> list:
        with self._lock:
            ranks = set(self._seq) | set(self._heartbeats)
            ranks.update(e.get("rank") for e in self._ring)
        ranks.discard(None)
        return sorted(ranks) or [_rank()]

    def snapshot(self, rank=None, max_events: int = 512) -> dict:
        """Per-rank JSON-ready snapshot (what :func:`publish_snapshot`
        ships over the KV store)."""
        r = _rank() if rank is None else rank
        with self._lock:
            evs = [dict(e) for e in self._ring if e.get("rank") == r]
            last_seq = self._seq.get(r, 0)
            inflight = [dict(e) for (rr, _), e in self._inflight.items()
                        if rr == r]
        from .telemetry import get_registry
        return {
            "schema": DUMP_SCHEMA, "rank": r, "unix_time": time.time(),
            "last_seq": last_seq,
            "in_flight": inflight,
            "events": evs[-max_events:],
            "collectives": [e for e in evs
                            if e.get("kind") == "collective"][-max_events:],
            "metrics": get_registry().collect(),
        }

    def _provider_state(self) -> dict:
        state = {}
        for name, fn in list(_STATE_PROVIDERS.items()):
            try:
                state[name] = fn()
            except Exception as e:       # a dump must never die on a probe
                state[name] = {"error": repr(e)}
        return state

    def dump(self, reason: str = "manual", directory=None, stalled=None,
             deadline_s=None) -> dict:
        """Write one debug file per known rank plus (when more than one
        rank is visible, e.g. under the thread simulator) a cross-rank
        desync/straggler report. Returns ``{"ranks": {rank: path},
        "report": path | None}``."""
        directory = directory or os.environ.get("PADDLE_FLIGHT_DIR",
                                                "./flight_recorder")
        os.makedirs(directory, exist_ok=True)
        stacks = _thread_stacks()
        state = self._provider_state()
        try:
            from .telemetry import get_registry
            metrics_snap = get_registry().collect()
        except Exception:
            metrics_snap = {}
        ranks = self.known_ranks()
        paths: dict = {}
        for r in ranks:
            snap = self.snapshot(rank=r)
            snap.update({
                "reason": reason,
                "stalled_ranks": list(stalled) if stalled else [],
                "deadline_s": deadline_s,
                "thread_stacks": stacks,
                "state": state,
                "metrics": metrics_snap,
            })
            path = os.path.join(directory, f"flight_rank{r}.json")
            with open(path, "w") as f:
                json.dump(snap, f)
            paths[r] = path
        report_path = None
        if len(ranks) > 1:
            by_rank = self.collective_events(by_rank=True)
            report = {
                "schema": REPORT_SCHEMA, "reason": reason,
                "unix_time": time.time(),
                "stalled_heartbeat_ranks": (sorted(stalled)
                                            if stalled else []),
                "desync": desync_report(by_rank, world=ranks),
                "straggler": straggler_report(by_rank),
            }
            report_path = os.path.join(directory, "flight_cross_report.json")
            with open(report_path, "w") as f:
                json.dump(report, f)
        return {"ranks": paths, "report": report_path}


class Watchdog:
    """Heartbeat monitor: when any tracked rank goes quiet past
    ``deadline_s``, dump the recorder once (latched; re-arms when every
    rank is fresh again). Optionally rewrites ``metrics_text()`` to a
    file on each poll so a scraper can tail it."""

    def __init__(self, recorder: FlightRecorder, deadline_s: float = 300.0,
                 poll_s=None, dump_dir=None, metrics_text_path=None):
        self.recorder = recorder
        self.deadline_s = float(deadline_s)
        self.poll_s = (max(self.deadline_s / 4.0, 0.05)
                       if poll_s is None else float(poll_s))
        self.dump_dir = dump_dir
        self.metrics_text_path = metrics_text_path or os.environ.get(
            "PADDLE_METRICS_TEXT_PATH")
        self.last_dump = None
        self._fired = False
        self._stop = threading.Event()
        self._thread = None

    def start(self):
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._thread = threading.Thread(target=self._loop, daemon=True,
                                            name="paddle-flight-watchdog")
            self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None

    def write_metrics_text(self):
        if not self.metrics_text_path:
            return
        try:
            from .telemetry import metrics_text
            # write-tmp-then-replace with a WRITER-UNIQUE tmp name: two
            # watchdogs (or a watchdog racing a manual rewrite) must
            # never interleave writes into one tmp file and publish the
            # torn result — a scraper tailing the path may read a
            # complete exposition or the previous one, never a truncated
            # body
            tmp = (f"{self.metrics_text_path}.tmp."
                   f"{os.getpid()}.{threading.get_ident()}")
            with open(tmp, "w") as f:
                f.write(metrics_text())
            os.replace(tmp, self.metrics_text_path)
        except Exception:
            pass                   # a metrics dump must never kill the dog

    def check(self, now=None) -> list:
        """One poll: returns the currently-stale ranks, dumping once per
        stall episode."""
        now = time.monotonic() if now is None else now
        hb = dict(self.recorder._heartbeats)
        stale = sorted(r for r, t in hb.items() if now - t > self.deadline_s)
        if stale and not self._fired:
            self._fired = True
            self.last_dump = self.recorder.dump(
                reason=(f"watchdog: no heartbeat within "
                        f"{self.deadline_s:g}s from ranks {stale}"),
                directory=self.dump_dir, stalled=stale,
                deadline_s=self.deadline_s)
        elif not stale:
            self._fired = False    # everyone fresh again: re-arm
        return stale

    def _loop(self):
        while not self._stop.wait(self.poll_s):
            self.write_metrics_text()
            self.check()


# ---------------------------------------------------------------------------
# module facade (the wired call sites go through these; all are a plain
# bool check when disabled)
# ---------------------------------------------------------------------------


def get_flight_recorder() -> FlightRecorder:
    global _RECORDER
    if _RECORDER is None:
        with _MODULE_LOCK:
            if _RECORDER is None:
                try:
                    cap = int(os.environ.get("PADDLE_FLIGHT_CAPACITY", 2048))
                except ValueError:
                    cap = 2048
                _RECORDER = FlightRecorder(capacity=cap)
    return _RECORDER


def is_enabled() -> bool:
    return _ENABLED


def enable(capacity=None, watchdog=False, deadline_s=None, poll_s=None,
           dump_dir=None, metrics_text_path=None) -> FlightRecorder:
    """Turn recording on (and optionally start the watchdog)."""
    global _ENABLED, _WATCHDOG
    fr = get_flight_recorder()
    if capacity is not None and int(capacity) != fr.capacity:
        with fr._lock:
            fr.capacity = int(capacity)
            fr._ring = deque(fr._ring, maxlen=fr.capacity)
    _ENABLED = True
    if watchdog:
        if deadline_s is None:
            try:
                deadline_s = float(
                    os.environ.get("PADDLE_FLIGHT_DEADLINE_S", 300.0))
            except ValueError:
                deadline_s = 300.0
        with _MODULE_LOCK:
            if _WATCHDOG is not None:
                _WATCHDOG.stop()
            _WATCHDOG = Watchdog(fr, deadline_s=deadline_s, poll_s=poll_s,
                                 dump_dir=dump_dir,
                                 metrics_text_path=metrics_text_path).start()
    return fr


def disable():
    global _ENABLED, _WATCHDOG
    _ENABLED = False
    with _MODULE_LOCK:
        if _WATCHDOG is not None:
            _WATCHDOG.stop()
            _WATCHDOG = None


def get_watchdog() -> "Watchdog | None":
    return _WATCHDOG


def reset():
    """Drop all recorded state (tests / between jobs). Keeps the enabled
    flag and state providers."""
    global _RECORDER
    with _MODULE_LOCK:
        _RECORDER = None


def record_event(kind: str, **fields):
    # tee into the structured event log (ISSUE 15) independently of the
    # ring gate: controller actions, alert firings and replica deaths
    # must survive the process even when the flight ring is off
    from . import eventlog as _eventlog
    if _eventlog.is_enabled():
        _eventlog.log_event(kind, **fields)
    if not _ENABLED:
        return None
    return get_flight_recorder().record(kind, **fields)


def heartbeat(rank=None):
    if not _ENABLED:
        return
    get_flight_recorder().heartbeat(rank)


def collective_begin(op: str, nbytes: int, group_ranks):
    if not _ENABLED:
        return None
    return get_flight_recorder().collective_begin(op, nbytes, group_ranks)


def collective_end(ev):
    if ev is not None:
        get_flight_recorder().collective_end(ev)


def register_state_provider(name: str, fn):
    """``fn()`` -> JSON-able dict captured into every dump (e.g. the
    serving engine's request-queue state)."""
    _STATE_PROVIDERS[name] = fn


def unregister_state_provider(name: str):
    _STATE_PROVIDERS.pop(name, None)


# ---------------------------------------------------------------------------
# cross-rank analysis (pure functions over per-rank collective events)
# ---------------------------------------------------------------------------


def _pctl(sorted_vals, p):
    if not sorted_vals:
        return 0.0
    k = max(0, min(len(sorted_vals) - 1,
                   int(round((p / 100.0) * (len(sorted_vals) - 1)))))
    return sorted_vals[k]


def desync_report(events_by_rank: dict, world=None) -> dict:
    """Detect sequence desync across ranks.

    ``events_by_rank``: {rank: [collective event dicts]} (each event has
    ``seq``/``op``/``bytes``). ``world``: optional full rank list so
    ranks with NO events at all are reported too. Returns the frontier
    seq (max entered anywhere), per-rank last seq, the ranks stuck
    behind the frontier (with the first seq they never entered and what
    that collective was on the ranks that did enter it), and per-seq
    op/byte mismatches."""
    ranks = sorted(set(events_by_rank) | set(world or []))
    by_seq: dict = {}
    last = {}
    for r in ranks:
        evs = events_by_rank.get(r, [])
        last[r] = max((e.get("seq", 0) for e in evs), default=0)
        for e in evs:
            by_seq.setdefault(e.get("seq"), {})[r] = e
    frontier = max(last.values(), default=0)
    stalled = []
    for r in ranks:
        if last[r] < frontier:
            missing = last[r] + 1
            peer = next(iter(by_seq.get(missing, {}).values()), {})
            stalled.append({
                "rank": r, "last_seq": last[r], "missing_seq": missing,
                "op": peer.get("op"), "bytes": peer.get("bytes"),
                "entered_by": sorted(by_seq.get(missing, {})),
            })
    mismatches = []
    for seq in sorted(by_seq):
        sigs = {r: (e.get("op"), e.get("bytes"))
                for r, e in by_seq[seq].items()}
        if len(set(sigs.values())) > 1:
            mismatches.append({
                "seq": seq,
                "detail": {r: {"op": op, "bytes": b}
                           for r, (op, b) in sorted(sigs.items())},
            })
    return {"ranks": ranks, "frontier_seq": frontier, "last_seq": last,
            "stalled": stalled, "mismatches": mismatches}


def straggler_report(events_by_rank: dict, percentiles=(50, 95, 99)) -> dict:
    """Per-collective entry-time skew: for every seq that more than one
    rank entered, the lag of each rank behind the earliest entrant.
    Reports slowest-rank lag percentiles overall and per op kind, plus
    per-rank mean/max lag and the worst offender."""
    by_seq: dict = {}
    for r, evs in events_by_rank.items():
        for e in evs:
            if e.get("t_enter") is not None:
                by_seq.setdefault(e.get("seq"), {})[r] = e
    skews = []                      # (seq, op, skew, slowest_rank)
    per_rank: dict = {}
    for seq, entries in by_seq.items():
        if len(entries) < 2:
            continue
        t0 = min(e["t_enter"] for e in entries.values())
        slowest_rank, skew = None, 0.0
        op = next(iter(entries.values())).get("op")
        for r, e in entries.items():
            lag = e["t_enter"] - t0
            per_rank.setdefault(r, []).append(lag)
            if lag >= skew:
                skew, slowest_rank = lag, r
        skews.append((seq, op, skew, slowest_rank))
    all_skews = sorted(s for _, _, s, _ in skews)
    by_op: dict = {}
    for _, op, s, slow in skews:
        by_op.setdefault(op, []).append((s, slow))
    op_stats = {}
    for op, pairs in by_op.items():
        vals = sorted(s for s, _ in pairs)
        worst = max(pairs, key=lambda p: p[0])
        op_stats[str(op)] = {
            "count": len(vals),
            **{f"p{p}_s": _pctl(vals, p) for p in percentiles},
            "max_s": vals[-1], "slowest_rank": worst[1],
        }
    rank_stats = {
        r: {"mean_s": sum(v) / len(v), "max_s": max(v), "n": len(v)}
        for r, v in per_rank.items() if v
    }
    slowest = max(rank_stats, key=lambda r: rank_stats[r]["mean_s"],
                  default=None)
    return {
        "n_seqs": len(skews),
        "skew_percentiles": {f"p{p}": _pctl(all_skews, p)
                             for p in percentiles},
        "max_skew_s": all_skews[-1] if all_skews else 0.0,
        "by_op": op_stats,
        "per_rank_lag": rank_stats,
        "slowest_rank": slowest,
    }


# ---------------------------------------------------------------------------
# cross-rank aggregation over the elastic KV store
# ---------------------------------------------------------------------------


def publish_snapshot(store, rank=None) -> dict:
    """Deposit this rank's flight snapshot (metrics + collective state)
    under ``flight/rank/<r>`` in any elastic KV store (``TcpKVStore`` /
    ``MemKVStore`` / ``FileKVStore``)."""
    snap = get_flight_recorder().snapshot(rank=rank)
    store.put(f"{KV_PREFIX}{snap['rank']}", snap)
    return snap


def publish_component_state(store, name, state) -> dict:
    """Deposit one named component's state dict into an elastic KV store
    — the serving fleet's replica-heartbeat path (same transport as
    :func:`publish_snapshot`; the store's own value timestamp makes TTL
    liveness checks via ``store.age`` work unchanged)."""
    payload = {"component": name, "state": state}
    if _ENABLED:
        # straight to the ring, NOT record_event: per-heartbeat publish
        # traffic must not flood the structured event log
        get_flight_recorder().record("component_state", component=name)
    store.put(name, payload)
    return payload


def gather_component_states(store, prefix) -> dict:
    """{key: state} for every component published under ``prefix``."""
    out = {}
    for key in store.keys(prefix):
        v = store.get(key)
        if isinstance(v, dict) and "component" in v:
            out[key] = v.get("state")
    return out


def gather_snapshots(store) -> dict:
    """{rank: snapshot} for every rank that published."""
    out = {}
    for key in store.keys(KV_PREFIX):
        v = store.get(key)
        if isinstance(v, dict) and "rank" in v:
            out[int(v["rank"])] = v
    return out


def merge_rank_snapshots(metrics_by_rank: dict) -> dict:
    """Union per-rank ``MetricRegistry.collect()`` dicts into ONE
    registry view: every family gains a leading ``rank`` label and each
    rank's series ride side by side."""
    merged: dict = {}
    for rank in sorted(metrics_by_rank):
        for name, fam in (metrics_by_rank[rank] or {}).items():
            m = merged.setdefault(name, {
                "type": fam.get("type", "untyped"),
                "help": fam.get("help", ""),
                "label_names": ["rank"] + list(fam.get("label_names", [])),
                "series": {},
            })
            for key, val in fam.get("series", {}).items():
                m["series"][f"{rank},{key}" if key else str(rank)] = val
    return merged


def gather_metrics(store=None) -> dict:
    """Cross-rank registry view. With a KV ``store``, merges every
    published rank snapshot (:func:`publish_snapshot`) rank-labeled into
    one view and attaches desync/straggler analysis; with no store,
    returns the local recorder's view (single rank)."""
    if store is None:
        fr = get_flight_recorder()
        snaps = {r: fr.snapshot(rank=r) for r in fr.known_ranks()}
    else:
        snaps = gather_snapshots(store)
    events_by_rank = {r: s.get("collectives", []) for r, s in snaps.items()}
    return {
        "ranks": sorted(snaps),
        "last_seq": {r: s.get("last_seq", 0) for r, s in snaps.items()},
        "merged": merge_rank_snapshots(
            {r: s.get("metrics", {}) for r, s in snaps.items()}),
        "desync": desync_report(events_by_rank),
        "straggler": straggler_report(events_by_rank),
    }


# ---------------------------------------------------------------------------
# chrome trace merging
# ---------------------------------------------------------------------------


def merge_chrome_traces(traces_by_rank: dict) -> dict:
    """Union per-rank (or per-replica) chrome traces into one: every
    event's ``pid`` becomes its lane key (plus a ``process_name``
    metadata event per lane), so Perfetto shows one process lane per
    rank/replica.

    Request flows: any merged event carrying ``args.trace_id`` (the
    per-request spans ``request_trace.timeline_to_chrome`` emits) is
    linked to the other events of the same trace_id with chrome flow
    events (``ph`` s/t/f, ``id`` = trace_id) — a disaggregated request
    renders as ONE arrow-connected flow from its prefill lane through
    the handoff to its decode lane.

    ``traces_by_rank``: {rank: trace dict | traceEvents list | path}."""
    events = []
    # ints (ranks) sort numerically, strings (replica lanes) after
    for rank in sorted(traces_by_rank,
                       key=lambda r: ((0, r, "") if isinstance(r, int)
                                      else (1, 0, str(r)))):
        t = traces_by_rank[rank]
        if isinstance(t, (str, os.PathLike)):
            with open(t) as f:
                t = json.load(f)
        evs = t.get("traceEvents", []) if isinstance(t, dict) else t
        events.append({"name": "process_name", "ph": "M", "pid": rank,
                       "tid": 0, "args": {"name": f"rank {rank}"}})
        for e in evs:
            e = dict(e)
            e["pid"] = rank
            events.append(e)
    flows: dict = {}
    for e in events:
        tid_ = (e.get("args") or {}).get("trace_id")
        if tid_ is not None and e.get("ph", "X") == "X":
            flows.setdefault(str(tid_), []).append(e)
    flow_events = []
    for trace_id, evs in sorted(flows.items()):
        if len(evs) < 2:
            continue
        evs.sort(key=lambda e: e.get("ts", 0))
        last = len(evs) - 1
        for i, e in enumerate(evs):
            ph = "s" if i == 0 else ("f" if i == last else "t")
            fe = {"name": f"request {trace_id}", "cat": "request",
                  "ph": ph, "id": trace_id, "pid": e["pid"],
                  "tid": e.get("tid", 0), "ts": e.get("ts", 0)}
            if ph == "f":
                fe["bp"] = "e"
            flow_events.append(fe)
    return {"traceEvents": events + flow_events, "displayTimeUnit": "ms"}


# ---------------------------------------------------------------------------
# env auto-enable
# ---------------------------------------------------------------------------


def _env_truthy(v) -> bool:
    return v not in (None, "", "0", "false", "False", "no")


if _env_truthy(os.environ.get("PADDLE_FLIGHT_RECORDER")):   # pragma: no cover
    enable(watchdog=_env_truthy(
        os.environ.get("PADDLE_FLIGHT_WATCHDOG", "1")))

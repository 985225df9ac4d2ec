"""Bench record comparator: diff two ``bench.py`` records with
per-metric direction + threshold rules and exit 1 on regression.

The bench trajectory (``bench.py``'s one-line JSON, saved per run)
is only useful if a regression between two records is *mechanically*
detectable — a human eyeballing "26.1 vs 24.9 images/sec" does not
scale to the aux-metric surface (phase fractions, peak bytes, p95s,
speedups). This tool knows which direction each metric should move:

* direction is inferred from the metric name (``DIRECTION_RULES`` —
  ``*_per_sec``/``*speedup``/``mfu*``/``*recover_ratio*`` are
  higher-better, ``*_ms``/``*_bytes``/``*waste*``/``*overhead*``/
  ``*time_to_recover*`` are lower-better; ``*controller_actions*`` is
  an action COUNT — churn is workload-shaped, so it is informational);
  unknown metrics are reported as info, never failed;
* a metric regresses when it moves in the bad direction by more than
  the threshold (default 10%, per-metric overrides via
  ``--rule name=higher|lower[:pct]``);
* input records are ``bench.py`` output dicts, driver wrappers with a
  ``parsed``/``result`` key, or lists (last record wins); nested dicts
  flatten to dotted keys.

Usage::

    python tools/bench_compare.py OLD.json NEW.json
    python tools/bench_compare.py --threshold 5 --html diff.html A.json B.json
    python tools/bench_compare.py --rule train_peak_bytes=lower:25 A.json B.json

Exit codes: 0 ok, 1 regression(s), 2 usage/input error. Same import
discipline as ``fleet_console.py``: stdlib-only, no jax/numpy — this
runs on a laptop against records scp'd off the fleet.
"""
from __future__ import annotations

import argparse
import html as _html
import json
import os
import sys

DEFAULT_THRESHOLD_PCT = 10.0

#: (substring, direction) — first match wins, checked in order. More
#: specific entries go first (``waste_ratio`` before ``ratio``).
DIRECTION_RULES = [
    ("telemetry_export_overhead", "lower"),
    ("scrape_age", "lower"),
    ("overhead_pct", "lower"),
    # steady-state serving recompiles must be ZERO; any rise is shape
    # churn past the declared buckets (warmup compile seconds are the
    # cold-start budget — also lower-better, via the _s suffix rule)
    ("recompiles_per_1k", "lower"),
    ("post_warmup_misses", "lower"),
    ("waste_ratio", "lower"),
    ("qblock_step_ratio", "lower"),
    ("weight_bytes_ratio", "lower"),
    ("forwards_per_token", "lower"),
    ("forwards_per_tick", "lower"),
    ("recover_ratio", "higher"),
    # tiered KV / long-context serving: host-tier TTFT win on prefix
    # re-admission and sep-prefill prompt throughput are the point of
    # the tier — both must not sink (explicit entries so they never
    # fall through to a suffix rule)
    ("kv_tier_hit_speedup", "higher"),
    ("long_context_tokens_per_s", "higher"),
    ("kv_tier_ttft", "lower"),
    ("controller_actions", "ignore"),
    ("time_to_recover", "lower"),
    ("wire_bytes", "lower"),
    ("peak_bytes", "lower"),
    ("per_sec", "higher"),
    ("per_s", "higher"),
    ("throughput", "higher"),
    ("tokens_per", "higher"),
    ("samples_per", "higher"),
    ("images/sec", "higher"),
    ("speedup", "higher"),
    ("goodput", "higher"),
    ("hit_rate", "higher"),
    ("acceptance", "higher"),
    ("mfu", "higher"),
    ("capacity_ratio", "higher"),
    ("compression_ratio", "higher"),
    ("sessions", "higher"),
]

#: (suffix, direction) — matched against the END of the name only, so
#: ``_s`` catches ``p99_latency_s`` without hijacking ``tokens_per_sec``
SUFFIX_RULES = [
    ("_bytes", "lower"),
    ("_ms", "lower"),
    ("_seconds", "lower"),
    ("_s", "lower"),
]

#: metric names that are configuration echoes, never judged
SKIP_KEYS = {"vs_baseline", "seed", "steps", "workers", "dp", "n",
             "rc", "value"}


def direction_of(name: str) -> "str | None":
    low = name.lower()
    for sub, d in DIRECTION_RULES:
        if sub in low:
            return d
    for suf, d in SUFFIX_RULES:
        if low.endswith(suf):
            return d
    return None


def load_record(path: str) -> dict:
    """Load one bench record: a flat bench.py dict, a driver wrapper
    ({"parsed": ...} / {"result": ...}), or a list (last wins)."""
    with open(path) as f:
        data = json.load(f)
    if isinstance(data, list):
        data = data[-1] if data else {}
    for key in ("parsed", "result"):
        if isinstance(data, dict) and isinstance(data.get(key), dict):
            data = data[key]
    if not isinstance(data, dict):
        raise ValueError(f"{path}: not a bench record")
    return data


def flatten(rec: dict, prefix="") -> dict:
    """Numeric leaves as dotted keys. The headline ``value`` is keyed
    by the record's ``metric`` name so direction inference applies to
    what the number *is*, not to the word 'value'. String leaves named
    ``*_digest`` (content digests, e.g. ``serving_token_digest``) are
    kept too — they compare exact-match, so output-content drift fails
    the diff like a perf regression would."""
    out: dict = {}
    metric = rec.get("metric") if not prefix else None
    for k, v in rec.items():
        if k in SKIP_KEYS and not (k == "value" and metric):
            continue
        key = f"{prefix}{k}"
        if k == "value" and metric:
            key = str(metric)
        if isinstance(v, bool):
            continue
        if isinstance(v, (int, float)):
            out[key] = float(v)
        elif isinstance(v, str) and k.lower().endswith("_digest"):
            out[key] = v
        elif isinstance(v, dict):
            out.update(flatten(v, prefix=f"{key}."))
    return out


def parse_rule_overrides(specs) -> dict:
    """``--rule name=higher|lower[:pct]`` → {name: (direction, pct)}."""
    rules = {}
    for spec in specs or ():
        name, _, rest = spec.partition("=")
        if not name or not rest:
            raise ValueError(f"bad --rule {spec!r} "
                             "(want name=higher|lower[:pct])")
        d, _, pct = rest.partition(":")
        if d not in ("higher", "lower", "ignore"):
            raise ValueError(f"bad direction in --rule {spec!r} "
                             "(higher/lower/ignore)")
        rules[name] = (d, float(pct) if pct else None)
    return rules


def compare(old: dict, new: dict, threshold_pct=DEFAULT_THRESHOLD_PCT,
            overrides=None) -> list:
    """Row per metric present in BOTH records:
    ``{metric, old, new, delta_pct, direction, status}`` where status is
    ``ok`` / ``improved`` / ``REGRESSED`` / ``info`` (no direction)."""
    overrides = overrides or {}
    a, b = flatten(old), flatten(new)
    rows = []
    for name in sorted(set(a) & set(b)):
        va, vb = a[name], b[name]
        direction, pct = overrides.get(
            name, (direction_of(name), None))
        if isinstance(va, str) or isinstance(vb, str):
            # content digests: exact match or regression — no threshold
            status = ("info" if direction == "ignore"
                      else "ok" if va == vb else "REGRESSED")
            rows.append({"metric": name, "old": va, "new": vb,
                         "delta_pct": 0.0 if va == vb else 100.0,
                         "direction": "exact", "threshold_pct": 0.0,
                         "status": status})
            continue
        pct = threshold_pct if pct is None else pct
        if va == 0:
            delta = 0.0 if vb == 0 else float("inf") * (1 if vb > 0 else -1)
        else:
            delta = (vb - va) / abs(va) * 100.0
        if direction in (None, "ignore"):
            status = "info"
        else:
            worse = -delta if direction == "higher" else delta
            if worse > pct:
                status = "REGRESSED"
            elif worse < -pct:
                status = "improved"
            else:
                status = "ok"
        rows.append({"metric": name, "old": va, "new": vb,
                     "delta_pct": delta, "direction": direction or "?",
                     "threshold_pct": pct, "status": status})
    return rows


def fmt(v) -> str:
    if isinstance(v, float):
        return f"{v:.6g}"
    return str(v)


def render_text(rows, old_path, new_path) -> str:
    out = [f"bench compare: {os.path.basename(old_path)} -> "
           f"{os.path.basename(new_path)}"]
    if not rows:
        out.append("(no comparable numeric metrics)")
        return "\n".join(out) + "\n"
    w = max(len(r["metric"]) for r in rows)
    for r in rows:
        d = ("+inf" if r["delta_pct"] == float("inf")
             else f"{r['delta_pct']:+.2f}%")
        out.append(f"{r['status']:<10} {r['metric']:<{w}}  "
                   f"{fmt(r['old'])} -> {fmt(r['new'])}  ({d}, "
                   f"{r['direction']} better, thr {r['threshold_pct']:g}%)")
    bad = [r for r in rows if r["status"] == "REGRESSED"]
    out.append(f"{len(rows)} metric(s) compared, {len(bad)} regression(s)")
    return "\n".join(out) + "\n"


def render_html(rows, old_path, new_path) -> str:
    def esc(x):
        return _html.escape(str(x))

    parts = ["<!doctype html><html><head><meta charset='utf-8'>",
             "<title>bench compare</title><style>",
             "body{font-family:monospace;background:#111;color:#ddd;"
             "padding:1em}",
             "table{border-collapse:collapse}",
             "td,th{padding:2px 10px;text-align:left;"
             "border-bottom:1px solid #333}",
             ".REGRESSED{color:#f66;font-weight:bold}",
             ".improved{color:#6f6}",
             ".info{color:#888}",
             "</style></head><body>",
             f"<h1>bench compare</h1><p>{esc(os.path.basename(old_path))}"
             f" &rarr; {esc(os.path.basename(new_path))}</p>",
             "<table><tr><th>status</th><th>metric</th><th>old</th>"
             "<th>new</th><th>delta</th><th>direction</th></tr>"]
    for r in rows:
        d = ("+inf" if r["delta_pct"] == float("inf")
             else f"{r['delta_pct']:+.2f}%")
        parts.append(
            f"<tr class='{esc(r['status'])}'><td>{esc(r['status'])}</td>"
            f"<td>{esc(r['metric'])}</td><td>{fmt(r['old'])}</td>"
            f"<td>{fmt(r['new'])}</td><td>{esc(d)}</td>"
            f"<td>{esc(r['direction'])}</td></tr>")
    parts.append("</table></body></html>")
    return "".join(parts)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="compare two bench JSON records; exit 1 on regression")
    ap.add_argument("old", help="baseline bench record (JSON)")
    ap.add_argument("new", help="candidate bench record (JSON)")
    ap.add_argument("--threshold", type=float,
                    default=DEFAULT_THRESHOLD_PCT,
                    help="regression threshold in percent (default 10)")
    ap.add_argument("--rule", action="append", metavar="NAME=DIR[:PCT]",
                    help="per-metric override, e.g. "
                         "train_peak_bytes=lower:25 or foo=ignore")
    ap.add_argument("--html", metavar="PATH",
                    help="also write an HTML diff table")
    args = ap.parse_args(argv)
    try:
        old = load_record(args.old)
        new = load_record(args.new)
        overrides = parse_rule_overrides(args.rule)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"bench_compare: {e}", file=sys.stderr)
        return 2
    rows = compare(old, new, threshold_pct=args.threshold,
                   overrides=overrides)
    if not rows:
        print("bench_compare: no comparable numeric metrics",
              file=sys.stderr)
        return 2
    sys.stdout.write(render_text(rows, args.old, args.new))
    if args.html:
        with open(args.html, "w") as f:
            f.write(render_html(rows, args.old, args.new))
    return 1 if any(r["status"] == "REGRESSED" for r in rows) else 0


if __name__ == "__main__":
    sys.exit(main())

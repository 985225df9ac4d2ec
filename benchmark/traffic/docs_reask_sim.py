"""A tick-level simulation of ``docs_reask`` traffic under the engine's
ragged packing, for the questions a chip run is too dear to ask many times:
where in the closed loop's oscillating delivery rate a window ends, by how
much the window's count multiplies a difference of speed there, and what a
race at admission does to it. No device, no model: a tick costs
``base_ms + ns_per_pair * (query token x context token pairs)``, a decode
row counted as ``decode_rows`` query rows (its q-block), which reproduced
the chip's nine windows of one stretch within 3 % (PERF.md section 6,
PR 27). The packing is ``inference/serving.py::_serve_ragged``'s: every
live decode row one token, then the FIFO prefill queue in order, each row
up to ``chunk`` tokens, while the budget lasts; a re-ask finds its document
cached; a request is admitted at the first tick that starts after it
arrived, ``turnaround_s`` after its reply, one tick's in the clients'
order (``order_noise_s`` > 0 makes that order and tick a race again).

``python3 -m benchmark.traffic.docs_reask_sim [traffic.json]`` prints the
rate by 5 s, the windows, and the count's response to speed.
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np

from benchmark.traffic import docs_reask


def plan_lengths(traffic):
    """[client][request] -> (prompt tokens, new tokens, first ask?)."""
    plan, asks = docs_reask.docs_reask_requests(traffic, 1, 1 << 14)
    return [[(len(prompt), new, asks[c][i] == 0)
             for i, (prompt, new) in enumerate(reqs)]
            for c, reqs in enumerate(plan)]


def simulate(lengths, *, speed=1.0, base_ms=35.0, ns_per_pair=9.2,
             decode_rows=8, budget=512, chunk=512, doc_grid=256,
             question_min=32, turnaround_s=0.012, order_noise_s=0.0,
             tick_jitter=0.0, horizon_s=60.0, seed=0):
    """-> (seconds of the ramp, stamps of the tokens delivered after it,
    counted from the window's opening: every client's first request done)."""
    rng = np.random.default_rng(seed)
    n = len(lengths)
    nxt = [0] * n
    state = [None] * n
    cached = [0] * n                      # tokens of the client's document
    prefill_q, arrivals = [], [(0.0, c) for c in range(n)]
    t, ramps, t_open, stamps = 0.0, 0, None, []
    while t_open is None or t - t_open <= horizon_s:
        arrivals.sort()
        while arrivals and arrivals[0][0] <= t:
            _, c = arrivals.pop(0)
            if nxt[c] < len(lengths[c]):
                total, new, first = lengths[c][nxt[c]]
                state[c] = {"len": total, "new": new, "first": first,
                            "pos": 0 if first else cached[c], "out": 0,
                            "ramp": nxt[c] == 0}
                prefill_q.append(c)
                nxt[c] += 1
        decode = [c for c in range(n) if state[c] is not None
                  and state[c]["pos"] >= state[c]["len"]]
        room, spans = budget - len(decode), []
        for c in prefill_q:
            k = min(chunk, state[c]["len"] - state[c]["pos"], room)
            if k <= 0:
                break
            spans.append((c, state[c]["pos"], k))
            room -= k
        if not decode and not spans:
            if not arrivals:
                break
            t = max(t, arrivals[0][0])
            continue
        pairs = sum(k * (p + k / 2) for _, p, k in spans) + decode_rows * sum(
            state[c]["len"] + state[c]["out"] for c in decode)
        tokens = budget - room
        cost = base_ms - 8.0 * max(0.0, 1 - tokens / 256)   # small buckets
        t += (cost + ns_per_pair * 1e-6 * pairs) * speed * (
            1 + tick_jitter * rng.standard_normal()) / 1e3
        emit = list(decode)
        for c, _, k in spans:
            state[c]["pos"] += k
            if state[c]["pos"] >= state[c]["len"]:
                prefill_q.remove(c)
                emit.append(c)
        for c in sorted(emit):
            s = state[c]
            s["out"] += 1
            if t_open is not None:
                stamps.append(t - t_open)
            if s["out"] < s["new"]:
                continue
            if s["first"]:                # the document: on its grid
                cached[c] = (s["len"] - question_min) // doc_grid * doc_grid
            state[c] = None
            arrivals.append((t + turnaround_s + 1e-6 * c + order_noise_s
                             * abs(rng.standard_normal()), c))
            if s["ramp"]:
                ramps += 1
                if ramps == n:
                    t_open = t
    return t_open, np.asarray(stamps)


def window_rate(stamps, start, length):
    return float(((stamps > start) & (stamps <= start + length)).sum()
                 / length)


def speed_response(lengths, speeds, seconds=50.0, **kw):
    """-> (tokens/s of the window at each speed factor, the local factor by
    which the count multiplies a difference of speed: 1 where the rate at the
    window's end is the mean rate)."""
    rates = np.asarray([window_rate(simulate(
        lengths, speed=s, horizon_s=seconds + 5, **kw)[1], 0, seconds)
        for s in speeds])
    return rates, -np.gradient(np.log(rates), np.log(speeds))


def main(argv):
    here = os.path.dirname(os.path.abspath(__file__))
    path = argv[0] if argv else os.path.join(here,
                                             "docs_reask_closed_16.json")
    with open(path) as f:
        traffic = json.load(f)
    lengths = plan_lengths(traffic)
    kw = {"turnaround_s": traffic.get("turnaround_ms", 0) / 1e3}
    ramp, stamps = simulate(lengths, horizon_s=90, **kw)
    print(f"ramp {ramp:.1f} s; tokens/s by 5 s:",
          [round(window_rate(stamps, s, 5)) for s in range(0, 90, 5)])
    print("windows (start, length, tokens/s):",
          [(s, n, round(window_rate(stamps, s, n), 1))
           for s, n in ((0, 50), (5, 50), (10, 50), (20, 50), (0, 70))])
    speeds = np.arange(0.80, 1.2001, 0.02)
    rates, factor = speed_response(lengths, speeds, **kw)
    for s, r, a in zip(speeds, rates, factor):
        print(f"speed x{s:.2f}: {r:6.1f} tokens/s, a speed difference "
              f"counts x{a:.2f}")


if __name__ == "__main__":
    main(sys.argv[1:])

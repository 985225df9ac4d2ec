"""The one general traffic generator. A traffic mix is a data file
(``traffic/<mix>.json``) of parameters; these functions turn it and
``--seed`` into inputs. The program sees only what they return.

Every seed gets the same amount of work: the same shapes for training, the
same lengths in the same places for serving, with other token ids.
"""
from __future__ import annotations

import math
import statistics

import numpy as np


def token_rows(traffic, seed, vocab, batch):
    """Training feed: an endless stream of [batch, seq + 1] token ids, new
    and all different every step (``kind: token_rows``)."""
    if traffic["kind"] != "token_rows":
        raise ValueError(f"not a training feed: {traffic['kind']!r}")
    rng = np.random.default_rng([int(seed), 1])
    while True:
        yield rng.integers(0, vocab, (batch, traffic["seq"] + 1))


def lognormal_grid(n, median, sigma, lo, hi):
    """``n`` lengths at the mid-quantiles of a log-normal, clipped: the same
    multiset whatever the seed."""
    nd = statistics.NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        out.append(int(min(max(round(median * math.exp(sigma * z)), lo), hi)))
    return out


def closed_loop_requests(traffic, seed, vocab):
    """Serving, closed loop (``kind: closed_loop``): for each client its
    list of (prompt ids, new tokens). Every round (the clients' i-th
    requests) holds the same lengths, one a client, from fixed quantile grids
    of the mix's distributions. Which client gets which is dealt anew each
    round from the mix's ``deal_seed``: the schedule is the mix's, a piece of
    data like its lengths, and ``--seed`` draws the token ids (and the
    weights). Dealt by ``--seed`` instead, five seeds read 30.0 to 36.4
    tokens/s in a window that holds one round (PERF.md, PR 24, call 18),
    which no bound holds; another schedule is another mix file. ``ramp``
    gives each client a first short request whose length staggers the
    clients' phases."""
    if traffic["kind"] != "closed_loop":
        raise ValueError(f"not closed-loop traffic: {traffic['kind']!r}")
    rng = np.random.default_rng([int(seed), 2])
    deal = np.random.default_rng([int(traffic["deal_seed"]), 4])
    clients, rounds = traffic["clients"], traffic["requests_per_client"]
    pl, ol = traffic["prompt_len"], traffic["output_len"]
    prompts = lognormal_grid(clients, pl["median"], pl["sigma"], pl["min"],
                             pl["max"])
    outputs = lognormal_grid(clients, ol["median"], ol["sigma"], ol["min"],
                             ol["max"])
    plan = [[] for _ in range(clients)]
    ramp = traffic.get("ramp")
    for c in range(clients):
        if ramp:
            new = ramp["output_min"] + (
                (ramp["output_max"] - ramp["output_min"]) * c) // max(
                    clients - 1, 1)
            plan[c].append((rng.integers(1, vocab, ramp["prompt_len"]),
                            int(new)))
    for _ in range(rounds):
        who_p, who_o = deal.permutation(clients), deal.permutation(clients)
        for c in range(clients):
            plan[c].append((rng.integers(1, vocab, int(prompts[who_p[c]])),
                            int(outputs[who_o[c]])))
    return plan


def arrivals(traffic, seed):
    """Open loop (``kind: open_loop``): the times, in seconds from the start,
    at which requests are due, after ``inference/fleet/replay.make_trace``'s
    two presets. ``poisson``: exponential gaps at ``rate_rps``. ``bursty``:
    the same, with the rate ``burst_factor`` times higher inside
    [``burst_start_frac``, ``burst_start_frac + burst_dur_frac``) of
    ``duration_s``. No cell uses it yet (PERF.md, Open questions)."""
    if traffic["kind"] != "open_loop":
        raise ValueError(f"not open-loop traffic: {traffic['kind']!r}")
    rng = np.random.default_rng([int(seed), 5])
    dur, rate = float(traffic["duration_s"]), float(traffic["rate_rps"])
    b0 = b1 = None
    if traffic["arrivals"] == "bursty":
        b0 = dur * traffic["burst_start_frac"]
        b1 = b0 + dur * traffic["burst_dur_frac"]
    elif traffic["arrivals"] != "poisson":
        raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
    out, t = [], 0.0
    while True:
        r = rate * (traffic["burst_factor"]
                    if b0 is not None and b0 <= t < b1 else 1.0)
        t += rng.exponential(1.0 / r)
        if t >= dur:
            return out
        out.append(t)

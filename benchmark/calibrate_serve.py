"""Readings that a serving cell's limits are set from, and the verdict on
each, many seeds in one process: ``benchmark.calibrate`` for any serving
driver (the traffic file names it), which that file, written for
``drivers/serve`` alone, is not. A seed's run goes through the cell's own
driver with the control switched on and is written as ONE line with the
reference's per-token readings, from which the driver's ``judged_rows``
makes the rows (the program; the control, that is the reference at int8 in
the program's place; an altered token) under the committed
``limits/<cell>.json``: the program's row has to come out correct, every
other row not. On the chip:
``python3 -m benchmark.calibrate_serve --workload <cell> --seeds 1,2,3
--control-seeds 1,2 --reading-seeds 1 --out chiprun_out/calib.jsonl``;
``--rejudge <file.jsonl>`` makes and judges the rows again from the recorded
readings, with the limits as they are now, and needs no chip. A driver
without ``judged_rows`` is judged on its run's ``checks`` and ``stand_ins``.
``--windows`` reads other windows out of one measured stretch (``--seconds``
long): (start, length) pairs, for the question of when a window should
open. ``--variant router_bf16`` runs the PROGRAM with its router's product
and scores in bf16: a reading of what that would do, judged like the
program and expected to be nothing. The benchmark's own runs never call
this. Limits go into ``limits/<cell>.json`` by hand, with the readings.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

from benchmark import harness
from benchmark.calibrate import row


def window_numbers(stamps, start, length):
    """``serve_tok_s`` and ``itl_mean_ms`` of the window (start, start +
    length] of a run, from every request's token stamps."""
    end = start + length
    delivered = sum(1 for r in stamps for t in r if start < t <= end)
    gaps = [b - a for r in stamps for a, b in zip(r, r[1:])
            if start < b <= end]
    return {"start": start, "length": length,
            "serve_tok_s": delivered / length,
            "itl_mean_ms": 1e3 * statistics.fmean(gaps) if gaps else None}


def rows_of(rec, driver, limits):
    """The judged rows of one recorded seed. A variant of the program and
    the reference's further readings are expected to be nothing."""
    cell, seed = rec["cell"], rec["seed"]
    if rec.get("gaps") and hasattr(driver, "judged_rows"):
        judged = driver.judged_rows(rec["gaps"], limits)
    else:
        judged = {who: [(n, v, limits[n]) for n, v in numbers.items()
                        if n in limits]
                  for who, numbers in rec["numbers"].items()}
    out = []
    for who, checks in judged.items():
        r = row(cell, seed, who, checks)
        if rec.get("variant"):
            r["who"], r["expected"] = f"{who}.{rec['variant']}", None
        out.append(r)
    return out


def wrong_rows(rows):
    for r in rows:
        print(json.dumps(r), flush=True)
    return sum(r["expected"] is not None and r["correct"] != r["expected"]
               for r in rows)


def router_bf16():
    """The program's router with its product and its scores in bf16."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.incubate.distributed.models.moe import held

    def route(x, w_router, bias, *, n_group, topk_group, top_k, scale,
              norm_topk=True):
        bf = jnp.bfloat16
        scores = jax.nn.sigmoid(jnp.matmul(x.astype(bf), w_router.astype(bf)))
        choice = (scores + bias.astype(bf)[None]).astype(jnp.float32)
        idx = held.group_limited_topk(choice, n_group, topk_group, top_k)
        w = jnp.take_along_axis(scores.astype(jnp.float32), idx, axis=-1)
        if norm_topk:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return idx.astype(jnp.int32), w * scale

    held.sigmoid_group_route = route


VARIANTS = {"router_bf16": router_bf16}


def one_seed(ctx, driver, seed, control, readings, windows, variant):
    run = driver.run(dict(ctx, seed=seed, trace=False,
                          control="int8" if control else None,
                          readings=readings))
    rec = {"cell": ctx["cell"]["name"], "seed": seed, "variant": variant,
           "numbers": dict(
               {"program": {n: v for n, v, _ in run["checks"]}},
               **{who: {n: v for n, v, _ in checks}
                  for who, checks in run["stand_ins"].items()}),
           "gaps": run.get("gaps"), "end_to_end": run["end_to_end"],
           "attempted": run["attempted"], "failed": run["failed"],
           "finished": run.get("finished"),
           "memory_peak_bytes": run["memory_peak_bytes"],
           "compiles_in_window": run["compiles_in_window"]}
    if run.get("token_stamps"):
        rec["windows"] = [window_numbers(run["token_stamps"], a, b)
                          for a, b in windows]
    if run.get("gaps"):
        rec["reading_gaps"] = {
            name: {"max": max(run["gaps"][name]),
                   "mean": statistics.fmean(run["gaps"][name])}
            for name in readings}
    return rec


def rejudge(path, manifest):
    wrong = 0
    with open(path) as f:
        for line in f:
            rec = json.loads(line)
            _, _, traffic = harness.find_cell(manifest, rec["cell"])
            limits = harness.load_json(os.path.join(
                harness.HERE, "limits", rec["cell"] + ".json"))
            wrong += wrong_rows(rows_of(
                rec, harness.load_driver(traffic["driver"]), limits))
    return wrong


def seeds_of(text):
    return [int(s) for s in text.split(",") if s]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rejudge", default=None)
    ap.add_argument("--workload")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--reading-seeds", default="")
    ap.add_argument("--readings", default="bf16,bf16_routed")
    ap.add_argument("--variant", choices=sorted(VARIANTS), default=None)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--windows", default="",
                    help="start:length,... in seconds of the stretch")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    manifest = harness.load_manifest()
    if args.rejudge:
        return 1 if rejudge(args.rejudge, manifest) else 0
    from benchmark import run as runmod
    ns = argparse.Namespace(workload=args.workload, seed=0,
                            seconds=args.seconds, trace=0, dump_trace=None)
    try:
        ctx = runmod.context(ns, manifest)
        device, ctx["peaks"] = harness.require_chips(ctx["chips"])
    except harness.Refused as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1
    ctx["watch"] = harness.CompileWatch()
    harness.enable_compile_cache()
    if args.variant:
        VARIANTS[args.variant]()
    driver = harness.load_driver(ctx["traffic"]["driver"])
    controls, reading = set(seeds_of(args.control_seeds)), set(
        seeds_of(args.reading_seeds))
    windows = [tuple(float(x) for x in w.split(":"))
               for w in args.windows.split(",") if w]
    wrong = 0
    with open(args.out, "a") as out:
        for seed in sorted(set(seeds_of(args.seeds)) | controls | reading):
            harness.log(f"calibrate {args.workload} seed {seed} on {device}")
            rec = one_seed(ctx, driver, seed, seed in controls,
                           tuple(r for r in args.readings.split(",") if r)
                           if seed in reading else (), windows, args.variant)
            out.write(json.dumps(rec) + "\n")
            out.flush()
            wrong += wrong_rows(rows_of(rec, driver, ctx["limits"]))
    harness.log(f"calibrate_serve: {wrong} rows came out as they may not")
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())

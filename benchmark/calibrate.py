"""Readings that the limits are set from, and the verdict on each, many
seeds in one process (set-up is most of a run): the program against the
reference (the lower readings), the control, that is the reference at int8
put in the program's place, and the planted faults (the upper readings).
Every row goes through the cell's own ``compare`` with the committed
``limits/<cell>.json`` and ``harness.judge``, and is written with its
numbers, their limits and ``correct``: the program's rows have to come out
correct, every other row not. On the chip:
``python3 -m benchmark.calibrate --workload <cell> --seeds 1,2,3
--control-seeds 1,2,3 --out chiprun_out/calib.jsonl``;
``--rejudge <file.jsonl>`` judges recorded readings again, with the limits
as they are now, and needs no chip. The benchmark's own runs never call this.
Limits go into ``limits/<cell>.json`` by hand, with the readings in PERF.md.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

from benchmark import harness


def row(cell, seed, who, checks, **more):
    """One judged row. ``expected`` is what the row has to come out as."""
    return dict(cell=cell, seed=seed, who=who,
                numbers={n: v for n, v, _ in checks},
                limits={n: lim for n, _, lim in checks},
                correct=harness.judge(checks), expected=who == "program",
                **more)


def write(rec, out):
    """-> whether the row came out as it may not."""
    print(json.dumps(rec), flush=True)
    out.write(json.dumps(rec) + "\n")
    out.flush()
    return rec["correct"] != rec["expected"]


def train_seed(ctx, seed, control, faults, out):
    from benchmark.drivers import train
    config, traffic = ctx["config"], ctx["traffic"]
    trainer = train.Trainer(config, traffic, seed)
    prog = trainer.first_steps()
    batches = trainer.first_batches
    trainer.free()
    t = time.perf_counter()
    ref = train.reference_readings(config, seed, batches)
    ref_s = time.perf_counter() - t
    rows = [("program", prog)]
    for quant in ("int8", "bf16_int8") if control else ():
        rows.append((f"control_{quant}", train.reference_readings(
            config, seed, batches, quant=quant)))
    for fault in faults if control else ():
        broken = train.Trainer(config, traffic, seed, fault=fault)
        rows.append((f"fault_{fault}", broken.first_steps()))
        broken.free()
    return sum(
        write(row(ctx["cell"]["name"], seed, who,
                  train.compare(got, ref, ctx["limits"]),
                  reference_s=ref_s, loss_gaps=train.loss_gaps(got, ref),
                  losses=got["losses"], ref_losses=ref["losses"],
                  leaf_gaps={k: train.leaf_gaps(got, ref, k)
                             for k in ("grad_norms", "change_norms")},
                  ref_norms={k: ref[k]
                             for k in ("grad_norms", "change_norms")}), out)
        for who, got in rows)


def serve_seed(ctx, seed, control, faults, out):
    from benchmark.drivers import serve
    run = serve.run(dict(ctx, seed=seed, trace=False,
                         control="int8" if control else None))
    cell = ctx["cell"]["name"]
    wrong = write(row(cell, seed, "program", run["checks"],
                      end_to_end=run["end_to_end"],
                      attempted=run["attempted"], failed=run["failed"],
                      compiles_in_window=run["compiles_in_window"]), out)
    return wrong + sum(write(row(cell, seed, who, checks), out)
                       for who, checks in run["stand_ins"].items())


def rejudge(path):
    """Recorded readings under the limits as they are now: one line a row,
    judged on the numbers that have a limit today (in a limits file the
    numbers compared are the keys that end in ``_gap`` or ``_max``) (older records also hold
    numbers that are no longer compared; a row that lacks a number compared
    today is judged on the rest, says so under ``missing`` and is not
    counted). Serving
    records of before the rows were split carry the control's and the
    altered token's numbers under ``control``. Returns how many rows came
    out as they may not."""
    wrong = 0
    with open(path) as f:
        for line in f:
            rec = json.loads(line)
            limits = harness.load_json(os.path.join(
                harness.HERE, "limits", rec["cell"] + ".json"))
            rows = [(rec["who"], rec["numbers"])]
            old, name = rec.get("control") or {}, "served_logit_gap_max"
            if name in old:
                rows.append(("control_int8", {name: old[name]}))
            if "altered_token_gap_min" in old:
                rows.append(("altered_token",
                             {name: old["altered_token_gap_min"]}))
            for who, numbers in rows:
                compared = [n for n in limits if n.endswith(("_gap", "_max"))]
                checks = [(n, numbers[n], limits[n]) for n in compared
                          if n in numbers]
                r = row(rec["cell"], rec["seed"], who, checks,
                        missing=[n for n in compared if n not in numbers])
                wrong += not r["missing"] and r["correct"] != r["expected"]
                print(json.dumps(r))
    return wrong


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rejudge", default=None)
    ap.add_argument("--workload")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="half_batch")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    if args.rejudge:
        return 1 if rejudge(args.rejudge) else 0
    from benchmark import run as runmod
    manifest = harness.load_manifest()
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
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    faults = [f for f in args.faults.split(",") if f]
    one = {"train": train_seed, "serve": serve_seed}[ctx["traffic"]["driver"]]
    wrong = 0
    with open(args.out, "a") as out:
        for seed in sorted(set(seeds) | controls):
            harness.log(f"calibrate {args.workload} seed {seed} on {device}")
            wrong += one(ctx, seed, seed in controls, faults, out)
    harness.log(f"calibrate: {wrong} rows came out as they may not")
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())

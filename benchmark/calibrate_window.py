"""Readings that ``serve_mixed_window_closed``'s limits are set from, and
the verdict on each: ``benchmark.calibrate_serve`` (one recorded line a
seed with the reference's per-token readings; the program's row has to come
out correct, the int8 control's and the altered token's not) and, beside
it, two faults of the mechanism this cell exists for, each a run of the
PROGRAM with the mechanism broken, whose row has to come out not correct:

* ``window_ignored``: the window layers' kernel calls get no window (no
  lower bound in the job list or the mask): they attend the whole context,
  and where the window group has released a block, its scratch page;
* ``released_early``: a window group gives every block back one block
  before the last query that sees it has run.

On the chip: ``python3 -m benchmark.calibrate_window --seeds 1,2
--control-seeds 1 --faults window_ignored:3,released_early:4 --out
chiprun_out/calib.jsonl``; ``--rejudge <file.jsonl>`` judges recorded lines
again under the limits as they are now, and needs no chip. The benchmark's
own runs never call this; limits go into ``limits/<cell>.json`` by hand.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

from benchmark import calibrate_serve, harness
from benchmark.calibrate import row

CELL = "serve_mixed_window_closed"


def window_ignored():
    rpa = importlib.import_module(
        "paddle_tpu.ops.pallas.ragged_paged_attention")
    entry = rpa.ragged_paged_attention

    def no_window(*a, window=None, **kw):
        return entry(*a, **kw)

    rpa.ragged_paged_attention = no_window
    return lambda: setattr(rpa, "ragged_paged_attention", entry)


def released_early():
    from paddle_tpu.models.generation import SlotPagedKVCache
    first_live = SlotPagedKVCache._window_first_live

    def one_block_early(self, g, filled):
        return first_live(self, g, filled) + 1

    SlotPagedKVCache._window_first_live = one_block_early
    return lambda: setattr(SlotPagedKVCache, "_window_first_live",
                           first_live)


FAULTS = {"window_ignored": window_ignored, "released_early": released_early}


def rows_of(rec, driver, limits):
    """The judged rows of one recorded seed. The program's row (a fault's
    run stands there too, and may not pass) is judged on every number its
    run compared, the window groups' guarantee among them; the int8
    control and the altered token stand in the program's place in the
    logits' comparison alone (``judged_rows`` from the recorded per-token
    and per-position readings)."""
    cell, seed = rec["cell"], rec["seed"]
    numbers = rec["numbers"]["program"]
    judged = driver.judged_rows(rec["gaps"], limits) if rec.get("gaps") \
        else {}
    # what the run compared: the logits' numbers again from its recorded
    # readings (a line may be older than a number), the cache's count as
    # the run took it
    checks = judged.pop("program", None) or [
        (n, numbers[n], limits[n]) for n in driver.CHECKS if n in numbers]
    if driver.UNHELD in numbers:
        checks = checks + [(driver.UNHELD, numbers[driver.UNHELD],
                            limits[driver.UNHELD])]
    # a line recorded before the runs read the program's own logits (and,
    # a fault's, before they counted the cache's blocks): the tokens'
    # numbers alone tell neither the control nor a block released early
    # (limits file), so such a row is shown and nothing is expected of it
    gaps = rec.get("gaps") or {}
    if rec.get("fault"):
        told = bool(gaps.get("rms")) or driver.UNHELD in numbers \
            or rec["fault"] != "released_early"
        return [dict(row(cell, seed, "fault_" + rec["fault"], checks),
                     expected=False if told else None)]
    rows = [row(cell, seed, "program", checks)]
    for who, c in judged.items():
        rows.append(row(cell, seed, who, c))
        if who == "control_int8" and not gaps.get("rms_int8"):
            rows[-1]["expected"] = None
    return rows


def rejudge(path):
    limits = harness.load_json(os.path.join(harness.HERE, "limits",
                                            CELL + ".json"))
    driver = harness.load_driver("serve_window_moe")
    wrong = 0
    with open(path) as f:
        for line in f:
            wrong += calibrate_serve.wrong_rows(
                rows_of(json.loads(line), driver, limits))
    return wrong


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rejudge", default=None)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="",
                    help="fault:seed,... (" + ", ".join(FAULTS) + ")")
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    if args.rejudge:
        return 1 if rejudge(args.rejudge) else 0
    from benchmark import run as runmod
    manifest = harness.load_manifest()
    ns = argparse.Namespace(workload=CELL, seed=0, seconds=args.seconds,
                            trace=0, dump_trace=None)
    try:
        ctx = runmod.context(ns, manifest)
        device, ctx["peaks"] = harness.require_chips(ctx["chips"])
    except harness.Refused as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1
    ctx["watch"] = harness.CompileWatch()
    harness.enable_compile_cache()
    driver = harness.load_driver(ctx["traffic"]["driver"])
    controls = set(calibrate_serve.seeds_of(args.control_seeds))
    plain = sorted(set(calibrate_serve.seeds_of(args.seeds)) | controls)
    faults = [(f.split(":")[0], int(f.split(":")[1]))
              for f in args.faults.split(",") if f]
    wrong = 0
    with open(args.out, "a") as out:
        for fault, seed in [(None, s) for s in plain] + faults:
            harness.log(f"calibrate {CELL} seed {seed} fault {fault} on "
                        f"{device}")
            undo = FAULTS[fault]() if fault else None
            try:
                rec = calibrate_serve.one_seed(
                    ctx, driver, seed, seed in controls and not fault, (),
                    (), None)
            finally:
                if undo:
                    undo()
            rec["fault"] = fault
            out.write(json.dumps(rec) + "\n")
            out.flush()
            wrong += calibrate_serve.wrong_rows(
                rows_of(rec, driver, ctx["limits"]))
    harness.log(f"calibrate_window: {wrong} rows came out as they may not")
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())

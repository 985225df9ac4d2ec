"""Readings that ``serve_reason_state_closed``'s limits are set from, and
the verdict on each: ``benchmark.calibrate_serve`` (one recorded line a seed
with the reference's per-token readings; the program's row has to come out
correct, the int8 control's and the altered token's not) and, beside it,
two faults of the mechanism this cell exists for, each a run of the PROGRAM
with the mechanism broken, whose row has to come out not correct:

* ``state_not_reset``: admission leaves a slot's state as the slot's last
  request left it (``SlotPagedKVCache.reset_state`` does nothing): a
  request that is not its slot's first starts from another's state;
* ``chunk_state_dropped``: a prefill span of more than one token that does
  not start its request starts from a zero state (the chunk before it is
  forgotten): every prompt longer than a tick's chunk is served wrongly.

On the chip: ``python3 -m benchmark.calibrate_state --seeds 1,2
--control-seeds 1 --faults state_not_reset:3,chunk_state_dropped:4 --out
chiprun_out/calib.jsonl``; ``--rejudge <file.jsonl>`` judges recorded lines
again under the limits as they are now, and needs no chip. The benchmark's
own runs never call this; limits go into ``limits/<cell>.json`` by hand.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

from benchmark import calibrate_serve, harness
from benchmark.calibrate import row

CELL = "serve_reason_state_closed"
DRIVER = "serve_linear_latent"


def state_not_reset():
    from paddle_tpu.models.generation import SlotPagedKVCache
    reset = SlotPagedKVCache.reset_state
    SlotPagedKVCache.reset_state = lambda self, slot: None
    return lambda: setattr(SlotPagedKVCache, "reset_state", reset)


def chunk_state_dropped():
    import jax.numpy as jnp
    from paddle_tpu.models.bailing_hybrid import BailingKDALayer
    mix = BailingKDALayer.mix_state

    def forgetful(self, cache, plan, *rows):
        state = cache.layer_state(self, self.state_spec)
        for slot, _, n, start in cache.ragged_spans():
            if n > 1 and start > 0:
                state["S"] = state["S"].at[jnp.int32(slot)].set(0.0)
        return mix(self, cache, plan, *rows)

    BailingKDALayer.mix_state = forgetful
    return lambda: setattr(BailingKDALayer, "mix_state", mix)


FAULTS = {"state_not_reset": state_not_reset,
          "chunk_state_dropped": chunk_state_dropped}


def rows_of(rec, driver, limits):
    """The judged rows of one recorded seed: the program's row (a fault's
    run stands there, and may not pass), then the int8 control and the
    altered token in the program's place (``judged_rows`` from the
    recorded per-token and per-position readings)."""
    cell, seed = rec["cell"], rec["seed"]
    numbers = rec["numbers"]["program"]
    judged = driver.judged_rows(rec["gaps"], limits) if rec.get("gaps") \
        else {}
    checks = judged.pop("program", None) or [
        (n, numbers[n], limits[n]) for n in driver.CHECKS if n in numbers]
    if rec.get("fault"):
        return [dict(row(cell, seed, "fault_" + rec["fault"], checks),
                     expected=False)]
    return [row(cell, seed, "program", checks)] + [
        row(cell, seed, who, c) for who, c in judged.items()]


def rejudge(path):
    limits = harness.load_json(os.path.join(harness.HERE, "limits",
                                            CELL + ".json"))
    driver = harness.load_driver(DRIVER)
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
    harness.log(f"calibrate_state: {wrong} rows came out as they may not")
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())

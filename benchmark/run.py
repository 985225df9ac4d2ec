"""``python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell of ``BENCHMARK.json``, in one process
that holds the chip.

Everything that belongs to one cell is found by name: the configuration's
file, the traffic mix's file (which names the driver), the cell's limits and
each per-layer metric's reader. Without a TPU, with fewer chips than the cell
asks for, or on a device kind that ``peaks.json`` does not list, the run
exits with a code other than 0 and prints no result. Nothing falls back.
"""
from __future__ import annotations

import argparse
import os
import sys

from benchmark import harness


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", default=None,
                    help="with --trace 1: also write the trace's planes, "
                         "lines and first events to this JSON file")
    return ap.parse_args(argv)


def context(args, manifest):
    cell, config, traffic = harness.find_cell(manifest, args.workload)
    limits = harness.load_json(os.path.join(
        harness.HERE, "limits", cell["name"] + ".json"))
    return {"cell": cell, "config": config, "traffic": traffic,
            "limits": limits, "seed": args.seed, "seconds": args.seconds,
            "trace": bool(args.trace), "dump_trace": args.dump_trace,
            "chips": cell["chips"]}


def result_line(ctx, manifest, run, device):
    """The contract's last line, without ``checks`` (``emit`` adds it)."""
    cell = ctx["cell"]["name"]
    device = dict(device, count=ctx["chips"],
                  memory_peak_bytes=run["memory_peak_bytes"])
    if ctx["trace"]:
        entries = harness.metrics_of(manifest, "per_layer", cell)
        values = {m["name"]: harness.load_reader(m["name"])(run)
                  for m in entries}
        trace = run["trace"]
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        extra = {"breakdown": {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}}
    else:
        entries = harness.metrics_of(manifest, "end_to_end", cell)
        values = run["end_to_end"]
        extra = {}
    correct = harness.judge(run["checks"]) and run["failed"] == 0
    return {"correct": correct, "attempted": run["attempted"],
            "failed": run["failed"],
            "metrics": harness.metric_values(entries, values),
            "device": device, **extra,
            "workload": cell, "seed": ctx["seed"],
            "compiles_in_window": run["compiles_in_window"]}


def main(argv=None):
    args = parse(argv)
    try:
        manifest = harness.load_manifest()
        ctx = context(args, manifest)
        device, peaks = harness.require_chips(ctx["chips"])
    except harness.Refused as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1
    ctx["peaks"] = peaks
    ctx["watch"] = harness.CompileWatch()
    cache = harness.enable_compile_cache()
    harness.log(f"cell {ctx['cell']['name']} seed {ctx['seed']} on {device}; "
                f"compile cache at {cache}")
    run = harness.load_driver(ctx["traffic"]["driver"]).run(ctx)
    run["peaks"], run["chips"] = peaks, ctx["chips"]
    run["config"] = ctx["config"]
    run["pre_device_s"] = harness.pre_device_seconds()
    harness.emit(result_line(ctx, manifest, run, device), run["checks"])
    return 0


if __name__ == "__main__":
    sys.exit(main())

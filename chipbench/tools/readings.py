#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, many seeds in one
process (the set-up is long, so each seed pays no second start-up):

    python3 chipbench/tools/readings.py --workload <cell> \
        --seeds 11,12,13 --seconds 20 [--control N] [--out FILE]

For each seed it runs the cell as ``chipbench/run.py`` does, at the
cell's own load for ``--seconds``, and prints the numbers compared. On
the first ``--control`` seeds it also reads the lower-precision control
on the same inputs (int8 for a bf16 configuration, bf16 for float32). The benchmark's own runs never
run the control. Each seed's readings go to ``--out`` as a JSON line.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, default=0,
                    help="read the control on the first N seeds")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    from chipbench import harness
    bench = harness.load_bench()
    cell, conf, traffic = harness.resolve_cell(bench, args.workload)
    harness.enable_compile_cache()
    device = harness.device_check(cell["chips"])
    import importlib
    from chipbench.compilelog import CompileLog
    from chipbench.peaks import peaks_for
    compiles = CompileLog()
    driver = importlib.import_module("chipbench.drivers." + conf["driver"])
    for k, seed in enumerate(int(s) for s in args.seeds.split(",")):
        ctx = harness.Context(
            root=ROOT, cell=cell, config=conf, traffic=traffic, seed=seed,
            seconds=args.seconds, tracer=harness.Tracer(False, 1),
            compiles=compiles, t0=time.perf_counter(), device=device,
            peaks=peaks_for(device["kind"]), control=k < args.control)
        out = driver.run(ctx)
        rec = {"workload": args.workload, "seed": seed,
               "checks": {c["name"]: c["value"] for c in out["checks"]},
               "loss_gap": getattr(ctx, "loss_gap", None),
               "control": (out["counters"].get("control_logit_gap_max")
                           or getattr(ctx, "control_readings", None)),
               "control_correct": out["counters"].get(
                   "control_correct", getattr(ctx, "control_correct", None)),
               "e2e": out["e2e"]}
        print("readings", json.dumps(rec), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()

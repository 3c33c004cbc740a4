"""Benchmark harness — one function per paper table/figure (DESIGN.md §6).

    PYTHONPATH=src python -m benchmarks.run [--only fig3,tab1,...] [--fast]
                                            [--dry-run]

Prints ``name,us_per_call,derived`` CSV rows. JSON artifacts land in
experiments/bench/ (stable schema: {"name", "config", "metrics"});
``--dry-run`` is the CI smoke mode — tiny shapes, seconds not minutes,
covering the pruned-matmul kernel path and the multi-straggler migration
dataflow so perf regressions are visible per-PR.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import traceback


# key -> (module, slow real-training job, part of the --dry-run smoke set)
JOBS = [
    ("fig3", "benchmarks.imputation", False, False),
    ("kernel", "benchmarks.kernel_bench", False, True),
    ("roofline", "benchmarks.roofline", False, False),
    ("tab1", "benchmarks.migration_policies", False, False),
    ("fig9", "benchmarks.hetero_resizing", True, False),
    ("fig56", "benchmarks.homo_resizing", True, False),
    ("fig10", "benchmarks.single_straggler", True, False),
    ("fig11", "benchmarks.multi_straggler", False, True),
    ("serve", "benchmarks.serve_bench", False, True),
    ("cluster", "benchmarks.cluster_bench", False, True),
    ("xla_flags", "benchmarks.xla_flags_sweep", False, True),
    ("telemetry", "benchmarks.telemetry_bench", False, True),
    ("analyze", "benchmarks.analysis_smoke", False, True),
    ("ablate", "benchmarks.ablations", True, False),
]


# named job subsets for --suite (CI entry points)
SUITES = {
    "kernels": {"kernel", "xla_flags"},
    "migration": {"fig11", "tab1"},
    "serve": {"serve"},
    "cluster": {"cluster"},
    "telemetry": {"telemetry"},
    "analysis": {"analyze"},
    "smoke": {key for key, _, _, smoke in JOBS if smoke},
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: fig3,fig56,fig9,tab1,fig10,fig11,"
                         "kernel,roofline,serve,cluster,telemetry,analyze")
    ap.add_argument("--suite", default=None, choices=sorted(SUITES),
                    help="named subset (CI): kernels | migration | serve "
                         "| cluster | telemetry | analysis | smoke")
    ap.add_argument("--fast", action="store_true",
                    help="skip the slow real-training ACC benchmarks")
    ap.add_argument("--dry-run", action="store_true",
                    help="CI smoke: tiny shapes on the smoke job subset")
    args = ap.parse_args()
    if args.dry_run:
        os.environ["REPRO_BENCH_DRY"] = "1"
    from repro.launch._bootstrap import enable_compile_cache
    enable_compile_cache()

    only = set(args.only.split(",")) if args.only else None
    if args.suite:
        only = SUITES[args.suite] | (only or set())

    print("name,us_per_call,derived")
    failed = []
    ran = []
    for key, module, slow, smoke in JOBS:
        if only and key not in only:
            continue
        if args.dry_run and not smoke and only is None:
            # dry-run default = smoke subset; explicit --only/--suite wins
            continue
        if args.fast and slow:
            continue
        try:
            mod = __import__(module, fromlist=["main"])
            for row in mod.main():
                print(row, flush=True)
            ran.append(key)
        except Exception as e:                              # noqa: BLE001
            failed.append((key, repr(e)))
            print(f"{key}_FAILED,0.0,{e!r}", flush=True)
            traceback.print_exc(file=sys.stderr)

    if args.dry_run:
        from benchmarks.common import OUT_DIR
        os.makedirs(OUT_DIR, exist_ok=True)
        with open(os.path.join(OUT_DIR, "smoke_summary.json"), "w") as f:
            json.dump({"name": "smoke_summary",
                       "config": {"dry_run": True},
                       "metrics": {"ran": ran,
                                   "failed": [k for k, _ in failed]}},
                      f, indent=1)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()

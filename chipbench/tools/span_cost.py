#!/usr/bin/env python3
"""What one program span (``repro.telemetry.span``) costs the host:
nanoseconds to enter and leave it, with no counters, with two, and with
two more set once the spanned work has run (as ``repro.serve.step``
does), with the profiler off and then on, started as the benchmark's
harness starts it.

    python3 chipbench/tools/span_cost.py [--n 200000] [--out FILE]

Prints one JSON object (and writes it to ``FILE``): ns per span for
each case, the best of five rounds of ``--n`` spans.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def per_span_ns(body, n: int) -> float:
    best = float("inf")
    for _ in range(5):
        t = time.perf_counter_ns()
        body(n)
        best = min(best, (time.perf_counter_ns() - t) / n)
    return best


def cases():
    from repro.telemetry import span

    def bare(n):
        for _ in range(n):
            with span("repro.bench"):
                pass

    def two(n):
        for i in range(n):
            with span("repro.bench", step=i, lanes=24):
                pass

    def late(n):
        for i in range(n):
            with span("repro.bench", step=i) as sp:
                sp.count(lanes=24, completed=1)
    return {"no_counters": bare, "two_counters": two,
            "counters_set_late": late}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=200_000)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    import jax
    out = {"platform": jax.devices()[0].platform, "n": args.n}
    for name, body in cases().items():
        out["off_" + name] = per_span_ns(body, args.n)
    d = tempfile.mkdtemp(prefix="span_cost_")
    try:
        jax.profiler.start_trace(d)
        for name, body in cases().items():
            out["on_" + name] = per_span_ns(body, args.n // 10)
        jax.profiler.stop_trace()
    finally:
        shutil.rmtree(d, ignore_errors=True)
    print(json.dumps(out), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())

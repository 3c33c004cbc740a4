#!/usr/bin/env python3
"""Offered-load sweep of a serving cell, one engine in one process:

    python3 chipbench/tools/sweep.py --workload <cell> \
        --rates 0.3,0.4,0.5 --seconds 40 [--seed N]

After the cell's own set-up, each rate gets a window of ``--seconds`` of
the cell's traffic mix at that rate, one after the other on the same
engine; a line per rate gives what was completed, the tails and the
backlog left at the window's close. The knee is the highest rate whose
completed tokens keep up with the offered load with no growing backlog;
a cell's traffic file then fixes its rate below it.
"""
import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    t0 = time.perf_counter()
    from chipbench import harness
    bench = harness.load_bench()
    cell, conf, traffic = harness.resolve_cell(bench, args.workload)
    harness.enable_compile_cache()
    device = harness.device_check(cell["chips"])
    import numpy as np
    from chipbench import traffic as traffic_lib
    from chipbench.compilelog import CompileLog
    from chipbench.drivers import serve
    from chipbench.metrics._common import pct
    from chipbench.peaks import peaks_for
    ctx = harness.Context(
        root=ROOT, cell=cell, config=conf, traffic=traffic, seed=args.seed,
        seconds=args.seconds, tracer=harness.Tracer(False, 1),
        compiles=CompileLog(), t0=t0, device=device,
        peaks=peaks_for(device["kind"]))
    vocab = conf["model"]["vocab_size"]
    gen = traffic_lib.open_loop(traffic, seed=args.seed, seconds=1.0,
                                vocab=vocab)
    eng, loop = serve.setup(ctx, [(p, n) for _, p, n in gen["warm"]])
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        tr = dict(traffic, rate_rps=rate, warm_requests=0)
        reqs = traffic_lib.open_loop(tr, seed=args.seed + k + 1,
                                     seconds=args.seconds,
                                     vocab=vocab)["window"]
        q0 = len(eng.queue)
        t_open = time.perf_counter()
        due, _, first, traced = serve.window(ctx, loop, reqs, t_open,
                                             args.seconds)
        e2e, c = serve.stats(ctx, loop, due, first, traced, t_open,
                             args.seconds)
        offered = sum(n for _, _, n in reqs) / args.seconds
        print(f"sweep {args.workload} rate {rate} rps: offered "
              f"{offered:.1f} tokens/s, done {e2e['serve_tokens_per_s']:.1f}"
              f" tokens/s; ttft p50 {pct(c['ttft_ms'], 50)} p90 "
              f"{e2e['ttft_p90_ms']} ms; itl p50 {pct(c['itl_ms'], 50)} "
              f"p95 {e2e['itl_p95_ms']} ms; step median "
              f"{np.median(c['step_ms']):.1f} ms; queue {q0} -> "
              f"{c['queued_at_close']}; active "
              f"{sum(s is not None for s in eng.slots)}/{eng.num_slots}",
              flush=True)
    eng.close()


if __name__ == "__main__":
    main()

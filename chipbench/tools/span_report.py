#!/usr/bin/env python3
"""Run one cell traced, as ``chipbench/run.py --trace 1`` does, and keep
what the result line leaves out: every program span (``repro.*``), the
device's idle time split over them, and the host time of every step.

    python3 chipbench/tools/span_report.py --workload <cell> --seed <n> \
        --seconds <s> [--trace-seconds <s>] [--long-ms 1000] --out FILE

``--trace-seconds`` traces that much of the window in place of the
configuration's ``trace_seconds`` (the file is not changed); the window's
length does it all. Writes ``FILE`` (JSON: the result line, the trace's
reduction with the program's spans (``chipbench.program_trace``), the
host's own time in the median step and the control plane's share of the
window, each step's start and end from the window's opening, and the
traced stretch) and prints to standard error: the step-time median
inside and outside the traced stretch (what the profiler costs), those
readings, the idle time per program span, the longest idle gaps, and
every program step span longer than ``--long-ms`` with its phases, the
runtime's ``Execute`` calls and chip 0's program runs inside it.
"""
from __future__ import annotations

import argparse
import collections
import importlib
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import harness  # noqa: E402
from chipbench import program_trace  # noqa: E402
from chipbench import trace as trace_lib  # noqa: E402


class Clocked(harness.Tracer):
    """The harness's tracer, noting when the traced stretch opens and
    closes."""
    t_start = t_stop = None

    def start(self):
        super().start()
        if self.t_start is None:
            self.t_start = time.perf_counter()

    def stop(self):
        if self._win is not None and self.t_stop is None:
            self.t_stop = time.perf_counter()
        super().stop()

    def reduce(self):
        path = self.dir and trace_lib.find_xplane(self.dir)
        self.runtime = runtime_events(path) if path else {}
        self.program = program_trace.reduce_file(path) if path else {}
        return super().reduce()


def runtime_events(path):
    """The runtime's ``Execute`` calls on the host and chip 0's program
    runs, in seconds from the window's opening, the program runs moved
    onto the host clock as ``chipbench.trace`` moves them."""
    from jax.profiler import ProfileData
    win, execs, mods = None, [], []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                iv = (e.start_ns, e.start_ns + e.duration_ns)
                if plane.name == "/host:CPU":
                    if e.name == "bench.window":
                        win = iv
                    elif e.name == "PJRT_LoadedExecutable_Execute":
                        execs.append(iv)
                elif (plane.name == "/device:TPU:0"
                      and line.name == "XLA Modules"):
                    mods.append(iv)
    if win is None:
        return {}
    execs.sort()
    mods.sort()
    offs = sorted(h[0] - d[0] for h, d in zip(execs, mods))
    shift = offs[len(offs) // 2] if offs else 0.0

    def rel(iv, k=0.0):
        return ((iv[0] + k - win[0]) * 1e-9, (iv[1] + k - win[0]) * 1e-9)
    return {"execute": [rel(iv) for iv in execs],
            "program": [rel(iv, shift) for iv in mods]}


def step_times(driver, run):
    """Runs ``run()`` and returns (its output, [(start, end)] of every
    step on the host clock): the engine's steps as the serve loop times
    them, or the train loop's steps as the image feed sees them."""
    if hasattr(driver, "Loop"):
        loops = []
        step = driver.Loop.step

        def timed(self):
            if not loops:
                loops.append(self)
            return step(self)
        with harness.patched(driver.Loop, step=timed):
            out = run()
        return out, [(s[0], s[1]) for s in loops[0].steps]
    feeds = []

    class Feed(driver.Feed):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            feeds.append(self)
    with harness.patched(driver, Feed=Feed):
        out = run()
    g = feeds[0].given
    return out, list(zip(g, g[1:]))


def phases(spans, step, runtime):
    """Milliseconds of each direct child of one step span, and its own;
    and, in ms from the step's start, the runtime's ``Execute`` calls and
    chip 0's program runs that overlap it."""
    _, a, d, _, _ = step
    kids = collections.defaultdict(float)
    for n, s, dd, p, _ in spans:
        if p == step[0] and a <= s and s + dd <= a + d:
            kids[n] += dd * 1e3
    kids["self"] = d * 1e3 - sum(kids.values())
    out = {k: round(v, 3) for k, v in kids.items()}
    for key, ivs in runtime.items():
        out[key] = [[round((s - a) * 1e3, 3), round((e - a) * 1e3, 3)]
                    for s, e in ivs if s < a + d and e > a]
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-seconds", type=float, default=None)
    ap.add_argument("--long-ms", type=float, default=1000.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    t0 = time.perf_counter()
    bench = harness.load_bench()
    cell, conf, traffic = harness.resolve_cell(bench, args.workload)
    if args.trace_seconds is not None:
        conf = dict(conf, trace_seconds=args.trace_seconds)
    harness.enable_compile_cache()
    device = harness.device_check(cell["chips"])
    from chipbench.compilelog import CompileLog
    from chipbench.peaks import peaks_for
    opened = []

    class Marked(CompileLog):
        def mark(self):
            opened.append(time.perf_counter())
            super().mark()
    ctx = harness.Context(
        root=ROOT, cell=cell, config=conf, traffic=traffic, seed=args.seed,
        seconds=args.seconds, tracer=Clocked(True, cell["chips"]),
        compiles=Marked(), t0=t0, device=device,
        peaks=peaks_for(device["kind"]))
    driver = importlib.import_module("chipbench.drivers." + conf["driver"])
    out, steps = step_times(driver, lambda: driver.run(ctx))
    ctx.tracer.stop()
    t_reduce = time.perf_counter()
    out["trace"] = ctx.tracer.reduce() or {}
    t_reduce = time.perf_counter() - t_reduce
    out.update(peaks=ctx.peaks, config=conf, chips=cell["chips"])
    out["e2e"]["setup_s"] = out["setup_s"]
    line = harness.build_line(bench, cell, out, True, device)

    t_open, tr = opened[0], ctx.tracer
    close = t_open + args.seconds
    win = [(a, b) for a, b in steps if a >= t_open and b <= close]
    inside = [(b - a) * 1e3 for a, b in win
              if tr.t_start <= a and b <= tr.t_stop]
    outside = [(b - a) * 1e3 for a, b in win
               if b <= tr.t_start or a >= tr.t_stop]
    t = dict(out["trace"], **tr.program)
    spans = t.get("program_spans", [])
    readings = {
        "host_self_ms.decode": program_trace.host_self_ms(
            spans, "repro.serve.step"),
        "host_self_ms.train": program_trace.host_self_ms(
            spans, "repro.train.step"),
        "control_host_share.train": program_trace.share_of_window(
            spans, t.get("window_s"), "repro.control.")}
    long = [(s, phases(spans, s, tr.runtime)) for s in spans
            if s[0].endswith(".step") and s[2] * 1e3 >= args.long_ms]
    rep = {"line": line, "trace": t, "readings": readings,
           "reduce_s": t_reduce,
           "steps": [(a - t_open, b - t_open) for a, b in win],
           "traced": [tr.t_start - t_open, tr.t_stop - t_open],
           "step_ms_traced": inside, "step_ms_untraced": outside,
           "long_steps": long}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rep, f)
    med = lambda v: statistics.median(v) if v else None
    err = sys.stderr
    print(f"span_report: {args.workload} seed {args.seed}: {len(win)} window "
          f"steps; median step {med(inside)} ms traced ({len(inside)}), "
          f"{med(outside)} ms untraced ({len(outside)}); reduction "
          f"{t_reduce:.1f} s", file=err)
    print("span_report: readings: " + json.dumps(readings), file=err)
    print("span_report: idle by program span (s): " + json.dumps(
        t.get("idle_by_program_span_s")), file=err)
    print("span_report: longest idle gaps: " + json.dumps(
        t.get("top_program_gaps")), file=err)
    for s, ph in long:
        print(f"span_report: long {s[0]} at {s[1]:.3f} s, "
              f"{s[2] * 1e3:.1f} ms, args {s[4]}: {json.dumps(ph)}",
              file=err)
    print(harness.format_checks(out["checks"]), file=err)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

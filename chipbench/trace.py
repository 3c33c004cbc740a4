"""Reduction of a ``jax.profiler`` trace (``.xplane.pb``) to the numbers
the per-layer metrics read.

On a TPU the trace has one plane per chip, ``/device:TPU:<n>``, whose
``XLA Ops`` line holds every operation the TensorCore ran, in order and
without overlap, each named by its HLO text (``%name = <shape>
<opcode>(...)``). The host plane ``/host:CPU`` holds the benchmark's own
spans (``bench.*``, from ``jax.profiler.TraceAnnotation``) and the
runtime's ``PJRT_LoadedExecutable_Execute`` calls.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_OPCODE = re.compile(r"[\}\]\)] ([a-z][a-z0-9\-]*)\(")
_SHORT = re.compile(r"^%?([^\s=]+)")
#: ops whose events enclose other ops' events (loop and branch bodies)
CONTAINERS = ("while", "conditional", "call")
COLLECTIVES = ("all-reduce", "all-gather", "collective-permute",
               "reduce-scatter", "all-to-all")


def op_parts(text: str) -> Tuple[str, str]:
    """(short name, opcode) of an ``XLA Ops`` event's HLO text."""
    m = _SHORT.match(text)
    short = m.group(1) if m else text
    m = _OPCODE.search(text)
    return short, (m.group(1) if m else "")


def is_collective(opcode: str) -> bool:
    return opcode.startswith(COLLECTIVES)


def is_mosaic(text: str, opcode: str) -> bool:
    return opcode == "custom-call" and "tpu_custom_call" in text


def kernel_name(short: str) -> str:
    """``gqa_paged_decode_attn_2d.1`` -> ``gqa_paged_decode_attn_2d``."""
    return re.sub(r"\.\d+$", "", short)


def union_s(intervals: List[Tuple[float, float]]) -> float:
    """Length of the union of [start, end) intervals, in their unit."""
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def gaps(intervals: List[Tuple[float, float]], lo: float, hi: float):
    """Idle [start, end) stretches between busy intervals within [lo, hi)."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def _events(line):
    return [(e.name, float(e.start_ns), float(e.duration_ns),
             dict(e.stats)) for e in line.events]


def reduce_planes(planes, chips: int, top: int = 10) -> Dict:
    """``planes``: the trace's planes, as ``ProfileData.planes``.

    Device time is taken on the first ``chips`` chips and averaged over
    them; ops, kernels and collectives are read on chip 0. The window is
    the host span ``bench.window`` when there is one, else the span from
    the first to the last device op."""
    dev_ops: Dict[int, list] = {}
    modules: Dict[int, list] = {}
    host: List[Tuple[str, float, float]] = []
    executes: List[float] = []
    for plane in planes:
        m = _DEVICE.match(plane.name)
        for line in plane.lines:
            if m and int(m.group(1)) < chips:
                if line.name == "XLA Ops":
                    dev_ops[int(m.group(1))] = _events(line)
                elif line.name == "XLA Modules":
                    modules[int(m.group(1))] = _events(line)
            elif plane.name == "/host:CPU":
                for name, s, d, _ in _events(line):
                    if name.startswith("bench."):
                        host.append((name, s, s + d))
                    elif name == "PJRT_LoadedExecutable_Execute":
                        executes.append(s)
    if 0 not in dev_ops or not dev_ops[0]:
        return {}
    win = [(s, e) for n, s, e in host if n == "bench.window"]
    ops0 = dev_ops[0]
    if win:
        w_lo, w_hi = win[0]
    else:
        w_lo = min(s for _, s, _, _ in ops0)
        w_hi = max(s + d for _, s, d, _ in ops0)
    window_ns = w_hi - w_lo

    busy = [union_s([(s, s + d) for _, s, d, _ in dev_ops[c]])
            for c in sorted(dev_ops)]
    by_op: Dict[str, float] = defaultdict(float)
    kernels: Dict[str, float] = defaultdict(float)
    coll_ns = 0.0
    for text, s, d, _ in ops0:
        short, opcode = op_parts(text)
        if opcode in CONTAINERS:
            continue
        by_op[short] += d
        if is_mosaic(text, opcode):
            kernels[kernel_name(short)] += d
        if is_collective(opcode):
            coll_ns += d

    # device clock -> host clock: the i-th program run on chip 0 against
    # the i-th host Execute call, median offset
    mods = sorted(s for _, s, _, _ in modules.get(0, []))
    offs = sorted(h - dv for h, dv in zip(sorted(executes), mods))
    shift = offs[len(offs) // 2] if offs else 0.0
    busy0 = [(s + shift, s + d + shift) for _, s, d, _ in ops0]
    spans = sorted((e - s, n, s, e) for n, s, e in host
                   if n != "bench.window")
    labelled: Dict[str, float] = defaultdict(float)
    longest = []
    for s, e in gaps(busy0, w_lo, w_hi):
        mid = 0.5 * (s + e)
        inner = next((n for _, n, a, b in spans if a <= mid < b),
                     "outside_bench_spans")
        labelled[inner] += e - s
        longest.append((e - s, inner))
    longest.sort(reverse=True)
    ns = 1e-9
    return {
        "window_s": window_ns * ns,
        "busy_s": sum(busy) / len(busy) * ns,
        "busy_s_by_chip": [b * ns for b in busy],
        "op_s": {k: v * ns for k, v in by_op.items()},
        "kernel_s": {k: v * ns for k, v in kernels.items()},
        "collective_s": coll_ns * ns,
        "idle_by_span_s": {k: v * ns for k, v in labelled.items()},
        "top_ops": [[k, v * ns] for k, v in sorted(
            by_op.items(), key=lambda kv: -kv[1])[:top]],
        "top_gaps": [[n, d * ns] for d, n in longest[:top]],
    }


def reduce_file(path: str, chips: int) -> Dict:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes, chips)


def find_xplane(trace_dir: str) -> Optional[str]:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return found[0] if found else None


def reduce_dir(trace_dir: str, chips: int) -> Dict:
    path = find_xplane(trace_dir)
    return reduce_file(path, chips) if path else {}

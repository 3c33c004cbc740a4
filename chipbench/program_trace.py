"""Reduction of the program's own spans (``repro.*``, written by
``repro.telemetry.span``, their counters as the events' stats) in a
``jax.profiler`` trace, beside ``chipbench.trace``'s reduction of the
device ops and the benchmark's ``bench.*`` spans.

``reduce_planes`` gives, over the ``bench.window`` span:

- ``program_spans``: every ``repro.*`` host span wholly inside the
  window, as ``[name, start_s (from the window's opening), dur_s,
  parent, args]``, ``parent`` the innermost ``repro.*`` span enclosing
  it on the same thread line. Spans cut by the profiler's start or stop
  are dropped.
- ``idle_by_program_span_s``: chip 0's idle time, on the host clock,
  split exactly over the innermost program span covering each part of
  it; the parts no span covers go to ``outside_program_spans``.
- ``top_program_gaps``: the longest idle stretches, each with the span
  holding most of it.

``host_self_ms`` and ``share_of_window`` compute the numbers a per-layer
reader of these spans would report.
"""
from __future__ import annotations

import statistics
from collections import defaultdict
from typing import Dict, List, Optional

from chipbench.trace import _DEVICE, _events, find_xplane, gaps, union_s

OUTSIDE = "outside_program_spans"


def nest(events):
    """``events``: one thread line's spans as (name, start, end, args).
    Returns them sorted by start, each as (name, start, end, parent,
    args), ``parent`` the innermost span of the list enclosing it."""
    out, stack = [], []
    for name, s, e, args in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and not (stack[-1][1] <= s and e <= stack[-1][2]):
            stack.pop()
        out.append((name, s, e, stack[-1][0] if stack else None, args))
        stack.append((name, s, e))
    return out


def innermost(spans):
    """Time cut into [start, end) pieces, each labelled by the shortest
    of ``spans`` ((name, start, end)) covering it; uncovered time has no
    piece. Spans of one thread nest, so the shortest is the innermost."""
    cuts = sorted({t for _, s, e in spans for t in (s, e)})
    by_start = sorted(spans, key=lambda x: x[1])
    out, live, k = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(by_start) and by_start[k][1] <= a:
            live.append(by_start[k])
            k += 1
        live = [x for x in live if x[2] > a]
        if live:
            out.append((min(live, key=lambda x: x[2] - x[1])[0], a, b))
    return out


def split_idle(idle, pieces):
    """Each idle [start, end) of ``idle`` split exactly over the labelled
    ``pieces`` (``innermost``); the rest goes to ``OUTSIDE``. Both lists
    sorted and each free of overlap. Returns (time by label, in their
    unit, and per idle interval the label that holds most of it)."""
    total: Dict[str, float] = defaultdict(float)
    most, k = [], 0
    for s, e in idle:
        while k < len(pieces) and pieces[k][2] <= s:
            k += 1
        part: Dict[str, float] = {}
        j = k
        while j < len(pieces) and pieces[j][1] < e:
            d = min(e, pieces[j][2]) - max(s, pieces[j][1])
            if d > 0:
                part[pieces[j][0]] = part.get(pieces[j][0], 0.0) + d
            j += 1
        rest = e - s - sum(part.values())
        if rest > 0:
            part[OUTSIDE] = rest
        for n, d in part.items():
            total[n] += d
        most.append(max(part, key=part.get))
    return total, most


def reduce_planes(planes, top: int = 10) -> Dict:
    """``planes``: the trace's planes, as ``ProfileData.planes``. Empty
    without a ``bench.window`` span or without chip 0's ops. Chip 0's
    ops are moved onto the host clock as ``chipbench.trace`` moves them:
    by the median offset of its program runs from the host's
    ``Execute`` calls."""
    ops0, modules, program, executes, win = [], [], [], [], None
    for plane in planes:
        m = _DEVICE.match(plane.name)
        for line in plane.lines:
            if m and int(m.group(1)) == 0:
                if line.name == "XLA Ops":
                    ops0 = _events(line)
                elif line.name == "XLA Modules":
                    modules = _events(line)
            elif plane.name == "/host:CPU":
                mine = []
                for name, s, d, st in _events(line):
                    if name == "bench.window":
                        win = (s, s + d)
                    elif name.startswith("repro."):
                        mine.append((name, s, s + d, st))
                    elif name == "PJRT_LoadedExecutable_Execute":
                        executes.append(s)
                if mine:
                    program.append(mine)
    if win is None or not ops0:
        return {}
    w_lo, w_hi = win
    mods = sorted(s for _, s, _, _ in modules)
    offs = sorted(h - dv for h, dv in zip(sorted(executes), mods))
    shift = offs[len(offs) // 2] if offs else 0.0
    busy0 = [(s + shift, s + d + shift) for _, s, d, _ in ops0]

    kept = []
    for line in program:
        kept += [x for x in nest(line) if w_lo <= x[1] and x[2] <= w_hi]
    kept.sort(key=lambda x: x[1])
    idle = gaps(busy0, w_lo, w_hi)
    by_span, most = split_idle(idle, innermost(
        [(n, s, e) for n, s, e, _, _ in kept]))
    longest = sorted(((e - s, n) for (s, e), n in zip(idle, most)),
                     reverse=True)
    ns = 1e-9
    return {
        "window_s": (w_hi - w_lo) * ns,
        "program_spans": [[n, (s - w_lo) * ns, (e - s) * ns, par, args]
                          for n, s, e, par, args in kept],
        "idle_by_program_span_s": {k: v * ns for k, v in by_span.items()},
        "top_program_gaps": [[n, d * ns] for d, n in longest[:top]],
    }


def reduce_file(path: str) -> Dict:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes)


def reduce_dir(trace_dir: str) -> Dict:
    path = find_xplane(trace_dir)
    return reduce_file(path) if path else {}


def host_self_ms(spans: List[list], step: str) -> Optional[float]:
    """Median over the ``step`` spans of ``spans`` (``program_spans``) of
    their length less the union of their ``repro.device`` children (the
    dispatch and the wait on the device), in ms: the host's own time in
    one step. ``None`` without such a span."""
    steps = [(s[1], s[1] + s[2]) for s in spans if s[0] == step]
    if not steps:
        return None
    device = sorted((s[1], s[1] + s[2]) for s in spans
                    if s[0] == "repro.device" and s[3] == step)
    own = []
    for a, b in steps:
        inner = [(s, e) for s, e in device if a <= s and e <= b]
        own.append((b - a - union_s(inner)) * 1e3)
    return statistics.median(own)


def share_of_window(spans: List[list], window_s: float,
                    prefix: str) -> Optional[float]:
    """The union of the spans whose names start with ``prefix``, over
    the window, in %. ``None`` without such a span."""
    mine = [(s[1], s[1] + s[2]) for s in spans if s[0].startswith(prefix)]
    if not mine or not window_s:
        return None
    return 100.0 * union_s(mine) / window_s

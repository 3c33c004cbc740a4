"""The reduction of the program's spans (``chipbench.program_trace``:
``program_spans``, ``idle_by_program_span_s``, ``top_program_gaps``) and
the step and window readings computed from them, on hand-built planes
and on the two traces recorded on v5e chips."""
import os

import pytest

from chipbench import program_trace as pt
from chipbench import trace

DATA = os.path.join(os.path.dirname(__file__), "data")
MS = 1e6                                   # ns per ms


class Ev:
    def __init__(self, name, start_ms, end_ms, **stats):
        self.name = name
        self.start_ns = start_ms * MS
        self.duration_ns = (end_ms - start_ms) * MS
        self.stats = list(stats.items())


class Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def planes(main, other=(), ops=((0, 20), (20, 48), (70, 100))):
    """Chip 0 busy over ``ops`` (ms), its clock the host's (the one
    program starts as the host's Execute call does); a window [0, 100)
    ms; ``main`` and ``other`` are two host thread lines."""
    dev = [Ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", s, e)
           for s, e in ops]
    host = [Ev("bench.window", 0, 100), Ev("bench.engine_step", 9, 61),
            Ev("PJRT_LoadedExecutable_Execute", 0, 1)] + list(main)
    return [Plane("/device:TPU:0", [Line("XLA Ops", dev),
                                     Line("XLA Modules",
                                          [Ev("jit_step", 0, 100)])]),
            Plane("/host:CPU", [Line("python", host),
                                Line("other", list(other))])]


STEP = [Ev("repro.serve.step", 10, 60, step=1, lanes=3),
        Ev("repro.control.decide", 10.2, 10.8),
        Ev("repro.serve.feed", 11, 20, lanes=3),
        Ev("repro.device", 20, 50),
        Ev("repro.serve.harvest", 50, 58, completed=1),
        Ev("repro.serve.request_done", 55, 56, uid=7, tokens=4),
        Ev("repro.serve.step", 61, 69, step=2),
        Ev("repro.device", 62, 66),
        Ev("repro.control.dispatch", 84, 86, new_signature=0),
        # cut by the profiler's start and stop
        Ev("repro.serve.step", -5, 5), Ev("repro.serve.step", 95, 105)]
OTHER = [Ev("repro.control.capture", 80, 85, gathered=1)]


@pytest.fixture(scope="module")
def red():
    return pt.reduce_planes(planes(STEP, OTHER))


def test_program_spans_name_their_parents_and_args(red):
    got = {(n, round(s * 1e3, 3)): (round(d * 1e3, 3), p, a)
           for n, s, d, p, a in red["program_spans"]}
    assert got[("repro.serve.step", 10)] == (50, None,
                                             {"step": 1, "lanes": 3})
    assert got[("repro.serve.feed", 11)] == (9, "repro.serve.step",
                                             {"lanes": 3})
    assert got[("repro.control.decide", 10.2)][1] == "repro.serve.step"
    assert got[("repro.device", 20)][1] == "repro.serve.step"
    assert got[("repro.serve.request_done", 55)] == (
        1, "repro.serve.harvest", {"uid": 7, "tokens": 4})
    # another thread's span has no parent from this one; bench.* spans
    # are never parents
    assert got[("repro.control.capture", 80)][1] is None
    assert got[("repro.control.dispatch", 84)][1] is None
    # spans cut by the profiler's start or stop are dropped
    assert ("repro.serve.step", -5) not in got
    assert ("repro.serve.step", 95) not in got
    assert len(got) == 10
    starts = [s for _, s, _, _, _ in red["program_spans"]]
    assert starts == sorted(starts)


def test_idle_is_split_exactly_over_the_innermost_spans(red):
    # one idle stretch, [48, 70) ms: the device span's tail, the harvest
    # around its request, the first step's own tail, a gap between
    # steps, the second step around its device span, and a last ms
    # outside every span
    idle = red["idle_by_program_span_s"]
    assert idle == pytest.approx({
        "repro.device": 6e-3, "repro.serve.harvest": 7e-3,
        "repro.serve.request_done": 1e-3, "repro.serve.step": 6e-3,
        "outside_program_spans": 2e-3})
    busy = trace.reduce_planes(planes(STEP, OTHER), chips=1)["busy_s"]
    assert sum(idle.values()) == pytest.approx(red["window_s"] - busy)
    assert red["top_program_gaps"] == [["repro.serve.harvest",
                                        pytest.approx(22e-3)]]


def test_split_of_one_interval_across_two_phases():
    pieces = pt.innermost([("a", 0, 10), ("b", 10, 14), ("a.in", 2, 4)])
    assert pieces == [("a", 0, 2), ("a.in", 2, 4), ("a", 4, 10),
                      ("b", 10, 14)]
    assert pt.innermost([("a", 0, 4), ("b", 6, 8)]) == [("a", 0, 4),
                                                          ("b", 6, 8)]
    total, most = pt.split_idle([(3, 12), (13, 20)], pieces)
    assert dict(total) == {"a.in": 1, "a": 6, "b": 3,
                           "outside_program_spans": 6}
    assert most == ["a", "outside_program_spans"]


def test_nest_resolves_the_innermost_enclosing_span():
    got = pt.nest([("c", 2, 3, {}), ("a", 0, 10, {}), ("b", 1, 5, {}),
                      ("d", 6, 7, {}), ("e", 0, 10, {})])
    assert [(n, p) for n, _, _, p, _ in got] == [
        ("a", None), ("e", "a"), ("b", "e"), ("c", "b"), ("d", "e")]


def test_readings_on_hand_built_spans(red):
    spans, win = red["program_spans"], red["window_s"]
    # (50 - 30) and (8 - 4) ms of host time in the two steps
    assert pt.host_self_ms(spans, "repro.serve.step") == pytest.approx(12.0)
    # decide 0.6 ms, dispatch and capture overlapping over [80, 86)
    assert pt.share_of_window(spans, win, "repro.control.") \
        == pytest.approx(6.6)
    assert pt.host_self_ms(spans, "repro.train.step") is None
    steps = [
        ["repro.train.step", 0.0, 0.140, None, {"step": 5}],
        ["repro.device", 0.010, 0.120, "repro.train.step", {}],
        ["repro.train.step", 0.2, 3.6, None, {"step": 9}],
        ["repro.device", 0.21, 0.12, "repro.train.step", {}],
        ["repro.train.step", 4.0, 0.150, None, {"step": 6}],
        ["repro.device", 4.01, 0.125, "repro.train.step", {}]]
    # host time 20, 3480 and 25 ms: the median leaves the long step out
    assert pt.host_self_ms(steps, "repro.train.step") == pytest.approx(25.0)


def test_a_trace_without_program_spans():
    red = pt.reduce_planes(planes([]))
    assert red["program_spans"] == []
    assert red["idle_by_program_span_s"] == pytest.approx(
        {"outside_program_spans": 22e-3})
    assert pt.host_self_ms([], "repro.serve.step") is None
    assert pt.share_of_window([], red["window_s"], "repro.control.") is None
    # no window, or no device ops: nothing to reduce
    no_window = planes([])
    no_window[1].lines[0].events = no_window[1].lines[0].events[1:]
    assert pt.reduce_planes(no_window) == {}
    assert pt.reduce_planes(planes([], ops=())) == {}


@pytest.mark.parametrize("name,chips", [("trace_1chip", 1),
                                        ("trace_4chip", 4)])
def test_recorded_traces_split_all_of_chip_0s_idle(name, chips):
    """Traces recorded before the program wrote spans: every idle second
    of chip 0 lies outside the program's spans, and the split adds up
    to the window less chip 0's busy time, as ``chipbench.trace`` reads
    them."""
    path = os.path.join(DATA, name + ".xplane.pb")
    red = pt.reduce_file(path)
    base = trace.reduce_file(path, chips)
    assert red["program_spans"] == []
    assert set(red["idle_by_program_span_s"]) == {pt.OUTSIDE}
    assert red["window_s"] == pytest.approx(base["window_s"])
    assert red["idle_by_program_span_s"][pt.OUTSIDE] == pytest.approx(
        base["window_s"] - base["busy_s_by_chip"][0], rel=1e-9)
    assert [n for n, _ in red["top_program_gaps"]] == [pt.OUTSIDE] * len(
        red["top_program_gaps"])

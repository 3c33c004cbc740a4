"""The trace reduction, on a small trace recorded on a TPU v5e by
``chipbench/tools/record_trace.py``: three calls of one program (a bf16
matmul and the fused paged decode-attention kernel) with a 2 ms host
sleep after each, inside a ``bench.window`` span."""
import os

import pytest

from chipbench import trace

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "trace_1chip.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    return trace.reduce_file(DATA, chips=1)


def test_union_and_gaps_of_intervals():
    iv = [(0, 2), (1, 3), (5, 6), (5.5, 5.7)]
    assert trace.union_s(iv) == 4
    assert trace.gaps(iv, -1, 8) == [(-1, 0), (3, 5), (6, 8)]


def test_op_parts_reads_names_and_opcodes():
    t = ('%gqa_paged_decode_attn_2d.1 = bf16[4,4,8,128]{3,2,1,0:T(8,128)'
         '(2,1)S(1)} custom-call(s32[4]{0:T(128)} %cp.1), '
         'custom_call_target="tpu_custom_call"')
    assert trace.op_parts(t) == ("gqa_paged_decode_attn_2d.1",
                                 "custom-call")
    assert trace.is_mosaic(t, "custom-call")
    t2 = ('%copy-start = (bf16[2]{0}, u32[]{:S(2)}) copy-start(bf16[2]{0}'
          ' %w)')
    assert trace.op_parts(t2) == ("copy-start", "copy-start")
    assert trace.is_collective("all-reduce-start")
    assert not trace.is_collective("fusion")


def test_busy_is_the_union_of_chip_ops(summary):
    # three runs of a ~59 us program inside a ~10 ms window
    assert summary["window_s"] == pytest.approx(0.010047349)
    assert 3 * 50e-6 < summary["busy_s"] < 3 * 62e-6
    assert summary["busy_s"] < summary["window_s"]


def test_kernel_time_is_the_mosaic_call(summary):
    assert list(summary["kernel_s"]) == ["gqa_paged_decode_attn_2d"]
    k = summary["kernel_s"]["gqa_paged_decode_attn_2d"]
    assert k == pytest.approx(summary["op_s"]["gqa_paged_decode_attn_2d.1"])
    assert 3 * 9e-6 < k < 3 * 13e-6
    assert summary["collective_s"] == 0.0


def test_idle_gaps_are_labelled_by_host_spans(summary):
    idle = summary["idle_by_span_s"]
    # the sleeps dominate the idle time, and every gap found a span
    assert idle["bench.host_wait"] > 0.8 * (summary["window_s"]
                                            - summary["busy_s"])
    assert "outside_bench_spans" not in idle
    assert summary["top_gaps"][0][0] == "bench.host_wait"
    assert len(summary["top_ops"]) <= 10


def test_collectives_on_four_chips():
    """The same program on four chips with an all-reduce (``psum``)
    over them: busy time is read on every chip, collective time on
    chip 0."""
    s = trace.reduce_file(os.path.join(os.path.dirname(__file__), "data",
                                       "trace_4chip.xplane.pb"), chips=4)
    assert len(s["busy_s_by_chip"]) == 4
    assert s["busy_s"] == pytest.approx(sum(s["busy_s_by_chip"]) / 4)
    assert s["collective_s"] == pytest.approx(s["op_s"]["psum.7"])
    assert 0 < s["collective_s"] < s["busy_s_by_chip"][0]
    assert list(s["kernel_s"]) == ["gqa_paged_decode_attn_2d"]

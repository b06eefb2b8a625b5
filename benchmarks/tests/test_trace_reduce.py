"""The reduction from a profiler trace to metrics, pinned on a hand-written
trace whose numbers can be counted from the comment at its top, and on a
small trace recorded on a TPU v5e (``fixtures/record_tpu_trace.py``)."""

from pathlib import Path

import pytest

from benchmarks import trace_reduce as t
from benchmarks.record import MARK

FIXTURES = Path(__file__).resolve().parent / "fixtures"


@pytest.fixture(scope="module")
def hand():
    return t.reduce_trace(FIXTURES / "two_chip_steps.textproto", mark=MARK)


def test_interval_arithmetic():
    assert t.merge([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)]) == [(0, 3), (5, 7)]
    assert t.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert t.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert t.total(t.clip([(0, 5), (8, 12)], 2, 10)) == 5


def test_span_is_whole_step_periods(hand):
    # three executions of jit_step 200 us apart: two whole periods
    assert hand.periods == 2 and hand.devices[0].span_kind == "step_starts"
    assert hand.window_s == pytest.approx(400e-6)
    assert [d.chip for d in hand.devices] == [0, 1]


def test_busy_is_the_union_of_leaf_ops(hand):
    # per step: [0,60) and [70,100) busy; the while op's own [60,70) is a gap
    assert hand.busy_s == pytest.approx(2 * 90e-6)
    assert hand.idlest.idle_share == pytest.approx(0.55)
    gaps = hand.devices[0].gaps
    assert len(gaps) == 4 and gaps[0][1] - gaps[0][0] == pytest.approx(100e3)
    assert gaps[-1][1] - gaps[-1][0] == pytest.approx(10e3)


def test_own_time_and_top_ops(hand):
    d = hand.devices[0]
    assert d.self_by_name["while.1"] == pytest.approx(2 * 5e3)  # 80 - 30 - 20 - 15 - 10
    assert d.count_by_name["fused_qlora.3"] == 2
    assert hand.top_ops(2) == [["fusion.2", pytest.approx(60e-6)],
                               ["fused_qlora.3 bf16[8,128]", pytest.approx(40e-6)]]


def test_kernel_matching_by_name_and_by_source_text(hand):
    # fusion.5's HLO line names %fused_qlora.3 as an operand: not a call of it
    assert hand.matching("fused_qlora") == (pytest.approx(40e-6), 2)
    assert t.own_name("%fusion.5 = bf16[8]{0} fusion(%fused_qlora.3)") == "fusion.5"
    # custom-call.9 carries the Pallas name only in its long_name stat
    assert hand.matching("decode_attention") == (pytest.approx(20e-6), 2)
    assert hand.matching("no_such_kernel") == (0.0, 0)


def test_collective_time_and_exposure(hand):
    d = hand.devices[0]
    # all-reduce.4 [45,60) + all-gather.6 [80,90); fused_qlora.3 covers [45,50)
    assert d.collective_ns == pytest.approx(2 * 25e3)
    assert d.collective_exposed_ns == pytest.approx(2 * 20e3)


def test_marks_are_read_from_the_host_plane(hand):
    assert hand.marks_ns == [1000.0, 191000.0, 391000.0, 591000.0]


def test_recorded_tpu_trace():
    path = FIXTURES / "tpu_toy_steps.xplane.pb"
    if not path.exists():
        pytest.skip("no recorded trace in this checkout")
    r = t.reduce_trace(path, mark=MARK)
    assert len(r.devices) == 1 and r.periods == 3 and len(r.marks_ns) == 4
    assert r.devices[0].span_kind == "step_starts"
    assert r.window_s == pytest.approx(0.010526794) and r.busy_s == pytest.approx(2.9585e-05)
    # 4 loop iterations x 3 whole periods; the while op that holds them is no leaf
    assert r.matching("toy_kernel") == (pytest.approx(4.514e-06), 12)
    assert r.devices[0].count_by_name["while"] == 3
    assert r.top_ops(1) == [["convolution_tanh_fusion.2 bf16[512,512]", pytest.approx(1.7789e-05)]]

"""The reduction from a profiler trace to busy time, idle share, kernel
time and named idle gaps: on hand-made events, and on a small trace
recorded on the chip (``fixtures/chip_trace.xplane.pb``)."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import trace_reduce as TR  # noqa: E402

FIXTURE = os.path.join(HERE, "fixtures", "chip_trace.xplane.pb")


def _trace():
    host = {TR.WINDOW_SPAN: [(10.0, 20.0)],
            "ingest": [(10.0, 12.0), (15.0, 15.5)],
            "segment": [(12.0, 13.0)]}
    dev = {0: [("kernel", 13.0, 15.0), ("fusion.1", 15.0, 16.0),
               ("kernel", 18.0, 19.0), ("copy", 9.0, 10.5)],
           1: [("kernel", 10.0, 20.0)]}
    return TR.Trace(host, dev)


def test_busy_union_and_idle_share():
    t = _trace()
    assert t.window() == (10.0, 20.0)
    # device 0: [10,10.5] + [13,16] + [18,19] = 4.5 s; device 1: 10 s
    assert t.busy(0) == [(10.0, 10.5), (13.0, 16.0), (18.0, 19.0)]
    assert t.busy_s() == pytest.approx((4.5 + 10.0) / 2)
    assert t.window_s() == 10.0
    assert t.idle_share() == pytest.approx(100 * (1 - 7.25 / 10))


def test_op_time_clipped_to_the_window():
    t = _trace()
    assert t.op_seconds(lambda n: n == "kernel") == pytest.approx(13.0)
    assert t.op_seconds(lambda n: n == "copy") == pytest.approx(0.5)
    top = t.top_ops(2)
    assert top[0] == ["kernel", pytest.approx(13.0)]
    assert len(top) == 2


def test_idle_gaps_are_named_by_the_open_host_span():
    gaps = _trace().idle_gaps(10, dev=0)
    # gaps of device 0: [10.5,13] ingest/segment, [16,18] ingest 0
    # and nothing else, [19,20] nothing
    assert gaps[0] == ["ingest", pytest.approx(2.5)]
    assert gaps[1] == ["host:other", pytest.approx(2.0)]
    assert gaps[2] == ["host:other", pytest.approx(1.0)]


def test_no_window_reads_nothing():
    t = TR.Trace({}, {0: [("k", 0.0, 1.0)]})
    assert t.window() is None and t.busy_s() is None
    assert t.idle_share() is None and t.top_ops() == []


@pytest.mark.skipif(not os.path.exists(FIXTURE),
                    reason="no chip trace fixture")
def test_chip_trace_fixture():
    t = TR.Trace.from_file(FIXTURE, ["ingest", "segment"])
    lo, hi = t.window()
    assert hi > lo
    assert t.device_ops, "no TPU device plane in the fixture"
    busy = t.busy_s()
    assert 0 < busy <= t.window_s()
    assert 0 <= t.idle_share() < 100
    ops = t.top_ops(10)
    assert ops and all(s >= 0 for _, s in ops)
    # the fused kernel is most of the device time of a 50k-op check
    assert ops[0][0].endswith("[tpu_custom_call]")
    kern = t.op_seconds(TR.is_kernel)
    assert 0.5 * busy < kern <= busy + 1e-9
    gaps = t.idle_gaps(10)
    assert all(g[1] > 0 for g in gaps)

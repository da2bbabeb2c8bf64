"""The reduction from a trace to busy time, idle share and breakdown:
interval arithmetic by hand, then a small recorded ``.xplane.pb``
(``data/tiny.xplane.pb``: 0.4 s of `seqread-32m.scan-16g` on one TPU
v5e chip, my chip run, PR 24)."""

import os

import pytest

from benchmark.harness import xtrace

RECORDED = os.path.join(os.path.dirname(__file__), "data", "tiny.xplane.pb")


def test_union_overlap_gaps():
    busy = xtrace.union([(3, 4), (0, 1), (0.5, 2), (3.5, 3.8)])
    assert busy == [(0, 2), (3, 4)]
    assert xtrace.gaps(busy, 0, 5) == [(2, 3), (4, 5)]
    assert xtrace.gaps(busy, 0.5, 3.5) == [(2, 3)]
    assert xtrace.gaps([], 1, 2) == [(1, 2)]
    assert xtrace.overlap([(2, 3), (4, 5)], [(2.5, 4.5)]) == 1.0
    assert xtrace.overlap([(0, 1)], [(1, 2)]) == 0.0


class FakeTrace(xtrace.Trace):
    def __init__(self, ops, modules, host):  # no file
        self.device_ops, self.device_modules, self.host = ops, modules, host


def test_reduce_attributes_idle_time_to_host_spans():
    # window 10..14 s from the bench.* spans; device busy 1.5 s of it
    ops = {"/device:TPU:0": [("sum", 9.0, 0.5),      # before the window
                             ("sum", 10.5, 0.5), ("sum", 12.0, 1.0),
                             ("psum_all-reduce", 12.5, 0.25)]}
    host = {"bench.wait_input": [("bench.wait_input", 10.0, 2.0)],
            "bench.dispatch_step": [("bench.dispatch_step", 12.0, 2.0)],
            "atpu.loader.host_read": [("atpu.loader.host_read", 10.0, 0.5),
                                      ("atpu.loader.host_read", 11.0, 1.0)],
            "atpu.loader.h2d": []}
    out = xtrace.reduce(FakeTrace(ops, {}, host))
    assert out["window_s"] == pytest.approx(4.0)
    assert out["busy_s"] == pytest.approx(1.5)
    assert out["device_ops"][0] == ["sum", pytest.approx(1.5)]
    gaps = dict(out["idle_gaps"])
    # idle: 10-10.5, 11-12, 13-14; wait_input covers the first two
    assert gaps["bench.wait_input"] == pytest.approx(1.5)
    assert gaps["bench.dispatch_step"] == pytest.approx(1.0)
    assert gaps["atpu.loader.host_read"] == pytest.approx(1.5)
    assert xtrace.NO_SPAN not in gaps
    assert xtrace.matching(FakeTrace(ops, {}, host), "XLA Ops",
                           "all-reduce") == [[0.25]]


def test_reduce_averages_busy_time_over_the_chips():
    ops = {"/device:TPU:0": [("a", 0.0, 1.0)],
           "/device:TPU:1": [("a", 0.0, 0.5)]}
    host = {"bench.wait_input": [("bench.wait_input", 0.0, 2.0)],
            "bench.dispatch_step": []}
    out = xtrace.reduce(FakeTrace(ops, {}, host))
    assert out["busy_s"] == pytest.approx(0.75)
    assert out["device_ops"] == [["a", pytest.approx(0.75)]]


def test_a_trace_with_no_device_op_is_an_error():
    host = {"bench.wait_input": [("bench.wait_input", 0.0, 1.0)],
            "bench.dispatch_step": []}
    with pytest.raises(ValueError):
        xtrace.reduce(FakeTrace({}, {}, host))


def test_recorded_trace_reduces_to_idle_share_and_breakdown():
    trace = xtrace.Trace(RECORDED)
    assert list(trace.device_ops) == ["/device:TPU:0"]
    assert trace.host["atpu.loader.host_read"]
    out = xtrace.reduce(trace)
    assert 0 < out["busy_s"] < out["window_s"] < 1.0
    idle_share = 1 - out["busy_s"] / out["window_s"]
    assert idle_share > 0.9  # the scan is paced by the host
    assert 1 <= len(out["device_ops"]) <= 10
    assert 1 <= len(out["idle_gaps"]) <= 10
    names = [n for n, _s in out["idle_gaps"]]
    assert "bench.wait_input" in names
    idle_s = out["window_s"] - out["busy_s"]
    assert all(0 < s <= idle_s + 1e-9 for _n, s in out["idle_gaps"])
    sums = xtrace.matching(trace, "XLA Ops", "reduce")
    assert sums and all(d > 0 for d in sums[0])

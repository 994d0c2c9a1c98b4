"""The trace reduction: busy union, op sums, collectives, and idle gaps
attributed to the bench.* host spans; on hand-made events and on a trace
recorded on one TPU v5 lite (one q18-groupby job, committed under data/)."""
import os
import re

import pytest

import benchtest_util as bt
import devtrace

TRACE = os.path.join(bt.BENCH, "tests", "data", "q18-groupby.xplane.pb")
MS = 1_000_000


def _trace():
    spans = [("bench.window", 0, 100 * MS),
             ("bench.dispatch", 0, 10 * MS), ("bench.wait", 10 * MS, 60 * MS),
             ("bench.dispatch", 60 * MS, 65 * MS),
             ("bench.wait", 65 * MS, 100 * MS)]
    dev0 = [("sort.1", 5 * MS, 30 * MS), ("fusion.2", 20 * MS, 40 * MS),
            ("all-reduce.3", 70 * MS, 80 * MS), ("sort.1", 85 * MS, 90 * MS)]
    dev1 = [("sort.1", 0, 50 * MS), ("all-reduce-start.4", 50 * MS, 60 * MS)]
    return devtrace.Trace(devices=[dev0, dev1], spans=spans)


def test_union_and_idle():
    cover = devtrace.union([(5, 30), (20, 40), (50, 55), (-3, 1)], 0, 52)
    assert cover == [[0, 1], [5, 40], [50, 52]]
    assert devtrace.idle(cover, 0, 60) == [(1, 5), (40, 50), (52, 60)]


def test_summary_of_hand_made_trace():
    s = devtrace.summarize(_trace())
    assert s.window_s == pytest.approx(0.1)
    # device 0 busy 35+10+5 ms, device 1 60 ms: mean 55 ms
    assert s.busy_s == pytest.approx(0.055)
    assert s.ops_s["sort.1"] == pytest.approx((25 + 5 + 50) / 2 * 1e-3)
    assert devtrace.op_seconds(s, devtrace.COLLECTIVE) \
        == pytest.approx((10 + 10) / 2 * 1e-3)
    assert devtrace.op_seconds(s, re.compile(r"^sort")) \
        == pytest.approx(0.040)
    # device 0 idle: 0-5 (dispatch), 40-70 (wait 40-60, dispatch 60-65,
    # wait 65-70: most in the first wait), 80-85, 90-100 (wait)
    assert s.gaps[0] == ("bench.wait", pytest.approx(0.030))
    assert sorted(g[0] for g in s.gaps) == ["bench.dispatch"] + \
        ["bench.wait"] * 3
    bd = devtrace.breakdown(s)
    assert bd["device_ops"][0][0] == "sort.1"
    assert len(bd["idle_gaps"]) == 4


def test_gap_outside_every_span():
    tr = devtrace.Trace(devices=[[("x", 0, 10)]],
                        spans=[("bench.window", 0, 30),
                               ("bench.wait", 0, 9)])
    s = devtrace.summarize(tr)
    assert s.gaps == [(devtrace.NO_SPAN, pytest.approx(20e-9))]


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        devtrace.summarize(devtrace.Trace(devices=[[]], spans=[]))


def test_recorded_chip_trace():
    """One q18-groupby job on a TPU v5 lite: the salted sort backend's ops,
    by name; a window the device was busy in all but a few milliseconds."""
    tr = devtrace.load(TRACE)
    assert len(tr.devices) == 1
    assert [n for n, _, _ in tr.spans] == ["bench.window", "bench.dispatch",
                                           "bench.wait"]
    s = devtrace.summarize(tr)
    assert s.window_s == pytest.approx(2.9824995, rel=1e-6)
    assert s.busy_s == pytest.approx(2.9789669, rel=1e-6)
    assert set(s.ops_s) >= {"sort.0", "fusion", "fusion.1", "fusion.2",
                            "dynamic_slice.1"}
    sort = run_metric("sort_ms")
    assert devtrace.op_seconds(s, sort.SORT) == pytest.approx(0.178435842)
    assert devtrace.op_seconds(s, devtrace.COLLECTIVE) == 0.0
    # the device was idle 3.5 ms, while the host was in run() and waiting
    assert sum(g for _, g in s.gaps) == pytest.approx(
        s.window_s - s.busy_s)
    assert {n for n, g in s.gaps if g > 1e-3} == {"bench.dispatch",
                                                 "bench.wait"}
    bd = devtrace.breakdown(s)
    assert [k for k, _ in bd["device_ops"][:4]] == [
        "fusion", "fusion.1", "fusion.2", "sort.0"]


def run_metric(name):
    return bt.run.load_module("metrics", name)

"""The program's own spans and scopes in a trace (bench/progtrace.py) and
the four readers built on them: probe_ms, run_idle_pct, segreduce_ms,
permute_ms.  Spans recorded on the CPU from a real run(); the readers on
hand-made traces, and on one q1-groupby job recorded on a TPU v5 lite
with the spans and scopes in place (committed under data/)."""
import os

import jax
import numpy as np
import pytest

import benchtest_util as bt
import devtrace
import progtrace

Q1_TRACE = os.path.join(bt.BENCH, "tests", "data", "q1-groupby.xplane.pb")
MS = 1_000_000
READERS = ("probe_ms", "run_idle_pct", "segreduce_ms", "permute_ms")


def reader(name):
    return bt.run.load_module("metrics", name)


def test_run_spans_nest_on_the_callers_thread(tmp_path):
    """repro.run holds prepare, salt_probe and then compile (the first
    call) or enqueue (a cache hit), in that order."""
    from repro.core import compile_program, programs
    cp = compile_program(programs.group_by, op_select="force:sort",
                         autotune_cache=str(tmp_path / "none.json"))
    key = np.random.default_rng(1).integers(0, 30, 500).astype(np.float32)
    ins = dict(S=(key, np.ones(500, np.float32)), C=np.zeros(30, np.float32))
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        for _ in range(2):
            jax.block_until_ready(cp.run(ins))
    finally:
        jax.profiler.stop_trace()
    assert (cp.trace_count, cp.cache_hits) == (1, 1)
    pt = progtrace.load(devtrace.latest_xplane(str(tmp_path / "trace")))
    runs = [sp for sp in pt.spans if sp[0] == progtrace.RUN_SPAN]
    assert len(runs) == 2
    inside = [[n for n, s, e in pt.spans
               if n != progtrace.RUN_SPAN and r[1] <= s and e <= r[2]]
              for r in runs]
    assert inside == [
        ["repro.prepare", "repro.salt_probe", "repro.compile"],
        ["repro.prepare", "repro.salt_probe", "repro.enqueue"]]
    assert len(pt.spans) == 8          # nothing outside the two calls


def test_innermost_pieces():
    spans = [("a", 0, 10), ("b", 1, 3), ("c", 3, 5), ("d", 4, 5),
             ("a", 20, 30)]
    assert progtrace.innermost(spans) == [
        ("a", 0, 1), ("b", 1, 3), ("c", 3, 4), ("d", 4, 5), ("a", 5, 10),
        ("a", 20, 30)]


def test_clock_offset_from_program_runs():
    # run 1 enqueued at 10, ran 8-9 on the device's clock, completed at
    # 12: offset in [2, 3]; run 2 enqueued at 20 behind a busy device, ran
    # 19.5-30, done 33: [0.5, 3]; run 3 never enqueued in the trace
    runs = {1: (8, 9), 2: (19.5, 30), 3: (40, 41)}
    enq, done = {1: 10, 2: 20}, {1: 12, 2: 33}
    assert progtrace.clock_offset(runs, enq, done) == (2, 3, 2, 2)
    assert progtrace.clock_offset(runs, {}, done) is None
    assert progtrace.clock_offset(runs, enq, {}) == (2, float("inf"), 2, 2)
    # runs paired wrongly are outvoted: 4 cannot hold any offset, 5 holds
    # [50, 59] alone; a run without an id pairs with nothing
    runs.update({4: (50, 60), 5: (100, 101), None: (0, 1)})
    enq.update({4: 45, 5: 150, None: 500})
    done.update({4: 48, 5: 160, None: 500})
    assert progtrace.clock_offset(runs, enq, done) == (2, 3, 2, 4)


def test_scope_path_and_stage():
    assert progtrace.scope_path(
        "jit(traced)/SegmentReduce:C/sort/jit(argsort)/sort:") \
        == "SegmentReduce:C/sort/jit(argsort)"
    assert progtrace.scope_path("jit(traced)/jit(argsort)/sort:") \
        == "jit(argsort)"
    assert progtrace.node_stage(
        "SeqLoop/while/body/SegmentReduce:NP/reduce/jit(segment_reduce)/"
        "layout") == ("SegmentReduce:NP", "reduce/layout")
    assert progtrace.node_stage("Fused:A+B/SegmentReduce:A/permute") \
        == ("SegmentReduce:A", "permute")
    assert progtrace.node_stage("jit(argsort)") == ("", "")
    assert progtrace.node_stage("") == ("", "")


def _trace(spans=True, scopes=True):
    """Two jobs in a 100 ms window on one device.  Job 1: run 0-20 ms
    (prepare 0-2, probe 2-6, enqueue 6-8), device 5-40; job 2: run 50-60
    (prepare 50-51, probe 51-55, enqueue 55-56), device 58-90."""
    def sc(path):
        return path if scopes else ""
    ops = [("dynamic_slice.1", "", 5 * MS, 6 * MS),          # the probe
           ("sort.1", sc("SegmentReduce:C/sort"), 8 * MS, 20 * MS),
           ("fusion.1", sc("SegmentReduce:C/permute"), 20 * MS, 30 * MS),
           ("fusion.2", sc("SegmentReduce:C/reduce"), 30 * MS, 40 * MS),
           ("while.3", sc("SeqLoop"), 58 * MS, 90 * MS),    # container
           ("fusion.4", sc("SeqLoop/while/body/SegmentReduce:C/keys"),
            58 * MS, 70 * MS),
           ("fusion.5", sc("SeqLoop/while/body/DenseMap:P"),
            70 * MS, 90 * MS)]
    sp = [("repro.run", 0, 20 * MS), ("repro.prepare", 0, 2 * MS),
          ("repro.salt_probe", 2 * MS, 6 * MS),
          ("repro.enqueue", 6 * MS, 8 * MS),
          ("repro.run", 50 * MS, 60 * MS), ("repro.prepare", 50 * MS, 51 * MS),
          ("repro.salt_probe", 51 * MS, 55 * MS),
          ("repro.enqueue", 55 * MS, 56 * MS)]
    return progtrace.ProgTrace(window=(0, 100 * MS), devices=[ops],
                               spans=sp if spans else [])


def _reading(monkeypatch, pt, jobs=2):
    monkeypatch.setattr(devtrace, "latest_xplane", lambda d: "unused")
    monkeypatch.setattr(progtrace, "load", lambda path: pt)
    summary = devtrace.Summary(window_s=0.1, busy_s=0.067, ops_s={}, gaps=[])
    return bt.run.Reading(jobs=jobs, dispatch_s=[0.02, 0.01], compiles=0,
                          work={}, peaks={}, trace=summary)


def test_idle_by_innermost_span():
    idle = progtrace.idle_by_span(_trace())
    # idle 0-5 (prepare 0-2, probe 2-5), 6-8 (enqueue), 40-58 (outside
    # 40-50, prepare 50-51, probe 51-55, enqueue 55-56, run alone 56-58),
    # 90-100 (outside)
    want = {"repro.prepare": 3e-3, "repro.salt_probe": 7e-3,
            "repro.enqueue": 3e-3, "repro.run": 2e-3,
            progtrace.OUTSIDE: 20e-3}
    assert idle.keys() == want.keys()
    for k, v in want.items():
        assert idle[k] == pytest.approx(v), k
    assert progtrace.run_idle_seconds(_trace()) == pytest.approx(15e-3)


def test_readers_on_a_hand_made_trace(monkeypatch):
    r = _reading(monkeypatch, _trace())
    got = {n: reader(n).read(r) for n in READERS}
    assert got["probe_ms"] == pytest.approx(4.0)            # 8 ms / 2 jobs
    assert got["run_idle_pct"] == pytest.approx(15.0)       # 15 of 100 ms
    # sort 12 + permute 10 + reduce 10 + keys 12 ms, the while left out
    assert got["segreduce_ms"] == pytest.approx(22.0)
    assert got["permute_ms"] == pytest.approx(5.0)


def test_readers_none_without_the_programs_spans(monkeypatch):
    """A program without the spans (the parent of the change that added
    them): every reader reads None, none raises."""
    r = _reading(monkeypatch, _trace(spans=False))
    assert {n: reader(n).read(r) for n in READERS} == dict.fromkeys(READERS)
    untraced = bt.run.Reading(jobs=2, dispatch_s=[], compiles=0, work={},
                              peaks={}, trace=None)
    assert {n: reader(n).read(untraced) for n in READERS} \
        == dict.fromkeys(READERS)


def test_scope_readers_none_without_scopes(monkeypatch):
    r = _reading(monkeypatch, _trace(scopes=False))
    assert reader("segreduce_ms").read(r) is None
    assert reader("permute_ms").read(r) is None
    assert reader("probe_ms").read(r) == pytest.approx(4.0)


def test_permute_reads_zero_once_the_gathers_are_gone(monkeypatch):
    pt = _trace()
    pt.devices[0] = [op for op in pt.devices[0] if "permute" not in op[1]]
    r = _reading(monkeypatch, pt)
    assert reader("permute_ms").read(r) == 0.0
    assert reader("segreduce_ms").read(r) == pytest.approx(17.0)


def _recorded_q1():
    tr = devtrace.load(Q1_TRACE)
    (d,) = [e - s for n, s, e in tr.spans if n == "bench.dispatch"]
    return tr, bt.run.Reading(jobs=1, dispatch_s=[d * 1e-9], compiles=0,
                              work={}, peaks={},
                              trace=devtrace.summarize(tr))


def test_recorded_q1_trace(monkeypatch):
    """One q1-groupby job on a TPU v5 lite with the spans and scopes in
    place: the Pallas kernel and its layout under SegmentReduce:C, no
    permutation; the probe's round trip inside run()."""
    monkeypatch.setattr(devtrace, "latest_xplane", lambda d: Q1_TRACE)
    tr, r = _recorded_q1()
    got = {n: reader(n).read(r) for n in READERS}
    assert got == {"probe_ms": pytest.approx(1.75245),
                   "run_idle_pct": pytest.approx(4.661378, rel=1e-6),
                   "segreduce_ms": pytest.approx(37.997319),
                   "permute_ms": 0.0}
    pt = progtrace.load(Q1_TRACE)
    # the device's clock read 1.31-1.72 ms early: the job's program began
    # 1.15 ms before run() enqueued it, the probe's slice before the probe
    assert pt.offsets == [(1312627.0, 1722627.0, 2, 2)]
    # aligned, the device idles through the whole probe (a round trip for
    # 4096 keys; its slice takes under a microsecond)
    idle = progtrace.idle_by_span(pt)
    assert idle["repro.salt_probe"] == pytest.approx(
        progtrace.span_seconds(pt, "repro.salt_probe"), rel=1e-3)
    stages = progtrace.by_stage(pt)
    assert stages[("SegmentReduce:C", "reduce/segment_reduce")] \
        == pytest.approx(0.036242448)
    assert stages[("SegmentReduce:C", "reduce/layout")] \
        == pytest.approx(0.00175464)
    # the readings agree with the benchmark's own
    assert got["probe_ms"] <= 1e3 * r.dispatch_s[0]
    idle_pct = reader("device_idle_pct").read(r)
    assert got["run_idle_pct"] <= idle_pct
    assert got["permute_ms"] <= got["segreduce_ms"] \
        <= 1e3 * r.trace.busy_s / r.jobs


def test_result_line_traced_with_program_spans(monkeypatch):
    """--trace 1 on the q1 cell, reading the recorded q1 trace: the new
    readers join the result line (no roofline without the chip's peaks)."""
    monkeypatch.setattr(devtrace, "latest_xplane", lambda d: Q1_TRACE)
    res = bt.run.measure(bt.tiny_cell("tpch-sf10.q1-groupby"), 2**33 + 7,
                         0.3, True, require_device=False)
    assert set(res["metrics"]) == {"dispatch_ms", "window_compiles",
                                   "device_idle_pct", "probe_ms",
                                   "run_idle_pct", "segreduce_ms"}
    assert res["correct"] is True

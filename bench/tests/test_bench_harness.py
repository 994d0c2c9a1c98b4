"""The harness's result line, its refusal without a chip, the per-layer
readers and the necessary-bytes count (CPU, tiny sizes)."""
import json
import os
import subprocess
import sys

import pytest

import benchtest_util as bt

run = bt.run
TRACE = os.path.join(bt.BENCH, "tests", "data", "q18-groupby.xplane.pb")
BASE_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def test_result_line_untraced():
    res = run.measure(bt.tiny_cell("tpch-sf10.q18-groupby"), 2**31 + 5, 0.3,
                      False, require_device=False)
    assert list(res) == BASE_KEYS + ["checks"]      # the checks come last
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert set(res["metrics"]) == {"setup_s", "job_s"}
    for m in res["metrics"].values():
        assert m["value"] > 0 and m["unit"] == "s"
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert res["device"]["count"] == 1
    for c in res["checks"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]
    json.loads(json.dumps(res))


def test_result_line_traced(monkeypatch):
    """--trace 1: per-layer metrics, busy and window seconds, breakdown.
    The CPU has no device plane, so the trace read is the one recorded on
    the chip."""
    import devtrace
    monkeypatch.setattr(devtrace, "latest_xplane", lambda d: TRACE)
    res = run.measure(bt.tiny_cell("tpch-sf10.q18-groupby"), 9, 0.3, True,
                      require_device=False)
    assert list(res) == BASE_KEYS + ["breakdown", "checks"]
    assert set(res["metrics"]) == {"dispatch_ms", "window_compiles",
                                   "device_idle_pct", "sort_ms"}
    assert res["metrics"]["window_compiles"]["value"] == 0
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    bd = res["breakdown"]
    assert set(bd) == {"device_ops", "idle_gaps"}
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_no_accelerator_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(bt.BENCH, "run.py"),
                        "--workload", bt.CELLS[0], "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0 and p.stdout == ""
    assert "no accelerator" in p.stderr


def test_unknown_cell_is_refused():
    with pytest.raises(run.BenchError):
        run.load_cell("no-such-cell")


def test_necessary_bytes_and_roofline():
    rl = run.load_module("metrics", "segreduce_roofline")
    assert rl.necessary_bytes(59986052, 15000000) == 8 * 59986052 + 8 * 15e6
    import devtrace
    summary = devtrace.Summary(window_s=2.0, busy_s=1.0, ops_s={}, gaps=[])
    r = run.Reading(jobs=4, dispatch_s=[0.001] * 4, compiles=0,
                    work=dict(rows=1000, groups=24),
                    peaks={"hbm_bytes_per_s": 8192e3}, trace=summary)
    # 8192 bytes at 8192e3 B/s = 1 ms against 250 ms busy per job
    assert rl.read(r) == pytest.approx(0.4)
    assert run.load_module("metrics", "dispatch_ms").read(r) \
        == pytest.approx(1.0)
    assert run.load_module("metrics", "device_idle_pct").read(r) \
        == pytest.approx(50.0)
    assert run.load_module("metrics", "sort_ms").read(r) is None
    assert rl.read(run.Reading(jobs=4, dispatch_s=[], compiles=0, work={},
                               peaks={}, trace=None)) is None


def test_seed_key_keeps_high_bits():
    import jax
    a, b = run.seed_key(5), run.seed_key(5 + (1 << 32))
    assert not (jax.random.key_data(a) == jax.random.key_data(b)).all()

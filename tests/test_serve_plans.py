"""Deterministic concurrency tests for the plan-serving layer (§10).

No real sleeps anywhere: every test drives PlanServer with the shared
FakeClock + scripted arrival schedules from conftest, so flush-timeout
decisions replay bit-for-bit.  The core contract under test: a request
served through a padded, vmapped batch returns results BIT-IDENTICAL to a
solo sequential run() — for all three mixed-workload programs, including
ragged shapes that share a bucket (padded) and ones that split buckets.
"""
import numpy as np
import pytest

from conftest import FakeClock, run_schedule
from test_core_programs import data_for

from repro.core import programs as progs
from repro.core.lower import compile_program
from repro.serve import PlanServer

WORKLOADS = ("pagerank", "group_by", "kmeans_step")

_CPS = {}


def cps():
    """Module-shared compiled programs (compilation and batch traces are
    the expensive part; the server under test is cheap)."""
    if not _CPS:
        for name in WORKLOADS:
            _CPS[name] = compile_program(getattr(progs, name))
    return _CPS


def ragged(name, scale, seed):
    """data_for() variant with a rescaled bag — ragged client traffic.
    Dtypes mirror data_for exactly so solo and served requests
    canonicalize identically."""
    rng = np.random.default_rng(seed)
    d = data_for(name)
    if name == "pagerank":
        N, m = int(d["N"]), max(4, int(len(d["E"][0]) * scale))
        d["E"] = (rng.integers(0, N, m).astype(np.float64),
                  rng.integers(0, N, m).astype(np.float64))
    elif name == "group_by":
        m = max(4, int(len(d["S"][0]) * scale))
        d["S"] = (rng.integers(0, 10, m).astype(np.float64),
                  rng.standard_normal(m))
    elif name == "kmeans_step":
        m = max(8, int(len(d["P"][0]) * scale))
        d["P"] = (rng.standard_normal(m) * 3, rng.standard_normal(m) * 3)
        d["D"] = np.zeros((m, d["K"]))
        d["MinD"] = np.full(m, 1e30)
        d["Cl"] = np.zeros(m)
    return d


# scales whose bag lengths round up to ONE shared power-of-two bucket
# (base lengths: pagerank E=30 → 32, group_by S=40 → 64, kmeans P=20 → 32)
SHARED_BUCKET_SCALES = {
    "pagerank": (1.0, 0.9, 0.8, 0.6),        # 30, 27, 24, 18 rows
    "group_by": (1.0, 0.95, 0.9, 0.85),      # 40, 38, 36, 34 rows
    "kmeans_step": (1.0, 0.95, 0.9, 0.85),   # 20, 19, 18, 17 rows
}


def deep_copy(ins):
    return {k: (tuple(np.copy(c) for c in v) if isinstance(v, tuple)
                else np.copy(v) if isinstance(v, np.ndarray) else v)
            for k, v in ins.items()}


def assert_bit_identical(name, ins, out):
    """Serving-path output must equal a solo run() bitwise."""
    ref = cps()[name].run(deep_copy(ins))
    for k, rv in ref.items():
        np.testing.assert_array_equal(out[k], np.asarray(rv),
                                      err_msg=f"{name}:{k}")


def make_server(clock, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("flush_ms", 2.0)
    kw.setdefault("bucket_floor", 8)
    return PlanServer(cps(), clock=clock, **kw)


# ---------------------------------------------------------------------------
# batched == sequential, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", WORKLOADS)
def test_batched_matches_sequential(name, fake_clock):
    srv = make_server(fake_clock)
    reqs = [(ragged(name, 1.0, seed), None) for seed in (0, 1, 2, 3)]
    reqs = [(ins, srv.submit(name, ins)) for ins, _ in reqs]
    assert srv.pump() == 4          # full bucket flushes with no timeout
    for ins, t in reqs:
        assert t.state == "done"
        assert_bit_identical(name, ins, t.output)
    s = srv.stats()
    assert s["flushes"] == 1 and s["batch_traced"] == 1
    assert s["seq_fallbacks"] == 0


@pytest.mark.parametrize("name", WORKLOADS)
def test_ragged_requests_pad_into_shared_bucket(name, fake_clock):
    """Different bag lengths under one bucket edge: padded lanes must not
    perturb results (the §3.4 limit masks), outputs slice back to each
    request's own shapes."""
    srv = make_server(fake_clock)
    reqs = [(ins := ragged(name, sc, seed), srv.submit(name, ins))
            for seed, sc in enumerate(SHARED_BUCKET_SCALES[name])]
    assert len(srv.stats()["buckets"]) == 1     # one shared shape bucket
    assert srv.pump() == 4
    for ins, t in reqs:
        assert_bit_identical(name, ins, t.output)
    (row,) = srv.stats()["buckets"].values()
    assert row["pad"] > 0           # padding actually happened


def test_ragged_shapes_land_in_different_buckets(fake_clock):
    """Lengths on opposite sides of a power-of-two edge split buckets —
    and both still serve bit-identically."""
    srv = make_server(fake_clock, max_batch=2)
    small = ragged("group_by", 0.2, 0)      # 8 rows  → bucket 8 (floor)
    large = ragged("group_by", 2.0, 1)      # 80 rows → bucket 128
    ts = srv.submit("group_by", small)
    tl = srv.submit("group_by", large)
    assert len(srv.stats()["buckets"]) == 2
    assert srv.drain() == 2
    assert_bit_identical("group_by", small, ts.output)
    assert_bit_identical("group_by", large, tl.output)


# ---------------------------------------------------------------------------
# scheduling: full-bucket flush, straggler timeout, scripted arrivals
# ---------------------------------------------------------------------------

def test_straggler_timeout_flush(fake_clock):
    """A single request never fills its bucket; the flush_ms timeout must
    flush it — at exactly the scripted tick, not before."""
    srv = make_server(fake_clock, flush_ms=2.0)
    ins = ragged("group_by", 1.0, 0)
    t = srv.submit("group_by", ins)
    assert srv.pump() == 0                  # t=0: not full, not timed out
    fake_clock.advance(0.0015)
    assert srv.pump() == 0                  # 1.5ms < 2ms: still waiting
    fake_clock.advance(0.0006)
    assert srv.pump() == 1                  # 2.1ms: timeout flush fires
    assert t.state == "done"
    assert_bit_identical("group_by", ins, t.output)
    (row,) = srv.stats()["buckets"].values()
    assert row["reqs"] == 1 and row["flushes"] == 1


def test_scripted_arrivals_mixed_programs(fake_clock):
    """Interleaved arrivals across all three programs on one scripted
    timeline: full buckets flush at arrival, stragglers at timeout."""
    srv = make_server(fake_clock, max_batch=2, flush_ms=2.0)
    tickets = []

    def sub(name, seed):
        ins = ragged(name, 1.0, seed)
        tickets.append((name, ins, srv.submit(name, ins)))

    events = [
        (0.0000, lambda: sub("pagerank", 0)),
        (0.0002, lambda: sub("group_by", 1)),
        (0.0004, lambda: sub("pagerank", 2)),   # fills pagerank bucket
        (0.0006, lambda: sub("kmeans_step", 3)),
        (0.0031, lambda: None),                 # group_by+kmeans time out
    ]
    done = run_schedule(fake_clock, events, srv.pump)
    assert done == 4
    for name, ins, t in tickets:
        assert t.state == "done"
        assert_bit_identical(name, ins, t.output)
    s = srv.stats()
    assert s["admitted"] == s["completed"] == 4 and s["queued"] == 0


def test_second_flush_hits_batch_cache(fake_clock):
    """Same bucket, same lane count → the second flush reuses the traced
    batch computation (no retrace)."""
    srv = make_server(fake_clock, max_batch=2)
    for seed in (0, 1):
        srv.submit("group_by", ragged("group_by", 1.0, seed))
    assert srv.pump() == 2
    for seed in (2, 3):
        srv.submit("group_by", ragged("group_by", 1.0, seed))
    assert srv.pump() == 2
    s = srv.stats()
    assert s["batch_traced"] == 1 and s["batch_hits"] == 1


def test_cancel_before_flush(fake_clock):
    srv = make_server(fake_clock)
    keep = srv.submit("group_by", ragged("group_by", 1.0, 0))
    gone = srv.submit("group_by", ragged("group_by", 1.0, 1))
    assert srv.cancel(gone)
    assert gone.state == "cancelled"
    with pytest.raises(RuntimeError, match="cancelled"):
        gone.result(0)
    assert srv.drain() == 1
    assert keep.state == "done"
    assert not srv.cancel(keep)             # too late: already served
    s = srv.stats()
    assert s["admitted"] == s["completed"] + s["cancelled"] + s["queued"]


# ---------------------------------------------------------------------------
# golden: the observability surface is pinned (cf. test_plan_explain.py)
# ---------------------------------------------------------------------------

def test_explain_serving_golden(fake_clock):
    """Under a fake clock every number in explain_serving() is exact:
    bucket rows, occupancy, pad fraction, latency percentiles,
    throughput, and the batch-signature cache line.  Freshly compiled
    programs (not the module-shared ones) pin the traced/hit counts
    regardless of test order."""
    fresh = {n: compile_program(getattr(progs, n)) for n in WORKLOADS}
    srv = PlanServer(fresh, clock=fake_clock, max_batch=2, flush_ms=2.0,
                     bucket_floor=8)
    for seed, sc in ((0, 1.0), (1, 0.9)):
        srv.submit("group_by", ragged("group_by", sc, seed))
    assert srv.pump() == 2                  # full bucket at t=0
    srv.submit("kmeans_step", ragged("kmeans_step", 1.0, 2))
    fake_clock.advance(0.004)
    assert srv.pump() == 1                  # straggler timeout at t=4ms
    text = srv.explain_serving()
    assert text.splitlines()[0] == (
        "== serving plans: 3 programs, max_batch=2, flush=2.0ms, "
        "bucket_floor=8 ==")
    assert "bucket group_by{S:64}#" in text
    assert "depth=0 reqs=2 flushes=1 occ=100% pad=" in text
    assert "bucket kmeans_step{P:32 Cl:32 D:32 MinD:32 K=4}#" in text
    assert ("totals: admitted=3 completed=3 cancelled=0 failed=0 queued=0"
            in text)
    assert "latency: p50=0.0ms p99=4.0ms  throughput=750.0 req/s" in text
    assert ("whole-program cache: 2 batch signatures traced, 0 hits, "
            "0 sequential fallbacks") in text


# ---------------------------------------------------------------------------
# batchable-entry hooks (core/lower.py, core/plan.py)
# ---------------------------------------------------------------------------

def test_entry_signature_matches_device_signature():
    """Host-side bucketing key == the device-side compile-cache key."""
    for name in WORKLOADS:
        cp = cps()[name]
        ins = ragged(name, 1.0, 0)
        host = cp.entry_signature(cp.canonical_inputs(ins))
        dev = cp._signature(cp.prepare_env(deep_copy(ins)))
        assert host == dev, name


def test_bag_row_aligned_analysis():
    """kmeans' per-point scratch arrays ride the bag's row count; the
    dim-N state of pagerank and group_by's keyed map do not."""
    assert cps()["kmeans_step"].bag_row_aligned == {
        "D": "P", "MinD": "P", "Cl": "P"}
    assert cps()["pagerank"].bag_row_aligned == {}
    assert cps()["group_by"].bag_row_aligned == {}


def test_probe_salted_request_serves_like_solo_run(fake_clock):
    """Where the cost model salts a hot key (TPU costs), a solo run()
    salts from its run-time probe; the served request must take the same
    salted trace — the probe runs host-side at submit and keys the bucket
    — or the two reduce in different orders and differ in the last bit."""
    cp = compile_program(progs.group_by, op_select="force:scatter")
    cp.selector._platform = "tpu"          # its cost row salts hot keys
    rng = np.random.default_rng(3)

    def req():
        keys = np.where(rng.random(1024) < 0.5, 7, rng.integers(0, 64, 1024))
        return dict(S=(keys.astype(np.float64), rng.standard_normal(1024)),
                    C=np.zeros(64))

    reqs = [req() for _ in range(2)]
    srv = PlanServer({"group_by": cp}, clock=fake_clock, max_batch=2)
    tickets = [srv.submit("group_by", deep_copy(r)) for r in reqs]
    assert srv.pump() == 2
    (label,) = srv.stats()["buckets"]
    assert "salt[C]=16" in label, label
    for r, t in zip(reqs, tickets):
        solo = cp.run(deep_copy(r))
        assert "salt=16x[probe]" in cp.explain()
        np.testing.assert_array_equal(t.output["C"], np.asarray(solo["C"]))

"""Fault-injection harness + classified degradation ladder (DESIGN.md
§11): scripted faults at every named site recover to bit-identical output
(same-level retries), recover after exactly one descent (deterministic
errors with a level left to descend to), or surface (deterministic errors
that reproduce at every level) — with every move visible in
explain_faults().  Mid-loop checkpoint/resume rides the same harness: a
SeqLoop killed at iteration k resumes bit-identically.

The distributed ladder (dist.* sites, fused → per-member → REP-everything
→ single-device) runs in a slow subprocess with 8 forced host devices,
like test_core_distributed.py.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from test_core_programs import data_for

from repro.core import compile_program, interpret
from repro.core import faults as F
from repro.core.programs import ALL
from repro.runtime import LoopRunner

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fresh(ins):
    out = {}
    for k, v in ins.items():
        if isinstance(v, tuple):
            out[k] = tuple(np.array(c) for c in v)
        elif isinstance(v, np.ndarray):
            out[k] = v.copy()
        else:
            out[k] = v
    return out


def _quiet(cp):
    cp.faults.sleep = lambda s: None        # no real backoff sleeps
    return cp


def _bitident(a, b):
    return all(np.array_equal(np.asarray(a[k]), np.asarray(b[k]))
               for k in a)


# ---------------------------------------------------------------------------
# harness unit behaviour
# ---------------------------------------------------------------------------

def test_unknown_site_rejected():
    with pytest.raises(ValueError, match="unknown injection site"):
        F.FaultSpec("no.such.site")


def test_unknown_kind_rejected():
    with pytest.raises(ValueError, match="unknown fault kind"):
        F.FaultSpec("lower.node", "flaky")


def test_site_is_noop_without_injector():
    F.site("lower.node", node="MapExpr")     # must not raise or record
    assert F.active() is None


def test_nth_hit_counting():
    with F.inject(F.FaultSpec("lower.node", "transient", nth=3)) as inj:
        for _ in range(2):
            F.site("lower.node")
        with pytest.raises(F.TransientFault):
            F.site("lower.node")
        F.site("lower.node")                 # hit 4: spec exhausted
    assert inj.hits["lower.node"] == 4
    assert [f["hit"] for f in inj.fired] == [3]


def test_classify():
    assert F.classify(F.TransientFault("x")) == "transient"
    assert F.classify(F.CapacityFault("x")) == "capacity"
    assert F.classify(F.DeterministicFault("x")) == "deterministic"
    assert F.classify(MemoryError()) == "capacity"
    assert F.classify(RuntimeError("RESOURCE_EXHAUSTED: oom")) == "capacity"
    assert F.classify(RuntimeError("UNAVAILABLE: peer reset")) == "transient"
    assert F.classify(RuntimeError("DEADLINE_EXCEEDED")) == "transient"
    # the safe default: unknown errors must never be retried forever
    assert F.classify(ValueError("bad user input")) == "deterministic"


def test_classify_real_oom_messages():
    """Verbatim allocator messages captured from jaxlib / XLA / CUDA /
    torch runs: every one must read as capacity, or real OOMs would take
    the retry (or surface) path instead of the chunked rung."""
    real = [
        "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
        "75497472 bytes.",
        "RESOURCE_EXHAUSTED: Ran out of memory in memory space hbm. "
        "Used 33.61G of 15.48G hbm. Exceeded hbm capacity by 18.13G.",
        "Resource exhausted: Out of memory while trying to allocate "
        "4294967296 bytes.",
        "CUDA_ERROR_OUT_OF_MEMORY: out of memory",
        "CUDA out of memory. Tried to allocate 20.00 MiB",
        "INTERNAL: Failed to allocate 1073741824 bytes",
    ]
    for msg in real:
        assert F.classify(RuntimeError(msg)) == "capacity", msg

    # the runtime's exception TYPES classify by name even with an
    # unhelpful message (jaxlib.xla_extension.XlaRuntimeError subclasses
    # RuntimeError; torch raises OutOfMemoryError)
    class XlaRuntimeError(RuntimeError):
        pass

    class OutOfMemoryError(RuntimeError):
        pass

    assert F.classify(OutOfMemoryError("")) == "capacity"
    assert F.classify(XlaRuntimeError("RESOURCE_EXHAUSTED: oom")) \
        == "capacity"
    assert F.classify(XlaRuntimeError("INTERNAL: unknown")) \
        == "deterministic"

    # word-boundary matching: "bloom"/"BOOM" must NOT read as OOM
    assert F.classify(RuntimeError("bloom filter rebuild failed")) \
        == "deterministic"
    assert F.classify(RuntimeError("BOOM")) == "deterministic"
    assert F.classify(RuntimeError("device OOM during fusion")) \
        == "capacity"


def test_run_with_retries_bounded_backoff():
    ledger = F.FaultLedger("t")
    sleeps = []
    attempts = []

    def fn():
        attempts.append(1)
        raise F.TransientFault("UNAVAILABLE")

    with pytest.raises(F.TransientFault):
        F.run_with_retries(fn, policy=F.RetryPolicy(max_retries=3,
                                                    backoff_s=0.01),
                           ledger=ledger, label="x", sleep=sleeps.append)
    assert len(attempts) == 4                # 1 initial + 3 retries
    assert sleeps == [0.01, 0.02, 0.04]      # exponential, recorded
    assert ledger.counters["retry"] == 3


def test_run_with_retries_never_retries_deterministic():
    ledger = F.FaultLedger("t")
    attempts = []

    def fn():
        attempts.append(1)
        raise F.DeterministicFault("user error")

    with pytest.raises(F.DeterministicFault):
        F.run_with_retries(fn, policy=F.RetryPolicy(), ledger=ledger,
                           label="x", sleep=lambda s: None)
    assert len(attempts) == 1 and ledger.counters["retry"] == 0


def test_straggler_watchdog_trailing_median():
    ledger = F.FaultLedger("t")
    for _ in range(5):
        ledger.note_time("round", 0.01)
    ledger.note_time("round", 0.2)           # 20x the trailing median
    assert ledger.counters["straggler"] == 1
    assert "straggler" in ledger.explain()


# ---------------------------------------------------------------------------
# single-device ladder matrix: site x kind x mode on three programs
# ---------------------------------------------------------------------------

PROGRAMS = ("pagerank", "group_by", "kmeans_step")


@pytest.mark.parametrize("name", PROGRAMS)
@pytest.mark.parametrize("site", ("lower.whole_trace", "lower.node"))
@pytest.mark.parametrize("mode", ("whole", "eager"))
def test_transient_recovers_bitidentical(name, site, mode):
    """A transient fault at any site is retried at the SAME ladder level:
    the re-attempt runs the identical computation, so recovery is
    bit-identical to the fault-free run of the same mode."""
    if mode == "eager" and site == "lower.whole_trace":
        pytest.skip("site not on the eager path")
    ins = data_for(name)
    ref = _quiet(compile_program(ALL[name], compile_mode=mode)) \
        .run(_fresh(ins))
    cp = _quiet(compile_program(ALL[name], compile_mode=mode))
    with F.inject(F.FaultSpec(site, "transient", nth=1)) as inj:
        out = cp.run(_fresh(ins))
    assert inj.fired, "spec never fired"
    assert _bitident(out, ref)
    assert cp.faults.counters["retry"] >= 1
    assert cp.faults.counters["recover"] >= 1
    assert cp.faults.counters["descend"] == 0


@pytest.mark.parametrize("name", PROGRAMS)
@pytest.mark.parametrize("site", ("lower.whole_trace", "lower.node"))
def test_deterministic_descends_whole_to_eager(name, site):
    """A deterministic fault inside the whole-program attempt gets its ONE
    ladder descent: the eager level absorbs it (the spec's single firing
    was consumed), and the result is bit-identical to a fault-free EAGER
    run — the recovered path IS the eager path."""
    ins = data_for(name)
    ref = _quiet(compile_program(ALL[name], compile_mode="eager")) \
        .run(_fresh(ins))
    cp = _quiet(compile_program(ALL[name]))
    with F.inject(F.FaultSpec(site, "deterministic", nth=1)) as inj:
        out = cp.run(_fresh(ins))
    assert inj.fired
    assert _bitident(out, ref)
    assert cp.faults.counters["descend"] == 1
    assert cp.faults.level_reached == "eager"
    assert cp.trace_failures == 1 and cp._whole_disabled


@pytest.mark.parametrize("name", PROGRAMS)
@pytest.mark.parametrize("mode", ("whole", "eager"))
def test_deterministic_forever_surfaces(name, mode):
    """A deterministic error that reproduces at every level SURFACES after
    at most one ladder descent — never an infinite retry, and never the
    interpreter oracle (which would silently mask a user error)."""
    cp = _quiet(compile_program(ALL[name], compile_mode=mode))
    with F.inject(F.FaultSpec("lower.node", "deterministic", nth=1,
                              times=10 ** 6)):
        with pytest.raises(F.DeterministicFault):
            cp.run(_fresh(data_for(name)))
    assert cp.faults.counters["descend"] <= 1
    assert cp.faults.level_reached != "interp"


def test_persistent_transient_reaches_interp_oracle():
    """Transients that persist past the bounded retries descend all the
    way to the interpreter oracle — correct float64 results (allclose,
    not bit-identical; the ledger says the level was reached)."""
    name = "group_by"
    ins = data_for(name)
    ref = interpret(ALL[name].program,
                    {k: (np.array(v, np.float64)
                         if isinstance(v, np.ndarray) else v)
                     for k, v in _fresh(ins).items()})
    cp = _quiet(compile_program(ALL[name], compile_mode="eager"))
    with F.inject(F.FaultSpec("lower.node", "transient", nth=1,
                              times=10 ** 6)):
        out = cp.run(_fresh(ins))
    np.testing.assert_allclose(np.asarray(out["C"], np.float64),
                               np.asarray(ref["C"], np.float64),
                               rtol=1e-5, atol=1e-6)
    assert cp.faults.level_reached == "interp"
    assert cp.faults.counters["retry"] >= cp.policy.max_retries


# ---------------------------------------------------------------------------
# per-signature whole-program disable (satellite: sticky _whole_disabled)
# ---------------------------------------------------------------------------

def test_whole_disable_is_per_signature():
    """A trace failure for one input signature must not disable
    whole-program mode for other signatures (the old global boolean did)."""
    cp = _quiet(compile_program(ALL["group_by"]))
    small = data_for("group_by")
    big = dict(small)
    big["S"] = (np.concatenate([small["S"][0]] * 2),
                np.concatenate([small["S"][1]] * 2))
    with F.inject(F.FaultSpec("lower.whole_trace", "deterministic", nth=1)):
        cp.run(_fresh(small))                # signature A: trace fails
    assert cp.trace_failures == 1 and len(cp._whole_bad) == 1
    cp.run(_fresh(big))                      # signature B: traces fine
    assert cp.trace_count == 1
    assert len(cp._whole_bad) == 1           # A still sitting out its ttl


def test_whole_disable_expires_and_retraces():
    """The per-signature disable is a bounded sit-out, not a life
    sentence: after `disable_ttl` eager runs the trace is re-attempted
    (and succeeds once the fault is gone), with the probes counting it."""
    cp = _quiet(compile_program(ALL["group_by"]))
    cp.policy.disable_ttl = 2
    ins = data_for("group_by")
    with F.inject(F.FaultSpec("lower.whole_trace", "deterministic", nth=1)):
        cp.run(_fresh(ins))
    assert cp._whole_disabled and cp.trace_count == 0
    ref = cp.run(_fresh(ins))                # ttl 2 -> 1 (eager)
    cp.run(_fresh(ins))                      # ttl expires -> re-trace
    assert cp.trace_count == 1 and cp.whole_retries == 1
    assert not cp._whole_disabled
    out = cp.run(_fresh(ins))                # whole-program again, cached
    assert cp.cache_hits >= 1
    assert _bitident(out, ref)


def test_explain_faults_renders_ledger():
    cp = _quiet(compile_program(ALL["pagerank"]))
    ins = data_for("pagerank")
    with F.inject(F.FaultSpec("lower.whole_trace", "transient", nth=1)):
        cp.run(_fresh(ins))
    text = cp.explain_faults()
    assert "== fault ledger: pagerank ==" in text
    assert "retries=1 descents=0 recoveries=1" in text
    assert "retry" in text and "[whole]" in text
    assert "whole-program: 0 trace failures" in text


# ---------------------------------------------------------------------------
# mid-loop checkpoint/resume (tentpole part 4)
# ---------------------------------------------------------------------------

def test_seq_loops_numbering():
    from repro.core import plan as P
    cp = compile_program(ALL["pagerank"])
    loops = P.seq_loops(cp.plan)
    assert loops and all(isinstance(n, P.SeqLoop) for _, n in loops)


@pytest.mark.parametrize("every", (1, 2))
def test_midloop_kill_resumes_bitidentical(every, tmp_path):
    """An iterative plan killed at iteration k resumes from the latest
    carry snapshot with BIT-IDENTICAL final outputs vs an uninterrupted
    stepwise run (both execute the same per-iteration computations on the
    same carry values; npz round-trips are exact) — whether every
    iteration was snapshotted or only every other one."""
    from repro.core.plan import seq_loops
    ins = data_for("pagerank")
    ins["num_steps"] = 6.0
    cp = _quiet(compile_program(ALL["pagerank"]))
    assert seq_loops(cp.plan), "pagerank must have a top-level SeqLoop"
    ref = cp.run_stepwise(_fresh(ins))
    runner = LoopRunner(cp, str(tmp_path / "ck"), every=every)
    with F.inject(F.FaultSpec("lower.loop_iter", "deterministic", nth=4,
                              message="kill -9")):
        with pytest.raises(F.DeterministicFault):
            runner.run(_fresh(ins), resume=False)
    at_kill = runner.mgr.latest()
    assert at_kill is not None and runner.saves >= 1
    resumed = LoopRunner(cp, str(tmp_path / "ck"), every=every)
    out = resumed.run(_fresh(ins), resume=True)
    assert resumed.resumed_from == at_kill
    assert _bitident(out, ref)


def test_stepwise_matches_run_allclose():
    """run_stepwise (host-driven loops) is a different XLA compilation
    than run() (on-device lax.while_loop): equal to float tolerance, and
    exactly repeatable against itself — the bit-identity contract of
    resume is stepwise-vs-stepwise."""
    cp = compile_program(ALL["pagerank"])
    ins = data_for("pagerank")
    a = cp.run_stepwise(_fresh(ins))
    b = cp.run(_fresh(ins))
    for k in a:
        np.testing.assert_allclose(np.asarray(a[k], np.float64),
                                   np.asarray(b[k], np.float64),
                                   rtol=1e-5, atol=1e-6)
    assert _bitident(a, cp.run_stepwise(_fresh(ins)))


# ---------------------------------------------------------------------------
# distributed ladder: fused -> per-member -> REP-everything -> single-device
# (subprocess with 8 forced host devices, like test_core_distributed.py)
# ---------------------------------------------------------------------------

_DIST_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import numpy as np
from repro.core import faults as F
from repro.core.distributed import compile_distributed
from repro.core.programs import ALL
from repro.launch.mesh import make_test_mesh

mesh = make_test_mesh((8,), ("data",))
rng = np.random.default_rng(7)
ins = dict(E=(rng.integers(0, 12, 64).astype(np.float64),
              rng.integers(0, 12, 64).astype(np.float64)),
           P=np.full(12, 1/12), NP=np.zeros(12), C=np.zeros(12),
           N=12, num_steps=2.0, steps=0.0, b=0.85)
fn = ALL["pagerank"]
ref = compile_distributed(fn, mesh, ("data",), mode="shardmap").run(ins)

def fresh():
    dp = compile_distributed(fn, mesh, ("data",), mode="shardmap")
    dp.faults.sleep = lambda s: None
    return dp

def maxerr(out):
    return max(float(np.max(np.abs(np.asarray(out[k], np.float64)
                                   - np.asarray(ref[k], np.float64))))
               for k in ref)

# transient at each dist site: same-level retry, bit-identical
for site in ("dist.fused_compile", "dist.round_exec", "dist.exchange"):
    dp = fresh()
    with F.inject(F.FaultSpec(site, "transient", nth=1)) as inj:
        out = dp.run(ins)
    assert inj.fired, site
    assert maxerr(out) == 0.0, (site, maxerr(out))
    assert dp.faults.counters["retry"] >= 1, site
    assert dp.faults.counters["recover"] >= 1, site

# deterministic once at fused compile: ONE descent to per-member rounds,
# bit-identical (fusion never changes results)
dp = fresh()
with F.inject(F.FaultSpec("dist.fused_compile", "deterministic", nth=1)):
    out = dp.run(ins)
assert maxerr(out) == 0.0
assert dp.faults.level_reached == "per-member rounds"

# deterministic once at round exec: descend to REP-everything placements
# (allclose: different placement compiles differently)
dp = fresh()
with F.inject(F.FaultSpec("dist.round_exec", "deterministic", nth=1)):
    out = dp.run(ins)
assert maxerr(out) < 1e-6
assert dp.faults.level_reached == "rep"
assert dp.faults.counters["descend"] == 1

# deterministic FOREVER: surfaces after exactly one ladder descent
dp = fresh()
raised = False
try:
    with F.inject(F.FaultSpec("dist.round_exec", "deterministic", nth=1,
                              times=10**6)):
        dp.run(ins)
except F.DeterministicFault:
    raised = True
assert raised
assert dp.faults.counters["descend"] == 1

# capacity FOREVER: rounds -> chunked (out-of-core streaming), NEVER the
# rep rung — replicating everything ASCENDS the per-device memory curve,
# exactly the wrong move for an OOM
dp = fresh()
with F.inject(F.FaultSpec("dist.round_exec", "capacity", nth=1,
                          times=10**6)):
    out = dp.run(ins)
assert maxerr(out) < 1e-6
assert dp.faults.level_reached == "chunked"
text = dp.explain_faults()
assert "== fault ledger: pagerank ==" in text
assert "ladder-level-reached=chunked" in text
assert "rounds->chunked" in text
assert "rounds->rep" not in text

# with out-of-core disabled there is no rung down the memory curve:
# capacity surfaces at once (never rep, never single-device, which would
# concentrate every input on one device)
dp = compile_distributed(fn, mesh, ("data",), mode="shardmap",
                         out_of_core="off")
dp.faults.sleep = lambda s: None
with F.inject(F.FaultSpec("dist.round_exec", "capacity", nth=1,
                          times=10**6)):
    try:
        dp.run(ins)
        raise AssertionError("capacity fault did not surface")
    except F.CapacityFault:
        pass
assert dp.faults.counters["descend"] == 0
assert dp.faults.level_reached == ""
print("DIST_FAULTS_OK")
"""


@pytest.mark.slow
def test_distributed_fault_ladder():
    r = subprocess.run([sys.executable, "-c", _DIST_CODE],
                       capture_output=True, text=True, cwd=_ROOT,
                       timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "DIST_FAULTS_OK" in r.stdout

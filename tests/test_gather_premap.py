"""A group-by's value whose gathers share one index reads one gather of a
map over the arrays: pagerank's `P[s] / C[s]` as `(P / C)[s]`.  The same
elementwise ops on the same values, so results are bit-identical to one
gather per array; cases that must keep one gather per array do; the
compiled program holds one value gather, the map under `value/premap`; the
executor counts each premap and `explain()` names its arrays.  The
distributed case runs in a subprocess on 4 forced host devices."""
import os
import re
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import compile_program, programs
from repro.core.frontend import bag, loop_program, scalar, vector
from repro.core.lower import ExecContext, PlanExecutor

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def graph(n, m, seed=16):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, n, m).astype(np.float64),
            rng.integers(0, n, m).astype(np.float64))


def pagerank_inputs(n=40, m=300, seed=16):
    return dict(E=graph(n, m, seed), P=np.full(n, 1.0 / n),
                NP=np.zeros(n), C=np.zeros(n), N=n, num_steps=4.0,
                steps=0.0, b=0.85)


def run(prog, inputs, monkeypatch, *, premap=True, **kw):
    """Outputs and the compiled program; `premap=False` runs with the
    premap helper patched off (one gather per array, as before it)."""
    with monkeypatch.context() as m:
        if not premap:
            m.setattr(PlanExecutor, "_premap", lambda self, *a: None)
        cp = compile_program(prog, **kw)
        out = cp.run(dict(inputs))
    return {k: np.asarray(v) for k, v in out.items()}, cp


def assert_identical(a, b):
    assert a.keys() == b.keys()
    for k in a:
        assert a[k].dtype == b[k].dtype and a[k].shape == b[k].shape, k
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


@pytest.mark.parametrize("op_select", ["cost", "force:sort"])
@pytest.mark.parametrize("salting", ["off", "force:4"])
def test_pagerank_bit_identical(tmp_path, monkeypatch, salting, op_select):
    kw = dict(skew_salting=salting, op_select=op_select,
              autotune_cache=str(tmp_path / "none.json"))
    ins = pagerank_inputs()
    got, cp = run(programs.pagerank, ins, monkeypatch, **kw)
    want, cp_off = run(programs.pagerank, ins, monkeypatch, premap=False,
                       **kw)
    assert cp.executor.premaps == 1 and cp_off.executor.premaps == 0
    assert_identical(got, want)


def test_compiled_value_holds_one_gather(tmp_path):
    cp = compile_program(programs.pagerank, op_select="force:sort",
                         autotune_cache=str(tmp_path / "none.json"))
    env = cp.prepare_env(pagerank_inputs())
    static = {k: v for k, v in env.items() if isinstance(v, int)}
    arrays = {k: v for k, v in env.items() if k not in static}

    def whole(arrays):
        e = dict(static, **arrays)
        cp.executor.execute(cp.plan, e, ExecContext())
        return {n: e[n] for n in cp.program.outputs}
    text = jax.jit(whole).lower(arrays).compile().as_text()
    gathers = [line for line in text.splitlines()
               if re.search(r"= \S+ gather\(", line)
               and "SegmentReduce:NP/value/" in line]
    assert len(gathers) == 1, gathers
    scopes = {"/".join(p for p in name.split("/")[1:-1]
                       if not p.startswith("jit("))
              for name in re.findall(r'op_name="([^"]*)"', text)}
    assert "SeqLoop/while/body/SegmentReduce:NP/value/premap" in scopes


@loop_program
def other_index(E: bag[2], P: vector, C: vector, NP: vector):
    for s, d in E:
        NP[d] += P[s] / C[d]


@loop_program
def one_gather(E: bag[2], P: vector, NP: vector, b: scalar):
    for s, d in E:
        NP[d] += P[s] * b


@loop_program
def same_array(E: bag[2], P: vector, NP: vector):
    for s, d in E:
        NP[d] += P[s] * P[s]


@loop_program
def weighted(E: bag[3], P: vector, C: vector, NP: vector):
    for s, d, x in E:
        NP[d] += x * (P[s] / C[s])


@loop_program
def shifted(E: bag[2], P: vector, C: vector, NP: vector, b: scalar):
    for s, d in E:
        NP[d] += (P[s] + b) / max(C[s], 1.0)


def case_inputs(n=40, m=300, nc=None, weights=False):
    rng = np.random.default_rng(7)
    s, d = graph(n, m)
    e = (s, d, rng.uniform(0.5, 2.0, m)) if weights else (s, d)
    return dict(E=e, P=rng.uniform(0.0, 1.0, n),
                C=rng.integers(0, 4, nc or n).astype(np.float64),
                NP=np.zeros(n), b=0.25)


@pytest.mark.parametrize("prog,inputs,note", [
    (other_index, case_inputs(), None),
    (shifted, case_inputs(nc=43), None),
    (programs.pagerank, pagerank_inputs(n=64, m=20), None),
    (one_gather, case_inputs(), None),
    (same_array, case_inputs(), None),
    (weighted, case_inputs(weights=True), "premap=P,C"),
    (shifted, case_inputs(), "premap=P,C"),
], ids=["different-indices", "different-extents", "table-over-rows",
        "single-gather", "same-array-twice", "subexpression",
        "scalar-and-call"])
def test_engages_only_where_it_may(tmp_path, monkeypatch, prog, inputs,
                                   note):
    kw = dict(autotune_cache=str(tmp_path / "none.json"))
    got, cp = run(prog, inputs, monkeypatch, **kw)
    want, _ = run(prog, inputs, monkeypatch, premap=False, **kw)
    assert_identical(got, want)
    assert cp.executor.premaps == (1 if note else 0)
    assert ("premap=" in cp.explain()) == bool(note)
    if note:
        assert note in cp.explain()


def test_counter_and_explain_note(tmp_path):
    cp = compile_program(programs.pagerank,
                         autotune_cache=str(tmp_path / "none.json"))
    assert cp.executor.premaps == 0 and "premap=" not in cp.explain()
    for _ in range(2):
        cp.run(pagerank_inputs())
    assert cp.trace_count == 1 and cp.cache_hits == 1
    assert cp.executor.premaps == 1           # counted at trace time
    selected = [line.strip() for line in cp.explain().splitlines()
                if line.strip().startswith("selected: segment:")]
    # C's value is the constant 1.0; NP's reads P[s] / C[s]
    assert len(selected) == 2 and "premap" not in selected[0]
    assert selected[1].endswith(" premap=P,C"), selected


_DIST_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import re, sys; sys.path.insert(0, "src")
import numpy as np
import jax
from repro.core import programs
from repro.core.distributed import compile_distributed
from repro.core.lower import PlanExecutor
from repro.launch.mesh import make_test_mesh

mesh = make_test_mesh((4,), ("data",))
n = 61                      # not a multiple of 4: P, C padded to 64
rng = np.random.default_rng(11)
ins = dict(E=(rng.integers(0, n, 402).astype(np.int32),
              rng.integers(0, n, 402).astype(np.int32)),
           P=np.full(n, 1.0 / n, np.float32), NP=np.zeros(n, np.float32),
           C=np.zeros(n, np.float32), N=n, num_steps=3.0, steps=0.0,
           b=0.85)


def run():
    # C as row blocks, as the cost model places it at graph500-25's size
    dp = compile_distributed(programs.pagerank, mesh, ("data",),
                             out_of_core="off", op_select="force:shard")
    rounds = []
    call = dp._call_round
    def keep(fn, args, *rest):
        rounds.append((fn, args))
        return call(fn, args, *rest)
    dp._call_round = keep
    out = {k: np.asarray(v) for k, v in dp.run(dict(ins)).items()}
    assert dp.faults.counters["descend"] == 0
    assert "bailed:" not in dp.explain_rounds()
    hlo = [fn.lower(*args).compile().as_text() for fn, args in rounds]
    return out, dp, hlo


got, dp, hlo = run()
assert dp.cp.executor.premaps == 1, dp.cp.executor.premaps
assert "all_gather: C,P" in dp.explain_rounds()
assert "premap=P,C" in dp.explain_rounds()
fused = hlo[-1]
assert "Gather:P" in fused and "Exchange:NP" in fused
value = [l for l in fused.splitlines()
         if re.search(r"= \\S+ gather\\(", l) and "SegmentReduce:NP/value/" in l]
assert len(value) == 1, value
PlanExecutor._premap = lambda self, *a: None
want, dp_off, hlo_off = run()
assert dp_off.cp.executor.premaps == 0
assert len([l for l in hlo_off[-1].splitlines()
            if re.search(r"= \\S+ gather\\(", l)
            and "SegmentReduce:NP/value/" in l]) == 2
for k in want:
    assert got[k].dtype == want[k].dtype, k
    assert np.array_equal(got[k], want[k], equal_nan=True), k
print("PREMAP_DIST_OK")
"""


@pytest.mark.slow
def test_distributed_bit_identical_padded_rows():
    """compile_distributed PageRank over 4 shards with 61 vertices (P and
    C padded row blocks, `array_limits` set): bit-identical to the run
    with the helper patched off; the fused round keeps the all-gather of
    P and holds one value gather where it held two."""
    r = subprocess.run([sys.executable, "-c", _DIST_CODE],
                       capture_output=True, text=True, cwd=_ROOT,
                       timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "PREMAP_DIST_OK" in r.stdout

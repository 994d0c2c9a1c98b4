"""Skew-aware distribution (DESIGN.md §6): hot-key salting decision
goldens under the cost model, probe goldens, cache overrides, degenerate
key streams (all-one-key, Zipf(1.5), negative/out-of-range, empty after
filter) equivalent across every segment backend × salting mode, and the
salted explain()/explain_rounds() observable contract."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import bag, compile_program, loop_program, map_, matrix
from repro.core.op_select import OpSelector, probe_hot_fraction
from repro.core.programs import ALL

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# degenerate programs defined here (not part of the paper's Fig. 3 set)
# ---------------------------------------------------------------------------

@loop_program
def filtered_sum(S: bag[2], C: map_):
    for k, v in S:
        if v > 0.0:
            C[k] += v


@loop_program
def pair_hist(S: bag[2], C: matrix):
    for i, j in S:
        C[i, j] += 1.0


# ---------------------------------------------------------------------------
# decision-table goldens: choose_salt is a deterministic function of the
# (n, k, op, nshards, hot-bucket) class and platform
# ---------------------------------------------------------------------------

def test_salt_decision_table_cpu_never_salts():
    # the CPU scatter loop is sequential whether keys collide or not
    # (dup_row=0): cost mode must keep S=1 even on a one-key column
    sel = OpSelector(mode="cost", cache_path=None, platform="cpu")
    for hot in (0.0, 0.02, 0.4, 1.0):
        dec = sel.choose_salt(n=1 << 16, k=1024, op="+", nshards=8,
                              hot_frac=hot)
        assert dec.backend == "none", (hot, dec)


def test_salt_decision_table_tpu():
    # hardware scatters serialize colliding updates (dup_row=1): a hot
    # key pays, a uniform stream must NOT be salted (fold cost only)
    sel = OpSelector(mode="cost", cache_path=None, platform="tpu")
    hot = sel.choose_salt(n=1 << 16, k=1024, op="+", nshards=8,
                          hot_frac=0.4)
    assert hot.backend == "salt:16", hot
    one_key = sel.choose_salt(n=1 << 16, k=1024, op="+", nshards=8,
                              hot_frac=1.0)
    assert one_key.backend == "salt:16", one_key
    uniform = sel.choose_salt(n=1 << 16, k=1024, op="+", nshards=8,
                              hot_frac=1.0 / 1024)
    assert uniform.backend == "none", uniform
    assert hot.source == "cost"


def test_salt_skew_guard_fair_share():
    # a key holding less than ~4x its fair 1/K share is not "hot": the
    # collision chain is the inherent n/K every group-by pays, so the
    # guard declines before the cost comparison even on TPU
    sel = OpSelector(mode="cost", cache_path=None, platform="tpu")
    dec = sel.choose_salt(n=1 << 16, k=1024, op="+", nshards=8,
                          hot_frac=3.9 / 1024)
    assert dec.backend == "none"
    dec = sel.choose_salt(n=1 << 16, k=4, op="+", nshards=8,
                          hot_frac=0.9)  # k=4: 0.9 < 4 * 0.25 fair share
    assert dec.backend == "none"


def test_salt_cache_entry_overrides_cost(tmp_path):
    # the autotune cache is the override channel in every mode: a pinned
    # salt class must be honored by cost mode (source "cache") — this is
    # also how mesh-owning benchmarks teach CPU runs to salt
    sel = OpSelector(mode="cost", cache_path=None, platform="cpu")
    cls = sel.salt_class(512, 32, "+", 1, 1.0)
    path = tmp_path / "tune.json"
    path.write_text(json.dumps({
        "version": 1, "platform": "cpu",
        "decisions": {cls: {"backend": "salt:8"}}}))
    pinned = OpSelector(mode="cost", cache_path=str(path), platform="cpu")
    dec = pinned.choose_salt(n=512, k=32, op="+", nshards=1, hot_frac=1.0)
    assert (dec.backend, dec.source) == ("salt:8", "cache")
    # a different skew bucket is a different class: the pin must not fire
    miss = pinned.choose_salt(n=512, k=32, op="+", nshards=1,
                              hot_frac=1.0 / 32)
    assert miss.backend == "none"


def test_probe_hot_fraction():
    assert probe_hot_fraction(np.zeros(100)) == 1.0
    assert probe_hot_fraction(np.array([])) == 0.0
    assert probe_hot_fraction(np.arange(64.0)) == 1.0 / 64
    # the probe reads a bounded prefix: O(1) host work per trace
    big = np.arange(1 << 20, dtype=np.float64)
    assert probe_hot_fraction(big, cap=4096) == 1.0 / 4096


# ---------------------------------------------------------------------------
# degenerate key streams × segment backend × salting mode: every
# combination must agree with the unsalted scatter reference
# ---------------------------------------------------------------------------

_NV, _NE = 32, 512


def _streams():
    rng = np.random.default_rng(41)
    vals = rng.standard_normal(_NE)
    yield "one_key", np.zeros(_NE), vals
    yield "zipf", ((rng.zipf(1.5, _NE) - 1) % _NV).astype(np.float64), vals
    yield "neg_oob", rng.integers(-_NV, 2 * _NV, _NE).astype(np.float64), \
        vals
    # empty-after-filter: no row survives `v > 0` in filtered_sum
    yield "all_filtered", rng.integers(0, _NV, _NE).astype(np.float64), \
        -np.abs(vals) - 1.0


def _cases(keys, vals):
    return [
        ("word_count", dict(W=keys.copy(), C=np.zeros(_NV))),
        ("group_by", dict(S=(keys.copy(), vals.copy()), C=np.zeros(_NV))),
        (filtered_sum, dict(S=(keys.copy(), vals.copy()),
                            C=np.zeros(_NV))),
    ]


def _reference(prog, ins):
    cp = compile_program(ALL[prog] if isinstance(prog, str) else prog,
                         op_select="force:scatter", skew_salting="off")
    return np.asarray(cp.run(ins)["C"], np.float64)


@pytest.mark.parametrize("backend", ["scatter", "sort", "onehot",
                                     "pallas"])
@pytest.mark.parametrize("salting", ["off", "force:4"])
def test_degenerate_streams_equivalent(backend, salting):
    for stream, keys, vals in _streams():
        for (prog, ins), (_, ref_ins) in zip(_cases(keys, vals),
                                             _cases(keys, vals)):
            ref = _reference(prog, ref_ins)
            cp = compile_program(
                ALL[prog] if isinstance(prog, str) else prog,
                op_select=f"force:{backend}", skew_salting=salting)
            got = np.asarray(cp.run(ins)["C"], np.float64)
            err = np.abs(got - ref).max()
            name = prog if isinstance(prog, str) else "filtered_sum"
            assert err < 1e-4, (name, stream, backend, salting, err)


def test_empty_filter_stays_zero():
    rng = np.random.default_rng(7)
    keys = rng.integers(0, _NV, _NE).astype(np.float64)
    ins = dict(S=(keys, -np.ones(_NE)), C=np.zeros(_NV))
    cp = compile_program(filtered_sum, skew_salting="force:4")
    assert np.abs(np.asarray(cp.run(ins)["C"])).max() == 0.0


# ---------------------------------------------------------------------------
# the observable contract: static hints and run-time probes show up in
# explain(); shapes salting cannot express are skipped, not broken
# ---------------------------------------------------------------------------

def test_forced_salt_is_visible_in_explain():
    rng = np.random.default_rng(5)
    ins = dict(W=rng.integers(0, _NV, _NE).astype(np.float64),
               C=np.zeros(_NV))
    cp = compile_program(ALL["word_count"], skew_salting="force:4")
    cp.run(ins)
    assert "salt=4x[hint]" in cp.explain(), cp.explain()


def test_probe_salts_only_the_skewed_stream(tmp_path):
    # "auto" mode: the run-time probe keys both the decision and the
    # compile cache.  A cache entry pinned at the one-key skew bucket
    # (dup_row=0 on CPU means cost alone never salts here) fires for the
    # all-one-key stream and must NOT fire for a uniform stream through
    # the SAME CompiledProgram.
    sel = OpSelector(mode="cost", cache_path=None, platform="cpu")
    cls = sel.salt_class(_NE, _NV, "+", 1, 1.0)
    path = tmp_path / "tune.json"
    path.write_text(json.dumps({
        "version": 1, "platform": "cpu",
        "decisions": {cls: {"backend": "salt:8"}}}))
    cp = compile_program(ALL["word_count"], autotune_cache=str(path),
                         skew_salting="auto")
    uniform = np.arange(_NE, dtype=np.float64) % _NV
    cp.run(dict(W=uniform.copy(), C=np.zeros(_NV)))
    assert "salt=" not in cp.explain(), cp.explain()
    skewed = np.zeros(_NE)
    out = cp.run(dict(W=skewed, C=np.zeros(_NV)))
    assert "salt=8x[probe]" in cp.explain(), cp.explain()
    want = np.zeros(_NV)
    want[0] = _NE
    assert np.abs(np.asarray(out["C"]) - want).max() < 1e-4


def test_multikey_2d_dest_skips_salting():
    # C[i, j] has two key columns: the salted rewrite only covers the
    # single-key 1-D map form, so a force:<S> pin must be a no-op here
    rng = np.random.default_rng(3)
    k1 = rng.integers(0, 8, 256).astype(np.float64)
    k2 = rng.integers(0, 8, 256).astype(np.float64)
    cp = compile_program(pair_hist, skew_salting="force:4")
    out = cp.run(dict(S=(k1.copy(), k2.copy()), C=np.zeros((8, 8))))
    ref = np.zeros((8, 8))
    np.add.at(ref, (k1.astype(int), k2.astype(int)), 1.0)
    assert np.abs(np.asarray(out["C"]) - ref).max() < 1e-4
    assert "salt=" not in cp.explain()


# ---------------------------------------------------------------------------
# distributed: degenerate streams through both exchanges × salting on a
# forced 8-device host mesh, equivalent to single-device; the salted
# round is visible in explain_rounds()
# ---------------------------------------------------------------------------

_DIST_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import numpy as np
from repro.core import compile_program
from repro.core.distributed import compile_distributed
from repro.core.programs import ALL
from repro.launch.mesh import make_test_mesh

mesh = make_test_mesh((8,), ("data",))
rng = np.random.default_rng(13)

def streams(nv, ne):
    return {
        "one_key": np.zeros(ne),
        "zipf": ((rng.zipf(1.5, ne) - 1) % nv).astype(np.float64),
        "neg_oob": rng.integers(-nv, 2 * nv, ne).astype(np.float64),
    }

def run_case(nv, ne, op_select, salting, want, forbid=()):
    for stream, keys in streams(nv, ne).items():
        vals = rng.standard_normal(ne)
        cp = compile_program(ALL["group_by"], op_select=op_select,
                             skew_salting=salting)
        dp = compile_distributed(cp, mesh, ("data",), mode="shardmap")
        out = dp.run(dict(S=(keys.copy(), vals.copy()), C=np.zeros(nv)))
        single = compile_program(ALL["group_by"]).run(
            dict(S=(keys.copy(), vals.copy()), C=np.zeros(nv)))
        err = np.abs(np.asarray(out["C"], np.float64)
                     - np.asarray(single["C"], np.float64)).max()
        assert err < 1e-4, (stream, op_select, salting, err)
        text = dp.explain_rounds()
        for w in want:
            assert w in text, (stream, w, text)
        for f in forbid:
            assert f not in text, (stream, f, text)

# large K, reduce-scatter exchange, salted rounds: the salt*K+key
# sub-destinations fold back before the exchange, so the wire format
# (dense [K] partial) is unchanged
run_case(1 << 19, 4096, "force:psum_scatter", "force:4",
         want=["reduce(psum_scatter", "salt=4x[hint]"])
# same shapes through the allreduce exchange, unsalted
run_case(1 << 19, 4096, "force:allreduce", "off",
         want=["reduce(allreduce[forced]"], forbid=["salt="])
# small K demotes the destination to REP (plain psum): salting must
# compose with the replicated round too
run_case(128, 2048, "cost", "force:4",
         want=["placement: C→REP", "salt=4x[hint]"])
print("SKEW_DIST_OK")
"""


@pytest.mark.slow
def test_distributed_degenerate_streams():
    r = subprocess.run([sys.executable, "-c", _DIST_CODE],
                       capture_output=True, text=True, cwd=_ROOT)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "SKEW_DIST_OK" in r.stdout

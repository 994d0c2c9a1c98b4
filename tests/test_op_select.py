"""Operator-selection subsystem (core/op_select.py, DESIGN.md §8):
decision-table goldens under the forced cost model, autotune-cache
round-trips, backend equivalence on randomized programs, and the
explain()/explain_rounds() observable contract."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import compile_program
from repro.core.op_select import (EXCHANGE_CANDIDATES, SEGMENT_CANDIDATES,
                                  OpSelector)
from repro.core.programs import ALL

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# decision-table goldens: the cost model is a deterministic function of the
# shape class and platform (autotune may override it; these pin the model)
# ---------------------------------------------------------------------------

def test_cost_model_decision_table_cpu():
    sel = OpSelector(mode="cost", cache_path=None, platform="cpu")
    table = [
        # (n, k, d, op)                      -> expected backend
        ((200_000, 1000, 1, "+"), "scatter"),   # large N: scatter wins
        ((8192, 128, 1, "+"), "scatter"),
        ((4096, 16, 1, "+"), "scatter"),        # tiny K as well
        ((512, 8, 1, "+"), "scatter"),
        ((200_000, 1000, 1, "min"), "scatter"),
        ((65_536, 4096, 1, "*"), "scatter"),     # sort never wins on cpu
    ]
    for (n, k, d, op), want in table:
        dec = sel.choose_segment(n=n, k=k, d=d, op=op, dtype="float32",
                                 dest_dist="ONED_ROW")
        assert dec.backend == want, ((n, k, d, op), dec)
        assert dec.source == "cost"


def test_cost_model_decision_table_tpu():
    # the pallas MXU kernel is only ever cost-picked on a real TPU backend
    sel = OpSelector(mode="cost", cache_path=None, platform="tpu")
    big = sel.choose_segment(n=200_000, k=1000, d=1, op="+",
                             dtype="float32", dest_dist="ONED_ROW")
    assert big.backend == "pallas"
    small = sel.choose_segment(n=512, k=8, d=1, op="+", dtype="float32",
                               dest_dist="ONED_ROW")
    assert small.backend == "pallas"
    cpu = OpSelector(mode="cost", cache_path=None, platform="cpu")
    for n, k in [(512, 8), (8192, 128), (200_000, 1000)]:
        dec = cpu.choose_segment(n=n, k=k, d=1, op="+", dtype="float32",
                                 dest_dist="ONED_ROW")
        assert dec.backend != "pallas", (n, k, dec)


@pytest.mark.parametrize("cell,n,k,want", [
    ("graph500-s22.pagerank10", 128_310_114, 2_396_887, "sort"),
    ("tpch-sf10.q18-groupby", 59_986_052, 15_000_000, "sort"),
    ("tpch-sf10.q1-groupby", 59_986_052, 6, "pallas"),
    # the per-chip share of the four-chip cell's fold
    ("graph500-s25.pagerank10-mesh4", 261_802_476, 17_063_336, "sort")])
def test_tpu_cost_row_picks_at_cell_shapes(cell, n, k, want):
    # the backend each chip benchmark cell runs, pinned at its shape: a
    # change to the candidate sets or the TPU cost row must not move it
    sel = OpSelector(mode="cost", cache_path=None, platform="tpu")
    for dest in ("REP", "ONED_ROW"):
        dec = sel.choose_segment(n=n, k=k, d=1, op="+", dtype="float32",
                                 dest_dist=dest)
        assert (dec.backend, dec.source) == (want, "cost"), (cell, dest, dec)


@pytest.mark.parametrize("dtype", ["int32", "uint32"])
def test_tpu_never_picks_pallas_for_an_integer_sum(dtype):
    # v5e's MXU has no int32 dot, so Mosaic refuses the kernel's exact-int
    # sum: the TPU row must route integer sums elsewhere, even when forced
    for mode in ("cost", "force:pallas"):
        sel = OpSelector(mode=mode, cache_path=None, platform="tpu")
        for n, k in [(1 << 24, 256), (1 << 27, 64)]:
            assert sel.choose_segment(n=n, k=k, d=1, op="+",
                                      dtype=dtype).backend != "pallas"
    # float sums and integer min/max keep the kernel
    sel = OpSelector(mode="cost", cache_path=None, platform="tpu")
    assert sel.choose_segment(n=1 << 24, k=256, d=1, op="+",
                              dtype="float32").backend == "pallas"
    assert sel.choose_segment(n=1 << 24, k=256, d=1, op="min",
                              dtype=dtype).backend == "pallas"


@pytest.mark.parametrize("platform,mode,expect", [
    ("cpu", "cost", False), ("cpu", "force:scatter", False),
    ("cpu", "force:pallas", True), ("cpu", "force:pallas-tiled", True),
    ("tpu", "cost", True)])
def test_may_pick_pallas_only_where_a_kernel_can_be_chosen(platform, mode,
                                                            expect):
    # distributed rounds keep shard_map's replication check unless this
    # says an interpreted kernel may run in them
    sel = OpSelector(mode=mode, cache_path=None, platform=platform)
    assert sel.may_pick_pallas() is expect


def test_cost_model_refuses_a_platform_without_a_cost_row():
    # an unknown backend must not silently borrow the CPU's constants
    sel = OpSelector(mode="cost", cache_path=None, platform="metal")
    with pytest.raises(ValueError, match="no cost table.*'metal'"):
        sel.choose_segment(n=4096, k=64, d=1, op="+", dtype="float32")


def test_candidate_sets_respect_monoid():
    assert "pallas" not in SEGMENT_CANDIDATES["*"]
    assert set(SEGMENT_CANDIDATES["+"]) == {"scatter", "sort", "pallas"}
    for op in ("min", "max"):
        assert set(SEGMENT_CANDIDATES[op]) == {"scatter", "sort", "pallas"}


def test_exchange_decision_table():
    sel = OpSelector(mode="cost", cache_path=None, platform="cpu")
    # + into a row-block destination: reduce-scatter (K/P rows per shard)
    dec = sel.choose_exchange(k=1024, d=1, op="+", nshards=8, n_local=128,
                              dest_dist="ONED_ROW")
    assert dec.backend == "psum_scatter"
    # non-+ monoids have no reduce-scatter primitive
    dec = sel.choose_exchange(k=1024, d=1, op="min", nshards=8,
                              n_local=128, dest_dist="ONED_ROW")
    assert dec.backend == "allreduce"
    # replicated destination: allreduce is the only candidate
    dec = sel.choose_exchange(k=1024, d=1, op="+", nshards=8, n_local=128,
                              dest_dist="REP")
    assert dec.backend == "allreduce"
    assert set(EXCHANGE_CANDIDATES) == {"psum_scatter", "allreduce"}


def test_reduce_dest_decision_table():
    sel = OpSelector(mode="cost", cache_path=None, platform="cpu")
    # tiny destination: sharding pays placement overhead for nothing
    small = sel.choose_reduce_dest(k=128, d=1, op="+", nshards=8)
    assert small.backend == "replicate"
    # large destination: dense partial + reduce-scatter wins (K/P rows
    # per shard instead of K everywhere)
    big = sel.choose_reduce_dest(k=1 << 20, d=1, op="+", nshards=8)
    assert big.backend == "shard"


def test_demotable_dests_static_analysis():
    from repro.core.dist_analysis import demotable_dests
    # word_count's C is only ever an unaligned reduce destination
    cp = compile_program(ALL["word_count"])
    assert "C" in demotable_dests(cp.plan, cp.program)
    # pagerank: C is an unaligned dest + cross-shard read (demotable),
    # but P and NP have aligned store rounds — never demoted
    cp = compile_program(ALL["pagerank"])
    dem = demotable_dests(cp.plan, cp.program)
    assert "C" in dem and "P" not in dem and "NP" not in dem


def test_contract_decision_per_platform():
    cpu = OpSelector(mode="cost", cache_path=None, platform="cpu")
    tpu = OpSelector(mode="cost", cache_path=None, platform="tpu")
    # off-TPU the Pallas tiled kernel runs in python-level interpret mode
    assert cpu.choose_contract(m=512, k=512, n=512).backend == \
        "unpack-einsum"
    assert tpu.choose_contract(m=512, k=512, n=512).backend == \
        "pallas-tiled"


# ---------------------------------------------------------------------------
# autotune: measure once per shape class, persist, reload identically
# ---------------------------------------------------------------------------

def test_autotune_cache_roundtrip(tmp_path):
    cache = str(tmp_path / "autotune.json")
    sel = OpSelector(mode="autotune", cache_path=cache)
    d1 = sel.choose_segment(n=256, k=16, d=1, op="+", dtype="float32",
                            dest_dist="REP")
    assert d1.source == "autotune"
    assert os.path.exists(cache)
    blob = json.load(open(cache))
    assert blob["version"] == 1 and len(blob["decisions"]) == 1
    entry = next(iter(blob["decisions"].values()))
    assert entry["backend"] == d1.backend
    assert set(entry["us"]) == set(SEGMENT_CANDIDATES["+"])
    # a fresh selector reloads the decision without re-measuring
    sel2 = OpSelector(mode="autotune", cache_path=cache)
    d2 = sel2.choose_segment(n=256, k=16, d=1, op="+", dtype="float32",
                             dest_dist="REP")
    assert d2.source == "cache" and d2.backend == d1.backend
    # same shape CLASS (power-of-two bucket) → same cached decision
    d3 = sel2.choose_segment(n=250, k=15, d=1, op="+", dtype="float32",
                             dest_dist="REP")
    assert d3.source == "cache" and d3.backend == d1.backend


def test_autotune_compile_produces_identical_plan(tmp_path):
    cache = str(tmp_path / "autotune.json")
    rng = np.random.default_rng(3)
    ins = dict(S=(rng.integers(0, 50, 2000).astype(float),
                  rng.standard_normal(2000)), C=np.zeros(50))

    def run_once():
        cp = compile_program(ALL["group_by"], op_select="autotune",
                             autotune_cache=cache)
        out = cp.run(dict(S=(ins["S"][0].copy(), ins["S"][1].copy()),
                          C=np.zeros(50)))
        sel_lines = [ln for ln in cp.explain().splitlines()
                     if "selected:" in ln]
        return np.asarray(out["C"]), sel_lines

    c1, lines1 = run_once()          # measures + persists
    c2, lines2 = run_once()          # reloads from disk
    np.testing.assert_allclose(c1, c2)
    assert len(lines1) == 1 and "segment:" in lines1[0]
    assert lines2[0].replace("[cache]", "[autotune]") == lines1[0] \
        or lines1 == lines2          # same backend, cache provenance


# ---------------------------------------------------------------------------
# backend equivalence: every candidate computes the same ⊕-merge with the
# paper's drop semantics (negative and out-of-range keys)
# ---------------------------------------------------------------------------

_FORCE_MODES = ("force:scatter", "force:sort", "force:pallas")


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_backend_equivalence_randomized(seed):
    rng = np.random.default_rng(seed)
    nv, ne = int(rng.integers(8, 60)), int(rng.integers(16, 400))
    # keys deliberately include negatives and ≥ nv (must drop everywhere)
    keys = rng.integers(-4, nv + 5, ne).astype(np.float64)
    vals = rng.standard_normal(ne)
    cases = {
        "word_count": dict(W=keys.copy(), C=np.zeros(nv)),
        "group_by": dict(S=(keys.copy(), vals.copy()), C=np.zeros(nv)),
        "histogram": dict(P=tuple(rng.integers(-2, nv + 2, ne)
                                  .astype(np.float64) for _ in range(3)),
                          R=np.zeros(nv), G=np.zeros(nv), B=np.zeros(nv)),
    }
    for name, ins in cases.items():
        ref = None
        for mode in _FORCE_MODES:
            cp = compile_program(ALL[name], op_select=mode)
            out = cp.run({k: (tuple(c.copy() for c in v)
                              if isinstance(v, tuple) else
                              (v.copy() if isinstance(v, np.ndarray) else v))
                          for k, v in ins.items()})
            got = {k: np.asarray(v, np.float64) for k, v in out.items()}
            if ref is None:
                ref = got
                continue
            for k in ref:
                np.testing.assert_allclose(got[k], ref[k], rtol=1e-4,
                                           atol=1e-4,
                                           err_msg=f"{name}/{mode}/{k}")


def test_onehot_and_pallas_drop_nonfinite_values():
    # dropped rows may carry non-finite values (a condition guarding a
    # division); the Pallas kernel's one-hot dot must zero them — 0 × inf
    # = NaN would otherwise contaminate every segment the matmul touches
    import jax.numpy as jnp
    from repro.core.frontend import bag, loop_program, map_

    @loop_program
    def safe_inv(S: bag[2], C: map_):
        for k, v in S:
            if v != 0.0:
                C[k] += 1.0 / v

    keys = np.array([0.0, 1.0, 2.0, 1.0])
    vals = np.array([2.0, 0.0, 4.0, 8.0])     # row 1 dropped, 1/0 = inf
    want = np.array([0.5, 0.125, 0.25])
    for mode in _FORCE_MODES:
        out = compile_program(safe_inv, op_select=mode).run(
            dict(S=(keys.copy(), vals.copy()), C=np.zeros(3)))
        got = np.asarray(out["C"], np.float64)
        assert np.isfinite(got).all(), (mode, got)
        np.testing.assert_allclose(got, want, rtol=1e-5, err_msg=mode)
    # and the kernel directly, with an OOB-row inf
    from repro.kernels.segment_reduce import segment_reduce
    r = segment_reduce(jnp.asarray(np.array([0, 99, -1], np.int32)),
                       jnp.asarray(np.array([1.0, np.inf, np.nan],
                                            np.float32)), 2)
    np.testing.assert_allclose(np.asarray(r), [1.0, 0.0])


def test_force_unpack_einsum_respected_on_packed_lhs():
    # a pinned single-candidate TiledMatmul must honor the pin, not fall
    # through to the Pallas kernel
    import jax.numpy as jnp
    from repro.core.tiles import pack
    rng = np.random.default_rng(5)
    n, m, l = 32, 24, 16
    M = rng.standard_normal((n, l))
    N = rng.standard_normal((l, m))
    tm = pack(jnp.asarray(M, jnp.float32), bm=16, bn=16)
    for mode, tag in [("force:unpack-einsum", "tiled:unpack-einsum[pinned]"),
                      ("force:pallas-tiled", "tiled:pallas-tiled[pinned]"),
                      ("cost", "tiled:unpack-einsum[cost]")]:  # cpu model
        cp = compile_program(ALL["matrix_multiplication"], op_select=mode)
        out = cp.run(dict(M=tm, N=N, R=np.zeros((n, m)), n=n, m=m, l=l))
        np.testing.assert_allclose(np.asarray(out["R"]), M @ N, rtol=1e-3,
                                   atol=1e-4, err_msg=mode)
        assert tag in cp.explain(tiled={"M"}), (mode, cp.explain(tiled={"M"}))


def test_force_dense_grid_skips_einsum():
    cp = compile_program(ALL["matrix_multiplication"],
                         op_select="force:dense-grid")
    rng = np.random.default_rng(6)
    A, B = rng.standard_normal((12, 8)), rng.standard_normal((8, 10))
    out = cp.run(dict(M=A, N=B, R=np.zeros((12, 10)), n=12, m=10, l=8))
    np.testing.assert_allclose(np.asarray(out["R"]), A @ B, rtol=1e-4,
                               atol=1e-4)
    assert "selected: fallback:dense-grid" in cp.explain()


def test_forced_backend_shows_in_explain():
    cp = compile_program(ALL["word_count"], op_select="force:sort")
    assert "backend=sort" in cp.explain()
    cp.run(dict(W=(np.array([1.0, 2.0, 1.0]),), C=np.zeros(4)))
    assert "selected: segment:sort[pinned]" in cp.explain()


def test_backend_selection_reaches_fused_parts():
    # operator-selection runs AFTER update-fusion: the three fused
    # histogram updates must each get candidates / honor forcing
    cp = compile_program(ALL["histogram"])
    assert cp.explain().count("backend=auto{") == 3
    cp = compile_program(ALL["histogram"], op_select="force:sort")
    assert cp.explain().count("backend=sort") == 3


def test_auto_backend_selected_line_golden(tmp_path):
    # empty cache path isolates the golden from any developer-local
    # .repro_autotune.json (the cache overrides cost mode by design)
    cp = compile_program(ALL["group_by"],
                         autotune_cache=str(tmp_path / "cache.json"))
    assert "backend=auto{scatter|sort|pallas}" in cp.explain()
    assert "selected:" not in cp.explain()      # no run yet: no decision
    rng = np.random.default_rng(0)
    cp.run(dict(S=(rng.integers(0, 1000, 200_000).astype(float),
                   rng.standard_normal(200_000)), C=np.zeros(1000)))
    # the committed CPU cost table picks scatter for this class
    assert "selected: segment:scatter[cost]" in cp.explain()


def test_cost_mode_honors_cache_override(tmp_path):
    # the cache file is the override channel in EVERY mode: a supplied
    # entry beats the analytical model (e.g. pinning the exchange on a
    # platform whose reduce-scatter lowering underperforms)
    sel0 = OpSelector(mode="cost", cache_path=None, platform="cpu")
    key = sel0.exchange_class(4096, 1, "+", 8, 512)
    cache = tmp_path / "cache.json"
    cache.write_text(json.dumps({
        "version": 1, "platform": "cpu",
        "decisions": {key: {"backend": "allreduce"}}}))
    sel = OpSelector(mode="cost", cache_path=str(cache), platform="cpu")
    dec = sel.choose_exchange(k=4096, d=1, op="+", nshards=8, n_local=512,
                              dest_dist="ONED_ROW")
    assert dec.backend == "allreduce" and dec.source == "cache"


def test_force_inapplicable_falls_through_to_model():
    # force:pallas cannot apply to a *-group-by (the kernel has no
    # product): the selector must fall through to the cost model, not
    # silently pin the first candidate
    sel = OpSelector(mode="force:pallas", cache_path=None, platform="cpu")
    dec = sel.choose_segment(n=8192, k=128, d=1, op="*", dtype="float32",
                             dest_dist="REP")
    assert dec.backend == "scatter" and dec.source == "cost"


def test_use_kernels_legacy_flag_pins_pallas():
    cp = compile_program(ALL["word_count"], use_kernels=True)
    assert "backend=pallas" in cp.explain()
    out = cp.run(dict(W=(np.array([0.0, 1.0, 1.0, 3.0]),), C=np.zeros(4)))
    np.testing.assert_allclose(np.asarray(out["C"]), [1, 2, 0, 1])
    assert "selected: segment:pallas[pinned]" in cp.explain()


# ---------------------------------------------------------------------------
# distributed: the exchange is an op_select decision, printed per round
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
rng = np.random.default_rng(11)

def run_case(nv, ne):
    ins = dict(S=(rng.integers(0, nv, ne).astype(np.float64),
                  rng.standard_normal(ne)), C=np.zeros(nv))
    cp = compile_program(ALL["group_by"])
    dp = compile_distributed(cp, mesh, ("data",))
    out = dp.run(ins)
    single = compile_program(ALL["group_by"]).run(
        dict(S=(ins["S"][0].copy(), ins["S"][1].copy()), C=np.zeros(nv)))
    err = np.abs(np.asarray(out["C"], np.float64)
                 - np.asarray(single["C"], np.float64)).max()
    assert err < 1e-4, (nv, err)
    return dp.explain_rounds()

# small K: sharding the 128-row destination doesn't pay — op_select
# demotes it to REP and the exchange is a plain psum
text = run_case(128, 1024)
assert "placement: C→REP (dest-replicate[cost])" in text, text
assert "reduce(psum)" in text, text
assert "per-shard[C]: segment:" in text, text

# large K: the dense partial + reduce-scatter exchange pays; the
# destination stays ONED_ROW and the round uses psum_scatter
text = run_case(1 << 19, 4096)
assert "placement:" not in text, text
assert "reduce(psum_scatter[cost])" in text, text
print("OPSEL_DIST_OK")
"""


@pytest.mark.slow
def test_exchange_decision_in_rounds():
    r = subprocess.run([sys.executable, "-c", _DIST_CODE],
                       capture_output=True, text=True, cwd=_ROOT)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OPSEL_DIST_OK" in r.stdout

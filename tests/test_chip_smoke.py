"""chip_smoke.py's phases at tiny sizes on the CPU, with every check on:
numpy float64 references, no ladder descent or retry, no fused-round bail,
PlanServer answers equal to solo runs.  The distributed phase runs on 4
forced host devices in a subprocess (this process keeps one device), so an
API break that drops a fused round to REP fails here, not silently."""
import os
import subprocess
import sys

import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

import chip_smoke as cs  # noqa: E402

# large enough that the TPU cost row picks the Pallas kernel for the
# histogram and the served group-bys, as it does at full size
TINY = cs.Sizes(rmat_scale=8, edge_factor=8, pagerank_steps=3,
                group_rows=4096, group_keys=256, hist_pixels=8192,
                hist_bins=32, kmeans_points=2048, kmeans_k=16, matmul_n=256,
                serve_scale=8, serve_rows=4096, serve_keys=64,
                serve_requests=4)

_STEPS = [label for label, _ in cs.single_steps(TINY, 0, "cpu")]


@pytest.mark.parametrize("costs", ["cpu", "tpu"])
@pytest.mark.parametrize("label", _STEPS)
def test_single_device_step_matches_numpy(label, costs, monkeypatch):
    # costs="tpu": the TPU cost row's decisions (Pallas kernels in
    # interpret mode, probe-decided salting, pallas-tiled), which the
    # step then requires where chip_smoke.TPU_EXPECT names one
    from repro.core.op_select import OpSelector
    monkeypatch.setattr(OpSelector, "platform", property(lambda s: costs))
    steps = dict(cs.single_steps(TINY, 0, costs))
    recs = steps[label]()
    recs = recs if isinstance(recs, list) else [recs]
    for rec in recs:
        assert rec["max_rel_err"] <= cs.TOL
        assert rec["selected"], rec      # op_select decisions were recorded


@pytest.mark.parametrize("costs", ["cpu", "tpu"])
def test_serve_phase_bit_identical_to_solo_runs(costs, monkeypatch):
    from repro.core.op_select import OpSelector
    monkeypatch.setattr(OpSelector, "platform", property(lambda s: costs))
    rec = cs.phase_serve(TINY, 0)
    assert rec["requests"] == 2 * TINY.serve_requests
    assert rec["max_rel_err"] <= cs.TOL


def test_main_refuses_a_non_tpu_platform(capsys):
    assert cs.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_checks_catch_descent_and_tolerance():
    from repro.core import compile_program
    from repro.core.programs import group_by
    cp = compile_program(group_by)
    cs.check_clean("clean", cp)
    cp.faults.descend("whole", "eager", RuntimeError("boom"))
    with pytest.raises(cs.SmokeFailure, match="fallback taken"):
        cs.check_clean("descended", cp)
    with pytest.raises(cs.SmokeFailure, match="relative error"):
        cs.close("off", np.array([1.0, 2.0]), np.array([1.0, 2.1]))
    with pytest.raises(cs.SmokeFailure, match="non-finite"):
        cs.close("nan", np.array([np.nan]), np.array([1.0]))


def test_normwise_check_passes_float32_and_catches_one_bf16_pass():
    """A long float32 inner product can miss the elementwise measure where
    its terms cancel, yet is normwise exact to ~1e-6; one bf16 pass (a
    TPU f32 dot at DEFAULT precision) misses the normwise measure too."""
    import ml_dtypes
    rng = np.random.default_rng(0)
    m = rng.standard_normal((64, 8192), dtype=np.float32)
    n = rng.standard_normal((8192, 256), dtype=np.float32)
    ref = m.astype(np.float64) @ n.astype(np.float64)
    assert cs.close("f32", m @ n, ref, elementwise=False) < 1e-5
    bf = [x.astype(ml_dtypes.bfloat16).astype(np.float64) for x in (m, n)]
    with pytest.raises(cs.SmokeFailure, match="relative error"):
        cs.close("bf16", bf[0] @ bf[1], ref, elementwise=False)


def test_rmat_edges_follow_the_graph500_generator():
    src, dst = cs.rmat_edges(np.random.default_rng(0), 10, 16)
    assert src.shape == dst.shape == (16 << 10,)
    assert src.min() >= 0 and max(src.max(), dst.max()) < 1 << 10
    # a=0.57 skew: the hottest vertex holds far more than its 1/N share
    assert np.bincount(src).max() > 20 * src.size / (1 << 10)


_DIST_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys; sys.path.insert(0, "src"); sys.path.insert(0, ".")
import chip_smoke as cs
tiny = cs.Sizes(rmat_scale=8, edge_factor=8, pagerank_steps=3,
                group_rows=4096, group_keys=256)
recs = cs.phase_distributed(tiny, 0, 4)
print("DIST_OK", len(recs))
# again with the TPU cost row's decisions (sort + probe-decided salting)
from repro.core.op_select import OpSelector
OpSelector.platform = property(lambda s: "tpu")
recs = cs.phase_distributed(tiny, 0, 4)
print("DIST_OK", len(recs))
"""


def test_distributed_phase_four_virtual_devices_zero_descents():
    r = subprocess.run([sys.executable, "-c", _DIST_CODE],
                       capture_output=True, text=True, cwd=_ROOT,
                       timeout=600, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert r.stdout.count("DIST_OK 4") == 2
    # the fused pagerank loop ran on device in shardmap mode
    assert "on-device lax.while_loop inside ONE fused shard_map round" \
        in r.stdout


def test_compile_cache_has_one_home(monkeypatch):
    import jax
    from repro import compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere/cache")
        assert compile_cache.enable() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv(compile_cache.ENV_VAR)
        path = compile_cache.enable()
        assert path == os.path.join(_ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_interpret_mode_is_refused_on_tpu(monkeypatch):
    import jax.numpy as jnp
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interp", lambda: False)
    with pytest.raises(ValueError, match="interpret mode"):
        ops.segment_reduce(jnp.zeros(8, jnp.int32), jnp.ones(8), 4,
                           interpret=True)


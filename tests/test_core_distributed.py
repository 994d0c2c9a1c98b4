"""Distributed (shard_map) execution of compiled loop programs
equals single-device execution — run in a subprocess with 8 forced host
devices (the main test process must keep 1 device)."""
import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import numpy as np
from repro.core import compile_program
from repro.core.distributed import compile_distributed
from repro.core.programs import ALL
from repro.launch.mesh import make_test_mesh

mesh = make_test_mesh((8,), ("data",))
rng = np.random.default_rng(7)
nv = 16
cases = {
  "word_count": dict(W=rng.integers(0, nv, 64).astype(np.float64), C=np.zeros(nv)),
  "group_by": dict(S=(rng.integers(0, nv, 64).astype(np.float64),
                      rng.standard_normal(64)), C=np.zeros(nv)),
  "histogram": dict(P=tuple(rng.integers(0, nv, 64).astype(np.float64)
                            for _ in range(3)),
                    R=np.zeros(nv), G=np.zeros(nv), B=np.zeros(nv)),
  "conditional_sum": dict(V=rng.standard_normal(64), s=0.0, limit=0.3),
  "pagerank": dict(E=(rng.integers(0, 12, 64).astype(np.float64),
                      rng.integers(0, 12, 64).astype(np.float64)),
                   P=np.full(12, 1/12), NP=np.zeros(12), C=np.zeros(12),
                   N=12, num_steps=2.0, steps=0.0, b=0.85),
  "matrix_multiplication": dict(M=rng.standard_normal((16, 8)),
                                N=rng.standard_normal((8, 12)),
                                R=np.zeros((16, 12)), n=16, m=12, l=8),
  # bag generator x dim-bounded range in one reduction: dims must reach
  # the shard_map body as static python ints, not traced operands
  "kmeans_step": dict(P=(rng.standard_normal(24) * 3,
                         rng.standard_normal(24) * 3),
                      CX=rng.standard_normal(4), CY=rng.standard_normal(4),
                      K=4, D=np.zeros((24, 4)), MinD=np.full(24, 1e30),
                      Cl=np.zeros(24), SX=np.zeros(4), SY=np.zeros(4),
                      CN=np.zeros(4), NX=np.zeros(4), NY=np.zeros(4)),
}
for name, ins in cases.items():
    fn = ALL[name]
    single = compile_program(fn).run(ins)
    dist = compile_distributed(fn, mesh, ("data",)).run(ins)
    for k in single:
        a = np.asarray(dist[k], np.float64)
        b = np.asarray(single[k], np.float64)
        err = np.max(np.abs(a - b) / (np.abs(b) + 1.0))
        assert err < 1e-4, (name, k, err)
print("DIST_OK")
"""


@pytest.mark.slow
def test_distributed_equals_single_device():
    r = subprocess.run([sys.executable, "-c", _CODE], capture_output=True,
                       text=True, cwd=_ROOT, timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "DIST_OK" in r.stdout


_ODD_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import numpy as np
from jax.sharding import PartitionSpec
from repro.core import compile_program
from repro.core.distributed import compile_distributed
from repro.core.programs import ALL
from repro.launch.mesh import make_test_mesh

mesh = make_test_mesh((8,), ("data",))
rng = np.random.default_rng(11)
nv = 16
n = 65                      # NOT divisible by 8: pads to 72, masks 7 rows
cases = {
  "word_count": dict(W=rng.integers(0, nv, n).astype(np.float64),
                     C=np.zeros(nv)),
  "group_by": dict(S=(rng.integers(0, nv, n).astype(np.float64),
                      rng.standard_normal(n)), C=np.zeros(nv)),
  "conditional_sum": dict(V=rng.standard_normal(n), s=0.0, limit=0.3),
}
for name, ins in cases.items():
    fn = ALL[name]
    single = compile_program(fn).run(ins)
    dp = compile_distributed(fn, mesh, ("data",))
    # odd-length bags must SHARD (padded), not silently replicate
    placed, limits, dense_limits = dp.place(ins)
    bag = next(k for k, t in fn.program.params.items()
               if t.kind == "bag")
    assert limits[bag] == n, (name, limits)
    col = placed[bag][0]
    assert col.shape[0] == 72
    assert col.sharding.spec == PartitionSpec(("data",)), \
        (name, col.sharding.spec)
    dist = dp.run(ins)
    for k in single:
        a = np.asarray(dist[k], np.float64)
        b = np.asarray(single[k], np.float64)
        err = np.max(np.abs(a - b) / (np.abs(b) + 1.0))
        assert err < 1e-4, (name, k, err)
print("ODD_OK")
"""


@pytest.mark.slow
def test_odd_length_bag_pads_and_shards():
    r = subprocess.run([sys.executable, "-c", _ODD_CODE],
                       capture_output=True, text=True, cwd=_ROOT,
                       timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "ODD_OK" in r.stdout


_EINSUM_BAG_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import numpy as np
from repro.core import compile_program, loop_program, bag, matrix, vector, dim
from repro.core.distributed import compile_distributed
from repro.core.plan import EinsumContract
from repro.launch.mesh import make_test_mesh


@loop_program
def col_sums(B: bag[1], M: matrix, R: vector, m: dim):
    # +-product of gathers contracting the BAG axis: plans as an
    # EinsumContract whose shardmap execution must fall back to the
    # masked AxisReduce inside each shard (traced bag offsets)
    for i, w in items(B):
        for j in range(0, m):
            R[j] += M[i, j]


cp = compile_program(col_sums)
assert any(isinstance(x, EinsumContract) for x in cp.plan), cp.explain()
rng = np.random.default_rng(13)
nb, m = 24, 5
ins = dict(B=rng.standard_normal(nb), M=rng.standard_normal((nb, m)),
           R=np.zeros(m), m=m)
single = cp.run(ins)
mesh = make_test_mesh((8,), ("data",))
dist = compile_distributed(col_sums, mesh, ("data",)).run(ins)
err = np.max(np.abs(np.asarray(dist["R"], np.float64)
                    - np.asarray(single["R"], np.float64)))
assert err < 1e-4, err
print("EINSUM_BAG_OK")
"""


@pytest.mark.slow
def test_bag_driven_einsum_distributes(tmp_path):
    script = tmp_path / "einsum_bag.py"          # @loop_program needs a file
    script.write_text(_EINSUM_BAG_CODE)
    r = subprocess.run([sys.executable, str(script)], capture_output=True,
                       text=True, cwd=_ROOT, timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "EINSUM_BAG_OK" in r.stdout


_DENSE_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys; sys.path.insert(0, "src")
import numpy as np
from jax.sharding import PartitionSpec
from repro.core import compile_program
from repro.core.distributed import compile_distributed
from repro.core.programs import ALL
from repro.launch.mesh import make_test_mesh

mesh = make_test_mesh((4,), ("data",))
rng = np.random.default_rng(17)


def check(name, ins):
    fn = ALL[name]
    single = compile_program(fn).run(ins)
    dist = compile_distributed(fn, mesh, ("data",)).run(ins)
    for k in single:
        a = np.asarray(dist[k], np.float64)
        b = np.asarray(single[k], np.float64)
        assert a.shape == b.shape, (name, k, a.shape, b.shape)
        err = np.max(np.abs(a - b) / (np.abs(b) + 1.0))
        assert err < 1e-4, (name, k, err)
    return single


# ---- PageRank: dense rank vectors must SHARD, with N=13 NOT divisible by
# 4 exercising the dense pad+mask path (pad to 16, mask 3 rows) ----
N = 13
pr_ins = dict(E=(rng.integers(0, N, 40).astype(np.float64),
                 rng.integers(0, N, 40).astype(np.float64)),
              P=np.full(N, 1 / N), NP=np.zeros(N), C=np.zeros(N),
              N=N, num_steps=3.0, steps=0.0, b=0.85)
text = compile_program(ALL["pagerank"]).explain()
assert "P=ONED_ROW(i)" in text, text        # ranks inferred sharded...
assert "P=REP" not in text, text            # ...not replicated
dp = compile_distributed(ALL["pagerank"], mesh, ("data",))
placed, bag_limits, array_limits = dp.place(pr_ins)
assert array_limits["P"] == N               # padded 13 -> 16
assert placed["P"].shape[0] == 16
assert placed["P"].sharding.spec == PartitionSpec(("data",)), \\
    placed["P"].sharding.spec                # row blocks, NOT replicated
single = check("pagerank", pr_ins)

# ---- REP-everything fallback (shard_dense=False): same results, dense
# arrays placed replicated ----
dp_rep = compile_distributed(ALL["pagerank"], mesh, ("data",),
                             shard_dense=False)
placed, _, alims = dp_rep.place(pr_ins)
assert alims == {} and placed["P"].shape[0] == N
assert placed["P"].sharding.spec == PartitionSpec(), \\
    placed["P"].sharding.spec
rep = dp_rep.run(pr_ins)
for k in single:
    err = np.max(np.abs(np.asarray(rep[k], np.float64)
                        - np.asarray(single[k], np.float64)))
    assert err < 1e-6, ("rep-fallback", k, err)

# ---- Matrix factorization: every factor matrix ONED_ROW, l=5 and n=10
# both non-divisible by 4 ----
n, m, l = 10, 6, 5
mf_ins = dict(R=rng.standard_normal((n, m)),
              P=rng.standard_normal((n, l)) * 0.1,
              Q=rng.standard_normal((l, m)) * 0.1,
              Pp=rng.standard_normal((n, l)) * 0.1,
              Qp=rng.standard_normal((l, m)) * 0.1,
              pq=np.zeros((n, m)), err=np.zeros((n, m)),
              n=n, m=m, l=l, a=0.01, lam=0.1)
from repro.core.dist_analysis import Dist
cp = compile_program(ALL["matrix_factorization_step"])
assert all(d == Dist.ONED_ROW for d in cp.dists.values()), cp.dists
check("matrix_factorization_step", mf_ins)
print("DENSE_OK")
"""


@pytest.mark.slow
def test_dense_arrays_shard_not_replicate():
    """Tentpole acceptance: PageRank ranks and MF factors shard on a
    4-device mesh (non-divisible rows → pad+mask), match single-device,
    and the REP-everything fallback still works."""
    r = subprocess.run([sys.executable, "-c", _DENSE_CODE],
                       capture_output=True, text=True, cwd=_ROOT,
                       timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "DENSE_OK" in r.stdout


def test_only_shardmap_mode_is_accepted():
    from repro.core import compile_program
    from repro.core.distributed import compile_distributed
    from repro.core.programs import ALL
    from repro.launch.mesh import make_test_mesh
    mesh = make_test_mesh((1,), ("data",))
    cp = compile_program(ALL["word_count"])
    assert compile_distributed(cp, mesh, ("data",),
                               mode="shardmap").mode == "shardmap"
    with pytest.raises(ValueError, match="only 'shardmap'"):
        compile_distributed(cp, mesh, ("data",), mode="gspmd")

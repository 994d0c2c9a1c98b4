"""Distributed data-analytics example: PageRank and KMeans written as
imperative loops, compiled by DIABLO-JAX, and executed over a mesh of
every device — the chips of a TPU host, or 8 forced host devices on the
CPU — with the paper's operator mapping — sharded bags AND, via the
distribution-analysis pass (DESIGN.md §6), sharded dense arrays: the rank
vectors are ONED_ROW row blocks, not replicas.

  PYTHONPATH=src python examples/analytics.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro import compile_cache
from repro.core import compile_program
from repro.core.dist_analysis import Dist
from repro.core.distributed import compile_distributed
from repro.core.programs import kmeans_step, pagerank
from repro.launch.mesh import make_test_mesh


def main():
    compile_cache.enable()
    mesh = make_test_mesh((len(jax.devices()),), ("data",))
    rng = np.random.default_rng(0)

    # ---- PageRank over a random graph ----
    nvert, nedge = 1000, 8000
    E = (rng.integers(0, nvert, nedge).astype(np.float64),
         rng.integers(0, nvert, nedge).astype(np.float64))
    ins = dict(E=E, P=np.full(nvert, 1 / nvert), NP=np.zeros(nvert),
               C=np.zeros(nvert), N=nvert, num_steps=5.0, steps=0.0, b=0.85)
    cp = compile_program(pagerank)
    # memory admission (DESIGN.md §12): the estimator prices the plan's
    # peak device bytes BEFORE anything touches the device — over a
    # budget, run() streams the edge bag in tiles instead of OOM-ing
    est = cp.estimate_memory(ins)
    print(est.summary(None))
    print(cp.explain())        # operator + inferred sharding per statement
    sharded = [a for a, d in cp.dists.items() if d >= Dist.ONED_ROW]
    print(f"\ndense arrays sharded (not replicated): {sorted(sharded)}\n")
    dp = compile_distributed(cp, mesh, ("data",), mode="shardmap")
    ranks = np.asarray(dp.run(ins)["P"])
    single = np.asarray(cp.run(ins)["P"])
    # the operator-selection subsystem (DESIGN.md §8) resolved each
    # group-by's backend at trace time; after a run, explain() carries a
    # `selected:` line per reduce node — surface just those decisions
    print("trace-time decisions per node (op_select backends for the "
          "group-bys,\nfast-path materializations for the stores):")
    for line in cp.explain().splitlines():
        if "selected:" in line:
            print("  " + line.strip())
    print()
    print(f"pagerank: top vertex {ranks.argmax()} rank={ranks.max():.5f} "
          f"(dist vs single max err {np.abs(ranks - single).max():.2e})")
    # REP-everything fallback: same result, replicated placement
    rep = np.asarray(compile_distributed(cp, mesh, ("data",),
                                         shard_dense=False).run(ins)["P"])
    print(f"          REP fallback max err {np.abs(rep - single).max():.2e}")

    # ---- one KMeans step on 2-D points ----
    npts, K = 4000, 8
    ins = dict(P=(rng.standard_normal(npts) * 3, rng.standard_normal(npts) * 3),
               CX=rng.standard_normal(K), CY=rng.standard_normal(K), K=K,
               D=np.zeros((npts, K)), MinD=np.full(npts, 1e30),
               Cl=np.zeros(npts), SX=np.zeros(K), SY=np.zeros(K),
               CN=np.zeros(K), NX=np.zeros(K), NY=np.zeros(K))
    ck = compile_distributed(kmeans_step, mesh, ("data",), mode="gspmd")
    print(ck.cp.estimate_memory(ins).summary(None))
    out = ck.run(ins)
    print("kmeans new centroids x:",
          np.round(np.asarray(out["NX"]), 3).tolist())


if __name__ == "__main__":
    main()

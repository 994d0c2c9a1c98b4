"""Serving compiled loop programs to concurrent clients (DESIGN.md §10).

One PlanServer hosts the mixed pagerank + group_by + kmeans workload; a
background pump thread batches whatever the (asyncio-simulated) clients
throw at it — ragged shapes bucket by compile-cache signature, pad, and
coalesce into vmapped whole-program calls.

  PYTHONPATH=src python examples/serve_plans.py
"""
import asyncio
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro import compile_cache
from repro.core import compile_program
from repro.core.programs import group_by, kmeans_step, pagerank
from repro.serve import PlanServer

rng = np.random.default_rng(0)


def request_for(i: int) -> tuple:
    """Client i's request: program and bag length vary per client, so the
    server sees genuinely ragged concurrent traffic."""
    kind = i % 3
    if kind == 0:
        N, ne = 64, 200 + 8 * (i % 5)
        return "pagerank", dict(
            E=(rng.integers(0, N, ne).astype(np.float64),
               rng.integers(0, N, ne).astype(np.float64)),
            P=np.full(N, 1.0 / N), NP=np.zeros(N), C=np.zeros(N),
            N=N, num_steps=3.0, steps=0.0, b=0.85)
    if kind == 1:
        m = 300 + 16 * (i % 5)
        return "group_by", dict(
            S=(rng.integers(0, 16, m).astype(np.float64),
               rng.standard_normal(m)), C=np.zeros(16))
    m, K = 100 + 8 * (i % 5), 4
    return "kmeans_step", dict(
        P=(rng.standard_normal(m) * 3, rng.standard_normal(m) * 3),
        CX=rng.standard_normal(K), CY=rng.standard_normal(K), K=K,
        D=np.zeros((m, K)), MinD=np.full(m, 1e30), Cl=np.zeros(m),
        SX=np.zeros(K), SY=np.zeros(K), CN=np.zeros(K),
        NX=np.zeros(K), NY=np.zeros(K))


async def client(server: PlanServer, i: int, n_requests: int):
    for _ in range(n_requests):
        name, inputs = request_for(i)
        out = await server.arun(name, inputs)
        assert all(np.all(np.isfinite(v)) for v in out.values())


def main():
    compile_cache.enable()
    print("compiling the workload programs...")
    server = PlanServer({
        "pagerank": compile_program(pagerank),
        "group_by": compile_program(group_by),
        "kmeans_step": compile_program(kmeans_step),
    }, max_batch=8, flush_ms=2.0)
    server.start()                      # pump thread: batches + dispatches

    async def drive():
        await asyncio.gather(*(client(server, i, 4) for i in range(24)))

    try:
        print("serving 24 concurrent clients x 4 requests each...")
        asyncio.run(asyncio.wait_for(drive(), timeout=120))
    finally:
        server.stop()
    print()
    print(server.explain_serving())


if __name__ == "__main__":
    main()

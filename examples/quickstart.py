"""Quickstart: write an imperative array loop, let DIABLO-JAX translate it
to a bulk data-parallel program.

  PYTHONPATH=src python examples/quickstart.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro import compile_cache
from repro.core import (RejectionError, compile_program, dim, loop_program,
                        map_, matrix, vector)


# --- the paper's running example: loop-based matrix multiplication -------
@loop_program
def matmul(M: matrix, N: matrix, R: matrix, n: dim, m: dim, l: dim):
    for i in range(0, n):
        for j in range(0, m):
            R[i, j] = 0.0
            for k in range(0, l):
                R[i, j] += M[i, k] * N[k, j]


# --- the paper's intro example: indirect group-by  C[K[i]] += V[i] -------
@loop_program
def grouped_sum(K: vector, V: vector, C: map_, n: dim):
    for i in range(0, n):
        C[int(K[i])] += V[i]


def main():
    compile_cache.enable()
    print("== source (parsed loop language) ==")
    print(matmul.program.pretty())
    cp = compile_program(matmul)
    print("\n== translated target (monoid comprehensions, paper Fig. 2) ==")
    print(cp.pretty_target())

    rng = np.random.default_rng(0)
    n = 64
    M, N = rng.standard_normal((n, n)), rng.standard_normal((n, n))
    out = cp.run(dict(M=M, N=N, R=np.zeros((n, n)), n=n, m=n, l=n))
    err = np.abs(np.asarray(out["R"]) - M @ N).max()
    print(f"\nmatmul vs numpy max err: {err:.2e} "
          f"(lowered to a single jnp.einsum — contraction recognition)")

    cp2 = compile_program(grouped_sum)
    print("\n== grouped sum target ==")
    print(cp2.pretty_target())
    k = rng.integers(0, 8, 100).astype(np.float64)
    v = rng.standard_normal(100)
    got = np.asarray(cp2.run(dict(K=k, V=v, C=np.zeros(8), n=100))["C"])
    want = np.zeros(8)
    np.add.at(want, k.astype(int), v)
    print("grouped sum max err:", np.abs(got - want).max())

    print("\n== rejection (paper §3.2 recurrence) ==")
    try:
        def smoothing(V: vector, n: dim):
            for i in range(1, n - 1):
                V[i] = (V[i - 1] + V[i + 1]) / 2.0
        from repro.core import parse_program
        compile_program(parse_program(smoothing))
    except RejectionError as e:
        print("rejected as expected:", e)


if __name__ == "__main__":
    main()

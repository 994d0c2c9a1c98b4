"""PageRank as LDBC Graphalytics specifies it (damping `damping`,
`num_steps` iterations from the uniform vector, the rank of dangling
vertices handed to every vertex), run by the loop program
`repro.core.programs.pagerank` on an edge bag (src, dst).  The program has
no dangling term; the Graphalytics graphs it runs on have no dangling
vertex, and the reference keeps the term, so a graph with one would show.

The plain reference is float64 numpy, independent of the program; the
control is the same reference computed in bfloat16 on the device.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

PROGRAM = "pagerank"
OUTPUTS = ("P", "C")


def inputs(cols: dict, domains: dict, traffic: dict) -> dict:
    n = domains[traffic["vertices"]]
    return dict(E=(cols[traffic["src"]], cols[traffic["dst"]]),
                P=jnp.full(n, 1.0 / n, jnp.float32),
                NP=jnp.zeros(n, jnp.float32), C=jnp.zeros(n, jnp.float32),
                N=n, num_steps=float(traffic["num_steps"]), steps=0.0,
                b=float(traffic["damping"]))


def warm_inputs(ins: dict, traffic: dict) -> dict:
    """Same shapes and signature (num_steps is a traced scalar), fewer
    iterations."""
    return dict(ins, num_steps=float(traffic["warmup_num_steps"]))


def host_data(ins: dict) -> dict:
    src, dst = (np.asarray(c).astype(np.int64) for c in ins["E"])
    return dict(src=src, dst=dst, n=ins["N"], steps=int(ins["num_steps"]),
                b=ins["b"])


def reference(h: dict) -> dict:
    n, b = h["n"], h["b"]
    src, dst = h["src"], h["dst"]
    C = np.bincount(src, minlength=n).astype(np.float64)
    P = np.full(n, 1.0 / n)
    dangling = C == 0
    with np.errstate(divide="ignore", invalid="ignore"):
        for _ in range(h["steps"]):    # P[s]/C[s] summed into NP[d]
            NP = np.bincount(dst, weights=(P / C)[src], minlength=n)
            P = (1.0 - b) / n + b * (NP + P[dangling].sum() / n)
    return dict(P=P, C=C)


def control(ins: dict) -> dict:
    """The reference in bfloat16, on the device."""
    return _control(ins["E"][0], ins["E"][1], ins["N"], int(ins["num_steps"]),
                    ins["b"])


@jax.jit
def _step(P, C, src, dst, b):
    bf = jnp.bfloat16
    n = P.shape[0]
    NP = jax.ops.segment_sum((P / C)[src], dst, n)
    lost = jnp.sum(jnp.where(C == 0, P, bf(0))) / bf(n)
    return (bf((1.0 - b) / n) + bf(b) * (NP + lost)).astype(bf)


def _control(src_f, dst_f, n, steps, b):
    bf = jnp.bfloat16
    src, dst = src_f.astype(jnp.int32), dst_f.astype(jnp.int32)
    C = jax.ops.segment_sum(jnp.ones(src.shape, bf), src, n)
    P = jnp.full(n, 1.0 / n, bf)
    for _ in range(steps):
        P = _step(P, C, src, dst, b)
    return dict(P=P.astype(jnp.float32), C=C.astype(jnp.float32))


def compare(out: dict, ref: dict) -> dict:
    """P: worst elementwise relative error (every rank is at least
    (1-b)/N > 0) and the normwise max|a-b|/max|b|; C: out-degrees, exact."""
    P = np.asarray(out["P"], np.float64)
    C = np.asarray(out["C"], np.float64)
    if P.shape != ref["P"].shape or C.shape != ref["C"].shape:
        return dict(P_rel_err=np.inf, P_norm_err=np.inf, C_abs_err=np.inf)
    dp = np.abs(P - ref["P"])
    res = dict(P_rel_err=float(np.max(dp / ref["P"])),
               P_norm_err=float(np.max(dp) / np.max(ref["P"])),
               C_abs_err=float(np.max(np.abs(C - ref["C"]))))
    return {k: (v if np.isfinite(v) else np.inf) for k, v in res.items()}


def work(ins: dict) -> dict:
    return {}

"""PageRank as `jobs/pagerank.py` defines it, for edge bags of a billion
rows: the program, its inputs, the bfloat16 control and the comparison are
that module's.  The float64 reference is the same computation, each sum
over the edges split into row chunks summed in threads (numpy's gathers
and bincounts release the interpreter lock), since one thread takes
minutes per iteration at that size.  The host copy keeps the int32 ids.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from jobs.pagerank import (OUTPUTS, PROGRAM, compare, control,  # noqa: F401
                           inputs, warm_inputs, work)

THREADS = min(32, os.cpu_count() or 1)


def host_data(ins: dict) -> dict:
    src, dst = (np.asarray(c) for c in ins["E"])
    return dict(src=src, dst=dst, n=ins["N"], steps=int(ins["num_steps"]),
                b=ins["b"])


def reference(h: dict) -> dict:
    n, b = h["n"], h["b"]
    src, dst = h["src"], h["dst"]
    cuts = np.linspace(0, len(src), THREADS + 1).astype(np.int64)
    rows = list(zip(cuts[:-1], cuts[1:]))

    with ThreadPoolExecutor(THREADS) as pool:
        def total(part):
            """sum over the row chunks of part(lo, hi), a [n] float64"""
            out = np.zeros(n)
            for p in pool.map(lambda r: part(*r), rows):
                out += p
            return out

        C = total(lambda lo, hi: np.bincount(src[lo:hi], minlength=n))
        P = np.full(n, 1.0 / n)
        dangling = C == 0
        with np.errstate(divide="ignore", invalid="ignore"):
            for _ in range(h["steps"]):    # P[s]/C[s] summed into NP[d]
                w = P / C
                NP = total(lambda lo, hi: np.bincount(
                    dst[lo:hi], weights=w[src[lo:hi]], minlength=n))
                P = (1.0 - b) / n + b * (NP + P[dangling].sum() / n)
    return dict(P=P, C=C)

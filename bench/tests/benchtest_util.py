"""Shared set-up of the benchmark's CPU tests: the harness's modules on the
path, and cells cut to a size a test run holds."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import run  # noqa: E402

CELLS = ("graph500-s22.pagerank10", "tpch-sf10.q18-groupby",
         "tpch-sf10.q1-groupby")
TINY = {"graph500": dict(scale=9, vertices=461, edges=9628),
        "tpch_lineitem": dict(orders=3000, rows=11900)}


def tiny_cell(name: str):
    """The cell as BENCHMARK.json defines it, its configuration cut to
    test size (traffic and limits unchanged)."""
    cell = run.load_cell(name)
    cell.config.update(TINY[cell.config["dataset"]])
    return cell

"""The four-chip cell, `graph500-s25.pagerank10-mesh4`, at test size: the
harness drives it on 4 forced host devices (in a subprocess: this process
keeps 1 device) with its configuration cut to scale 9 (scale 11 for a
generator whose edge count does not divide by 4).  The sound run reads
`correct` true; the control, and the program with its exchange left out,
read false."""
import json
import os
import subprocess
import sys

import pytest

import benchtest_util as bt
import devtrace

CELL = "graph500-s25.pagerank10-mesh4"
# graph_seed 0 at these scales: the counts the generator gives
TINY = {9: dict(scale=9, vertices=451, edges=9648),
        11: dict(scale=11, vertices=1741, edges=45554)}

_CODE = """
import os, json, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, os.path.join("bench", "tests"))
import numpy as np
import jax
import benchtest_util as bt
from repro.core.distributed import DistributedProgram
CELL, TINY = %r, %r
cell = bt.run.load_cell(CELL)
cfg = dict(cell.config)
out = {}

def tiny(scale):
    cell.config = dict(cfg, **TINY[scale])
    return cell

def summary(res):
    return dict(correct=res["correct"], attempted=res["attempted"],
                failed=res["failed"], checks=res["checks"])

out["sound"] = summary(bt.run.measure(tiny(9), 2**33 + 17, 0.3, False,
                                      require_device=False))
out["control"] = summary(bt.run.measure(tiny(9), 2**33 + 18, 0.3, False,
                                        require_device=False,
                                        entry="control"))
# the exchange left out: each chip's partial goes on unsummed (a
# replicated destination gets the first chip's)
real = DistributedProgram._combine_shard
def unsummed(self, part, op, shard, dest_oned, exchange="psum_scatter",
             dest=""):
    if not dest_oned:
        return jax.lax.psum(jax.numpy.where(shard == 0, part, 0), self.dp)
    blk = part.shape[0] // self.dp_n
    return jax.lax.dynamic_slice_in_dim(part, shard * blk, blk, axis=0)
DistributedProgram._combine_shard = unsummed
out["no_exchange"] = summary(bt.run.measure(tiny(9), 2**33 + 19, 0.3, False,
                                            require_device=False))
DistributedProgram._combine_shard = real

dataset = bt.run.load_module("datasets", cfg["dataset"])
gen = {}
for scale in TINY:
    c = dict(cfg, **TINY[scale])
    cols, dom = dataset.generate(c, bt.run.seed_key(2**33 + scale))
    s, d = (np.asarray(cols[k]) for k in ("src", "dst"))
    n = dom["vertex"]
    pairs = set(zip(s.tolist(), d.tolist()))
    blocks = [sh.data.shape[0] for sh in cols["src"].data.addressable_shards]
    pad = np.asarray(cols["src"].data)[cols["src"].rows:]
    other, _ = dataset.generate(c, bt.run.seed_key(2**33 + scale + 1))
    o = np.asarray(other["src"])
    gen[scale] = dict(
        dtype=str(s.dtype), rows=len(s), vertices=n,
        ids_in_range=bool(s.min() >= 0 and max(s.max(), d.max()) < n),
        self_loops=int(np.sum(s == d)), repeats=len(s) - len(pairs),
        symmetric=all((b, a) in pairs for a, b in pairs),
        dense=len(np.unique(np.concatenate([s, d]))) == n,
        blocks=blocks, devices=len({sh.device for sh in
                                    cols["src"].data.addressable_shards}),
        pad=pad.tolist(),
        relabelled=not np.array_equal(o, s),
        same_degrees=bool(np.array_equal(
            np.sort(np.bincount(s, minlength=n)),
            np.sort(np.bincount(o, minlength=n)))))
out["generator"] = gen
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def results():
    r = subprocess.run([sys.executable, "-c", _CODE % (CELL, TINY)],
                       capture_output=True, text=True, cwd=bt.ROOT,
                       timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    line = next(x for x in r.stdout.splitlines() if x.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


def test_sound_run_is_correct(results):
    res = results["sound"]
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0


def test_control_fails_the_limits(results):
    res = results["control"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["correct"] is False, res["checks"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_exchange_left_out_is_not_correct(results):
    """The fault a one-chip cell cannot have: each chip's partial of the
    out-degrees and of NP goes on without the other chips'."""
    res = results["no_exchange"]
    assert res["attempted"] >= 1 and res["correct"] is False, res["checks"]


@pytest.mark.parametrize("scale", sorted(TINY))
def test_generator_keeps_graphalytics_form_in_row_blocks(results, scale):
    g = results["generator"][str(scale)]
    assert g["dtype"] == "int32"
    assert (g["rows"], g["vertices"]) == (TINY[scale]["edges"],
                                          TINY[scale]["vertices"])
    assert g["ids_in_range"] and g["dense"] and g["symmetric"]
    assert g["self_loops"] == 0 and g["repeats"] == 0
    blk = -(-g["rows"] // 4)
    assert g["blocks"] == [blk] * 4 and g["devices"] == 4
    assert g["pad"] == [0] * (4 * blk - g["rows"])
    assert g["relabelled"] and g["same_degrees"]


def test_count_check_refuses_another_graph(results):
    """The configuration's counts are checked on every run."""
    cell = bt.run.load_cell(CELL)
    dataset = bt.run.load_module("datasets", cell.config["dataset"])
    cfg = dict(cell.config, **TINY[9], chips=1)
    cfg["edges"] += 2
    with pytest.raises(RuntimeError, match="graph_seed 0 gives"):
        dataset.generate(cfg, bt.run.seed_key(5))


def test_new_metrics_read_nothing_without_the_spans(monkeypatch):
    """On a trace with no `repro.place` span and no collective (a one-chip
    q1-groupby job recorded on a TPU v5 lite) neither metric reads; on
    collective ops named as XLA names them, collective_ms reads their
    time per job."""
    q1 = os.path.join(bt.BENCH, "tests", "data", "q1-groupby.xplane.pb")
    monkeypatch.setattr(devtrace, "latest_xplane", lambda d: q1)
    tr = devtrace.load(q1)
    r = bt.run.Reading(jobs=1, dispatch_s=[0.002], compiles=0, work={},
                       peaks={}, trace=devtrace.summarize(tr))
    for name in ("place_ms", "collective_ms"):
        assert bt.run.load_module("metrics", name).read(r) is None, name
    summary = devtrace.Summary(
        window_s=1.0, busy_s=0.5, gaps=[],
        ops_s={"all-gather-start.1": 0.002, "all-gather-done.1": 0.001,
               "reduce-scatter.4": 0.004, "fusion.2": 0.3})
    r2 = bt.run.Reading(jobs=2, dispatch_s=[], compiles=0, work={},
                        peaks={}, trace=summary)
    assert bt.run.load_module("metrics", "collective_ms").read(r2) \
        == pytest.approx(3.5)

"""The on-device generators follow their laws (tiny scale, CPU)."""
import numpy as np
import pytest

import benchtest_util as bt

run = bt.run


def test_rmat_quadrant_shares():
    import jax
    g = run.load_module("datasets", "graph500")
    scale, ef, a, b, c = 12, 16, 0.57, 0.19, 0.19
    s, d = (np.asarray(x) for x in g.rmat(jax.random.key(7), scale, ef,
                                          a, b, c))
    assert s.shape == d.shape == (ef << scale,)
    assert s.min() >= 0 and max(s.max(), d.max()) < 1 << scale
    bits = np.arange(scale)
    sb = (s[:, None] >> bits) & 1
    db = (d[:, None] >> bits) & 1
    tol = 0.005                    # > 5 sigma at 786k draws
    assert abs(sb.mean() - (1 - a - b)) < tol          # rows 1: c + d
    assert abs(db.mean() - (1 - a - c)) < tol          # cols 1: b + d
    assert abs((sb & db).mean() - (1 - a - b - c)) < tol
    assert abs(((1 - sb) & (1 - db)).mean() - a) < tol


def test_graph500_labels_permuted_and_seeded():
    """Graphalytics' form: undirected (each edge both ways), no self-loop,
    no repeated edge, every vertex has an edge, ids dense; the key permutes
    labels and order of one graph, which the configuration fixes."""
    import jax
    g = run.load_module("datasets", "graph500")
    cfg = dict(scale=10, edge_factor=16, a=0.57, b=0.19, c=0.19,
               graph_seed=0, vertices=886, edges=20946)
    cols, dom = g.generate(cfg, jax.random.key(3))
    again, _ = g.generate(cfg, jax.random.key(3))
    other, _ = g.generate(cfg, jax.random.key(4))
    assert dom == {"vertex": 886}
    src, dst = (np.asarray(cols[k]) for k in ("src", "dst"))
    assert src.dtype == np.float32 and src.shape == (20946,)
    assert np.all(src == np.round(src))
    s, d = src.astype(np.int64), dst.astype(np.int64)
    assert not np.any(s == d)
    fwd = s * 886 + d
    assert len(np.unique(fwd)) == len(fwd)                 # no repeats
    assert np.array_equal(np.sort(fwd), np.sort(d * 886 + s))  # both ways
    deg = np.bincount(s, minlength=886)
    assert deg.shape == (886,) and deg.min() >= 1          # none dangling
    assert np.array_equal(src, np.asarray(again["src"]))
    o = np.asarray(other["src"]).astype(np.int64)
    assert not np.array_equal(s, o)
    assert np.array_equal(np.sort(deg), np.sort(np.bincount(o)))
    # R-MAT puts the most edges on the lowest ids; the labels move the hub
    assert deg.argmax() != 0
    with pytest.raises(RuntimeError, match="configuration states"):
        g.generate(dict(cfg, vertices=887), jax.random.key(3))


def _flag_status_law(tp):
    """Exact shares of (returnflag, linestatus) under dbgen's date laws."""
    od = np.arange(tp.LAST_ORDER_DAY + 1)
    ship = (od[:, None] + np.arange(1, 122)[None, :]).ravel()
    rec = (ship[:, None] + np.arange(1, 31)[None, :]).ravel()
    ship_r = np.repeat(ship, 30)
    cur = tp.CURRENT_DAY
    returned = rec <= cur
    late = ship_r > cur
    return {("R", "F"): (returned & ~late).mean() / 2,
            ("A", "F"): (returned & ~late).mean() / 2,
            ("N", "F"): (~returned & ~late).mean(),
            ("N", "O"): (~returned & late).mean(),
            ("R", "O"): (returned & late).mean()}


@pytest.mark.parametrize("seed", [0, 1])
def test_lineitem_laws(seed):
    import jax
    tp = run.load_module("datasets", "tpch_lineitem")
    orders, rows = 20000, 79900
    cols, dom = tp.generate(dict(orders=orders, rows=rows),
                            jax.random.key(seed))
    assert dom["l_orderkey"] == orders
    ok = np.asarray(cols["l_orderkey"]).astype(np.int64)
    assert ok.shape == (rows,)                          # pinned row count
    assert np.all(np.diff(ok) >= 0) and ok[0] == 0      # clustered, dense
    per = np.bincount(ok)
    full = per[:-1]                # the last order present may be cut
    assert full.min() >= 1 and full.max() <= 7
    assert abs(full.mean() - 4.0) < 0.05
    q = np.asarray(cols["l_quantity"])
    assert q.min() == 1 and q.max() == 50 and abs(q.mean() - 25.5) < 0.3
    rf = np.asarray(cols["l_returnflag"]).astype(int)
    ls = np.asarray(cols["l_linestatus"]).astype(int)
    law = _flag_status_law(tp)
    for (f, s), p in law.items():
        got = np.mean((rf == tp.FLAGS.index(f)) & (ls == tp.STATUSES.index(s)))
        assert abs(got - p) < 5 * np.sqrt(p * (1 - p) / rows) + 1e-9, (f, s)
    assert law[("R", "O")] == 0.0             # Q1 sees four groups of six


def test_lineitem_redraws_a_short_draw():
    import jax
    tp = run.load_module("datasets", "tpch_lineitem")
    key = jax.random.key(0)
    first = int(tp._lines_per_order(
        jax.random.fold_in(jax.random.split(key)[0], 0), 100)[1])
    # one row more than the first draw holds: a later draw has to serve
    cols, _ = tp.generate(dict(orders=100, rows=first + 1), key)
    assert cols["l_orderkey"].shape == (first + 1,)
    with pytest.raises(RuntimeError, match="fell short"):
        tp.generate(dict(orders=100, rows=701), key)

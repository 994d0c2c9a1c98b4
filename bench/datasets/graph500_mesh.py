"""Graph500 Kronecker graphs as LDBC Graphalytics' graph500-<scale> datasets
hold them, generated sharded over the cell's chips: no chip ever holds the
whole edge list, and the columns come out in equal row blocks over the
chips, as `compile_distributed` takes a bag.

The edge law and the Graphalytics form are those of `graph500.py`:
`edge_factor << scale` draws, each of `scale` levels one quadrant draw with
probabilities a, b, c, 1-a-b-c; self-loops and duplicate edges dropped,
every edge kept in both directions, only the vertices that have an edge
kept, numbered densely, so no vertex is dangling.  Here the work is split
over the chips:

1. each chip makes its share of the draws (threefry, keyed by the chip's
   index), and sends each canonical edge (min, max) to the chip a hash of
   the pair names, in one `all_to_all` whose capacity a first counting pass
   sets exactly;
2. each chip sorts what it received and drops repeats, and a `pmax` of the
   vertex-presence bitmap gives every chip the dense numbering;
3. each chip labels its edges (the run's key permutes the labels), keeps
   them in both directions in random order, and a second `all_to_all`
   moves the few rows at the block edges so that every chip holds exactly
   its row block.

The graph is drawn once from the configuration's `graph_seed`; the
configuration states the vertex and directed-edge counts, checked on every
run.  Columns: `src`, `dst` as int32 vertex ids in `RowBlocks` (the last
block zero-padded when the edge count does not divide by the chip count);
domain `vertex`, the number of vertices.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

AXIS = "data"


def _shard(chips: int):
    return jax.lax.axis_index(AXIS) if chips > 1 else 0


def _draws(key, shard, m: int, scale: int, a: float, b: float, c: float,
           chips: int):
    """This chip's `m` R-MAT draws as canonical pairs u <= v (int32), and
    the chip each pair goes to; self-loops go to `chips` (nowhere)."""
    key = jax.random.fold_in(key, shard)

    def level(i, sd):
        s, d = sd
        u = jax.random.uniform(jax.random.fold_in(key, i), (m,))
        ii = u >= a + b
        jj = ((u >= a) & ~ii) | (u >= a + b + c)
        bit = jnp.left_shift(jnp.int32(1), i)
        return s | jnp.where(ii, bit, 0), d | jnp.where(jj, bit, 0)

    z = jnp.zeros(m, jnp.int32)
    s, d = jax.lax.fori_loop(0, scale, level, (z, z))
    u, v = jnp.minimum(s, d), jnp.maximum(s, d)
    h = (u.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
         + v.astype(jnp.uint32)) * jnp.uint32(0x85EBCA6B)
    dest = ((h >> 16) % jnp.uint32(chips)).astype(jnp.int32)
    return u, v, jnp.where(s == d, chips, dest)


def _per_dest(dest, chips: int):
    return jnp.zeros(chips, jnp.int32).at[dest].add(1, mode="drop")


@partial(jax.jit, static_argnames=("mesh", "m", "scale", "a", "b", "c"))
def _route_counts(key, mesh, m, scale, a, b, c):
    """[chips * chips]: row k holds how many pairs chip k sends each chip."""
    chips = mesh.size

    def local(key):
        _, _, dest = _draws(key, _shard(chips), m, scale, a, b, c, chips)
        return _per_dest(dest, chips)
    return jax.shard_map(local, mesh=mesh, check_vma=False, in_specs=P(),
                         out_specs=P(AXIS))(key)


@partial(jax.jit, static_argnames=("mesh", "m", "scale", "a", "b", "c",
                                   "cap"))
def _simple(key, mesh, m, scale, a, b, c, cap):
    """Every chip's received pairs, sorted, with `keep` marking the first of
    each distinct edge; the global vertex-presence bitmap; and the kept
    edges per chip."""
    chips = mesh.size
    none = jnp.int32(1 << scale)
    nv = 1 << scale

    def local(key):
        u, v, dest = _draws(key, _shard(chips), m, scale, a, b, c, chips)
        # slot of each pair in its destination's bucket: how many pairs
        # before it go there too
        slot = jnp.zeros(m, jnp.int32)
        for t in range(chips):
            to_t = dest == t
            slot = jnp.where(to_t, jnp.cumsum(to_t, dtype=jnp.int32) - 1,
                             slot)
        at = jnp.where(dest < chips, dest * cap + slot, chips * cap)
        su = jnp.full(chips * cap, none).at[at].set(u, mode="drop")
        sv = jnp.full(chips * cap, none).at[at].set(v, mode="drop")
        su, sv = su.reshape(chips, cap), sv.reshape(chips, cap)
        if chips > 1:
            su = jax.lax.all_to_all(su, AXIS, 0, 0)
            sv = jax.lax.all_to_all(sv, AXIS, 0, 0)
        ru, rv = jax.lax.sort((su.reshape(-1), sv.reshape(-1)), num_keys=2)
        new = jnp.ones(ru.shape, bool).at[1:].set(
            (ru[1:] != ru[:-1]) | (rv[1:] != rv[:-1]))
        keep = new & (ru < none)
        present = (jnp.zeros(nv, jnp.int32)
                   .at[jnp.where(keep, ru, nv)].set(1, mode="drop")
                   .at[jnp.where(keep, rv, nv)].set(1, mode="drop"))
        if chips > 1:
            present = jax.lax.pmax(present, AXIS)
        return ru, rv, keep, present, keep.sum()[None]
    return jax.shard_map(local, mesh=mesh, check_vma=False, in_specs=P(),
                         out_specs=(P(AXIS),) * 3 + (P(), P(AXIS)))(key)


@partial(jax.jit, static_argnames=("mesh", "vertices", "rows"),
         donate_argnums=(1, 2, 3))
def _labelled(key, ru, rv, keep, present, mesh, vertices, rows):
    """Each chip's kept edges (`rows` slots, the first `kept` filled) with
    the vertices renumbered densely and their labels permuted."""
    k_perm = jax.random.split(key)[0]

    def local(ru, rv, keep, present):
        dense = jnp.cumsum(present) - 1
        # a permutation of the labels: one sort by random keys
        _, perm = jax.lax.sort_key_val(
            jax.random.bits(k_perm, (vertices,), jnp.uint32),
            jnp.arange(vertices, dtype=jnp.int32))
        label = perm[jnp.clip(dense, 0)]
        at = jnp.where(keep, jnp.cumsum(keep, dtype=jnp.int32) - 1, rows)
        lu = jnp.zeros(rows, jnp.int32).at[at].set(label[ru], mode="drop")
        lv = jnp.zeros(rows, jnp.int32).at[at].set(label[rv], mode="drop")
        return lu, lv
    return jax.shard_map(local, mesh=mesh, check_vma=False,
                         in_specs=(P(AXIS),) * 3 + (P(),),
                         out_specs=(P(AXIS), P(AXIS)))(ru, rv, keep, present)


@partial(jax.jit, static_argnames=("mesh", "blk", "cap"),
         donate_argnums=(1, 2))
def _blocks(key, a, b, kept, offs, mesh, blk, cap):
    """Both directions of each chip's edges in random order, moved into
    equal row blocks of `blk` rows.  Chip k holds 2 * kept[k] rows, whose
    global rows start at offs[k]; global row g lands on chip g // blk.
    Rows past the edge count are zero."""
    k_order = jax.random.split(key)[1]
    chips = mesh.size

    def local(a, b, kept, offs):
        i = _shard(chips)
        half = a.shape[0]
        n = 2 * kept[i]
        src = jnp.concatenate([a, b])
        dst = jnp.concatenate([b, a])
        live = (jnp.arange(2 * half) % half) < kept[i]
        bits = jax.random.bits(jax.random.fold_in(k_order, i), (2 * half,),
                               jnp.uint32) >> 1
        order = jnp.where(live, bits, jnp.uint32(1 << 31))   # dead rows last
        _, src, dst = jax.lax.sort((order, src, dst), num_keys=1)
        # the rows of this chip's block that it holds itself
        j = jnp.arange(blk) + i * blk - offs[i]
        mine = (j >= 0) & (j < n)
        out_s = jnp.where(mine, jnp.take(src, j, mode="clip"), 0)
        out_d = jnp.where(mine, jnp.take(dst, j, mode="clip"), 0)
        if chips == 1:
            return out_s, out_d
        # the rows at the block edges: sent to chip t, received from chip k
        t = jnp.arange(chips)[:, None]
        sj = jnp.clip(t * blk - offs[i], 0, n) + jnp.arange(cap)[None, :]
        go = (sj < jnp.clip((t + 1) * blk - offs[i], 0, n)) & (t != i)
        recv_s = jax.lax.all_to_all(
            jnp.where(go, jnp.take(src, sj, mode="clip"), 0), AXIS, 0, 0)
        recv_d = jax.lax.all_to_all(
            jnp.where(go, jnp.take(dst, sj, mode="clip"), 0), AXIS, 0, 0)
        k = t
        nk = 2 * kept[k]
        kj = jnp.clip(i * blk - offs[k], 0, nk) + jnp.arange(cap)[None, :]
        come = (kj < jnp.clip((i + 1) * blk - offs[k], 0, nk)) & (k != i)
        pos = jnp.where(come, offs[k] + kj - i * blk, blk).reshape(-1)
        return (out_s.at[pos].set(recv_s.reshape(-1), mode="drop"),
                out_d.at[pos].set(recv_d.reshape(-1), mode="drop"))
    return jax.shard_map(local, mesh=mesh, check_vma=False,
                         in_specs=(P(AXIS), P(AXIS), P(), P()),
                         out_specs=(P(AXIS), P(AXIS)))(a, b, kept, offs)


def generate(cfg: dict, key) -> tuple[dict, dict]:
    """(columns, domains) of one input set, in row blocks over the first
    `cfg["chips"]` devices."""
    from repro.core.distributed import RowBlocks
    from repro.launch.mesh import make_test_mesh
    chips = int(cfg["chips"])
    mesh = make_test_mesh((chips,), (AXIS,))
    scale = int(cfg["scale"])
    draws = int(cfg["edge_factor"]) << scale
    if draws % chips:
        raise ValueError(f"{draws} draws do not split over {chips} chips")
    law = dict(m=draws // chips, scale=scale, a=cfg["a"], b=cfg["b"],
               c=cfg["c"])
    gkey = jax.random.key(cfg["graph_seed"])
    routed = np.asarray(_route_counts(gkey, mesh, **law))
    ru, rv, keep, present, kept = _simple(gkey, mesh, cap=int(routed.max()),
                                          **law)
    kept = np.asarray(kept, np.int64)
    got = dict(vertices=int(jnp.sum(present)), edges=2 * int(kept.sum()))
    want = {k: cfg[k] for k in got}
    if got != want:
        raise RuntimeError(f"graph_seed {cfg['graph_seed']} gives {got}, "
                           f"the configuration states {want}")
    a, b = _labelled(key, ru, rv, keep, present, mesh=mesh,
                     vertices=got["vertices"], rows=int(kept.max()))
    del ru, rv, keep, present
    blk = -(-got["edges"] // chips)
    offs = np.concatenate([[0], np.cumsum(2 * kept)[:-1]])
    # rows chip k sends chip t: its global rows inside t's block
    lo, hi = offs[:, None], offs[:, None] + 2 * kept[:, None]
    t = np.arange(chips)[None, :]
    moved = np.clip(np.minimum(hi, (t + 1) * blk)
                    - np.maximum(lo, t * blk), 0, None)
    np.fill_diagonal(moved, 0)
    rep = NamedSharding(mesh, P())
    src, dst = _blocks(key, a, b, jax.device_put(kept.astype(np.int32), rep),
                       jax.device_put(offs.astype(np.int32), rep),
                       mesh=mesh, blk=blk, cap=max(1, int(moved.max())))
    return ({"src": RowBlocks(src, got["edges"]),
             "dst": RowBlocks(dst, got["edges"])},
            {"vertex": got["vertices"]})

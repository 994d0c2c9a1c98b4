"""Graph500 Kronecker graphs as LDBC Graphalytics' graph500-<scale>
datasets hold them, generated on the device.

The edge law is the Graph500 reference generator's: `edge_factor << scale`
draws, each of `scale` levels one quadrant draw with probabilities a, b, c,
1-a-b-c (the joint law of the reference code's two draws per level).
Graphalytics then makes the graph simple and undirected: self-loops and
duplicate edges are dropped, every edge is kept in both directions, and
only the vertices that have an edge are kept, numbered densely.  So no
vertex is dangling.

The graph is drawn once from the configuration's `graph_seed`, as a
Graphalytics dataset is one fixed graph: every run has the same sizes and
the same work, and the configuration states the vertex and edge counts,
checked here.  The run's key permutes the vertex labels and the order of
the edges.

Columns: `src`, `dst` as float32 vertex ids (exact below 2**24), the form
the loop programs take a bag in; each undirected edge appears once in each
direction.  Domain: `vertex`, the number of vertices.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


@partial(jax.jit, static_argnames=("scale", "edge_factor", "a", "b", "c"))
def rmat(key, scale: int, edge_factor: int, a: float, b: float, c: float):
    """Unpermuted R-MAT (src, dst) int32 ids, `edge_factor << scale` edges."""
    m = edge_factor << scale

    def level(i, sd):
        s, d = sd
        u = jax.random.uniform(jax.random.fold_in(key, i), (m,))
        ii = u >= a + b
        jj = ((u >= a) & ~ii) | (u >= a + b + c)
        bit = jnp.left_shift(jnp.int32(1), i)
        return s | jnp.where(ii, bit, 0), d | jnp.where(jj, bit, 0)

    z = jnp.zeros(m, jnp.int32)
    return jax.lax.fori_loop(0, scale, level, (z, z))


@partial(jax.jit, static_argnames=("scale", "edge_factor", "a", "b", "c"))
def simple_graph(key, scale, edge_factor, a, b, c):
    """The R-MAT draws made simple and undirected: each edge as (u, v) with
    u < v, sorted; `keep` marks the first of each distinct edge (self-loops
    and repeats are not kept); `present` marks the vertices with an edge.
    Also the counts of kept edges and of present vertices."""
    s, d = rmat(key, scale, edge_factor, a, b, c)
    none = jnp.int32(1 << scale)          # self-loops sort last, unkept
    loop = s == d
    u = jnp.where(loop, none, jnp.minimum(s, d))
    v = jnp.where(loop, none, jnp.maximum(s, d))
    u, v = jax.lax.sort((u, v), num_keys=2)
    new = jnp.ones(u.shape, bool).at[1:].set((u[1:] != u[:-1])
                                             | (v[1:] != v[:-1]))
    keep = new & (u < none)
    nv = 1 << scale
    present = (jnp.zeros(nv, bool)
               .at[jnp.where(keep, u, nv)].set(True, mode="drop")
               .at[jnp.where(keep, v, nv)].set(True, mode="drop"))
    return u, v, keep, present, keep.sum(), present.sum()


@partial(jax.jit, static_argnames=("vertices", "edges"))
def _labelled(key, u, v, keep, present, vertices, edges):
    """The kept edges, both directions, vertices renumbered densely and
    their labels permuted, the edges in random order."""
    k_perm, k_order = jax.random.split(key)
    (idx,) = jnp.nonzero(keep, size=edges // 2)
    dense = jnp.cumsum(present.astype(jnp.int32)) - 1
    label = jax.random.permutation(k_perm, vertices).astype(jnp.int32)[dense]
    a, b = label[u[idx]], label[v[idx]]
    order = jax.random.bits(k_order, (edges,), jnp.uint32)
    _, src, dst = jax.lax.sort((order, jnp.concatenate([a, b]),
                                jnp.concatenate([b, a])),
                               num_keys=1, is_stable=True)
    return src.astype(jnp.float32), dst.astype(jnp.float32)


def generate(cfg: dict, key) -> tuple[dict, dict]:
    """(columns, domains) of one input set, on the default device."""
    u, v, keep, present, m, n = simple_graph(
        jax.random.key(cfg["graph_seed"]), cfg["scale"], cfg["edge_factor"],
        cfg["a"], cfg["b"], cfg["c"])
    got = dict(vertices=int(n), edges=2 * int(m))
    want = {k: cfg[k] for k in got}
    if got != want:
        raise RuntimeError(f"graph_seed {cfg['graph_seed']} gives {got}, "
                           f"the configuration states {want}")
    src, dst = _labelled(key, u, v, keep, present, got["vertices"],
                         got["edges"])
    return {"src": src, "dst": dst}, {"vertex": got["vertices"]}

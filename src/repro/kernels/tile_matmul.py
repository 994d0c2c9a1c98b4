"""Pallas TPU kernel: tiled matmul (paper §5 packed arrays).

C[M,N] = A[M,K] @ B[K,N] with MXU-aligned [bm, bk] x [bk, bn] tiles and
fp32 accumulation (full f32 precision for f32 operands) in the revisited
output tile (grid (m, n, k), k innermost).  `tile_mask` supports
block-sparse tiled matrices: the [M/bm, K/bk] mask is scalar-prefetched
into SMEM and a grid step whose A tile is absent skips its MXU work.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..precision import F32_DOT


def _accumulate(a_ref, b_ref, out_ref):
    out_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        precision=F32_DOT,
        preferred_element_type=jnp.float32)


def _kernel(a_ref, b_ref, out_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    _accumulate(a_ref, b_ref, out_ref)


def _masked_kernel(mask_ref, a_ref, b_ref, out_ref):
    i, kk = pl.program_id(0), pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(mask_ref[i * pl.num_programs(2) + kk] != 0)
    def _present():
        _accumulate(a_ref, b_ref, out_ref)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def tile_matmul(a: jax.Array, b: jax.Array, *, bm: int = 128, bn: int = 128,
                bk: int = 128, tile_mask: jax.Array | None = None,
                interpret: bool = True) -> jax.Array:
    """a: [M,K]; b: [K,N] -> [M,N] fp32.  tile_mask: [M/bm, K/bk] optional."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    mp, np_, kp = (-(-m // bm) * bm, -(-n // bn) * bn, -(-k // bk) * bk)
    ap = jnp.zeros((mp, kp), a.dtype).at[:m, :k].set(a)
    bp = jnp.zeros((kp, np_), b.dtype).at[:k, :n].set(b)
    grid = (mp // bm, np_ // bn, kp // bk)
    # inside shard_map the product varies over the mesh axes its operands
    # vary over: the annotation shard_map's replication check reads
    out_shape = jax.ShapeDtypeStruct(
        (mp, np_), jnp.float32,
        vma=jax.typeof(ap).vma | jax.typeof(bp).vma)

    if tile_mask is None:
        out = pl.pallas_call(
            _kernel,
            grid=grid,
            in_specs=[pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                      pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
            out_shape=out_shape,
            interpret=interpret,
        )(ap, bp)
    else:
        # the flat int32 mask rides in SMEM (scalar prefetch), so a grid
        # step reads its tile's presence bit without a VMEM block
        mask = (tile_mask != 0).astype(jnp.int32).reshape(-1)
        out = pl.pallas_call(
            _masked_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=grid,
                in_specs=[
                    pl.BlockSpec((bm, bk), lambda i, j, kk, m: (i, kk)),
                    pl.BlockSpec((bk, bn), lambda i, j, kk, m: (kk, j))],
                out_specs=pl.BlockSpec((bm, bn),
                                       lambda i, j, kk, m: (i, j))),
            out_shape=out_shape,
            interpret=interpret,
        )(mask, ap, bp)
    return out[:m, :n]

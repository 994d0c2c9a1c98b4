"""Pallas TPU kernel: segment-⊕ via one-hot MXU matmul / one-hot select.

This is the TPU-native form of the paper's group-by-⊕: instead of a shuffle
(Spark) or a scatter (GPU), each block of bn segment ids becomes a
[bk, bn] one-hot matrix (segments on sublanes, rows on lanes).  For ⊕ = +
the one-hot contracts with the [bd, bn] value block on the MXU — group-by
as matrix multiplication; for ⊕ = min/max the one-hot SELECTS the value
row into an identity-filled [bk, bn] block that reduces over lanes on the
VPU.  Out-of-range ids ([num_segments, ∞) and negatives) contribute
nothing (drop semantics, matching the ◁ merge): padded rows get id = Kp
which no k-block matches, and ids in [num_segments, Kp) land in output
rows that are sliced off.

Layout: rows run along lanes everywhere — ids as [1, N], values
transposed to [D, N], the output as [D, K] — so every block is lane-dense
and the 1-D id column needs no sublane relayout inside the kernel.

Values may be [N] (returns [num_segments]) or [N, D] (returns
[num_segments, D]).  Floating values accumulate in float32 at full f32
precision.  Integer values accumulate EXACTLY in int32 (an int32 one-hot
dot): v5e's MXU has no int32 dot, so Mosaic refuses integer `+` and
op_select never picks this backend for it on a TPU; integer min/max only
select and compile.  The returned dtype is the accumulator's.

Grid: (K/bk, D/bd, N/bn), N innermost so each output tile accumulates
across value blocks in VMEM.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..precision import F32_DOT

_IDENTITY = {"+": 0.0, "min": np.inf, "max": -np.inf}
_LANES = 128


def _int_identity(op: str) -> int:
    if op == "+":
        return 0
    big = jnp.iinfo(jnp.int32).max
    return big if op == "min" else -big


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _kernel(ids_ref, val_ref, out_ref, *, bk: int, bd: int, op: str, acc):
    k = pl.program_id(0)
    n = pl.program_id(2)
    ident = jnp.asarray(_int_identity(op) if acc == jnp.int32
                        else _IDENTITY[op], acc)

    @pl.when(n == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, ident)

    ids = ids_ref[...]                                    # [1, bn]
    vals = val_ref[...]                                   # [bd, bn]
    bn = ids.shape[1]
    hit = (k * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, bn), 0)) == ids
    if op == "+":
        # [bd, bn] · [bk, bn]ᵀ → [bd, bk], contracting the lane (row) axis
        out_ref[...] += jax.lax.dot_general(
            vals, hit.astype(acc), (((1,), (1,)), ((), ())),
            precision=F32_DOT if acc == jnp.float32 else None,
            preferred_element_type=acc)
    else:
        # one-hot select: rows not in a segment carry the ⊕ identity,
        # reduce over lanes to a [bk, 1] column, then move the column onto
        # lanes with a diagonal select-and-reduce (exact: values are only
        # selected, never combined arithmetically)
        red = jnp.min if op == "min" else jnp.max
        comb = jnp.minimum if op == "min" else jnp.maximum
        eye = (jax.lax.broadcasted_iota(jnp.int32, (bk, bk), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (bk, bk), 1))
        for d in range(bd):
            col = red(jnp.where(hit, vals[d:d + 1, :], ident), axis=1,
                      keepdims=True)                      # [bk, 1]
            row = red(jnp.where(eye, col, ident), axis=0,
                      keepdims=True)                      # [1, bk]
            out_ref[d:d + 1, :] = comb(out_ref[d:d + 1, :], row)


@functools.partial(jax.jit, static_argnames=("num_segments", "op", "bn",
                                             "bk", "bd", "interpret"))
def segment_reduce(ids: jax.Array, values: jax.Array, num_segments: int,
                   *, op: str = "+", bn: int = 1024, bk: int = 256,
                   bd: int = 8, interpret: bool = True) -> jax.Array:
    """ids: [N] int; values: [N] or [N, D] -> [num_segments(, D)].
    op ∈ {"+", "min", "max"}.  Integer values take the exact-int path
    (int32 accumulation); floats accumulate in float32.  The defaults are
    TPU-aligned blocks (bn, bk multiples of 128; bd ≤ 8 or D itself)."""
    if op not in ("+", "min", "max"):
        raise ValueError(f"segment_reduce: unsupported op {op!r}")
    squeeze = values.ndim == 1
    if squeeze:
        values = values[:, None]
    n, d = values.shape
    acc = jnp.int32 if jnp.issubdtype(values.dtype, jnp.integer) \
        else jnp.float32
    bn = min(bn, _round_up(n, _LANES))
    bk = min(bk, _round_up(num_segments, _LANES))
    bd = min(bd, d)
    # pad to block multiples; padded rows get id = Kp (matches no k block)
    np_ = _round_up(n, bn)
    kp = _round_up(num_segments, bk)
    dp = _round_up(d, bd)
    ident = jnp.asarray(_int_identity(op) if acc == jnp.int32
                        else _IDENTITY[op], acc)
    # the pads and transposes into the lane-dense layout, and back, carry
    # the named scope "layout": the device trace tells them from the kernel
    with jax.named_scope("layout"):
        ids32 = ids.astype(jnp.int32)
        values = values.astype(acc)
        if op == "+":
            # dropped rows (id < 0 or ≥ num_segments) hit an all-zero
            # one-hot column, but 0 × inf/NaN would still contaminate the
            # MXU dot — zero their values so they contribute nothing
            # regardless of content (min/max use a pure select, which
            # never multiplies)
            keep = (ids32 >= 0) & (ids32 < num_segments)
            values = jnp.where(keep[:, None], values, jnp.zeros((), acc))
        ids_p = jnp.full((1, np_), kp, jnp.int32).at[0, :n].set(ids32)
        vals_p = jnp.full((dp, np_), ident, acc).at[:d, :n].set(values.T)
    # inside shard_map the output varies over the mesh axes its operands
    # vary over: the annotation shard_map's replication check reads
    vma = jax.typeof(ids_p).vma | jax.typeof(vals_p).vma

    out = pl.pallas_call(
        functools.partial(_kernel, bk=bk, bd=bd, op=op, acc=acc),
        grid=(kp // bk, dp // bd, np_ // bn),
        in_specs=[
            pl.BlockSpec((1, bn), lambda k, dd, nn: (0, nn)),
            pl.BlockSpec((bd, bn), lambda k, dd, nn: (dd, nn)),
        ],
        out_specs=pl.BlockSpec((bd, bk), lambda k, dd, nn: (dd, k)),
        out_shape=jax.ShapeDtypeStruct((dp, kp), acc, vma=vma),
        interpret=interpret,
        name="segment_reduce",
    )(ids_p, vals_p)
    with jax.named_scope("layout"):
        out = out[:d, :num_segments].T
        return out[:, 0] if squeeze else out


def segment_sum(ids: jax.Array, values: jax.Array, num_segments: int,
                *, bn: int = 1024, bk: int = 256, bd: int = 8,
                interpret: bool = True) -> jax.Array:
    """ids: [N] int32; values: [N, D] -> [num_segments, D] float32.
    Kept as the historical fp32 entry point; `segment_reduce` is the
    general (dtype-preserving, [N]-or-[N,D], min/max-capable) form."""
    return segment_reduce(ids, values.astype(jnp.float32), num_segments,
                          op="+", bn=bn, bk=bk, bd=bd, interpret=interpret)

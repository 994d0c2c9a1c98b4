"""Pallas TPU kernel: chunked selective-scan (Mamba-1 inner recurrence).

h_t = a_t ⊙ h_{t-1} + b_t ;  y_t = Σ_N c_t ⊙ h_t

Grid: (B, D/bd); each step owns a [bd, N] state slice in VMEM and walks the
sequence in [bk]-step chunks with a fori_loop — the state never leaves
VMEM, matching how the reference CUDA kernel keeps state in registers
(HBM traffic is O(S·(bd + N)) instead of O(S·bd·N) for the materialized
jnp path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(a_ref, bx_ref, c_ref, y_ref, *, bk: int):
    # a, bx: [1, S, bd, N]; c: [1, S, N]; y: [1, S, bd]
    s = a_ref.shape[1]
    bd, n = a_ref.shape[2], a_ref.shape[3]
    a_full = a_ref[0]
    bx_full = bx_ref[0]
    c_full = c_ref[0]

    def chunk(j, h):
        aj = jax.lax.dynamic_slice_in_dim(a_full, j * bk, bk, 0)
        bj = jax.lax.dynamic_slice_in_dim(bx_full, j * bk, bk, 0)
        cj = jax.lax.dynamic_slice_in_dim(c_full, j * bk, bk, 0)

        def step(t, carry):
            h_in, ys = carry
            h_new = aj[t] * h_in + bj[t]                 # [bd, N]
            y = jnp.sum(h_new * cj[t][None, :], axis=1)  # [bd]
            ys = jax.lax.dynamic_update_slice_in_dim(ys, y[None], t, 0)
            return h_new, ys

        h, ys = jax.lax.fori_loop(0, bk, step,
                                  (h, jnp.zeros((bk, bd), jnp.float32)))
        y_ref[0, pl.dslice(j * bk, bk), :] = ys
        return h

    h0 = jnp.zeros((bd, n), jnp.float32)
    jax.lax.fori_loop(0, s // bk, chunk, h0)


@functools.partial(jax.jit, static_argnames=("bd", "bk", "interpret"))
def selective_scan(a: jax.Array, bx: jax.Array, c: jax.Array, *,
                   bd: int = 128, bk: int = 64,
                   interpret: bool = True) -> jax.Array:
    """a, bx: [B, S, D, N] (discretized decay / input); c: [B, S, N].
    Returns y: [B, S, D] with y_t = Σ_N c_t ⊙ h_t, h_t = a_t h_{t-1} + b_t."""
    b, s, d, n = a.shape
    bd = min(bd, d)
    bk = min(bk, s)
    assert d % bd == 0 and s % bk == 0, (d, bd, s, bk)

    return pl.pallas_call(
        functools.partial(_kernel, bk=bk),
        grid=(b, d // bd),
        in_specs=[
            pl.BlockSpec((1, s, bd, n), lambda i, j: (i, 0, j, 0)),
            pl.BlockSpec((1, s, bd, n), lambda i, j: (i, 0, j, 0)),
            pl.BlockSpec((1, s, n), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, s, bd), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((b, s, d), jnp.float32),
        interpret=interpret,
        name="selective_scan",
    )(a.astype(jnp.float32), bx.astype(jnp.float32), c.astype(jnp.float32))

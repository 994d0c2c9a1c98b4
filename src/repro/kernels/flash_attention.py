"""Pallas TPU kernel: causal flash attention (online softmax).

Grid: (batch*heads, Sq/bq).  Each step holds one query tile and the full
K/V for its (batch, head) in VMEM, scanning K/V in [bk] chunks with the
running (max, sum, acc) online-softmax state — O(bq * hd) live state, no
[Sq, Sk] score materialization.  Used by the LM stack as the TPU target of
`attention_core` (the jnp chunked path is the dry-run/interpret fallback).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, bq: int, bk: int, causal: bool,
            scale: float):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale              # [bq, hd]
    k_full = k_ref[0]                                     # [Sk, hd]
    v_full = v_ref[0]
    sk = k_full.shape[0]
    nk = sk // bk
    hd = q.shape[-1]

    def body(j, carry):
        m_i, l_i, acc = carry
        kc = jax.lax.dynamic_slice_in_dim(k_full, j * bk, bk, 0)
        vc = jax.lax.dynamic_slice_in_dim(v_full, j * bk, bk, 0)
        s = jax.lax.dot_general(q, kc.astype(jnp.float32),
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [bq,bk]
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, NEG)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_i - m_new)
        l_new = l_i * alpha + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p, vc.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    m0 = jnp.full((bq,), NEG, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc0 = jnp.zeros((bq, hd), jnp.float32)
    m_i, l_i, acc = jax.lax.fori_loop(0, nk, body, (m0, l0, acc0))
    o_ref[0] = (acc / jnp.maximum(l_i, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bq", "bk", "causal",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    bq: int = 128, bk: int = 128, causal: bool = True,
                    interpret: bool = True) -> jax.Array:
    """q: [BH, Sq, hd]; k, v: [BH, Sk, hd] -> [BH, Sq, hd]."""
    bh, sq, hd = q.shape
    sk = k.shape[1]
    bq = min(bq, sq)
    bk = min(bk, sk)
    assert sq % bq == 0 and sk % bk == 0, (sq, bq, sk, bk)
    scale = hd ** -0.5

    return pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, causal=causal, scale=scale),
        grid=(bh, sq // bq),
        in_specs=[pl.BlockSpec((1, bq, hd), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((1, sk, hd), lambda b, i: (b, 0, 0)),
                  pl.BlockSpec((1, sk, hd), lambda b, i: (b, 0, 0))],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)

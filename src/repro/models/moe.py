"""Mixture-of-Experts layer with expert parallelism.

This is where the paper's technique is a first-class feature of the LM
framework: the MoE combine step is literally the paper's incremental-update
pattern

    for a in assignments:  Y[token(a)] += weight(a) * expert_out(a)

i.e. a *group-by destination index + commutative ⊕-reduction* (paper §3.7),
lowered to a segment-reduce (scatter-add).  The dispatch step is the dual
(group tokens by routed expert).  Three execution modes:

* ``local``        — single device / no mesh: sort-by-expert + ragged_dot.
* ``ep_alltoall``  — tokens sequence-sharded over the `model` axis; a
                     capacity-bounded all_to_all moves tokens to their
                     expert's shard and back (shard_map).  Used for
                     train/prefill shapes.
* ``ep_local``     — decode (S too small to shard): tokens replicated over
                     `model`; each shard computes only its local experts and
                     the combine is a psum.  No all_to_all.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import ParamDef, dense


def moe_defs(cfg) -> dict[str, ParamDef]:
    d, e, ff = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    dt = cfg.param_dtype
    dm = "embed" if cfg.fsdp_experts else "none"  # FSDP d_model dim or not
    return {
        "router": ParamDef((d, e), ("embed", "none"), dt),
        "w_gate": ParamDef((e, d, ff), ("experts", dm, "expert_ff"), dt),
        "w_in": ParamDef((e, d, ff), ("experts", dm, "expert_ff"), dt),
        "w_out": ParamDef((e, ff, d), ("experts", "expert_ff", dm), dt),
    }


def _router(cfg, p, xt):
    """xt: [T,d] -> (weights [T,k], experts [T,k]) with normalized weights."""
    logits = dense(xt, p["router"]).astype(jnp.float32)
    gw, ge = jax.lax.top_k(logits, cfg.top_k)
    gw = jax.nn.softmax(gw, axis=-1)
    return gw, ge


def _padded_expert_pass(xt_flat, eloc, valid, n_experts, cap_e,
                        w_gate, w_in, w_out):
    """Expert-major padded-buffer grouped matmul (the TPU-native MoE form).

    Rows are scattered into a static [E, cap_e, d] buffer by (expert,
    rank-within-expert) — rank via the paper's group-by cumsum pattern —
    then all experts run as ONE block einsum with zero dense waste beyond
    the capacity padding.  Overflow rows are dropped (capacity semantics).
    Returns per-row outputs gathered back ([N, d]) with dropped rows zero.
    """
    n, d = xt_flat.shape
    onehot = (eloc[:, None] == jnp.arange(n_experts)[None]).astype(jnp.int32)
    onehot = onehot * valid[:, None].astype(jnp.int32)
    rank = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1,
                               eloc[:, None], axis=1)[:, 0]
    keep = (rank < cap_e) & valid
    slot = jnp.where(keep, rank, cap_e)
    buf = jnp.zeros((n_experts, cap_e + 1, d), xt_flat.dtype) \
        .at[eloc, slot].set(xt_flat)[:, :cap_e]
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate)) * \
        jnp.einsum("ecd,edf->ecf", buf, w_in)
    y = jnp.einsum("ecf,efd->ecd", h, w_out)
    out = y[eloc, jnp.where(keep, rank, 0)]
    return out * keep[:, None].astype(out.dtype)


def _cap_e(n_rows: int, n_experts: int, cf: float) -> int:
    cap = math.ceil(n_rows / n_experts * cf)
    return max(8, -(-cap // 8) * 8)


def segment_add(values, segment_ids, num_segments):
    """The paper's group-by-⊕ combine (scatter-add).  jnp path; the Pallas
    one-hot-MXU kernel in repro.kernels.segment_reduce implements the same
    contract for TPU hot loops."""
    return jnp.zeros((num_segments,) + values.shape[1:], values.dtype) \
        .at[segment_ids].add(values)


# ---------------------------------------------------------------------------
# local (single-shard) path
# ---------------------------------------------------------------------------

def moe_local(cfg, p, x):
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    gw, ge = _router(cfg, p, xt)
    k = cfg.top_k
    flat_e = ge.reshape(t * k)
    flat_w = gw.reshape(t * k)
    src = jnp.repeat(jnp.arange(t), k)
    cap_e = _cap_e(t * k, cfg.num_experts, cfg.capacity_factor)
    ys = _padded_expert_pass(jnp.take(xt, src, axis=0), flat_e,
                             jnp.ones((t * k,), bool), cfg.num_experts, cap_e,
                             p["w_gate"], p["w_in"], p["w_out"])
    y = segment_add(ys * flat_w[:, None].astype(ys.dtype), src, t)
    return y.reshape(b, s, d).astype(x.dtype)


# ---------------------------------------------------------------------------
# expert-parallel paths (shard_map over the mesh)
# ---------------------------------------------------------------------------

def _capacity(tokens_per_shard: int, top_k: int, n_shards: int, cf: float) -> int:
    cap = math.ceil(tokens_per_shard * top_k / n_shards * cf)
    return max(8, -(-cap // 8) * 8)  # round up to multiple of 8


def moe_ep(cfg, p, x, mesh, dp_axes: tuple[str, ...]):
    """Dispatch to the right EP mode based on static shapes."""
    model_n = mesh.shape["model"]
    if model_n == 1:
        return moe_local(cfg, p, x)
    b, s, d = x.shape
    dp_n = 1
    for a in dp_axes:
        dp_n *= mesh.shape[a]
    if s % model_n == 0 and (b % dp_n == 0) and (b // dp_n) * (s // model_n) >= 64:
        return _moe_ep_alltoall(cfg, p, x, mesh, dp_axes)
    return _moe_ep_localexperts(cfg, p, x, mesh, dp_axes)


def _moe_ep_alltoall(cfg, p, x, mesh, dp_axes):
    m = mesh.shape["model"]
    e_loc = cfg.num_experts // m
    b, s, d = x.shape
    dp_n = 1
    for a in dp_axes:
        dp_n *= mesh.shape[a]
    t_loc = (b // dp_n) * (s // m)
    cap = _capacity(t_loc, cfg.top_k, m, cfg.capacity_factor)
    k = cfg.top_k

    def local_fn(router_w, w_gate, w_in, w_out, x_loc):
        if cfg.fsdp_experts:
            # FSDP: gather the d_model shards of the local experts' weights
            w_gate = jax.lax.all_gather(w_gate, "data", axis=1, tiled=True)
            w_in = jax.lax.all_gather(w_in, "data", axis=1, tiled=True)
            w_out = jax.lax.all_gather(w_out, "data", axis=2, tiled=True)

        bl, sl, _ = x_loc.shape
        t = bl * sl
        xt = x_loc.reshape(t, d)
        gw, ge = _router(cfg, {"router": router_w}, xt)
        flat_e = ge.reshape(t * k)
        flat_w = gw.reshape(t * k)
        src = jnp.repeat(jnp.arange(t), k)
        dest = flat_e // e_loc                                  # [t*k] shard id
        onehot = (dest[:, None] == jnp.arange(m)[None]).astype(jnp.int32)
        pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1,
                                  dest[:, None], axis=1)[:, 0]
        keep = pos < cap
        pos_safe = jnp.where(keep, pos, cap)                    # overflow slot

        send_x = jnp.zeros((m, cap + 1, d), xt.dtype).at[dest, pos_safe].set(
            jnp.take(xt, src, axis=0))[:, :cap]
        send_el = jnp.zeros((m, cap + 1), jnp.int32).at[dest, pos_safe].set(
            flat_e % e_loc)[:, :cap]
        send_ok = jnp.zeros((m, cap + 1), jnp.bool_).at[dest, pos_safe].set(
            keep)[:, :cap]

        recv_x = jax.lax.all_to_all(send_x, "model", 0, 0)      # [m,cap,d]
        recv_el = jax.lax.all_to_all(send_el, "model", 0, 0)
        recv_ok = jax.lax.all_to_all(send_ok, "model", 0, 0)

        flat_x = recv_x.reshape(m * cap, d)
        eloc = jnp.where(recv_ok.reshape(m * cap), recv_el.reshape(m * cap), 0)
        cap_e = _cap_e(m * cap, e_loc, cfg.capacity_factor)
        ys = _padded_expert_pass(flat_x, eloc, recv_ok.reshape(m * cap),
                                 e_loc, cap_e, w_gate, w_in, w_out)

        back = jax.lax.all_to_all(ys.reshape(m, cap, d), "model", 0, 0)
        gathered = back[dest, pos_safe.clip(0, cap - 1)]
        contrib = gathered * (flat_w * keep)[:, None].astype(gathered.dtype)
        y = segment_add(contrib, src, t)                        # paper group-by
        return y.reshape(bl, sl, d).astype(x_loc.dtype)

    dp = tuple(dp_axes)
    wdm = "data" if cfg.fsdp_experts else None
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(None, None), P("model", wdm, None), P("model", wdm, None),
                  P("model", None, wdm), P(dp, "model", None)),
        out_specs=P(dp, "model", None))
    return fn(p["router"], p["w_gate"], p["w_in"], p["w_out"], x)


def _moe_ep_localexperts(cfg, p, x, mesh, dp_axes):
    """Decode-friendly EP: tokens replicated over `model`; each shard runs
    its local experts on the tokens routed to it; combine via psum."""
    m = mesh.shape["model"]
    e_loc = cfg.num_experts // m
    b, s, d = x.shape
    k = cfg.top_k

    def local_fn(router_w, w_gate, w_in, w_out, x_loc):
        if cfg.fsdp_experts:
            w_gate = jax.lax.all_gather(w_gate, "data", axis=1, tiled=True)
            w_in = jax.lax.all_gather(w_in, "data", axis=1, tiled=True)
            w_out = jax.lax.all_gather(w_out, "data", axis=2, tiled=True)
        my = jax.lax.axis_index("model")
        bl, sl, _ = x_loc.shape
        t = bl * sl
        xt = x_loc.reshape(t, d)
        gw, ge = _router(cfg, {"router": router_w}, xt)
        flat_e = ge.reshape(t * k)
        flat_w = gw.reshape(t * k)
        src = jnp.repeat(jnp.arange(t), k)
        mine = (flat_e // e_loc) == my
        xin = jnp.take(xt, src, axis=0)
        eloc = jnp.where(mine, flat_e % e_loc, 0)
        cap_e = _cap_e(t * k, cfg.num_experts, cfg.capacity_factor)
        ys = _padded_expert_pass(xin, eloc, mine, e_loc, cap_e,
                                 w_gate, w_in, w_out)
        contrib = ys * (flat_w * mine)[:, None].astype(ys.dtype)
        y = segment_add(contrib, src, t)
        y = jax.lax.psum(y, "model")
        return y.reshape(bl, sl, d).astype(x_loc.dtype)

    dp = tuple(dp_axes)
    wdm = "data" if cfg.fsdp_experts else None
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(None, None), P("model", wdm, None), P("model", wdm, None),
                  P("model", None, wdm), P(dp, None, None)),
        out_specs=P(dp, None, None))
    return fn(p["router"], p["w_gate"], p["w_in"], p["w_out"], x)


def moe_forward(cfg, p, x, mesh=None, dp_axes=("data",)):
    if mesh is None:
        return moe_local(cfg, p, x)
    return moe_ep(cfg, p, x, mesh, dp_axes)

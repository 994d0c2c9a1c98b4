"""Shared model-building machinery.

Parameters are plain nested dicts of jnp arrays. A parallel "definition
tree" of :class:`ParamDef` is the single source of truth from which we
derive (a) abstract ShapeDtypeStructs for the dry-run, (b) PartitionSpecs
for the mesh, and (c) real initialized arrays for smoke tests / training.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# ---------------------------------------------------------------------------
# Logical axis -> mesh axis mapping.
#
# TP over "model", FSDP over "data".  The "pod" axis is deliberately absent:
# params are replicated across pods (only gradient all-reduce crosses DCN).
# A dim is only sharded if its size is divisible by the mesh axis size.
# ---------------------------------------------------------------------------
LOGICAL_RULES: dict[str, tuple[str, ...]] = {
    "vocab": ("model",),
    "embed": ("data",),       # FSDP axis for the d_model dim
    "ff": ("model",),
    "qkv": ("model",),        # fused q/k/v output dim (heads*head_dim)
    "heads": ("model",),
    "experts": ("model",),    # expert parallelism
    "expert_ff": (),
    "dinner": ("model",),     # mamba inner dim
    "lru": ("model",),        # RG-LRU width
    "layers": (),             # stacked-layer leading axis: never sharded
    "conv": (),
    "state": (),
    "dtrank": (),
    "none": (),
}


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: tuple[int, ...]
    logical: tuple[str, ...]        # one logical axis name per dim
    dtype: Any = jnp.bfloat16
    init: str = "lecun"             # lecun | normal | zeros | ones | ssm_a | ssm_dt

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)


def pspec_for(d: ParamDef, axis_sizes: dict[str, int]) -> P:
    """Map logical dims to mesh axes, dropping non-divisible shardings."""
    used: set[str] = set()
    spec = []
    for size, name in zip(d.shape, d.logical):
        chosen = None
        for ax in LOGICAL_RULES.get(name, ()):
            if ax in used:
                continue
            n = axis_sizes.get(ax, 1)
            if n > 1 and size % n == 0:
                chosen = ax
                used.add(ax)
                break
        spec.append(chosen)
    return P(*spec)


def _path_key(path: tuple[str, ...]) -> int:
    h = hashlib.sha256("/".join(path).encode()).digest()
    return int.from_bytes(h[:4], "little")


def init_array(d: ParamDef, key: jax.Array) -> jax.Array:
    shape, dtype = d.shape, d.dtype
    if d.init == "zeros":
        return jnp.zeros(shape, dtype)
    if d.init == "ones":
        return jnp.ones(shape, dtype)
    if d.init == "ssm_a":  # A_log init: log(1..N) broadcast over d_inner
        n = shape[-1]
        a = jnp.broadcast_to(jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)), shape)
        return a.astype(dtype)
    if d.init == "ssm_dt":  # dt bias ~ log-uniform in [1e-3, 1e-1]
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
        return jnp.log(jnp.expm1(dt)).astype(dtype)
    if d.init == "normal":
        return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)
    # lecun: fan_in = product of all but last dim (or last-but-one for stacks)
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = 1.0 / (fan_in ** 0.5)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def is_def(x) -> bool:
    return isinstance(x, ParamDef)


def tree_abstract(defs):
    return jax.tree.map(lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype), defs,
                        is_leaf=is_def)


def tree_pspecs(defs, axis_sizes):
    return jax.tree.map(lambda d: pspec_for(d, axis_sizes), defs, is_leaf=is_def)


def tree_init(defs, seed: int):
    leaves, treedef = jax.tree.flatten_with_path(defs, is_leaf=is_def)
    out = []
    base = jax.random.PRNGKey(seed)
    for path, d in leaves:
        pth = tuple(str(p) for p in path)
        out.append(init_array(d, jax.random.fold_in(base, _path_key(pth))))
    return jax.tree.unflatten(treedef, out)


# ---------------------------------------------------------------------------
# Basic NN ops (pure functions over param dicts)
# ---------------------------------------------------------------------------

def constrain(x, mesh, *spec):
    """with_sharding_constraint that is a no-op without a mesh and drops
    non-divisible axis entries.  Its transpose applies the same sharding to
    cotangents — this is what keeps XLA from all-gathering backward buffers."""
    if mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def ok(dim, entry):
        if entry is None:
            return None
        axes = entry if isinstance(entry, tuple) else (entry,)
        n = 1
        for a in axes:
            n *= sizes.get(a, 1)
        return entry if n > 1 and x.shape[dim] % n == 0 else None

    fixed = PartitionSpec(*(ok(i, e) for i, e in enumerate(spec)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, fixed))


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


def dense(x: jax.Array, w: jax.Array, b: jax.Array | None = None) -> jax.Array:
    y = jnp.einsum("...d,df->...f", x, w)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def swiglu(x, w_gate, w_in, w_out):
    h = jax.nn.silu(dense(x, w_gate)) * dense(x, w_in)
    return dense(h, w_out)


def gelu_mlp(x, w_in, b_in, w_out, b_out):
    return dense(jax.nn.gelu(dense(x, w_in, b_in)), w_out, b_out)


# ---------------------------------------------------------------------------
# RoPE (+ the M-RoPE variant used by qwen2-vl; position ids are a stub input)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """x: [..., S, H, hd]; pos: broadcastable to [..., S] (int)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                           # [hd/2]
    angles = pos.astype(jnp.float32)[..., None] * freqs     # [..., S, hd/2]
    angles = angles[..., None, :]                           # [..., S, 1, hd/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, pos3: jax.Array, theta: float,
                sections: tuple[int, ...]) -> jax.Array:
    """M-RoPE: head_dim/2 split into len(sections) position streams.

    x: [B, S, H, hd]; pos3: [B, S, 3] (temporal/height/width — stub input).
    """
    import numpy as np
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                           # [hd/2]
    # choose which of the 3 position streams each frequency uses (static)
    sec_id = jnp.asarray(np.repeat(np.arange(len(sections)), np.array(sections)))
    pos = jnp.take_along_axis(
        pos3.astype(jnp.float32),
        jnp.broadcast_to(sec_id[None, None, :], pos3.shape[:2] + (hd // 2,)).astype(jnp.int32),
        axis=-1) if pos3.shape[-1] == 3 else pos3.astype(jnp.float32)
    angles = pos[..., None, :]                              # [B, S, 1, hd/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)

"""`select sum(value) ... group by key`, run by the loop program
`repro.core.programs.group_by` on a bag (key, value).

A composite key (several columns in `traffic["key"]`) is coded in mixed
radix over the columns' domains, so its K is their product.  The plain
reference is float64 numpy; the control is the same sum in bfloat16 on the
device.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

PROGRAM = "group_by"
OUTPUTS = ("C",)


def inputs(cols: dict, domains: dict, traffic: dict) -> dict:
    names = traffic["key"]
    key, k = jnp.zeros_like(cols[names[0]]), 1
    for name in names:
        key = key * domains[name] + cols[name]
        k *= domains[name]
    if k >= 1 << 24:
        raise ValueError(f"group key domain {k} is not exact in float32")
    return dict(S=(key, cols[traffic["value"]]), C=jnp.zeros(k, jnp.float32))


def warm_inputs(ins: dict, traffic: dict) -> dict:
    return ins


def host_data(ins: dict) -> dict:
    key, val = ins["S"]
    return dict(key=np.asarray(key).astype(np.int64),
                val=np.asarray(val).astype(np.float64), k=ins["C"].shape[0])


def reference(h: dict) -> dict:
    return dict(C=np.bincount(h["key"], weights=h["val"], minlength=h["k"]))


def control(ins: dict) -> dict:
    """The reference in bfloat16, on the device."""
    key, val = ins["S"]
    return dict(C=_control(key, val, ins["C"].shape[0]))


@partial(jax.jit, static_argnames=("k",))
def _control(key, val, k):
    return jax.ops.segment_sum(val.astype(jnp.bfloat16),
                               key.astype(jnp.int32), k).astype(jnp.float32)


def compare(out: dict, ref: dict) -> dict:
    """Worst absolute error over the groups, and the worst relative to
    max(|reference|, 1) (empty groups sum to 0)."""
    C = np.asarray(out["C"], np.float64)
    if C.shape != ref["C"].shape:
        return dict(C_abs_err=np.inf, C_rel_err=np.inf)
    d = np.abs(C - ref["C"])
    res = dict(C_abs_err=float(np.max(d)),
               C_rel_err=float(np.max(d / np.maximum(np.abs(ref["C"]), 1.0))))
    return {k: (v if np.isfinite(v) else np.inf) for k, v in res.items()}


def work(ins: dict) -> dict:
    """What a group-by must touch: N rows of key and value, K groups."""
    return dict(rows=int(ins["S"][0].shape[0]), groups=int(ins["C"].shape[0]))

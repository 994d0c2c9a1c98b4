"""The sort backend of the group-by: one stable key/value sort carries the
values with their ids.  Its results equal, bit for bit, a plain argsort
followed by two permutation gathers (the classic form, written out here),
for every monoid, float and int values, salted or not, with dropped rows
routed to the sentinel segment; and the branch compiles to no gather."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compile_program
from repro.core.frontend import bag, loop_program, map_
from repro.core.lower import COMBINE, REDUCE, ExecContext


@loop_program
def gb_sum(S: bag[2], C: map_):
    for k, v in S:
        C[k] += v


@loop_program
def gb_min(S: bag[2], C: map_):
    for k, v in S:
        C[k] = min(C[k], v)


@loop_program
def gb_max(S: bag[2], C: map_):
    for k, v in S:
        C[k] = max(C[k], v)


@loop_program
def gb_prod(S: bag[2], C: map_):
    for k, v in S:
        C[k] *= v


PROGRAMS = {"+": gb_sum, "min": gb_min, "max": gb_max, "*": gb_prod}
SEG = {"+": jax.ops.segment_sum, "min": jax.ops.segment_min,
       "max": jax.ops.segment_max, "*": jax.ops.segment_prod}
N, K = 3000, 37


def inputs(op, dtype):
    rng = np.random.default_rng(14)
    # keys include out-of-range ones (negative and ≥ K): dropped rows
    keys = rng.integers(-3, K + 3, N)
    if dtype == jnp.int32:
        vals = rng.integers(-2, 3, N) if op == "*" \
            else rng.integers(-1000, 1000, N)
    else:
        vals = rng.uniform(0.9, 1.1, N) if op == "*" \
            else rng.standard_normal(N) * 1e3
    dest = rng.integers(-5, 5, K) if op != "*" else np.ones(K)
    return (jnp.asarray(keys, jnp.int32), jnp.asarray(vals, dtype),
            jnp.asarray(dest, dtype))


def argsort_gather_reference(keys, vals, dest, op, salt):
    """The group-by with argsort and two permutation gathers: dropped rows
    to the sentinel segment, salted ids `key*S + row % S` folded back."""
    num = dest.shape[0]
    ids = jnp.where((keys >= 0) & (keys < num), keys, num)
    if salt > 1:
        row = jnp.arange(ids.shape[0], dtype=jnp.int32) % salt
        ids = jnp.where(ids < num, ids * salt + row, num * salt)
    order = jnp.argsort(ids, stable=True)
    part = SEG[op](vals[order], ids[order], num_segments=num * salt + 1,
                   indices_are_sorted=True)[:num * salt]
    if salt > 1:
        part = REDUCE[op](part.reshape(num, salt), axis=1)
    return COMBINE[op](dest, part)


@pytest.mark.parametrize("salting", ["off", "force:4"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32],
                         ids=["float32", "int32"])
@pytest.mark.parametrize("op", ["+", "min", "max", "*"])
def test_sort_backend_matches_argsort_gather(tmp_path, op, dtype, salting):
    cp = compile_program(PROGRAMS[op], op_select="force:sort",
                         skew_salting=salting,
                         autotune_cache=str(tmp_path / "none.json"))
    keys, vals, dest = inputs(op, dtype)

    def whole(keys, vals, dest):
        # the executor straight, so the destination keeps its dtype
        env = {"S": (keys, vals), "C": dest}
        cp.executor.execute(cp.plan, env, ExecContext())
        return env["C"]
    got = jax.jit(whole)(keys, vals, dest)
    salt = 4 if salting == "force:4" else 1
    want = jax.jit(argsort_gather_reference, static_argnums=(3, 4))(
        keys, vals, dest, op, salt)
    assert got.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    selected = cp.explain()
    assert "segment:sort[pinned]" in selected
    assert ("salt=4x" in selected) == (salt > 1)

    # the sort branch alone: one sort, no gather (the reference has two)
    num = K * salt
    ids = jnp.minimum(jnp.abs(keys), num)
    branch = jax.jit(lambda i, v: cp.executor._segment_flat(
        "sort", i, v, num, op)).lower(ids, vals).compile().as_text()
    assert re.search(r"\bsort\(", branch)
    assert not re.search(r"\bgather\(", branch)
    ref = jax.jit(argsort_gather_reference, static_argnums=(3, 4)).lower(
        keys, vals, dest, op, salt).compile().as_text()
    assert len(re.findall(r"\bgather\(", ref)) == 2

"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

Every kernel the TPU cost model can pick (op_select: `pallas` segment
reduce for + / min / max on float values and min / max on int values,
never an int +; `pallas-tiled` for the §5 packed product) is compiled by
the TPU compiler at real widths, with no chip attached: Mosaic refuses misaligned blocks, unsupported layouts and
missing MXU types here, in about a second per kernel.  The topology, and
everything built from it, is described inside the fixtures — never while
a module is imported — so only the test worker that runs this file loads
the TPU compiler.
"""
import os

import pytest

import jax
import jax.numpy as jnp

from repro.kernels.segment_reduce import segment_reduce
from repro.kernels.tile_matmul import tile_matmul

N = 1 << 24
MM = 4096


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("k", [256, 1 << 20])
@pytest.mark.parametrize("op,dtype", [("+", jnp.float32),
                                      ("min", jnp.float32),
                                      ("max", jnp.float32),
                                      ("min", jnp.int32),
                                      ("max", jnp.int32)])
def test_segment_reduce_compiles_for_v5e(one_chip, op, dtype, k):
    ids = jax.ShapeDtypeStruct((N,), jnp.int32, sharding=one_chip)
    vals = jax.ShapeDtypeStruct((N,), dtype, sharding=one_chip)
    text = _compiled_text(
        lambda i, v: segment_reduce(i, v, k, op=op, interpret=False),
        ids, vals)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("d", [3, 12])
def test_segment_reduce_2d_values_compile_for_v5e(one_chip, d):
    ids = jax.ShapeDtypeStruct((N,), jnp.int32, sharding=one_chip)
    vals = jax.ShapeDtypeStruct((N, d), jnp.float32, sharding=one_chip)
    for op in ("+", "max"):
        text = _compiled_text(
            lambda i, v: segment_reduce(i, v, 256, op=op, interpret=False),
            ids, vals)
        assert "tpu_custom_call" in text


@pytest.mark.parametrize("masked", [False, True])
def test_tile_matmul_compiles_for_v5e(one_chip, masked):
    a = jax.ShapeDtypeStruct((MM, MM), jnp.float32, sharding=one_chip)
    b = jax.ShapeDtypeStruct((MM, MM), jnp.float32, sharding=one_chip)
    if masked:
        m = jax.ShapeDtypeStruct((MM // 128, MM // 128), jnp.float32,
                                 sharding=one_chip)
        text = _compiled_text(
            lambda x, y, t: tile_matmul(x, y, tile_mask=t, interpret=False),
            a, b, m)
    else:
        text = _compiled_text(
            lambda x, y: tile_matmul(x, y, interpret=False), a, b)
    assert "tpu_custom_call" in text

"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing never touches jax
device state.  Single pod: (data=16, model=16) = 256 chips; multi-pod adds
a leading `pod` axis (2 pods = 512 chips).  The dry-run forces 512 host
devices via XLA_FLAGS (see dryrun.py); the single-pod mesh then uses the
first 256.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType


def _auto(axes) -> tuple:
    """Auto axes: the plan's collectives and XLA's SPMD partitioner decide
    the layouts (jax.make_mesh otherwise makes Explicit axes, which reject
    sharded contracting dimensions)."""
    return (AxisType.Auto,) * len(axes)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, found {len(devs)} — run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=512 (see dryrun.py)")
    return jax.make_mesh(shape, axes, devices=devs[:need],
                         axis_types=_auto(axes))


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Mesh over the first prod(shape) devices: forced host devices in the
    tests, the chips of a TPU host in chip_smoke.py."""
    need = int(np.prod(shape))
    return jax.make_mesh(shape, axes, devices=jax.devices()[:need],
                         axis_types=_auto(axes))


def axis_sizes(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def dp_axes_of(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))

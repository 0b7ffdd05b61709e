"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state — the dry-run sets
XLA_FLAGS for 512 host devices before first jax init; tests/benches see
the single real CPU device.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

from repro.sharding import FLEET_AXIS

__all__ = ["make_production_mesh", "make_local_mesh", "make_fleet_mesh",
           "mesh_axes"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // data))
    return _auto_mesh((data, model), ("data", "model"))


def _auto_mesh(shape: tuple, axes: tuple) -> Mesh:
    """``jax.make_mesh`` with Auto axes: the sharding rules place arrays
    with ``with_sharding_constraint``, which Explicit axes refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_fleet_mesh(shards: int | None = None, axis: str = FLEET_AXIS) -> Mesh:
    """1-D mesh for registry slab sharding (``ClockRegistry(mesh=...)``).

    Takes the FIRST ``shards`` local devices (default: all of them), so
    shard counts below the device count work — the multi-device test
    harness sweeps {1, 2, 4, 8} on one 8-device host platform.  For
    local testing without accelerators, force host devices BEFORE jax
    initializes:  XLA_FLAGS=--xla_force_host_platform_device_count=8
    (tests/conftest.py does this for the whole suite).
    """
    devs = jax.devices()
    shards = len(devs) if shards is None else shards
    if shards < 1 or shards > len(devs):
        raise ValueError(
            f"need 1 <= shards <= {len(devs)} local devices, got {shards}")
    return Mesh(np.asarray(devs[:shards]), (axis,))


def mesh_axes(mesh) -> tuple:
    return tuple(mesh.shape.keys())

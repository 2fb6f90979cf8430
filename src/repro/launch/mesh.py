"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before first
device query, and tests must keep seeing 1 device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.configs.base import (MULTI_POD_MESH, SINGLE_POD_MESH, MeshConfig,
                                UNIT_MESH)


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the partitioning rules place
    arrays with ``with_sharding_constraint``, which rejects the Explicit
    axes ``make_mesh`` now defaults to."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def mesh_config(*, multi_pod: bool = False) -> MeshConfig:
    return MULTI_POD_MESH if multi_pod else SINGLE_POD_MESH


def make_mesh_from_config(cfg: MeshConfig):
    return auto_mesh(cfg.shape, cfg.axes)


def local_mesh_config() -> MeshConfig:
    """Whatever this host actually has (CPU tests / examples)."""
    n = len(jax.devices())
    return MeshConfig((n, 1), ("data", "model")) if n > 1 else UNIT_MESH

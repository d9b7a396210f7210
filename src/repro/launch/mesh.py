"""Mesh construction: every mesh in the repo is built by ``make_mesh``.

FUNCTIONS, not module-level constants — importing this module never touches
jax device state; callers (dryrun.py) force the placeholder device count via
XLA_FLAGS *before* any jax import.

Axes are ``AxisType.Auto``: shardings stay out of array types and the
partitioner propagates them (``jax.make_mesh`` defaults to Explicit axes,
under which ops such as argsort refuse operands sharded differently).

Mesh roles (shared with the tabular VFL runtime, federation/mesh_roles.py):
  single pod   (16, 16)      -> ("data", "model")       256 chips
  multi-pod    (2, 16, 16)   -> ("pod", "data", "model") 512 chips
"""

from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None) -> jax.sharding.Mesh:
    """A mesh of ``shape`` over ``axes`` (Auto axis types) on ``devices``
    (default: the first ``prod(shape)`` devices)."""
    if devices is None:
        devices = jax.devices()[:math.prod(shape)]
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(num_devices: int | None = None):
    """Small mesh for in-pytest dry-run smoke (8 forced host devices)."""
    n = num_devices or len(jax.devices())
    model = 2 if n % 2 == 0 else 1
    return make_mesh((n // model, model), ("data", "model"))


def make_vfl_mesh(parties: int, data_shards: int = 0):
    """2-D (data × party) training mesh for the vfl-* backends (DESIGN.md §8).

    ``parties`` is the model-axis extent (the VFL party decomposition);
    ``data_shards`` the data-axis extent rows shard over (``vfl-*-sharded``
    backends).  0 = auto: spread the remaining devices over the data axis.
    Raises if the device pool cannot host the requested grid.
    """
    n_dev = len(jax.devices())
    if data_shards <= 0:
        data_shards = max(1, n_dev // parties)
    need = parties * data_shards
    if n_dev < need:
        raise ValueError(
            f"mesh ({data_shards} data x {parties} model) needs {need} "
            f"devices, got {n_dev} (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need})"
        )
    return make_mesh((data_shards, parties), ("data", "model"))


def batch_axes(mesh: jax.sharding.Mesh) -> tuple:
    """Axes the global batch shards over (pod folds into data)."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)

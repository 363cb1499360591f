"""The one home of the mesh and shard_map API calls (DESIGN.md sec. 6.1).

Every module imports ``shard_map`` / ``make_mesh`` from here instead of from
``jax`` directly (enforced by tests/test_fold_codecs.py), so a change of the
JAX API surface touches this file only.
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType, Mesh


def shard_map(f, mesh=None, in_specs=None, out_specs=None, check_vma=False):
    """``jax.shard_map`` (``check_vma=True`` is what makes shard_map
    transposes insert psums for replicated operands, see repro.models.moe)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """A mesh with Auto axis types.

    devices: optional explicit device list (e.g. the first 256 of 512
    placeholder devices).  ``jax.make_mesh`` cannot subset the device pool,
    so that path constructs the Mesh directly.
    """
    axis_shapes = tuple(axis_shapes)
    axis_names = tuple(axis_names)
    axis_types = (AxisType.Auto,) * len(axis_names)
    if devices is not None:
        return Mesh(np.asarray(devices).reshape(axis_shapes), axis_names,
                    axis_types=axis_types)
    return jax.make_mesh(axis_shapes, axis_names, axis_types=axis_types)

"""The mesh and shard_map spellings the repo uses, in one place.

Everything that builds a mesh, enters a mesh context, or wraps a
function in shard_map goes through this module, so a later JAX API move
touches one file.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np


def make_mesh(shape: Sequence[int], axis_names: Sequence[str],
              devices: Optional[Sequence] = None):
    """A mesh with auto axis types.

    ``devices`` restricts the mesh to an explicit device list (the
    elasticity path: a rebuilt mesh over the survivors of a device loss,
    ``popshard.local_devices``); the default uses every local device.
    """
    axis_types = (jax.sharding.AxisType.Auto,) * len(axis_names)
    if devices is not None:
        arr = np.array(list(devices), dtype=object).reshape(tuple(shape))
        return jax.sharding.Mesh(arr, tuple(axis_names),
                                 axis_types=axis_types)
    return jax.make_mesh(tuple(shape), tuple(axis_names),
                         axis_types=axis_types)


def use_mesh(mesh):
    """Context manager activating ``mesh``."""
    return jax.set_mesh(mesh)


def shard_map(f, mesh, in_specs, out_specs):
    """shard_map without replication checking."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

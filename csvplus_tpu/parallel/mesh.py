"""Mesh + sharding helpers.

One flat data axis ("shards") is the natural mesh for a columnar ETL
engine: rows are the only dimension that scales.  Collectives ride ICI
within a slice.  For multi-slice deployments :func:`make_mesh_2d` adds
an outer "slice" axis modelling DCN between slices: row shardings then
split over BOTH axes (slice-major), so intra-slice traffic stays on ICI
and only slice-crossing collectives touch DCN — XLA routes per-axis.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS = "shards"
SLICE_AXIS = "slice"


def make_mesh(n_devices: Optional[int] = None, devices: Optional[Sequence] = None) -> Mesh:
    """A 1-D mesh over the first *n_devices* devices (default: all).
    Asking for more devices than exist raises — a short mesh would run
    the pipeline at a sharding the caller did not ask for."""
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            if n_devices > len(devices):
                raise ValueError(
                    f"make_mesh({n_devices}): only {len(devices)} "
                    f"{devices[0].platform} device(s) exist"
                )
            devices = devices[:n_devices]
    return Mesh(np.array(devices), (AXIS,))


def make_mesh_2d(
    n_slices: int, chips_per_slice: int, devices: Optional[Sequence] = None
) -> Mesh:
    """A (slice, chip) mesh: the outer axis models DCN between slices,
    the inner axis ICI within a slice.  ``row_spec(mesh)`` shardings
    split rows over both axes, slice-major."""
    if devices is None:
        devices = jax.devices()
    devices = np.array(devices[: n_slices * chips_per_slice])
    return Mesh(devices.reshape(n_slices, chips_per_slice), (SLICE_AXIS, AXIS))


def row_spec(mesh: Mesh) -> P:
    """The PartitionSpec splitting dim 0 over ALL mesh axes (1-D mesh:
    plain row sharding; 2-D: slice-major over (slice, chip))."""
    return P(tuple(mesh.axis_names))


def shard_rows(mesh: Mesh, x: "jax.Array | np.ndarray") -> jax.Array:
    """Place *x* row-sharded over the mesh (dim 0 split across every
    mesh axis)."""
    return jax.device_put(x, NamedSharding(mesh, row_spec(mesh)))


def replicate(mesh: Mesh, x: "jax.Array | np.ndarray") -> jax.Array:
    """Place *x* fully replicated over the mesh."""
    return jax.device_put(x, NamedSharding(mesh, P()))


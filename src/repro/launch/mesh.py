"""Mesh construction.

Defined as FUNCTIONS so importing this module never touches jax device
state (a process that wants host devices sets XLA_FLAGS before its first
jax import; tests see the single real device).

Axes:
  pod   — DCN-connected pods; data-parallel only (gradient all-reduce).
  data  — ICI within a pod; batch + FSDP axis.
  model — ICI; tensor / expert parallel axis.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # jax.make_mesh defaults to Explicit axis types; models/layers.shard()
    # emits with_sharding_constraint, which only accepts Auto axes.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_mesh(shape, axes=None):
    """Arbitrary mesh for tests / elastic configurations. `shape` may use -1
    for one axis to absorb the remaining devices."""
    shape = tuple(shape)
    n = len(jax.devices())
    if -1 in shape:
        known = int(np.prod([s for s in shape if s != -1]))
        shape = tuple(n // known if s == -1 else s for s in shape)
    if axes is None:
        axes = ("pod", "data", "model")[-len(shape):] if len(shape) <= 3 \
            else tuple(f"ax{i}" for i in range(len(shape)))
    return _auto_mesh(shape, tuple(axes))


def local_mesh():
    """Single-device mesh (smoke tests, measured CPU runs)."""
    return _auto_mesh((1, 1), ("data", "model"))


def batch_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def mesh_degree(mesh, axis: str) -> int:
    return mesh.shape[axis] if axis in mesh.axis_names else 1

"""Mesh-aware sharding helpers.

``constrain(x, spec)`` = with_sharding_constraint that degrades gracefully:
no-op without a mesh context, and silently drops mesh axes that don't exist
in the current mesh (so the same model code runs on 1 CPU device in smoke
tests and on the 512-chip production mesh in the dry-run).
"""
from __future__ import annotations

from typing import Optional

import jax
from jax import lax
from jax.sharding import AxisType, PartitionSpec as P


def auto_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with every axis ``Auto``. JAX's default axis type is
    ``Explicit``; the models mix automatic sharding (``constrain``) with
    ``jax.shard_map`` regions, which needs Auto axes."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def mesh_axis_sizes() -> dict:
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return {}
    return dict(zip(mesh.axis_names, mesh.axis_sizes))


def filter_spec(spec: P) -> Optional[P]:
    """Drop axes that aren't in the current mesh; None if no mesh at all."""
    sizes = mesh_axis_sizes()
    if not sizes:
        return None
    dims = []
    for entry in spec:
        if entry is None:
            dims.append(None)
        elif isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in sizes)
            dims.append(kept if kept else None)
        else:
            dims.append(entry if entry in sizes else None)
    return P(*dims)


def constrain(x, spec: P):
    fs = filter_spec(spec)
    if fs is None:
        return x
    return lax.with_sharding_constraint(x, fs)

"""Production mesh definitions.

Kept as FUNCTIONS so importing this module never touches jax device state
(device count is locked at first jax init — see launch/dryrun.py which must
set XLA_FLAGS before any jax import).

Topology: TPU v5e pods of 256 chips arranged (16, 16) = (data, model);
multi-pod adds a leading DCN "pod" axis: (2, 16, 16) = (pod, data, model).
Batch shards over (pod, data); tensor/expert parallelism over model.
"""
from __future__ import annotations

import jax

from repro.models.sharding import auto_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_local_mesh(n_model: int = 1):
    """Small mesh over however many real devices exist (tests/examples)."""
    n = len(jax.devices())
    n_model = min(n_model, n)
    return auto_mesh((n // n_model, n_model), ("data", "model"))


def mesh_axis_names(mesh) -> tuple:
    return tuple(mesh.axis_names)

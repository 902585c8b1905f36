"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never touches
jax device state (required so smoke tests see 1 device while the dry-run sees
512 placeholder host devices via XLA_FLAGS)."""
from __future__ import annotations

from repro.distributed.sharding import auto_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16 x 16 = 256 chips (data, model).
    Multi-pod: 2 x 16 x 16 = 512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def mesh_dp_size(mesh) -> int:
    size = 1
    for name in ("pod", "data"):
        if name in mesh.axis_names:
            size *= mesh.shape[name]
    return size

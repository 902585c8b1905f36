"""Logical→physical sharding translation.

Model code annotates params/activations with *logical* axes:
    "dp"  — data parallel   (physical: ("data",) or ("pod", "data"))
    "tp"  — tensor parallel (physical: ("model",))

`translate` rewrites a PartitionSpec tree for a concrete mesh;
`maybe_shard` applies a with_sharding_constraint only when a mesh context is
active (so the same model code runs un-meshed in unit tests).
"""
from __future__ import annotations

from typing import Any, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def auto_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with every axis of the ``Auto`` type."""
    return jax.make_mesh(
        axis_shapes,
        axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
    )


def _phys_axes(axis, mesh_axis_names) -> Any:
    if axis is None:
        return None
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    out = []
    for a in axes:
        if a == "dp":
            out.extend(n for n in ("pod", "data") if n in mesh_axis_names)
        elif a == "tp":
            if "model" in mesh_axis_names:
                out.append("model")
        elif a in mesh_axis_names:
            out.append(a)
    if not out:
        return None
    return out[0] if len(out) == 1 else tuple(out)


def translate_spec(spec: P, mesh_axis_names: Sequence[str]) -> P:
    return P(*(_phys_axes(a, mesh_axis_names) for a in spec))


def translate_tree(tree, mesh_axis_names: Sequence[str]):
    return jax.tree.map(
        lambda s: translate_spec(s, mesh_axis_names),
        tree,
        is_leaf=lambda s: isinstance(s, P),
    )


def maybe_shard(x, spec: P):
    """Apply a logical sharding constraint iff a mesh context is active."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    return jax.lax.with_sharding_constraint(
        x, translate_spec(spec, mesh.axis_names)
    )


def named_sharding_tree(tree, mesh: Mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, translate_spec(s, mesh.axis_names)),
        tree,
        is_leaf=lambda s: isinstance(s, P),
    )


def zero1_spec(spec: P, shape, dp_axis_size: int) -> P:
    """ZeRO-1-style optimizer-state spec: additionally shard the first
    dimension that is unsharded and divisible by the dp axis."""
    parts = list(spec)
    while len(parts) < len(shape):
        parts.append(None)
    for i, (axis, dim) in enumerate(zip(parts, shape)):
        if axis is None and dim % dp_axis_size == 0 and dim >= dp_axis_size:
            parts[i] = "dp"
            break
    return P(*parts)

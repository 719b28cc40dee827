"""Production mesh construction.

A function (not a module constant) so importing never touches jax device
state.  Single pod: 16×16 = 256 chips (v5e pod), axes (data, model).
Multi-pod: 2×16×16 = 512 chips, axes (pod, data, model) — the pod axis is
pure data parallelism over DCN in the baseline layout.

Every mesh is built with ``Auto`` axes: the model code places activations
with ``with_sharding_constraint`` through the logical rules, which an
``Explicit`` mesh (``jax.make_mesh``'s default since JAX 0.7) refuses.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh

from ..sharding.logical import DEFAULT_TABLE, ShardingRules

__all__ = ["make_production_mesh", "make_rules", "make_test_mesh"]


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_test_mesh(shape, axes)


def make_test_mesh(shape: Tuple[int, ...] = (2, 2), axes: Tuple[str, ...] = ("data", "model")) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_rules(mesh: Mesh, *, long_context: bool = False, overrides: Optional[dict] = None) -> ShardingRules:
    """Bind the logical table to a mesh.  Axes missing from the mesh are
    dropped; ``long_context`` turns on KV-cache sequence sharding (context
    parallelism for the ``long_500k`` decode cells)."""
    table = dict(DEFAULT_TABLE)
    if long_context:
        table["seq_kv"] = "data"
    if overrides:
        table.update(overrides)
    present = set(mesh.shape)

    def fix(v):
        if v is None:
            return None
        if isinstance(v, (tuple, list)):
            kept = tuple(a for a in v if a in present)
            return kept if len(kept) > 1 else (kept[0] if kept else None)
        return v if v in present else None

    return ShardingRules({k: fix(v) for k, v in table.items()}, mesh)

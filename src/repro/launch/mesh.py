"""Production mesh builders (see MULTI-POD DRY-RUN spec).

Functions, not module-level constants: importing this module never touches
jax device state. ``make_production_mesh(multi_pod=True)`` needs 512 devices —
the dry-run entrypoint sets XLA_FLAGS before any jax import.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(*, model: int | None = None) -> Mesh:
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    model = model or 1
    data = n // model
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def mesh_world(mesh: Mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))

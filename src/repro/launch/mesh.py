"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run entry point sets XLA_FLAGS before any jax import).

Single pod: 16 x 16 = 256 chips, axes (data, model).
Multi-pod:  2 x 16 x 16 = 512 chips, axes (pod, data, model) — TP/EP stay
on `model` (intra-pod ICI); only DP gradient traffic crosses `pod`.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """`jax.make_mesh` with Auto axes. The sharding rules place arrays
    with `with_sharding_constraint`, which only names Auto axes, and
    `jax.make_mesh` now defaults to Explicit ones."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_data: Optional[int] = None, n_model: int = 1):
    """Small mesh over whatever devices exist (tests, CPU)."""
    n = jax.device_count()
    n_data = n_data if n_data is not None else n // n_model
    return make_mesh((n_data, n_model), ("data", "model"))


def mesh_chips(mesh) -> int:
    return mesh.devices.size

"""Production mesh builders.

Functions, not module-level constants: importing this module never touches
jax device state.  The production target is a TPU v5e pod of 16×16 = 256
chips; multi-pod doubles it with a leading "pod" axis (2 × 256 = 512 chips)
riding data-center interconnect (see core/hardware.py extra_links).
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """Arbitrary mesh for tests / elastic-reshard experiments."""
    return _auto_mesh(shape, axes)


def make_host_mesh() -> Mesh:
    """Single-device mesh (CPU smoke tests): both axes size 1."""
    return make_mesh((1, 1), ("data", "model"))

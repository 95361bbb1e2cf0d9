"""Production mesh construction.

Single pod:  (data=16, model=16)            = 256 chips (TPU v5e pod)
Multi-pod:   (pod=2, data=16, model=16)     = 512 chips

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _make(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make(shape, axes)


def make_mesh(shape, axes) -> Mesh:
    """Arbitrary mesh with Auto axis types (tests / small-scale runs)."""
    return _make(tuple(shape), tuple(axes))


def chips(mesh: Mesh) -> int:
    return mesh.devices.size

"""Production meshes (TPU v5e pods).

A FUNCTION, not a module-level constant — importing this module must never
touch jax device state (the dry-run pins the device count via XLA_FLAGS
before any jax initialisation).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: shardings propagate through jit as the partition rules
    # and the MoE shard_map expect (make_mesh's default is Explicit)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """(data=16, model=16) single pod (256 chips) or
    (pod=2, data=16, model=16) two pods (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Tiny mesh over however many local devices exist (tests/examples)."""
    n = jax.device_count()
    data = n // model
    return _mesh((data, model), ("data", "model"))

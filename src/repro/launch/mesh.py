"""Production mesh construction.

A function, not a module-level constant: importing this module never touches
jax device state (the dry-run forces 512 host devices BEFORE calling this).
"""
from __future__ import annotations

import jax

_AUTO = jax.sharding.AxisType.Auto


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(_AUTO,) * len(axes))


def make_mesh_for_devices(n_devices: int | None = None, model_parallel: int | None = None):
    """Smaller meshes for tests/examples: (data, model) factorization of the
    available device count."""
    n = n_devices or len(jax.devices())
    mp = model_parallel or 1
    assert n % mp == 0
    return jax.make_mesh((n // mp, mp), ("data", "model"), axis_types=(_AUTO,) * 2)

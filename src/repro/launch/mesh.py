"""Production mesh definitions (TPU v5e pods).

A function, not a module-level constant: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2 pods x 256 = 512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model_parallel: int = 1, axis_names=("data", "model")):
    """Mesh over whatever devices the host actually has (tests/examples)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return jax.make_mesh(
        (n // model_parallel, model_parallel),
        axis_names,
        axis_types=(AxisType.Auto,) * 2,
    )

"""Production mesh construction (assignment-specified topology).

A FUNCTION, not a module-level constant — importing this module never touches
jax device state (smoke tests must keep seeing 1 CPU device).
"""

from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds a leading pod=2 axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Whatever this process actually has (CPU tests, examples)."""
    n = len(jax.devices())
    model = model if n % model == 0 else 1
    return _make_mesh((n // model, model), ("data", "model"))

"""Production mesh construction.

A FUNCTION (not a module constant) so importing never touches jax device
state — the dry-run sets XLA_FLAGS before first jax init; tests and
benches see the real single device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(model: int = 1):
    """Tiny mesh over whatever devices exist (tests/examples on CPU)."""
    n = len(jax.devices())
    model = min(model, n)
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)

"""Production mesh construction (dry-run spec).

A function — not a module-level constant — so importing this module never
touches jax device state (device count locks on first use).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; multi_pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(n: int = 1, axis: str = "data"):
    """Small mesh over locally visible devices (tests / examples)."""
    n = min(n, jax.device_count())
    return jax.make_mesh((n,), (axis,), axis_types=(AxisType.Auto,))

"""Production mesh definitions (functions — importing never touches jax
device state)."""
from __future__ import annotations

import jax


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with Auto axis types (over ``devices`` when given)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(n_devices: int | None = None):
    """Small mesh over whatever devices exist (tests / single host)."""
    n = n_devices or len(jax.devices())
    return make_mesh((1, n, 1), ("pod", "data", "model"))

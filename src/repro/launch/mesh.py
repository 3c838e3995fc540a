"""Mesh construction (importing this module never touches jax device state)."""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """The assigned production mesh: one pod = 16x16 = 256 chips; the
    multi-pod variant adds a leading 2-pod axis (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (CPU) devices exist — tests/examples."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)

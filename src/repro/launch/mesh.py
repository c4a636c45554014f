"""Production meshes.

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  512 chips as (pod=2, data=16, model=16) — the 'pod' axis carries
the slower inter-pod (DCN/ICI-bridge) links, so the rules place only
data-parallel (gradient reduce) traffic on it.

A FUNCTION, not a module constant: importing this module must never touch
jax device state (device count is locked at first backend init).
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_host_mesh"]


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — used by tests."""
    return _make_mesh((data, model), ("data", "model"))

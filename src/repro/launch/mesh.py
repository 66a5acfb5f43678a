"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — required because the dry-run inflates the host
platform to 512 placeholder devices while tests must see a single device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    # Auto axes: the sharding rules place arrays with
    # ``with_sharding_constraint``, which ``jax.make_mesh``'s default
    # Explicit axes refuse.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 (one v5e pod's worth of chips) or 2×16×16 (two pods).

    Axes: 'pod' (DCI, data-parallel only), 'data' (ICI, DP+FSDP),
    'model' (ICI, TP/EP).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh for tests / elastic restarts."""
    return _auto_mesh(shape, axes)

"""Production mesh construction.

A FUNCTION, not a module constant — importing this module never touches jax
device state (device count is locked at first backend init, which the
dry-run controls via XLA_FLAGS before any import).
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips with a leading "pod" axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_miner_mesh(n: int):
    """1-D mesh for the Parallel-FIMI miner axis (launch/mine.py)."""
    return _make_mesh((n,), ("miners",))


def make_debug_mesh(data: int = 2, model: int = 2):
    """Small mesh for CPU multi-device tests (device count set by the test)."""
    return _make_mesh((data, model), ("data", "model"))

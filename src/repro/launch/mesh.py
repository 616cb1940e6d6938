"""Miner mesh construction.

A FUNCTION, not a module constant — importing this module never touches jax
device state (device count is locked at first backend init, which the
launchers control via XLA_FLAGS before any import).
"""
from __future__ import annotations

import jax


def make_miner_mesh(n: int):
    """1-D mesh for the Parallel-FIMI miner axis (launch/mine.py)."""
    return jax.make_mesh(
        (n,), ("miners",), axis_types=(jax.sharding.AxisType.Auto,)
    )

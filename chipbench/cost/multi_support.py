"""Least HBM bytes of one multi-prefix support sweep (``kernels/multi_support``).

Per call, the sweep reads the item bitmap ``[I, W]`` and the ``K`` prefix
tidlists ``[K, W]`` once and writes ``K x I`` int32 supports; vmapped over
miners, the operands that differ by miner carry a leading miner dim.  At
the mine cells' Phase-4 shapes, with the bitmap per miner:
``4 * P * (I*W + K*W + K*I)``.  The count is of the work, not of any
implementation's padding.
"""
from __future__ import annotations

from cost import hlo

MATCH = "multi_extension_supports_pallas"


def least_bytes(miners: int, K: int, I: int, W: int) -> int:
    return 4 * miners * (I * W + K * W + K * I)


def logical_dims(config: dict) -> list:
    """The sizes a mine's sweeps can have: miners, frontier, items, and the
    tidlist words of the Phase-1 sample and of the Phase-4 slab."""
    m, ds = config["mining"], config["dataset"]
    P = m["P"]
    n_db = max(1, min(m["n_db_sample"], ds["n_tx"]) // P) * P
    return [P, m["frontier_size"], ds["n_items"], -(-n_db // 32),
            -(-(ds["n_tx"] // P * P) // 32)]


def event_bytes(name: str, config: dict) -> int:
    return hlo.least_bytes(name, logical_dims(config))

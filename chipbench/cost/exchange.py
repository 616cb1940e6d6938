"""Least interconnect bytes of the Phase-3 exchange
(``core/phases.phase3_exchange``).

A row that leaves its miner crosses the interconnect once for each other
miner that needs it, as its packed item mask: ``4 * ceil(I / 32)`` bytes.
The program's ``rows_moved`` arg on span ``cluster/exchange`` counts those
crossings per round.  What the all-to-all really moves is more: the
fixed-capacity slabs, their padding, the valid flags and each miner's slot
for itself.  The count is of the work, not of any implementation.
"""
from __future__ import annotations

MATCH = "all-to-all"


def row_bytes(config: dict) -> int:
    return 4 * -(-config["dataset"]["n_items"] // 32)


def least_bytes(rows_moved: int, config: dict) -> int:
    return rows_moved * row_bytes(config)


def is_exchange_op(label: str) -> bool:
    """An XLA op of the exchange: an all-to-all, synchronous or the start
    or end of an asynchronous one.  Op labels are ``%<hlo name> = ...``, and
    JAX names the instruction ``all_to_all.<n>`` where XLA's opcode is
    ``all-to-all``."""
    return label.lstrip("%").replace("_", "-").startswith(MATCH)


def a2a_ns_per_chip(device) -> float:
    """Device time of the exchange's ops over the traced segments, summed
    over the chips and averaged over them (ns)."""
    total, chips = 0.0, 0
    for part in device.parts:
        chips = max(chips, len(part.chips))
        for chip in part.chips:
            total += sum(ns for label, ns in chip.by_op.items()
                         if is_exchange_op(label))
    return total / chips if chips else 0.0

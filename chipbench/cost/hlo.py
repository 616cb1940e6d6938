"""Operand and result shapes of a kernel event, read from its HLO text.

A TPU op's trace event is named by its HLO instruction, e.g.::

    %multi_extension_supports_pallas.10 = s32[4,16,1024]{...} custom-call(
        u32[4,16,128]{...} %pad.100, u32[1024,128]{...} %pad.101), ...

The shapes there are the padded arrays the kernel was handed.  The least
bytes of the work use the logical sizes: each dim is read back as the
largest logical size of the cell that fits in it (padding only grows a dim).
"""
from __future__ import annotations

import math
import re

SHAPE = re.compile(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\]")
BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
         "u16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
         "f64": 8}


def shapes(name: str):
    """``(results, operands)``: lists of ``(dtype, dims)``."""
    head, _, rest = name.partition(" custom-call(")
    args = rest.split("), custom_call_target", 1)[0]
    parse = lambda text: [(t, [int(d) for d in dims.split(",") if d])  # noqa: E731
                          for t, dims in SHAPE.findall(text)]
    return parse(head.partition(" = ")[2]), parse(args)


def unpad(dim: int, logical) -> int:
    fits = [n for n in logical if n <= dim]
    return max(fits) if fits else dim


def least_bytes(name: str, logical) -> int:
    """Bytes to read every operand once and write every result once."""
    results, operands = shapes(name)
    return sum(BYTES[t] * math.prod(unpad(d, logical) for d in dims)
               for t, dims in results + operands)

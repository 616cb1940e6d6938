"""Pallas TPU kernel: batched extension-support counting.

The Eclat inner loop (thesis §B.3.1 "support counting") — for a node with
prefix tidlist t and candidate extensions, compute ``popcount(bits_i & t)``
for every item i.  On the original CPU implementation this is |Σ| independent
sorted-list merges; here it is one dense 2-D sweep over the packed bitmap
slab, tiled through VMEM:

  grid = (I/BI, W/BW);  per step AND a ``[BI, BW]`` uint32 tile of item
  bitmaps with a ``[1, BW]`` tile of the prefix tidlist, SWAR-popcount on the
  VPU, and accumulate a ``[BI, 1]`` partial into the output block.  The W grid
  axis is the minormost (sequential on TPU), so the f32/int32 accumulator
  lives in the output block across W steps.

Tile defaults (BI=256, BW=512 words = 16 Ki transactions) keep the working
set at 256·512·4 B = 512 KiB ≪ VMEM while giving 8·128-aligned lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.bitmap import popcount_u32 as popcount


def _kernel(items_ref, tid_ref, out_ref):
    w = pl.program_id(1)

    @pl.when(w == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    tile = items_ref[...] & tid_ref[...]            # [BI, BW] & [1, BW]
    partial = popcount(tile).sum(axis=1, keepdims=True)  # [BI, 1]
    out_ref[...] += partial


@functools.partial(jax.jit, static_argnames=("block_i", "block_w", "interpret"))
def extension_supports_pallas(
    item_bits: jnp.ndarray,   # uint32[I, W]
    prefix_tid: jnp.ndarray,  # uint32[W]
    *,
    block_i: int = 256,
    block_w: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """int32[I] supports of prefix ∪ {i}; pads I and W to tile multiples."""
    I, W = item_bits.shape
    bi = min(block_i, max(8, I))
    bw = min(block_w, max(128, W))
    pi = (-I) % bi
    pw = (-W) % bw
    items = jnp.pad(item_bits, ((0, pi), (0, pw)))
    tid = jnp.pad(prefix_tid, (0, pw))[None, :]      # [1, Wp]
    Ip, Wp = items.shape

    out = pl.pallas_call(
        _kernel,
        grid=(Ip // bi, Wp // bw),
        in_specs=[
            pl.BlockSpec((bi, bw), lambda i, w: (i, w)),
            pl.BlockSpec((1, bw), lambda i, w: (0, w)),
        ],
        out_specs=pl.BlockSpec((bi, 1), lambda i, w: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Ip, 1), jnp.int32),
        interpret=interpret,
    )(items, tid)
    return out[:I, 0]

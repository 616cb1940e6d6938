"""Pallas TPU kernel: fused per-block itemset-containment supports.

The streaming hot spot (`repro.stream`): when a transaction block enters the
sliding window and another expires, every mined itemset's window support
changes by

  ``Δ[f] = |{t ∈ arrive : f ⊆ t}| − |{t ∈ expire : f ⊆ t}|``

so the serving table is *updated in place* instead of recomputed over the
whole window.  The kernel computes the general form — S stacked transaction
blocks against all F itemset masks in ONE launch,

  ``counts[s, f] = Σ_t [ fi[f] ⊆ tx[s, t] ]``

(S = 2 for the arrive/expire pair).  Containment over packed little-endian
uint32 masks (layout of ``core.bitmap.pack_bool``) is a zero test on the
set-difference popcount, the same SWAR sweep as ``multi_support.py`` /
``subset_query.py``:

  ``f ⊆ t  ⇔  Σ_w popcount(fi[f, w] & ~tx[t, w]) == 0``

Unlike those kernels the reduced word axis must be *fully resident* per grid
step (the zero test needs the complete count before thresholding), which is
free here: the item-word axis IW = n_words(n_items) is a few words.  The
grid is ``(S, F/BF, T/BT)`` with T minormost (sequential on TPU) so the
``[1, 1, BF]`` int32 accumulator lives in its output block across T steps.

Row-padding trick: T and F pad to tile multiples, and a padded all-zero
transaction row would falsely "contain" the empty itemset.  The wrapper
appends one **sentinel word** set to 1 on every itemset row and every *real*
transaction row but left 0 on padding — padded rows therefore miss the
sentinel bit and can never count, making the kernel exact for every mask
(∅ included) without a separate validity operand.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.bitmap import popcount_u32 as popcount

_U32 = jnp.uint32


def _kernel(tx_ref, fi_ref, out_ref):
    t_step = pl.program_id(2)

    @pl.when(t_step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    tx = tx_ref[0]                                  # [BT, W]
    fi = fi_ref[...]                                # [BF, W]
    missing = fi[None, :, :] & ~tx[:, None, :]      # [BT, BF, W]
    miss_ct = popcount(missing).sum(axis=-1)  # [BT, BF]
    contained = (miss_ct == 0).astype(jnp.int32)
    out_ref[...] += contained.sum(axis=0)[None, None, :]


@functools.partial(
    jax.jit, static_argnames=("block_f", "block_t", "interpret")
)
def block_itemset_supports_pallas(
    tx_blocks: jnp.ndarray,  # uint32[S, T, IW] — horizontal packed rows
    fi_masks: jnp.ndarray,   # uint32[F, IW]    — packed itemset masks
    *,
    block_f: int = 128,
    block_t: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """int32[S, F] — per-block containment counts of every itemset.

    Pads T and F to tile multiples and the word axis to a multiple of 8
    (one extra sentinel word, see module docstring).  VMEM per step ≈
    BT·BF·Wp·4 B for the widened ANDN (512 KiB at defaults with Wp = 8).
    """
    S, T, IW = tx_blocks.shape
    F = fi_masks.shape[0]
    assert fi_masks.shape[1] == IW, "tx/itemset word width mismatch"
    bt = min(block_t, max(8, T))
    bf = min(block_f, max(8, F))
    Wp = -(-(IW + 1) // 8) * 8           # sentinel word, padded to 8
    pt, pf = (-T) % bt, (-F) % bf

    tx = jnp.zeros((S, T + pt, Wp), _U32)
    tx = tx.at[:, :T, :IW].set(tx_blocks)
    tx = tx.at[:, :T, IW].set(_U32(1))   # sentinel: real transaction rows
    fi = jnp.zeros((F + pf, Wp), _U32)
    fi = fi.at[:F, :IW].set(fi_masks)
    fi = fi.at[:F, IW].set(_U32(1))      # sentinel: every itemset row
    Tp, Fp = T + pt, F + pf

    out = pl.pallas_call(
        _kernel,
        grid=(S, Fp // bf, Tp // bt),
        in_specs=[
            pl.BlockSpec((1, bt, Wp), lambda s, f, t: (s, t, 0)),
            pl.BlockSpec((bf, Wp), lambda s, f, t: (f, 0)),
        ],
        # S rides a leading axis: a (1, BF) block over S rows would break
        # the sublane tiling, a (1, 1, BF) block over [S, 1, Fp] does not
        out_specs=pl.BlockSpec((1, 1, bf), lambda s, f, t: (s, 0, f)),
        out_shape=jax.ShapeDtypeStruct((S, 1, Fp), jnp.int32),
        interpret=interpret,
    )(tx, fi)
    return out[:, 0, :F]

"""Pallas TPU kernel: batched subset/superset queries over packed itemsets.

The serving hot spot (`repro.serve.engine`): Q query masks against the F
itemset masks of the FI/rule index, all pairs, one fused sweep.  For packed
little-endian uint32 masks (layout of ``core.bitmap.pack_bool``) the kernel
computes the two **set-difference popcount** matrices

  ``miss[q, f]  = Σ_w popcount(fi[f, w]    & ~query[q, w])``   (= |f ∖ q|)
  ``extra[q, f] = Σ_w popcount(query[q, w] & ~fi[f, w])``      (= |q ∖ f|)

from one pass over both operands.  Membership is a comparison on top:

  ``miss == 0``   ⇔  f ⊆ q   (rule antecedent applies to basket q)
  ``extra == 0``  ⇔  q ⊆ f   (f is a superset of the queried itemset)
  both zero      ⇔  f = q   (exact support lookup)

Returning counts instead of booleans costs nothing (the AND/ANDN + SWAR
popcount dominates) and buys ranking signals: |f ∖ q| is "items missing from
the basket", |q ∖ f| is "extra items beyond the query" — the tie-breakers
the top-K superset query uses.

Grid ``(Q/BQ, F/BF, W/BW)`` with W minormost (sequential on TPU) so both
int32 accumulators live in their output blocks across W steps — the pattern
of ``multi_support.py``/``pair_support.py``.  Unlike those kernels the
reduced axis here is the *item-word* axis (IW = n_words(n_items), a few
words), not the transaction-word axis, so by default the word block is the
whole of IW (padded to a lane multiple past 128 words) and W is a single
step; Q and F carry the parallelism.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.bitmap import popcount_u32 as popcount


def _kernel(query_ref, fi_ref, miss_ref, extra_ref):
    w = pl.program_id(2)

    @pl.when(w == 0)
    def _init():
        miss_ref[...] = jnp.zeros_like(miss_ref)
        extra_ref[...] = jnp.zeros_like(extra_ref)

    q = query_ref[...]                              # [BQ, BW]
    f = fi_ref[...]                                 # [BF, BW]
    only_f = f[None, :, :] & ~q[:, None, :]         # [BQ, BF, BW]
    only_q = q[:, None, :] & ~f[None, :, :]
    miss_ref[...] += popcount(only_f).sum(axis=-1)
    extra_ref[...] += popcount(only_q).sum(axis=-1)


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_f", "block_w", "interpret")
)
def subset_superset_counts_pallas(
    query_masks: jnp.ndarray,  # uint32[Q, IW]
    fi_masks: jnp.ndarray,     # uint32[F, IW]
    *,
    block_q: int = 128,
    block_f: int = 128,
    block_w: int | None = None,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(miss, extra)`` int32[Q, F] set-difference popcount matrices.

    Pads Q, F, W to tile multiples (zero words change no counts; padded
    rows are sliced off).  ``block_w=None`` takes the whole word axis.
    VMEM per step ≈ 2·BQ·BF·BW·4 B for the widened ANDNs.
    """
    Q, W = query_masks.shape
    F = fi_masks.shape[0]
    assert fi_masks.shape[1] == W, "query/index word width mismatch"
    bq = min(block_q, max(8, Q))
    bf = min(block_f, max(8, F))
    bw = min(block_w, W) if block_w else (W if W <= 128 else -(-W // 128) * 128)
    pq, pf, pw = (-Q) % bq, (-F) % bf, (-W) % bw
    q = jnp.pad(query_masks, ((0, pq), (0, pw)))
    f = jnp.pad(fi_masks, ((0, pf), (0, pw)))
    Qp, Wp = q.shape
    Fp = f.shape[0]

    miss, extra = pl.pallas_call(
        _kernel,
        grid=(Qp // bq, Fp // bf, Wp // bw),
        in_specs=[
            pl.BlockSpec((bq, bw), lambda i, j, w: (i, w)),
            pl.BlockSpec((bf, bw), lambda i, j, w: (j, w)),
        ],
        out_specs=[
            pl.BlockSpec((bq, bf), lambda i, j, w: (i, j)),
            pl.BlockSpec((bq, bf), lambda i, j, w: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Qp, Fp), jnp.int32),
            jax.ShapeDtypeStruct((Qp, Fp), jnp.int32),
        ],
        interpret=interpret,
    )(q, f)
    return miss[:Q, :F], extra[:Q, :F]

"""Packed bitmap representation of a transaction database.

The thesis (Ch. 2, B.3) stores the *vertical representation* of the database as
per-item tidlists (sorted integer arrays) and computes support by tidlist
merge-intersection.  On TPU we replace tidlists with **packed bitmaps**:

  * vertical:   ``item_bits[i, w]``  — bit ``t`` of word ``w`` set iff transaction
                ``32*w + t`` contains item ``i``;  shape ``[n_items, n_words]``.
  * horizontal: ``tx_bits[t, w]``    — bit ``i`` of word ``w`` set iff transaction
                ``t`` contains item ``32*w + i``;  shape ``[n_tx, n_item_words]``.

Support of an itemset U is ``popcount(AND_{i in U} item_bits[i])`` (Lemma 2.28).
AND + popcount is branch-free, lane-parallel, and batches over candidate
extensions into a dense 2-D sweep — the natural TPU shape (see DESIGN.md,
"Hardware adaptation").

Everything here is pure jnp and jit-friendly; the Pallas kernels in
``repro.kernels`` accelerate the two hot spots (extension supports and all-pairs
supports) with the functions here as oracles.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

WORD_BITS = 32
_U32 = jnp.uint32


def n_words(n: int) -> int:
    """Number of 32-bit words needed for ``n`` bits."""
    return (n + WORD_BITS - 1) // WORD_BITS


def popcount_u32(x: jnp.ndarray) -> jnp.ndarray:
    """SWAR population count of a uint32 array (elementwise, returns int32).

    Classic bit-twiddling reduction; identical code runs inside Pallas kernels.
    """
    x = x.astype(_U32)
    x = x - ((x >> 1) & _U32(0x55555555))
    x = (x & _U32(0x33333333)) + ((x >> 2) & _U32(0x33333333))
    x = (x + (x >> 4)) & _U32(0x0F0F0F0F)
    return ((x * _U32(0x01010101)) >> 24).astype(jnp.int32)


def pack_bool(dense: jnp.ndarray) -> jnp.ndarray:
    """Pack a boolean array ``[..., n]`` into uint32 words ``[..., n_words(n)]``.

    Bit ``k`` of word ``w`` corresponds to column ``32*w + k`` (little-endian
    within the word).
    """
    n = dense.shape[-1]
    W = n_words(n)
    pad = W * WORD_BITS - n
    if pad:
        dense = jnp.concatenate(
            [dense, jnp.zeros(dense.shape[:-1] + (pad,), dense.dtype)], axis=-1
        )
    bits = dense.reshape(dense.shape[:-1] + (W, WORD_BITS)).astype(_U32)
    shifts = jnp.arange(WORD_BITS, dtype=_U32)
    return (bits << shifts).sum(axis=-1, dtype=_U32)


def unpack_bool(packed: jnp.ndarray, n: int) -> jnp.ndarray:
    """Inverse of :func:`pack_bool`; returns bool array ``[..., n]``."""
    shifts = jnp.arange(WORD_BITS, dtype=_U32)
    bits = (packed[..., None] >> shifts) & _U32(1)
    flat = bits.reshape(packed.shape[:-1] + (packed.shape[-1] * WORD_BITS,))
    return flat[..., :n].astype(jnp.bool_)


#: Hacker's Delight §7-3: the shift and mask of each swap of a 32×32 bit
#: transpose, widest blocks first.
_TRANSPOSE_STEPS = ((16, 0x0000FFFF), (8, 0x00FF00FF), (4, 0x0F0F0F0F),
                    (2, 0x33333333), (1, 0x55555555))


def transpose_bits(packed: jnp.ndarray, n: int) -> jnp.ndarray:
    """Bit-matrix transpose of packed rows ``uint32[..., R, C]`` into the
    first ``n`` columns as packed rows ``uint32[..., n, n_words(R)]``; equal
    to ``pack_bool(swapaxes(unpack_bool(packed, n), -1, -2))``.

    Each 32×32 bit block is transposed in its own words by five masked
    swaps, so nothing wider than the input is built (unpacking first holds
    32× the input as ``uint32``)."""
    *lead, R, C = packed.shape
    nb = n_words(R)
    x = jnp.pad(packed.astype(_U32),
                [(0, 0)] * len(lead) + [(0, nb * WORD_BITS - R), (0, 0)])
    # [..., 32 (row within block), C, nb]: the long block axis stays minor
    x = jnp.moveaxis(x.reshape(*lead, nb, WORD_BITS, C), -3, -1)
    for s, m in _TRANSPOSE_STEPS:
        x = x.reshape(*lead, WORD_BITS // (2 * s), 2, s, C, nb)
        lo, hi = x[..., 0, :, :, :], x[..., 1, :, :, :]
        t = ((lo >> s) ^ hi) & _U32(m)
        x = jnp.stack([lo ^ (t << s), hi ^ t], axis=-4)
        x = x.reshape(*lead, WORD_BITS, C, nb)
    # x[..., j, c, b]: column 32c + j over rows 32b .. 32b + 31
    x = jnp.swapaxes(x, -3, -2).reshape(*lead, C * WORD_BITS, nb)
    return x[..., :n, :]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class BitmapDB:
    """A transaction database in packed vertical + horizontal bitmap form.

    Attributes:
      item_bits: ``uint32[n_items, n_tx_words]`` vertical representation.
      tx_bits:   ``uint32[n_tx, n_item_words]`` horizontal representation.
      n_tx:      number of (valid) transactions.  Static python int.
      n_items:   size of the base set B.  Static python int.
    """

    item_bits: jnp.ndarray
    tx_bits: jnp.ndarray
    n_tx: int
    n_items: int

    # -- pytree plumbing (n_tx / n_items are static aux data) ----------------
    def tree_flatten(self):
        return (self.item_bits, self.tx_bits), (self.n_tx, self.n_items)

    @classmethod
    def tree_unflatten(cls, aux, children):
        item_bits, tx_bits = children
        return cls(item_bits, tx_bits, aux[0], aux[1])

    # -- constructors ---------------------------------------------------------
    @classmethod
    def from_dense(cls, dense: jnp.ndarray) -> "BitmapDB":
        """Build from a dense bool matrix ``[n_tx, n_items]``."""
        dense = jnp.asarray(dense, jnp.bool_)
        n_tx, n_items = dense.shape
        return cls(
            item_bits=pack_bool(dense.T),
            tx_bits=pack_bool(dense),
            n_tx=n_tx,
            n_items=n_items,
        )

    @classmethod
    def from_transactions(cls, transactions, n_items: int) -> "BitmapDB":
        """Build from a python list of iterables of item ids."""
        dense = np.zeros((len(transactions), n_items), dtype=bool)
        for t, items in enumerate(transactions):
            for i in items:
                dense[t, int(i)] = True
        return cls.from_dense(jnp.asarray(dense))

    # -- views ----------------------------------------------------------------
    def dense(self) -> jnp.ndarray:
        """Dense bool ``[n_tx, n_items]``."""
        return unpack_bool(self.tx_bits, self.n_items)

    @property
    def n_tx_words(self) -> int:
        return self.item_bits.shape[-1]

    @property
    def n_item_words(self) -> int:
        return self.tx_bits.shape[-1]

    def all_tids(self) -> jnp.ndarray:
        """Bitmap of all valid transaction ids: tidlist of the empty itemset."""
        full = jnp.full((self.n_tx_words,), jnp.iinfo(np.uint32).max, _U32)
        # mask the tail bits beyond n_tx
        tail_bits = self.n_tx_words * WORD_BITS - self.n_tx
        if tail_bits:
            last = _U32(0xFFFFFFFF) >> np.uint32(tail_bits)
            full = full.at[-1].set(last)
        return full


# ---------------------------------------------------------------------------
# Support counting (Lemma 2.28 / Corollary 2.29), pure-jnp reference forms.
# ---------------------------------------------------------------------------


def tidlist_of_itemset(db: BitmapDB, itemset_mask: jnp.ndarray) -> jnp.ndarray:
    """Tidlist bitmap ``uint32[W]`` of an itemset given as a bool mask ``[n_items]``.

    T(U) = AND over item bitmaps of members (all-ones for the empty set).
    """
    member = itemset_mask[:, None]  # [I, 1]
    # For non-members substitute all-ones so they don't constrain the AND.
    rows = jnp.where(member, db.item_bits, _U32(0xFFFFFFFF))
    # AND-reduce over items via ufunc reduce on the item axis.
    tid = jax.lax.reduce(
        rows, _U32(0xFFFFFFFF), lambda a, b: jnp.bitwise_and(a, b), (0,)
    )
    return jnp.bitwise_and(tid, db.all_tids())


def support_of_tidlist(tid: jnp.ndarray) -> jnp.ndarray:
    """Support (int32 scalar) = popcount of a tidlist bitmap."""
    return popcount_u32(tid).sum()


def support_of_itemset(db: BitmapDB, itemset_mask: jnp.ndarray) -> jnp.ndarray:
    return support_of_tidlist(tidlist_of_itemset(db, itemset_mask))


def extension_supports(
    item_bits: jnp.ndarray, prefix_tid: jnp.ndarray
) -> jnp.ndarray:
    """Supports of ``prefix ∪ {i}`` for every item i.

    Args:
      item_bits: ``uint32[I, W]`` vertical bitmaps.
      prefix_tid: ``uint32[W]`` tidlist of the prefix.
    Returns:
      ``int32[I]`` supports.  This is the Eclat inner loop — the Pallas kernel
      ``repro.kernels.bitmap_support`` computes exactly this.
    """
    return popcount_u32(item_bits & prefix_tid[None, :]).sum(axis=-1)


def multi_extension_supports(
    item_bits: jnp.ndarray, prefix_tids: jnp.ndarray
) -> jnp.ndarray:
    """Supports of ``prefix_k ∪ {i}`` for K prefixes at once.

    The frontier-batched Eclat inner loop (DESIGN.md, "Frontier-batched DFS"):
    one fused AND+popcount sweep over K prefix tidlists instead of K separate
    ``extension_supports`` launches.

    Args:
      item_bits: ``uint32[I, W]`` vertical bitmaps.
      prefix_tids: ``uint32[K, W]`` tidlists of the K frontier prefixes.
    Returns:
      ``int32[K, I]`` supports.  Oracle of the Pallas kernels in
      ``repro.kernels.multi_support``.
    """
    inter = prefix_tids[:, None, :] & item_bits[None, :, :]   # [K, I, W]
    return popcount_u32(inter).sum(axis=-1)


def pair_supports(item_bits: jnp.ndarray, valid_tid: jnp.ndarray) -> jnp.ndarray:
    """All-pairs supports ``int32[I, I]``: support({i, j}).

    The C2 counting step of Parallel-Eclat (Alg. 5 line 3).  AND/popcount
    "semiring matmul"; Pallas kernel ``repro.kernels.pair_support`` mirrors it.
    """
    masked = item_bits & valid_tid[None, :]
    return popcount_u32(masked[:, None, :] & masked[None, :, :]).sum(axis=-1)


def itemset_mask_to_packed(mask: jnp.ndarray) -> jnp.ndarray:
    """Pack a bool itemset mask ``[..., I]`` into uint32 ``[..., n_words(I)]``."""
    return pack_bool(mask)


def is_subset_packed(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Elementwise test a ⊆ b for packed itemset masks (last axis = words)."""
    return jnp.all((a & ~b) == _U32(0), axis=-1)


@partial(jax.jit, static_argnames=("n_sample", "n_tx"))
def sample_transactions(
    tx_bits: jnp.ndarray, key: jax.Array, n_sample: int, n_tx: int
) -> jnp.ndarray:
    """i.i.d. (with replacement) sample of transaction rows — Phase-1 DB sample.

    Thesis §6.1: the database sample is drawn **with replacement**, so the
    Chernoff analysis (Thm 6.1) applies without finite-population corrections.
    """
    idx = jax.random.randint(key, (n_sample,), 0, n_tx)
    return jnp.take(tx_bits, idx, axis=0)


def rebuild_vertical(tx_bits: jnp.ndarray, n_items: int, n_tx: int) -> BitmapDB:
    """Re-pack a horizontal bitmap slab into a full BitmapDB (host+device ok)."""
    dense = unpack_bool(tx_bits, n_items)[:n_tx]
    return BitmapDB.from_dense(dense)

"""Pure-jnp oracles for the Pallas kernels (the contract every kernel meets).

These are thin named wrappers over ``repro.core.bitmap`` reference forms so the
kernel tests have a single import point, plus the unpacked-MXU reference.
The two all-pairs sweeps are jitted: run op by op they would hold their
``[..., F, IW]`` broadcast in device memory (gigabytes at serving and
streaming widths); fused, only the counts are.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import bitmap as bm


def extension_supports_ref(item_bits: jnp.ndarray, prefix_tid: jnp.ndarray) -> jnp.ndarray:
    """int32[I] = popcount(item_bits[i] & prefix_tid) summed over words."""
    return bm.extension_supports(item_bits, prefix_tid)


def multi_extension_supports_ref(
    item_bits: jnp.ndarray, prefix_tids: jnp.ndarray
) -> jnp.ndarray:
    """int32[K, I] = popcount(item_bits[i] & prefix_tids[k]) summed over words."""
    return bm.multi_extension_supports(item_bits, prefix_tids)


def pair_supports_ref(item_bits: jnp.ndarray, valid_tid: jnp.ndarray) -> jnp.ndarray:
    """int32[I, I] all-pairs supports via VPU-style popcount(AND)."""
    return bm.pair_supports(item_bits, valid_tid)


def unpack_bits_f32(words: jnp.ndarray) -> jnp.ndarray:
    """uint32[..., W] -> float32[..., W*32] of 0/1 — the MXU-form operand."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)
    return bits.reshape(words.shape[:-1] + (words.shape[-1] * 32,)).astype(jnp.float32)


def pair_supports_mxu_ref(item_bits: jnp.ndarray, valid_tid: jnp.ndarray) -> jnp.ndarray:
    """All-pairs supports as a matmul over unpacked bits (exact in f32 for
    supports < 2^24).  Oracle of the fused unpack+dot Pallas kernel."""
    masked = unpack_bits_f32(item_bits & valid_tid[None, :])
    return jnp.dot(masked, masked.T).astype(jnp.int32)


@jax.jit
def subset_superset_counts_ref(
    query_masks: jnp.ndarray, fi_masks: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``(miss, extra)`` int32[Q, F]: |f ∖ q| and |q ∖ f| per (query, FI) pair.

    ``miss == 0`` ⇔ f ⊆ q;  ``extra == 0`` ⇔ q ⊆ f;  both ⇔ f = q.
    Oracle of the fused serving kernel ``kernels.subset_query``.
    """
    only_f = fi_masks[None, :, :] & ~query_masks[:, None, :]   # [Q, F, IW]
    only_q = query_masks[:, None, :] & ~fi_masks[None, :, :]
    return (
        bm.popcount_u32(only_f).sum(axis=-1),
        bm.popcount_u32(only_q).sum(axis=-1),
    )


@jax.jit
def block_itemset_supports_ref(
    tx_blocks: jnp.ndarray, fi_masks: jnp.ndarray
) -> jnp.ndarray:
    """int32[S, F]: per transaction block, how many rows contain each itemset.

    ``counts[s, f] = Σ_t [fi_masks[f] ⊆ tx_blocks[s, t]]`` — containment is a
    zero test on the set-difference popcount (``subset_query`` semantics).
    Oracle of the fused streaming delta kernel ``kernels.delta_support``.
    """
    missing = fi_masks[None, None, :, :] & ~tx_blocks[:, :, None, :]
    contained = bm.popcount_u32(missing).sum(axis=-1) == 0      # [S, T, F]
    return contained.sum(axis=1).astype(jnp.int32)


def multi_extension_supports_mxu_ref(
    item_bits: jnp.ndarray, prefix_tids: jnp.ndarray
) -> jnp.ndarray:
    """Multi-prefix supports as a matmul over unpacked bits — oracle of the
    fused unpack+dot multi-prefix Pallas kernel."""
    t = unpack_bits_f32(prefix_tids)
    a = unpack_bits_f32(item_bits)
    return jnp.dot(t, a.T).astype(jnp.int32)

"""Public jit'd entry points for the mining kernels with backend dispatch.

On TPU the Pallas kernels run compiled; on CPU (this container) the pure-jnp
reference path is used for speed, with ``interpret=True`` Pallas execution
available everywhere for validation (exercised by the kernel tests).

``support_fns`` gives the Eclat/MFI miners their ``support_fn`` and
``multi_support_fn`` plug-ins; ``fimi.run`` and the cluster executor both
mine through it.

Every dispatch is wrapped by the kernel profiler
(:mod:`repro.obs.profile`): when enabled, eager calls get device-synced
per-call timing bucketed by shape, and trace-time dispatches (kernels
compiled into ``while_loop`` bodies) are tallied for later loop
attribution.  When disabled — the default — the wrapper is one attribute
check and a plain tail call (gated <2 % overhead in
``tests/test_profile.py``).
"""
from __future__ import annotations

import functools
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import bitmap_support as _bs
from repro.kernels import delta_support as _ds
from repro.kernels import multi_support as _ms
from repro.kernels import pair_support as _ps
from repro.kernels import ref as _ref
from repro.kernels import subset_query as _sq
from repro.obs import profile as _prof


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.lru_cache(maxsize=None)
def support_fns(force: str | None = None, use_mxu: bool = False):
    """``(support_fn, multi_support_fn)`` miner plug-ins pinned to ``force``.

    The miners take these as static jit arguments; caching gives each
    ``(force, use_mxu)`` one stable identity, so repeated runs reuse their
    compiled executables.
    """
    return (
        partial(extension_supports, force=force),
        partial(multi_extension_supports, use_mxu=use_mxu, force=force),
    )


def _profiled(family, dims_fn):
    """Route a dispatch through the kernel profiler when it is enabled."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if not _prof.PROFILER.enabled:
                return fn(*args, **kwargs)
            return _prof.PROFILER.call(
                family, dims_fn(*args), lambda: fn(*args, **kwargs)
            )

        return wrapper

    return deco


@_profiled(
    "bitmap",
    lambda item_bits, prefix_tid: {
        "I": int(item_bits.shape[0]), "W": int(item_bits.shape[1]),
    },
)
def extension_supports(
    item_bits: jnp.ndarray,
    prefix_tid: jnp.ndarray,
    *,
    force: str | None = None,
) -> jnp.ndarray:
    """Supports of prefix ∪ {i} for all items.  force ∈ {None,'pallas','ref',
    'interpret'} selects the implementation."""
    mode = force or ("pallas" if _on_tpu() else "ref")
    if mode == "pallas":
        return _bs.extension_supports_pallas(item_bits, prefix_tid)
    if mode == "interpret":
        return _bs.extension_supports_pallas(item_bits, prefix_tid, interpret=True)
    return _ref.extension_supports_ref(item_bits, prefix_tid)


@_profiled(
    "multi",
    lambda item_bits, prefix_tids: {
        "K": int(prefix_tids.shape[0]),
        "I": int(item_bits.shape[0]), "W": int(item_bits.shape[1]),
    },
)
def multi_extension_supports(
    item_bits: jnp.ndarray,
    prefix_tids: jnp.ndarray,
    *,
    use_mxu: bool = False,
    force: str | None = None,
) -> jnp.ndarray:
    """Supports of prefix_k ∪ {i} for K prefixes: int32[K, I].

    The frontier-batched Eclat plug-in (``multi_support_fn``).  ``use_mxu``
    picks the unpack+dot kernel (wins once K fills MXU rows); force ∈
    {None, 'pallas', 'ref', 'interpret'} selects the implementation.
    """
    mode = force or ("pallas" if _on_tpu() else "ref")
    if mode in ("pallas", "interpret"):
        f = (
            _ms.multi_extension_supports_mxu_pallas
            if use_mxu
            else _ms.multi_extension_supports_pallas
        )
        return f(item_bits, prefix_tids, interpret=(mode == "interpret"))
    if use_mxu:
        return _ref.multi_extension_supports_mxu_ref(item_bits, prefix_tids)
    return _ref.multi_extension_supports_ref(item_bits, prefix_tids)


@_profiled(
    "subset",
    lambda query_masks, fi_masks: {
        "Q": int(query_masks.shape[0]),
        "F": int(fi_masks.shape[0]), "IW": int(fi_masks.shape[1]),
    },
)
def subset_superset_counts(
    query_masks: jnp.ndarray,
    fi_masks: jnp.ndarray,
    *,
    force: str | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``(miss, extra)`` int32[Q, F] set-difference popcounts (|f∖q|, |q∖f|).

    The batched serving sweep (``repro.serve.engine``); force ∈ {None,
    'pallas', 'ref', 'interpret'} selects the implementation.
    """
    mode = force or ("pallas" if _on_tpu() else "ref")
    if mode in ("pallas", "interpret"):
        return _sq.subset_superset_counts_pallas(
            query_masks, fi_masks, interpret=(mode == "interpret")
        )
    return _ref.subset_superset_counts_ref(query_masks, fi_masks)


@_profiled(
    "delta",
    lambda tx_blocks, fi_masks: {
        "S": int(tx_blocks.shape[0]), "T": int(tx_blocks.shape[1]),
        "F": int(fi_masks.shape[0]), "IW": int(fi_masks.shape[1]),
    },
)
def block_itemset_supports(
    tx_blocks: jnp.ndarray,
    fi_masks: jnp.ndarray,
    *,
    force: str | None = None,
) -> jnp.ndarray:
    """int32[S, F] per-block containment counts of every itemset.

    The streaming update sweep (``repro.stream``): S stacked transaction
    blocks ``uint32[S, T, IW]`` against F packed itemset masks in one fused
    launch; force ∈ {None, 'pallas', 'ref', 'interpret'} selects the
    implementation.
    """
    mode = force or ("pallas" if _on_tpu() else "ref")
    if mode in ("pallas", "interpret"):
        return _ds.block_itemset_supports_pallas(
            tx_blocks, fi_masks, interpret=(mode == "interpret")
        )
    return _ref.block_itemset_supports_ref(tx_blocks, fi_masks)


def delta_supports(
    arrive: jnp.ndarray,   # uint32[T, IW] — admitted transaction block
    expire: jnp.ndarray,   # uint32[T, IW] — evicted transaction block
    fi_masks: jnp.ndarray,  # uint32[F, IW]
    *,
    force: str | None = None,
) -> jnp.ndarray:
    """int32[2, F] — (arrive counts, expire counts) from ONE fused sweep.

    The window support update is ``supports += counts[0] - counts[1]``;
    keeping the two contributions separate lets callers also track ingress
    rates.  Both blocks ride the S axis of :func:`block_itemset_supports`,
    so the itemset slab streams from HBM once for the pair.
    """
    return block_itemset_supports(
        jnp.stack([arrive, expire]), fi_masks, force=force
    )


@_profiled(
    "pair",
    lambda item_bits, valid_tid: {
        "I": int(item_bits.shape[0]), "W": int(item_bits.shape[1]),
    },
)
def pair_supports(
    item_bits: jnp.ndarray,
    valid_tid: jnp.ndarray,
    *,
    use_mxu: bool = True,
    force: str | None = None,
) -> jnp.ndarray:
    """All-pairs supports S[i,j].  ``use_mxu`` picks the unpack+dot kernel."""
    mode = force or ("pallas" if _on_tpu() else "ref")
    if mode == "pallas":
        f = _ps.pair_supports_mxu_pallas if use_mxu else _ps.pair_supports_pallas
        return f(item_bits, valid_tid)
    if mode == "interpret":
        f = _ps.pair_supports_mxu_pallas if use_mxu else _ps.pair_supports_pallas
        return f(item_bits, valid_tid, interpret=True)
    if use_mxu:
        return _ref.pair_supports_mxu_ref(item_bits, valid_tid)
    return _ref.pair_supports_ref(item_bits, valid_tid)

"""Eclat in JAX: depth-first FI mining over packed-bitmap tidlists.

This is the TPU-native re-expression of the thesis' Eclat (§B.3, Alg. 34/35)
used as the Phase-4 sequential miner and (on the database sample) as the
Phase-1 FI enumerator feeding the reservoir sampler.

Adaptation (see DESIGN.md):
  * recursion → ``lax.while_loop`` over a fixed-capacity explicit stack;
  * **frontier batching**: each loop trip pops up to ``frontier_size`` (K)
    nodes — the top of the stack — and computes all their extension supports
    in ONE fused ``[K, I]`` AND+popcount sweep (``multi_extension_supports``,
    replaceable by the Pallas kernels in ``repro.kernels.multi_support``);
    surviving children of the whole frontier are pushed back with a single
    vectorized scatter.  K=1 reproduces the classic one-node-per-trip DFS
    exactly and serves as the parity oracle;
  * the stack holds no tidlist per node: a node is (its parent's row in a
    tidlist arena, its last item), and ``T(node) = arena[row] & T(item)``
    is rebuilt at pop.  A trip writes one ``[K, W]`` block of the pushing
    nodes' tidlists, never a ``[K, I, W]`` block of children;
  * dynamic item re-ordering by support (§B.4.2) is kept: each node sorts its
    frequent extensions ascending by support before splitting into child
    PBECs (Prop. 2.23 keeps the classes disjoint for *any* per-node order);
  * the (optional) reservoir sampler runs *inside* the mining loop: the FI
    stream never leaves the device (Alg. 9 / Vitter, §6.2.2).  Each trip
    compacts its emitted itemsets into an index list and takes one
    sequential Algorithm-R step per offered itemset, not one per slot of
    the ``[K, I]`` frontier.

All shapes are static; overflow of the stack or output buffer is counted and
reported, never silently dropped.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import bitmap as bm

_U32 = jnp.uint32


@dataclasses.dataclass(frozen=True)
class EclatConfig:
    """Static configuration of the DFS miner."""

    max_out: int = 4096          # capacity of the FI output buffer
    max_stack: int = 1024        # DFS stack capacity
    max_iters: int = 1 << 20     # hard bound on loop trips (≥ |F|+1 at K=1)
    reservoir_size: int = 0      # >0 enables the in-loop reservoir sampler
    count_only: bool = False     # skip writing the FI buffer (Phase-1 f_i count)
    frontier_size: int = 1       # K — DFS nodes mined per while_loop trip


class EclatResult(NamedTuple):
    """Mining result; buffers are only valid up to their counts."""

    items: jnp.ndarray       # uint32[max_out, IW] packed itemset masks
    supports: jnp.ndarray    # int32[max_out]
    n_out: jnp.ndarray       # int32 — number of FIs written (≤ max_out)
    n_total: jnp.ndarray     # int32 — number of FIs *found* (may exceed max_out)
    stack_overflow: jnp.ndarray  # int32 — dropped pushes (0 ⇒ complete result)
    reservoir_items: jnp.ndarray     # uint32[R, IW]
    reservoir_supports: jnp.ndarray  # int32[R]
    n_iters: jnp.ndarray     # int32 — loop trips executed
    n_popped: jnp.ndarray    # int32 — DFS nodes mined; /(n_iters·K) =
    #                          frontier occupancy (the batching efficiency)
    n_pushed: jnp.ndarray    # int32 — children pushed onto the stack (kept
    #                          pushes; dropped ones are ``stack_overflow``)


class _State(NamedTuple):
    sp: jnp.ndarray
    stk_items: jnp.ndarray   # uint32[S, IW]
    stk_ext: jnp.ndarray     # uint32[S, IW]
    stk_ref: jnp.ndarray     # int32[S] — arena row of the node's parent
    stk_item: jnp.ndarray    # int32[S] — node's last item (I: none, a seed)
    arena: jnp.ndarray       # uint32[S + F, W] — parent tidlists (seeds: own)
    out_items: jnp.ndarray
    out_supp: jnp.ndarray
    n_out: jnp.ndarray
    n_total: jnp.ndarray
    overflow: jnp.ndarray
    res_items: jnp.ndarray
    res_supp: jnp.ndarray
    res_seen: jnp.ndarray    # t in Algorithm R
    key: jax.Array
    it: jnp.ndarray
    popped: jnp.ndarray      # DFS nodes popped over all trips
    pushed: jnp.ndarray      # children pushed (kept) over all trips


#: single-prefix support plug-in: (item_bits[I, W], tid[W]) -> int32[I]
SupportFn = Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]
#: multi-prefix support plug-in: (item_bits[I, W], tids[K, W]) -> int32[K, I]
MultiSupportFn = Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]


def _lift_support_fn(support_fn: SupportFn) -> MultiSupportFn:
    """vmap a single-prefix support fn over the frontier axis."""

    def multi(item_bits, prefix_tids):
        return jax.vmap(lambda t: support_fn(item_bits, t))(prefix_tids)

    return multi


def _reservoir_update(state, node_items, e_packed, supports, emit, R):
    """Algorithm R over the itemsets this trip offers: one sequential step per
    offered itemset ``node_items[f] | {e}``, in ascending slot order f·I + e.

    The emitted slots are compacted into an index list first, so the loop
    runs ``n_emit`` steps (under ``vmap``, the most any miner offers) and
    rebuilds each offered row from its index; nothing of size F·I·IW is
    built.  One key split per offer, in slot order."""
    F, I = emit.shape
    with jax.named_scope("fimi/phase1/reservoir"):
        flat = emit.reshape(F * I)
        n_emit = flat.sum().astype(jnp.int32)
        pos = jnp.where(flat, jnp.cumsum(flat) - 1, F * I)   # ≥F·I ⇒ dropped
        slots = jnp.zeros((F * I,), jnp.int32).at[pos].set(
            jnp.arange(F * I, dtype=jnp.int32), mode="drop"
        )

        def body(k, carry):
            res_items, res_supp, seen, key = carry
            f, e = jnp.divmod(slots[k], I)
            seen = seen + 1
            key, sub = jax.random.split(key)
            j = jax.random.randint(sub, (), 0, seen)
            slot = jnp.where(seen <= R, seen - 1, j)
            take = (seen <= R) | (j < R)
            slot = jnp.where(take, slot, R)  # R = out-of-bounds ⇒ drop
            res_items = res_items.at[slot].set(node_items[f] | e_packed[e],
                                               mode="drop")
            res_supp = res_supp.at[slot].set(supports[f, e], mode="drop")
            return res_items, res_supp, seen, key

        return jax.lax.fori_loop(0, n_emit, body, state)


@partial(
    jax.jit,
    static_argnames=("config", "n_items", "support_fn", "multi_support_fn",
                     "scope"),
)
def mine_seeded(
    item_bits: jnp.ndarray,
    seed_prefix: jnp.ndarray,   # bool [K, I]
    seed_ext: jnp.ndarray,      # bool [K, I]
    seed_tid: jnp.ndarray,      # uint32 [K, W]
    seed_valid: jnp.ndarray,    # bool [K]
    min_support: jnp.ndarray,
    key: jax.Array,
    *,
    config: EclatConfig,
    n_items: int,
    support_fn: Optional[SupportFn] = None,
    multi_support_fn: Optional[MultiSupportFn] = None,
    scope: str = "eclat_loop",
) -> EclatResult:
    """Mine all FIs in the union of K PBECs ``[prefix_k | ext_k]``.

    This is `Exec-Eclat` (thesis Alg. 21): a processor's assigned classes are
    the DFS seeds; the `Prepare-Tidlists` branch simulation of Ch. 9 becomes
    "caller passes T(U_k)" (computed in one batched AND-reduce).  The prefixes
    U_k themselves are *not* emitted (Phase 4 handles prefix supports via the
    side channel, Alg. 19 line 2).

    Each loop trip mines a **frontier** of up to ``config.frontier_size``
    nodes: one fused multi-prefix support sweep, one vectorized child scatter.
    ``multi_support_fn`` (if given) computes the fused ``[F, I]`` supports;
    otherwise a provided single-prefix ``support_fn`` is vmapped over the
    frontier, falling back to the pure-jnp oracle.  The loop runs under
    ``jax.named_scope(scope)``, which names it in the profiler's op metadata.
    """
    if multi_support_fn is None:
        if support_fn is not None:
            multi_support_fn = _lift_support_fn(support_fn)
        else:
            multi_support_fn = bm.multi_extension_supports
    I = n_items
    IW = bm.n_words(I)
    W = item_bits.shape[-1]
    S, O, R = config.max_stack, config.max_out, max(config.reservoir_size, 1)
    K = seed_prefix.shape[0]
    assert K <= S, "seed count exceeds stack capacity"
    F = max(1, min(config.frontier_size, S))   # frontier width per trip

    # Compact valid seeds to the bottom of the stack.  The seed at stack
    # position k keeps its own tidlist in arena row k, under item I (none).
    seed_valid = seed_valid.astype(jnp.bool_)
    seed_order = jnp.argsort(~seed_valid, stable=True)
    n_seeds = seed_valid.sum().astype(jnp.int32)

    def bottom(rows, seeds):
        return rows.at[:K].set(seeds[seed_order])

    init = _State(
        sp=n_seeds,
        stk_items=bottom(jnp.zeros((S, IW), _U32),
                         bm.pack_bool(seed_prefix.astype(jnp.bool_))),
        stk_ext=bottom(jnp.zeros((S, IW), _U32),
                       bm.pack_bool(seed_ext.astype(jnp.bool_))),
        stk_ref=jnp.arange(S, dtype=jnp.int32),
        stk_item=jnp.full((S,), I, jnp.int32),
        arena=bottom(jnp.zeros((S + F, W), _U32), seed_tid),
        out_items=jnp.zeros((O, IW), _U32),
        out_supp=jnp.zeros((O,), jnp.int32),
        n_out=jnp.asarray(0, jnp.int32),
        n_total=jnp.asarray(0, jnp.int32),
        overflow=jnp.asarray(0, jnp.int32),
        res_items=jnp.zeros((R, IW), _U32),
        res_supp=jnp.zeros((R,), jnp.int32),
        res_seen=jnp.asarray(0, jnp.int32),
        key=key,
        it=jnp.asarray(0, jnp.int32),
        popped=jnp.asarray(0, jnp.int32),
        pushed=jnp.asarray(0, jnp.int32),
    )

    # Constant across iterations: packed one-hot masks of every item
    # (hoisted out of the loop body — built fresh every trip in the seed).
    e_packed = bm.pack_bool(jax.nn.one_hot(jnp.arange(I), I, dtype=jnp.bool_))

    def cond(s: _State):
        return (s.sp > 0) & (s.it < config.max_iters)

    def body(s: _State) -> _State:
        # --- pop a frontier: the top min(sp, F) stack nodes -----------------
        idx = s.sp - 1 - jnp.arange(F)        # [F] — top of stack first
        active = idx >= 0                      # [F]
        idx_c = jnp.maximum(idx, 0)
        node_items = s.stk_items[idx_c]        # uint32[F, IW]
        node_ext = s.stk_ext[idx_c]            # uint32[F, IW]
        # T(node) = T(parent) ∩ T(last item); item I (a seed) ANDs all ones.
        item = s.stk_item[idx_c]               # [F]
        item_row = jnp.where((item < I)[:, None],
                             item_bits[jnp.minimum(item, I - 1)], ~_U32(0))
        node_tid = s.arena[s.stk_ref[idx_c]] & item_row   # uint32[F, W]
        # Inactive lanes alias stack slot 0; masking their extension sets to ∅
        # makes them emit and push nothing.
        ext_bool = bm.unpack_bool(node_ext, I) & active[:, None]   # [F, I]

        # --- fused multi-prefix support counting (the Pallas hot spot) ------
        supports = multi_support_fn(item_bits, node_tid)     # int32[F, I]
        freq = ext_bool & (supports >= min_support)
        nf = freq.sum(axis=-1).astype(jnp.int32)             # [F]
        nf_total = nf.sum()

        # --- dynamic re-ordering: rank frequent extensions by support ------
        sort_key = jnp.where(freq, supports, jnp.iinfo(jnp.int32).max)
        order = jnp.argsort(sort_key, axis=-1)               # frequent first, asc
        rank = jnp.argsort(order, axis=-1)                   # rank per item
        # rank[f] < nf[f]  ⇔  item is a frequent extension of node f.

        # --- emit FIs: prefix_f ∪ {e} for each frequent e -------------------
        child_items = node_items[:, None, :] | e_packed[None, :, :]  # [F, I, IW]
        node_off = s.n_out + jnp.cumsum(nf) - nf             # exclusive prefix sum
        out_pos = jnp.where(freq, node_off[:, None] + rank, O)   # ≥O ⇒ dropped
        flat_pos = out_pos.reshape(F * I)
        flat_items = child_items.reshape(F * I, IW)
        flat_supp = supports.reshape(F * I)
        if not config.count_only:
            out_items = s.out_items.at[flat_pos].set(flat_items, mode="drop")
            out_supp = s.out_supp.at[flat_pos].set(flat_supp, mode="drop")
        else:
            out_items, out_supp = s.out_items, s.out_supp
        n_out = jnp.minimum(s.n_out + nf_total, O)
        n_total = s.n_total + nf_total

        # --- reservoir over the emitted stream ------------------------------
        if config.reservoir_size > 0:
            res_items, res_supp, res_seen, key = _reservoir_update(
                (s.res_items, s.res_supp, s.res_seen, s.key),
                node_items,
                e_packed,
                supports,
                freq,
                config.reservoir_size,
            )
        else:
            res_items, res_supp, res_seen, key = (
                s.res_items,
                s.res_supp,
                s.res_seen,
                s.key,
            )

        # --- push child PBECs (one scatter for the whole frontier) ----------
        # Child of extension e keeps extensions with larger rank (Prop. 2.23).
        later = rank[:, None, :] > rank[:, :, None]          # [F, I(child e), I]
        child_ext_bool = later & freq[:, None, :]
        child_ext = bm.pack_bool(child_ext_bool)             # [F, I, IW]
        # Children with no extensions are leaves: their FI was already emitted
        # above, so pushing them would only burn a trip — skip them.
        has_ext = child_ext_bool.any(axis=-1)
        push = freq & has_ext                                # [F, I]
        push_flat = push.reshape(F * I)
        n_push = push_flat.sum().astype(jnp.int32)
        sp_pop = s.sp - active.sum().astype(jnp.int32)
        push_rank = jnp.cumsum(push_flat) - 1                # 0..n_push-1
        stack_pos = jnp.where(push_flat, sp_pop + push_rank, S)  # ≥S ⇒ dropped
        dropped = jnp.maximum(sp_pop + n_push - S, 0)
        stk_items = s.stk_items.at[stack_pos].set(flat_items, mode="drop")
        stk_ext = s.stk_ext.at[stack_pos].set(
            child_ext.reshape(F * I, IW), mode="drop"
        )
        # A child is (its parent's arena row, item e).  The frontier nodes
        # that push write their tidlists once, as one block of F rows at
        # sp_pop, in lane order: node f's row sp_pop + j (j = pushing nodes
        # before f) is at most its first child's position.  So every node
        # refers to a row at or below its own stack position, and rows at or
        # above sp_pop are referred to by nothing left after this trip's pops
        # (which read the arena before this write).  The arena has F spare
        # rows so the block never clamps.
        lane_push = push.any(axis=-1)                        # [F]
        row_f = sp_pop + jnp.cumsum(lane_push) - lane_push   # [F]
        block = node_tid[jnp.argsort(~lane_push, stable=True)]
        arena = jax.lax.dynamic_update_slice(s.arena, block, (sp_pop, 0))
        stk_ref = s.stk_ref.at[stack_pos].set(
            jnp.broadcast_to(row_f[:, None], (F, I)).reshape(F * I),
            mode="drop",
        )
        stk_item = s.stk_item.at[stack_pos].set(
            jnp.tile(jnp.arange(I, dtype=jnp.int32), F), mode="drop"
        )
        sp_new = jnp.minimum(sp_pop + n_push, S)

        return _State(
            sp=sp_new,
            stk_items=stk_items,
            stk_ext=stk_ext,
            stk_ref=stk_ref,
            stk_item=stk_item,
            arena=arena,
            out_items=out_items,
            out_supp=out_supp,
            n_out=n_out,
            n_total=n_total,
            overflow=s.overflow + dropped,
            res_items=res_items,
            res_supp=res_supp,
            res_seen=res_seen,
            key=key,
            it=s.it + 1,
            popped=s.popped + active.sum().astype(jnp.int32),
            pushed=s.pushed + (sp_new - sp_pop),
        )

    with jax.named_scope(scope):
        final = jax.lax.while_loop(cond, body, init)
    return EclatResult(
        items=final.out_items,
        supports=final.out_supp,
        n_out=final.n_out,
        n_total=final.n_total,
        stack_overflow=final.overflow,
        reservoir_items=final.res_items,
        reservoir_supports=final.res_supp,
        n_iters=final.it,
        n_popped=final.popped,
        n_pushed=final.pushed,
    )


def mine(
    item_bits: jnp.ndarray,
    prefix_mask: jnp.ndarray,
    ext_mask: jnp.ndarray,
    prefix_tid: jnp.ndarray,
    min_support: jnp.ndarray,
    key: jax.Array,
    *,
    config: EclatConfig,
    n_items: int,
    support_fn: Optional[SupportFn] = None,
    multi_support_fn: Optional[MultiSupportFn] = None,
) -> EclatResult:
    """Single-PBEC convenience wrapper over :func:`mine_seeded`."""
    return mine_seeded(
        item_bits,
        prefix_mask[None, :],
        ext_mask[None, :],
        prefix_tid[None, :],
        jnp.ones((1,), jnp.bool_),
        min_support,
        key,
        config=config,
        n_items=n_items,
        support_fn=support_fn,
        multi_support_fn=multi_support_fn,
    )


def mine_all(
    db: bm.BitmapDB,
    min_support,
    key: Optional[jax.Array] = None,
    *,
    config: EclatConfig = EclatConfig(),
    support_fn: Optional[SupportFn] = None,
    multi_support_fn: Optional[MultiSupportFn] = None,
) -> EclatResult:
    """Mine *all* FIs of a database (root PBEC [∅ | B])."""
    if key is None:
        key = jax.random.PRNGKey(0)
    I = db.n_items
    return mine(
        db.item_bits,
        jnp.zeros((I,), jnp.bool_),
        jnp.ones((I,), jnp.bool_),
        db.all_tids(),
        jnp.asarray(min_support, jnp.int32),
        key,
        config=config,
        n_items=I,
        support_fn=support_fn,
        multi_support_fn=multi_support_fn,
    )


# ---------------------------------------------------------------------------
# Host-side oracle: brute-force FI mining for tests (exponential, tiny DBs).
# ---------------------------------------------------------------------------


def brute_force_fis(dense, min_support: int):
    """All frequent itemsets of a dense bool matrix, as {frozenset: support}."""
    import itertools

    import numpy as np

    dense = np.asarray(dense)
    n_tx, n_items = dense.shape
    out = {}
    frontier = []
    for i in range(n_items):
        s = int(dense[:, i].sum())
        if s >= min_support:
            out[frozenset([i])] = s
            frontier.append((frozenset([i]), dense[:, i]))
    while frontier:
        nxt = []
        for items, cover in frontier:
            last = max(items)
            for j in range(last + 1, n_items):
                cov = cover & dense[:, j]
                s = int(cov.sum())
                if s >= min_support:
                    ns = items | {j}
                    out[frozenset(ns)] = s
                    nxt.append((frozenset(ns), cov))
        frontier = nxt
    return out

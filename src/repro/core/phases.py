"""Phases 1–4 of Parallel-FIMI as axis-name-parameterized SPMD programs.

Every device function here takes ``axis_name`` and runs identically under

  * ``jax.vmap(f, axis_name=AX)``   — single-device P-way simulation (tests,
    CPU container), and
  * ``jax.shard_map(f, mesh, ...)`` — real multi-device execution (the
    ``launch/mine.py`` path and the dry-run),

because the only cross-processor communication is ``psum / all_gather /
all_to_all / axis_index`` — the JAX-native image of the thesis' MPI collectives
(DESIGN.md, "Hardware adaptation").  Host-side control plane (Phase 2
partition + LPT, reservoir merge) lives in ``pbec.py`` / ``schedule.py`` /
``sampling.py`` and is orchestrated by ``fimi.py``.

Layout conventions
  * Global DB: horizontal packed ``tx_bits  uint32[P, T, IW_tx]`` — shard i is
    D_i, exactly |D|/P transactions (thesis §2.1); ``IW_tx = n_words(I)``.
  * A "slab" is a horizontal sub-database a processor holds after exchange.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import bitmap as bm
from repro.core import eclat, mfi

_U32 = jnp.uint32


# ---------------------------------------------------------------------------
# Shared device helpers
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("n_items",))
def vertical_from_slab(
    slab: jnp.ndarray, valid: jnp.ndarray, n_items: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Horizontal packed slab ``uint32[T, IW]`` (+ row-valid mask) → vertical
    ``item_bits uint32[I, n_words(T)]`` and the valid-tid bitmap.

    The transpose lives on device, one 32×32 bit block at a time, with
    invalid rows masked to zero first.
    """
    rows = jnp.where(valid[:, None], slab, _U32(0))
    item_bits = bm.transpose_bits(rows, n_items)             # [I, W]
    valid_tid = bm.pack_bool(valid)                          # [W]
    return item_bits, valid_tid


def seed_tidlists(
    item_bits: jnp.ndarray, seed_prefix: jnp.ndarray, valid_tid: jnp.ndarray
) -> jnp.ndarray:
    """T(U_k) for K packed seed prefixes — batched AND-reduce (`Prepare-
    Tidlists`, Alg. 20).  Mapped over seeds in batches of 8: one vmap over
    all K would hold a ``[K, I, W]`` select, 32 GB at K=640 on a
    100,000-transaction slab."""

    def one(prefix_bool):
        rows = jnp.where(prefix_bool[:, None], item_bits, _U32(0xFFFFFFFF))
        tid = jax.lax.reduce(
            rows, _U32(0xFFFFFFFF), lambda a, b: jnp.bitwise_and(a, b), (0,)
        )
        return tid & valid_tid

    return jax.lax.map(one, seed_prefix, batch_size=8)


# ---------------------------------------------------------------------------
# Phase 1 — sampling
# ---------------------------------------------------------------------------


class Phase1DeviceOut(NamedTuple):
    sample_db: jnp.ndarray       # uint32[n_sample, IW] — D̃, replicated
    reservoir: jnp.ndarray       # uint32[R, IW_items] — local reservoir (Res.)
    reservoir_supports: jnp.ndarray
    fi_count: jnp.ndarray        # int32 — f_i, #FIs streamed locally
    mfi_items: jnp.ndarray       # uint32[Mmax, IW_items] — M_i (Par variant)
    mfi_supports: jnp.ndarray
    mfi_count: jnp.ndarray       # int32
    overflow: jnp.ndarray        # int32 — any stack/output overflow
    n_iters: jnp.ndarray         # int32 — sample-mine loop trips (0: seq)
    n_popped: jnp.ndarray        # int32 — DFS nodes mined (0: par, seq)


def _assigned_item_seeds(order: jnp.ndarray, n_items: int, p_idx, P: int):
    """Static 1-prefix block assignment (Alg. 11 line 3): processor i takes
    the items at positions j of the support-ascending ``order`` with
    ``j % P == i`` (round-robin balances heavy early classes better than
    contiguous blocks; any fixed rule is valid).

    Returns bool [K, I] prefix masks, [K, I] ext masks, valid [K] with
    K = ceil(I/P).
    """
    I = n_items
    K = (I + P - 1) // P
    slots = p_idx + P * jnp.arange(K)                       # positions in order
    valid = slots < I
    slots_c = jnp.minimum(slots, I - 1)
    items = order[slots_c]                                  # item ids
    prefix = jax.nn.one_hot(items, I, dtype=jnp.bool_) & valid[:, None]
    pos_of = jnp.argsort(order)                             # item -> position
    later = pos_of[None, None, :] > pos_of[None, :, None]   # unused broad form
    # ext_k = items with position > slots[k]
    positions = jnp.arange(I)
    ext = (positions[None, :] > slots_c[:, None])           # positions in order
    # map position-mask back to item-id mask
    ext_items = jnp.zeros((K, I), jnp.bool_)
    ext_items = ext_items.at[:, order].set(ext)
    ext_items = ext_items & valid[:, None]
    return prefix, ext_items, valid


def phase1_device(
    local_tx: jnp.ndarray,        # uint32[T, IW] — this processor's D_i
    key: jax.Array,
    min_support_rel: jnp.ndarray,  # float scalar — min_support*
    *,
    axis_name: str,
    n_items: int,
    n_tx_local: int,
    n_sample_per_proc: int,
    reservoir_size: int,
    eclat_cfg: eclat.EclatConfig,
    mfi_cfg: mfi.MFIConfig,
    variant: str,                 # "reservoir" | "par"
    support_fn,                   # kernels.ops.support_fns plug-ins
    multi_support_fn,
) -> Phase1DeviceOut:
    """Device part of Phase 1 (Algs. 12/13/14 lines 1–9).

    1. sample T' = n_sample_per_proc transactions of D_i i.i.d.;
    2. all-gather → D̃ replicated on every processor;
    3. mine D̃ restricted to this processor's 1-prefix PBECs, streaming FIs
       through a local reservoir (reservoir variant) or collecting MFI
       candidates M_i (par variant).
    """
    P = jax.lax.axis_size(axis_name)
    k_samp, k_res = jax.random.split(jax.random.fold_in(key, jax.lax.axis_index(axis_name)))

    rows = bm.sample_transactions(local_tx, k_samp, n_sample_per_proc, n_tx_local)
    sample_db = jax.lax.all_gather(rows, axis_name).reshape(
        P * n_sample_per_proc, -1
    )
    n_samp = P * n_sample_per_proc
    min_support = jnp.ceil(min_support_rel * n_samp).astype(jnp.int32)

    IW_items = bm.n_words(n_items)
    if variant == "sample":  # Seq variant: p_1 mines D̃ on the host afterwards
        return Phase1DeviceOut(
            sample_db=sample_db,
            reservoir=jnp.zeros((max(reservoir_size, 1), IW_items), _U32),
            reservoir_supports=jnp.zeros((max(reservoir_size, 1),), jnp.int32),
            fi_count=jnp.zeros((), jnp.int32),
            mfi_items=jnp.zeros((mfi_cfg.max_out, IW_items), _U32),
            mfi_supports=jnp.zeros((mfi_cfg.max_out,), jnp.int32),
            mfi_count=jnp.zeros((), jnp.int32),
            overflow=jnp.zeros((), jnp.int32),
            n_iters=jnp.zeros((), jnp.int32),
            n_popped=jnp.zeros((), jnp.int32),
        )

    # vertical form of D̃ (identical on every processor)
    item_bits, valid_tid = vertical_from_slab(
        sample_db, jnp.ones((n_samp,), jnp.bool_), n_items
    )

    # support-ascending global item order for the 1-prefix classes
    root_supp = support_fn(item_bits, valid_tid)
    frequent_item = root_supp >= min_support
    order = jnp.argsort(jnp.where(frequent_item, root_supp, jnp.iinfo(jnp.int32).max))

    p_idx = jax.lax.axis_index(axis_name)
    seed_prefix, seed_ext, seed_valid = _assigned_item_seeds(
        order, n_items, p_idx, P
    )
    # drop seeds whose item is not frequent
    seed_item_freq = (seed_prefix & frequent_item[None, :]).any(axis=-1)
    seed_valid = seed_valid & seed_item_freq
    seed_tid = seed_tidlists(item_bits, seed_prefix, valid_tid)
    seed_supp = (
        jnp.where(seed_prefix, root_supp[None, :], 0).sum(axis=-1).astype(jnp.int32)
    )

    if variant == "reservoir":
        res = eclat.mine_seeded(
            item_bits,
            seed_prefix,
            seed_ext,
            seed_tid,
            seed_valid,
            min_support,
            k_res,
            config=dataclasses.replace(
                eclat_cfg, reservoir_size=reservoir_size, count_only=True
            ),
            n_items=n_items,
            multi_support_fn=multi_support_fn,
            scope="fimi/phase1/eclat_loop",
        )
        # The stream contains every FI of D̃ with |W| ≥ 2; singleton FIs are
        # exactly the class prefixes, which the partitioner handles through
        # the prefix side channel (the thesis' "{V}" term of Prop. 2.23), so
        # the sample space is consistently F̃_{≥2}.
        fi_count = res.n_total
        return Phase1DeviceOut(
            sample_db=sample_db,
            reservoir=res.reservoir_items,
            reservoir_supports=res.reservoir_supports,
            fi_count=fi_count,
            mfi_items=jnp.zeros((mfi_cfg.max_out, IW_items), _U32),
            mfi_supports=jnp.zeros((mfi_cfg.max_out,), jnp.int32),
            mfi_count=jnp.zeros((), jnp.int32),
            overflow=res.stack_overflow,
            n_iters=res.n_iters,
            n_popped=res.n_popped,
        )
    elif variant == "par":
        res = mfi.mine_candidates_seeded(
            item_bits,
            seed_prefix,
            seed_ext,
            seed_tid,
            seed_supp,
            seed_valid,
            min_support,
            config=mfi_cfg,
            n_items=n_items,
            support_fn=support_fn,
        )
        return Phase1DeviceOut(
            sample_db=sample_db,
            reservoir=jnp.zeros((max(reservoir_size, 1), IW_items), _U32),
            reservoir_supports=jnp.zeros((max(reservoir_size, 1),), jnp.int32),
            fi_count=jnp.zeros((), jnp.int32),
            mfi_items=res.items,
            mfi_supports=res.supports,
            mfi_count=res.n_out,
            overflow=res.overflow,
            n_iters=res.n_iters,
            n_popped=jnp.zeros((), jnp.int32),
        )
    else:
        raise ValueError(f"unknown phase-1 variant {variant!r}")


# ---------------------------------------------------------------------------
# Phase 3 — database partition exchange (Alg. 18 → all_to_all)
# ---------------------------------------------------------------------------


class Phase3Out(NamedTuple):
    slab: jnp.ndarray          # uint32[P*cap, IW] — D'_i rows (incl. padding)
    slab_valid: jnp.ndarray    # bool [P*cap]
    recv_counts: jnp.ndarray   # int32[P]
    overflow: jnp.ndarray      # int32 — rows that did not fit cap (global err)
    replication: jnp.ndarray   # float — Σ|D'_i| / |D| (thesis Ch. 10)


@jax.named_scope("fimi/phase3/exchange")
def phase3_exchange(
    local_tx: jnp.ndarray,       # uint32[T, IW] — D_i
    local_valid: jnp.ndarray,    # bool [T]
    class_prefix_packed: jnp.ndarray,  # uint32[C, IW] — U_k (padded classes)
    class_valid: jnp.ndarray,    # bool [C]
    class_assign: jnp.ndarray,   # int32[C] — processor per class
    *,
    axis_name: str,
    capacity: int,
) -> Phase3Out:
    """Each processor sends to p_j the transactions containing any U_k with
    assign(k)=j, via fixed-capacity ``all_to_all`` (replaces the round-robin
    tournament of Alg. 18 — see DESIGN.md).  Overflow is *counted*, never
    silently dropped.
    """
    P = jax.lax.axis_size(axis_name)
    T = local_tx.shape[0]

    # contains[t, k]: U_k ⊆ t, mapped over classes in batches so the
    # [T, C, IW] difference is never held at once
    contains = jax.lax.map(
        lambda u: bm.is_subset_packed(u[None, :], local_tx),
        class_prefix_packed, batch_size=32,
    ).T  # [T, C]
    contains = contains & class_valid[None, :] & local_valid[:, None]
    dest_onehot = jax.nn.one_hot(class_assign, P, dtype=jnp.bool_)  # [C, P]
    need = jnp.einsum("tc,cp->tp", contains, dest_onehot) > 0       # [T, P]

    # pack up to `capacity` rows per destination
    rank = jnp.cumsum(need, axis=0) - 1                             # [T, P]
    sent = need & (rank < capacity)
    overflow_local = (need & ~sent).sum()
    send = jnp.zeros((P, capacity, local_tx.shape[1]), _U32)
    send_valid = jnp.zeros((P, capacity), jnp.bool_)
    # scatter rows: for each dest p, positions rank[t,p]
    t_idx = jnp.arange(T)
    for_axis = jnp.where(sent, rank, capacity)                      # cap ⇒ drop

    def scatter_dest(p, carry):
        send, send_valid = carry
        pos = for_axis[:, p]
        send = send.at[p, pos].set(local_tx, mode="drop")
        send_valid = send_valid.at[p, pos].set(sent[:, p], mode="drop")
        return send, send_valid

    send, send_valid = jax.lax.fori_loop(0, P, scatter_dest, (send, send_valid))

    recv = jax.lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0)
    recv_valid = jax.lax.all_to_all(
        send_valid, axis_name, split_axis=0, concat_axis=0
    )
    recv_counts = recv_valid.sum(axis=1).astype(jnp.int32)
    n_local = local_valid.sum()
    total_tx = jax.lax.psum(n_local, axis_name)
    my_rows = recv_valid.sum()
    replication = jax.lax.psum(my_rows, axis_name) / jnp.maximum(total_tx, 1)
    overflow = jax.lax.psum(overflow_local, axis_name)
    return Phase3Out(
        slab=recv.reshape(P * capacity, -1),
        slab_valid=recv_valid.reshape(P * capacity),
        recv_counts=recv_counts,
        overflow=overflow.astype(jnp.int32),
        replication=replication.astype(jnp.float32),
    )


# ---------------------------------------------------------------------------
# Phase 4 — parallel FI computation (Alg. 19 / 22)
# ---------------------------------------------------------------------------


class Phase4Out(NamedTuple):
    fi_items: jnp.ndarray      # uint32[max_out, IW_items]
    fi_supports: jnp.ndarray   # int32[max_out]
    fi_count: jnp.ndarray      # int32 — local |F_q| (excl. prefix side channel)
    fi_total: jnp.ndarray      # int32 — found (≥ fi_count if buffer overflowed)
    prefix_supports: jnp.ndarray  # int32[A] — global Supp(W) for ancestor set
    overflow: jnp.ndarray
    work_iters: jnp.ndarray    # int32 — DFS trips (the load-balance metric)
    nodes_popped: jnp.ndarray  # int32 — DFS nodes mined; /(trips·K) is the
    #                            lane fill (args of span fimi/phase4_mine)
    nodes_pushed: jnp.ndarray  # int32 — children pushed; /(trips·K·I) is the
    #                            share of the frontier's child slots kept


def phase4_mine(
    slab: jnp.ndarray,            # uint32[Tcap, IW] — D'_q from Phase 3
    slab_valid: jnp.ndarray,      # bool [Tcap]
    local_tx: jnp.ndarray,        # uint32[T, IW] — original D_q (side channel)
    local_valid: jnp.ndarray,     # bool [T]
    my_seed_prefix: jnp.ndarray,  # bool [K, I] — assigned classes (padded)
    my_seed_ext: jnp.ndarray,     # bool [K, I]
    my_seed_valid: jnp.ndarray,   # bool [K]
    ancestor_masks: jnp.ndarray,  # bool [A, I] — prefix side-channel itemsets
    min_support: jnp.ndarray,     # absolute, int32
    key: jax.Array,
    *,
    axis_name: str,
    n_items: int,
    eclat_cfg: eclat.EclatConfig,
    support_fn=None,
    multi_support_fn=None,
) -> Phase4Out:
    """Alg. 19 (Phase-4-Compute-FI) with Eclat (Alg. 22):

    * line 2–5: local supports of ancestor prefixes on D_q, ``psum`` → global;
    * line 6: Exec-Eclat over the assigned PBECs on the received slab D'_q,
      mining ``eclat_cfg.frontier_size`` nodes per loop trip.
    """
    from repro.core.apriori import count_supports

    # --- prefix side channel on the ORIGINAL partition D_q ------------------
    item_bits_orig, valid_tid_orig = vertical_from_slab(
        local_tx, local_valid, n_items
    )
    local_anc = count_supports(item_bits_orig, ancestor_masks, valid_tid_orig)
    prefix_supports = jax.lax.psum(local_anc, axis_name)

    # --- Exec-Eclat on the exchanged slab D'_q ------------------------------
    item_bits, valid_tid = vertical_from_slab(slab, slab_valid, n_items)
    seed_tid = seed_tidlists(item_bits, my_seed_prefix, valid_tid)
    res = eclat.mine_seeded(
        item_bits,
        my_seed_prefix,
        my_seed_ext,
        seed_tid,
        my_seed_valid,
        min_support,
        key,
        config=eclat_cfg,
        n_items=n_items,
        support_fn=support_fn,
        multi_support_fn=multi_support_fn,
        scope="fimi/phase4/eclat_loop",
    )
    return Phase4Out(
        fi_items=res.items,
        fi_supports=res.supports,
        fi_count=res.n_out,
        fi_total=res.n_total,
        prefix_supports=prefix_supports,
        overflow=res.stack_overflow,
        work_iters=res.n_iters,
        nodes_popped=res.n_popped,
        nodes_pushed=res.n_pushed,
    )

"""Parallel-FIMI drivers — Methods 1–3 of the thesis (§8.5).

``run`` executes the full four-phase pipeline over P miners.  The device
phases are SPMD programs from :mod:`repro.core.phases`, mapped over the miner
axis by a pluggable ``spmd`` combinator:

  * ``vmap_spmd``       — P virtual miners on one device (tests, CPU),
  * ``shard_map_spmd``  — real devices along a mesh axis (launch/mine.py).

Host control plane between the phases (sampling merge, Partition+LPT,
seed construction) is identical for both — exactly what a production launcher
does between collectives.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitmap as bm
from repro.core import eclat, mfi, pbec, phases, sampling, schedule
from repro.kernels import ops as kernel_ops
from repro.obs import metrics as obs_metrics
from repro.obs import profile as obs_profile
from repro.obs import progress as obs_progress
from repro.obs import trace as obs_trace

_U32 = jnp.uint32


@dataclasses.dataclass(frozen=True)
class FimiParams:
    """User-facing knobs (thesis Ch. 8 inputs)."""

    variant: str = "reservoir"          # "seq" | "par" | "reservoir"
    min_support_rel: float = 0.1        # min_support*
    eps_db: float = 0.05                # ε_D̃   (Thm 6.1)
    delta_db: float = 0.1               # δ_D̃
    eps_fs: float = 0.05                # ε_F̃s  (Thm 6.2/6.3)
    delta_fs: float = 0.1               # δ_F̃s
    rho: float = 0.01                   # smallest-PBEC relative size
    alpha: float = 0.5                  # Phase-2 granularity
    n_db_sample: Optional[int] = None   # override |D̃| (else from ε,δ)
    n_fi_sample: Optional[int] = None   # override |F̃s|
    scheduler: str = "lpt"              # "lpt" | "repl_min"
    exchange_capacity: Optional[int] = None  # Phase-3 per-(src,dst) row cap
    max_classes: int = 512
    eclat: eclat.EclatConfig = eclat.EclatConfig(max_out=8192, max_stack=2048)
    mfi: mfi.MFIConfig = mfi.MFIConfig(max_out=2048, max_stack=2048)
    force: Optional[str] = None         # kernel backend pin (kernels.ops)


@dataclasses.dataclass
class FimiResult:
    sample_masks: np.ndarray            # bool [N, I] — F̃s
    classes: List[pbec.PBEC]
    assignment: np.ndarray              # int [C]
    est_loads: np.ndarray               # float [P] — estimated work shares
    replication: float                  # Phase-3 replication factor
    exchange_overflow: int
    phase4: phases.Phase4Out            # stacked over P
    ancestor_masks: np.ndarray          # bool [A, I]
    ancestor_supports: np.ndarray       # int [A] — global supports
    n_fis: int                          # |F| (classes ∪ frequent ancestors)
    work_iters: np.ndarray              # int [P] — DFS trips per miner
    fi_dict: Optional[Dict] = None      # materialized {frozenset: supp}
    nodes_popped: Optional[np.ndarray] = None  # int [P] — DFS nodes mined
    progress: Optional[obs_progress.ProgressSnapshot] = None  # final snapshot


# ---------------------------------------------------------------------------
# SPMD combinators
# ---------------------------------------------------------------------------

AXIS = "miners"


def vmap_spmd(fn, P: int, mesh=None):
    """Map an SPMD fn over stacked [P, ...] arrays on a single device."""
    return jax.vmap(fn, axis_name=AXIS)


def shard_map_spmd(fn, P: int, mesh):
    """Map over real devices along mesh axis ``AXIS`` (1-D miner mesh).

    shard_map keeps the mapped dim (local size 1) where vmap removes it; the
    squeeze/unsqueeze wrapper gives both combinators identical semantics so
    the phase functions are written once.  The result is jitted: an eager
    shard_map traces and compiles again on every call.
    """
    from jax.sharding import PartitionSpec as PS

    def body(*args):
        args = jax.tree.map(lambda a: a.reshape(a.shape[1:]), args)
        out = fn(*args)
        return jax.tree.map(lambda a: jnp.asarray(a)[None], out)

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=PS(AXIS), out_specs=PS(AXIS), check_vma=False
    ))


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

#: process-wide count of ``run`` calls: the ``mine`` arg of its spans
_MINES = itertools.count(1)


def run(
    tx_shards,                # uint32[P, T, IW] shards — or a store.TxStore
    n_items: int,
    params: FimiParams,
    key: jax.Array,
    *,
    spmd=vmap_spmd,
    mesh=None,
    materialize: bool = False,
    P: Optional[int] = None,
    host_budget_blocks: int = 2,
    reader=None,
) -> FimiResult:
    """Mine every FI of ``tx_shards`` with P miners (Phases 1–4).

    While tracing, the whole mine is span ``fimi/run`` (its self time is the
    host glue between phases), and it and every ``fimi/*`` span inside carry
    ``mine=<n>``, this call's number in the process.
    """
    mine = next(_MINES)
    with obs_trace.TRACER.span("fimi/run", mine=mine):
        return _run(tx_shards, n_items, params, key, spmd, mesh, materialize,
                    P, host_budget_blocks, reader, mine)


def _run(tx_shards, n_items, params, key, spmd, mesh, materialize, P,
         host_budget_blocks, reader, mine) -> FimiResult:
    tr = obs_trace.TRACER
    if not hasattr(tx_shards, "shape"):   # a TxStore: mine out-of-core
        from repro.store import checksum, reader as store_reader

        if P is None:
            raise ValueError("P (miner count) is required when mining a TxStore")
        if n_items is None:
            n_items = tx_shards.n_items
        # Assemble the device shards block-by-block through the double-
        # buffered reader: host residency stays within the block budget, the
        # device holds only the packed working set, and the result is
        # bit-exact with shard_db(dense, P) — so everything below (sampling
        # included) matches the in-memory path bit for bit.  Drivers pass
        # ``reader`` (a BlockReader on this store) to observe the streamed
        # host high-water mark of this very pass.
        with tr.span("fimi/assemble_store", P=P, mine=mine,
                     crc=checksum.IMPLEMENTATION):
            tx_shards = tr.sync(store_reader.to_device_shards(
                tx_shards, P, host_budget_blocks=host_budget_blocks,
                reader=reader,
            ))
    if mesh is not None:
        from repro.store.reader import place_on_mesh

        tx_shards = place_on_mesh(tx_shards, mesh)
    P, T, IW = tx_shards.shape
    n_tx = P * T
    abs_minsup = int(np.ceil(params.min_support_rel * n_tx))
    support_fn, multi_support_fn = kernel_ops.support_fns(params.force)

    n_db = params.n_db_sample or sampling.db_sample_size(
        params.eps_db, params.delta_db
    )
    n_db = min(n_db, n_tx)  # sampling more than |D| adds nothing but cost
    per_proc = max(1, n_db // P)
    n_db = per_proc * P
    n_fs = params.n_fi_sample or sampling.reservoir_sample_size(
        params.eps_fs, params.delta_fs, params.rho
    )

    # ---------------- Phase 1 ------------------------------------------------
    variant_dev = {"seq": "sample", "par": "par", "reservoir": "reservoir"}[
        params.variant
    ]
    p1 = partial(
        phases.phase1_device,
        axis_name=AXIS,
        n_items=n_items,
        n_tx_local=T,
        n_sample_per_proc=per_proc,
        reservoir_size=n_fs if params.variant == "reservoir" else 1,
        eclat_cfg=params.eclat,
        mfi_cfg=params.mfi,
        variant=variant_dev,
        support_fn=support_fn,
        multi_support_fn=multi_support_fn,
    )
    keys = jnp.broadcast_to(key, (P, *key.shape))
    minsup_rel = jnp.broadcast_to(
        jnp.asarray(params.min_support_rel, jnp.float32), (P,)
    )
    K = max(1, min(params.eclat.frontier_size, params.eclat.max_stack))
    with tr.span("fimi/phase1_sample", P=P, variant=params.variant,
                 mine=mine) as sp:
        out1 = tr.sync(spmd(p1, P, mesh)(tx_shards, keys, minsup_rel))
        if tr.enabled:
            iters, popped, offers = jax.device_get(
                (out1.n_iters, out1.n_popped, out1.fi_count))
            sp.set(trips=int(np.max(iters)), popped=int(np.sum(popped)),
                   offers=int(np.sum(offers)), K=K, I=n_items)

    sample_db_rows = np.asarray(jax.device_get(out1.sample_db))[0]  # replicated
    n_samp = sample_db_rows.shape[0]
    sample_minsup = int(np.ceil(params.min_support_rel * n_samp))
    sample_bitdb = bm.rebuild_vertical(
        jnp.asarray(sample_db_rows), n_items, n_samp
    )

    rng = np.random.default_rng(int(jax.random.key_data(key).sum()) & 0x7FFFFFFF)

    if params.variant == "reservoir":
        f_counts = np.asarray(out1.fi_count)
        X = sampling.merge_reservoirs(rng, f_counts, n_fs)
        picked = []
        res_items = np.asarray(out1.reservoir)
        for i in range(P):
            avail = int(min(f_counts[i], n_fs))
            if X[i] == 0 or avail == 0:
                continue
            sel = rng.choice(avail, size=int(min(X[i], avail)), replace=False)
            picked.append(res_items[i][sel])
        fs_packed = (
            np.concatenate(picked, axis=0)
            if picked
            else np.zeros((0, bm.n_words(n_items)), np.uint32)
        )
    elif params.variant == "par":
        m_items = np.asarray(out1.mfi_items)     # [P, Mmax, IW]
        m_counts = np.asarray(out1.mfi_count)
        all_m = [m_items[i, : int(m_counts[i])] for i in range(P)]
        M = (
            np.concatenate(all_m, axis=0)
            if any(len(a) for a in all_m)
            else np.zeros((0, bm.n_words(n_items)), np.uint32)
        )
        # global pick m ∝ 2^|m| ≡ thesis' per-processor s_i/s split (Alg. 13)
        fs_packed = _coverage_sample_host(M, n_fs, n_items, key)
    else:  # "seq": p_1 mines the MFIs of D̃ sequentially (Alg. 12)
        r = mfi.mine_all_candidates(
            sample_bitdb, sample_minsup, config=params.mfi,
            support_fn=support_fn,
        )
        n = int(r.n_out)
        valid = np.zeros(r.items.shape[0], bool)
        valid[:n] = True
        keep = np.asarray(mfi.filter_maximal(r.items, jnp.asarray(valid)))
        M = np.asarray(r.items)[keep]
        fs_packed = _coverage_sample_host(M, n_fs, n_items, key)

    sample_masks = np.asarray(
        bm.unpack_bool(jnp.asarray(fs_packed), n_items)
    ).reshape(-1, n_items)
    # coverage samplers can emit ∅/singletons — the partitioner needs |W| ≥ 2
    # consistently with the reservoir stream (see phases.phase1_device).
    sample_masks = sample_masks[sample_masks.sum(axis=1) >= 2]

    # ---------------- Phase 2 ------------------------------------------------
    probes = 0

    def ext_supports(prefix: np.ndarray) -> np.ndarray:
        nonlocal probes
        probes += 1
        with tr.span("fimi/phase2_probe", mine=mine):
            tid = bm.tidlist_of_itemset(sample_bitdb, jnp.asarray(prefix))
            return np.asarray(support_fn(sample_bitdb.item_bits, tid))

    with tr.span("fimi/phase2_partition", scheduler=params.scheduler,
                 mine=mine) as sp:
        classes = pbec.partition(
            sample_masks,
            P,
            params.alpha,
            ext_supports,
            n_items,
            max_classes=params.max_classes,
        )
        # Drop classes whose prefix is infrequent even in the sample: their
        # whole subtree is infrequent w.h.p.; their FIs (if any) are still
        # covered by the ancestor side channel check below only if prefix
        # frequent — so keep all classes to stay exact (the miner prunes cheap
        # infrequent seeds itself).
        sizes = np.array([c.est_count for c in classes], dtype=np.float64)
        if params.scheduler == "repl_min":
            pref_packed, _ = pbec.classes_to_packed(classes)
            tids = np.asarray(
                phases.seed_tidlists(
                    sample_bitdb.item_bits,
                    jnp.asarray(np.stack([c.prefix for c in classes])),
                    sample_bitdb.all_tids(),
                )
            )
            profit = schedule.pairwise_shared_transactions(tids)
            # no tidlists: the volume report (NaN then) is unused here
            assignment = schedule.db_repl_min(sizes, profit, P).assignment
        else:
            assignment = schedule.lpt_schedule(sizes, P)
        est_loads = schedule.loads_of(sizes, assignment, P)
        sp.set(probes=probes)

    # ---------------- Phase 3 ------------------------------------------------
    C = len(classes)
    pref_packed, _ = pbec.classes_to_packed(classes)
    cap = params.exchange_capacity or T
    p3 = partial(phases.phase3_exchange, axis_name=AXIS, capacity=cap)
    local_valid = jnp.ones((P, T), jnp.bool_)
    class_prefix_b = jnp.broadcast_to(
        jnp.asarray(pref_packed), (P, C, pref_packed.shape[-1])
    )
    class_valid_b = jnp.ones((P, C), jnp.bool_)
    class_assign_b = jnp.broadcast_to(jnp.asarray(assignment, jnp.int32), (P, C))
    with tr.span("fimi/phase3_exchange", C=C, mine=mine):
        out3 = tr.sync(spmd(p3, P, mesh)(
            tx_shards, local_valid, class_prefix_b, class_valid_b,
            class_assign_b,
        ))

    # ---------------- Phase 4 ------------------------------------------------
    Cmax = max(int((assignment == p).sum()) for p in range(P))
    Cmax = max(Cmax, 1)
    seed_prefix = np.zeros((P, Cmax, n_items), dtype=bool)
    seed_ext = np.zeros((P, Cmax, n_items), dtype=bool)
    seed_valid = np.zeros((P, Cmax), dtype=bool)
    for p in range(P):
        mine_ids = np.nonzero(assignment == p)[0]
        for j, cid in enumerate(mine_ids):
            seed_prefix[p, j] = classes[cid].prefix
            seed_ext[p, j] = classes[cid].ext
            seed_valid[p, j] = True

    # ancestor side channel: every DFS-path prefix of every class, dedup'd
    ancestor_masks, anc_list = pbec.ancestor_closure(classes, n_items)
    A = ancestor_masks.shape[0]

    p4 = partial(
        phases.phase4_mine,
        axis_name=AXIS,
        n_items=n_items,
        eclat_cfg=params.eclat,
        multi_support_fn=multi_support_fn,
    )
    keys4 = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(P))
    slab = out3.slab.reshape(P, -1, IW) if out3.slab.ndim == 2 else out3.slab
    progress = obs_progress.ProgressEstimator(est_loads)
    progress.start()
    mine_t0 = time.perf_counter()
    with tr.span("fimi/phase4_mine", Cmax=Cmax, A=A, mine=mine) as sp:
        out4 = spmd(p4, P, mesh)(
            slab,
            out3.slab_valid.reshape(P, -1),
            tx_shards,
            local_valid,
            jnp.asarray(seed_prefix),
            jnp.asarray(seed_ext),
            jnp.asarray(seed_valid),
            jnp.broadcast_to(jnp.asarray(ancestor_masks), (P, A, n_items)),
            jnp.broadcast_to(jnp.asarray(abs_minsup, jnp.int32), (P,)),
            keys4,
        )
        out4 = jax.block_until_ready(out4)
        if tr.enabled:
            iters, popped, pushed = jax.device_get(
                (out4.work_iters, out4.nodes_popped, out4.nodes_pushed))
            sp.set(trips=int(np.max(iters)), popped=int(np.sum(popped)),
                   pushed=int(np.sum(pushed)), P=P, K=K)
    mine_s = time.perf_counter() - mine_t0
    trips_arr = np.asarray(out4.work_iters).astype(np.float64).reshape(-1)
    # Loop-attributed kernel work: the multi-support sweep executes inside
    # the compiled Eclat while_loop once per DFS trip (the ops wrapper only
    # sees the trace-time dispatch); shapes come from the mined slab.
    if obs_profile.PROFILER.enabled:
        obs_profile.PROFILER.observe_loop(
            "multi",
            {
                "K": K,
                "I": n_items,
                "W": (int(slab.shape[1]) + 31) // 32,
            },
            n_exec=int(trips_arr.sum()),
            wall_s=mine_s,
        )
    # One-shot pipeline: the single update closes the progress record with
    # the trip-grounded straggler scores (Thm 6.1 estimate vs observation).
    final_progress = progress.update(est_loads, trips_arr)
    progress.finish()

    anc_supports = np.asarray(out4.prefix_supports)[0]  # identical on all p
    anc_frequent = int((anc_supports >= abs_minsup).sum()) if anc_list else 0
    n_fis = int(np.asarray(out4.fi_total).sum()) + anc_frequent

    result = FimiResult(
        sample_masks=sample_masks,
        classes=classes,
        assignment=assignment,
        est_loads=est_loads,
        replication=float(np.asarray(out3.replication).reshape(-1)[0]),
        exchange_overflow=int(np.asarray(out3.overflow).reshape(-1)[0]),
        phase4=out4,
        ancestor_masks=ancestor_masks[: len(anc_list)],
        ancestor_supports=anc_supports[: len(anc_list)],
        n_fis=n_fis,
        work_iters=np.asarray(out4.work_iters),
        nodes_popped=np.asarray(out4.nodes_popped).reshape(-1),
        progress=final_progress,
    )
    _emit_run_metrics(result)
    if materialize:
        result.fi_dict = materialize_fis(result, n_items, abs_minsup)
    return result


def _emit_run_metrics(result: FimiResult) -> None:
    """Publish one pipeline pass into the process-global metrics registry."""
    reg = obs_metrics.registry()
    reg.counter("fimi/runs").inc()
    reg.counter("fimi/trips").inc(int(result.work_iters.sum()))
    reg.counter("fimi/exchange_overflow").inc(result.exchange_overflow)
    reg.gauge("fimi/n_fis").set(float(result.n_fis))
    reg.gauge("fimi/n_classes").set(float(len(result.classes)))
    reg.gauge("fimi/replication").set(float(result.replication))


def _coverage_sample_host(M: np.ndarray, n_fs: int, n_items: int, key) -> np.ndarray:
    if len(M) == 0:
        return np.zeros((0, M.shape[-1] if M.ndim == 2 else bm.n_words(n_items)), np.uint32)
    valid = jnp.ones((len(M),), jnp.bool_)
    # oversample: ∅/singletons get filtered downstream
    samp = sampling.modified_coverage_sample(
        key, jnp.asarray(M), valid, int(n_fs * 1.3) + 8, n_items
    )
    return np.asarray(samp)


def materialize_fis(result: FimiResult, n_items: int, abs_minsup: int) -> Dict:
    """Collect the distributed result into {frozenset: support} (tests only)."""
    out: Dict = {}
    items = np.asarray(result.phase4.fi_items)
    supps = np.asarray(result.phase4.fi_supports)
    counts = np.asarray(result.phase4.fi_count)
    P = items.shape[0]
    for p in range(P):
        for k in range(int(counts[p])):
            mask = np.asarray(bm.unpack_bool(jnp.asarray(items[p, k]), n_items))
            out[frozenset(np.nonzero(mask)[0].tolist())] = int(supps[p, k])
    for mask, s in zip(result.ancestor_masks, result.ancestor_supports):
        if s >= abs_minsup:
            out[frozenset(np.nonzero(mask)[0].tolist())] = int(s)
    return out


def shard_db(db_dense: np.ndarray, P: int) -> jnp.ndarray:
    """Split a dense bool DB row-wise into P packed shards [P, T, IW]."""
    n_tx, n_items = db_dense.shape
    T = n_tx // P
    rows = db_dense[: T * P].reshape(P, T, n_items)
    return bm.pack_bool(jnp.asarray(rows))

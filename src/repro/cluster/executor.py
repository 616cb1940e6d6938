"""Distributed mining executor: Phase-3 exchange + Phase-4 shard_map mining.

Takes a packed transaction DB sharded over a 1-D miner mesh and runs the full
paper pipeline end to end::

    plan (host, sample-based)                                  planner.py
      └─► per-shard class queues
    round r = 0, 1, …                                          this module
      ├─ Phase 3: all_to_all exchange of the transactions the
      │           round's classes need (fixed-capacity slabs)  core/phases.py
      ├─ Phase 4: frontier-batched Eclat per shard under
      │           jax.shard_map / vmap, multi_support kernels  core/eclat.py
      └─ rebalance: telemetry-driven donation of queued PBEC
                    subtrees between shard queues              rebalance.py
    merge: all shards' FI buffers + frequent ancestors ──► one FITable

Every device buffer is **static-shape**: the per-round class table is padded
to ``P·chunk`` rows and the seed slabs to ``[P, chunk, I]``, so each phase
compiles exactly once and rounds replay the same executables (DESIGN.md,
"Distributed mining").  Donating a class re-runs the Phase-3 exchange for the
round that mines it, so ownership changes never mine a stale slab — results
stay bit-exact w.r.t. single-device ``fimi.run`` regardless of how many
donations the rebalancer makes.

The SPMD combinator is pluggable exactly as in ``core.fimi``: ``vmap`` for
P virtual miners on one device, ``shard_map`` over a real miner mesh when
enough devices exist (``launch/cluster_mine.py`` forks host devices).
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from functools import partial
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitmap as bm
from repro.core import eclat, fimi, phases
from repro.cluster import checkpoint as checkpoint_mod
from repro.cluster import planner as planner_mod
from repro.cluster import rebalance as rebalance_mod
from repro.obs import metrics as obs_metrics
from repro.obs import profile as obs_profile
from repro.obs import progress as obs_progress
from repro.obs import trace as obs_trace

AXIS = fimi.AXIS  # the miner mesh axis name ("miners")


@dataclasses.dataclass(frozen=True)
class ClusterParams:
    """Executor knobs on top of the planner's."""

    planner: planner_mod.PlannerParams = planner_mod.PlannerParams()
    eclat: eclat.EclatConfig = eclat.EclatConfig(
        max_out=1 << 14, max_stack=4096, frontier_size=16
    )
    exchange_capacity: Optional[int] = None  # Phase-3 per-(src,dst) row cap
    chunk: Optional[int] = None     # classes per shard per round (None: auto)
    rebalance: bool = True          # telemetry-driven queue donation
    skew_threshold: float = 1.25    # rebalance when max/mean exceeds this
    max_donations: int = 8          # bounded moves per inter-round pass
    max_rounds: int = 128           # hard bound on mining rounds
    target_rounds: int = 4          # auto-chunk aims for this many rounds
    use_mxu: bool = False           # MXU unpack-dot multi-support kernel
    force: Optional[str] = None     # kernel backend pin (kernels.ops)
    strict: bool = True             # raise on any overflow (exactness guard)


@dataclasses.dataclass(frozen=True)
class FITable:
    """The merged global mining result — one table, every shard's FIs.

    Supports are **bit-exact** full-database counts: Phase 4 mines each class
    on the slab of all transactions containing its prefix, which preserves
    the support of every itemset in the class (thesis Prop. 8.1).
    """

    masks: np.ndarray       # uint32 [F, IW] packed itemset masks
    supports: np.ndarray    # int64 [F]
    n_items: int
    n_tx: int

    @property
    def n_fis(self) -> int:
        return int(self.masks.shape[0])

    def to_dict(self) -> Dict[frozenset, int]:
        """Materialize as {frozenset(items): support} (tests / serving glue)."""
        out: Dict[frozenset, int] = {}
        if self.n_fis == 0:
            return out
        dense = np.asarray(
            bm.unpack_bool(jnp.asarray(self.masks), self.n_items)
        ).reshape(self.n_fis, self.n_items)
        for row, s in zip(dense, self.supports):
            out[frozenset(np.nonzero(row)[0].tolist())] = int(s)
        assert len(out) == self.n_fis, "duplicate itemsets in merged FITable"
        return out


@dataclasses.dataclass
class RoundStats:
    """Telemetry of one mining round (driver- and benchmark-observable)."""

    round_index: int
    classes_mined: List[int]        # per shard
    work_iters: np.ndarray          # int [P] — DFS trips (the load metric)
    est_mined: np.ndarray           # float [P] — planner units mined
    replication: float              # Phase-3 Σ|D'_i| / |D| for this round
    donations: List[rebalance_mod.Donation]
    mine_ms: float = 0.0            # this round's mine-phase wall (host)


@dataclasses.dataclass
class ClusterReport:
    """What the executor observed, for the driver/benchmark to print."""

    P: int
    backend: str                    # "shard_map" | "vmap"
    rounds: List[RoundStats]
    phase_ms: Dict[str, float]      # plan / exchange / mine / merge
    est_loads: np.ndarray           # float [P] — planner prediction
    observed_loads: np.ndarray      # float [P] — cumulative DFS trips
    donations: List[rebalance_mod.Donation]
    exchange_overflow: int
    mine_overflow: int

    @property
    def n_rounds(self) -> int:
        return len(self.rounds)

    @property
    def imbalance(self) -> float:
        """max/mean of observed per-shard load (1.0 = perfect)."""
        mean = float(self.observed_loads.mean())
        return float(self.observed_loads.max()) / mean if mean > 0 else 1.0

    @property
    def makespan_trips(self) -> float:
        """Modeled makespan: Σ_r max_p trips(r, p) — rounds are barriers."""
        return float(
            sum(float(np.max(r.work_iters)) for r in self.rounds)
        )

    def estimation_error(self) -> float:
        """Relative error between predicted and observed load *shares*.

        ``max_p |est_share_p − obs_share_p|`` — the planner is judged on the
        distribution it balanced, not on absolute trip counts (estimates are
        in sample-FI units, observations in DFS trips).
        """
        est, obs = self.est_loads.astype(float), self.observed_loads.astype(float)
        if est.sum() <= 0 or obs.sum() <= 0:
            return 0.0
        return float(np.abs(est / est.sum() - obs / obs.sum()).max())

    def snapshot(self) -> Dict[str, dict]:
        """This report in the canonical metrics-snapshot shape.

        The properties above (``imbalance``, ``makespan_trips``, …) stay the
        ergonomic views; this is the machine-readable form every subsystem
        shares (``repro.obs.metrics.snapshot()``), so run records and
        ``obs_report`` diff cluster telemetry like any other metric.
        """
        counters = {
            "cluster/donations": len(self.donations),
            "cluster/exchange_overflow": int(self.exchange_overflow),
            "cluster/mine_overflow": int(self.mine_overflow),
            "cluster/rounds": self.n_rounds,
        }
        gauges = {
            "cluster/imbalance": self.imbalance,
            "cluster/makespan_trips": self.makespan_trips,
            "cluster/load/estimation_error": self.estimation_error(),
        }
        for phase, ms in self.phase_ms.items():
            gauges[f"cluster/phase_ms/{phase}"] = float(ms)
        for p in range(self.P):
            gauges[f"cluster/shard{p}/est_load"] = float(self.est_loads[p])
            gauges[f"cluster/shard{p}/obs_load"] = float(self.observed_loads[p])
        for r in self.rounds:
            # per-round detail the speedup waterfall's compile term needs
            gauges[f"cluster/round{r.round_index}/mine_ms"] = float(r.mine_ms)
            gauges[f"cluster/round{r.round_index}/max_trips"] = (
                float(np.max(r.work_iters)) if len(r.work_iters) else 0.0
            )
        hist = obs_metrics.Histogram("cluster/round_makespan_trips")
        for r in self.rounds:
            hist.record(float(np.max(r.work_iters)) if len(r.work_iters) else 0.0)
        # the additive speedup-loss decomposition rides along: every run
        # record with cluster gauges also carries its own waterfall
        from repro.obs import speedup as speedup_mod

        wf = speedup_mod.from_snapshot(
            {"counters": counters, "gauges": gauges, "histograms": {}}
        )
        if wf is not None:
            gauges.update(wf.gauges())
        return {
            "counters": counters,
            "gauges": gauges,
            "histograms": {hist.name: hist.summary()},
        }

    def emit(self, reg: Optional[obs_metrics.MetricsRegistry] = None) -> None:
        """Publish this report into the (default: global) metrics registry."""
        reg = reg if reg is not None else obs_metrics.registry()
        snap = self.snapshot()
        for name, v in snap["counters"].items():
            reg.counter(name).inc(int(v))
        for name, v in snap["gauges"].items():
            reg.gauge(name).set(float(v))
        h = reg.histogram("cluster/round_makespan_trips")
        for r in self.rounds:
            h.record(float(np.max(r.work_iters)) if len(r.work_iters) else 0.0)

    def republish_gauges(
        self, reg: Optional[obs_metrics.MetricsRegistry] = None
    ) -> None:
        """Re-set the gauge family (gauges only — counters/histograms would
        double-count).  Drivers call this after back-patching ``phase_ms``
        with work that happened outside :func:`execute` (off-disk planning,
        block-streamed assembly), so the recorded waterfall charges it to
        ``host_tail`` instead of the unexplained driver residual."""
        reg = reg if reg is not None else obs_metrics.registry()
        for name, v in self.snapshot()["gauges"].items():
            reg.gauge(name).set(float(v))


@dataclasses.dataclass
class ClusterResult:
    table: FITable
    plan: planner_mod.MiningPlan
    report: ClusterReport


#: process-wide count of cluster mines: the ``mine`` arg of their spans
_MINES = itertools.count(1)


def _auto_spmd(P: int, spmd, mesh):
    """Resolve the SPMD combinator: real devices when available, else vmap."""
    if spmd is not None:
        return spmd, mesh, ("shard_map" if spmd is fimi.shard_map_spmd else "vmap")
    if len(jax.devices()) >= P:
        from repro.launch.mesh import make_miner_mesh

        return fimi.shard_map_spmd, make_miner_mesh(P), "shard_map"
    return fimi.vmap_spmd, None, "vmap"


def execute(
    tx_shards: jnp.ndarray,   # uint32[P, T, IW] — horizontal packed D_i shards
    n_items: int,
    params: ClusterParams,
    key: jax.Array,
    *,
    spmd=None,
    mesh=None,
    plan: Optional[planner_mod.MiningPlan] = None,
    checkpoint_dir: Optional[str] = None,
    resume: bool = False,
    round_hook: Optional[Callable[[int], None]] = None,
    progress_cb: Optional[
        Callable[[obs_progress.ProgressSnapshot], None]
    ] = None,
    mine: Optional[int] = None,
) -> ClusterResult:
    """Run the full distributed pipeline; returns table + plan + telemetry.

    Fault tolerance (DESIGN.md, "Failure model"): with ``checkpoint_dir``
    set, the complete inter-round state is persisted atomically after every
    round; ``resume=True`` restores the latest checkpoint (plan-hash
    guarded) and replays only the remaining rounds, **bit-exact** with the
    uninterrupted run — round keys are derived from the round index, the
    chunk width from the plan, and donations from the restored ledger.
    ``round_hook(r)`` is called after round ``r`` is checkpointed; the
    fault harness raises from it to simulate a mid-run death.

    ``progress_cb`` receives a live :class:`ProgressSnapshot` after every
    round — the drivers print its ``line()`` — fed from the planner's
    estimated loads and the observed per-round completions (ETA math in
    :mod:`repro.obs.progress`).

    While tracing, every ``cluster/*`` span carries ``mine``, this mine's
    number in the process (``mine_store`` passes its own).  ``cluster/plan``
    wraps the plan only when the executor makes it; ``cluster/exchange``
    carries ``rows_moved`` (rows that left their miner), ``replication`` and
    ``overflow``; ``cluster/mine`` covers the round's mine and the
    rebalancer's decision after it, with ``trips`` (per miner) and
    ``donations``.  Those args are read off the device only while tracing.
    """
    P, T, IW = tx_shards.shape
    spmd, mesh, backend = _auto_spmd(P, spmd, mesh)
    if mesh is not None:
        from repro.store.reader import place_on_mesh

        tx_shards = place_on_mesh(tx_shards, mesh)
    phase_ms = {"plan": 0.0, "exchange": 0.0, "mine": 0.0, "merge": 0.0}

    tr = obs_trace.TRACER
    mine = next(_MINES) if mine is None else mine
    t0 = time.perf_counter()
    if plan is None:
        with tr.span("cluster/plan", P=P, backend=backend, mine=mine):
            plan = planner_mod.plan(
                tx_shards,
                n_items,
                dataclasses.replace(params.planner),
                key,
            )
    phase_ms["plan"] = (time.perf_counter() - t0) * 1e3
    classes = plan.classes
    est_sizes = plan.est_sizes
    queues = plan.shard_queues()

    maxlen = max((len(q) for q in queues), default=0)
    if params.chunk is not None:
        chunk = max(1, params.chunk)
    elif params.rebalance and maxlen > 1:
        chunk = max(1, -(-maxlen // max(params.target_rounds, 1)))
    else:
        chunk = max(1, maxlen)
    assert chunk <= params.eclat.max_stack, "chunk exceeds miner stack capacity"

    # one-time device constants / mapped phase programs
    cap = params.exchange_capacity or T
    local_valid = jnp.ones((P, T), jnp.bool_)
    minsup_b = jnp.broadcast_to(jnp.asarray(plan.abs_minsup, jnp.int32), (P,))
    A = plan.ancestor_masks.shape[0]
    anc_b = jnp.broadcast_to(
        jnp.asarray(plan.ancestor_masks), (P, A, n_items)
    )
    from repro.kernels import ops

    _, multi_support_fn = ops.support_fns(params.force, params.use_mxu)
    p3 = spmd(
        partial(phases.phase3_exchange, axis_name=AXIS, capacity=cap), P, mesh
    )
    p4 = spmd(
        partial(
            phases.phase4_mine,
            axis_name=AXIS,
            n_items=n_items,
            eclat_cfg=params.eclat,
            multi_support_fn=multi_support_fn,
        ),
        P,
        mesh,
    )

    C_round = P * chunk  # padded class-table width, static across rounds
    ledger = rebalance_mod.LoadLedger(P)
    rounds: List[RoundStats] = []
    donations: List[rebalance_mod.Donation] = []
    fi_masks: List[np.ndarray] = []
    fi_supports: List[np.ndarray] = []
    exchange_overflow = 0
    mine_overflow = 0
    anc_supports: Optional[np.ndarray] = None

    plan_hash = (
        checkpoint_mod.plan_fingerprint(plan) if checkpoint_dir else ""
    )
    r = 0
    if resume and checkpoint_dir:
        state = checkpoint_mod.load(checkpoint_dir, plan_hash=plan_hash)
        if state is not None:
            # chunk/C_round above are pure functions of the plan, so the
            # restored queues slot into the same static-shape executables
            r = state.round_index
            queues = state.queues
            if state.fi_masks.shape[0]:
                fi_masks = [np.asarray(state.fi_masks, np.uint32)]
                fi_supports = [np.asarray(state.fi_supports, np.int64)]
            anc_supports = state.anc_supports
            ledger.observed[:] = state.observed
            ledger.est_mined[:] = state.est_mined
            exchange_overflow = state.exchange_overflow
            mine_overflow = state.mine_overflow
            rounds = list(state.rounds)
            donations = list(state.donations)

    progress = obs_progress.ProgressEstimator(plan.est_loads)
    progress.start()
    if r > 0:
        # resumed mid-run: credit the restored rounds as one bulk update so
        # frac/straggler pick up where the dead run left off (the warm-up
        # discount then treats this replay credit like compile time)
        progress.update(ledger.est_mined, ledger.observed)

    while any(queues) and r < params.max_rounds:
        take = [q[:chunk] for q in queues]
        queues = [q[chunk:] for q in queues]

        # ---- padded static class table for this round's exchange ----------
        round_ids = [cid for ids in take for cid in ids]
        prefix_rows = np.zeros((C_round, n_items), dtype=bool)
        class_valid = np.zeros((C_round,), dtype=bool)
        class_assign = np.zeros((C_round,), dtype=np.int32)
        k = 0
        for p, ids in enumerate(take):
            for cid in ids:
                prefix_rows[k] = classes[cid].prefix
                class_valid[k] = True
                class_assign[k] = p
                k += 1
        prefix_packed = np.asarray(bm.pack_bool(jnp.asarray(prefix_rows)))

        t0 = time.perf_counter()
        with tr.span("cluster/exchange", round=r, classes=len(round_ids),
                     mine=mine) as sp:
            out3 = p3(
                tx_shards,
                local_valid,
                jnp.broadcast_to(
                    jnp.asarray(prefix_packed),
                    (P, C_round, prefix_packed.shape[-1]),
                ),
                jnp.broadcast_to(jnp.asarray(class_valid), (P, C_round)),
                jnp.broadcast_to(jnp.asarray(class_assign), (P, C_round)),
            )
            out3 = jax.block_until_ready(out3)
            if tr.enabled:
                # recv_counts[dst, src]: rows dst took from src; the
                # diagonal never left its chip
                recv, repl, over = jax.device_get(
                    (out3.recv_counts, out3.replication, out3.overflow))
                recv = np.asarray(recv).reshape(P, P)
                sp.set(rows_moved=int(recv.sum() - np.trace(recv)),
                       replication=float(np.reshape(repl, -1)[0]),
                       overflow=int(np.reshape(over, -1)[0]))
        phase_ms["exchange"] += (time.perf_counter() - t0) * 1e3

        # ---- Phase 4: mine this round's classes on the received slabs -----
        seed_prefix, seed_ext, seed_valid = planner_mod.pack_seeds(
            classes, take, n_items, chunk
        )
        keys4 = jax.vmap(lambda i: jax.random.fold_in(key, i))(
            r * P + jnp.arange(P)
        )
        with tr.span("cluster/mine", round=r, chunk=chunk, mine=mine) as sp:
            mine_t0 = time.perf_counter()
            out4 = p4(
                out3.slab.reshape(P, -1, IW),
                out3.slab_valid.reshape(P, -1),
                tx_shards,
                local_valid,
                jnp.asarray(seed_prefix),
                jnp.asarray(seed_ext),
                jnp.asarray(seed_valid),
                anc_b,
                minsup_b,
                keys4,
            )
            out4 = jax.device_get(out4)
            mine_s = time.perf_counter() - mine_t0
            trips = np.asarray(out4.work_iters).reshape(P).astype(np.float64)
            est_mined = np.array(
                [sum(max(float(est_sizes[c]), 1.0) for c in ids)
                 for ids in take]
            )
            ledger.record_round(trips, est_mined)
            moved: List[rebalance_mod.Donation] = []
            if params.rebalance and any(queues):
                moved = rebalance_mod.rebalance(
                    queues,
                    est_sizes,
                    ledger,
                    round_index=r,
                    skew_threshold=params.skew_threshold,
                    max_donations=params.max_donations,
                )
            sp.set(trips=trips.astype(int).tolist(),
                   pushed=np.asarray(out4.nodes_pushed).reshape(P)
                   .astype(int).tolist(),
                   donations=len(moved))
        phase_ms["mine"] += mine_s * 1e3

        exchange_overflow += int(np.asarray(out3.overflow).reshape(-1)[0])
        counts = np.asarray(out4.fi_count).reshape(P)
        totals = np.asarray(out4.fi_total).reshape(P)
        mine_overflow += int((totals - counts).sum()) + int(
            np.asarray(out4.overflow).sum()
        )
        items = np.asarray(out4.fi_items).reshape(P, -1, IW)
        supps = np.asarray(out4.fi_supports).reshape(P, -1)
        for p in range(P):
            n = int(counts[p])
            if n:
                fi_masks.append(items[p, :n])
                fi_supports.append(supps[p, :n])
        anc_supports = np.asarray(out4.prefix_supports).reshape(P, -1)[0]

        snap = progress.update(est_mined, trips)
        if progress_cb is not None:
            progress_cb(snap)
        if obs_profile.PROFILER.enabled:
            # The multi-support kernel runs once per DFS trip inside the
            # compiled Phase-4 while_loop; attribute this round's mine wall
            # time to those executions (shapes from the per-shard slab).
            obs_profile.PROFILER.observe_loop(
                "multi",
                {
                    "K": max(1, int(params.eclat.frontier_size)),
                    "I": n_items,
                    "W": (int(out3.slab.reshape(P, -1, IW).shape[1]) + 31)
                    // 32,
                },
                n_exec=int(trips.sum()),
                wall_s=mine_s,
            )

        if tr.enabled:
            # Modeled per-shard lanes: shards run the round in lockstep, so
            # shard p's busy fraction is its DFS-trip share of the slowest
            # shard — the rendered lane gaps ARE the round's imbalance.
            t_max = max(float(trips.max()), 1.0)
            for p in range(P):
                tr.add_span(
                    "cluster/mine",
                    mine_t0,
                    mine_s * float(trips[p]) / t_max,
                    track=f"shard{p}",
                    args={
                        "round": r,
                        "trips": int(trips[p]),
                        "classes": len(take[p]),
                        "est_mined": float(est_mined[p]),
                    },
                )

        donations.extend(moved)
        for d in moved:
            tr.instant(
                "cluster/donate",
                round=d.round_index, class_id=d.class_id,
                src=d.src, dst=d.dst,
            )
        rounds.append(
            RoundStats(
                round_index=r,
                classes_mined=[len(ids) for ids in take],
                work_iters=trips.astype(np.int64),
                est_mined=est_mined,
                replication=float(np.asarray(out3.replication).reshape(-1)[0]),
                donations=moved,
                mine_ms=mine_s * 1e3,
            )
        )
        r += 1
        if checkpoint_dir:
            checkpoint_mod.save(
                checkpoint_dir,
                checkpoint_mod.RoundState(
                    round_index=r,
                    queues=queues,
                    fi_masks=(
                        np.concatenate(fi_masks, axis=0)
                        if fi_masks else np.zeros((0, IW), np.uint32)
                    ),
                    fi_supports=(
                        np.concatenate(fi_supports, axis=0)
                        if fi_supports else np.zeros((0,), np.int64)
                    ),
                    anc_supports=anc_supports,
                    observed=ledger.observed,
                    est_mined=ledger.est_mined,
                    exchange_overflow=exchange_overflow,
                    mine_overflow=mine_overflow,
                    rounds=rounds,
                    donations=donations,
                ),
                plan_hash,
            )
        if round_hook is not None:
            round_hook(r - 1)
    assert not any(queues), "max_rounds exhausted with classes still queued"
    progress.finish()

    if params.strict and (exchange_overflow or mine_overflow):
        raise RuntimeError(
            f"cluster executor overflow (exchange={exchange_overflow}, "
            f"mine={mine_overflow}): raise exchange_capacity / eclat.max_out "
            f"/ eclat.max_stack — the result would not be exact"
        )

    # ---- merge: one global table = all shards' FIs + frequent ancestors ---
    t0 = time.perf_counter()
    with tr.span("cluster/merge", mine=mine):
        if anc_supports is None:  # no classes ⇒ still need prefix supports
            anc_supports = np.zeros((A,), np.int64)
        n_anc = plan.n_ancestors
        anc_keep = np.zeros((A,), bool)
        anc_keep[:n_anc] = anc_supports[:n_anc] >= plan.abs_minsup
        if anc_keep.any():
            fi_masks.append(np.asarray(
                bm.pack_bool(jnp.asarray(plan.ancestor_masks[anc_keep]))))
            fi_supports.append(anc_supports[anc_keep])
        if fi_masks:
            masks = np.concatenate(fi_masks, axis=0).astype(np.uint32)
            supports = np.concatenate(fi_supports, axis=0).astype(np.int64)
        else:
            masks = np.zeros((0, bm.n_words(n_items)), np.uint32)
            supports = np.zeros((0,), np.int64)
        table = FITable(
            masks=masks, supports=supports, n_items=n_items, n_tx=plan.n_tx
        )
    phase_ms["merge"] = (time.perf_counter() - t0) * 1e3

    report = ClusterReport(
        P=P,
        backend=backend,
        rounds=rounds,
        phase_ms=phase_ms,
        est_loads=plan.est_loads,
        observed_loads=ledger.observed.copy(),
        donations=donations,
        exchange_overflow=exchange_overflow,
        mine_overflow=mine_overflow,
    )
    report.emit()
    return ClusterResult(table=table, plan=plan, report=report)


def mine_store(
    store,
    params: ClusterParams,
    key: jax.Array,
    P: int,
    *,
    adjust_plan: Optional[
        Callable[[planner_mod.MiningPlan], planner_mod.MiningPlan]
    ] = None,
    **execute_kw,
) -> ClusterResult:
    """Mine an on-disk :class:`repro.store.TxStore` on P miners, end to end.

    Plans off disk (the Thm 6.1 sample gathered block by block, bit-exact
    with the in-RAM sample), assembles the ``[P, T, IW]`` row shards through
    the double-buffered reader (two blocks on the host), places them one
    per device of the miner mesh (``shard_map`` when at least P devices
    exist, else ``vmap`` on one), runs the rounds and merges into one
    :class:`FITable`.  ``adjust_plan``
    rewrites the plan before any round (fault injection); ``execute_kw``
    goes to :func:`execute` (checkpointing, hooks, progress).

    ``report.phase_ms`` charges ``plan`` to the off-disk planning and adds
    ``assemble``.  While tracing the mine is span ``cluster/run``, with
    ``cluster/plan`` and ``cluster/assemble`` inside it beside the
    executor's spans, all carrying ``mine``; those two also carry ``crc``,
    the CRC32C path that verified their block reads (``"c"`` or
    ``"numpy"``).
    """
    from repro.store import checksum, reader as store_reader

    mine = next(_MINES)
    tr = obs_trace.TRACER
    with tr.span("cluster/run", P=P, mine=mine):
        t0 = time.perf_counter()
        with tr.span("cluster/plan", P=P, mine=mine,
                     crc=checksum.IMPLEMENTATION):
            plan = planner_mod.plan(store, None, params.planner, key, P=P)
            if adjust_plan is not None:
                plan = adjust_plan(plan)
        t1 = time.perf_counter()
        spmd, mesh, _ = _auto_spmd(P, None, None)
        with tr.span("cluster/assemble", P=P, mine=mine,
                     crc=checksum.IMPLEMENTATION):
            shards = store_reader.to_device_shards(store, P)
            if mesh is not None:
                shards = store_reader.place_on_mesh(shards, mesh)
            shards = jax.block_until_ready(shards)
        t2 = time.perf_counter()
        res = execute(shards, store.n_items, params, key, spmd=spmd,
                      mesh=mesh, plan=plan, mine=mine, **execute_kw)
    res.report.phase_ms["plan"] = (t1 - t0) * 1e3
    res.report.phase_ms["assemble"] = (t2 - t1) * 1e3
    res.report.republish_gauges()
    return res


# ---------------------------------------------------------------------------
# StreamingMiner integration — the distributed re-miner
# ---------------------------------------------------------------------------


def cluster_mine_fn(
    P: int = 4,
    cluster_params: Optional[ClusterParams] = None,
    seed: int = 0,
) -> Callable:
    """A ``StreamingMiner.mine_fn`` that re-mines the window distributed.

    Shards the materialized window row-wise over the P miners and runs the
    full planner → exchange → shard-mine → rebalance pipeline; drift-triggered
    re-mines then scale with the mesh instead of a single device.
    ``cluster_params`` overrides everything except ``min_support_rel``, which
    is always derived from the trigger's absolute minsup.
    """

    def mine(window, abs_minsup: int) -> Dict[frozenset, int]:
        n_tx = window.n_tx
        assert n_tx % P == 0, f"window size {n_tx} not divisible by P={P}"
        shards = window.rows().reshape(P, n_tx // P, window.n_words)
        base = cluster_params or ClusterParams(
            planner=planner_mod.PlannerParams(
                n_db_sample=min(1024, n_tx), n_fi_sample=512
            )
        )
        # (abs−0.5)/n_tx survives the float round-trip: the planner's
        # ceil(rel·n_tx) lands exactly on abs_minsup, whereas abs/n_tx can
        # ceil to abs+1 and silently drop itemsets at exactly abs_minsup
        params = dataclasses.replace(
            base,
            planner=dataclasses.replace(
                base.planner, min_support_rel=(abs_minsup - 0.5) / n_tx
            ),
        )
        res = execute(
            shards, window.n_items, params, jax.random.PRNGKey(seed)
        )
        return res.table.to_dict()

    return mine

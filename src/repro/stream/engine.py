"""StreamingMiner — keep the serving layer fresh against a live stream.

Orchestrates the loop the subsystem exists for (DESIGN.md, "Streaming
subsystem")::

    admit block ─→ fused delta-support update ─→ drift check ─→ (on trigger)
        full re-mine of the window ─→ build standby indexes ─→ atomic
        hot-swap inside the QueryEngine (generation bump + cache clear)

Between re-mines the serving indexes are **immutable** — queries stay pure
vector work against frozen device arrays — while a host-side support vector
tracks the *exact* current window supports of every indexed itemset via the
``[2, F]`` arrive/expire kernel (``kernels/delta_support.py``).  That exact
vector feeds the monitor's border signal and the staleness report; the
sample-based Thm 6.1 signal needs no exact state at all.

Re-mining is pluggable: ``mine_fn(window, abs_minsup) -> {frozenset: supp}``
defaults to the full Parallel-FIMI pipeline over the window
(:func:`fimi_mine_fn`); tests inject the brute-force oracle.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitmap as bm
from repro.kernels import ops
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.serve.cache import QueryCache
from repro.serve.engine import QueryEngine
from repro.serve.index import build_indexes
from repro.stream.monitor import DriftMonitor, DriftVerdict
from repro.stream.window import SlidingWindow, WindowSpill

MineFn = Callable[[SlidingWindow, int], Dict[frozenset, int]]


@dataclasses.dataclass(frozen=True)
class StreamParams:
    """Knobs of the streaming subsystem (window ∪ monitor ∪ serving)."""

    n_blocks: int = 8               # ring length B (window = B·block_tx tx)
    block_tx: int = 256             # transactions per stream block
    min_support_rel: float = 0.1
    min_confidence: float = 0.6
    eps: float = 0.1                # staleness tolerance ε (monitor)
    delta: float = 0.05             # confidence 1−δ (Thm 6.1)
    border_margin: float = 0.0      # exact border tracking width (0 = off)
    border_hysteresis: float = 0.0  # crossing must clear minsup by this much
    check_every: int = 1            # drift-check cadence in blocks
    cooldown_blocks: int = 0        # suppress triggers this long after a mine
    batch: int = 256                # QueryEngine dispatch width
    top_k: int = 5
    cache_capacity: int = 2048
    force: Optional[str] = None     # kernel backend pin (kernels.ops),
    #                                 re-mines included
    spill_dir: Optional[str] = None  # persist expired blocks to a TxStore
    seed: int = 0


@dataclasses.dataclass
class AdmitEvent:
    """What one :meth:`StreamingMiner.admit` did (driver-observable)."""

    block_index: int
    expired: bool                   # an old block left the window
    delta_applied: bool             # supports updated in place
    verdict: Optional[DriftVerdict]
    remined: bool
    remine_reason: Optional[str]    # "initial" | "error" | "border" | "recovery"
    mine_ms: float = 0.0            # re-mine + standby index build
    swap_ms: float = 0.0            # the atomic publish itself
    generation: int = 0


@dataclasses.dataclass
class StreamStats:
    blocks_in: int = 0
    tx_in: int = 0
    remines: int = 0
    drift_checks: int = 0
    fired_error: int = 0
    fired_border: int = 0
    fired_recovery: int = 0   # re-mines forced by an empty mined table
    mine_ms: List[float] = dataclasses.field(default_factory=list)
    swap_ms: List[float] = dataclasses.field(default_factory=list)

    def as_dict(self) -> dict:
        return {
            "blocks_in": self.blocks_in,
            "tx_in": self.tx_in,
            "remines": self.remines,
            "drift_checks": self.drift_checks,
            "fired_error": self.fired_error,
            "fired_border": self.fired_border,
            "fired_recovery": self.fired_recovery,
            "mine_ms_mean": float(np.mean(self.mine_ms)) if self.mine_ms else 0.0,
            "swap_ms_max": float(np.max(self.swap_ms)) if self.swap_ms else 0.0,
        }


def fimi_mine_fn(
    P: int = 4, fimi_params=None, seed: int = 0, force: Optional[str] = None
) -> MineFn:
    """Default re-miner: the full Parallel-FIMI pipeline over the window.

    Shards the materialized window row-wise over ``P`` (virtual) miners and
    runs the four-phase pipeline (``core.fimi.run``) with ``materialize=True``.
    ``fimi_params`` overrides everything except ``min_support_rel``, which is
    always derived from the trigger's absolute minsup; ``force`` pins the
    default params' kernels.
    """
    from repro.core import eclat, fimi

    def mine(window: SlidingWindow, abs_minsup: int) -> Dict[frozenset, int]:
        n_tx = window.n_tx
        assert n_tx % P == 0, f"window size {n_tx} not divisible by P={P}"
        rows = window.rows()
        shards = rows.reshape(P, n_tx // P, window.n_words)
        base = fimi_params or fimi.FimiParams(
            n_db_sample=min(1024, n_tx),
            n_fi_sample=512,
            eclat=eclat.EclatConfig(
                max_out=1 << 14, max_stack=4096, frontier_size=16
            ),
            force=force,
        )
        # (abs−0.5)/n_tx survives the float round-trip: fimi.run's
        # ceil(rel·n_tx) lands exactly on abs_minsup, whereas abs/n_tx can
        # ceil to abs+1 and silently drop itemsets at exactly abs_minsup
        params = dataclasses.replace(
            base, min_support_rel=(abs_minsup - 0.5) / n_tx
        )
        res = fimi.run(
            shards, window.n_items, params, jax.random.PRNGKey(seed),
            materialize=True,
        )
        return res.fi_dict

    return mine


class StreamingMiner:
    """The streaming control loop: window + monitor + serving engine.

    Life cycle: admit blocks; once the window first fills, mine it and bring
    the :class:`~repro.serve.engine.QueryEngine` up (generation 0).  Every
    later admit evicts the oldest block, applies the fused arrive/expire
    support delta, and (on the configured cadence) runs the drift check;
    a trigger re-mines the *current* window into standby indexes and
    hot-swaps them in.  ``engine`` is None until the first mine completes.
    """

    def __init__(
        self,
        params: StreamParams,
        n_items: int,
        *,
        mine_fn: Optional[MineFn] = None,
    ):
        self.params = params
        self.n_items = n_items
        self.window = SlidingWindow.empty(
            params.n_blocks, params.block_tx, n_items
        )
        self.monitor = DriftMonitor(
            params.n_blocks,
            params.block_tx,
            eps=params.eps,
            delta=params.delta,
            border_margin=params.border_margin,
            border_hysteresis=params.border_hysteresis,
            seed=params.seed,
        )
        self.mine_fn = mine_fn or fimi_mine_fn(
            seed=params.seed, force=params.force
        )
        # store-backed spill: evicted blocks persist as the stream's history
        self.spill: Optional[WindowSpill] = (
            WindowSpill(params.spill_dir, params.block_tx, n_items)
            if params.spill_dir
            else None
        )
        self.cache = QueryCache(capacity=params.cache_capacity)
        self.engine: Optional[QueryEngine] = None
        self.current_supports: Optional[np.ndarray] = None  # int64[F], exact
        self.stats = StreamStats()
        self._since_check = 0
        self._since_remine = 0

    # -- views ----------------------------------------------------------------
    @property
    def abs_minsup(self) -> int:
        return int(np.ceil(self.params.min_support_rel * self.window.n_tx))

    def _index_masks(self) -> jnp.ndarray:
        """Valid rows of the serving FI mask slab (drops shape padding)."""
        idx = self.engine.index
        return idx.masks[: idx.n_fis]

    def served_rel_supports(self) -> np.ndarray:
        """float64[F] — what the serving index claims (mine-time snapshot)."""
        idx = self.engine.index
        return (
            np.asarray(idx.supports)[: idx.n_fis].astype(np.float64) / idx.n_tx
        )

    def current_rel_supports(self) -> np.ndarray:
        """float64[F] — exact delta-maintained window supports, relative."""
        return self.current_supports.astype(np.float64) / self.window.n_tx

    def exact_window_supports(self) -> np.ndarray:
        """int64[F] — offline oracle: full recompute over the whole window.

        O(window) work — this is the per-block cost the delta kernel avoids
        (benchmarks/stream.py); used for staleness reporting and invariants.
        """
        counts = ops.block_itemset_supports(
            self.window.stacked(), self._index_masks(), force=self.params.force
        )
        return np.asarray(counts).sum(axis=0).astype(np.int64)

    def staleness(self) -> float:
        """max |served_rel − true current rel support| over indexed FIs."""
        if self.engine is None or self.engine.index.n_fis == 0:
            return 0.0
        true_rel = (
            self.exact_window_supports().astype(np.float64) / self.window.n_tx
        )
        return float(np.abs(self.served_rel_supports() - true_rel).max())

    # -- the control loop ------------------------------------------------------
    def admit(self, block) -> AdmitEvent:
        """Ingest one stream block (dense bool [T, I] or packed uint32 [T, IW])."""
        block = np.asarray(block)
        if block.dtype != np.uint32:
            block = np.asarray(bm.pack_bool(jnp.asarray(block, jnp.bool_)))
        arrive = jnp.asarray(block, jnp.uint32)

        self.window, expired = self.window.admit(arrive)
        if expired is not None and self.spill is not None:
            self.spill.append(expired)
        self.monitor.admit(block)
        self.stats.blocks_in += 1
        self.stats.tx_in += self.window.block_tx
        reg = obs_metrics.registry()
        reg.counter("stream/blocks_in").inc()
        reg.counter("stream/tx_in").inc(self.window.block_tx)
        ev = AdmitEvent(
            block_index=self.stats.blocks_in - 1,
            expired=expired is not None,
            delta_applied=False,
            verdict=None,
            remined=False,
            remine_reason=None,
        )

        if self.engine is None:
            if self.window.full:
                self._remine("initial", ev)
            return self._stamp(ev)

        # steady state: engine exists ⇒ the window was full ⇒ every admit evicts
        assert expired is not None
        F = self.engine.index.n_fis
        if F:
            counts = ops.delta_supports(
                arrive, expired, self._index_masks(), force=self.params.force
            )
            counts = np.asarray(counts).astype(np.int64)
            self.current_supports += counts[0] - counts[1]
            ev.delta_applied = True
            reg.counter("stream/delta_updates").inc()

        # drift-triggered re-mining is rate-limited: during a drift washout
        # the window keeps changing for B blocks, and re-mining every one of
        # them buys little freshness for full mining cost.
        self._since_remine += 1
        if self._since_remine <= self.params.cooldown_blocks:
            return self._stamp(ev)

        self._since_check += 1
        if self._since_check >= self.params.check_every:
            self._since_check = 0
            if F == 0:
                # an empty mined table has nothing to monitor (no masks to
                # estimate, no border to track) but must not wedge the loop:
                # re-mine unconditionally until the stream yields FIs again
                self.stats.fired_recovery += 1
                self._remine("recovery", ev)
                return self._stamp(ev)
            self.stats.drift_checks += 1
            reg.counter("stream/drift_checks").inc()
            ev.verdict = self.monitor.check(
                self._index_masks(),
                current_rel=self.current_rel_supports(),
                force=self.params.force,
            )
            if ev.verdict.fired:
                if ev.verdict.reason == "border":
                    self.stats.fired_border += 1
                else:
                    self.stats.fired_error += 1
                reg.counter(f"stream/fired_{ev.verdict.reason}").inc()
                obs_trace.TRACER.instant(
                    "stream/drift",
                    reason=ev.verdict.reason,
                    block=ev.block_index,
                )
                self._remine(ev.verdict.reason, ev)
        return self._stamp(ev)

    def _stamp(self, ev: AdmitEvent) -> AdmitEvent:
        ev.generation = self.engine.generation if self.engine else -1
        return ev

    def _remine(self, reason: str, ev: AdmitEvent) -> None:
        """Mine the current window, build standby indexes, hot-swap."""
        t0 = time.perf_counter()
        with obs_trace.TRACER.span("stream/remine", reason=reason,
                                   block=ev.block_index):
            fis = self.mine_fn(self.window, self.abs_minsup)
            fi_idx, rule_idx = build_indexes(
                fis,
                self.n_items,
                self.window.n_tx,
                min_confidence=self.params.min_confidence,
            )
        ev.mine_ms = (time.perf_counter() - t0) * 1e3

        t0 = time.perf_counter()
        with obs_trace.TRACER.span("stream/swap", reason=reason):
            if self.engine is None:
                self.engine = QueryEngine(
                    fi_idx,
                    rule_idx,
                    batch=self.params.batch,
                    top_k=self.params.top_k,
                    force=self.params.force,
                    cache=self.cache,
                )
            else:
                self.engine.swap_indexes(fi_idx, rule_idx)
        ev.swap_ms = (time.perf_counter() - t0) * 1e3
        reg = obs_metrics.registry()
        reg.counter("stream/remines").inc()
        reg.histogram("stream/mine_ms").record(ev.mine_ms)
        reg.histogram("stream/swap_ms").record(ev.swap_ms)

        F = fi_idx.n_fis
        self.current_supports = (
            np.asarray(fi_idx.supports)[:F].astype(np.int64)
        )
        self.monitor.rearm(
            self.served_rel_supports(), self.params.min_support_rel
        )
        self.stats.remines += 1
        self.stats.mine_ms.append(ev.mine_ms)
        self.stats.swap_ms.append(ev.swap_ms)
        ev.remined = True
        ev.remine_reason = reason
        self._since_check = 0
        self._since_remine = 0

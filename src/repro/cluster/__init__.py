"""Distributed mining executor — the paper pipeline over a device mesh.

``planner``   Phase-1/2 control plane: Thm 6.1 database sample → reservoir FI
              sample → PBEC partition → LPT / DB-Repl-Min assignment priced
              by replicated-transaction volume.
``executor``  Phase-3/4 data plane: all_to_all transaction exchange +
              frontier-batched Eclat per shard under ``jax.shard_map`` (or
              vmap simulation), merged into one global :class:`FITable`;
              ``mine_store`` runs plan → assemble → rounds → merge on an
              on-disk store (the launcher's and the benchmark's path).
``rebalance`` Dynamic correction: per-round load telemetry, bounded donation
              of unexplored PBEC subtrees from overloaded to idle shards.
``checkpoint`` Fault tolerance: atomic round-granular checkpoints (CRC32C-
              guarded payload, plan-hash binding) enabling bit-exact resume
              of an interrupted distributed mine.
"""
from repro.cluster.checkpoint import (  # noqa: F401
    CheckpointError,
    RoundState,
    plan_fingerprint,
)
from repro.cluster.executor import (  # noqa: F401
    ClusterParams,
    ClusterReport,
    ClusterResult,
    FITable,
    RoundStats,
    cluster_mine_fn,
    execute,
    mine_store,
)
from repro.cluster.planner import (  # noqa: F401
    MiningPlan,
    PlannerParams,
    pack_seeds,
    plan,
)
from repro.cluster.rebalance import (  # noqa: F401
    Donation,
    LoadLedger,
    rebalance,
    remaining_loads,
)

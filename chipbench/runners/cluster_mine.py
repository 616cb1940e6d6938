"""Batch mining on a miner mesh: the stored log mined again and again by P
miners, one per chip, as users of ``launch/cluster_mine.py --store`` run it.

Each mine is ``repro.cluster.mine_store(store, params, key, P)``: the plan
drawn off disk, the P row shards assembled block by block and placed one
per chip of the miner mesh, the rounds of Phase-3 all-to-all exchange and
Phase-4 Eclat under ``shard_map``, and the merge into one FI table, which
ends on the host.  The work cycle, set-up, ``mine_s``, ``setup_s`` and the
check that decides ``correct`` are those of ``runners/mine.py``.

A traced run mines ``TRACED_MINES`` whole mines, the first of the seed's
cycle, in a profiler session of its own: on four chips the profiler takes
minutes to collect one mine's trace.

A program without ``repro.cluster.mine_store`` cannot run this traffic: the
runner fails on that import before it makes any data.
"""
from __future__ import annotations

import contextlib
import gc
import time

import data
import reference
from runners.mine import cycle

TRACED_MINES = 1


def cluster_params(config: dict):
    from repro import cluster
    from repro.core import eclat

    m = config["mining"]
    return cluster.ClusterParams(
        planner=cluster.PlannerParams(
            min_support_rel=config["minsup"], alpha=m["alpha"],
            scheduler=m["scheduler"],
            n_db_sample=min(m["n_db_sample"], config["dataset"]["n_tx"]),
            n_fi_sample=m["n_fi_sample"]),
        eclat=eclat.EclatConfig(max_out=m["max_out"],
                                max_stack=m["max_stack"],
                                frontier_size=m["frontier_size"]),
        chunk=m["chunk"], rebalance=m["rebalance"],
        skew_threshold=m["skew_threshold"],
        target_rounds=m["target_rounds"],
    )


def run(ctx) -> None:
    """Set up, drive the window, then judge every mine of the window."""
    from repro.cluster import mine_store

    config, traffic = ctx.config, ctx.traffic
    m = config["mining"]
    work = cycle(traffic, ctx.seed)
    stores = {rs: data.store(config, rs, ctx.cache) for rs, _ in work}
    params = cluster_params(config)

    def mine_once(rs, key):
        """One mine; strict params raise on any overflow, and the merged
        table is numpy on the host when it returns."""
        return mine_store(stores[rs], params, key, m["P"])

    for rs, key in work:
        mine_once(rs, key)
    gc.collect()
    gc.freeze()     # set-up's objects stay out of the window's collections
    ctx.setup_done()

    results, ends = [], []
    window = ctx.window()

    def more() -> bool:
        if ctx.trace:
            return len(results) < TRACED_MINES
        return not window.closed() or len(results) % len(work) != 0

    with window:
        while more():
            i = len(results)
            seg = ctx.segment() if ctx.trace else contextlib.nullcontext()
            with seg, ctx.annotate(f"mine.{i}"):
                rs, key = work[i % len(work)]
                results.append((rs, mine_once(rs, key)))
            ends.append(time.perf_counter())
    gc.unfreeze()
    ctx.metric("mine_s", (ends[-1] - window.t0) / len(results), "s")
    ctx.layer_data["mines"] = len(results)
    ctx.info(mines=len(results), mine_end_s=[e - window.t0 for e in ends],
             minsup=config["minsup"],
             abs_minsup=reference.abs_minsup(config["minsup"],
                                             config["dataset"]["n_tx"]),
             rows_seeds=[rs for rs, _ in results],
             n_fis=[r.table.n_fis for _, r in results],
             backend=sorted({r.report.backend for _, r in results}),
             rounds=[r.report.n_rounds for _, r in results],
             scheduler=[r.plan.scheduler_used for _, r in results])
    ctx.read_memory()
    del stores

    want = {rs: data.exact_table(config, rs, ctx.cache) for rs, _ in work}
    bad = [reference.table_mismatches(
        reference.as_dict(r.table.masks, r.table.supports), want[rs],
        r.table.n_fis)
        for rs, r in results]
    ctx.attempted = len(results)
    ctx.failed = sum(1 for b in bad if b)
    ctx.check("mismatched_itemsets", max(bad), 0)
    ctx.info(exact_n_fis={rs: len(w) for rs, w in want.items()})

"""Streaming driver: ingest a drifting stream while serving queries.

The live half of the store-owner scenario: transactions arrive in blocks,
the sliding window advances, mined FI supports are delta-updated in place
(one fused arrive/expire kernel sweep per block), and the drift monitor
decides when the serving table is stale enough to re-mine — at which point
the window is re-mined with the full Parallel-FIMI pipeline and the serving
indexes are hot-swapped under live traffic.  Between admits, a Zipf-hot
query workload is served through the engine + LRU cache (cache keys carry
the swap generation, so a hot-swap can never serve a stale hit).

Reports ingest throughput, re-mine count by trigger reason, swap latency,
staleness (max support error of the served table vs. the offline window
oracle), serving QPS / cache hit rate, and the torn-index parity check
(engine vs. host oracle before and after every swap — must be 0 failures).

  python -m repro.launch.stream_mine --db T2I0.048P50PL10TL16 --support 0.1 \\
      --blocks 8 --blocktx 256 --stream 32 --breaks 16 [-P 4] [--eps 0.1]
"""
from __future__ import annotations

import argparse

from repro.launch.host_devices import preparse_devices

preparse_devices()  # must run before anything imports jax

import time  # noqa: E402

import numpy as np  # noqa: E402


def parity_failures(sm, rng, n_probe=32) -> int:
    """Torn-index check: engine answers vs the host-read index itself.

    Every indexed itemset must look up at exactly its indexed support; a
    torn swap (old FI masks against new supports, or half-published state)
    breaks this immediately.
    """
    idx = sm.engine.index
    if idx.n_fis == 0:
        return 0
    pick = rng.choice(idx.n_fis, size=min(n_probe, idx.n_fis), replace=False)
    masks = np.asarray(idx.masks)[pick]
    want = np.asarray(idx.supports)[pick]
    got = sm.engine.support(masks)
    return int((got != want).sum())


def serve_block(sm, rng, n_queries, zipf_a=1.3):
    """Serve a Zipf-hot batch of support lookups through cache + engine."""
    from repro.serve.cache import query_key

    idx = sm.engine.index
    if idx.n_fis == 0 or n_queries == 0:
        return 0.0, 0
    rows = np.minimum(
        rng.zipf(zipf_a, size=n_queries) - 1, idx.n_fis - 1
    ).astype(np.int64)
    masks = np.asarray(idx.masks)[rows]
    gen = sm.engine.generation
    keys = [
        query_key("support", m, sm.engine.top_k, gen) for m in masks
    ]
    t0 = time.perf_counter()
    results, miss = sm.cache.split_batch(keys)
    # dispatch misses in batch-width chunks, then resolve the whole batch in
    # ONE fill (fill_batch resolves every pending None from the values it is
    # given, so partial fills would KeyError on keys of later chunks)
    vals = []
    for lo in range(0, len(miss), sm.engine.batch):
        part = miss[lo: lo + sm.engine.batch]
        vals.extend(sm.engine.support(masks[part]))
    sm.cache.fill_batch(keys, results, miss, vals)
    return time.perf_counter() - t0, len(miss)


def main():
    from repro.core import eclat, fimi
    from repro.launch import compile_cache
    from repro.data.ibm_gen import drifting_stream, params_from_name
    from repro.obs.session import add_obs_flags, start_session
    from repro.stream import StreamingMiner, StreamParams, fimi_mine_fn

    ap = argparse.ArgumentParser()
    ap.add_argument("--db", default="T2I0.048P50PL10TL16",
                    help="IBM generator family (n_tx field sets nothing; "
                         "the stream length does)")
    ap.add_argument("--support", type=float, default=0.12)
    ap.add_argument("--blocks", type=int, default=8,
                    help="sliding-window length B in blocks")
    ap.add_argument("--blocktx", type=int, default=256,
                    help="transactions per stream block")
    ap.add_argument("--stream", type=int, default=32,
                    help="total blocks to replay")
    ap.add_argument("--breaks", default="16",
                    help="comma-separated block indices of concept drift")
    ap.add_argument("--eps", type=float, default=0.1,
                    help="staleness tolerance ε (Thm 6.1 monitor)")
    ap.add_argument("--delta", type=float, default=0.05)
    ap.add_argument("--margin", type=float, default=0.02,
                    help="border tracking width around minsup (0 disables)")
    ap.add_argument("--hysteresis", type=float, default=0.02,
                    help="border crossing must clear minsup by this much")
    ap.add_argument("--check-every", type=int, default=1)
    ap.add_argument("--cooldown", type=int, default=2,
                    help="blocks after a re-mine before triggers re-arm")
    ap.add_argument("-P", type=int, default=4, help="miners for re-mining")
    ap.add_argument("--cluster", type=int, default=0, metavar="N",
                    help="re-mine with the distributed cluster executor over "
                         "N miners (planner + exchange + shard-mine + "
                         "rebalance) instead of the in-process fimi.run")
    ap.add_argument("--frontier", type=int, default=16)
    ap.add_argument("--queries", type=int, default=512,
                    help="queries served per ingested block")
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--topk", type=int, default=5)
    ap.add_argument("--minconf", type=float, default=0.6)
    ap.add_argument("--cache", type=int, default=2048)
    ap.add_argument("--spill", default="", metavar="DIR",
                    help="persist expired window blocks to a TxStore at DIR")
    ap.add_argument("--force", default=None,
                    choices=[None, "pallas", "ref", "interpret"])
    ap.add_argument("--seed", type=int, default=0)
    add_obs_flags(ap)
    args = ap.parse_args()
    compile_cache.enable()
    obs = start_session(args, "stream_mine")

    gen_params = params_from_name(args.db, seed=args.seed)
    breaks = tuple(int(b) for b in args.breaks.split(",") if b != "")
    n_items = gen_params.n_items
    window_tx = args.blocks * args.blocktx
    if args.cluster and window_tx % args.cluster:
        ap.error(f"--cluster {args.cluster} must divide the window size "
                 f"({args.blocks} blocks x {args.blocktx} tx = {window_tx})")

    if args.cluster:
        from repro import cluster as cluster_mod

        mine_fn = cluster_mod.cluster_mine_fn(
            P=args.cluster,
            cluster_params=cluster_mod.ClusterParams(
                planner=cluster_mod.PlannerParams(
                    n_db_sample=min(2048, window_tx), n_fi_sample=1024
                ),
                eclat=eclat.EclatConfig(
                    max_out=1 << 15, max_stack=8192,
                    frontier_size=args.frontier,
                ),
                force=args.force,
            ),
            seed=args.seed,
        )
    else:
        mine_fn = fimi_mine_fn(
            P=args.P,
            fimi_params=fimi.FimiParams(
                n_db_sample=min(2048, window_tx),
                n_fi_sample=1024,
                eclat=eclat.EclatConfig(
                    max_out=1 << 15, max_stack=8192,
                    frontier_size=args.frontier,
                ),
                force=args.force,
            ),
            seed=args.seed,
        )
    sp = StreamParams(
        n_blocks=args.blocks, block_tx=args.blocktx,
        min_support_rel=args.support, min_confidence=args.minconf,
        eps=args.eps, delta=args.delta, border_margin=args.margin,
        border_hysteresis=args.hysteresis, check_every=args.check_every,
        cooldown_blocks=args.cooldown,
        batch=args.batch, top_k=args.topk, cache_capacity=args.cache,
        force=args.force, spill_dir=args.spill or None, seed=args.seed,
    )
    sm = StreamingMiner(sp, n_items, mine_fn=mine_fn)
    print(f"stream: db-family={args.db} |B|={n_items} window={args.blocks}"
          f"x{args.blocktx}tx sup={args.support} eps={args.eps} "
          f"breaks={breaks} stream={args.stream} blocks")

    rng = np.random.default_rng(args.seed + 1)
    ingest_s = 0.0
    serve_s = 0.0
    n_served = 0
    n_dispatched = 0
    torn = 0
    max_stale = 0.0
    remine_log = []
    prev_gen = -1
    for dense_block, segment in drifting_stream(
        gen_params, n_blocks=args.stream, block_tx=args.blocktx,
        breaks=breaks,
    ):
        if sm.engine is not None:
            torn += parity_failures(sm, rng)     # before a potential swap
        t0 = time.perf_counter()
        ev = sm.admit(dense_block)
        ingest_s += time.perf_counter() - t0
        if ev.remined:
            post = parity_failures(sm, rng)      # after the swap
            torn += post
            if args.cluster:
                # a distributed re-mine must preserve the serving invariants:
                # the swap is atomic (no torn index) and bumps the generation
                assert post == 0, (
                    f"cluster re-mine broke index parity ({post} failures)"
                )
                assert ev.generation == prev_gen + 1, (
                    f"cluster re-mine generation {ev.generation} != "
                    f"{prev_gen + 1}"
                )
            remine_log.append(
                (ev.block_index, segment, ev.remine_reason, ev.mine_ms,
                 ev.swap_ms, sm.engine.index.n_fis)
            )
            if obs:
                obs.event(
                    "remine", block=ev.block_index, segment=segment,
                    reason=ev.remine_reason, mine_ms=ev.mine_ms,
                    swap_ms=ev.swap_ms, generation=ev.generation,
                    n_fis=sm.engine.index.n_fis,
                )
            print(f"  block {ev.block_index:>3} (segment {segment}): "
                  f"re-mine [{ev.remine_reason}] -> F={sm.engine.index.n_fis} "
                  f"R={sm.engine.rules.n_rules} gen={ev.generation} "
                  f"mine={ev.mine_ms:.0f}ms swap={ev.swap_ms:.2f}ms")
        prev_gen = sm.engine.generation if sm.engine else -1
        if sm.engine is not None:
            max_stale = max(max_stale, sm.staleness())   # off the clock
            dt, nd = serve_block(sm, rng, args.queries)
            serve_s += dt
            n_served += args.queries
            n_dispatched += nd

    s = sm.stats
    print(f"ingest: {s.tx_in} tx in {ingest_s:.3f}s -> "
          f"{s.tx_in / ingest_s:,.0f} tx/s "
          f"({s.blocks_in} blocks, delta-updated supports)")
    if sm.engine is None:
        print(f"no mine: stream ended after {s.blocks_in} blocks, window "
              f"needs {args.blocks} to fill (raise --stream)")
        if obs:
            obs.finish(**s.as_dict())
        return
    reasons = {
        "initial": s.remines - s.fired_error - s.fired_border
        - s.fired_recovery,
        "error": s.fired_error, "border": s.fired_border,
        "recovery": s.fired_recovery,
    }
    print(f"re-mine: {s.remines} total ({reasons}), "
          f"mine mean={np.mean(s.mine_ms):.0f}ms, "
          f"swap p100={np.max(s.swap_ms):.2f}ms")
    print(f"staleness: max |served - true| = {max_stale:.4f} "
          f"(tolerance eps={args.eps})")
    if n_served:
        print(f"serve: {n_served} queries in {serve_s:.3f}s -> "
              f"{n_served / serve_s:,.0f} QPS "
              f"({n_dispatched} engine dispatches after cache)")
    es = sm.engine.stats()
    print(f"engine: generation={es['generation']} F={es['n_fis']} "
          f"R={es['n_rules']} cache hit_rate={es['hit_rate']:.1%} "
          f"invalidations={es['invalidations']}")
    print(f"torn-index parity failures: {torn}"
          + ("  <-- BUG" if torn else "  (zero = atomic swaps)"))
    if obs:
        obs.finish(
            **s.as_dict(), max_staleness=max_stale, torn=torn,
            ingest_wall_s=ingest_s, serve_wall_s=serve_s,
            n_served=n_served, generation=sm.engine.generation,
        )
    if sm.spill is not None:
        hist = sm.spill.store()
        print(f"spill: {hist.n_blocks} expired blocks persisted to "
              f"{args.spill} ({hist.n_tx} tx, {hist.total_bytes} packed "
              f"bytes) — re-minable via `launch.mine --store`")


if __name__ == "__main__":
    main()

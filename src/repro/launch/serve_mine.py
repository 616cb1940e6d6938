"""Mine-then-serve driver: the paper's store-owner scenario end to end.

Mines a named IBM database with the frontier-batched Parallel-FIMI pipeline,
builds the serving indexes (FI table → packed FI index, ap-genrules → rule
index), then replays a synthetic query workload through the batched engine
with an LRU cache in front and reports QPS, latency percentiles, and the
cache hit rate.

The workload models serving traffic, not mining: a fixed population of
distinct queries per kind (support lookups, basket→rules, itemset→supersets)
drawn with a Zipf-tilted popularity so hot queries repeat — the regime the
cache exists for.  Every dispatch is a fixed-width batch (one compiled
program per query kind for the whole session).

  python -m repro.launch.serve_mine --db T2I0.048P50PL10TL16 --support 0.1 \\
      --queries 1024 [--frontier 16] [-P 4] [--devices 4] [--batch 256]
"""
from __future__ import annotations

import argparse
import sys

from repro.launch.host_devices import DEVICES_HELP, preparse_devices

preparse_devices()  # must run before anything imports jax

import time  # noqa: E402

import numpy as np  # noqa: E402

KINDS = ("support", "rules", "superset")


def build_workload(rng, fis, dense, n_items, n_queries, pool=64, zipf_a=1.3):
    """A query stream [(kind, packed_mask_row)] with Zipf-hot repetition."""
    from repro.core.rules import pack_itemsets

    fi_list = sorted(fis, key=lambda s: (len(s), tuple(sorted(s))))
    pools = {}
    # support: indexed FIs plus a sprinkle of (likely) non-frequent probes
    cand = [fi_list[i] for i in rng.choice(len(fi_list),
                                           size=min(pool, len(fi_list)),
                                           replace=False)]
    probes = [
        frozenset(rng.choice(n_items, size=min(6, n_items), replace=False)
                  .tolist())
        for _ in range(max(pool // 8, 1))
    ]
    pools["support"] = cand + probes
    # rules: real baskets — transaction rows of the database
    rows = rng.choice(dense.shape[0], size=min(pool, dense.shape[0]),
                      replace=False)
    pools["rules"] = [frozenset(np.nonzero(dense[t])[0].tolist())
                      for t in rows]
    # superset: small frequent prefixes (completion queries)
    small = [s for s in fi_list if len(s) <= 2] or fi_list[:1]
    pools["superset"] = [small[i] for i in
                         rng.choice(len(small),
                                    size=min(pool, len(small)),
                                    replace=False)]

    packed = {k: pack_itemsets(v, n_items) for k, v in pools.items()}
    mix = rng.choice(len(KINDS), size=n_queries, p=[0.5, 0.3, 0.2])
    stream = []
    for kind_id in mix:
        kind = KINDS[kind_id]
        n = packed[kind].shape[0]
        # Zipf-tilted popularity over the pool (hot queries repeat)
        i = min(int(rng.zipf(zipf_a)) - 1, n - 1)
        stream.append((kind, packed[kind][i]))
    return stream


def _dispatchers(engine):
    """Per-kind batched dispatch: packed masks [n, IW] -> n result values."""
    return {
        "support": lambda m: list(engine.support(m)),
        "rules": lambda m: list(zip(*map(list, engine.rules_for(m)))),
        "superset": lambda m: list(zip(*map(list, engine.supersets(m)))),
    }


def warm(stream, engine):
    """Compile each query kind's program off the clock (deploy-time warm)."""
    dispatch = _dispatchers(engine)
    for kind in KINDS:
        mask = next((m for k, m in stream if k == kind), None)
        if mask is not None:
            dispatch[kind](mask[None])


def replay(stream, engine, cache, batch):
    """Serve the stream in fixed-width batches; return latency samples [s]."""
    from repro.serve.cache import query_key

    dispatch = _dispatchers(engine)
    latencies = []
    n_dispatched = 0
    for lo in range(0, len(stream), batch):
        chunk = stream[lo: lo + batch]
        t0 = time.perf_counter()
        for kind in KINDS:
            rows = [(i, m) for i, (k, m) in enumerate(chunk) if k == kind]
            if not rows:
                continue
            # keys carry the swap generation: a hot-swapped index (the
            # streaming subsystem) can never serve a stale cached hit
            keys = [query_key(kind, m, engine.top_k, engine.generation)
                    for _, m in rows]
            results, miss = cache.split_batch(keys)
            if miss:
                masks = np.stack([rows[j][1] for j in miss])
                vals = dispatch[kind](masks)
                n_dispatched += len(miss)
                cache.fill_batch(keys, results, miss, vals)
        latencies.append(time.perf_counter() - t0)
    return latencies, n_dispatched


def main(argv=None) -> int:
    import jax

    from repro.core import eclat, fimi
    from repro.data.ibm_gen import generate_dense, params_from_name
    from repro.launch import compile_cache
    from repro.launch.mesh import make_miner_mesh
    from repro.obs.session import add_obs_flags, start_session
    from repro.serve import QueryCache, QueryEngine
    from repro.serve.index import build_indexes

    ap = argparse.ArgumentParser()
    ap.add_argument("--db", default="T2I0.048P50PL10TL16")
    ap.add_argument("--support", type=float, default=0.1)
    ap.add_argument("--variant", default="reservoir",
                    choices=["seq", "par", "reservoir"])
    ap.add_argument("-P", type=int, default=4)
    ap.add_argument("--devices", type=int, default=0, help=DEVICES_HELP)
    ap.add_argument("--frontier", type=int, default=16,
                    help="DFS nodes mined per while_loop trip (K)")
    ap.add_argument("--queries", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=256,
                    help="queries per engine dispatch")
    ap.add_argument("--topk", type=int, default=5)
    ap.add_argument("--minconf", type=float, default=0.5)
    ap.add_argument("--cache", type=int, default=2048,
                    help="LRU capacity (0 disables)")
    ap.add_argument("--pool", type=int, default=64,
                    help="distinct queries per kind in the workload")
    ap.add_argument("--seed", type=int, default=0)
    add_obs_flags(ap)
    args = ap.parse_args(argv)
    compile_cache.enable()
    obs = start_session(args, "serve_mine")

    # ---- mine ---------------------------------------------------------------
    dense = generate_dense(params_from_name(args.db, seed=args.seed))
    n_tx, n_items = dense.shape
    abs_minsup = int(np.ceil(args.support * n_tx))
    shards = fimi.shard_db(dense, args.P)
    params = fimi.FimiParams(
        variant=args.variant, min_support_rel=args.support,
        n_db_sample=min(2048, n_tx), n_fi_sample=1024,
        eclat=eclat.EclatConfig(
            max_out=1 << 15, max_stack=8192, frontier_size=args.frontier
        ),
    )
    use_shard_map = len(jax.devices()) >= args.P
    spmd = fimi.shard_map_spmd if use_shard_map else fimi.vmap_spmd
    mesh = make_miner_mesh(args.P) if use_shard_map else None
    print(f"mine: db={args.db} |D|={n_tx} |B|={n_items} sup={args.support} "
          f"P={args.P} frontier={args.frontier} "
          f"backend={'shard_map' if use_shard_map else 'vmap'}")
    t0 = time.time()
    res = fimi.run(shards, n_items, params, jax.random.PRNGKey(args.seed),
                   spmd=spmd, mesh=mesh, materialize=True)
    fis = res.fi_dict
    print(f"mine: |F| = {len(fis)} in {time.time() - t0:.2f}s")

    # ---- index + rules ------------------------------------------------------
    t0 = time.time()
    fi_index, rule_index = build_indexes(
        fis, n_items, n_tx, min_confidence=args.minconf
    )
    print(f"index: F={fi_index.n_fis} itemsets "
          f"(max size {fi_index.max_size}, {fi_index.n_words} words/mask), "
          f"R={rule_index.n_rules} rules @ conf>={args.minconf} "
          f"in {time.time() - t0:.2f}s")

    # ---- serve --------------------------------------------------------------
    cache = QueryCache(capacity=args.cache)
    engine = QueryEngine(fi_index, rule_index, batch=args.batch,
                         top_k=args.topk, cache=cache)
    rng = np.random.default_rng(args.seed + 1)
    stream = build_workload(rng, fis, dense, n_items, args.queries,
                            pool=args.pool)

    # warm every query kind's compiled program off the clock (a real server
    # warms at deploy time), then replay the measured session
    warm(stream, engine)

    t0 = time.time()
    latencies, n_dispatched = replay(stream, engine, cache, args.batch)
    wall = time.time() - t0
    lat = np.asarray(latencies) * 1e3
    qps = len(stream) / wall
    print(f"serve: {len(stream)} queries in {wall:.3f}s -> {qps:,.0f} QPS "
          f"(batch={args.batch}, {len(latencies)} dispatch rounds, "
          f"{n_dispatched} engine queries after cache)")
    print(f"serve: batch latency ms p50={np.percentile(lat, 50):.2f} "
          f"p95={np.percentile(lat, 95):.2f} "
          f"p99={np.percentile(lat, 99):.2f} max={lat.max():.2f}")
    s = cache.stats
    print(f"cache: {s.hits}/{s.lookups} hits ({s.hit_rate:.1%}), "
          f"{s.evictions} evictions, {s.invalidations} invalidations, "
          f"{len(cache)} resident")
    es = engine.stats()
    print(f"engine: generation={es['generation']} (index hot-swaps; see "
          f"repro.launch.stream_mine) F={es['n_fis']} R={es['n_rules']}")
    if obs:
        obs.event("served", queries=len(stream), dispatched=n_dispatched,
                  qps=qps)
        obs.finish(
            n_fis=fi_index.n_fis, n_rules=rule_index.n_rules, qps=qps,
            serve_wall_s=wall,
            batch_p50_ms=float(np.percentile(lat, 50)),
            batch_p95_ms=float(np.percentile(lat, 95)),
            batch_p99_ms=float(np.percentile(lat, 99)),
            cache_hit_rate=s.hit_rate,
        )

    # a taste of the product: the most confident rules overall
    print(f"top-{min(5, rule_index.n_rules)} rules by confidence:")
    from repro.core.rules import format_rule
    for r in range(min(5, rule_index.n_rules)):
        print("  " + format_rule(rule_index.rule(r), n_tx))
    return 0


if __name__ == "__main__":
    sys.exit(main())

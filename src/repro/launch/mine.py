"""Distributed Parallel-FIMI launcher (the paper's production entry point).

Runs the full four-phase method over real devices when available (shard_map
over a 1-D miner mesh) or P virtual miners on one device (vmap).  On a TPU
host the miner axis maps one miner onto each chip; on a CPU host use
--devices to fork virtual CPU devices (set before jax import, hence the flag
is handled in __main__ preamble).

Three data sources:

  * default          — generate the --db IBM database in RAM (seed behavior);
  * --store DIR      — mine **out of core** from an on-disk TxStore (spilled
                       there block-by-block from --db first if DIR is empty);
  * --dataset F.dat  — ingest a standard FIMI file into a store, then mine it
                       out of core (--store names the store dir, else a temp).

--parity is the exactness gate: mine the same database through the dense
in-RAM path and require the two FITables to match bit for bit; exits
non-zero on any difference (CI runs this on a store larger than the host
block budget).

  python -m repro.launch.mine --db T2I0.048P50PL10TL16 --support 0.1 \
      --variant reservoir -P 8 [--devices 8]
  python -m repro.launch.mine --db T2I0.048P50PL10TL16 --support 0.1 \
      --store /tmp/txstore --blocktx 256 --parity
  python -m repro.launch.mine --dataset examples/retail_tiny.dat \
      --support 0.2 -P 2 --parity
"""
from __future__ import annotations

import argparse
import sys

from repro.launch.host_devices import DEVICES_HELP, preparse_devices

preparse_devices()  # must run before anything imports jax

import time  # noqa: E402


def main():
    import jax

    from repro.core import eclat, fimi
    from repro.launch import compile_cache
    from repro.launch.data_source import resolve_source
    from repro.launch.mesh import make_miner_mesh
    from repro.obs.session import add_obs_flags, start_session
    from repro.store.reader import BlockReader

    ap = argparse.ArgumentParser()
    ap.add_argument("--db", default="T2I0.048P50PL10TL16")
    ap.add_argument("--dataset", default="",
                    help="mine a FIMI .dat file (ingested into a store)")
    ap.add_argument("--store", default="",
                    help="mine out-of-core from this TxStore dir "
                         "(spilled from --db when empty)")
    ap.add_argument("--blocktx", type=int, default=256,
                    help="store block size (rows) when spilling/ingesting")
    ap.add_argument("--budget-blocks", type=int, default=2,
                    help="host block budget of the streamed reader")
    ap.add_argument("--parity", action="store_true",
                    help="verify bit-exact FITable vs the dense in-RAM path")
    ap.add_argument("--support", type=float, default=0.1)
    ap.add_argument("--variant", default="reservoir",
                    choices=["seq", "par", "reservoir"])
    ap.add_argument("-P", type=int, default=4)
    ap.add_argument("--devices", type=int, default=0, help=DEVICES_HELP)
    ap.add_argument("--scheduler", default="lpt", choices=["lpt", "repl_min"])
    ap.add_argument("--alpha", type=float, default=0.5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--frontier", type=int, default=16,
                    help="DFS nodes mined per while_loop trip (K)")
    add_obs_flags(ap)
    args = ap.parse_args()
    compile_cache.enable()
    obs = start_session(args, "mine")

    # ---- resolve the data source -------------------------------------------
    store, dense, src = resolve_source(
        args.dataset, args.store, args.db,
        block_tx=args.blocktx, seed=args.seed,
    )
    if store is not None:
        n_tx, n_items = store.n_tx, store.n_items
    else:
        n_tx, n_items = dense.shape

    params = fimi.FimiParams(
        variant=args.variant, min_support_rel=args.support,
        alpha=args.alpha, scheduler=args.scheduler,
        n_db_sample=min(2048, n_tx), n_fi_sample=1024,
        eclat=eclat.EclatConfig(
            max_out=1 << 15, max_stack=8192, frontier_size=args.frontier
        ),
    )
    use_shard_map = len(jax.devices()) >= args.P
    spmd = fimi.shard_map_spmd if use_shard_map else fimi.vmap_spmd
    mesh = make_miner_mesh(args.P) if use_shard_map else None
    print(
        f"{src} |D|={n_tx} |B|={n_items} sup={args.support} "
        f"variant={args.variant} P={args.P} frontier={args.frontier} "
        f"backend={'shard_map' if use_shard_map else 'vmap'}"
    )
    if store is not None:
        budget = args.budget_blocks * max(store.max_block_bytes, 1)
        print(
            f"store: {store.n_blocks} blocks x <= {store.block_tx} tx "
            f"({store.total_bytes} packed bytes on disk)  "
            f"host budget = {args.budget_blocks} blocks ({budget} bytes)"
        )

    t0 = time.time()
    key = jax.random.PRNGKey(args.seed)
    if store is not None:
        # the mine's own block stream is the residency measurement: fimi.run
        # assembles the shards through this reader (one pass, no extra I/O)
        reader = BlockReader(store, args.budget_blocks)
        res = fimi.run(
            store, None, params, key, spmd=spmd, mesh=mesh,
            materialize=args.parity, P=args.P, reader=reader,
        )
    else:
        res = fimi.run(
            fimi.shard_db(dense, args.P), n_items, params, key,
            spmd=spmd, mesh=mesh, materialize=args.parity,
        )
    dt = time.time() - t0
    w = res.work_iters.astype(float)
    print(f"|F| = {res.n_fis}  in {dt:.2f}s")
    print(f"classes={len(res.classes)}  replication={res.replication:.2f}  "
          f"exchange_overflow={res.exchange_overflow}")
    print(f"per-miner work (DFS trips): {res.work_iters.tolist()}  "
          f"balance={w.max()/max(w.mean(),1):.2f}")
    if res.progress is not None:
        print(res.progress.line() + "  stragglers="
              + ",".join(f"{s:.2f}" for s in res.progress.stragglers))
    if store is not None:
        print(f"streamed host high-water: {reader.peak_host_bytes} bytes "
              f"(budget {reader.budget_bytes})")
    if obs:
        obs.event("mined", n_fis=res.n_fis, wall_s=dt,
                  work_iters=res.work_iters.tolist())
        obs.finish(n_fis=res.n_fis, n_tx=n_tx, n_items=n_items,
                   mine_wall_s=dt, replication=res.replication)

    # ---- parity gate: out-of-core result == dense in-RAM result ------------
    if args.parity:
        if store is None:
            print("--parity needs --store or --dataset (nothing to compare)")
            sys.exit(2)
        if store.total_bytes <= reader.budget_bytes:
            print(f"note: store ({store.total_bytes}B) fits the host budget "
                  f"({reader.budget_bytes}B); gate still exact but not "
                  f"out-of-core — use a bigger --db or smaller --blocktx")
        dense_ref = store.to_dense()  # O(n_tx) host — the gate's reference
        ref = fimi.run(
            fimi.shard_db(dense_ref, args.P), n_items, params, key,
            spmd=spmd, mesh=mesh, materialize=True,
        )
        got, want = res.fi_dict, ref.fi_dict
        if got != want:
            only_got = set(got) - set(want)
            only_ref = set(want) - set(got)
            diff = {k for k in set(got) & set(want) if got[k] != want[k]}
            print(f"PARITY FAIL: +{len(only_got)} -{len(only_ref)} "
                  f"support-mismatch={len(diff)}")
            sys.exit(1)
        print(f"parity vs dense in-RAM fimi.run: OK ({len(got)} itemsets, "
              f"bit-exact supports; store {store.total_bytes}B > "
              f"host budget {reader.budget_bytes}B)"
              if store.total_bytes > reader.budget_bytes else
              f"parity vs dense in-RAM fimi.run: OK ({len(got)} itemsets)")


if __name__ == "__main__":
    main()

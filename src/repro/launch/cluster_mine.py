"""Distributed mining launcher — the cluster executor end to end.

Runs planner → exchange → shard-mine → rebalance on N simulated host devices
(``--devices N`` forks CPU devices before jax imports, launch/host_devices.py)
or real mesh devices when present, and reports what a cluster operator needs:

  * per-phase time (plan / exchange / mine / merge),
  * load imbalance (observed DFS trips, max/mean) and the planner's
    estimation error (predicted vs observed load shares),
  * a speedup-vs-devices curve (``--curve 1,2,4``) in modeled makespan
    (Σ_r max_p trips — the barrier-aware metric) and wall time,
  * exact parity against single-device ``fimi.run`` (``--parity``; exits
    non-zero on any itemset/support mismatch — the CI gate uses this),
  * fault tolerance: ``--checkpoint DIR`` persists the inter-round state
    atomically after every round; ``--resume`` restarts from the latest
    checkpoint and the finished run is bit-exact with an uninterrupted
    one; ``--kill-after-round R`` dies (exit 0) right after round R's
    checkpoint — the fault-injection gate pairs it with ``--resume
    --parity``.

  python -m repro.launch.cluster_mine --db T2I0.048P50PL10TL16 --support 0.1 \
      -P 4 --devices 4 --parity [--curve 1,2,4] [--no-rebalance] \
      [--checkpoint DIR [--resume | --kill-after-round R]]
"""
from __future__ import annotations

import argparse
import sys

from repro.launch.host_devices import DEVICES_HELP, preparse_devices

preparse_devices()  # must run before anything imports jax

import dataclasses  # noqa: E402
import time  # noqa: E402

import numpy as np  # noqa: E402


def _skew_plan(plan):
    """Fault injection (``--force-skew``): pile every class onto shard 0.

    The estimated loads move with the assignment, so the skew is *planned*
    and predicted — pure imbalance, zero estimation error — which is
    exactly the shape the doctor's "imbalance dominates / rebalance not
    engaging" self-test needs to see.
    """
    assignment = np.zeros_like(plan.assignment)
    est_loads = np.zeros_like(plan.est_loads)
    if est_loads.shape[0]:
        est_loads[0] = float(np.sum(plan.est_loads))
    return dataclasses.replace(
        plan, assignment=assignment, est_loads=est_loads
    )


def run_once(dense, n_items, P, args, eclat_mod, fimi_mod, cluster,
             store=None):
    """One executor run at P miners; returns (result, wall seconds).

    With ``store`` set, the mine is ``cluster.mine_store``: the plan is
    computed **off disk** (Thm 6.1 sample via ``store.reader.sample_rows``
    — bit-exact vs the in-RAM sample) and the data-plane shards are
    assembled block-by-block through the double-buffered reader;
    ``dense`` is only used otherwise.
    """
    import jax

    params = cluster.ClusterParams(
        planner=cluster.PlannerParams(
            min_support_rel=args.support,
            alpha=args.alpha,
            scheduler=args.scheduler,
            n_db_sample=min(2048, store.n_tx if store else dense.shape[0]),
            n_fi_sample=1024,
        ),
        eclat=eclat_mod.EclatConfig(
            max_out=1 << 15, max_stack=8192, frontier_size=args.frontier
        ),
        chunk=args.chunk or None,
        # --force-skew also pins rebalancing off: the injected skew must
        # survive to the report for the self-test to observe it
        rebalance=not (args.no_rebalance
                       or getattr(args, "force_skew", False)),
        skew_threshold=args.skew,
    )
    force_skew = getattr(args, "force_skew", False)
    key = jax.random.PRNGKey(args.seed)
    ck = dict(
        checkpoint_dir=getattr(args, "checkpoint", "") or None,
        resume=getattr(args, "resume", False),
        round_hook=_kill_hook(args),
        # the live line: sample-estimated completion + barrier-aware ETA +
        # worst straggler, refreshed at every round boundary
        progress_cb=lambda s: print("  " + s.line(), flush=True),
    )
    t0 = time.perf_counter()
    if store is not None:
        # plan off disk, assemble block by block, mine one miner per device
        # (phase_ms: plan, exchange, mine, merge, assemble)
        res = cluster.mine_store(
            store, params, key, P,
            adjust_plan=_skew_plan if force_skew else None, **ck)
    else:
        shards = fimi_mod.shard_db(dense, P)
        if force_skew:
            plan = _skew_plan(cluster.plan(shards, n_items,
                                           params.planner, key))
            t1 = time.perf_counter()
            res = cluster.execute(shards, n_items, params, key, plan=plan,
                                  **ck)
            res.report.phase_ms["plan"] = (t1 - t0) * 1e3
            res.report.republish_gauges()
        else:
            res = cluster.execute(shards, n_items, params, key, **ck)
    return res, time.perf_counter() - t0


def _kill_hook(args):
    """Round hook that simulates a mid-run death for the fault gate."""
    kill_at = getattr(args, "kill_after_round", -1)
    if kill_at < 0:
        return None

    def hook(r: int) -> None:
        if r >= kill_at:
            print(f"KILLED after round {r} (checkpoint saved) — "
                  f"rerun with --resume to finish")
            sys.exit(0)

    return hook


def main():
    import jax

    from repro import cluster
    from repro.core import eclat, fimi
    from repro.launch import compile_cache
    from repro.launch.data_source import resolve_source
    from repro.obs.session import add_obs_flags, start_session

    ap = argparse.ArgumentParser()
    ap.add_argument("--db", default="T2I0.048P50PL10TL16")
    ap.add_argument("--dataset", default="",
                    help="mine a FIMI .dat file (ingested into a store)")
    ap.add_argument("--store", default="",
                    help="mine out-of-core from this TxStore dir "
                         "(spilled from --db when empty)")
    ap.add_argument("--blocktx", type=int, default=256,
                    help="store block size (rows) when spilling/ingesting")
    ap.add_argument("--support", type=float, default=0.1)
    ap.add_argument("-P", type=int, default=4)
    ap.add_argument("--devices", type=int, default=0, help=DEVICES_HELP)
    ap.add_argument("--scheduler", default="auto",
                    choices=["auto", "lpt", "repl_min"])
    ap.add_argument("--alpha", type=float, default=0.5)
    ap.add_argument("--frontier", type=int, default=16)
    ap.add_argument("--chunk", type=int, default=0,
                    help="classes per shard per round (0 = auto)")
    ap.add_argument("--skew", type=float, default=1.25,
                    help="rebalance when remaining max/mean exceeds this")
    ap.add_argument("--no-rebalance", action="store_true")
    ap.add_argument("--force-skew", action="store_true", dest="force_skew",
                    help="fault injection: assign every equivalence class "
                         "to shard 0 and disable rebalancing — the doctor's "
                         "'imbalance dominates' self-test")
    ap.add_argument("--curve", default="",
                    help="comma-separated device counts for a speedup curve")
    ap.add_argument("--parity", action="store_true",
                    help="verify exact FI parity vs single-device fimi.run")
    ap.add_argument("--checkpoint", default="",
                    help="persist inter-round state to this dir after "
                         "every round (atomic, CRC32C-guarded)")
    ap.add_argument("--resume", action="store_true",
                    help="restart from the latest checkpoint in "
                         "--checkpoint (bit-exact with an unbroken run)")
    ap.add_argument("--kill-after-round", type=int, default=-1,
                    dest="kill_after_round", metavar="R",
                    help="simulate a crash: exit 0 right after round R's "
                         "checkpoint is saved (fault-injection gate)")
    ap.add_argument("--seed", type=int, default=0)
    add_obs_flags(ap)
    args = ap.parse_args()
    compile_cache.enable()
    obs = start_session(args, "cluster_mine")

    store, dense, src = resolve_source(
        args.dataset, args.store, args.db,
        block_tx=args.blocktx, seed=args.seed,
    )
    n_tx = store.n_tx if store is not None else dense.shape[0]
    n_items = store.n_items if store is not None else dense.shape[1]
    print(
        f"{src} |D|={n_tx} |B|={n_items} sup={args.support} "
        f"P={args.P} devices={len(jax.devices())} "
        f"rebalance={not args.no_rebalance} scheduler={args.scheduler}"
    )
    if store is not None:
        print(f"store: {store.n_blocks} blocks x <= {store.block_tx} tx "
              f"({store.total_bytes} packed bytes; plan sampled off-disk)")

    res, wall = run_once(dense, n_items, args.P, args, eclat, fimi, cluster,
                         store=store)
    rep, plan = res.report, res.plan
    print(f"|F| = {res.table.n_fis}  in {wall:.2f}s  backend={rep.backend}  "
          f"rounds={rep.n_rounds}  scheduler={plan.scheduler_used}")
    print("per-phase ms: "
          + "  ".join(f"{k}={v:.0f}" for k, v in rep.phase_ms.items()))
    print(f"classes={len(plan.classes)}  "
          f"volume lpt={plan.lpt_volume:.0f} repl_min={plan.repl_volume:.0f}  "
          f"replication/round="
          f"{np.mean([r.replication for r in rep.rounds]):.2f}")
    print(f"load: observed trips={rep.observed_loads.astype(int).tolist()}  "
          f"imbalance={rep.imbalance:.2f}  "
          f"estimation_error={rep.estimation_error():.3f}  "
          f"donations={len(rep.donations)}")
    if obs:
        for r in rep.rounds:
            obs.event(
                "round", index=r.round_index,
                classes_mined=r.classes_mined,
                work_iters=r.work_iters.tolist(),
                replication=r.replication,
                donations=len(r.donations),
            )
        obs.finish(
            n_fis=res.table.n_fis, mine_wall_s=wall, rounds=rep.n_rounds,
            backend=rep.backend, imbalance=rep.imbalance,
            makespan_trips=rep.makespan_trips,
            estimation_error=rep.estimation_error(),
        )

    if args.curve:
        counts = [int(c) for c in args.curve.split(",") if c]
        base_makespan = None
        print("speedup curve (modeled makespan = sum of per-round max trips):")
        for Pc in counts:
            r, w = run_once(dense, n_items, Pc, args, eclat, fimi, cluster,
                            store=store)
            mk = r.report.makespan_trips
            if base_makespan is None:
                base_makespan = mk
            print(f"  P={Pc:<3d} makespan={mk:>8.0f} trips  "
                  f"speedup={base_makespan / max(mk, 1):.2f}x  wall={w:.2f}s  "
                  f"imbalance={r.report.imbalance:.2f}")

    if args.parity:
        if dense is None:
            dense = store.to_dense()  # O(n_tx) host — parity reference only
        fp = fimi.FimiParams(
            min_support_rel=args.support,
            n_db_sample=min(2048, dense.shape[0]), n_fi_sample=1024,
            eclat=eclat.EclatConfig(
                max_out=1 << 15, max_stack=8192, frontier_size=args.frontier
            ),
        )
        ref = fimi.run(
            fimi.shard_db(dense, 1), n_items, fp, jax.random.PRNGKey(args.seed),
            materialize=True,
        )
        got = res.table.to_dict()
        if got != ref.fi_dict:
            only_got = set(got) - set(ref.fi_dict)
            only_ref = set(ref.fi_dict) - set(got)
            diff_supp = {
                k for k in set(got) & set(ref.fi_dict)
                if got[k] != ref.fi_dict[k]
            }
            print(f"PARITY FAIL: +{len(only_got)} -{len(only_ref)} "
                  f"support-mismatch={len(diff_supp)}")
            sys.exit(1)
        print(f"parity vs single-device fimi.run: OK "
              f"({len(got)} itemsets, bit-exact supports)")


if __name__ == "__main__":
    main()

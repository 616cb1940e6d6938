"""Profiled demo mine: every kernel family, measured vs modeled.

One short run that drives all five ``repro.kernels.ops`` dispatch
families through the kernel profiler:

  * **bitmap / multi / pair** — the mining support counters, called
    eagerly (per-call device-synced timing) on an IBM-generator database;
  * **subset** — the serving sweep, queries against itemset masks;
  * **delta**  — the streaming sweep, stacked transaction blocks against
    itemset masks;
  * plus a real Parallel-FIMI mine, so the ``while_loop`` frontier work
    is loop-attributed and the sample-grounded live progress line shows.

With ``--trace DIR`` the attribution rides the run record as
``kernels/*`` gauges; ``tools/check.sh --profile`` renders and gates it::

    python -m repro.launch.profile_demo --trace RUN
    python -m repro.launch.obs_report kernels RUN \
        --require bitmap,multi,pair,subset,delta --check-model
"""
from __future__ import annotations

import argparse
import time


def main():
    import jax
    import jax.numpy as jnp

    from repro.core import bitmap as bm
    from repro.core import eclat, fimi
    from repro.data.ibm_gen import generate_dense, params_from_name
    from repro.kernels import ops
    from repro.launch import compile_cache
    from repro.obs import profile as obs_profile
    from repro.obs.session import add_obs_flags, start_session

    ap = argparse.ArgumentParser()
    ap.add_argument("--db", default="T0.5I0.024P8PL5TL8")
    ap.add_argument("--support", type=float, default=0.08)
    ap.add_argument("-P", type=int, default=2)
    ap.add_argument("--reps", type=int, default=3,
                    help="eager dispatches per family")
    ap.add_argument("--frontier", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    add_obs_flags(ap)
    args = ap.parse_args()
    compile_cache.enable()
    args.profile = True      # this driver exists to profile
    obs = start_session(args, "profile_demo")
    prof = obs_profile.profiler()
    if obs is None:          # no run record asked for: still profile + print
        prof.clear()
        prof.enable()

    dense = generate_dense(params_from_name(args.db, seed=args.seed))
    db = bm.BitmapDB.from_dense(jnp.asarray(dense))
    n_tx, n_items = dense.shape
    print(f"ibm:{args.db} |D|={n_tx} |B|={n_items} sup={args.support} "
          f"P={args.P} reps={args.reps}")

    # ---- eager family sweep (per-call device-synced timing) ----------------
    all_t = db.all_tids()
    prefix_tids = jnp.tile(all_t[None, :], (8, 1))
    q_masks = db.tx_bits[: min(32, n_tx)]
    fi_masks = db.tx_bits[: min(64, n_tx)]
    half = max(1, n_tx // 2)
    blocks = db.tx_bits[: 2 * half].reshape(2, half, -1)
    t0 = time.perf_counter()
    for _ in range(max(1, args.reps)):
        ops.extension_supports(db.item_bits, all_t)          # bitmap
        ops.multi_extension_supports(db.item_bits, prefix_tids)  # multi
        ops.pair_supports(db.item_bits, all_t)               # pair
        ops.subset_superset_counts(q_masks, fi_masks)        # subset
        ops.block_itemset_supports(blocks, fi_masks)         # delta
    print(f"eager sweep: {args.reps} reps x 5 families in "
          f"{time.perf_counter() - t0:.2f}s")

    # ---- a real mine: loop attribution + live progress ---------------------
    params = fimi.FimiParams(
        min_support_rel=args.support,
        n_db_sample=min(2048, n_tx), n_fi_sample=1024,
        eclat=eclat.EclatConfig(
            max_out=1 << 15, max_stack=8192, frontier_size=args.frontier
        ),
    )
    res = fimi.run(
        fimi.shard_db(jnp.asarray(dense), args.P), n_items, params,
        jax.random.PRNGKey(args.seed),
    )
    print(f"|F| = {res.n_fis}  work_iters={res.work_iters.tolist()}")
    if res.progress is not None:
        print(res.progress.line())

    # ---- attribution table --------------------------------------------------
    rep = prof.report()
    m = rep["machine"]
    print(f"machine={m['name']} word_ops_peak={m['word_ops_peak']:.3g} "
          f"hbm_bw={m['hbm_bw']:.3g}")
    for family in obs_profile.FAMILIES:
        fam = rep["families"].get(family)
        if fam is None:
            print(f"  {family:<7} (no dispatches)")
            continue
        frac = fam["achieved_frac"]
        print(f"  {family:<7} calls={fam['calls']:<4d} "
              f"loop_execs={fam['loop_execs']:<6d} "
              f"measured={fam['measured_ms']:.3f}ms "
              f"modeled={fam['modeled_ms']:.3f}ms "
              f"frac={frac if frac is None else round(frac, 4)} "
              f"{'memory' if fam['mem_bound'] else 'compute'}-bound")
    missing = [f for f in obs_profile.FAMILIES
               if rep["families"].get(f, {}).get("measured_ms", 0.0) <= 0.0]
    if obs:
        obs.finish(n_fis=res.n_fis, families=len(rep["families"]))
    else:
        prof.disable()
    if missing:
        print(f"profile_demo: families without measured time: {missing}")
        return 1
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())

#!/usr/bin/env python3
"""Bring-up check: mine -> serve -> stream on a TPU, through the Pallas kernels.

    python chip_smoke.py               # phases A-D on one chip
    python chip_smoke.py --four-chip   # phase B's executor mine, shard_map on
                                       # 4 chips vs vmap on one of them

Every phase runs in this one process (a chip belongs to one process) through
the library's own entry points, with the default kernel dispatch, which is
the compiled Pallas kernels on a TPU.  Each phase prints one line: set-up
time (first call: compile + run), steady wall time (a second call, results
on the host), result size, and the chip's ``peak_bytes_in_use``.

  A  executor mine of T1I0.032P20PL6TL10 at P=4, itemset for itemset
     against ``eclat.brute_force_fis`` on the host.
  B  T100I1P2000PL4TL10 (the IBM Quest shape of the FIMI repository's
     T10I4D100K: 100,000 tx x 1,000 items) spilled to a TxStore, mined by
     ``fimi.run(store)`` and by ``cluster.execute``; both must equal the
     executor with ``force="ref"`` bit for bit, with no overflow.
  C  FI and rule indexes from B's table; 1,024 Zipf queries through
     ``QueryEngine`` (``subset_query``), every answer equal to the
     ``force="ref"`` engine's.
  D  ``StreamingMiner`` over a drifting stream at 1,000 items, window of
     8 x 4,096 tx, then more blocks (``delta_support``); window supports
     equal to the ``force="ref"`` miner's after every block.

Exits non-zero, before printing a result, when JAX finds no TPU, on any
mismatch, error or overflow.  The last line of a passing run is one JSON
object naming the device as JAX reports it.  The compile cache goes to
``$JAX_COMPILATION_CACHE_DIR`` when set, else to ``.jax_cache`` here.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import tempfile
import time
from functools import partial
from pathlib import Path

ROOT = Path(__file__).resolve().parent

P = 4                              # miners: vmap on one chip, one per chip on 4
SEED = 0
SMALL_DB, SMALL_SUPPORT = "T1I0.032P20PL6TL10", 0.08
REAL_DB, REAL_SUPPORT = "T100I1P2000PL4TL10", 0.0025
STORE_BLOCK_TX = 4096
QUERIES, QUERY_BATCH = 1024, 256
STREAM_WINDOW_BLOCKS, STREAM_BLOCK_TX, STREAM_EXTRA_BLOCKS = 8, 4096, 4
STREAM_SUPPORT = 0.0025


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def timed(fn):
    """(result, seconds) of ``fn()``; results are host data or synced."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def report(phase: str, setup_s: float, steady_s: float, size: str) -> None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    print(f"[{phase}] setup(compile+first run)={setup_s:.3f}s "
          f"steady={steady_s:.3f}s result: {size} "
          f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
          f"bytes_in_use={stats.get('bytes_in_use')}", flush=True)


def lowers_to_pallas(fn, *shapes) -> bool:
    """Does ``fn`` on uint32 arguments of these shapes lower to a compiled
    Pallas call on this backend (and not to the jnp reference)?"""
    import jax

    args = [jax.ShapeDtypeStruct(s, "uint32") for s in shapes]
    return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()


def cluster_params(support: float, n_tx: int, force=None):
    from repro import cluster
    from repro.core import eclat

    return cluster.ClusterParams(
        planner=cluster.PlannerParams(
            min_support_rel=support, n_db_sample=min(2048, n_tx),
            n_fi_sample=1024,
        ),
        eclat=eclat.EclatConfig(
            max_out=1 << 15, max_stack=8192, frontier_size=16
        ),
        force=force,
    )


def same_table(a, b) -> bool:
    import numpy as np

    return (np.array_equal(a.masks, b.masks)
            and np.array_equal(a.supports, b.supports))


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def phase_a(db: str = SMALL_DB, support: float = SMALL_SUPPORT) -> None:
    """Executor mine on the chip's kernels == the brute-force host oracle."""
    import jax

    from repro import cluster
    from repro.core import eclat, fimi
    from repro.data.ibm_gen import generate_dense, params_from_name
    from repro.kernels import ops

    dense = generate_dense(params_from_name(db, seed=SEED))
    shards = fimi.shard_db(dense, P)
    _, T, IW = shards.shape
    n_items = dense.shape[1]
    _, multi = ops.support_fns()
    W = -(-P * T // 32)
    check(lowers_to_pallas(multi, (n_items, W), (16, W)),
          "A: the default multi-support plug-in does not lower to Pallas")
    params = cluster_params(support, P * T)
    key = jax.random.PRNGKey(SEED)
    run = partial(cluster.execute, shards, n_items, params, key,
                  spmd=fimi.vmap_spmd)
    res, setup_s = timed(run)
    res, steady_s = timed(run)
    oracle = eclat.brute_force_fis(dense[: P * T], res.plan.abs_minsup)
    got = res.table.to_dict()
    check(got == oracle,
          f"A: executor |F|={len(got)} != brute force |F|={len(oracle)} "
          f"(missing {len(set(oracle) - set(got))}, extra "
          f"{len(set(got) - set(oracle))})")
    report("A exact vs brute force", setup_s, steady_s,
           f"|F|={len(got)} on {db} (P={P}, minsup={res.plan.abs_minsup})")


def phase_b(store_dir: str, db: str = REAL_DB, support: float = REAL_SUPPORT):
    """Real size from disk: fimi.run(store) and the executor, both exact."""
    import jax

    from repro import cluster
    from repro.core import eclat, fimi
    from repro.data.ibm_gen import params_from_name
    from repro.store import write_ibm_store
    from repro.store.reader import to_device_shards

    t0 = time.perf_counter()
    store = write_ibm_store(params_from_name(db, seed=SEED), store_dir,
                            block_tx=STORE_BLOCK_TX)
    print(f"[B] spilled {db}: {store.n_tx} tx x {store.n_items} items, "
          f"{store.n_blocks} blocks, {store.total_bytes} B on disk in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    key = jax.random.PRNGKey(SEED)
    n_items = store.n_items

    # -- as launch/mine.py does ----------------------------------------------
    fp = fimi.FimiParams(
        min_support_rel=support, n_db_sample=min(2048, store.n_tx),
        n_fi_sample=1024,
        eclat=eclat.EclatConfig(
            max_out=1 << 15, max_stack=8192, frontier_size=16
        ),
    )
    mine = partial(fimi.run, store, None, fp, key, P=P, materialize=True)
    res, fimi_setup = timed(mine)
    res, fimi_steady = timed(mine)
    check(res.exchange_overflow == 0, "B: fimi.run exchange overflow")
    check(int(res.phase4.overflow.sum()) == 0, "B: fimi.run stack overflow")
    check(int(res.phase4.fi_total.sum()) == int(res.phase4.fi_count.sum()),
          "B: fimi.run output buffer overflow")

    # -- as launch/cluster_mine.py does --------------------------------------
    cp = cluster_params(support, store.n_tx)
    plan = cluster.plan(store, None, cp.planner, key, P=P)
    shards = jax.block_until_ready(to_device_shards(store, P))
    execute = partial(cluster.execute, shards, n_items, cp, key, plan=plan,
                      spmd=fimi.vmap_spmd)
    exe, exe_setup = timed(execute)
    exe, exe_steady = timed(execute)
    ref, ref_s = timed(partial(
        cluster.execute, shards, n_items, dataclasses.replace(cp, force="ref"),
        key, plan=plan, spmd=fimi.vmap_spmd))
    check(same_table(exe.table, ref.table),
          f"B: executor table != force='ref' table "
          f"(|F| {exe.table.n_fis} vs {ref.table.n_fis})")
    want = ref.table.to_dict()
    check(res.fi_dict == want,
          f"B: fimi.run |F|={len(res.fi_dict)} != executor(ref) "
          f"|F|={len(want)}")
    report("B fimi.run(store)", fimi_setup, fimi_steady,
           f"|F|={len(res.fi_dict)} on {db} (P={P}, minsup={plan.abs_minsup})")
    report("B executor", exe_setup, exe_steady,
           f"|F|={exe.table.n_fis}, rounds={exe.report.n_rounds}; "
           f"force='ref' reference took {ref_s:.3f}s")
    return store, exe.table


def phase_c(store, table, queries: int = QUERIES) -> None:
    """Serve B's table: every answer equals the force='ref' engine's."""
    import numpy as np

    from repro.kernels import ops
    from repro.launch.serve_mine import KINDS, build_workload
    from repro.serve import QueryEngine
    from repro.serve.index import build_indexes

    fis = table.to_dict()
    fi_index, rule_index = build_indexes(fis, store.n_items, store.n_tx,
                                         min_confidence=0.5)
    check(lowers_to_pallas(ops.subset_superset_counts,
                           (QUERY_BATCH, store.n_words),
                           (fi_index.n_fis, store.n_words)),
          "C: the serving sweep does not lower to Pallas")
    engine = QueryEngine(fi_index, rule_index, batch=QUERY_BATCH)
    reference = QueryEngine(fi_index, rule_index, batch=QUERY_BATCH,
                            force="ref")
    stream = build_workload(np.random.default_rng(SEED + 1), fis,
                            store.to_dense(), store.n_items, queries)
    by_kind = {k: np.stack([m for kk, m in stream if kk == k])
               for k in KINDS if any(kk == k for kk, _ in stream)}

    def answer(eng):
        out = {}
        for kind, masks in by_kind.items():
            call = {"support": eng.support, "rules": eng.rules_for,
                    "superset": eng.supersets}[kind]
            parts = [call(masks[lo: lo + QUERY_BATCH])
                     for lo in range(0, len(masks), QUERY_BATCH)]
            out[kind] = (np.concatenate(parts) if kind == "support" else
                         tuple(np.concatenate(p) for p in zip(*parts)))
        return out

    got, setup_s = timed(lambda: answer(engine))
    got, steady_s = timed(lambda: answer(engine))
    want = answer(reference)
    for kind in by_kind:
        g, w = got[kind], want[kind]
        same = (np.array_equal(g, w) if kind == "support" else
                all(np.array_equal(a, b, equal_nan=True)
                    for a, b in zip(g, w)))
        check(same, f"C: {kind} answers differ from the force='ref' engine")
    report("C serve", setup_s, steady_s,
           f"{len(stream)} queries, 0 errors, F={fi_index.n_fis} "
           f"R={rule_index.n_rules}, mix "
           + ",".join(f"{k}={len(v)}" for k, v in by_kind.items()))


def phase_d(n_items: int = 1000, block_tx: int = STREAM_BLOCK_TX,
            window_blocks: int = STREAM_WINDOW_BLOCKS,
            extra_blocks: int = STREAM_EXTRA_BLOCKS,
            support: float = STREAM_SUPPORT) -> None:
    """Stream: delta-maintained window supports equal the ref miner's."""
    import numpy as np

    from repro.core import bitmap as bm
    from repro.data.ibm_gen import IBMParams, drifting_stream
    from repro.kernels import ops
    from repro.stream import StreamingMiner, StreamParams

    IW = bm.n_words(n_items)
    check(lowers_to_pallas(ops.delta_supports, (block_tx, IW), (block_tx, IW),
                           (1024, IW)),
          "D: the window update does not lower to Pallas")
    gen = IBMParams(n_items=n_items, n_patterns=2000, avg_pattern_len=4,
                    avg_tx_len=10, seed=SEED)
    n_blocks = window_blocks + extra_blocks
    blocks = [b for b, _ in drifting_stream(
        gen, n_blocks=n_blocks, block_tx=block_tx,
        breaks=(window_blocks + extra_blocks // 2,))]
    sp = StreamParams(n_blocks=window_blocks, block_tx=block_tx,
                      min_support_rel=support, border_margin=0.002,
                      border_hysteresis=0.001, cooldown_blocks=1, seed=SEED)
    miner = StreamingMiner(sp, n_items)
    reference = StreamingMiner(dataclasses.replace(sp, force="ref"), n_items)
    admit_s, remines = [], 0
    for i, block in enumerate(blocks):
        ev, dt = timed(lambda: miner.admit(block))
        ref_ev = reference.admit(block)
        admit_s.append(dt)
        remines += ev.remined
        check(ev.remined == ref_ev.remined and
              ev.delta_applied == ref_ev.delta_applied,
              f"D: block {i}: control flow differs from the ref miner")
        if miner.engine is not None:
            check(np.array_equal(miner.current_supports,
                                 reference.current_supports),
                  f"D: block {i}: window supports differ from the ref miner")
    check(np.array_equal(miner.current_supports,
                         miner.exact_window_supports()),
          "D: delta-maintained supports != full window recount")
    steady = admit_s[window_blocks:]
    report("D stream", sum(admit_s[:window_blocks]),
           sum(steady) / max(len(steady), 1),
           f"{n_blocks} blocks x {block_tx} tx, window {window_blocks} "
           f"blocks, F={miner.engine.index.n_fis}, {remines} mines "
           f"(steady = mean admit incl. delta update)")


def phase_four_chip(store_dir: str, db: str = REAL_DB,
                    support: float = REAL_SUPPORT) -> None:
    """B's executor mine under shard_map on 4 chips == vmap on one chip."""
    import jax
    from jax.sharding import NamedSharding

    from repro import cluster
    from repro.core import fimi, phases
    from repro.data.ibm_gen import params_from_name
    from repro.launch.mesh import make_miner_mesh
    from repro.store import write_ibm_store
    from repro.store.reader import place_on_mesh, to_device_shards

    store = write_ibm_store(params_from_name(db, seed=SEED), store_dir,
                            block_tx=STORE_BLOCK_TX)
    mesh = make_miner_mesh(P)
    key = jax.random.PRNGKey(SEED)
    cp = cluster_params(support, store.n_tx)
    plan = cluster.plan(store, None, cp.planner, key, P=P)
    on_one = jax.block_until_ready(to_device_shards(store, P))
    placed = place_on_mesh(on_one, mesh)
    devices = {s.device for s in placed.addressable_shards}
    check(isinstance(placed.sharding, NamedSharding) and len(devices) == P,
          f"4-chip: shards not one per chip ({placed.sharding})")
    _, T, IW = placed.shape
    exchange = fimi.shard_map_spmd(
        partial(phases.phase3_exchange, axis_name=fimi.AXIS, capacity=T),
        P, mesh)
    C = 8
    hlo = exchange.lower(
        placed, jax.numpy.ones((P, T), bool),
        jax.numpy.zeros((P, C, IW), "uint32"), jax.numpy.zeros((P, C), bool),
        jax.numpy.zeros((P, C), "int32"),
    ).compile().as_text()
    check("all-to-all" in hlo, "4-chip: no all-to-all in the exchange HLO")

    run = partial(cluster.execute, placed, store.n_items, cp, key, plan=plan,
                  spmd=fimi.shard_map_spmd, mesh=mesh)
    sm, setup_s = timed(run)
    sm, steady_s = timed(run)
    vm, vm_s = timed(partial(cluster.execute, on_one, store.n_items, cp, key,
                             plan=plan, spmd=fimi.vmap_spmd))
    check(sm.report.backend == "shard_map", "4-chip: executor did not shard")
    check(same_table(sm.table, vm.table),
          f"4-chip: shard_map table != vmap table "
          f"(|F| {sm.table.n_fis} vs {vm.table.n_fis})")
    report("4-chip executor shard_map", setup_s, steady_s,
           f"|F|={sm.table.n_fis} bit-exact vs vmap on one chip "
           f"({vm_s:.3f}s incl. compile), all-to-all in HLO, shards on "
           f"{len(devices)} chips")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chip", action="store_true", dest="four_chip",
                    help="run only phase B's executor mine under shard_map "
                         "on 4 chips and compare it with vmap on one")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {dev.platform}); nothing run",
              file=sys.stderr)
        return 2
    need = P if args.four_chip else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, found {len(devices)}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch import compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e})", file=sys.stderr)
        return 2
    print(f"chip_smoke: {dev.platform} {dev.device_kind} x{len(devices)}, "
          f"compile cache {compile_cache.enable()}", flush=True)

    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        if args.four_chip:
            phase_four_chip(tmp)
        else:
            phase_a()
            store, table = phase_b(tmp)
            phase_c(store, table)
            phase_d()
    print(f"chip_smoke: all phases passed in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

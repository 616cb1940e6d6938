"""Distributed mining executor: planner, executor parity, rebalancing.

The subsystem's headline invariant mirrors the paper's: however the sample
estimates the tree and however the rebalancer shuffles it, the merged result
is the EXACT frequent-itemset set of the whole database — asserted against
the brute-force oracle under vmap, under interpret-mode Pallas kernels on
ragged item counts, and (in a subprocess with its own device count) under
real 4-device shard_map.
"""
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import cluster
from repro.core import eclat, fimi, pbec


def _planner_params(**kw):
    base = dict(min_support_rel=0.08, n_db_sample=256, n_fi_sample=128,
                alpha=0.7)
    base.update(kw)
    return cluster.PlannerParams(**base)


@pytest.fixture(scope="module")
def small_plan(small_db):
    dense, db, minsup, oracle = small_db
    shards = fimi.shard_db(dense, 4)
    plan = cluster.plan(shards, 24, _planner_params(), jax.random.PRNGKey(3))
    return dense, oracle, shards, plan


# ---------------------------------------------------------------------------
# Planner
# ---------------------------------------------------------------------------


def test_planner_deterministic(small_db):
    """Same inputs + key ⇒ identical plan (multi-host agreement requirement)."""
    dense, db, minsup, oracle = small_db
    shards = fimi.shard_db(dense, 4)
    a = cluster.plan(shards, 24, _planner_params(), jax.random.PRNGKey(3))
    b = cluster.plan(shards, 24, _planner_params(), jax.random.PRNGKey(3))
    assert np.array_equal(a.assignment, b.assignment)
    assert np.array_equal(a.est_sizes, b.est_sizes)
    assert a.scheduler_used == b.scheduler_used
    assert [c.seq for c in a.classes] == [c.seq for c in b.classes]
    assert a.shard_queues() == b.shard_queues()


def test_planner_estimation_error_thm61(small_db):
    """Thm 6.1: item supports on D̃ are within ε of the true supports, and the
    class-size shares the scheduler balances on track the exact FI shares."""
    dense, db, minsup, oracle = small_db
    shards = fimi.shard_db(dense, 4)
    plan = cluster.plan(shards, 24, _planner_params(), jax.random.PRNGKey(3))

    true_rel = dense.mean(axis=0)
    err = np.abs(plan.sample_item_rel - true_rel).max()
    # the bound holds w.p. 1−δ; this seed is fixed, so assert it outright
    assert err <= plan.eps_db_effective, (err, plan.eps_db_effective)

    # class-size estimation: sample shares vs exact |class ∩ F| shares
    exact_masks = np.zeros((len(oracle), 24), bool)
    for i, s_ in enumerate(oracle):
        exact_masks[i, sorted(s_)] = True
    exact = np.array([
        pbec.member_mask(exact_masks, c.prefix, c.ext).sum()
        for c in plan.classes
    ], dtype=float)
    est = plan.est_sizes
    assert est.sum() > 0 and exact.sum() > 0
    share_err = np.abs(est / est.sum() - exact / exact.sum()).max()
    assert share_err <= 0.1, share_err


def test_planner_volumes_and_queues(small_plan):
    dense, oracle, shards, plan = small_plan
    # both schedules were priced; the chosen one is recorded
    assert plan.scheduler_used in ("lpt", "repl_min")
    assert plan.lpt_volume > 0 and plan.repl_volume > 0
    if plan.scheduler_used == "repl_min":
        assert plan.repl_volume < plan.lpt_volume
    queues = plan.shard_queues()
    assert sorted(c for q in queues for c in q) == list(range(len(plan.classes)))
    # queues drain heaviest-first so early rounds carry the scheduled weight
    for q in queues:
        sizes = [plan.est_sizes[c] for c in q]
        assert sizes == sorted(sizes, reverse=True)


# ---------------------------------------------------------------------------
# Executor: exactness under every backend/configuration
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("P", [2, 4])
def test_executor_exact_vmap(small_db, P):
    dense, db, minsup, oracle = small_db
    shards = fimi.shard_db(dense, P)
    res = cluster.execute(
        shards, 24,
        cluster.ClusterParams(planner=_planner_params()),
        jax.random.PRNGKey(1),
    )
    assert res.report.backend == "vmap"
    assert res.report.exchange_overflow == 0 and res.report.mine_overflow == 0
    assert res.table.to_dict() == oracle
    assert res.table.n_fis == len(oracle)


def test_executor_exact_under_rebalancing(small_db):
    """chunk=1 forces many rounds; a tiny FI sample forces skewed estimates;
    donations must fire and the result must stay exact."""
    dense, db, minsup, oracle = small_db
    shards = fimi.shard_db(dense, 4)
    res = cluster.execute(
        shards, 24,
        cluster.ClusterParams(
            planner=_planner_params(n_fi_sample=32),
            chunk=1, rebalance=True, skew_threshold=1.05,
        ),
        jax.random.PRNGKey(1),
    )
    assert res.report.n_rounds > 1
    assert len(res.report.donations) > 0
    assert res.table.to_dict() == oracle


def test_rebalancing_no_worse_than_static(small_db):
    """Same round structure, donations on vs off: modeled makespan must not
    regress, and the mined set is identical."""
    dense, db, minsup, oracle = small_db
    shards = fimi.shard_db(dense, 4)

    def run(rebalance):
        return cluster.execute(
            shards, 24,
            cluster.ClusterParams(
                planner=_planner_params(n_fi_sample=32, scheduler="lpt"),
                chunk=2, rebalance=rebalance,
            ),
            jax.random.PRNGKey(1),
        )

    static, rebal = run(False), run(True)
    assert static.table.to_dict() == rebal.table.to_dict() == oracle
    assert rebal.report.makespan_trips <= static.report.makespan_trips


def test_executor_exact_ragged_interpret():
    """Ragged item count (33 > one word) + interpret-mode Pallas kernels."""
    from repro.data.ibm_gen import IBMParams, generate_dense

    dense = generate_dense(IBMParams(
        n_tx=128, n_items=33, n_patterns=5, avg_pattern_len=4,
        avg_tx_len=6, seed=9,
    ))
    oracle = eclat.brute_force_fis(dense, int(np.ceil(0.1 * 128)))
    shards = fimi.shard_db(dense, 2)
    res = cluster.execute(
        shards, 33,
        cluster.ClusterParams(
            planner=_planner_params(
                min_support_rel=0.1, n_db_sample=64, n_fi_sample=64
            ),
            eclat=eclat.EclatConfig(
                max_out=4096, max_stack=1024, frontier_size=4
            ),
            force="interpret",
        ),
        jax.random.PRNGKey(5),
    )
    assert res.table.to_dict() == oracle


def test_executor_report_telemetry(small_plan):
    dense, oracle, shards, plan = small_plan
    res = cluster.execute(
        shards, 24,
        cluster.ClusterParams(planner=_planner_params()),
        jax.random.PRNGKey(3),
        plan=plan,
    )
    rep = res.report
    assert set(rep.phase_ms) == {"plan", "exchange", "mine", "merge"}
    assert rep.phase_ms["mine"] > 0
    assert rep.observed_loads.shape == (4,)
    assert rep.observed_loads.sum() > 0
    assert rep.imbalance >= 1.0
    assert 0.0 <= rep.estimation_error() <= 1.0
    assert rep.makespan_trips >= rep.observed_loads.max() / max(
        rep.n_rounds, 1
    )


# ---------------------------------------------------------------------------
# Rebalancer unit behavior
# ---------------------------------------------------------------------------


def test_ledger_rates_and_rebalance_bounds():
    ledger = cluster.LoadLedger(3)
    # shard 0's classes were under-estimated 4×; shard 1 spot-on; shard 2 idle
    ledger.record_round(np.array([40.0, 10.0, 0.0]), np.array([10.0, 10.0, 0.0]))
    rates = ledger.rates()
    assert rates[0] == pytest.approx(4.0)
    assert rates[1] == pytest.approx(1.0)
    assert rates[2] == pytest.approx(ledger.global_rate)  # no history → global

    est = np.array([8.0, 6.0, 4.0, 2.0, 1.0, 1.0])
    queues = [[0, 1, 2, 3], [4], [5]]
    moves = cluster.rebalance(
        queues, est, ledger, round_index=1,
        skew_threshold=1.1, max_donations=2,
    )
    assert 0 < len(moves) <= 2
    for m in moves:
        assert m.src == 0  # only the overloaded shard donates
        assert m.round_index == 1
    # donations come off the tail (cheapest pending classes first)
    donated = {m.class_id for m in moves}
    assert donated <= {2, 3}
    assert sorted(c for q in queues for c in q) == list(range(6))


def test_rebalance_noop_when_balanced():
    ledger = cluster.LoadLedger(2)
    queues = [[0], [1]]
    est = np.array([5.0, 5.0])
    moves = cluster.rebalance(queues, est, ledger, round_index=0)
    assert moves == []
    assert queues == [[0], [1]]


# ---------------------------------------------------------------------------
# shard_map parity — separate process with its own device count
# ---------------------------------------------------------------------------

_PARITY_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax
from repro import cluster
from repro.core import eclat, fimi
from repro.data.ibm_gen import IBMParams, generate_dense

dense = generate_dense(IBMParams(n_tx=256, n_items=16, n_patterns=6,
                                 avg_pattern_len=4, avg_tx_len=6, seed=11))
oracle = eclat.brute_force_fis(dense, int(np.ceil(0.1 * 256)))
shards = fimi.shard_db(dense, 4)
params = cluster.ClusterParams(
    planner=cluster.PlannerParams(min_support_rel=0.1, n_db_sample=128,
                                  n_fi_sample=64, alpha=0.7))
res = cluster.execute(shards, 16, params, jax.random.PRNGKey(2))
assert res.report.backend == "shard_map", res.report.backend
assert res.table.to_dict() == oracle, "cluster shard_map result != oracle"
fp = fimi.FimiParams(min_support_rel=0.1, n_db_sample=128, n_fi_sample=64,
                     alpha=0.7)
ref = fimi.run(fimi.shard_db(dense, 1), 16, fp, jax.random.PRNGKey(2),
               materialize=True)
assert res.table.to_dict() == ref.fi_dict, "cluster != single-device fimi.run"
print("CLUSTER_SHARD_MAP_PARITY_OK", len(oracle))
"""


def test_cluster_shard_map_parity_subprocess():
    """4 real host devices: shard_map executor == oracle == 1-device fimi.run
    (bit-exact supports; device-count flag isolated in a subprocess)."""
    r = subprocess.run(
        [sys.executable, "-c", _PARITY_SCRIPT],
        capture_output=True, text=True, timeout=900,
        env={**__import__("os").environ, "PYTHONPATH": "src"},
        cwd=str(__import__("pathlib").Path(__file__).resolve().parents[1]),
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "CLUSTER_SHARD_MAP_PARITY_OK" in r.stdout


# ---------------------------------------------------------------------------
# The store path: cluster.mine_store, its spans and their counts
# ---------------------------------------------------------------------------


def _store_of(dense, path, block_tx=64):
    from repro.store import StoreWriter

    w = StoreWriter(str(path), n_items=dense.shape[1], block_tx=block_tx)
    w.append_dense(dense)
    return w.close()


def _exchange_counts(dense, P, prefixes_per_miner):
    """Host count of one round's exchange: ``(rows that leave their miner,
    rows held after it over |D|)``.  Miner p needs every row that holds the
    prefix of one of its classes; miner q holds rows ``[q·T, (q+1)·T)``."""
    T = dense.shape[0] // P
    rows = np.asarray(dense[: P * T], bool)
    need = np.zeros((P * T, P), bool)
    for p, prefixes in enumerate(prefixes_per_miner):
        for prefix in prefixes:
            need[:, p] |= rows[:, np.asarray(prefix, bool)].all(axis=1)
    owner = np.repeat(np.arange(P), T)
    leaving = int(need.sum() - need[np.arange(P * T), owner].sum())
    return leaving, float(need.sum()) / (P * T)


def _host_spans(events, name):
    return [e for e in events if e.get("ph") == "X" and e["name"] == name
            and e.get("cat", "host") == "host"]


@pytest.fixture(scope="module")
def traced_store_mine(small_db, tmp_path_factory):
    """One traced ``mine_store`` of a store of the small DB (P=4 vmapped,
    chunk 2: several rounds), the classes each round handed each miner,
    and an untraced mine of the same store."""
    from repro.cluster import planner as planner_mod
    from repro.obs import trace as obs_trace

    dense, _, _, oracle = small_db
    store = _store_of(dense, tmp_path_factory.mktemp("st") / "st")
    params = cluster.ClusterParams(planner=_planner_params(n_fi_sample=32),
                                   chunk=2, skew_threshold=1.05)
    taken = []
    real_pack = planner_mod.pack_seeds

    def pack_seeds(classes, ids_per_shard, *a, **k):
        taken.append([[classes[c].prefix for c in ids] for ids in ids_per_shard])
        return real_pack(classes, ids_per_shard, *a, **k)

    tr = obs_trace.TRACER
    tr.clear()
    tr.enable()
    planner_mod.pack_seeds = pack_seeds
    try:
        res = cluster.mine_store(store, params, jax.random.PRNGKey(1), 4)
    finally:
        planner_mod.pack_seeds = real_pack
        tr.disable()
    events = tr.export()["traceEvents"]
    tr.clear()
    off = cluster.mine_store(store, params, jax.random.PRNGKey(1), 4)
    n_off = tr.n_events
    return dense, oracle, res, events, taken, off, n_off


def test_mine_store_is_exact_and_reports_its_phases(traced_store_mine):
    dense, oracle, res, _, _, off, _ = traced_store_mine
    assert res.report.backend == "vmap"
    assert res.table.to_dict() == oracle == off.table.to_dict()
    assert set(res.report.phase_ms) == {"plan", "exchange", "mine", "merge",
                                        "assemble"}
    assert res.report.phase_ms["plan"] > 0
    assert res.report.phase_ms["assemble"] > 0


def test_mine_store_spans_one_mine(traced_store_mine):
    _, _, res, events, _, _, n_off = traced_store_mine
    run = _host_spans(events, "cluster/run")
    assert len(run) == 1
    mine = run[0]["args"]["mine"]
    for name in ("cluster/plan", "cluster/assemble", "cluster/merge"):
        got = _host_spans(events, name)
        assert len(got) == 1, name
        assert got[0]["args"]["mine"] == mine
        assert run[0]["ts"] <= got[0]["ts"]
        assert got[0]["ts"] + got[0]["dur"] <= run[0]["ts"] + run[0]["dur"]
    for name in ("cluster/exchange", "cluster/mine"):
        got = _host_spans(events, name)
        assert len(got) == res.report.n_rounds, name
        assert {e["args"]["mine"] for e in got} == {mine}
    assert n_off == 0          # tracing off records nothing


def test_exchange_and_mine_spans_count_what_the_rounds_did(traced_store_mine):
    """``rows_moved`` and ``replication`` on ``cluster/exchange`` equal a
    host count over each round's class table; ``trips`` and ``donations``
    on ``cluster/mine`` equal the round's RoundStats, and ``pushed`` counts
    each miner's pushes, at most one per child slot of a trip."""
    dense, _, res, events, taken, _, _ = traced_store_mine
    rounds = res.report.rounds
    assert len(rounds) > 1 and len(taken) == len(rounds)
    assert sum(len(r.donations) for r in rounds) > 0
    exchange = _host_spans(events, "cluster/exchange")
    mined = _host_spans(events, "cluster/mine")
    slots = cluster.ClusterParams().eclat.frontier_size * dense.shape[1]
    for r, ex, mi, prefixes in zip(rounds, exchange, mined, taken):
        leaving, replication = _exchange_counts(dense, 4, prefixes)
        assert ex["args"]["rows_moved"] == leaving > 0
        assert ex["args"]["replication"] == pytest.approx(r.replication)
        assert r.replication == pytest.approx(replication, rel=1e-6)
        assert ex["args"]["overflow"] == 0
        assert mi["args"]["trips"] == r.work_iters.tolist()
        assert mi["args"]["donations"] == len(r.donations)
        pushed = mi["args"]["pushed"]
        assert len(pushed) == 4 and all(type(n) is int for n in pushed)
        assert all(0 <= n <= t * slots
                   for n, t in zip(pushed, r.work_iters.tolist()))
    assert sum(sum(mi["args"]["pushed"]) for mi in mined) > 0


_STORE_SCRIPT = """
import json, os, sys, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax
from repro import cluster
from repro.cluster import planner as planner_mod
from repro.core import eclat, fimi
from repro.data.ibm_gen import IBMParams, generate_dense
from repro.obs import trace as obs_trace
from repro.store import StoreWriter

dense = generate_dense(IBMParams(n_tx=256, n_items=16, n_patterns=6,
                                 avg_pattern_len=4, avg_tx_len=6, seed=11))
w = StoreWriter(os.path.join(tempfile.mkdtemp(), "st"), n_items=16,
                block_tx=48)
w.append_dense(dense)
store = w.close()
params = cluster.ClusterParams(
    planner=cluster.PlannerParams(min_support_rel=0.1, n_db_sample=128,
                                  n_fi_sample=64, alpha=0.7), chunk=2)
taken = []
real_pack = planner_mod.pack_seeds
def pack_seeds(classes, ids, *a, **k):
    taken.append([[np.nonzero(classes[c].prefix)[0].tolist() for c in i]
                  for i in ids])
    return real_pack(classes, ids, *a, **k)
planner_mod.pack_seeds = pack_seeds
obs_trace.TRACER.enable()
res = cluster.mine_store(store, params, jax.random.PRNGKey(2), 4)
obs_trace.TRACER.disable()
oracle = eclat.brute_force_fis(dense, int(np.ceil(0.1 * 256)))
fp = fimi.FimiParams(min_support_rel=0.1, n_db_sample=128, n_fi_sample=64,
                     alpha=0.7)
ref = fimi.run(fimi.shard_db(dense, 1), 16, fp, jax.random.PRNGKey(2),
               materialize=True)
got = res.table.to_dict()
ex = [e["args"] for e in obs_trace.TRACER.export()["traceEvents"]
      if e.get("name") == "cluster/exchange"]
print("MINE_STORE " + json.dumps({
    "backend": res.report.backend, "oracle": got == oracle,
    "fimi": got == ref.fi_dict, "n": len(got), "taken": taken,
    "rows_moved": [a["rows_moved"] for a in ex]}))
"""


def test_mine_store_shard_map_subprocess():
    """4 host devices: ``mine_store`` places one miner per device, equals
    the brute-force oracle and one-device ``fimi.run``, and its
    ``rows_moved`` under ``shard_map`` equals the host count."""
    import json
    import os
    from pathlib import Path

    from repro.data.ibm_gen import IBMParams, generate_dense

    r = subprocess.run(
        [sys.executable, "-c", _STORE_SCRIPT],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "PYTHONPATH": "src"},
        cwd=str(Path(__file__).resolve().parents[1]),
    )
    assert r.returncode == 0, r.stderr[-3000:]
    line = next(x for x in r.stdout.splitlines()
                if x.startswith("MINE_STORE "))
    got = json.loads(line[len("MINE_STORE "):])
    assert got["backend"] == "shard_map"
    assert got["oracle"] and got["fimi"] and got["n"] > 0
    dense = generate_dense(IBMParams(n_tx=256, n_items=16, n_patterns=6,
                                     avg_pattern_len=4, avg_tx_len=6,
                                     seed=11))
    assert len(got["taken"]) == len(got["rows_moved"]) > 1
    for items_per_miner, moved in zip(got["taken"], got["rows_moved"]):
        prefixes = [[np.isin(np.arange(16), items) for items in miner]
                    for miner in items_per_miner]
        assert moved == _exchange_counts(dense, 4, prefixes)[0]


# ---------------------------------------------------------------------------
# StreamingMiner integration — distributed re-mines
# ---------------------------------------------------------------------------


def test_streaming_miner_with_cluster_mine_fn():
    """The executor plugs in as StreamingMiner.mine_fn: the initial mine and
    a forced re-mine are exact over the live window and the swap generation
    advances atomically."""
    from repro.data.ibm_gen import IBMParams, drifting_stream
    from repro.stream import StreamingMiner, StreamParams

    p = IBMParams(n_tx=512, n_items=20, n_patterns=6, avg_pattern_len=4,
                  avg_tx_len=7, seed=4)
    sp = StreamParams(
        n_blocks=2, block_tx=64, min_support_rel=0.15,
        eps=0.01, delta=0.2, check_every=1, cooldown_blocks=0, seed=4,
    )
    mine_fn = cluster.cluster_mine_fn(
        P=2,
        cluster_params=cluster.ClusterParams(
            planner=cluster.PlannerParams(n_db_sample=128, n_fi_sample=64),
            eclat=eclat.EclatConfig(max_out=4096, max_stack=1024,
                                    frontier_size=4),
        ),
        seed=4,
    )
    sm = StreamingMiner(sp, p.n_items, mine_fn=mine_fn)

    seen = []
    for dense_block, _ in drifting_stream(
        p, n_blocks=4, block_tx=64, breaks=(2,)
    ):
        ev = sm.admit(dense_block)
        seen.append(np.asarray(dense_block))
        if ev.remined:
            # distributed re-mine == brute force over the current window
            window_dense = np.concatenate(seen[-2:], axis=0)
            oracle = eclat.brute_force_fis(window_dense, sm.abs_minsup)
            idx = sm.engine.index
            got = {}
            masks = np.asarray(
                jnp.asarray(idx.masks[: idx.n_fis])
            )
            from repro.core import bitmap as bm

            dense_masks = np.asarray(
                bm.unpack_bool(jnp.asarray(masks), p.n_items)
            )
            for row, s in zip(dense_masks, np.asarray(idx.supports)):
                got[frozenset(np.nonzero(row)[0].tolist())] = int(s)
            assert got == oracle
            assert ev.generation == sm.engine.generation
    assert sm.engine is not None and sm.stats.remines >= 1

"""The benchmark's data and traffic, and whole runs at a tiny size on the CPU:
correct when the program is sound, not correct under each planted fault."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
sys.path.insert(0, str(BENCH_DIR))

import reference  # noqa: E402
import run  # noqa: E402
from gen import ibm_quest  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: json.loads((ROOT / c["file"]).read_text())
           for c in BENCH["configs"]}


DS = dict(n_tx=6000, n_items=200, n_patterns=50, avg_pattern_len=6,
          avg_tx_len=20, correlation=0.5, corruption=0.5,
          corruption_var=0.1, pattern_seed=4, block_tx=2048)


def _dense(ds, seed):
    return np.concatenate(list(ibm_quest.generate_blocks(ds, seed)))


def test_rows_come_from_the_seed_and_the_table_from_the_config():
    a, b, c = _dense(DS, 2**31 + 11), _dense(DS, 2**31 + 11), _dense(
        DS, 2**31 + 12)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert [x.shape for x in ibm_quest.generate_blocks(DS, 1)] == [
        (2048, 200), (2048, 200), (1904, 200)]
    t1 = ibm_quest.pattern_table(**DS)
    t2 = ibm_quest.pattern_table(**dict(DS, pattern_seed=5))
    np.testing.assert_array_equal(t1.items, ibm_quest.pattern_table(**DS).items)
    assert not np.array_equal(t1.weights, t2.weights)


def test_pattern_table_follows_the_source():
    t = ibm_quest.pattern_table(**dict(DS, n_patterns=4000))
    assert abs(t.sizes.mean() - 6) < 0.2           # Poisson, mean |I|
    assert abs(t.weights.sum() - 1) < 1e-12
    # normal of mean 0.5 and variance 0.1, clipped to [0, 1]: the clip
    # leaves the quartiles where they were (IQR = 1.349 sd)
    q1, q2, q3 = np.quantile(t.corruption, [0.25, 0.5, 0.75])
    assert abs(q2 - 0.5) < 0.02
    assert abs((q3 - q1) - 1.349 * np.sqrt(0.1)) < 0.03
    assert abs(np.mean(t.corruption == 0.0) - 0.057) < 0.015
    for row, size in zip(t.items, t.sizes):
        got = row[row >= 0]
        assert len(got) == size == len(set(got.tolist()))
    # later itemsets share items with the one before them
    shared = [len(np.intersect1d(t.items[k][t.items[k] >= 0],
                                 t.items[k - 1][t.items[k - 1] >= 0]))
              for k in range(1, 200)]
    assert np.mean(shared) > 1.0


def test_corruption_drops_items_while_a_draw_is_below_the_level():
    """An itemset of size l keeps l items 1-c of the time, l-1 items
    c(1-c) of the time, and so on."""
    rng = np.random.default_rng(0)
    n, c = 200_000, 0.6
    k = ibm_quest.drops(rng, np.full(n, c), np.full(n, 5))
    for j in range(5):
        assert abs(np.mean(k == j) - c ** j * (1 - c)) < 0.005
    assert np.mean(k == 5) == pytest.approx(c ** 5, abs=0.005)
    assert np.all(ibm_quest.drops(rng, np.zeros(9), np.full(9, 3)) == 0)
    assert np.all(ibm_quest.drops(rng, np.ones(9), np.full(9, 3)) == 3)


def test_transactions_hold_about_the_stated_size():
    sizes = _dense(DS, 7).sum(axis=1)
    assert sizes.min() >= 1
    # items that two of a transaction's itemsets share count twice, so the
    # rows hold somewhat fewer distinct items than the sizes drawn
    assert 17 < sizes.mean() < 20.5


def _rows_of(table, **kw):
    rows, items = (np.concatenate(x) for x in zip(*ibm_quest.transactions(
        table, **kw)))
    return np.bincount(rows, minlength=kw["n_tx"])


def test_an_itemset_that_does_not_fit_moves_on_or_goes_in():
    """Itemsets of 4 items, never corrupted, into transactions of size 6:
    the second does not fit; half the time it goes in (8 items), half the
    time it starts the next transaction (4 items)."""
    table = ibm_quest.PatternTable(
        items=np.array([[0, 1, 2, 3], [4, 5, 6, 7], [8, 9, 10, 11]]),
        sizes=np.array([4, 4, 4]), weights=np.ones(3) / 3,
        corruption=np.zeros(3))
    # size 1 (Poisson of mean ~0, at least 1): the first itemset goes into
    # the empty transaction whole, and the transaction is then full
    assert np.all(_rows_of(table, n_tx=4000, n_items=12, avg_tx_len=1e-9,
                           seed=3) == 4)
    sizes = _rows_of(table, n_tx=4000, n_items=12, avg_tx_len=6, seed=3)
    assert set(np.unique(sizes).tolist()) <= {4, 8, 12}
    assert 0.2 < np.mean(sizes == 8) < 0.6


# ---------------------------------------------------------------------------
# Whole runs at a tiny size on the CPU
# ---------------------------------------------------------------------------


def tiny_spec(cell: str) -> dict:
    spec = run.resolve(BENCH, cell)
    cfg = json.loads(json.dumps(spec["config"]))
    cfg["name"] = "tiny"
    cfg["dataset"].update(n_tx=2048, n_items=48, n_patterns=20,
                          avg_pattern_len=4, avg_tx_len=8, pattern_seed=3,
                          block_tx=512)
    cfg["minsup"] = 0.05
    cfg["mining"].update(n_db_sample=512, n_fi_sample=256, max_out=4096,
                         max_stack=1024, frontier_size=8)
    spec["config"] = cfg
    return spec


MINE = next(c["name"] for c in BENCH["workloads"]
            if run.resolve(BENCH, c["name"])["traffic"]["runner"] == "mine")


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("chipbench_cache")


def _alter_one_support(monkeypatch):
    from repro.core import fimi

    real = fimi.run

    def run_(*a, **k):
        res = real(*a, **k)
        res.phase4 = res.phase4._replace(
            fi_supports=res.phase4.fi_supports.at[0, 0].add(1))
        return res

    monkeypatch.setattr(fimi, "run", run_)


def _half_the_rows_counted_twice(monkeypatch):
    from repro.store import reader

    real = reader.to_device_shards

    def shards(*a, **k):
        s = real(*a, **k)
        h = s.shape[1] // 2
        return s.at[:, h: 2 * h].set(s[:, :h])

    monkeypatch.setattr(reader, "to_device_shards", shards)


def _no_exchange(monkeypatch):
    import jax.numpy as jnp

    from repro.core import phases

    real = phases.phase3_exchange

    def exchange(local_tx, local_valid, *a, **k):
        out = real(local_tx, local_valid, *a, **k)
        T = local_tx.shape[0]
        slab = jnp.zeros_like(out.slab).at[:T].set(local_tx)
        valid = jnp.zeros_like(out.slab_valid).at[:T].set(local_valid)
        return out._replace(slab=slab, slab_valid=valid)

    monkeypatch.setattr(phases, "phase3_exchange", exchange)


def _loop_returns_its_state(monkeypatch):
    """The Phase-4 frontier loop hands back its state as it entered: an
    empty output buffer."""
    import jax.numpy as jnp

    from repro.core import phases

    real = phases.phase4_mine

    def mine(*a, **k):
        out = real(*a, **k)
        return out._replace(fi_count=jnp.zeros_like(out.fi_count),
                            fi_total=jnp.zeros_like(out.fi_total))

    monkeypatch.setattr(phases, "phase4_mine", mine)


@pytest.mark.parametrize("cell,fault", [
    (MINE, None),
    (MINE, _alter_one_support),
    (MINE, _half_the_rows_counted_twice),
    (MINE, _no_exchange),
    (MINE, _loop_returns_its_state),
], ids=["mine-sound", "mine-support-altered", "mine-half-rows",
        "mine-no-exchange", "mine-loop-state-unchanged"])
def test_run_is_correct_only_when_sound(cell, fault, cache, monkeypatch):
    if fault is not None:
        fault(monkeypatch)
    res = run.run_cell(tiny_spec(cell), 2**31 + 7, 1.5, False, cache=cache,
                       t_start=time.perf_counter())
    assert res["correct"] is (fault is None), res["checks"]
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert res["attempted"] > 0
    assert "setup_s" in res["metrics"] and len(res["metrics"]) == 2
    assert list(res)[-2] == "checks"     # "_info" is printed earlier


def test_run_py_without_a_chip_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH_DIR / "run.py"), "--workload", MINE,
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout


def test_every_seed_mines_the_same_work_in_its_own_order():
    from runners import mine

    traffic = json.loads((BENCH_DIR / "traffic" / "mine_repeat.json")
                         .read_text())
    orders = set()
    for seed in (1, 2, 2**31 + 5, 2**40 + 3):
        got = mine.cycle(traffic, seed)
        assert sorted(rs for rs, _ in got) == sorted(
            w["rows_seed"] for w in traffic["work"])
        orders.add(tuple(rs for rs, _ in got))
        assert mine.cycle(traffic, seed)[0][0] == got[0][0]
    assert len(orders) == 2

"""Exactness matrix, store path: ``cluster.mine_store`` (plan off disk,
shards assembled block by block, rounds, merge) returns the brute-force FI
table on every shape of ``exact_matrix``.  The store is written in 64-row
blocks: ``ragged_T``'s last block holds 52 rows, and none of its four
125-row shards fills its last tidlist word."""
import jax
import pytest

from exact_matrix import SHAPES, assert_exact, shape
from repro import cluster
from repro.store import StoreWriter

BLOCK_TX = 64


@pytest.mark.parametrize("name", SHAPES)
def test_mine_store_exact(name, tmp_path):
    sh = shape(name)
    w = StoreWriter(str(tmp_path / "st"), n_items=sh.n_items,
                    block_tx=BLOCK_TX)
    for lo in range(0, sh.n_tx, BLOCK_TX):
        w.append_dense(sh.dense[lo:lo + BLOCK_TX])
    store = w.close()
    assert store.n_tx == sh.n_tx
    params = cluster.ClusterParams(planner=cluster.PlannerParams(
        min_support_rel=sh.min_support_rel, n_db_sample=256, n_fi_sample=128,
        alpha=0.7))
    res = cluster.mine_store(store, params, jax.random.PRNGKey(1), P=4)
    assert res.report.exchange_overflow == 0
    assert res.report.mine_overflow == 0
    assert_exact(res.table.to_dict(), sh)

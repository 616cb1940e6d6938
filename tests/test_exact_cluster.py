"""Exactness matrix, round-based miner: ``cluster.execute`` (host-side
plan, mining in rounds, telemetry-driven donations) returns the brute-force
FI table on every shape of ``exact_matrix``, with no exchange or mining
overflow, whether it plans one round or many.  The phases run under
``exact_matrix.jit_vmap_spmd``."""
import jax
import pytest

from exact_matrix import SHAPES, assert_exact, jit_vmap_spmd, shape
from repro import cluster
from repro.core import fimi

#: arm -> (PlannerParams overrides, ClusterParams overrides)
ARMS = {
    "execute_auto_P4": (dict(scheduler="auto"), dict()),
    # a small FI sample skews the estimates; one class per shard per round
    # and a low skew threshold make many rounds and donations between them
    "execute_lpt_chunk1": (dict(scheduler="lpt", n_fi_sample=32),
                           dict(chunk=1, skew_threshold=1.05)),
}


@pytest.mark.parametrize("arm", ARMS)
@pytest.mark.parametrize("name", SHAPES)
def test_execute_exact(name, arm):
    sh = shape(name)
    plan_over, over = ARMS[arm]
    planner = dict(min_support_rel=sh.min_support_rel, n_db_sample=256,
                   n_fi_sample=128, alpha=0.7) | plan_over
    params = cluster.ClusterParams(
        planner=cluster.PlannerParams(**planner), **over)
    res = cluster.execute(fimi.shard_db(sh.dense, 4), sh.n_items, params,
                          jax.random.PRNGKey(1), spmd=jit_vmap_spmd)
    assert res.report.exchange_overflow == 0
    assert res.report.mine_overflow == 0
    assert_exact(res.table.to_dict(), sh)
    assert res.table.n_tx == sh.n_tx

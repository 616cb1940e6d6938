"""Integration: the full 4-phase Parallel-FIMI pipeline is EXACT.

The thesis' headline invariant — the method "always computes the set of
frequent itemsets from the whole database" regardless of sampling noise —
is asserted literally: distributed result == brute force, for all three
variants, several P, and under both vmap and (separately, in
test_shard_map_parity) real multi-device shard_map.
"""
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import eclat, fimi


@pytest.fixture(scope="module")
def mining_setup(small_db):
    dense, db, minsup, oracle = small_db
    return dense, minsup, oracle


@pytest.mark.parametrize("variant", ["reservoir", "par", "seq"])
@pytest.mark.parametrize("P", [2, 4])
def test_variant_exact(mining_setup, variant, P):
    dense, minsup, oracle = mining_setup
    shards = fimi.shard_db(dense, P)
    params = fimi.FimiParams(
        variant=variant, min_support_rel=0.08, n_db_sample=256,
        n_fi_sample=128, alpha=0.7,
        eclat=eclat.EclatConfig(max_out=4096, max_stack=1024),
    )
    res = fimi.run(shards, 24, params, jax.random.PRNGKey(1), materialize=True)
    assert res.exchange_overflow == 0
    assert res.fi_dict == oracle
    assert res.n_fis == len(oracle)


def test_replication_factor_sane(mining_setup):
    dense, minsup, oracle = mining_setup
    shards = fimi.shard_db(dense, 4)
    params = fimi.FimiParams(
        variant="reservoir", min_support_rel=0.08, n_db_sample=256,
        n_fi_sample=128, alpha=0.7,
        eclat=eclat.EclatConfig(max_out=4096, max_stack=1024),
    )
    res = fimi.run(shards, 24, params, jax.random.PRNGKey(0))
    # Ch. 10: 1 ≤ replication ≤ P
    assert 0.5 <= res.replication <= 4.001


def test_repl_min_scheduler_runs_exact(mining_setup):
    dense, minsup, oracle = mining_setup
    shards = fimi.shard_db(dense, 4)
    params = fimi.FimiParams(
        variant="reservoir", min_support_rel=0.08, n_db_sample=256,
        n_fi_sample=128, alpha=0.7, scheduler="repl_min",
        eclat=eclat.EclatConfig(max_out=4096, max_stack=1024),
    )
    res = fimi.run(shards, 24, params, jax.random.PRNGKey(0), materialize=True)
    assert res.fi_dict == oracle


def test_load_balance_quality(mining_setup):
    """Static balance: max load ≤ 2× mean real work for P=4 (thesis §11.3-ish:
    estimates good enough that no processor gets > ~2/P of the work)."""
    dense, minsup, oracle = mining_setup
    shards = fimi.shard_db(dense, 4)
    params = fimi.FimiParams(
        variant="reservoir", min_support_rel=0.08, n_db_sample=384,
        n_fi_sample=256, alpha=0.4,
        eclat=eclat.EclatConfig(max_out=4096, max_stack=1024),
    )
    res = fimi.run(shards, 24, params, jax.random.PRNGKey(5))
    work = res.work_iters.astype(float)
    assert work.max() <= 2.2 * max(work.mean(), 1.0)


# ---------------------------------------------------------------------------
# shard_map parity — separate process with its own device count
# ---------------------------------------------------------------------------

_PARITY_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax
from repro.core import fimi, eclat
from repro.data.ibm_gen import IBMParams, generate_dense
from repro.launch.mesh import make_miner_mesh

dense = generate_dense(IBMParams(n_tx=256, n_items=16, n_patterns=6,
                                 avg_pattern_len=4, avg_tx_len=6, seed=11))
oracle = eclat.brute_force_fis(dense, int(np.ceil(0.1 * 256)))
shards = fimi.shard_db(dense, 4)
params = fimi.FimiParams(variant="reservoir", min_support_rel=0.1,
                         n_db_sample=128, n_fi_sample=64, alpha=0.7,
                         eclat=eclat.EclatConfig(max_out=2048, max_stack=512))
mesh = make_miner_mesh(4)
res = fimi.run(shards, 16, params, jax.random.PRNGKey(2),
               spmd=fimi.shard_map_spmd, mesh=mesh, materialize=True)
assert res.fi_dict == oracle, "shard_map result != oracle"
res_v = fimi.run(shards, 16, params, jax.random.PRNGKey(2), materialize=True)
assert res_v.fi_dict == oracle, "vmap result != oracle"
print("SHARD_MAP_PARITY_OK", len(oracle))
"""


def test_shard_map_parity_subprocess():
    """The same SPMD phase code runs on 4 real devices via shard_map and
    produces the exact FI set (device-count flag isolated in a subprocess)."""
    r = subprocess.run(
        [sys.executable, "-c", _PARITY_SCRIPT],
        capture_output=True, text=True, timeout=900,
        env={**__import__("os").environ, "PYTHONPATH": "src"},
        cwd=str(__import__("pathlib").Path(__file__).resolve().parents[1]),
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "SHARD_MAP_PARITY_OK" in r.stdout

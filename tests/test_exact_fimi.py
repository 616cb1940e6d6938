"""Exactness matrix, one-shot miner: ``fimi.run`` (device-side planning per
miner, one mining pass) returns the brute-force FI table on every shape of
``exact_matrix`` under every variant, scheduler and miner count it offers,
with no exchange, stack or output-buffer overflow.  The phases run under
``exact_matrix.jit_vmap_spmd``."""
import jax
import numpy as np
import pytest

from exact_matrix import SHAPES, assert_exact, jit_vmap_spmd, shape
from repro.core import eclat, fimi
from repro.store.store import pack_bool_np, unpack_bool_np

#: arm -> (P, FimiParams overrides)
ARMS = {
    "fimi_reservoir_lpt_P4": (4, dict(variant="reservoir", scheduler="lpt")),
    "fimi_reservoir_repl_min_P4": (4, dict(variant="reservoir",
                                           scheduler="repl_min")),
    "fimi_par_P2": (2, dict(variant="par")),
    "fimi_seq_P2": (2, dict(variant="seq")),
    "fimi_P1": (1, dict()),
}


@pytest.mark.parametrize("arm", ARMS)
@pytest.mark.parametrize("name", SHAPES)
def test_fimi_exact(name, arm):
    sh = shape(name)
    P, over = ARMS[arm]
    params = fimi.FimiParams(
        min_support_rel=sh.min_support_rel, n_db_sample=256, n_fi_sample=128,
        alpha=0.7,
        eclat=eclat.EclatConfig(max_out=1 << 14, max_stack=4096,
                                frontier_size=16),
        **over)
    res = fimi.run(fimi.shard_db(sh.dense, P), sh.n_items, params,
                   jax.random.PRNGKey(1), spmd=jit_vmap_spmd)
    p4 = jax.device_get(res.phase4)
    assert res.exchange_overflow == 0
    assert int(np.sum(p4.overflow)) == 0, "DFS stack overflow"
    assert np.array_equal(p4.fi_total, p4.fi_count), "FI buffer overflow"
    anc = res.ancestor_supports >= sh.minsup
    n = p4.fi_count
    got = _table(
        np.concatenate([p4.fi_items[p, :c] for p, c in enumerate(n)]
                       + [pack_bool_np(res.ancestor_masks[anc])]),
        np.concatenate([p4.fi_supports[p, :c] for p, c in enumerate(n)]
                       + [res.ancestor_supports[anc]]),
        sh.n_items)
    assert_exact(got, sh)
    assert res.n_fis == len(sh.oracle)


def _table(masks: np.ndarray, supports: np.ndarray, n_items: int) -> dict:
    """Packed itemset masks + supports as {frozenset: support}; a duplicate
    itemset fails (a merge that reports one twice is not exact)."""
    rows = unpack_bool_np(masks, n_items).reshape(-1, n_items)
    out = {frozenset(np.flatnonzero(r).tolist()): int(s)
           for r, s in zip(rows, np.asarray(supports).reshape(-1))}
    assert len(out) == len(rows), "duplicate itemsets in the FI table"
    return out

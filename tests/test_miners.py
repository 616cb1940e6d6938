"""Eclat / MFI / Apriori miners vs the brute-force oracle."""
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

import jax
import jax.numpy as jnp

from repro.core import apriori, bitmap as bm, eclat, mfi


def _to_dict(res, n_items):
    out = {}
    for k in range(int(res.n_out)):
        mask = np.asarray(bm.unpack_bool(res.items[k], n_items))
        out[frozenset(np.nonzero(mask)[0].tolist())] = int(res.supports[k])
    return out


def test_eclat_thesis_example(thesis_db):
    """|F| = 25 with min_support = 5 (thesis Example 2.1)."""
    res = eclat.mine_all(
        thesis_db, 5, config=eclat.EclatConfig(max_out=128, max_stack=64)
    )
    assert int(res.n_total) == 25 and int(res.stack_overflow) == 0
    got = _to_dict(res, 6)
    want = eclat.brute_force_fis(np.asarray(thesis_db.dense()), 5)
    assert got == want


def test_eclat_matches_bruteforce(small_db):
    dense, db, minsup, oracle = small_db
    res = eclat.mine_all(
        db, minsup, config=eclat.EclatConfig(max_out=8192, max_stack=2048)
    )
    assert int(res.stack_overflow) == 0
    assert _to_dict(res, db.n_items) == oracle


@given(st.integers(0, 10_000), st.floats(0.15, 0.5))
@settings(max_examples=8, deadline=None)
def test_eclat_property_random_dbs(seed, minsup_rel):
    """Property: Eclat == brute force on random small databases."""
    rng = np.random.default_rng(seed)
    dense = rng.random((64, 12)) < rng.uniform(0.2, 0.5)
    db = bm.BitmapDB.from_dense(jnp.asarray(dense))
    minsup = max(1, int(np.ceil(minsup_rel * 64)))
    res = eclat.mine_all(
        db, minsup, config=eclat.EclatConfig(max_out=8192, max_stack=2048)
    )
    assert int(res.stack_overflow) == 0
    assert _to_dict(res, 12) == eclat.brute_force_fis(dense, minsup)


def test_eclat_pbec_restriction(small_db):
    """Mining one PBEC yields exactly the oracle FIs in that class."""
    dense, db, minsup, oracle = small_db
    I = db.n_items
    prefix = np.zeros(I, bool)
    prefix[3] = True
    ext = np.zeros(I, bool)
    ext[4:] = True
    tid = bm.tidlist_of_itemset(db, jnp.asarray(prefix))
    res = eclat.mine(
        db.item_bits, jnp.asarray(prefix), jnp.asarray(ext), tid,
        jnp.asarray(minsup, jnp.int32), jax.random.PRNGKey(0),
        config=eclat.EclatConfig(max_out=4096, max_stack=1024), n_items=I,
    )
    got = _to_dict(res, I)
    want = {
        fs: s for fs, s in oracle.items()
        if 3 in fs and len(fs) > 1 and all(i >= 3 for i in fs)
    }
    assert got == want


def test_mfi_thesis_example(thesis_db):
    """M = {134, 234, 245, 3456} (thesis Example 2.1, 1-based)."""
    r = mfi.mine_all_candidates(thesis_db, 5, config=mfi.MFIConfig(max_out=256))
    n = int(r.n_out)
    valid = np.zeros(r.items.shape[0], bool)
    valid[:n] = True
    keep = np.asarray(mfi.filter_maximal(r.items, jnp.asarray(valid)))
    got = set()
    for k in range(n):
        if keep[k]:
            m = np.asarray(bm.unpack_bool(r.items[k], 6))
            got.add(tuple(sorted(int(i) + 1 for i in np.nonzero(m)[0])))
    assert got == {(1, 3, 4), (2, 3, 4), (2, 4, 5), (3, 4, 5, 6)}


def test_mfi_bound_thm_7_5(small_db):
    """Candidates form M ⊇ M̃ with all candidates frequent (Thm 7.5 setup)."""
    dense, db, minsup, oracle = small_db
    r = mfi.mine_all_candidates(
        db, minsup, config=mfi.MFIConfig(max_out=4096, max_stack=2048)
    )
    n = int(r.n_out)
    assert int(r.overflow) == 0
    mfis_true = {
        fs for fs in oracle
        if not any(fs < other for other in oracle)
    }
    cands = set()
    for k in range(n):
        m = np.asarray(bm.unpack_bool(r.items[k], db.n_items))
        fs = frozenset(np.nonzero(m)[0].tolist())
        assert fs in oracle, "candidate must be frequent"
        assert oracle[fs] == int(r.supports[k])
        cands.add(fs)
    assert mfis_true <= cands
    # longest-MFI bound of Thm 7.5 (with P=1 here: |M| = |M̃| after filtering)
    valid = np.zeros(r.items.shape[0], bool)
    valid[:n] = True
    keep = np.asarray(mfi.filter_maximal(r.items, jnp.asarray(valid)))
    kept = {
        frozenset(np.nonzero(np.asarray(bm.unpack_bool(r.items[k], db.n_items)))[0].tolist())
        for k in range(n) if keep[k]
    }
    assert kept == mfis_true


def test_apriori_matches_eclat(small_db):
    dense, db, minsup, oracle = small_db
    assert apriori.apriori(db, minsup) == oracle


def test_count_distribution_psum(small_db):
    """Alg. 2: per-shard counts + psum == global supports."""
    dense, db, minsup, oracle = small_db
    P = 4
    T = dense.shape[0] // P
    shards = dense[: P * T].reshape(P, T, -1)
    cands = sorted(oracle, key=lambda s: (len(s), tuple(sorted(s))))[:64]
    masks = np.zeros((len(cands), db.n_items), bool)
    for i, c in enumerate(cands):
        masks[i, sorted(c)] = True

    def shard_fn(sh):
        sdb = bm.BitmapDB.from_dense(sh)
        return apriori.count_distribution_supports(
            sdb.item_bits, jnp.asarray(masks), sdb.all_tids(), "p"
        )

    out = jax.vmap(shard_fn, axis_name="p")(jnp.asarray(shards))
    for i, c in enumerate(cands):
        assert int(out[0, i]) == oracle[c]

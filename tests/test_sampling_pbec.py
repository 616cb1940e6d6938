"""Sampling theory (Ch. 6), PBEC partitioning (Ch. 8.2), schedulers."""
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

import jax
import jax.numpy as jnp

from repro.core import bitmap as bm, eclat, mfi, pbec, sampling, schedule


def test_sample_size_formulas():
    # Thm 6.1: 1/(2ε²)·ln(2/δ)
    assert sampling.db_sample_size(0.01, 0.1) == int(
        np.ceil(np.log(20) / (2 * 0.01**2))
    )
    # Thm 6.2
    assert sampling.coverage_sample_size(0.1, 0.1, 0.01) == int(
        np.ceil(4 / (0.1**2 * 0.01) * np.log(20))
    )
    # Thm 6.3 monotone in ε and ρ
    a = sampling.reservoir_sample_size(0.05, 0.1, 0.01)
    b = sampling.reservoir_sample_size(0.02, 0.1, 0.01)
    assert b > a > 0


def test_reservoir_inloop_uniformity(small_db):
    """χ²-style sanity: in-loop reservoir hits every FI with ≈equal freq."""
    dense, db, minsup, oracle = small_db
    R = 16
    counts = {}
    trials = 40
    for t in range(trials):
        res = eclat.mine_all(
            db, minsup, key=jax.random.PRNGKey(t),
            config=eclat.EclatConfig(max_out=8192, max_stack=2048, reservoir_size=R),
        )
        for k in range(R):
            m = np.asarray(bm.unpack_bool(res.reservoir_items[k], db.n_items))
            fs = frozenset(np.nonzero(m)[0].tolist())
            # mine_all's root is [∅|B], so singletons are in the stream too
            assert fs in oracle and len(fs) >= 1
            counts[fs] = counts.get(fs, 0) + 1
    n_multi = len(oracle)
    freq = np.array(list(counts.values()))
    expected = trials * R / n_multi
    # generous tolerance: uniform sampling over ~600 itemsets, 640 draws
    assert len(counts) > n_multi * 0.4
    assert freq.max() <= max(6.0 * expected, 6)


def test_reservoir_np_oracle_uniform():
    rng = np.random.default_rng(0)
    hits = np.zeros(100)
    for _ in range(2000):
        s = sampling.reservoir_sample_np(rng, np.arange(100), 10)
        hits[s] += 1
    p = hits / hits.sum()
    assert abs(p.mean() - 0.01) < 1e-9 and p.max() < 0.02


def test_merge_reservoirs_hypergeometric():
    rng = np.random.default_rng(1)
    counts = np.array([100, 50, 10, 0])
    X = sampling.merge_reservoirs(rng, counts, 40)
    assert X.sum() == 40 and (X <= counts).all()
    # expectation proportional to f_i
    Xs = np.mean(
        [sampling.merge_reservoirs(rng, counts, 40) for _ in range(300)], axis=0
    )
    np.testing.assert_allclose(Xs / 40, counts / counts.sum(), atol=0.03)


def test_modified_coverage_samples_are_frequent(small_db):
    dense, db, minsup, oracle = small_db
    r = mfi.mine_all_candidates(
        db, minsup, config=mfi.MFIConfig(max_out=4096, max_stack=2048)
    )
    n = int(r.n_out)
    valid = np.zeros(r.items.shape[0], bool)
    valid[:n] = True
    samp = sampling.modified_coverage_sample(
        jax.random.PRNGKey(2), r.items, jnp.asarray(valid), 128, db.n_items
    )
    sm = np.asarray(bm.unpack_bool(samp, db.n_items))
    for row in sm:
        fs = frozenset(np.nonzero(row)[0].tolist())
        if fs:
            assert fs in oracle


def test_coverage_uniform_host():
    rng = np.random.default_rng(0)
    mfis = np.zeros((2, 6), bool)
    mfis[0, :3] = True   # P(m0) = 8 subsets
    mfis[1, 2:5] = True  # P(m1) = 8 subsets, overlap {2}
    s = sampling.coverage_sample_uniform(rng, mfis, 4000)
    keys = {}
    for row in s:
        keys[tuple(np.nonzero(row)[0])] = keys.get(tuple(np.nonzero(row)[0]), 0) + 1
    # union has 8 + 8 - 2 = 14 distinct itemsets ({}, {2} shared)
    assert len(keys) == 14
    freq = np.array(list(keys.values())) / 4000
    np.testing.assert_allclose(freq, 1 / 14, atol=0.03)


# ---------------------------------------------------------------------------
# PBEC partition properties
# ---------------------------------------------------------------------------


def _ext_supports_fn(db):
    def f(prefix):
        tid = bm.tidlist_of_itemset(db, jnp.asarray(prefix))
        return np.asarray(bm.extension_supports(db.item_bits, tid))

    return f


@given(st.integers(2, 8), st.floats(0.2, 1.0), st.integers(0, 500))
@settings(max_examples=10, deadline=None)
def test_partition_disjoint_and_covering(P, alpha, seed):
    """Prop. 2.22/2.23: classes are disjoint and (with ancestors) cover F."""
    rng = np.random.default_rng(seed)
    dense = rng.random((64, 10)) < 0.45
    db = bm.BitmapDB.from_dense(jnp.asarray(dense))
    minsup = 8
    oracle = eclat.brute_force_fis(dense, minsup)
    if not oracle:
        return
    masks = np.zeros((len(oracle), 10), bool)
    for i, s_ in enumerate(oracle):
        masks[i, sorted(s_)] = True
    classes = pbec.partition(masks, P, alpha, _ext_supports_fn(db), 10)
    disjoint, covered = pbec.verify_disjoint_cover(classes, 10, masks)
    assert disjoint and covered


@given(
    st.lists(st.floats(0.0, 100.0), min_size=1, max_size=60),
    st.integers(1, 12),
)
@settings(max_examples=40, deadline=None)
def test_lpt_43_bound_property(sizes, P):
    """Graham's Lemma 8.2: LPT makespan ≤ 4/3 · OPT lower bound."""
    a = schedule.lpt_schedule(sizes, P)
    assert schedule.lpt_makespan_bound_ok(sizes, a, P)


def test_db_repl_min_improves_sharing(small_db):
    dense, db, minsup, oracle = small_db
    masks = np.zeros((len(oracle), db.n_items), bool)
    for i, s_ in enumerate(oracle):
        masks[i, sorted(s_)] = True
    classes = pbec.partition(masks, 4, 0.5, _ext_supports_fn(db), db.n_items)
    from repro.core.phases import seed_tidlists

    tids = np.asarray(
        seed_tidlists(
            db.item_bits,
            jnp.asarray(np.stack([c.prefix for c in classes])),
            db.all_tids(),
        )
    )
    profit = schedule.pairwise_shared_transactions(tids)
    sizes = [c.est_count for c in classes]
    r = schedule.db_repl_min(np.asarray(sizes), profit, 4, tidlists=tids)
    assert set(r.assignment) <= set(range(4))
    assert len(r.assignment) == len(classes)
    # the reported volume is the exact Σ_p |D'_p| of the returned assignment
    assert r.volume == schedule.replicated_volume(tids, r.assignment, 4)
    # and never better than the no-replication floor |∪ T(U_i)|
    union = np.bitwise_or.reduce(tids.astype(np.uint32), axis=0)
    floor = int(np.unpackbits(union.view(np.uint8)).sum())
    assert r.volume >= floor


def test_schedulers_makespan_and_volume_tradeoff():
    """LPT optimizes the makespan, DB-Repl-Min the replicated volume; on a
    skewed size vector with clustered tidlists each wins its own metric."""
    rng = np.random.default_rng(42)
    C, P, W = 24, 4, 8
    sizes = rng.zipf(1.4, C).astype(np.float64)
    # two tid "clusters": classes sharing a cluster share most transactions
    tids = np.zeros((C, W), np.uint32)
    for i in range(C):
        cluster = i % 2
        base = np.uint32(0x0F0F0F0F if cluster == 0 else 0xF0F0F0F0)
        noise = rng.integers(0, 1 << 32, W, dtype=np.uint64).astype(np.uint32)
        tids[i] = base & noise
    profit = schedule.pairwise_shared_transactions(tids)

    lpt = schedule.lpt_schedule(sizes, P)
    rep = schedule.db_repl_min(sizes, profit, P, tidlists=tids)

    mk_lpt = schedule.makespan_of(sizes, lpt, P)
    mk_rep = schedule.makespan_of(sizes, rep.assignment, P)
    vol_lpt = schedule.replicated_volume(tids, lpt, P)

    # LPT makespan is sound (Graham bound) and no worse than the QKP greedy's
    assert schedule.lpt_makespan_bound_ok(sizes, lpt, P)
    assert mk_lpt <= mk_rep + 1e-9
    # the replication-aware greedy moves fewer (or equal) transactions
    assert rep.volume <= vol_lpt + 1e-9
    # without tidlists no honest volume exists (sizes are FI counts, not
    # transactions) — the report says so with NaN rather than a wrong number
    no_tids = schedule.db_repl_min(sizes, profit, P)
    assert np.array_equal(no_tids.assignment, rep.assignment)
    assert np.isnan(no_tids.volume)

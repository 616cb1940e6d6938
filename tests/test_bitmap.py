"""Unit + property tests for the packed-bitmap substrate."""
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

import jax.numpy as jnp

from repro.core import bitmap as bm


@given(st.integers(1, 97), st.integers(1, 40), st.integers(0, 2**31 - 1))
@settings(max_examples=30, deadline=None)
def test_pack_unpack_roundtrip(n, rows, seed):
    rng = np.random.default_rng(seed)
    dense = rng.random((rows, n)) < 0.4
    packed = bm.pack_bool(jnp.asarray(dense))
    back = np.asarray(bm.unpack_bool(packed, n))
    np.testing.assert_array_equal(back, dense)


@pytest.mark.parametrize("lead,rows,words,n", [
    ((), 1, 1, 1), ((), 31, 2, 40), ((), 32, 1, 32), ((), 100, 3, 70),
    ((), 77, 2, 64), ((), 1000, 32, 1000), ((2, 3), 45, 2, 50),
])
def test_transpose_bits_matches_unpacked_transpose(lead, rows, words, n):
    """The in-word 32×32 block transpose equals unpack → transpose → pack,
    at every row count and column cut, tails and leading axes included."""
    rng = np.random.default_rng(rows * words + n)
    x = jnp.asarray(rng.integers(0, 2**32, size=(*lead, rows, words),
                                 dtype=np.uint32))
    want = bm.pack_bool(jnp.swapaxes(bm.unpack_bool(x, n), -1, -2))
    got = bm.transpose_bits(x, n)
    assert got.shape == (*lead, n, bm.n_words(rows))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@given(st.integers(0, 2**31 - 1), st.integers(1, 300))
@settings(max_examples=30, deadline=None)
def test_popcount_matches_numpy(seed, n):
    rng = np.random.default_rng(seed)
    x = rng.integers(0, 2**32, size=n, dtype=np.uint32)
    got = np.asarray(bm.popcount_u32(jnp.asarray(x)))
    want = np.array([bin(int(v)).count("1") for v in x])
    np.testing.assert_array_equal(got, want)


def test_support_monotonicity_property(small_db):
    """Thm 2.12: Supp(U) ≥ Supp(V) for U ⊊ V — on random chains."""
    dense, db, _, _ = small_db
    rng = np.random.default_rng(0)
    I = db.n_items
    for _ in range(25):
        size = rng.integers(1, 6)
        items = rng.choice(I, size=size, replace=False)
        prev = None
        for k in range(1, size + 1):
            mask = np.zeros(I, bool)
            mask[items[:k]] = True
            s = int(bm.support_of_itemset(db, jnp.asarray(mask)))
            # cross-check against numpy
            want = int(dense[:, items[:k]].all(axis=1).sum())
            assert s == want
            if prev is not None:
                assert s <= prev
            prev = s


def test_extension_supports_vs_dense(small_db):
    dense, db, _, _ = small_db
    got = np.asarray(bm.extension_supports(db.item_bits, db.all_tids()))
    np.testing.assert_array_equal(got, dense.sum(axis=0))


def test_pair_supports_vs_dense(small_db):
    dense, db, _, _ = small_db
    got = np.asarray(bm.pair_supports(db.item_bits, db.all_tids()))
    want = (dense.astype(np.int64).T @ dense.astype(np.int64))
    np.testing.assert_array_equal(got, want)


def test_tidlist_tail_masking(thesis_db):
    """all_tids masks bits beyond n_tx (15 tx → 17 junk bits must be 0)."""
    tid = np.asarray(thesis_db.all_tids())
    assert bm.popcount_u32(jnp.asarray(tid)).sum() == thesis_db.n_tx


def test_is_subset_packed():
    a = bm.pack_bool(jnp.asarray([[True, False, True, False] * 10]))
    b = bm.pack_bool(jnp.asarray([[True, True, True, False] * 10]))
    assert bool(bm.is_subset_packed(a, b)[0])
    assert not bool(bm.is_subset_packed(b, a)[0])

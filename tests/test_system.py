"""End-to-end behaviour: generator naming, phase-3 exchange unit."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import bitmap as bm, pbec, phases
from repro.data.ibm_gen import IBMParams, generate_dense, params_from_name


def test_ibm_name_roundtrip():
    p = params_from_name("T500I0.1P50PL10TL40")
    assert (p.n_tx, p.n_items, p.n_patterns) == (500_000, 100, 50)
    assert p.avg_pattern_len == 10 and p.avg_tx_len == 40
    q = IBMParams(n_tx=500_000, n_items=100, n_patterns=50,
                  avg_pattern_len=10, avg_tx_len=40)
    assert q.name == "T500I0.1P50PL10TL40"


def test_ibm_generator_statistics():
    p = IBMParams(n_tx=2000, n_items=100, n_patterns=20,
                  avg_pattern_len=8, avg_tx_len=20, seed=1)
    dense = generate_dense(p)
    lens = dense.sum(axis=1)
    assert lens.min() >= 1
    assert 5 < lens.mean() < 40  # corruption keeps it below TL but nonzero
    # deterministic
    np.testing.assert_array_equal(dense, generate_dense(p))


def test_phase3_exchange_unit(small_db):
    """Every processor receives exactly the transactions containing its
    assigned prefixes (Alg. 18 contract), via all_to_all under vmap."""
    dense, db, minsup, oracle = small_db
    P = 4
    T = dense.shape[0] // P
    from repro.core import fimi

    shards = fimi.shard_db(dense, P)
    I = db.n_items
    # 4 singleton classes, one per processor
    items = [0, 3, 5, 7]
    prefixes = np.zeros((4, I), bool)
    for c, it in enumerate(items):
        prefixes[c, it] = True
    pref_packed = np.asarray(bm.pack_bool(jnp.asarray(prefixes)))
    import functools

    p3 = functools.partial(phases.phase3_exchange, axis_name="p", capacity=T)
    out = jax.vmap(p3, axis_name="p")(
        shards,
        jnp.ones((P, T), jnp.bool_),
        jnp.broadcast_to(jnp.asarray(pref_packed), (P, 4, pref_packed.shape[-1])),
        jnp.ones((P, 4), jnp.bool_),
        jnp.broadcast_to(jnp.arange(4, dtype=jnp.int32), (P, 4)),
    )
    assert int(np.asarray(out.overflow).reshape(-1)[0]) == 0
    for proc in range(P):
        rows = np.asarray(out.slab[proc])
        valid = np.asarray(out.slab_valid[proc])
        got = rows[valid]
        want = dense[: P * T][dense[: P * T][:, items[proc]]]
        # every received row contains the item; count matches global count
        dmask = np.asarray(bm.unpack_bool(jnp.asarray(got), I))
        assert dmask[:, items[proc]].all()
        assert len(got) == len(want)
    # replication factor = sum of per-item covers / |D|
    covers = sum(dense[: P * T][:, it].sum() for it in items)
    np.testing.assert_allclose(
        float(np.asarray(out.replication).reshape(-1)[0]),
        covers / (P * T), rtol=1e-5,
    )

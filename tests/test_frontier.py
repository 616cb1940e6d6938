"""Frontier-batched Eclat: parity with the K=1 oracle path and brute force,
trip-count reduction, and interaction with reservoir / count_only / seeds."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import bitmap as bm, eclat


def _to_dict(res, n_items):
    out = {}
    for k in range(int(res.n_out)):
        mask = np.asarray(bm.unpack_bool(res.items[k], n_items))
        out[frozenset(np.nonzero(mask)[0].tolist())] = int(res.supports[k])
    return out


@pytest.mark.parametrize("frontier", [1, 8, 64])
def test_frontier_mine_all_matches_bruteforce(small_db, frontier):
    """End-to-end: identical FIs + supports vs brute force at every K."""
    dense, db, minsup, oracle = small_db
    res = eclat.mine_all(
        db, minsup,
        config=eclat.EclatConfig(
            max_out=8192, max_stack=2048, frontier_size=frontier
        ),
    )
    assert int(res.stack_overflow) == 0
    assert int(res.n_total) == len(oracle)
    assert _to_dict(res, db.n_items) == oracle


def test_frontier_trip_reduction_ibm_db(small_db):
    """frontier_size=64 must execute ≥5× fewer while_loop trips than the
    single-node miner on the IBM-generator database (the perf contract)."""
    dense, db, minsup, oracle = small_db
    trips = {}
    for k in (1, 64):
        res = eclat.mine_all(
            db, minsup,
            config=eclat.EclatConfig(
                max_out=8192, max_stack=2048, frontier_size=k
            ),
        )
        assert _to_dict(res, db.n_items) == oracle
        trips[k] = int(res.n_iters)
    assert trips[64] * 5 <= trips[1], trips


@pytest.mark.parametrize("frontier", [4, 32])
def test_frontier_seeded_matches_k1(small_db, frontier):
    """mine_seeded over several PBEC seeds: frontier path == K=1 oracle path."""
    dense, db, minsup, oracle = small_db
    I = db.n_items
    # three 1-prefix seeds with suffix extension sets (valid PBECs)
    seed_items = [1, 5, 9]
    prefix = np.zeros((3, I), bool)
    ext = np.zeros((3, I), bool)
    for j, it in enumerate(seed_items):
        prefix[j, it] = True
        ext[j, it + 1:] = True
    tids = jnp.stack([
        bm.tidlist_of_itemset(db, jnp.asarray(prefix[j])) for j in range(3)
    ])
    results = {}
    for k in (1, frontier):
        res = eclat.mine_seeded(
            db.item_bits,
            jnp.asarray(prefix),
            jnp.asarray(ext),
            tids,
            jnp.ones((3,), jnp.bool_),
            jnp.asarray(minsup, jnp.int32),
            jax.random.PRNGKey(0),
            config=eclat.EclatConfig(
                max_out=8192, max_stack=2048, frontier_size=k
            ),
            n_items=I,
        )
        assert int(res.stack_overflow) == 0
        results[k] = _to_dict(res, I)
    assert results[frontier] == results[1]
    want = {
        fs: s for fs, s in oracle.items()
        if len(fs) > 1 and min(fs) in seed_items
    }
    assert results[1] == want


def test_frontier_count_only_and_total(small_db):
    dense, db, minsup, oracle = small_db
    res = eclat.mine_all(
        db, minsup,
        config=eclat.EclatConfig(
            max_out=8192, max_stack=2048, frontier_size=16, count_only=True
        ),
    )
    assert int(res.n_total) == len(oracle)
    # count_only leaves the output buffer untouched
    assert not np.asarray(res.items).any()


def test_frontier_reservoir_stream(small_db):
    """The in-loop reservoir sees the same stream length under batching and
    every reservoir element is a real FI with its true support."""
    dense, db, minsup, oracle = small_db
    R = 32
    res = eclat.mine_all(
        db, minsup,
        config=eclat.EclatConfig(
            max_out=8192, max_stack=2048, frontier_size=8,
            reservoir_size=R, count_only=True,
        ),
        key=jax.random.PRNGKey(7),
    )
    assert int(res.n_total) == len(oracle)
    n_res = min(R, len(oracle))
    for k in range(n_res):
        mask = np.asarray(bm.unpack_bool(res.reservoir_items[k], db.n_items))
        fs = frozenset(np.nonzero(mask)[0].tolist())
        assert fs in oracle
        assert oracle[fs] == int(res.reservoir_supports[k])


def test_frontier_wider_than_stack_clamps():
    """frontier_size > max_stack must clamp, not crash."""
    rng = np.random.default_rng(3)
    dense = rng.random((64, 10)) < 0.4
    db = bm.BitmapDB.from_dense(jnp.asarray(dense))
    oracle = eclat.brute_force_fis(dense, 8)
    res = eclat.mine_all(
        db, 8,
        config=eclat.EclatConfig(max_out=4096, max_stack=32, frontier_size=128),
    )
    assert int(res.stack_overflow) == 0
    assert _to_dict(res, 10) == oracle


# ---------------------------------------------------------------------------
# The stack holds each parent's tidlist once and each child as (row, item):
# the DFS is the one the tidlist-per-node stack made, bit for bit.
# ---------------------------------------------------------------------------

#: (n_iters, n_popped, n_out, n_total, stack_overflow, FI digest, reservoir
#: digest) of ``mine_all(small_db)`` under PRNGKey(7), as the stack that kept
#: every child's tidlist computed them; digests are the first 16 hex digits
#: of sha256 over items[:n_out] then supports[:n_out] (resp. the reservoir).
GOLDEN = {
    ("plain", 1): (365, 365, 696, 696, 0, "1f56b74669881bd6", None),
    ("plain", 4): (92, 365, 696, 696, 0, "74d6915025c1dde4", None),
    ("plain", 16): (25, 365, 696, 696, 0, "fdcb094b9f07dab4", None),
    ("reservoir", 1): (365, 365, 696, 696, 0, "1f56b74669881bd6",
                       "b8c942f5e54679c3"),
    ("reservoir", 4): (92, 365, 696, 696, 0, "74d6915025c1dde4",
                       "5d9d89b568e2a10c"),
    ("reservoir", 16): (25, 365, 696, 696, 0, "fdcb094b9f07dab4",
                        "6271648b6bad8812"),
    ("count_only", 1): (365, 365, 696, 696, 0, "9f91948cf8e75e00", None),
    ("count_only", 4): (92, 365, 696, 696, 0, "9f91948cf8e75e00", None),
    ("count_only", 16): (25, 365, 696, 696, 0, "9f91948cf8e75e00", None),
    # max_stack=12 drops pushes (at K=16 the frontier clamps to 12)
    ("overflow", 1): (153, 153, 321, 321, 29, "cbe2a5e90423d3bc", None),
    ("overflow", 4): (25, 89, 220, 220, 53, "2eff199668c1d9d1", None),
    ("overflow", 16): (5, 38, 141, 141, 69, "df86e12fefdf240c", None),
}
VARIANTS = {
    "plain": dict(max_out=8192, max_stack=2048),
    "reservoir": dict(max_out=8192, max_stack=2048, reservoir_size=32),
    "count_only": dict(max_out=8192, max_stack=2048, count_only=True),
    "overflow": dict(max_out=8192, max_stack=12),
}


def _digest(*arrays):
    import hashlib

    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()[:16]


def _host_pushes(dense, minsup):
    """Children the DFS pushes: per node, its frequent extensions but the
    last in (support, item) order — the last one's child has no extension."""
    dense = np.asarray(dense, bool)
    n = 0
    todo = [(np.ones(len(dense), bool), list(range(dense.shape[1])))]
    while todo:
        cover, ext = todo.pop()
        supp = {e: int((cover & dense[:, e]).sum()) for e in ext}
        freq = sorted((e for e in ext if supp[e] >= minsup),
                      key=lambda e: (supp[e], e))
        for j, e in enumerate(freq[:-1]):
            n += 1
            todo.append((cover & dense[:, e], freq[j + 1:]))
    return n


@pytest.mark.parametrize("variant,frontier", sorted(GOLDEN))
def test_frontier_bit_identical_to_tidlist_stack(small_db, variant, frontier):
    dense, db, minsup, oracle = small_db
    res = eclat.mine_all(
        db, minsup, key=jax.random.PRNGKey(7),
        config=eclat.EclatConfig(frontier_size=frontier, **VARIANTS[variant]),
    )
    n = int(res.n_out)
    got = (int(res.n_iters), int(res.n_popped), n, int(res.n_total),
           int(res.stack_overflow), _digest(res.items[:n], res.supports[:n]),
           _digest(res.reservoir_items, res.reservoir_supports)
           if variant == "reservoir" else None)
    assert got == GOLDEN[variant, frontier]
    # every pushed child is popped: one seed plus the pushes
    assert int(res.n_popped) == 1 + int(res.n_pushed)
    if variant == "overflow":
        kept = _to_dict(res, db.n_items)
        assert len(kept) == n and all(oracle[fs] == s for fs, s in kept.items())
    else:
        assert int(res.n_pushed) == _host_pushes(dense, minsup)


def test_frontier_overwrites_the_arena_row_its_siblings_read():
    """Trip 1 pops the root and pushes its children at positions 0..3, all
    referring to arena row 0.  Trip 2 pops all four, the first child at
    position 0 among them, and writes its pushing nodes' tidlists from row 0
    up, over the row they were read from.  The itemsets stay exact."""
    rng = np.random.default_rng(11)
    dense = rng.random((200, 5)) < 0.7
    db = bm.BitmapDB.from_dense(jnp.asarray(dense))
    oracle = eclat.brute_force_fis(dense, 1)
    assert len(oracle) == 2**5 - 1          # every itemset is frequent
    cfg = dict(max_out=64, max_stack=64, frontier_size=8)
    two = eclat.mine_all(db, 1, config=eclat.EclatConfig(max_iters=2, **cfg))
    assert int(two.n_popped) == 1 + 4       # root, then its four children
    assert int(two.n_pushed) == 4 + 3 + 2 + 1
    assert all(oracle[fs] == s for fs, s in _to_dict(two, 5).items())
    res = eclat.mine_all(db, 1, config=eclat.EclatConfig(**cfg))
    assert int(res.stack_overflow) == 0
    assert _to_dict(res, 5) == oracle


def test_frontier_vmapped_miners_of_different_depth_match_separate_runs(
        small_db):
    """P=4 miners under vmap, with trees of different depth (the root, a
    1-prefix class, a 2-prefix class, no class): each lane is what its own
    run gives, field for field."""
    dense, db, minsup, oracle = small_db
    I = db.n_items
    prefix = np.zeros((4, 2, I), bool)
    ext = np.zeros((4, 2, I), bool)
    valid = np.zeros((4, 2), bool)
    ext[0, 0] = valid[0, 0] = True                       # [∅ | B]
    prefix[1, 0, 3], ext[1, 0, 4:], valid[1, 0] = True, True, True
    prefix[1, 1, 7], ext[1, 1, 8:], valid[1, 1] = True, True, True
    prefix[2, 0, [1, 2]], ext[2, 0, 3:], valid[2, 0] = True, True, True
    tids = jnp.stack([
        jnp.stack([bm.tidlist_of_itemset(db, jnp.asarray(prefix[p, k]))
                   for k in range(2)]) for p in range(4)])
    cfg = eclat.EclatConfig(max_out=8192, max_stack=2048, frontier_size=4,
                            reservoir_size=16)
    keys = jax.random.split(jax.random.PRNGKey(3), 4)

    def run(pre, ex, tid, ok, key):
        return eclat.mine_seeded(db.item_bits, pre, ex, tid, ok,
                                 jnp.asarray(minsup, jnp.int32), key,
                                 config=cfg, n_items=I)

    lanes = jax.vmap(run)(jnp.asarray(prefix), jnp.asarray(ext), tids,
                          jnp.asarray(valid), keys)
    iters = set()
    for p in range(4):
        one = run(jnp.asarray(prefix[p]), jnp.asarray(ext[p]), tids[p],
                  jnp.asarray(valid[p]), keys[p])
        for field, a, b in zip(one._fields, lanes, one):
            np.testing.assert_array_equal(np.asarray(a[p]), np.asarray(b),
                                          err_msg=field)
        iters.add(int(one.n_iters))
    assert len(iters) == 4                  # four depths, one loop
    assert int(lanes.n_iters[3]) == 0
    assert _to_dict(jax.tree.map(lambda a: a[0], lanes), I) == oracle

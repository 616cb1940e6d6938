"""The Phase-1 reservoir steps over the offered itemsets only: it leaves the
same reservoir, seen count and key as a scan over every frontier slot, and
its loop is bounded by the offers, not by the F·I slots of a trip."""
import numpy as np
import pytest

import jax
import jax.extend
import jax.numpy as jnp

from repro.core import bitmap as bm, eclat

P = 4
N_TRIPS = 3


def _per_slot_reservoir_update(state, itemsets_packed, supports, emit_mask, R):
    """The per-slot Algorithm R the offer loop replaced: one sequential step
    per itemset slot of the trip, a ``cond`` on whether the slot offers."""

    def body(i, carry):
        res_items, res_supp, seen, key = carry

        def do(carry):
            res_items, res_supp, seen, key = carry
            seen = seen + 1
            key, sub = jax.random.split(key)
            j = jax.random.randint(sub, (), 0, seen)
            slot = jnp.where(seen <= R, seen - 1, j)
            take = (seen <= R) | (j < R)
            slot = jnp.where(take, slot, R)  # R = out-of-bounds ⇒ drop
            res_items = res_items.at[slot].set(itemsets_packed[i], mode="drop")
            res_supp = res_supp.at[slot].set(supports[i], mode="drop")
            return res_items, res_supp, seen, key

        return jax.lax.cond(emit_mask[i], do, lambda c: c, carry)

    return jax.lax.fori_loop(0, emit_mask.shape[0], body, state)


def _frontier_trips(small_db, K, silent_trip):
    """``N_TRIPS`` trips of ``P`` miners, each popping K real DFS nodes of the
    small DB: a frequent itemset as prefix, the larger items as extensions.
    Returns per trip ``node_items[P, K, IW]``, ``supports[P, K, I]`` and
    ``emit[P, K, I]``; trip ``silent_trip`` (if any) pops only leaves."""
    _, db, minsup, oracle = small_db
    I = db.n_items
    itemsets = sorted((sorted(fs) for fs in oracle), key=lambda x: (len(x), x))
    rng = np.random.default_rng(11)
    trips = []
    for t in range(N_TRIPS):
        prefix = np.zeros((P, K, I), bool)
        ext = np.zeros((P, K, I), bool)
        for p in range(P):
            for k in range(K):
                fs = itemsets[rng.integers(len(itemsets))]
                prefix[p, k, fs] = True
                if t != silent_trip:
                    ext[p, k, max(fs) + 1:] = True
        tids = jax.vmap(jax.vmap(lambda m: bm.tidlist_of_itemset(db, m)))(
            jnp.asarray(prefix))
        supports = jax.vmap(
            lambda tk: bm.multi_extension_supports(db.item_bits, tk))(tids)
        emit = jnp.asarray(ext) & (supports >= minsup)
        trips.append((bm.pack_bool(jnp.asarray(prefix)), supports, emit))
    return trips


def _chain(trips, R, n_items, per_slot):
    e_packed = bm.pack_bool(
        jax.nn.one_hot(jnp.arange(n_items), n_items, dtype=jnp.bool_))
    IW = bm.n_words(n_items)

    if per_slot:
        def update(state, node_items, supports, emit):
            K = emit.shape[0]
            flat_items = (node_items[:, None, :] | e_packed[None]).reshape(
                K * n_items, IW)
            return _per_slot_reservoir_update(
                state, flat_items, supports.reshape(-1), emit.reshape(-1), R)
    else:
        def update(state, node_items, supports, emit):
            return eclat._reservoir_update(
                state, node_items, e_packed, supports, emit, R)

    step = jax.jit(jax.vmap(update))
    state = (jnp.zeros((P, R, IW), jnp.uint32),
             jnp.zeros((P, R), jnp.int32),
             jnp.zeros((P,), jnp.int32),
             jax.random.split(jax.random.PRNGKey(5), P))
    for node_items, supports, emit in trips:
        state = step(state, node_items, supports, emit)
    return state


@pytest.mark.parametrize("K", [1, 8])
@pytest.mark.parametrize("case", ["fill", "replace", "silent_trip"])
def test_offer_loop_matches_per_slot_scan(small_db, K, case):
    """Bit-identical ``(res_items, res_supp, seen, key)`` under ``vmap`` over
    P = 4 miners and three chained trips: offers below R (every offer fills
    a slot), above R (offers replace or drop), and a middle trip that
    offers nothing."""
    trips = _frontier_trips(small_db, K, silent_trip=1 if case == "silent_trip"
                            else None)
    offers = np.asarray([np.asarray(e).sum(axis=(1, 2)) for _, _, e in trips])
    seen = offers.sum(axis=0)
    R = max(1, int(seen.min()) // 2) if case == "replace" else 1024
    n_items = small_db[1].n_items
    got = _chain(trips, R, n_items, per_slot=False)
    want = _chain(trips, R, n_items, per_slot=True)
    if case == "fill":
        assert 0 < seen.max() < R
    elif case == "replace":
        assert seen.min() > R
    else:
        assert offers[1].sum() == 0 and seen.min() > 0
    np.testing.assert_array_equal(np.asarray(got[2]), seen)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _loops(jaxpr):
    """One entry per loop in ``jaxpr`` and the jaxprs nested in it: a scan's
    ``length``, or the constants a while loop's condition compares with."""
    found = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "scan":
                found.append(("scan", {eqn.params["length"]}))
            elif eqn.primitive.name == "while":
                cond = eqn.params["cond_jaxpr"]
                consts = {np.asarray(c).item() for c in cond.consts
                          if np.ndim(c) == 0}
                consts |= {np.asarray(v.val).item() for ce in cond.jaxpr.eqns
                           for v in ce.invars
                           if isinstance(v, jax.extend.core.Literal)}
                found.append(("while", consts))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    return found


@pytest.mark.parametrize("K", [1, 8])
def test_reservoir_loop_bound_follows_offers(small_db, K):
    """``mine_seeded`` with a reservoir holds no loop of F·I steps: the
    reservoir loop's bound is the trip's offer count, a traced value."""
    _, db, minsup, _ = small_db
    I = db.n_items
    FI = K * I
    seeds = (jnp.zeros((1, I), jnp.bool_), jnp.ones((1, I), jnp.bool_),
             db.all_tids()[None], jnp.ones((1,), jnp.bool_))

    def trace(reservoir_size):
        return jax.make_jaxpr(lambda *a: eclat.mine_seeded(
            db.item_bits, *a, jnp.asarray(minsup, jnp.int32),
            jax.random.PRNGKey(0),
            config=eclat.EclatConfig(max_out=256, max_stack=256,
                                     frontier_size=K,
                                     reservoir_size=reservoir_size),
            n_items=I))(*seeds)

    with_res = _loops(trace(64))
    assert len(with_res) > len(_loops(trace(0)))   # the reservoir's loop
    assert all(FI not in bounds for _, bounds in with_res), with_res

"""IBM Quest synthetic transactions (Agrawal & Srikant, "Fast Algorithms for
Mining Association Rules", VLDB 1994, sec. 2.4.3), as the paper states it.

A deployment is its table of potentially large itemsets, drawn once from the
configuration's ``pattern_seed``:

* ``n_patterns`` itemsets, each of a size drawn from a Poisson distribution
  of mean ``avg_pattern_len`` (at least 1).  The first has random items; in
  each later one a fraction of the items, drawn from an exponential
  distribution of mean ``correlation``, comes from the itemset before it,
  the rest are random;
* a weight per itemset, exponential of unit mean, normalised to sum to 1;
* a corruption level per itemset, normal of mean ``corruption`` and
  variance ``corruption_var``, clipped to [0, 1].

The transactions are drawn from ``seed`` (the run's seed): each gets a size
drawn from a Poisson distribution of mean ``avg_tx_len`` (at least 1), then
itemsets from the table by the weighted coin.  Each itemset is corrupted as
it is added: items are dropped, one at a time and at random, as long as a
uniform draw is below its corruption level.  An itemset that does not fit
in the transaction goes in anyway in half the cases, and moves to the next
transaction in the others; either way the transaction is then full.  The
size counts items as they are added, so an item that two of a
transaction's itemsets share counts twice.  An itemset never moves out of
an empty transaction, so no transaction is empty.

numpy only: it runs on the host before anything touches the chip.  The
benchmark keeps its own copy so that no change to the program can change
its data.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

CHUNK = 1 << 16         # itemsets drawn and corrupted per bulk step


@dataclass
class PatternTable:
    items: np.ndarray       # int64[P, Lmax], -1 past each itemset's size
    sizes: np.ndarray       # int64[P]
    weights: np.ndarray     # float64[P], sums to 1
    corruption: np.ndarray  # float64[P], in [0, 1]


def pattern_table(*, n_items, n_patterns, avg_pattern_len, correlation,
                  corruption, corruption_var, pattern_seed, **_) -> PatternTable:
    """The potentially large itemsets of one deployment."""
    rng = np.random.default_rng(pattern_seed)
    sizes = np.clip(rng.poisson(avg_pattern_len, n_patterns), 1, n_items)
    items = np.full((n_patterns, int(sizes.max())), -1, np.int64)
    prev = np.zeros(0, np.int64)
    for k, size in enumerate(sizes.tolist()):
        share = min(1.0, rng.exponential(correlation)) if k else 0.0
        n_prev = min(int(round(share * size)), len(prev))
        chosen = list(rng.choice(prev, size=n_prev, replace=False)) \
            if n_prev else []
        taken = set(chosen)
        while len(chosen) < size:
            for it in rng.integers(0, n_items, size - len(chosen)).tolist():
                if it not in taken and len(chosen) < size:
                    taken.add(it)
                    chosen.append(it)
        prev = np.sort(np.asarray(chosen, np.int64))
        items[k, :size] = prev
    weights = rng.exponential(1.0, n_patterns)
    weights /= weights.sum()
    corr = np.clip(rng.normal(corruption, np.sqrt(corruption_var),
                              n_patterns), 0.0, 1.0)
    return PatternTable(items, sizes.astype(np.int64), weights, corr)


def drops(rng, levels: np.ndarray, sizes: np.ndarray) -> np.ndarray:
    """Items dropped from each added itemset: the number of uniform draws in
    a row that fall below its corruption level, at most its size."""
    u = 1.0 - rng.random(len(levels))          # in (0, 1]
    with np.errstate(divide="ignore"):
        run = np.floor(np.log(u) / np.log(levels))
    run = np.where(levels <= 0.0, 0, np.where(levels >= 1.0, sizes, run))
    return np.minimum(run, sizes).astype(np.int64)


def _corrupted(rng, table: PatternTable, n: int):
    """``n`` itemsets drawn by weight and corrupted: (flat items, lengths,
    coin per itemset for the does-not-fit rule)."""
    picks = rng.choice(len(table.sizes), size=n, p=table.weights)
    items = table.items[picks]                             # [n, Lmax]
    sizes = table.sizes[picks]
    k = drops(rng, table.corruption[picks], sizes)
    # a random order of each itemset's items; the first k in it are dropped
    keys = rng.random(items.shape)
    keys[items < 0] = 2.0
    rank = np.argsort(np.argsort(keys, axis=1), axis=1)
    keep = (items >= 0) & (rank >= k[:, None])
    return items[keep], keep.sum(axis=1), rng.random(n) < 0.5


def transactions(table: PatternTable, *, n_tx, n_items, avg_tx_len, seed,
                 **_):
    """Yield ``(rows, items)`` of the database, a chunk of itemsets at a
    time: row ``rows[j]`` holds item ``items[j]``."""
    rng = np.random.default_rng(seed % 2**64)
    target = np.clip(rng.poisson(avg_tx_len, n_tx), 1, n_items).tolist()
    t, size = 0, 0
    while t < n_tx:
        flat, lens, coins = _corrupted(rng, table, CHUNK)
        dest = np.full(CHUNK, -1, np.int64)
        lens_l, coins_l = lens.tolist(), coins.tolist()
        j = 0
        while j < CHUNK and t < n_tx:
            n = lens_l[j]
            if size == 0 or size + n <= target[t]:
                dest[j] = t
                size += n
                j += 1
                if size >= target[t]:
                    t, size = t + 1, 0
            elif coins_l[j]:             # does not fit: in anyway ...
                dest[j] = t
                j += 1
                t, size = t + 1, 0
            else:                        # ... or on to the next transaction
                t, size = t + 1, 0
        rows = np.repeat(dest, lens)
        keep = rows >= 0
        yield rows[keep], flat[keep]


def generate_blocks(dataset: dict, seed: int):
    """Yield the database as dense bool blocks ``[<= block_tx, n_items]``.

    The same configuration and seed always give the same rows; the
    itemset table depends on the configuration alone.
    """
    n_tx, n_items, block_tx = (dataset["n_tx"], dataset["n_items"],
                               dataset["block_tx"])
    if block_tx <= 0:
        raise ValueError(f"block_tx must be positive (got {block_tx})")
    dense = np.zeros((n_tx, n_items), bool)
    table = pattern_table(**dataset)
    for rows, items in transactions(table, **dataset, seed=seed):
        dense[rows, items] = True
    for lo in range(0, n_tx, block_tx):
        yield dense[lo: lo + block_tx]

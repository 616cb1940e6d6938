"""The shapes of the exactness matrix (``tests/test_exact_*.py``).

Every arm of both miners mines each shape and must return the whole FI
table of ``eclat.brute_force_fis`` on the same dense rows, itemset for
itemset with its support.  Each shape is a small database placed where a
bit-packed miner can go wrong: item masks over two words, the top bit of a
full word, an item alone in the second word, tidlists whose last word is
partly used, a deep tree, an empty result and itemsets whose support equals
the threshold.
:func:`shape` asserts that its database really pins what it names, so a
change to the generator cannot quietly turn a case into an easy one.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Dict

import jax
import numpy as np

from repro.core import eclat, fimi
from repro.data.ibm_gen import IBMParams, generate_dense

SHAPES = ("sparse_2w", "dense_deep", "w32", "w33", "ragged_T", "empty",
          "at_threshold")


@dataclasses.dataclass(frozen=True)
class Shape:
    dense: np.ndarray           # bool [n_tx, n_items]
    min_support_rel: float      # what each miner is given
    minsup: int                 # ceil(min_support_rel · n_tx), as miners do
    oracle: Dict[frozenset, int]

    @property
    def n_tx(self) -> int:
        return self.dense.shape[0]

    @property
    def n_items(self) -> int:
        return self.dense.shape[1]


def _ibm(n_tx, n_items, seed, n_patterns=8, avg_pattern_len=5, avg_tx_len=8,
         **kw) -> np.ndarray:
    return generate_dense(IBMParams(
        n_tx=n_tx, n_items=n_items, n_patterns=n_patterns,
        avg_pattern_len=avg_pattern_len, avg_tx_len=avg_tx_len, seed=seed,
        **kw))


def _most_frequent_last(dense: np.ndarray) -> np.ndarray:
    """Swap the most frequent item into the last column."""
    order = np.arange(dense.shape[1])
    top = int(dense.sum(axis=0).argmax())
    order[[top, -1]] = order[[-1, top]]
    return dense[:, order]


def _build(name: str):
    """(dense rows, min_support_rel) of one shape."""
    if name == "sparse_2w":
        return _ibm(512, 40, seed=1, n_patterns=10, avg_pattern_len=3,
                    avg_tx_len=5), 0.03
    if name == "dense_deep":
        return _ibm(256, 20, seed=2, n_patterns=6, avg_pattern_len=8,
                    avg_tx_len=16, corruption=0.25), 0.35
    if name == "w32":
        return _most_frequent_last(_ibm(256, 32, seed=4, avg_pattern_len=4,
                                        avg_tx_len=7)), 0.08
    if name == "w33":
        return _most_frequent_last(_ibm(256, 33, seed=5, avg_pattern_len=4,
                                        avg_tx_len=7)), 0.08
    if name == "ragged_T":
        return _ibm(500, 24, seed=6), 0.08
    if name == "empty":
        dense = _ibm(256, 24, seed=7)
        return dense, (int(dense.sum(axis=0).max()) + 0.5) / dense.shape[0]
    if name == "at_threshold":
        dense = _ibm(256, 24, seed=8)
        # the support that most pairs at >= 5 % share (the larger on a tie);
        # (s - 0.5) / n_tx ceils back to s exactly, as cluster_mine_fn sets it
        d = dense.astype(np.int64)
        pairs = (d.T @ d)[np.triu_indices(dense.shape[1], 1)]
        sups = collections.Counter(int(s) for s in pairs
                                   if s >= 0.05 * dense.shape[0])
        s = max(sups, key=lambda v: (sups[v], v))
        return dense, (s - 0.5) / dense.shape[0]
    raise KeyError(name)


def _check_pins(name: str, sh: Shape) -> None:
    o, dense, I = sh.oracle, sh.dense, sh.n_items
    multi = [k for k in o if len(k) >= 2]
    if name == "sparse_2w":
        assert 4.0 <= dense.sum(axis=1).mean() <= 7.0
        assert any(min(k) < 32 <= max(k) for k in multi), "no cross-word FI"
    elif name == "dense_deep":
        assert dense.sum(axis=1).mean() >= 12.0
        assert max(map(len, o)) >= 10 and len(o) >= 5000
    elif name in ("w32", "w33"):
        assert I in (32, 33)
        assert any(I - 1 in k for k in multi), f"item {I - 1} in no pair"
    elif name == "ragged_T":
        assert all((sh.n_tx // P) % 32 for P in (1, 2, 4))
    elif name == "empty":
        assert o == {} and dense.sum(axis=0).max() < sh.minsup
    elif name == "at_threshold":
        on = [k for k, s in o.items() if s == sh.minsup]
        assert sum(len(k) == 2 for k in on) >= 2, "no pairs on the threshold"
    if name != "empty":
        assert o, f"{name}: the oracle is empty"


@functools.lru_cache(maxsize=None)
def shape(name: str) -> Shape:
    """The shape's rows and its brute-force table, built once per process."""
    dense, rel = _build(name)
    dense = np.ascontiguousarray(dense, dtype=bool)
    minsup = int(np.ceil(rel * dense.shape[0]))
    sh = Shape(dense, rel, minsup, eclat.brute_force_fis(dense, minsup))
    _check_pins(name, sh)
    return sh


def jit_vmap_spmd(fn, P: int, mesh=None):
    """``fimi.vmap_spmd`` jitted, as ``fimi.shard_map_spmd`` is: each phase
    compiles as one program instead of dispatching op by op, which halves
    the matrix's compile time on the CPU.  The unjitted default still runs
    every shape through ``test_exact_store`` and the fimi variants through
    ``test_parallel_fimi``."""
    return jax.jit(fimi.vmap_spmd(fn, P, mesh))


def assert_exact(got: Dict[frozenset, int], sh: Shape) -> None:
    """The whole table equals the oracle; on failure name a few itemsets."""
    if got == sh.oracle:
        return
    missing = [sorted(k) for k in sh.oracle.keys() - got.keys()]
    extra = [sorted(k) for k in got.keys() - sh.oracle.keys()]
    wrong = [(sorted(k), got[k], s) for k, s in sh.oracle.items()
             if k in got and got[k] != s]
    raise AssertionError(
        f"|F| {len(got)} vs oracle {len(sh.oracle)}: "
        f"{len(missing)} missing {missing[:5]}, {len(extra)} extra "
        f"{extra[:5]}, {len(wrong)} wrong support (got, want) {wrong[:5]}")

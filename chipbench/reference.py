"""Plain reference for the mining configurations, and the comparison that
decides ``correct``.

Independent of the program: numpy only, no import of ``repro``.  It works
on the transaction rows as the benchmark generated them and on the answers
the program returned, never on a table the program built.

* :func:`mine` -- every itemset with support >= ``minsup`` and its exact
  support: pair counts from one dense matrix product, then depth-first
  intersection of 64-bit tid bitsets (Eclat without any of the program's
  planning, sampling or kernels).
* :func:`table_mismatches` -- itemsets missing, extra, or with another
  support, between two tables.
* :func:`sampled_table` -- the control: the same miner on a uniform sample
  of the rows, supports scaled up.  It breaks the exactness guarantee and
  has to come out as not correct.
"""
from __future__ import annotations

import numpy as np

WORD = 32


# ---------------------------------------------------------------------------
# Packed itemset masks (bit i % 32 of word i // 32 is item i)
# ---------------------------------------------------------------------------


def n_words(n_items: int) -> int:
    return -(-n_items // WORD)


def pack_rows(dense: np.ndarray) -> np.ndarray:
    """bool ``[N, I]`` -> uint32 ``[N, n_words(I)]``."""
    dense = np.asarray(dense, bool)
    n, items = dense.shape
    pad = n_words(items) * WORD - items
    if pad:
        dense = np.concatenate([dense, np.zeros((n, pad), bool)], axis=1)
    return np.ascontiguousarray(
        np.packbits(dense, axis=1, bitorder="little")).view(np.uint32)


def unpack_rows(packed: np.ndarray, n_items: int) -> np.ndarray:
    """uint32 ``[N, W]`` -> bool ``[N, n_items]``."""
    packed = np.ascontiguousarray(np.asarray(packed, np.uint32))
    bits = np.unpackbits(packed.view(np.uint8), axis=1, bitorder="little")
    return bits[:, :n_items].astype(bool)


def abs_minsup(minsup_rel: float, n_tx: int) -> int:
    """The configuration's support threshold, ceil(minsup * n_tx)."""
    return int(np.ceil(minsup_rel * n_tx))


# ---------------------------------------------------------------------------
# Mining
# ---------------------------------------------------------------------------


def _tid_bitsets(dense: np.ndarray) -> np.ndarray:
    """uint64 ``[I, ceil(N/64)]``: bit t of item i's row says t holds i."""
    n = dense.shape[0]
    pad = (-n) % 64
    cols = np.asarray(dense, bool).T
    if pad:
        cols = np.concatenate([cols, np.zeros((cols.shape[0], pad), bool)], 1)
    return np.ascontiguousarray(
        np.packbits(cols, axis=1, bitorder="little")).view(np.uint64)


def mine(dense: np.ndarray, minsup: int):
    """All itemsets of support >= ``minsup`` over the rows of ``dense``.

    Returns ``(masks uint32[F, W], supports int64[F])`` in no set order.
    """
    dense = np.asarray(dense, bool)
    n_items = dense.shape[1]
    counts = dense.sum(axis=0)
    f1 = np.nonzero(counts >= minsup)[0]
    out_sets, out_supp = [], []
    for i in f1:
        out_sets.append((int(i),))
        out_supp.append(int(counts[i]))
    if len(f1) >= 2:
        sub = dense[:, f1].astype(np.float32)
        # exact: every count is an integer below 2**24
        pairs = np.rint(sub.T @ sub).astype(np.int64)
        tids = _tid_bitsets(dense[:, f1])
        for a in range(len(f1) - 1):
            later = np.arange(a + 1, len(f1))
            keep = later[pairs[a, later] >= minsup]
            if keep.size == 0:
                continue
            for b in keep:
                out_sets.append((int(f1[a]), int(f1[b])))
                out_supp.append(int(pairs[a, b]))
            _extend((int(f1[a]),), f1[keep], tids[a] & tids[keep],
                    pairs[a, keep], minsup, out_sets, out_supp)
    masks = np.zeros((len(out_sets), n_words(n_items)), np.uint32)
    for r, s in enumerate(out_sets):
        for i in s:
            masks[r, i // WORD] |= np.uint32(1 << (i % WORD))
    return masks, np.asarray(out_supp, np.int64)


def _extend(prefix, items, tids, supps, minsup, out_sets, out_supp):
    """Depth-first: ``items[j]`` extends ``prefix`` with bitset ``tids[j]``."""
    for j in range(len(items) - 1):
        child = tids[j + 1:] & tids[j]
        sup = np.bitwise_count(child).sum(axis=1, dtype=np.int64)
        keep = np.nonzero(sup >= minsup)[0]
        if keep.size == 0:
            continue
        head = prefix + (int(items[j]),)
        for k in keep:
            out_sets.append(head + (int(items[j + 1 + k]),))
            out_supp.append(int(sup[k]))
        if keep.size >= 2:
            _extend(head, items[j + 1:][keep], child[keep], sup[keep], minsup,
                    out_sets, out_supp)


def sampled_table(dense: np.ndarray, minsup_rel: float, n_sample: int,
                  seed: int):
    """The control: mine a uniform sample, scale supports to the whole DB."""
    n = dense.shape[0]
    rows = np.random.default_rng(seed).choice(n, size=n_sample, replace=False)
    masks, supp = mine(dense[rows], abs_minsup(minsup_rel, n_sample))
    return masks, np.rint(supp * (n / n_sample)).astype(np.int64)


# ---------------------------------------------------------------------------
# Table comparison
# ---------------------------------------------------------------------------


def as_dict(masks: np.ndarray, supports: np.ndarray) -> dict:
    """{mask bytes: support}; a duplicated itemset keeps its last support."""
    masks = np.ascontiguousarray(np.asarray(masks, np.uint32))
    return {m.tobytes(): int(s) for m, s in zip(masks, supports)}


def table_mismatches(got: dict, want: dict, n_got_rows: int) -> int:
    """Itemsets missing or extra, supports that differ, and duplicate rows."""
    missing = sum(1 for k in want if k not in got)
    extra = sum(1 for k in got if k not in want)
    wrong = sum(1 for k, s in got.items() if k in want and want[k] != s)
    return missing + extra + wrong + (n_got_rows - len(got))

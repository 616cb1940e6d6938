"""The plain reference against brute force, and the control of ``correct``
(the reference with its exactness guarantee broken) failing the comparison,
at a size a test run can hold."""
from __future__ import annotations

import itertools
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
sys.path.insert(0, str(BENCH_DIR))

import control  # noqa: E402
import reference  # noqa: E402
from gen import ibm_quest  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def small_config():
    cfg = json.loads((ROOT / BENCH["configs"][0]["file"]).read_text())
    cfg["name"] = "small"
    cfg["dataset"].update(n_tx=4096, n_items=40, n_patterns=16,
                          avg_pattern_len=4, avg_tx_len=8, pattern_seed=5,
                          block_tx=1024)
    cfg["minsup"] = 0.04
    cfg["mining"]["n_db_sample"] = 512
    return cfg


def brute_force(dense, minsup):
    out = {}
    items = np.flatnonzero(dense.sum(axis=0) >= minsup)
    for k in range(1, len(items) + 1):
        found = False
        for combo in itertools.combinations(items, k):
            s = int(dense[:, list(combo)].all(axis=1).sum())
            if s >= minsup:
                out[frozenset(int(i) for i in combo)] = s
                found = True
        if not found:
            break
    return out


def test_reference_miner_equals_brute_force():
    dense = np.concatenate(list(ibm_quest.generate_blocks(
        dict(n_tx=600, n_items=14, n_patterns=6, avg_pattern_len=4,
             avg_tx_len=6, correlation=0.5, corruption=0.5,
             corruption_var=0.1, pattern_seed=9, block_tx=300), 9)))
    masks, supp = reference.mine(dense, 30)
    got = {frozenset(np.flatnonzero(m).tolist()): int(s) for m, s in
           zip(reference.unpack_rows(masks, 14), supp)}
    assert got == brute_force(dense, 30)
    assert len(got) > 30


def test_pack_round_trip():
    rng = np.random.default_rng(0)
    dense = rng.random((7, 70)) < 0.3
    np.testing.assert_array_equal(
        reference.unpack_rows(reference.pack_rows(dense), 70), dense)


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    return small_config(), tmp_path_factory.mktemp("control_cache")


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_control_fails_the_comparison(small, seed):
    """Sampled mining, supports scaled up, is not correct: its table
    differs from the exact one (limit 0)."""
    cfg, cache = small
    assert control.mine_reading(cfg, 1, seed, cache) > 0

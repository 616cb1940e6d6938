"""Fault tolerance: CRC32C, retry policy, reader error propagation, fsck
classification/repair, writer crash-window resume, and bit-exact
kill-and-resume of the distributed executor — every claim of DESIGN.md's
"Failure model", driven through the ``tests/faults.py`` injectors."""
import functools
import json
import os
import threading

import numpy as np
import pytest

import jax

from faults import (
    SimulatedCrash,
    corrupt_block,
    fail_nth_read,
    kill_after_round,
    orphan_block,
)
from repro.core import eclat, fimi
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.store import (
    BlockReader,
    ChecksumMismatchError,
    MissingBlockError,
    NO_RETRY,
    RetriesExhausted,
    RetryPolicy,
    StaleManifestError,
    StoreIntegrityError,
    StoreWriter,
    TruncatedBlockError,
    TxStore,
    crc32c,
    fsck,
)
from repro.store import checksum
from repro.store.checksum import crc32c_ref
from repro.store.reader import BlockReadError


def _random_dense(n_tx, n_items, seed, density=0.3):
    rng = np.random.default_rng(seed)
    return rng.random((n_tx, n_items)) < density


def _store_from_dense(tmp_path, dense, sizes, name="st"):
    assert sum(sizes) == dense.shape[0]
    w = StoreWriter(str(tmp_path / name), n_items=dense.shape[1],
                    block_tx=max(sizes) if sizes else 1)
    off = 0
    for sz in sizes:
        w.append_dense(dense[off:off + sz])
        off += sz
    return w.close()


def _fimi_params():
    return fimi.FimiParams(
        min_support_rel=0.1, n_db_sample=128, n_fi_sample=256,
        eclat=eclat.EclatConfig(max_out=1 << 14, max_stack=2048,
                                frontier_size=8),
    )


# ---------------------------------------------------------------------------
# CRC32C — the vectorized implementation against spec and reference
# ---------------------------------------------------------------------------


def test_crc32c_check_value():
    # RFC 3720 B.4: CRC32C("123456789") == 0xE3069283
    data = np.frombuffer(b"123456789", np.uint8)
    assert crc32c(data) == 0xE3069283
    assert crc32c_ref(data) == 0xE3069283


@pytest.mark.parametrize("n", [0, 1, 3, 7, 63, 64, 65, 255, 1024, 4097])
def test_crc32c_matches_bytewise_reference(n):
    rng = np.random.default_rng(n)
    data = rng.integers(0, 256, size=n, dtype=np.uint8)
    assert crc32c(data) == crc32c_ref(data)


def test_crc32c_uint32_payload_and_sensitivity():
    rng = np.random.default_rng(0)
    arr = rng.integers(0, 1 << 32, size=(16, 3), dtype=np.uint32)
    c0 = crc32c(arr)
    assert c0 == crc32c_ref(arr.view(np.uint8).reshape(-1))
    arr[7, 1] ^= np.uint32(1 << 13)  # single bit flip must change the CRC
    assert crc32c(arr) != c0


# Each CRC32C path by name; the C arm runs where google_crc32c's C extension
# imports, and only the C arm skips where it does not.
_CRC_PATHS = {"c": checksum.crc32c_c, "numpy": checksum.crc32c_numpy}
_PATH_ARMS = [
    pytest.param("c", marks=pytest.mark.skipif(
        checksum.IMPLEMENTATION != "c",
        reason="google_crc32c's C extension is not importable")),
    "numpy",
]
_BLOCK_BYTES = 4096 * 32 * 4          # one 4,096-row, 32-word store block


@functools.lru_cache(maxsize=None)
def _random_bytes_and_ref(n):
    rng = np.random.default_rng(n)
    data = rng.integers(0, 256, size=n, dtype=np.uint8)
    return data, crc32c_ref(data)


@pytest.mark.parametrize("path", _PATH_ARMS)
@pytest.mark.parametrize(
    "n", [0, 1, 3, 7, 63, 64, 65, 255, 1024, 4097, _BLOCK_BYTES])
def test_crc32c_paths_agree_with_reference(path, n):
    data, want = _random_bytes_and_ref(n)
    assert _CRC_PATHS[path](data) == want
    assert _CRC_PATHS[path](data.tobytes()) == want


@pytest.mark.parametrize("path", _PATH_ARMS)
def test_crc32c_paths_check_value_and_strided_view(path):
    crc = _CRC_PATHS[path]
    assert crc(b"123456789") == 0xE3069283            # RFC 3720 B.4
    rng = np.random.default_rng(1)
    arr = rng.integers(0, 1 << 32, size=(64, 12), dtype=np.uint32)
    for view in (arr[:, ::3], arr.T, arr[::2]):
        assert not view.flags.c_contiguous
        flat = np.ascontiguousarray(view).view(np.uint8).reshape(-1)
        assert crc(view) == crc32c_ref(flat)


# ---------------------------------------------------------------------------
# Retry policy — deterministic schedule, injectable clock
# ---------------------------------------------------------------------------


def test_retry_survives_transient_fault_with_exact_schedule():
    slept = []
    pol = RetryPolicy(attempts=4, base_delay_s=0.01, backoff=3.0,
                      max_delay_s=0.05, sleep=slept.append,
                      clock=lambda: 0.0)
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    assert pol.call(flaky, describe="read") == "ok"
    assert calls["n"] == 3
    assert slept == [0.01, 0.03]          # base·backoff^k, no sleep on success


def test_retry_delay_is_capped():
    pol = RetryPolicy(base_delay_s=0.01, backoff=10.0, max_delay_s=0.25)
    assert [pol.delay(k) for k in range(4)] == [0.01, 0.1, 0.25, 0.25]


def test_retry_exhaustion_wraps_last_error():
    slept = []
    pol = RetryPolicy(attempts=3, base_delay_s=0.01, sleep=slept.append,
                      clock=lambda: 0.0)
    with pytest.raises(RetriesExhausted, match="pull block 7.*3 attempts"):
        pol.call(lambda: (_ for _ in ()).throw(OSError("gone")),
                 describe="pull block 7")
    assert len(slept) == 2                # attempts−1 sleeps, then give up
    try:
        pol.call(lambda: (_ for _ in ()).throw(OSError("gone")), describe="x")
    except RetriesExhausted as e:
        assert isinstance(e.__cause__, OSError)


def test_retry_never_retries_integrity_errors():
    slept = []
    pol = RetryPolicy(attempts=5, sleep=slept.append)

    def bad():
        raise ChecksumMismatchError("persistent fact about disk bytes")

    with pytest.raises(ChecksumMismatchError):
        pol.call(bad)
    assert slept == []                    # first throw propagates untouched


# ---------------------------------------------------------------------------
# BlockReader — worker-thread failures surface at the consumer, typed
# ---------------------------------------------------------------------------


def test_reader_survives_transient_read_fault(tmp_path):
    dense = _random_dense(96, 16, seed=1)
    s = _store_from_dense(tmp_path, dense, [32, 32, 32])
    slept = []
    rd = BlockReader(s, retry=RetryPolicy(attempts=3, base_delay_s=0.001,
                                          sleep=slept.append))
    with fail_nth_read(2, OSError, fail_count=2):
        n_rows = sum(n for _, _, _, n in rd.device_blocks())
    assert n_rows == 96                   # stream completed despite the fault
    assert len(slept) == 2                # block 1 needed both retries
    assert rd.read_attempts == 5          # 3 clean reads + 2 failed attempts


def test_reader_persistent_fault_raises_with_block_context(tmp_path):
    dense = _random_dense(96, 16, seed=2)
    s = _store_from_dense(tmp_path, dense, [32, 32, 32])
    before = threading.active_count()
    rd = BlockReader(s, retry=RetryPolicy(attempts=2, base_delay_s=0.0,
                                          sleep=lambda _: None))
    with fail_nth_read(2, OSError):
        with pytest.raises(RetriesExhausted, match=r"read block 1 .*block_0"):
            for _ in rd.device_blocks():
                pass
    assert threading.active_count() == before   # worker joined, not leaked


def test_reader_wraps_unexpected_worker_errors(tmp_path):
    dense = _random_dense(64, 16, seed=3)
    s = _store_from_dense(tmp_path, dense, [32, 32])
    rd = BlockReader(s, retry=NO_RETRY)
    with fail_nth_read(2, RuntimeError):     # not retryable, not typed
        with pytest.raises(BlockReadError, match=r"block 1 .*block_000001"):
            for _ in rd.device_blocks():
                pass


def test_reader_passes_integrity_errors_through_typed(tmp_path):
    dense = _random_dense(64, 16, seed=4)
    s = _store_from_dense(tmp_path, dense, [32, 32])
    corrupt_block(s.directory, 1, "bitflip")
    with pytest.raises(ChecksumMismatchError, match="block_000001"):
        for _ in BlockReader(TxStore.open(s.directory)).device_blocks():
            pass


# ---------------------------------------------------------------------------
# Corruption reaches the miner as a distinct, actionable error
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode,exc", [
    ("bitflip", ChecksumMismatchError),
    ("truncate", TruncatedBlockError),
    ("delete", MissingBlockError),
    ("stale", StaleManifestError),
])
def test_corruption_fails_mining_with_typed_error(tmp_path, mode, exc):
    dense = _random_dense(128, 16, seed=5)
    s = _store_from_dense(tmp_path, dense, [32, 32, 32, 32])
    corrupt_block(s.directory, 2, mode)
    s2 = TxStore.open(s.directory)        # manifest still loads fine
    with pytest.raises(exc, match="block_000002") as ei:
        fimi.run(s2, None, _fimi_params(), jax.random.PRNGKey(0),
                 materialize=True, P=2)
    assert isinstance(ei.value, StoreIntegrityError)   # one catchable base


def test_verify_off_skips_checksum_only(tmp_path):
    dense = _random_dense(64, 16, seed=6)
    s = _store_from_dense(tmp_path, dense, [32, 32])
    corrupt_block(s.directory, 0, "bitflip")
    st = TxStore.open(s.directory, verify=False)
    st.read_block(0)                      # geometry intact ⇒ readable
    with pytest.raises(ChecksumMismatchError):
        TxStore.open(s.directory).read_block(0)


def _crc_counts():
    reg = obs_metrics.registry()
    return {p: reg.counter(f"store/crc_blocks_{p}").value for p in _CRC_PATHS}


@pytest.mark.parametrize("path", _PATH_ARMS)
def test_each_crc_path_verifies_reads_and_refuses_a_flipped_bit(
        tmp_path, monkeypatch, path):
    monkeypatch.setattr(checksum, "IMPLEMENTATION", path)
    dense = _random_dense(96, 16, seed=9)
    s = _store_from_dense(tmp_path, dense, [32, 32, 32])
    assert [b.crc32c for b in s.manifest.blocks] == [
        crc32c_ref(np.ascontiguousarray(s.read_block(i)).view(np.uint8))
        for i in range(3)]
    want = TxStore.open(s.directory, verify=False).read_all_packed()
    before = _crc_counts()
    np.testing.assert_array_equal(
        TxStore.open(s.directory).read_all_packed(), want)
    after = _crc_counts()
    assert {p: after[p] - before[p] for p in after} == {
        p: (3 if p == path else 0) for p in after}
    corrupt_block(s.directory, 1, "bitflip")
    with pytest.raises(ChecksumMismatchError, match="block_000001"):
        TxStore.open(s.directory).read_block(1)
    assert _crc_counts()[path] == after[path] + 1


@pytest.mark.parametrize("path", _PATH_ARMS)
def test_mine_from_store_names_its_crc_path(tmp_path, monkeypatch, path):
    monkeypatch.setattr(checksum, "IMPLEMENTATION", path)
    dense = _random_dense(128, 16, seed=10)
    s = _store_from_dense(tmp_path, dense, [32, 32, 32, 32])
    tr = obs_trace.TRACER
    tr.clear()
    before = _crc_counts()
    tr.enable()
    try:
        fimi.run(TxStore.open(s.directory), None, _fimi_params(),
                 jax.random.PRNGKey(0), materialize=True, P=2)
    finally:
        tr.disable()
    (span,) = [e for e in tr.export()["traceEvents"]
               if e.get("ph") == "X" and e["name"] == "fimi/assemble_store"]
    tr.clear()
    assert span["args"]["crc"] == path
    assert _crc_counts()[path] - before[path] == s.n_blocks


# ---------------------------------------------------------------------------
# fsck — classification, repair, quarantine; the CLI exit contract
# ---------------------------------------------------------------------------


def test_fsck_classifies_every_damage_kind(tmp_path):
    dense = _random_dense(160, 16, seed=7)
    s = _store_from_dense(tmp_path, dense, [32, 32, 32, 32, 32])
    corrupt_block(s.directory, 0, "bitflip")
    corrupt_block(s.directory, 1, "truncate")
    corrupt_block(s.directory, 2, "delete")
    corrupt_block(s.directory, 3, "stale")
    orphan_block(s.directory, n_rows=4)
    rep = fsck(s.directory)               # read-only scan
    kinds = sorted(d.kind for d in rep.damages)
    assert kinds == ["bit-flip", "missing", "orphan", "stale-manifest",
                     "truncated"]
    assert not rep.clean and all(d.action == "none" for d in rep.damages)


def test_fsck_quarantine_salvages_survivors(tmp_path):
    dense = _random_dense(128, 16, seed=8)
    s = _store_from_dense(tmp_path, dense, [32, 32, 32, 32])
    corrupt_block(s.directory, 1, "bitflip")
    corrupt_block(s.directory, 3, "delete")
    rep = fsck(s.directory, quarantine=True)
    assert rep.clean and rep.n_blocks == 2 and rep.n_tx == 64
    q = os.path.join(s.directory, "quarantine")
    assert os.listdir(q) == ["block_000001.npy"]      # deleted one is gone
    st = TxStore.open(s.directory)
    got = np.concatenate([st.read_block(i) for i in range(st.n_blocks)])
    from repro.store import pack_bool_np
    want = np.concatenate([pack_bool_np(dense[0:32]),
                           pack_bool_np(dense[64:96])])
    assert np.array_equal(got, want)      # exactly the undamaged payloads
    assert fsck(s.directory).damages == []


def test_fsck_cli_exit_codes(tmp_path, capsys, monkeypatch):
    from repro.launch import fsck as cli

    dense = _random_dense(64, 16, seed=9)
    s = _store_from_dense(tmp_path, dense, [32, 32])

    def run(*argv):
        monkeypatch.setattr("sys.argv", ["fsck", *argv])
        with pytest.raises(SystemExit) as ei:
            cli.main()
        return ei.value.code or 0, capsys.readouterr().out

    code, out = run(s.directory)
    assert code == 0 and "clean" in out
    corrupt_block(s.directory, 0, "bitflip")
    code, _ = run(s.directory)
    assert code == 1                      # damage found, nothing done
    code, _ = run(s.directory, "--quarantine")
    assert code == 0                      # damage handled
    code, _ = run(s.directory)
    assert code == 0                      # now clean
    code, _ = run(str(tmp_path / "nowhere"))
    assert code == 2                      # not a store


# ---------------------------------------------------------------------------
# StoreWriter crash window — resume adopts or deletes residue, deterministic
# ---------------------------------------------------------------------------


def test_writer_resume_adopts_crash_residue(tmp_path):
    dense = _random_dense(64, 12, seed=10)
    s = _store_from_dense(tmp_path, dense, [32, 32])
    # crash between np.save and manifest flush: two valid orphans
    orphan_block(s.directory, n_rows=8)
    orphan_block(s.directory, n_rows=4)
    w = StoreWriter(s.directory, n_items=12, block_tx=32, resume=True)
    st = w.close()
    assert st.n_blocks == 4 and st.n_tx == 64 + 12
    assert st.manifest.item_counts[0] >= 12   # adopted rows counted exactly
    # adoption is deterministic: a second resume finds nothing left to do
    with open(os.path.join(s.directory, "manifest.json")) as f:
        m1 = json.load(f)
    StoreWriter(s.directory, n_items=12, block_tx=32, resume=True).close()
    with open(os.path.join(s.directory, "manifest.json")) as f:
        assert json.load(f) == m1


def test_writer_resume_deletes_torn_and_gapped_residue(tmp_path):
    dense = _random_dense(32, 12, seed=11)
    s = _store_from_dense(tmp_path, dense, [32])
    torn = orphan_block(s.directory, n_rows=8, torn=True)
    gapped = orphan_block(s.directory, n_rows=8, index=7)
    w = StoreWriter(s.directory, n_items=12, block_tx=32, resume=True)
    st = w.close()
    assert st.n_blocks == 1 and st.n_tx == 32     # neither was adoptable
    assert not os.path.exists(torn) and not os.path.exists(gapped)


def test_writer_resume_names_blocks_past_quarantine_gap(tmp_path):
    dense = _random_dense(96, 12, seed=12)
    s = _store_from_dense(tmp_path, dense, [32, 32, 32])
    corrupt_block(s.directory, 1, "bitflip")
    fsck(s.directory, quarantine=True)            # blocks/ now has 0 and 2
    w = StoreWriter(s.directory, n_items=12, block_tx=32, resume=True)
    w.append_dense(_random_dense(32, 12, seed=13))
    st = w.close()
    files = sorted(b.file for b in st.manifest.blocks)
    assert files == [os.path.join("blocks", f"block_{i:06d}.npy")
                     for i in (0, 2, 3)]          # never reuses a live name
    fsck(s.directory)                             # and the result is clean


# ---------------------------------------------------------------------------
# Checkpointed cluster rounds — kill, resume, bit-exact
# ---------------------------------------------------------------------------


def _cluster_setup():
    from repro import cluster

    dense = _random_dense(128, 16, seed=14, density=0.35)
    shards = fimi.shard_db(np.asarray(dense), 2)
    params = cluster.ClusterParams(
        planner=cluster.PlannerParams(min_support_rel=0.15, n_db_sample=128,
                                      n_fi_sample=128),
        eclat=eclat.EclatConfig(max_out=1 << 13, max_stack=2048,
                                frontier_size=8),
        chunk=1,                          # force several rounds
    )
    return cluster, shards, params, jax.random.PRNGKey(1)


def test_kill_and_resume_is_bit_exact(tmp_path):
    cluster, shards, params, key = _cluster_setup()
    ref = cluster.execute(shards, 16, params, key)
    assert ref.report.n_rounds >= 3       # the kill must land mid-run
    ck = str(tmp_path / "ck")
    with pytest.raises(SimulatedCrash):
        cluster.execute(shards, 16, params, key, checkpoint_dir=ck,
                        round_hook=kill_after_round(1))
    res = cluster.execute(shards, 16, params, key, checkpoint_dir=ck,
                          resume=True)
    assert np.array_equal(res.table.masks, ref.table.masks)
    assert np.array_equal(res.table.supports, ref.table.supports)
    assert res.report.n_rounds == ref.report.n_rounds
    assert np.array_equal(res.report.observed_loads, ref.report.observed_loads)
    assert res.report.donations == ref.report.donations


def test_resume_refuses_foreign_or_corrupt_checkpoint(tmp_path):
    import dataclasses

    cluster, shards, params, key = _cluster_setup()
    ck = str(tmp_path / "ck")
    with pytest.raises(SimulatedCrash):
        cluster.execute(shards, 16, params, key, checkpoint_dir=ck,
                        round_hook=kill_after_round(0))
    # different support threshold ⇒ different plan ⇒ refuse
    p2 = dataclasses.replace(params, planner=dataclasses.replace(
        params.planner, min_support_rel=0.3))
    with pytest.raises(cluster.CheckpointError, match="different run"):
        cluster.execute(shards, 16, p2, key, checkpoint_dir=ck, resume=True)
    # flip a payload bit ⇒ CRC mismatch ⇒ refuse
    payload = [f for f in os.listdir(ck) if f.endswith(".npz")][0]
    with open(os.path.join(ck, payload), "r+b") as f:
        raw = bytearray(f.read())
        raw[len(raw) // 2] ^= 0x01
        f.seek(0)
        f.write(raw)
    with pytest.raises(cluster.CheckpointError, match="corrupt"):
        cluster.execute(shards, 16, params, key, checkpoint_dir=ck,
                        resume=True)


def test_resume_without_checkpoint_runs_fresh(tmp_path):
    cluster, shards, params, key = _cluster_setup()
    ref = cluster.execute(shards, 16, params, key)
    res = cluster.execute(shards, 16, params, key,
                          checkpoint_dir=str(tmp_path / "empty"), resume=True)
    assert res.table.to_dict() == ref.table.to_dict()

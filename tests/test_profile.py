"""Kernel profiler: disabled-path transparency (bit-identical results,
<2 % dispatch overhead), analytic cost-model pricing, shape bucketing,
eager timed calls, traced-dispatch tally + while_loop attribution, and the
published ``kernels/*`` gauge scheme ``obs_report kernels`` consumes."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitmap as bm
from repro.kernels import ops
from repro.obs import metrics as obs_metrics
from repro.obs import profile as obs_profile
from repro.obs.machine import CPU_HOST, TPU_V5E, machine_for_device_kind


@pytest.fixture(autouse=True)
def _fresh_profiler():
    """Every test starts (and leaves) with a disabled, empty profiler."""
    obs_profile.PROFILER.disable()
    obs_profile.PROFILER.clear()
    obs_metrics.reset()
    yield
    obs_profile.PROFILER.disable()
    obs_profile.PROFILER.clear()
    obs_metrics.reset()


def _db(n_tx=96, n_items=12, seed=0):
    rng = np.random.default_rng(seed)
    dense = rng.random((n_tx, n_items)) < 0.35
    return bm.BitmapDB.from_dense(jnp.asarray(dense))


# ---------------------------------------------------------------------------
# Disabled path: the wrapper must be invisible
# ---------------------------------------------------------------------------


def test_disabled_dispatch_bit_identical():
    """Wrapped dispatch == the naked function, profiler off or on."""
    db = _db()
    all_t = db.all_tids()
    prefix_tids = jnp.tile(all_t[None, :], (4, 1))
    q = db.tx_bits[:8]
    f = db.tx_bits[:16]
    blocks = db.tx_bits[:32].reshape(2, 16, -1)
    cases = [
        (ops.extension_supports, (db.item_bits, all_t)),
        (ops.multi_extension_supports, (db.item_bits, prefix_tids)),
        (ops.pair_supports, (db.item_bits, all_t)),
        (ops.subset_superset_counts, (q, f)),
        (ops.block_itemset_supports, (blocks, f)),
    ]
    for fn, args in cases:
        want = jax.tree_util.tree_map(np.asarray, fn.__wrapped__(*args))
        got_off = fn(*args)
        obs_profile.PROFILER.enable()
        got_on = fn(*args)
        obs_profile.PROFILER.disable()
        for w, a, b in zip(
            jax.tree_util.tree_leaves(want),
            jax.tree_util.tree_leaves(got_off),
            jax.tree_util.tree_leaves(got_on),
        ):
            np.testing.assert_array_equal(w, np.asarray(a))
            np.testing.assert_array_equal(w, np.asarray(b))
    # nothing may have been recorded while disabled; one bucket per family
    # while enabled
    rep = obs_profile.PROFILER.report()
    assert all(f["calls"] == 1 for f in rep["families"].values())
    assert set(rep["families"]) == set(obs_profile.FAMILIES)


def test_disabled_overhead_under_2pct():
    """The disabled wrapper adds < 2 % to a real dispatch's wall time.

    An end-to-end A/B of full jnp dispatches is noise-bound (device
    dispatch jitter alone is >2 %), so measure the two costs separately:
    the wrapper's per-call overhead on a pure-Python stub (its disabled
    path does no jax work, so the stub sees the identical code path), and
    an actual eager dispatch as the denominator.
    """
    def stub(a, b):
        return a

    wrapped = ops._profiled("bitmap", lambda a, b: {"I": 1, "W": 1})(stub)
    n = 100_000

    def loop(fn):
        t0 = time.perf_counter()
        for _ in range(n):
            fn(1, 2)
        return time.perf_counter() - t0

    t_stub = min(loop(stub) for _ in range(5))
    t_wrapped = min(loop(wrapped) for _ in range(5))
    overhead_s = max(t_wrapped - t_stub, 0.0) / n

    db = _db()
    all_t = db.all_tids()
    jax.block_until_ready(ops.extension_supports(db.item_bits, all_t))
    t0 = time.perf_counter()
    for _ in range(50):
        ops.extension_supports(db.item_bits, all_t)
    jax.block_until_ready(ops.extension_supports(db.item_bits, all_t))
    dispatch_s = (time.perf_counter() - t0) / 50

    assert overhead_s < 0.02 * dispatch_s, (
        f"disabled-profiler wrapper costs {overhead_s * 1e9:.0f}ns/call = "
        f"{overhead_s / dispatch_s:.2%} of a {dispatch_s * 1e6:.0f}us "
        f"dispatch (>= 2%)"
    )


# ---------------------------------------------------------------------------
# Cost model + bucketing
# ---------------------------------------------------------------------------


def test_cost_model_word_op_counts():
    assert obs_profile.cost_model("bitmap", {"I": 4, "W": 2}) == (
        3.0 * 4 * 2, 4.0 * (4 * 2 + 2 + 4))
    assert obs_profile.cost_model("multi", {"K": 2, "I": 4, "W": 2}) == (
        3.0 * 2 * 4 * 2, 4.0 * (4 * 2 + 2 * 2 + 2 * 4))
    assert obs_profile.cost_model("pair", {"I": 4, "W": 2}) == (
        3.0 * 16 * 2, 4.0 * (4 * 2 + 2 + 16))
    assert obs_profile.cost_model("subset", {"Q": 2, "F": 3, "IW": 2}) == (
        8.0 * 2 * 3 * 2, 4.0 * ((2 + 3) * 2 + 2 * 2 * 3))
    assert obs_profile.cost_model(
        "delta", {"S": 2, "T": 3, "F": 4, "IW": 2}
    ) == (4.0 * 2 * 3 * 4 * 2, 4.0 * (2 * 3 * 2 + 4 * 2 + 2 * 4))
    with pytest.raises(ValueError):
        obs_profile.cost_model("nope", {})


def test_shape_buckets_round_up_to_pow2():
    lbl = obs_profile._bucket_label("multi", {"K": 5, "I": 100, "W": 3})
    assert lbl == "multi[K=8,I=128,W=4]"
    # same bucket for any shape in the pow2 cell → one histogram per cell
    assert lbl == obs_profile._bucket_label(
        "multi", {"K": 8, "I": 65, "W": 4})


def test_machine_for_backend():
    assert machine_for_device_kind("TPU v5 lite") is TPU_V5E
    assert machine_for_device_kind("cpu") is CPU_HOST
    assert TPU_V5E.link_bw == 200e9   # 1,600 Gbit/s published ICI
    for unknown in ("TPU v4", "TPU v6 lite", "gpu"):
        with pytest.raises(ValueError, match="no machine model"):
            machine_for_device_kind(unknown)
    # the profiler prices the device this process runs on, by its kind
    assert obs_profile.KernelProfiler().machine is machine_for_device_kind(
        jax.devices()[0].device_kind)
    assert TPU_V5E.balance_word_ops_per_byte > CPU_HOST.balance_word_ops_per_byte / 10


# ---------------------------------------------------------------------------
# Eager timing, loop attribution, publish
# ---------------------------------------------------------------------------


def test_eager_call_measured_vs_modeled():
    db = _db()
    obs_profile.PROFILER.enable()
    for _ in range(3):
        ops.pair_supports(db.item_bits, db.all_tids())
    rep = obs_profile.PROFILER.report()
    fam = rep["families"]["pair"]
    assert fam["calls"] == 3 and fam["loop_execs"] == 0
    assert fam["measured_ms"] > 0.0
    assert fam["modeled_ms"] == pytest.approx(
        max(fam["compute_ms"], fam["memory_ms"]))
    assert fam["achieved_frac"] == pytest.approx(
        fam["modeled_ms"] / fam["measured_ms"])
    assert fam["mem_bound"] == (fam["memory_ms"] > fam["compute_ms"])
    assert rep["machine"]["word_ops_peak"] > 0
    b = fam["buckets"][0]
    assert b["min_us"] is not None and b["max_us"] >= b["min_us"]


def test_traced_dispatch_tallied_then_loop_attributed():
    """Inside jit the dispatch is a tracer: tallied, not timed; the real
    work lands via observe_loop with the driver's trip count + wall."""
    db = _db()
    obs_profile.PROFILER.enable()
    fn = jax.jit(lambda ib, t: ops.extension_supports(ib, t))
    jax.block_until_ready(fn(db.item_bits, db.all_tids()))
    rep = obs_profile.PROFILER.report()
    fam = rep["families"]["bitmap"]
    assert fam["trace_dispatches"] >= 1
    assert fam["calls"] == 0 and fam["measured_ms"] == 0.0

    dims = {"I": db.n_items, "W": db.item_bits.shape[1]}
    obs_profile.PROFILER.observe_loop("bitmap", dims, n_exec=10, wall_s=0.5)
    fam = obs_profile.PROFILER.report()["families"]["bitmap"]
    assert fam["loop_execs"] == 10
    assert fam["measured_ms"] == pytest.approx(500.0)
    flops, _ = obs_profile.cost_model("bitmap", dims)
    assert fam["flops"] == pytest.approx(10 * flops)


def test_observe_loop_noop_when_disabled_or_empty():
    obs_profile.PROFILER.observe_loop("multi", {"K": 1, "I": 2, "W": 1},
                                      n_exec=5, wall_s=1.0)
    obs_profile.PROFILER.enable()
    obs_profile.PROFILER.observe_loop("multi", {"K": 1, "I": 2, "W": 1},
                                      n_exec=0, wall_s=1.0)
    assert obs_profile.PROFILER.report()["families"] == {}


def test_publish_gauge_scheme():
    db = _db()
    obs_profile.PROFILER.enable()
    ops.pair_supports(db.item_bits, db.all_tids())
    obs_profile.PROFILER.observe_loop(
        "multi", {"K": 4, "I": db.n_items, "W": db.item_bits.shape[1]},
        n_exec=7, wall_s=0.1)
    obs_profile.PROFILER.publish(obs_metrics.registry())
    snap = obs_metrics.snapshot()
    g, c = snap["gauges"], snap["counters"]
    for field in ("measured_ms", "modeled_ms", "compute_ms", "memory_ms",
                  "flops", "bytes", "achieved_frac", "mem_bound"):
        assert f"kernels/pair/{field}" in g
    assert c["kernels/pair/calls"] == 1
    assert c["kernels/multi/loop_execs"] == 7
    assert g["kernels/machine/word_ops_peak"] > 0
    assert g["kernels/machine/hbm_bw"] > 0
    # live per-bucket histogram recorded at call time
    assert any(k.startswith("kernels/pair/call_us/") for k in
               snap["histograms"])

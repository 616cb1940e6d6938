"""Observability layer: histogram percentiles vs the numpy nearest-rank
oracle, registry snapshot shape, tracer thread-safety + Chrome trace-event
export, subsystem instrumentation (reader/retry/cache/cluster report), run
records, and the report CLI's regression gates."""
import json
import math
import os
import subprocess
import sys
import threading
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np
import pytest

from repro.launch import obs_report
from repro.obs import metrics as obs_metrics
from repro.obs import runlog
from repro.obs import trace as obs_trace

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def _fresh_obs():
    """Every test starts (and leaves) with clean global registry/tracer."""
    obs_metrics.reset()
    obs_trace.TRACER.disable()
    obs_trace.TRACER.clear()
    yield
    obs_metrics.reset()
    obs_trace.TRACER.disable()
    obs_trace.TRACER.clear()


# ---------------------------------------------------------------------------
# Histogram: log-bucketed percentiles vs the exact numpy nearest-rank oracle
# ---------------------------------------------------------------------------


def _oracle(samples, q):
    return float(np.percentile(np.asarray(samples, float), q,
                               method="nearest"))


@pytest.mark.parametrize(
    "samples",
    [
        # uniform: adjacent ranks are close, buckets dominate the error
        np.random.default_rng(0).uniform(0.1, 10.0, size=1000).tolist(),
        # lognormal: 6 decades of dynamic range in one histogram
        np.random.default_rng(1).lognormal(0.0, 2.5, size=2000).tolist(),
        # bimodal with a 1000x gap right at the median rank — the adversarial
        # case for any bucketed sketch (both sides use round-half-even, so
        # the nearest rank is deterministic on both)
        [1.0] * 50 + [1000.0] * 50,
        # heavily skewed bimodal: p50 on the low mode, p95/p99 on the high
        [1.0] * 90 + [1000.0] * 10,
        # constant stream
        [3.7] * 64,
        # two samples, extreme spread
        [1e-6, 1e6],
    ],
    ids=["uniform", "lognormal", "bimodal-50", "bimodal-90", "constant",
         "pair"],
)
def test_histogram_percentiles_match_numpy(samples):
    h = obs_metrics.Histogram("t/lat_ms", growth=1.08)
    for v in samples:
        h.record(v)
    # documented bound: within a sqrt(growth) factor of the exact
    # nearest-rank percentile (bucket midpoint, clamped to [min, max])
    factor = math.sqrt(1.08) * (1 + 1e-9)
    for q in (0, 50, 95, 99, 100):
        want = _oracle(samples, q)
        got = h.percentile(q)
        assert got is not None
        assert want / factor <= got <= want * factor, (
            f"q={q}: got {got}, oracle {want}"
        )
    assert h.count == len(samples)
    assert h.sum == pytest.approx(sum(samples), rel=1e-9)


def test_histogram_single_sample_and_empty():
    h = obs_metrics.Histogram("t/x_s")
    assert h.percentile(50) is None
    s = h.summary()
    assert s["count"] == 0
    assert s["mean"] is None and s["p50"] is None and s["max"] is None
    h.record(42.0)
    # one sample: every percentile IS that sample, exactly
    for q in (0, 50, 99, 100):
        assert h.percentile(q) == 42.0
    s = h.summary()
    assert s["count"] == 1 and s["min"] == s["max"] == 42.0


def test_histogram_underflow_and_bad_samples():
    h = obs_metrics.Histogram("t/x_s")
    for v in [0.0, 0.0, 0.0, 5.0]:
        h.record(v)
    # zeros land in the underflow bucket; low ranks report the exact min
    assert h.percentile(50) == 0.0
    assert h.percentile(99) == pytest.approx(5.0, rel=0.05)
    with pytest.raises(ValueError):
        h.record(-1.0)
    with pytest.raises(ValueError):
        h.record(float("nan"))


# ---------------------------------------------------------------------------
# Registry: counters/gauges, canonical snapshot shape, typed names
# ---------------------------------------------------------------------------


def test_registry_snapshot_shape_and_reset():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("a/events").inc()
    reg.counter("a/events").inc(4)
    reg.gauge("a/level").set(2.5)
    reg.gauge("a/peak").update_max(7.0)
    reg.gauge("a/peak").update_max(3.0)     # high-water keeps 7
    reg.histogram("a/lat_ms").record(1.0)
    snap = reg.snapshot()
    assert set(snap) == {"counters", "gauges", "histograms"}
    assert snap["counters"] == {"a/events": 5}
    assert snap["gauges"] == {"a/level": 2.5, "a/peak": 7.0}
    assert set(snap["histograms"]["a/lat_ms"]) == {
        "count", "sum", "mean", "min", "max", "p50", "p95", "p99"
    }
    # snapshot is JSON-clean by construction (what runlog writes verbatim)
    json.dumps(snap)
    assert reg.names() == ["a/events", "a/lat_ms", "a/level", "a/peak"]
    reg.reset()
    assert reg.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}


def test_registry_rejects_type_mismatch():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")
    with pytest.raises(TypeError):
        reg.histogram("x")


def test_counter_thread_safety():
    reg = obs_metrics.MetricsRegistry()
    c = reg.counter("n")

    def hammer():
        for _ in range(2000):
            c.inc()

    with ThreadPoolExecutor(8) as ex:
        list(ex.map(lambda _: hammer(), range(8)))
    assert c.value == 8 * 2000


# ---------------------------------------------------------------------------
# Tracer: disabled fast path, nesting, export, thread-safety
# ---------------------------------------------------------------------------


def _assert_chrome_trace(obj):
    """Structural validity of a Chrome trace-event object (what Perfetto
    and chrome://tracing require to render)."""
    assert isinstance(obj, dict) and isinstance(obj["traceEvents"], list)
    for ev in obj["traceEvents"]:
        assert ev["ph"] in ("X", "i", "M", "C")
        assert isinstance(ev["name"], str)
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        if ev["ph"] == "X":
            assert isinstance(ev["ts"], (int, float))
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
        if ev["ph"] == "M":
            assert ev["name"] == "thread_name"
            assert isinstance(ev["args"]["name"], str)
        if ev["ph"] == "C":
            # counter samples: every lane value must be numeric
            assert ev["args"] and all(
                isinstance(v, (int, float)) for v in ev["args"].values())


def test_tracer_disabled_is_inert():
    tr = obs_trace.Tracer(enabled=False)
    # the disabled span is one shared object: no per-call allocation
    assert tr.span("a") is tr.span("b") is obs_trace._NULL_SPAN
    with tr.span("a"):
        pass
    tr.instant("mark")
    tr.add_span("lane", 0.0, 1.0, track="shard0")
    assert tr.n_events == 0
    # sync() must return the value untouched — no jax import, no block
    sentinel = object()
    assert tr.sync(sentinel) is sentinel


def test_tracer_span_nesting_and_export_round_trip():
    tr = obs_trace.Tracer(enabled=True)
    with tr.span("outer", P=4):
        with tr.span("inner"):
            pass
        tr.instant("tick", round=1)
    tr.add_span("modeled", 0.0, 0.25, track="shard1", args={"trips": 9})
    out = json.loads(json.dumps(tr.export()))   # byte round-trip
    _assert_chrome_trace(out)
    evs = {e["name"]: e for e in out["traceEvents"] if e["ph"] != "M"}
    assert set(evs) == {"outer", "inner", "tick", "modeled"}
    # nesting by time containment: inner ⊆ outer on the same track
    outer, inner = evs["outer"], evs["inner"]
    assert outer["tid"] == inner["tid"]
    assert outer["ts"] <= inner["ts"]
    assert outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]
    assert outer["args"] == {"P": 4}
    assert evs["tick"]["ph"] == "i" and evs["tick"]["args"] == {"round": 1}
    # the virtual track got a thread_name metadata record
    tracks = {e["args"]["name"] for e in out["traceEvents"]
              if e["ph"] == "M"}
    assert "shard1" in tracks
    assert evs["modeled"]["dur"] == pytest.approx(0.25e6)  # seconds → µs


def test_tracer_thread_safety_under_concurrent_spans():
    tr = obs_trace.Tracer(enabled=True)

    def worker(i):
        for k in range(50):
            with tr.span(f"w{i}", k=k):
                tr.instant(f"m{i}")

    with ThreadPoolExecutor(8) as ex:
        list(ex.map(worker, range(8)))
    with tr.span("main"):
        pass
    assert tr.n_events == 8 * 50 * 2 + 1
    out = tr.export()
    _assert_chrome_trace(out)
    # every recording thread is named in the metadata
    named_tids = {e["tid"] for e in out["traceEvents"] if e["ph"] == "M"}
    used_tids = {e["tid"] for e in out["traceEvents"] if e["ph"] != "M"}
    assert used_tids <= named_tids
    tr.clear()
    assert tr.n_events == 0


def test_tracer_enable_disable_cycle():
    tr = obs_trace.Tracer()
    with tr.span("off"):
        pass
    tr.enable()
    with tr.span("on"):
        pass
    tr.disable()
    with tr.span("off2"):
        pass
    names = [e["name"] for e in tr.export()["traceEvents"]
             if e["ph"] == "X"]
    assert names == ["on"]


# ---------------------------------------------------------------------------
# Subsystem instrumentation: reader (its prefetch worker thread records
# concurrently with the consumer), retry, cache stats, cluster report
# ---------------------------------------------------------------------------


def test_block_reader_metrics_and_prefetch_thread(tmp_path):
    from repro.store import BlockReader, StoreWriter

    rng = np.random.default_rng(7)
    dense = rng.random((64, 24)) < 0.3
    w = StoreWriter(str(tmp_path / "st"), n_items=24, block_tx=16)
    for off in range(0, 64, 16):
        w.append_dense(dense[off:off + 16])
    store = w.close()

    tr = obs_trace.TRACER
    tr.enable()
    reader = BlockReader(store, host_budget_blocks=2)
    n = 0
    for _i, _off, blk, _rows in reader.device_blocks():
        with tr.span("consume", block=n):
            np.asarray(blk)     # force the device value
        n += 1
    assert n == store.n_blocks
    snap = obs_metrics.snapshot()
    # the consumer thread recorded the stall histogram + block counter while
    # the prefetch worker recorded the residency high-water gauge
    assert snap["counters"]["store/blocks_read"] == store.n_blocks
    assert snap["histograms"]["store/prefetch_stall_s"]["count"] == \
        store.n_blocks
    assert snap["gauges"]["store/host_bytes_peak"] > 0
    assert snap["gauges"]["store/host_bytes_peak"] == reader.peak_host_bytes
    _assert_chrome_trace(tr.export())


def test_retry_policy_metrics():
    from repro.store.retry import RetriesExhausted, RetryPolicy

    pol = RetryPolicy(attempts=3, sleep=lambda s: None)
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    assert pol.call(flaky) == "ok"
    snap = obs_metrics.snapshot()
    assert snap["counters"]["store/retry/attempts"] == 3
    assert snap["counters"]["store/retry/retried_errors"] == 2
    assert "store/retry/exhausted" not in snap["counters"]

    def broken():
        raise OSError("persistent")

    with pytest.raises(RetriesExhausted):
        RetryPolicy(attempts=2, sleep=lambda s: None).call(broken)
    snap = obs_metrics.snapshot()
    assert snap["counters"]["store/retry/attempts"] == 3 + 2
    assert snap["counters"]["store/retry/exhausted"] == 1


def test_cache_stats_thin_views_and_global_mirror():
    from repro.serve.cache import CacheStats

    s = CacheStats()
    for _ in range(3):
        s.hit()
    s.miss()
    s.eviction()
    s.invalidation()
    assert (s.hits, s.misses, s.evictions, s.invalidations) == (3, 1, 1, 1)
    assert s.lookups == 4
    assert s.hit_rate == pytest.approx(0.75)
    assert s.as_dict()["hits"] == 3
    snap = s.snapshot()
    assert set(snap) == {"counters", "gauges", "histograms"}
    assert snap["counters"]["serve/cache/hits"] == 3
    # every event was mirrored into the process-global registry
    g = obs_metrics.snapshot()["counters"]
    assert g["serve/cache/hits"] == 3
    assert g["serve/cache/misses"] == 1
    # a second cache adds to the global mirror but keeps its own counts
    s2 = CacheStats()
    s2.hit()
    assert s2.hits == 1 and s.hits == 3
    assert obs_metrics.snapshot()["counters"]["serve/cache/hits"] == 4
    # backing a CacheStats with the global registry must not double-count
    obs_metrics.reset()
    sg = CacheStats(registry=obs_metrics.registry())
    sg.hit()
    assert sg.hits == 1
    assert obs_metrics.snapshot()["counters"]["serve/cache/hits"] == 1


def test_cluster_report_snapshot_and_emit():
    from repro.cluster.executor import ClusterReport, RoundStats

    rounds = [
        RoundStats(0, [2, 1], np.array([10, 20], np.int64),
                   np.array([1.0, 2.0]), 1.5, []),
        RoundStats(1, [1, 0], np.array([5, 0], np.int64),
                   np.array([1.0, 0.0]), 1.2, []),
    ]
    rep = ClusterReport(
        P=2, backend="vmap", rounds=rounds,
        phase_ms={"plan": 1.0, "exchange": 2.0, "mine": 8.0, "merge": 0.5},
        est_loads=np.array([1.0, 2.0]),
        observed_loads=np.array([15.0, 20.0]),
        donations=[], exchange_overflow=0, mine_overflow=0,
    )
    snap = rep.snapshot()
    assert set(snap) == {"counters", "gauges", "histograms"}
    assert snap["counters"]["cluster/rounds"] == 2
    assert snap["gauges"]["cluster/makespan_trips"] == 25.0   # 20 + 5
    assert snap["gauges"]["cluster/imbalance"] == rep.imbalance
    assert snap["gauges"]["cluster/phase_ms/mine"] == 8.0
    for p in range(2):
        assert f"cluster/shard{p}/est_load" in snap["gauges"]
        assert f"cluster/shard{p}/obs_load" in snap["gauges"]
    h = snap["histograms"]["cluster/round_makespan_trips"]
    assert h["count"] == 2 and h["max"] == 20.0 and h["min"] == 5.0
    # emit() replays the same numbers into a registry
    reg = obs_metrics.MetricsRegistry()
    rep.emit(reg)
    got = reg.snapshot()
    assert got["counters"] == snap["counters"]
    assert got["gauges"] == snap["gauges"]
    assert got["histograms"]["cluster/round_makespan_trips"]["count"] == 2


# ---------------------------------------------------------------------------
# Run records + report CLI
# ---------------------------------------------------------------------------


def _make_run(run_dir, wall=2.0, stall_scale=1.0):
    """A synthetic but structurally complete run record."""
    reg = obs_metrics.registry()
    reg.counter("fimi/runs").inc()
    reg.counter("store/blocks_read").inc(8)
    reg.gauge("fimi/n_fis").set(123.0)
    reg.gauge("cluster/phase_ms/mine").set(40.0 * stall_scale)
    h = reg.histogram("store/prefetch_stall_s")
    for v in (0.01, 0.02, 0.03, 0.5):
        h.record(v * stall_scale)
    tr = obs_trace.TRACER
    tr.enable()
    with tr.span("fimi/phase4_mine"):
        pass
    log = runlog.RunLog(str(run_dir), "testrun", {"support": 0.1})
    log.event("round", index=0, trips=[3, 4])
    log.event("round", index=1, trips=np.array([5, 6]))
    log.finish(metrics_snapshot=obs_metrics.snapshot(), tracer=tr,
               mine_wall_s=wall, n_fis=123)
    tr.disable()
    return str(run_dir)


def test_runlog_round_trip(tmp_path):
    d = _make_run(tmp_path / "run")
    run = runlog.load_run(d)
    man = run["manifest"]
    assert man["name"] == "testrun"
    assert man["config"] == {"support": 0.1}
    assert man["mine_wall_s"] == 2.0 and man["n_fis"] == 123
    assert isinstance(man["wall_s"], float)
    assert [e["kind"] for e in run["events"]] == ["round", "round"]
    assert run["events"][1]["trips"] == [5, 6]       # numpy made jsonable
    assert run["events"][0]["t"] <= run["events"][1]["t"]
    assert run["metrics"]["counters"]["fimi/runs"] == 1
    _assert_chrome_trace(run["trace"])
    with pytest.raises(FileNotFoundError):
        runlog.load_run(str(tmp_path / "nope"))


def test_obs_report_summary_and_self_diff(tmp_path, capsys):
    d = _make_run(tmp_path / "run")
    assert obs_report.main(["summary", d]) == 0
    out = capsys.readouterr().out
    assert "testrun" in out and "fimi/runs" in out
    assert "store/prefetch_stall_s" in out and "fimi/phase4_mine" in out
    # a run never regresses against itself
    assert obs_report.main(["diff", d, d]) == 0


def test_obs_report_diff_gates_injected_slowdown(tmp_path, capsys):
    a = _make_run(tmp_path / "a")
    b = str(tmp_path / "b")
    assert obs_report.main(["inject-slowdown", a, b, "--factor", "1.5"]) == 0
    # time-like metrics 1.5x slower: the 20% gate must fail...
    assert obs_report.main(["diff", a, b, "--threshold", "0.2"]) == 1
    out = capsys.readouterr().out
    assert "REGRESSION" in out
    assert "mine_wall_s" in out
    # ...a loose gate passes, and a speedup never gates
    assert obs_report.main(["diff", a, b, "--threshold", "0.6"]) == 0
    assert obs_report.main(["diff", b, a, "--threshold", "0.2"]) == 0
    # non-time metrics (counts, sizes) must never gate even when changed
    assert runlog.load_run(b)["metrics"]["gauges"]["fimi/n_fis"] == 123.0


def test_obs_report_diff_scales_every_time_family(tmp_path):
    a = _make_run(tmp_path / "a")
    b = str(tmp_path / "b")
    obs_report.main(["inject-slowdown", a, b, "--factor", "2.0"])
    ta = obs_report._time_metrics(runlog.load_run(a))
    tb = obs_report._time_metrics(runlog.load_run(b))
    assert set(ta) == set(tb) and len(ta) >= 3   # wall, gauge, hist p95
    for k in ta:
        assert tb[k] == pytest.approx(2.0 * ta[k], rel=1e-6), k


def test_obs_report_baseline_gate(tmp_path):
    bench_ok = tmp_path / "BENCH_ok.json"
    bench_ok.write_text(json.dumps(
        {"obs_overhead_streamed": 1.02, "mine_ms": 120.0}
    ))
    bench_bad = tmp_path / "BENCH_bad.json"
    bench_bad.write_text(json.dumps(
        {"nested": {"checksum_slowdown": 1.4}}
    ))
    assert obs_report.main(
        ["baseline", "--bench", str(bench_ok), "--threshold", "0.05"]
    ) == 0
    assert obs_report.main(
        ["baseline", "--bench", str(bench_bad), "--threshold", "0.05"]
    ) == 1
    # both at once: one bad file fails the whole gate
    assert obs_report.main(
        ["baseline", "--bench", str(bench_ok), "--bench", str(bench_bad)]
    ) == 1
    # --match narrows the gated keys: the bad slowdown key is out of scope
    assert obs_report.main(
        ["baseline", "--bench", str(bench_bad), "--match", "overhead"]
    ) == 0


def test_obs_report_unreadable_record_exits_2(tmp_path):
    with pytest.raises(SystemExit) as e:
        obs_report.main(["summary", str(tmp_path / "missing")])
    assert e.value.code == 2


def test_obs_report_is_jax_free():
    """The layering rule: the report CLI must import without jax."""
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.modules['jax'] = None\n"
         "from repro.launch import obs_report\n"
         "from repro.obs import metrics, runlog\n"
         "print('JAXFREE_OK')"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "PYTHONPATH": "src"}, cwd=str(REPO),
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "JAXFREE_OK" in r.stdout


def test_obs_report_summary_format_json(tmp_path, capsys):
    d = _make_run(tmp_path / "run")
    assert obs_report.main(["summary", d, "--format", "json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["name"] == "testrun"
    assert out["counters"]["fimi/runs"] == 1
    assert out["gauges"]["fimi/n_fis"] == 123.0
    assert out["histograms"]["store/prefetch_stall_s"]["count"] == 4
    assert [e["kind"] for e in out["events"]] == ["round", "round"]
    assert any(s["name"] == "fimi/phase4_mine" for s in out["spans"])


def test_obs_report_summary_format_markdown(tmp_path, capsys):
    d = _make_run(tmp_path / "run")
    assert obs_report.main(["summary", d, "--format", "markdown"]) == 0
    out = capsys.readouterr().out
    assert "### run `testrun`" in out
    assert "| fimi/runs | 1 |" in out
    assert "store/prefetch_stall_s" in out
    assert "fimi/phase4_mine" in out
    # same digest, three renderings: text remains the default
    assert obs_report.main(["summary", d]) == 0
    assert "### run" not in capsys.readouterr().out


# ---------------------------------------------------------------------------
# Crash-safe sessions: a killed run still writes a loadable partial record
# ---------------------------------------------------------------------------

_VICTIM = """\
import sys, time
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.session import ObsSession

s = ObsSession(sys.argv[1], "victim", {"x": 1}, trace_on=True)
obs_metrics.registry().counter("victim/progress").inc(3)
with obs_trace.TRACER.span("victim/work"):
    pass
s.event("tick", n=1)
print("READY", flush=True)
@TAIL@
"""


def _spawn_victim(tmp_path, tail):
    run_dir = tmp_path / "rec"
    proc = subprocess.Popen(
        [sys.executable, "-c", _VICTIM.replace("@TAIL@", tail), str(run_dir)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env={**os.environ, "PYTHONPATH": "src"}, cwd=str(REPO),
    )
    assert proc.stdout.readline().strip() == "READY"
    return run_dir, proc


def _assert_partial_record(run_dir, reason):
    man = json.loads((run_dir / "manifest.json").read_text())
    assert man["name"] == "victim"
    assert man["partial"] is True and man["partial_reason"] == reason
    assert isinstance(man["wall_s"], float)
    metrics = json.loads((run_dir / "metrics.json").read_text())
    assert metrics["counters"]["victim/progress"] == 3
    trace = json.loads((run_dir / "trace.json").read_text())
    _assert_chrome_trace(trace)
    assert "victim/work" in {e["name"] for e in trace["traceEvents"]}
    run = runlog.load_run(str(run_dir))
    assert [e["kind"] for e in run["events"]] == ["tick"]


def test_obs_session_sigterm_flushes_partial_record(tmp_path):
    run_dir, proc = _spawn_victim(tmp_path, "time.sleep(120)")
    proc.terminate()                      # SIGTERM mid-run
    proc.wait(timeout=60)
    # the chained default disposition preserves the conventional kill status
    assert proc.returncode == -15
    _assert_partial_record(run_dir, "sigterm")


def test_obs_session_atexit_flushes_partial_record(tmp_path):
    # the victim falls off the end of the script without calling finish()
    run_dir, proc = _spawn_victim(tmp_path, "pass")
    proc.wait(timeout=60)
    assert proc.returncode == 0
    _assert_partial_record(run_dir, "atexit")


def test_obs_session_finish_seals_and_disarms_crash_hooks(tmp_path):
    from repro.obs.session import ObsSession

    s = ObsSession(str(tmp_path / "rec"), "ok", {}, trace_on=False)
    obs_metrics.registry().counter("ok/n").inc()
    s.finish(n_fis=7)
    man = json.loads((tmp_path / "rec" / "manifest.json").read_text())
    assert "partial" not in man and man["n_fis"] == 7
    # the atexit hook is unregistered: simulating it must not resurrect
    # the partial flag on the sealed record
    s._atexit_flush()
    man = json.loads((tmp_path / "rec" / "manifest.json").read_text())
    assert "partial" not in man


# ---------------------------------------------------------------------------
# Driver smoke: --trace produces a loadable record end to end
# ---------------------------------------------------------------------------


def test_mine_driver_trace_smoke(tmp_path):
    run_dir = tmp_path / "rec"
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.mine",
         "--db", "T0.25I0.016P6PL4TL6", "--support", "0.15", "-P", "2",
         "--trace", str(run_dir)],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "PYTHONPATH": "src"}, cwd=str(REPO),
    )
    assert r.returncode == 0, r.stderr[-3000:]
    trace = json.loads((run_dir / "trace.json").read_text())
    _assert_chrome_trace(trace)
    names = {e["name"] for e in trace["traceEvents"] if e["ph"] == "X"}
    assert {"fimi/phase1_sample", "fimi/phase2_partition",
            "fimi/phase3_exchange", "fimi/phase4_mine"} <= names
    man = json.loads((run_dir / "manifest.json").read_text())
    assert man["name"] == "mine" and "mine_wall_s" in man
    metrics = json.loads((run_dir / "metrics.json").read_text())
    assert metrics["counters"]["fimi/runs"] == 1
    assert metrics["counters"]["fimi/trips"] > 0
    assert "fimi/n_fis" in metrics["gauges"]
    # the loop counts ride on the phase spans, not on registry gauges
    assert not any(k.startswith(("fimi/shard", "fimi/load"))
                   for k in metrics["gauges"])
    assert "fimi/frontier_occupancy" not in metrics["histograms"]
    args = {e["name"]: e.get("args", {}) for e in trace["traceEvents"]
            if e["ph"] == "X"}
    assert args["fimi/phase4_mine"]["trips"] > 0
    assert {"trips", "popped", "offers"} <= set(args["fimi/phase1_sample"])
    assert "probes" in args["fimi/phase2_partition"]
    # the record is diffable against itself through the CLI
    assert obs_report.main(["diff", str(run_dir), str(run_dir)]) == 0


# ---------------------------------------------------------------------------
# Tracer event cap: drop-oldest, dropped-event accounting, truncation note
# ---------------------------------------------------------------------------


def test_tracer_cap_drops_oldest_and_counts():
    tr = obs_trace.Tracer(enabled=True, max_events=5)
    for i in range(8):
        tr.instant(f"ev{i}")
    assert tr.n_events == 5
    assert tr.dropped_events == 3
    out = tr.export()
    names = [e["name"] for e in out["traceEvents"] if e["ph"] == "i"]
    assert names == ["ev3", "ev4", "ev5", "ev6", "ev7"]   # a suffix
    assert out["truncated_events"] == 3
    # the drop is visible as a metric too (the doctor's evidence key)
    snap = obs_metrics.snapshot()
    assert snap["counters"]["trace/dropped_events"] == 3


def test_tracer_uncapped_export_has_no_truncation_note():
    tr = obs_trace.Tracer(enabled=True, max_events=100)
    with tr.span("a"):
        pass
    out = tr.export()
    assert "truncated_events" not in out
    assert tr.dropped_events == 0


def test_tracer_set_max_events_recaps_keeping_newest():
    tr = obs_trace.Tracer(enabled=True, max_events=100)
    for i in range(10):
        tr.instant(f"ev{i}")
    tr.set_max_events(4)
    assert tr.max_events == 4 and tr.n_events == 4
    assert tr.dropped_events == 6
    names = [e["name"] for e in tr.export()["traceEvents"]
             if e["ph"] == "i"]
    assert names == ["ev6", "ev7", "ev8", "ev9"]


def test_tracer_clear_resets_dropped():
    tr = obs_trace.Tracer(enabled=True, max_events=2)
    for i in range(5):
        tr.instant(f"ev{i}")
    assert tr.dropped_events == 3
    tr.clear()
    assert tr.dropped_events == 0 and tr.n_events == 0


# ---------------------------------------------------------------------------
# summary: exclusive self-time via the critpath DAG (one implementation)
# ---------------------------------------------------------------------------


def test_summary_spans_carry_exclusive_self_time(tmp_path, capsys):
    reg = obs_metrics.registry()
    reg.counter("fimi/runs").inc()
    tr = obs_trace.TRACER
    tr.enable()
    with tr.span("outer"):
        with tr.span("inner"):
            pass
    log = runlog.RunLog(str(tmp_path / "run"), "selftime", {})
    log.finish(metrics_snapshot=obs_metrics.snapshot(), tracer=tr)
    tr.disable()

    assert obs_report.main(
        ["summary", str(tmp_path / "run"), "--format", "json"]) == 0
    out = json.loads(capsys.readouterr().out)
    spans = {s["name"]: s for s in out["spans"]}
    assert set(spans) == {"outer", "inner"}
    for s in out["spans"]:
        assert set(s) >= {"name", "total_ms", "self_ms", "count"}
    # the child's time is excluded from the parent's self time
    assert spans["outer"]["self_ms"] == pytest.approx(
        spans["outer"]["total_ms"] - spans["inner"]["total_ms"], abs=0.5)
    assert spans["inner"]["self_ms"] == pytest.approx(
        spans["inner"]["total_ms"])

    # and the markdown table grew the column
    assert obs_report.main(
        ["summary", str(tmp_path / "run"), "--format", "markdown"]) == 0
    assert "self ms" in capsys.readouterr().out

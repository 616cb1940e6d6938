"""SLO-gated load harness (launch/serve_load): healthy run passes the gate
and records windowed SLO keys into BENCH_serve.json, injected overload trips
the burn-rate alert and exits non-zero, micro-batched dispatch beats
per-query dispatch, and --trace yields a per-request Perfetto timeline."""
import json
from pathlib import Path

import pytest

from repro.launch import serve_load
from repro.obs import metrics as obs_metrics
from repro.obs import perfdb
from repro.obs import trace as obs_trace

DB = "T0.25I0.016P6PL4TL6"      # 250 tx, 16 items: serving is under test


@pytest.fixture(autouse=True)
def _history(tmp_path, monkeypatch):
    """Gated runs append to the perf trajectory: keep it out of the tree."""
    path = tmp_path / "BENCH_HISTORY.jsonl"
    monkeypatch.setattr(perfdb, "DEFAULT_PATH", str(path))
    return path


@pytest.fixture(autouse=True)
def _fresh_obs():
    obs_metrics.reset()
    obs_trace.TRACER.disable()
    obs_trace.TRACER.clear()
    yield
    obs_metrics.reset()
    obs_trace.TRACER.disable()
    obs_trace.TRACER.clear()


def _argv(tmp_path, **over):
    base = {
        "--db": DB, "--qps": "150", "--duration": "1.5", "--ramp": "0.5",
        "--window": "1.0", "--report-every": "0.25", "--replicas": "2",
        "--batch": "32", "--deadline-ms": "4.0",
        "--slo-p99-ms": "500", "--availability": "0.99",
        "--bench-out": str(tmp_path / "BENCH_serve.json"),
    }
    base.update({k: str(v) for k, v in over.items()})
    argv = [a for kv in base.items() for a in kv if a != ""]
    return argv + ["--no-dashboard", "--gate"]


def test_healthy_load_passes_gate_and_records_slo_keys(tmp_path, capsys,
                                                       _history):
    rc = serve_load.main(_argv(tmp_path) + ["--compare-dispatch"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "SLO gate: ok" in out
    bench = json.loads((tmp_path / "BENCH_serve.json").read_text())
    for k in ("slo_target_qps", "slo_qps", "slo_p99_ms",
              "slo_p99_objective_ms", "slo_shed_rate", "slo_burn_rate",
              "slo_alerts_fired", "slo_gate_ok"):
        assert k in bench, k
    assert bench["slo_gate_ok"] is True
    assert bench["slo_alerts_fired"] == 0
    assert bench["slo_p99_ms"] is not None
    assert bench["slo_p99_ms"] <= bench["slo_p99_objective_ms"]
    # acceptance: the fused micro-batch sweep beats per-query dispatch
    assert bench["slo_microbatch_speedup"] > 1.0
    # the gated run's trajectory row went to the history it was pointed at
    rows, _ = perfdb.load(str(_history))
    assert [r["suite"] for r in rows] == ["serve_load"]


def test_injected_overload_trips_burn_alert_and_gate(tmp_path, capsys):
    rc = serve_load.main(_argv(
        tmp_path, **{"--qps": "30000", "--max-queue": "32"}))
    cap = capsys.readouterr()
    assert rc == 1, cap.out
    assert "SLO GATE FAILED" in cap.err
    assert "[slo] slo_alert (availability)" in cap.err
    bench = json.loads((tmp_path / "BENCH_serve.json").read_text())
    assert bench["slo_gate_ok"] is False
    # cumulative, not the final-window rate: once the generator stops the
    # service catches up and the windowed shed rate can decay back to zero
    # before the closing evaluate, but the overload must have shed traffic
    # and burned the budget hard enough to fire the availability alert
    # (asserted on stderr above)
    assert bench["slo_shed_total"] > 0
    assert bench["slo_shed_rate"] >= 0.0


def test_trace_run_records_per_request_timeline(tmp_path, capsys):
    run_dir = tmp_path / "rec"
    rc = serve_load.main(_argv(tmp_path) + ["--trace", str(run_dir)])
    assert rc == 0, capsys.readouterr().out
    trace = json.loads((run_dir / "trace.json").read_text())
    spans = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    names = {e["name"] for e in spans}
    assert {"service/enqueue", "service/flush", "service/assemble",
            "service/sweep", "service/respond"} <= names
    # request ids thread the chain: every swept id was enqueued
    enq_ids = {e["args"]["req"] for e in spans
               if e["name"] == "service/enqueue"}
    sweep_ids = {i for e in spans if e["name"] == "service/sweep"
                 for i in e["args"]["reqs"]}
    assert sweep_ids and sweep_ids <= enq_ids
    man = json.loads((run_dir / "manifest.json").read_text())
    assert man["name"] == "serve_load" and "partial" not in man
    assert man["slo_gate_ok"] is True and "slo_p99_ms" in man
    metrics = json.loads((run_dir / "metrics.json").read_text())
    assert metrics["counters"]["service/flushes"] > 0
    assert "service/latency_ms" in metrics["histograms"]


def test_merge_bench_preserves_existing_keys(tmp_path):
    p = tmp_path / "BENCH_serve.json"
    p.write_text(json.dumps({"bench": "serve", "entries": [1, 2],
                             "engine_us": 42.0}))
    serve_load.merge_bench(str(p), {"slo_qps": 99.0})
    d = json.loads(p.read_text())
    assert d["entries"] == [1, 2] and d["engine_us"] == 42.0
    assert d["slo_qps"] == 99.0
    # and a fresh file self-initializes
    p2 = tmp_path / "new.json"
    serve_load.merge_bench(str(p2), {"slo_qps": 1.0})
    assert json.loads(p2.read_text())["bench"] == "serve"


def test_request_errors_exit_nonzero_without_gate(tmp_path, capsys,
                                                  monkeypatch):
    """A failed sweep ends its requests in error; the harness must not
    report success for them, gate or no gate."""
    from repro.serve.service import MiningService

    def broken(self, snap, kind, masks):
        raise RuntimeError("injected dispatch failure")

    monkeypatch.setattr(MiningService, "_dispatch", broken)
    argv = [a for a in _argv(tmp_path, **{"--duration": "0.5",
                                          "--ramp": "0"})
            if a != "--gate"]
    rc = serve_load.main(argv)
    cap = capsys.readouterr()
    assert rc == 1, cap.out
    assert "request(s) ended in error" in cap.err


def test_serve_mine_fails_on_dispatch_error(monkeypatch):
    """serve_mine answers through the engine directly: a failing dispatch
    must end the run with an error, never a clean exit."""
    from repro.launch import serve_mine
    from repro.serve import QueryEngine

    def broken(self, masks, **kw):
        raise RuntimeError("injected dispatch failure")

    monkeypatch.setattr(QueryEngine, "support", broken)
    with pytest.raises(RuntimeError, match="injected dispatch failure"):
        serve_mine.main(["--db", DB, "--support", "0.1", "-P", "2",
                         "--queries", "64", "--batch", "32"])

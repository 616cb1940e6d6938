"""The cluster cell: its readers on synthetic readings whose answers are
known by hand, and whole runs of its runner at a tiny size on four host
devices: correct when the program is sound, not correct under each planted
fault, and a clean failure on a program without ``cluster.mine_store``."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
sys.path.insert(0, str(BENCH_DIR))

import run  # noqa: E402
import trace_reduce as tr  # noqa: E402
from cost import exchange  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
V5E = json.loads((BENCH_DIR / "peaks.json").read_text())["devices"][
    "TPU v5 lite"]
CELL = next(c["name"] for c in BENCH["workloads"]
            if run.resolve(BENCH, c["name"])["traffic"]["runner"]
            == "cluster_mine")
CONFIG = run.resolve(BENCH, CELL)["config"]
NEW = ("cluster_assemble_ms", "cluster_plan_ms", "cluster_exchange_ms",
       "cluster_mine_ms", "cluster_imbalance", "exchange_replication",
       "exchange_a2a_ms", "exchange_ici_roofline")


def span(name, ts_us, dur_us, cat="host", **args):
    ev = {"ph": "X", "name": name, "cat": cat, "ts": ts_us, "dur": dur_us}
    if args:
        ev["args"] = args
    return ev


# two traced mines of P = 4 miners: two rounds, then one
SPANS = [
    span("cluster/run", 0.0, 9_000.0, mine=3, P=4),
    span("cluster/plan", 0.0, 1_000.0, mine=3, P=4),
    span("cluster/assemble", 1_000.0, 2_000.0, mine=3, P=4),
    span("cluster/exchange", 3_000.0, 500.0, mine=3, round=0,
         rows_moved=1_000, replication=1.5, overflow=0),
    span("cluster/mine", 3_500.0, 2_000.0, mine=3, round=0,
         trips=[4, 2, 2, 0], donations=1),
    span("cluster/mine", 3_500.0, 2_000.0, cat="modeled", trips=4),
    span("cluster/exchange", 5_500.0, 500.0, mine=3, round=1,
         rows_moved=3_000, replication=2.5, overflow=0),
    span("cluster/mine", 6_000.0, 2_500.0, mine=3, round=1,
         trips=[3, 3, 3, 3], donations=0),
    span("cluster/merge", 8_500.0, 500.0, mine=3),
    span("cluster/run", 20_000.0, 5_000.0, mine=4, P=4),
    span("cluster/plan", 20_000.0, 1_000.0, mine=4, P=4),
    span("cluster/assemble", 21_000.0, 1_000.0, mine=4, P=4),
    span("cluster/exchange", 22_000.0, 1_000.0, mine=4, round=0,
         rows_moved=2_000, replication=1.0, overflow=0),
    span("cluster/mine", 23_000.0, 1_500.0, mine=4, round=0,
         trips=[1, 1, 1, 5], donations=0),
    span("cluster/merge", 24_500.0, 500.0, mine=4),
]
SPAN_WANT = {
    "cluster_plan_ms": 1.0,              # 2 ms over 2 mines
    "cluster_assemble_ms": 1.5,
    "cluster_exchange_ms": 1.0,
    "cluster_mine_ms": 3.0,              # host spans only: 6 ms / 2
    "cluster_imbalance": 12.0 / 7.0,     # (4 + 3 + 5) / (2 + 3 + 2)
    "exchange_replication": 1.5,         # mean(2.0, 1.0)
}


def reading(spans, device=None, mines=2):
    return tr.Reading(spans=spans, device=device or tr.Segments([]),
                      layer_data={"mines": mines}, config=CONFIG, traffic={},
                      peaks=V5E)


def reader(name):
    return run.load_module(BENCH_DIR / "metrics" / f"{name}.py")


def test_the_cell_and_its_metrics_are_declared():
    cell = next(c for c in BENCH["workloads"] if c["name"] == CELL)
    assert cell["chips"] == 4 and cell["traffic"] == "cluster_repeat"
    layer = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW:
        assert layer[name]["workloads"] == [CELL]
        assert layer[name]["moves"] == "mine_s"
    for name in ("device_idle.mine", "multi_support_roofline"):
        assert layer[name]["workloads"][-1] == CELL
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["mine_s"]["workloads"][-1] == CELL
    spec = run.resolve(BENCH, CELL)
    assert {m["name"] for m in spec["per_layer"]} >= set(NEW)
    assert CONFIG["dataset"]["n_tx"] == 1_000_000 and CONFIG["reduced"] == []


@pytest.mark.parametrize("name", sorted(SPAN_WANT))
def test_span_reader_value(name):
    assert reader(name).read(reading(SPANS)) == pytest.approx(SPAN_WANT[name])


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_without_the_cluster_spans(name):
    fimi_only = [span("fimi/run", 0.0, 5.0, mine=1),
                 span("fimi/phase3_exchange", 1.0, 2.0, mine=1, C=9)]
    assert reader(name).read(reading(fimi_only)) is None
    assert reader(name).read(reading([])) is None


# the exchange's all-to-all as the v5e compiler names it (a 2x2 compile)
A2A = ("%all_to_all.9 = u32[4,250000,32]{1,2,0:T(8,128)} all-to-all("
       "%copy.28), channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}")
# window 0..10,000 ns; the program's spans map onto it from ts 0 (us)
EX_SPANS = [
    span("cluster/exchange", 1.0, 2.0, mine=1, rows_moved=1_000,
         replication=1.2),
    span("cluster/mine", 3.0, 4.0, mine=1, trips=[1, 1]),
    # past the recorded window: its rows do not count
    span("cluster/exchange", 20.0, 2.0, mine=1, rows_moved=7_000,
         replication=1.2),
]


def two_chips(window=(0.0, 10_000.0)):
    planes = [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ("chipbench.window", window[0], window[1] - window[0])]}]},
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            (A2A, 1_000.0, 400.0),
            ("%fusion.2 = u32[4]{0} fusion(%all_to_all.9)", 1_500.0, 100.0),
            ("%psum.7 = s32[1,1000]{1,0} all-reduce(...)", 2_000.0, 100.0),
            ("%while.1 = s32[]{:T(128)} while(...)", 3_000.0, 4_000.0)]}]},
        {"name": "/device:TPU:1", "lines": [{"name": "XLA Ops", "events": [
            ("%all-to-all-start.1 = (u32[1,8]{1,0}) all-to-all-start(...)",
             1_000.0, 200.0),
            ("%all-to-all-done.1 = u32[1,8]{1,0} all-to-all-done(...)",
             1_300.0, 400.0)]}]},
    ]
    return tr.Segments([tr.reduce(planes, 2, EX_SPANS, 0.0)])


def test_exchange_ops_are_the_all_to_alls():
    assert exchange.is_exchange_op(tr.op_label(A2A))
    assert exchange.is_exchange_op("%all-to-all-start.1 = (u32[1,8]")
    assert not exchange.is_exchange_op("%fusion.2 = u32[4]")
    assert not exchange.is_exchange_op("%psum.7 = s32[1,1000]")
    assert exchange.row_bytes(CONFIG) == 128
    # chip 0: 400 ns; chip 1: 200 + 400 ns; averaged over the two chips
    assert exchange.a2a_ns_per_chip(two_chips()) == pytest.approx(500.0)


def test_a2a_time_and_ici_roofline():
    r = reading(EX_SPANS, device=two_chips(), mines=1)
    assert reader("exchange_a2a_ms").read(r) == pytest.approx(500.0 / 1e6)
    # 1,000 rows of 128 B over 2 chips in 500 ns: 1.024e12 of 1.6e12 bit/s
    want = 100.0 * (1_000 * 128 * 8 / 2) / V5E["ici_bits_per_s"] / 500e-9
    assert want == pytest.approx(64.0)
    assert reader("exchange_ici_roofline").read(r) == pytest.approx(want)


def test_roofline_counts_only_what_the_tracer_kept():
    """A window cut before the exchange leaves neither bytes nor time."""
    r = reading(EX_SPANS, device=two_chips(window=(0.0, 900.0)), mines=1)
    assert reader("exchange_a2a_ms").read(r) is None
    assert reader("exchange_ici_roofline").read(r) is None


# ---------------------------------------------------------------------------
# Whole runs at a tiny size on four host devices
# ---------------------------------------------------------------------------

_RUNS = r"""
import json, os, sys, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
bench_dir, cache = sys.argv[1], sys.argv[2]
sys.path.insert(0, bench_dir)
import jax.numpy as jnp
import run, trace_reduce
from repro import cluster
from repro.core import phases

bench = json.load(open(os.path.join(bench_dir, "..", "BENCHMARK.json")))
cell = next(c["name"] for c in bench["workloads"]
            if run.resolve(bench, c["name"])["traffic"]["runner"]
            == "cluster_mine")


def tiny():
    spec = run.resolve(bench, cell)
    cfg = spec["config"]
    cfg["name"] = "tiny"
    cfg["dataset"].update(n_tx=2048, n_items=48, n_patterns=20,
                          avg_pattern_len=4, avg_tx_len=8, pattern_seed=3,
                          block_tx=512)
    cfg["minsup"] = 0.05
    cfg["mining"].update(n_db_sample=512, n_fi_sample=256, max_out=4096,
                         max_stack=1024, frontier_size=8)
    return spec


real_mine, real_exchange = cluster.mine_store, phases.phase3_exchange


def support_altered(*a, **k):
    res = real_mine(*a, **k)
    res.table.supports[0] += 1
    return res


def exchange_loses_rows(local_tx, local_valid, *a, **k):
    # every miner drops the rows the first miner sent it
    out = real_exchange(local_tx, local_valid, *a, **k)
    cap = out.slab_valid.shape[0] // 4
    return out._replace(slab_valid=out.slab_valid.at[:cap].set(False))


out = {}
for case, fault in [("sound", None), ("support-altered", support_altered),
                    ("exchange-loses-rows", exchange_loses_rows)]:
    cluster.mine_store = support_altered if fault is support_altered \
        else real_mine
    phases.phase3_exchange = exchange_loses_rows \
        if fault is exchange_loses_rows else real_exchange
    res = run.run_cell(tiny(), 2**31 + 7, 1.0, False, cache=cache,
                       t_start=time.perf_counter())
    res.pop("_info")
    out[case] = res
cluster.mine_store, phases.phase3_exchange = real_mine, real_exchange

spec = tiny()
ctx = run.Context(spec, 2**40 + 9, 1.0, True, run.Path(cache),
                  time.perf_counter())
run.load_module(run.HERE / "runners" / "cluster_mine.py").run(ctx)
r = trace_reduce.Reading(spans=ctx.spans, device=trace_reduce.Segments([]),
                         layer_data=ctx.layer_data, config=spec["config"],
                         traffic=spec["traffic"], peaks={})
out["traced"] = {
    "mines": ctx.layer_data["mines"], "segments": len(ctx.segments),
    "correct": ctx.checks["mismatched_itemsets"]["value"] == 0,
    "metrics": {m["name"]: run.load_module(
        run.HERE / "metrics" / f"{m['name']}.py").read(r)
        for m in spec["per_layer"] if m["source"] == "program_span"}}
print("CLUSTER_RUNS " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    r = subprocess.run(
        [sys.executable, "-c", _RUNS, str(BENCH_DIR),
         str(tmp_path_factory.mktemp("cluster_cache"))],
        capture_output=True, text=True, timeout=900, cwd=str(ROOT),
        env={**os.environ, "PYTHONPATH": str(ROOT / "src"),
             "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-3000:]
    line = next(x for x in r.stdout.splitlines()
                if x.startswith("CLUSTER_RUNS "))
    return json.loads(line[len("CLUSTER_RUNS "):])


@pytest.mark.parametrize("case", ["sound", "support-altered",
                                  "exchange-loses-rows"])
def test_cluster_run_is_correct_only_when_sound(runs, case):
    res = runs[case]
    assert res["correct"] is (case == "sound"), res["checks"]
    assert res["attempted"] > 0 and res["attempted"] % 2 == 0
    assert set(res["metrics"]) == {"mine_s", "setup_s"}
    assert res["device"]["count"] == 4


def test_traced_run_profiles_a_whole_mine_and_reads_the_spans(runs):
    got = runs["traced"]
    assert got["correct"] and got["mines"] == got["segments"] == 1
    span_metrics = {n for n in NEW if n.startswith("cluster_")} | {
        "exchange_replication"}
    assert span_metrics <= set(got["metrics"])
    for name in span_metrics:
        assert got["metrics"][name] is not None and got["metrics"][name] > 0
    assert got["metrics"]["cluster_imbalance"] >= 1.0
    assert got["metrics"]["exchange_replication"] >= 1.0


def test_a_program_without_mine_store_fails_before_making_data(
        tmp_path, monkeypatch):
    """The parent of this cell's program cannot run it: the runner stops at
    its import of ``cluster.mine_store``, before any snapshot is made."""
    import time

    import repro.cluster

    monkeypatch.delattr(repro.cluster, "mine_store")
    with pytest.raises(ImportError):
        run.run_cell(run.resolve(BENCH, CELL), 2**31 + 1, 1.0, False,
                     cache=tmp_path, t_start=time.perf_counter())
    assert list(tmp_path.iterdir()) == []

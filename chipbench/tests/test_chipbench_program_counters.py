"""The readers of the program's span args and counted spans, on synthetic
readings whose answers are known by hand; each finds nothing to read in a
program that does not record them."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
sys.path.insert(0, str(BENCH_DIR))

import run  # noqa: E402
import trace_reduce as tr  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
V5E = json.loads((BENCH_DIR / "peaks.json").read_text())["devices"][
    "TPU v5 lite"]


def span(name, dur_us, **args):
    ev = {"ph": "X", "name": name, "cat": "host", "ts": 0.0, "dur": dur_us}
    if args:
        ev["args"] = args
    return ev


# two traced mines, P = 4 miners, K = 16 lanes, I = 1,000 items
COUNTED = [
    span("fimi/run", 20_000_000.0, mine=7),
    span("fimi/phase1_sample", 9_000_000.0, mine=7, P=4, variant="reservoir",
         trips=1_000, popped=32_000, offers=640_000, K=16, I=1_000),
    span("fimi/phase2_partition", 400_000.0, mine=7, probes=30),
    span("fimi/phase2_probe", 2_000.0, mine=7),
    span("fimi/phase2_probe", 3_000.0, mine=7),
    span("fimi/phase4_mine", 1_000_000.0, mine=7, P=4, K=16, trips=500,
         popped=16_000),
    span("jax/compile", 1_500.0, fun="jit(mine_seeded)"),
    span("fimi/run", 10_000_000.0, mine=8),
    span("fimi/phase1_sample", 3_000_000.0, mine=8, P=4, variant="reservoir",
         trips=1_000, popped=64_000, offers=0, K=16, I=1_000),
    span("fimi/phase2_partition", 200_000.0, mine=8, probes=10),
    span("fimi/phase4_mine", 600_000.0, mine=8, P=4, K=16, trips=1_500,
         popped=48_000),
]
# the same mines as a program without the counters records them
PLAIN = [span("fimi/phase1_sample", 9_000_000.0, P=4, variant="reservoir"),
         span("fimi/phase2_partition", 400_000.0, scheduler="lpt"),
         span("fimi/phase4_mine", 1_000_000.0, Cmax=3, A=9)]

WANT = {
    "phase1_trips": 1_000.0,                       # (1,000 + 1,000) / 2
    "phase1_trip_us": 6_000.0,                     # 12 s / 2,000 trips
    "phase1_lane_fill": 100.0 * 96_000 / 128_000,  # popped / (P·trips·K)
    "phase1_reservoir_fill": 100.0 * 640_000 / 128_000_000,
    "phase2_probes": 20.0,
    "phase2_probe_ms": 2.5,                        # 5 ms / 2 mines
    "phase4_trips": 1_000.0,
    "phase4_trip_us": 800.0,                       # 1.6 s / 2,000 trips
    "phase4_lane_fill": 100.0 * 64_000 / 128_000,
    "compile_ms": 0.75,
}

PER_MINE = {"phase1_trips", "phase2_probes", "phase2_probe_ms", "phase4_trips",
            "compile_ms"}


def reading(spans, mines=2):
    dev = tr.Segments([])
    return tr.Reading(spans=spans, device=dev, layer_data={"mines": mines},
                      config={}, traffic={}, peaks=V5E)


def reader(name):
    return run.load_module(BENCH_DIR / "metrics" / f"{name}.py")


def test_every_new_metric_is_declared_for_both_mine_cells():
    layer = {m["name"]: m for m in BENCH["per_layer"]}
    for name in WANT:
        m = layer[name]
        assert m["source"] == "program_span" and m["moves"] == "mine_s"
        assert m["workloads"] == ["mine.t10i4", "mine.t40i10"]
    assert layer["compile_ms"]["layer"] == "set-up"


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_value(name):
    assert reader(name).read(reading(COUNTED)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_finds_nothing_without_the_counters(name):
    assert reader(name).read(reading(PLAIN)) is None
    assert reader(name).read(reading([])) is None
    if name in PER_MINE:
        assert reader(name).read(reading(COUNTED, mines=0)) is None


def test_nothing_counted_reads_zero_where_the_program_counts():
    quiet = [ev for ev in COUNTED
             if ev["name"] not in ("jax/compile", "fimi/phase2_probe")]
    assert reader("compile_ms").read(reading(quiet)) == 0.0
    assert reader("phase2_probe_ms").read(reading(quiet)) == 0.0


def test_trip_time_times_trips_is_the_phase_time():
    r = reading(COUNTED)
    for phase in ("phase1", "phase4"):
        ms = reader(f"{phase}_ms").read(r)
        trips = reader(f"{phase}_trips").read(r)
        trip_us = reader(f"{phase}_trip_us").read(r)
        assert trips * trip_us / 1e3 == pytest.approx(ms)

"""The trace reduction and the kernels' byte counts, on small traces whose
answers are known by hand, and on op names recorded from a v5e trace."""
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
from cost import hlo, multi_support  # noqa: E402

PEAKS = json.loads((BENCH_DIR / "peaks.json").read_text())["devices"]
V5E = PEAKS["TPU v5 lite"]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: json.loads((ROOT / c["file"]).read_text())
           for c in BENCH["configs"]}
T10 = CONFIGS["quest_t10i4d100k"]

# op names as a TPU v5e trace records them (one mine of the T10
# configuration)
PHASE1_SWEEP = (
    '%multi_extension_supports_pallas.10 = s32[4,16,1024]{2,1,0:T(8,128)S(1)}'
    ' custom-call(u32[4,16,128]{2,1,0:T(8,128)S(1)} %pad.100, u32[1024,128]'
    '{1,0:T(8,128)S(1)} %pad.101), custom_call_target="tpu_custom_call", '
    'operand_layout_constraints={u32[4,16,128]{2,1,0}, u32[1024,128]{1,0}}, '
    'frontend_attributes={kernel_metadata={}}')
PHASE4_SWEEP = (
    '%multi_extension_supports_pallas.3 = s32[4,16,1024]{2,1,0} custom-call('
    'u32[4,16,3328]{2,1,0} %pad.7, u32[4,1024,3328]{2,1,0} %pad.8), '
    'custom_call_target="tpu_custom_call"')
FUSION = "%fusion.1 = u32[4]{0} fusion(...)"


def planes(ops, host=(), window=(0.0, 1000.0), traceme=()):
    host_events = [("chipbench.window", window[0], window[1] - window[0])]
    host_events += list(host)
    return [
        {"name": "/host:CPU", "lines": [{"name": "python",
                                          "events": host_events}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA TraceMe", "events": list(traceme)}]},
        {"name": "/device:TPU:1", "lines": [{"name": "XLA Ops", "events": [
            ("other-chip", 0.0, 1000.0)]}]},
    ]


def test_union_of_intervals():
    chip = tr._chip([("a", 0.0, 10.0), ("b", 5.0, 10.0), ("c", 20.0, 10.0),
                     ("d", 30.0, 1.0)], (0.0, 100.0))
    s, e = chip.merged()
    assert list(s) == [0.0, 20.0] and list(e) == [15.0, 31.0]


def test_busy_idle_and_window():
    ops = [("a", 100.0, 200.0), ("b", 200.0, 200.0), ("c", 600.0, 100.0),
           ("late", 1100.0, 100.0), ("early", -50.0, 100.0)]
    dev = tr.reduce(planes(ops), n_chips=1)
    assert dev.window_s == pytest.approx(1e-6)
    # [0,50] + [100,400] + [600,700] inside the window; chip 1 not the cell's
    assert dev.busy_s == pytest.approx(450e-9)
    assert dev.idle_share() == pytest.approx(0.55)
    gs, ge = dev.gaps(0)
    assert list(zip(gs, ge)) == [(50.0, 100.0), (400.0, 600.0),
                                 (700.0, 1000.0)]


def test_a_loop_event_covers_its_body():
    ops = [("%while.1 = while(...)", 0.0, 800.0), ("body", 100.0, 50.0)]
    assert tr.reduce(planes(ops), 1).busy_s == pytest.approx(800e-9)


def test_busy_is_averaged_over_the_cells_chips():
    dev = tr.reduce(planes([("a", 0.0, 500.0)]), n_chips=2)
    assert dev.busy_s == pytest.approx((500e-9 + 1000e-9) / 2)


def test_dropped_buffers_cut_the_window_short():
    drop = [("Trace Buffers Dropped", 600.0, 5000.0)]
    dev = tr.reduce(planes([("a", 0.0, 300.0), ("b", 700.0, 100.0)],
                           traceme=drop), 1)
    assert dev.window == (0.0, 600.0)
    assert dev.busy_s == pytest.approx(300e-9)
    assert dev.dropped_ns == 400.0


def test_kernel_events_and_time():
    ops = [(FUSION, 0.0, 100.0), (PHASE1_SWEEP, 100.0, 50.0),
           (PHASE4_SWEEP, 200.0, 30.0)]
    dev = tr.reduce(planes(ops), n_chips=1)
    got = dev.events(multi_support.MATCH)
    assert [(s, e) for _, s, e in got] == [(100.0, 150.0), (200.0, 230.0)]
    assert dev.events("fusion") == []       # only Pallas calls are kernels


def test_idle_gaps_are_labelled_by_what_the_host_did():
    ops = [("a", 0.0, 100_000.0), ("b", 500_000.0, 500_000.0),
           ("c", 1_000_100.0, 100.0)]
    host = [("chipbench.mine.0", 0.0, 2_000_000.0)]
    # the program's span clock started 250 us before the window opened
    spans = [{"name": "fimi/phase2_partition", "cat": "host",
              "ts": 300.0, "dur": 400.0},
             {"name": "progress/lane", "cat": "modeled",
              "ts": 300.0, "dur": 400.0}]
    dev = tr.reduce(planes(ops, host, window=(0.0, 2_000_000.0)), 1, spans,
                    span_offset_s=250e-6)
    assert dev.label(150_000.0) == "fimi/phase2_partition"
    assert dev.label(20_000.0) == "mine.0"
    bd = tr.Segments([dev]).breakdown()
    assert bd["idle_gaps"] == [["mine.0", 999_800e-9],
                               ["fimi/phase2_partition", 400_000e-9],
                               [tr.SHORT_GAPS, 100e-9]]
    assert bd["device_ops"][0] == ["b", 500_000e-9]


def test_segments_add_up():
    """Two profiler sessions of one run: windows, busy time, kernel events
    and the breakdown add up; each keeps its own clock."""
    a = tr.reduce(planes([("x", 0.0, 300.0), (PHASE1_SWEEP, 400.0, 100.0)],
                         window=(0.0, 1000.0)), 1)
    b = tr.reduce(planes([("x", 5.0, 100.0)], window=(0.0, 500.0)), 1)
    dev = tr.Segments([a, b])
    assert dev.window_s == pytest.approx(1500e-9)
    assert dev.busy_s == pytest.approx(500e-9)
    assert dev.idle_share() == pytest.approx(2 / 3)
    assert len(dev.events(multi_support.MATCH)) == 1
    bd = dev.breakdown()
    assert bd["device_ops"][0] == ["x", pytest.approx(400e-9)]
    assert sum(v for _, v in bd["idle_gaps"]) == pytest.approx(1000e-9)


def test_a_segment_starts_at_the_programs_span(tmp_path):
    """A segment that starts at a span of the program opens its profiler
    session as the program enters it, and leaves the tracer as it was."""
    from repro.obs import trace as obs_trace

    spec = run.resolve(BENCH, BENCH["workloads"][0]["name"])
    ctx = run.Context(spec, 1, 1.0, True, tmp_path, 0.0)
    tracer = obs_trace.TRACER
    with ctx.window():
        with ctx.segment("fimi/phase2_partition"):
            with tracer.span("fimi/phase1_sample"):
                assert not ctx.segments
            with tracer.span("fimi/phase2_partition"):
                pass
        assert "span" not in tracer.__dict__
    (path, offset), = ctx.segments
    assert 0.0 < offset < 60.0
    dev = tr.load(path, 0, offset, ctx.spans)
    assert dev.window_s > 0
    names = [name for _, _, name in dev.labels]
    assert "fimi/phase2_partition" in names
    assert not tracer.enabled


def test_hbm_roofline_share():
    ops = [(PHASE4_SWEEP, 0.0, 1000.0), (PHASE4_SWEEP, 2000.0, 1000.0)]
    dev = tr.reduce(planes(ops, window=(0.0, 4000.0)), n_chips=1)
    r = tr.Reading(spans=[], device=tr.Segments([dev]), layer_data={},
                   config={}, traffic={}, peaks=V5E)
    # two calls, each needing 819 bytes: 2 ns at 819 GB/s over 2,000 ns
    assert r.hbm_roofline(multi_support.MATCH, lambda name: 819) == \
        pytest.approx(0.1)
    assert r.hbm_roofline("no_such_kernel", lambda name: 819) is None


def test_peaks_are_published_v5e_figures():
    assert V5E["hbm_bytes_per_s"] == 819e9
    assert V5E["bf16_flops_per_s"] == 197e12
    assert V5E["int8_ops_per_s"] == 393e12


def test_shapes_of_a_recorded_kernel_event():
    assert hlo.shapes(PHASE1_SWEEP) == (
        [("s32", [4, 16, 1024])], [("u32", [4, 16, 128]), ("u32", [1024, 128])])
    assert hlo.unpad(1024, [4, 16, 1000, 64, 3125]) == 1000
    assert hlo.unpad(128, [4, 16, 1000, 64, 3125]) == 64
    assert hlo.unpad(3, [4, 16]) == 3


def test_multi_support_bytes_by_hand():
    assert multi_support.logical_dims(T10) == [4, 16, 1000, 64, 3125]
    # Phase 1: 4 miners x 16 prefixes over the shared 2,048-row sample
    want1 = 4 * (4 * 16 * 64 + 1000 * 64 + 4 * 16 * 1000)
    assert multi_support.event_bytes(PHASE1_SWEEP, T10) == want1 == 528_384
    # Phase 4: each miner's own 100,000-row slab (3,125 words)
    want4 = 4 * 4 * (1000 * 3125 + 16 * 3125 + 16 * 1000)
    assert multi_support.event_bytes(PHASE4_SWEEP, T10) == want4 \
        == multi_support.least_bytes(4, 16, 1000, 3125) == 51_056_000


def test_span_metrics_per_mine():
    dev = tr.reduce(planes([]), n_chips=1)
    spans = [{"name": "fimi/phase1_sample", "ts": 0, "dur": 3000.0},
             {"name": "fimi/phase1_sample", "ts": 5, "dur": 1000.0}]
    r = tr.Reading(spans=spans, device=tr.Segments([dev]),
                   layer_data={"mines": 2}, config={}, traffic={}, peaks=V5E)
    metrics = BENCH_DIR / "metrics"
    assert run.load_module(metrics / "phase1_ms.py").read(r) == 2.0
    assert run.load_module(metrics / "phase2_ms.py").read(r) is None
    assert run.load_module(metrics / "device_idle.mine.py").read(r) == 100.0

"""BENCHMARK.json: names, units and limits, and that everything a cell needs
is found by name, so a new cell, traffic or metric is new files only."""
from __future__ import annotations

import json
import math
import re
import shutil
import sys
from pathlib import Path

import pytest

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
sys.path.insert(0, str(BENCH_DIR))

import run  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [c["name"] for c in BENCH["workloads"]]


def one_line(s: str) -> bool:
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(BENCH)) <= 64 * 1024
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    cells = len(BENCH["workloads"])
    # a full check: 2 + 14 runs a cell, run + 60 s each, 180 s compile a
    # cell, 1200 s spare, within 43200 s even at 24 cells
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    assert 1 <= cells <= 24
    assert sum(c["chips"] == 4 for c in BENCH["workloads"]) <= max(
        1, cells // 2)


def test_command_and_paths():
    cmd, paths = BENCH["command"], BENCH["paths"]
    assert 1 <= len(cmd) <= 32 and all(one_line(w) for w in cmd)
    assert 1 <= len(paths) <= 16
    for p in paths:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert (ROOT / p).is_dir()
    for word in cmd[1:]:
        if "/" in word or word.endswith(".py"):
            assert any(word == p or word.startswith(p + "/") for p in paths)


@pytest.mark.parametrize("section", ["configs", "workloads", "end_to_end",
                                     "per_layer"])
def test_names_are_unique_and_well_formed(section):
    names = [e["name"] for e in BENCH[section]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.fullmatch(n), n


def test_entry_keys():
    keys = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
    }
    for section, want in keys.items():
        for e in BENCH[section]:
            assert set(e) - {"workloads"} == want, (section, e["name"])


def test_metrics():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.fullmatch(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        for c in m.get("workloads", []):
            assert c in CELLS
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert one_line(m["layer"])
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%" and m["better"] == "higher"
    assert all(len(v) == 1 for v in layers.values())


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_are_found_by_name(cell):
    spec = run.resolve(BENCH, cell)
    c = spec["cell"]
    assert c["chips"] in (1, 4) and one_line(c["why"])
    assert spec["config"]["name"] == c["config"]
    assert (BENCH_DIR / "runners" / f"{spec['traffic']['runner']}.py"
            ).is_file()
    for m in spec["per_layer"]:
        mod = run.load_module(BENCH_DIR / "metrics" / f"{m['name']}.py")
        assert callable(mod.read)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_setup_another_metric_and_a_layer(cell):
    spec = run.resolve(BENCH, cell)
    names = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert spec["per_layer"]


def test_per_layer_cells_report_what_they_move():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", CELLS):
            assert cell in moved.get("workloads", CELLS), (m["name"], cell)


def test_configs():
    seen = set()
    for c in BENCH["configs"]:
        assert one_line(c["source"]) and one_line(c["why"])
        assert c["file"] not in seen
        seen.add(c["file"])
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert "assumed" in cfg and "guarantee" in cfg
        ds = cfg["dataset"]
        assert ds["n_tx"] % cfg["mining"]["P"] == 0
        assert math.ceil(cfg["minsup"] * ds["n_tx"]) >= 1
        assert any(c["config"] == cfg["name"] for c in BENCH["workloads"])


def test_a_new_cell_traffic_and_metric_are_new_files_only(tmp_path):
    """Copy the benchmark, add a traffic file, a metric file and entries:
    the harness resolves the new cell without any file of it edited."""
    shutil.copytree(BENCH_DIR, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "chipbench").rglob("*")
              if p.is_file()}
    traffic = json.loads((BENCH_DIR / "traffic" / "mine_repeat.json")
                         .read_text())
    traffic["work"] = traffic["work"][:1]
    (tmp_path / "chipbench" / "traffic" / "mine_one_key.json").write_text(
        json.dumps(traffic))
    (tmp_path / "chipbench" / "metrics" / "plan_count.py").write_text(
        "def read(r):\n    return None\n")
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "mine.one_key", "config":
                               "quest_t10i4d100k", "traffic": "mine_one_key",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "plan_count", "unit": "1",
                               "better": "lower", "source": "host_clock",
                               "layer": "sample planner", "moves": "mine_s",
                               "workloads": ["mine.one_key"]})
    for m in bench["end_to_end"]:
        if m["name"] == "mine_s":
            m["workloads"].append("mine.one_key")
    spec = run.resolve(bench, "mine.one_key", root=tmp_path)
    assert len(spec["traffic"]["work"]) == 1
    assert [m["name"] for m in spec["per_layer"]] == ["plan_count"]
    assert {m["name"] for m in spec["end_to_end"]} == {"mine_s", "setup_s"}
    for p, body in before.items():
        assert p.read_bytes() == body, p


def test_no_code_names_a_cell():
    """Cells, configurations and metrics are data: no harness code file
    mentions one by name."""
    names = CELLS + [c["name"] for c in BENCH["configs"]]
    for path in BENCH_DIR.rglob("*.py"):
        if "tests" in path.parts:
            continue
        text = path.read_text()
        for n in names:
            assert n not in text, (path, n)

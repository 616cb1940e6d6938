#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell names its
configuration (``chipbench/configs/<config>.json``) and its traffic
(``chipbench/traffic/<traffic>.json``); the traffic file names the runner
(``chipbench/runners/<runner>.py``) that drives it; each per-layer metric is
read by ``chipbench/metrics/<metric>.py``.  A new cell, configuration,
traffic mix or metric is new files and entries, never an edit.

With ``--trace 0`` the line carries the cell's end-to-end metrics; with
``--trace 1`` the runner profiles segments of a short window, each in a
profiler session of its own, and the line carries the per-layer metrics,
``busy_s``/``window_s`` summed over the segments, and a breakdown.
The numbers that decide ``correct`` are printed beside their limits, as
the last lines on standard error and under ``checks``, the line's last key.

Exits 2, printing no result, when JAX finds no TPU or fewer chips than the
cell asks for, or when the program is not beside this directory.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

import data  # noqa: E402
import trace_reduce  # noqa: E402


def load_module(path: Path):
    """Import a file by path (metric and runner files are named by cells)."""
    name = f"chipbench_{path.parent.name}_{path.stem}".replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def resolve(bench: dict, workload: str, root: Path = ROOT) -> dict:
    """The cell, its configuration and traffic, and its metrics, by name."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((root / entry["file"]).read_text())
    here = root / HERE.name
    traffic = json.loads(
        (here / "traffic" / f"{cell['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if workload in m.get("workloads", [workload])
             and m["moves"] in reported]
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": e2e, "per_layer": layer, "dir": here}


class Window:
    """The measured window.  Under tracing the program's host spans are
    recorded across it, and the runner profiles segments of it."""

    def __init__(self, ctx: "Context"):
        self.ctx = ctx
        self.t0 = self.t1 = None

    def __enter__(self):
        if self.ctx.trace:
            from repro.obs import trace as obs_trace

            shutil.rmtree(self.ctx.trace_dir, ignore_errors=True)
            obs_trace.TRACER.clear()
            self.mono0 = time.monotonic()   # the program's span clock at 0
            obs_trace.TRACER.enable()
        self.t0 = time.perf_counter()
        return self

    def closed(self) -> bool:
        return time.perf_counter() - self.t0 >= self.ctx.seconds

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        if self.ctx.trace:
            from repro.obs import trace as obs_trace

            obs_trace.TRACER.disable()
            self.ctx.spans = [
                e for e in obs_trace.TRACER.export()["traceEvents"]
                if e.get("ph") == "X"]
        return False


class Context:
    """What a runner needs from the harness, and what it reports back."""

    def __init__(self, spec: dict, seed: int, seconds: float, trace: bool,
                 cache: Path, t_start: float):
        self.cell, self.config = spec["cell"], spec["config"]
        self.traffic = spec["traffic"]
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.cache, self.t_start = cache, t_start
        self.trace_dir = cache / "trace" / self.cell["name"]
        self.metrics, self.checks, self.infos = {}, {}, {}
        self.layer_data: dict = {}
        self.spans: list = []
        self.segments: list = []    # (trace directory, span clock offset)
        self.attempted = self.failed = 0
        self.memory_peak_bytes = None
        self.window_ = None

    def setup_done(self) -> None:
        self.metric("setup_s", time.perf_counter() - self.t_start, "s")

    def window(self) -> Window:
        self.window_ = Window(self)
        return self.window_

    @contextlib.contextmanager
    def segment(self, start_span: str | None = None):
        """Profile one segment of the traced window: from here, or from the
        program's entry to its span ``start_span``, to the end of the block.
        Each segment is a profiler session of its own, so each gets the
        device tracer's whole buffer."""
        import jax

        from repro.obs import trace as obs_trace

        out = self.trace_dir / f"segment{len(self.segments)}"
        started = []

        def start():
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(out), profiler_options=opts)
            ann = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
            ann.__enter__()
            started.append((ann, time.monotonic() - self.window_.mono0))

        tracer = obs_trace.TRACER
        if start_span is None:
            start()
        else:
            enter = tracer.span

            def span(name, **args):
                if name == start_span and not started:
                    start()
                return enter(name, **args)

            tracer.span = span        # shadows the method on this instance
        try:
            yield
        finally:
            tracer.__dict__.pop("span", None)
            if started:
                ann, offset = started[0]
                ann.__exit__(None, None, None)
                jax.profiler.stop_trace()
                self.segments.append((out, offset))

    def annotate(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(f"chipbench.{name}")

    def metric(self, name: str, value: float, unit: str) -> None:
        self.metrics[name] = {"value": float(value), "unit": unit}

    def info(self, **kw) -> None:
        self.infos.update(kw)

    def check(self, name: str, value: float, limit: float) -> None:
        self.checks[name] = {"value": value, "limit": limit}

    def read_memory(self) -> None:
        import jax

        n = self.cell["chips"]
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.devices()[:n]]
        self.memory_peak_bytes = max((p for p in peaks if p is not None),
                                     default=None)


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def layer_metrics(ctx: Context, spec: dict):
    """Per-layer metrics from the traced segments; readers that find
    nothing to read return None and their metric is left out."""
    peaks = json.loads((HERE / "peaks.json").read_text())
    kind = device_info()["kind"]
    if kind not in peaks["devices"]:
        raise SystemExit(f"no peaks for device kind {kind!r} in peaks.json")
    dev = trace_reduce.Segments([
        trace_reduce.load(path, ctx.cell["chips"], offset, ctx.spans)
        for path, offset in ctx.segments])
    shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    ctx.info(trace_window_s=[s.window_s for s in dev.parts],
             trace_dropped_s=[s.dropped_ns / 1e9 for s in dev.parts])
    reading = trace_reduce.Reading(
        spans=ctx.spans, device=dev, layer_data=ctx.layer_data,
        config=ctx.config, traffic=ctx.traffic, peaks=peaks["devices"][kind])
    out = {}
    for m in spec["per_layer"]:
        reader = load_module(spec["dir"] / "metrics" / f"{m['name']}.py")
        value = reader.read(reading)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out, dev


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, *,
             cache: Path = data.CACHE, t_start: float | None = None) -> dict:
    """Drive one run of a resolved cell; returns the result line as a dict.

    Does not look for a chip: ``main`` does that before calling it.
    """
    ctx = Context(spec, seed, seconds, trace, Path(cache),
                  T_START if t_start is None else t_start)
    runner = load_module(spec["dir"] / "runners"
                         / f"{ctx.traffic['runner']}.py")
    runner.run(ctx)
    result = {
        "correct": all(c["value"] <= c["limit"] for c in ctx.checks.values())
        and bool(ctx.checks),
        "attempted": ctx.attempted,
        "failed": ctx.failed,
    }
    device = device_info()
    device["memory_peak_bytes"] = ctx.memory_peak_bytes
    if trace:
        result["metrics"], dev = layer_metrics(ctx, spec)
        device["busy_s"], device["window_s"] = dev.busy_s, dev.window_s
        result["device"] = device
        result["breakdown"] = dev.breakdown()
    else:
        names = [m["name"] for m in spec["end_to_end"]]
        result["metrics"] = {k: ctx.metrics[k] for k in names
                             if k in ctx.metrics}
        result["device"] = device
    result["checks"] = ctx.checks
    result["_info"] = ctx.infos
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec = resolve(bench, args.workload)
    chips = spec["cell"]["chips"]

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"chipbench: needs {chips} TPU chip(s), JAX found "
              f"{len(devices)} {devices[0].platform} device(s); nothing run",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"chipbench: the program is not beside this directory ({e})",
              file=sys.stderr)
        return 2
    cache = data.CACHE
    jax.config.update("jax_compilation_cache_dir", str(cache / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    result = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                      cache=cache)
    info = result.pop("_info")
    print("chipbench: " + json.dumps(info, default=str), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

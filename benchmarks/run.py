"""Benchmark orchestrator — one section per thesis table/figure family.

  speedup      → §11.4 Tables 11.3–11.14   (three Parallel-FIMI variants)
  pbec         → §11.3 Figs 11.1–11.12     (double-sampling estimation error)
  replication  → §11.5 Tables 11.15–11.21  (LPT vs DB-Repl-Min)
  kernels      → Eclat support-counting hot spot (B.3.1)
  serve        → batched subset-query serving sweep (DESIGN.md §Serving)
  stream       → fused delta-update vs full window recompute (§Streaming)
  cluster      → distributed-executor speedup curve + rebalancing payoff
                 (§Distributed mining)
  io           → out-of-core store: streamed vs in-RAM mine throughput +
                 host high-water marks, O(block) residency gates (§Storage)

``python -m benchmarks.run [--fast|--full|--smoke] [--only NAME]``.  Prints
``name,us_per_call,derived`` CSV lines where applicable.  Defaults to the
fast variant so the whole suite stays CPU-friendly; ``--smoke`` runs only
the kernels + serve + stream + cluster + io sections in fast mode (the CI
gate, tools/check.sh).  The kernels, serve, stream, cluster, and io
sections additionally write ``BENCH_kernels.json`` / ``BENCH_serve.json`` /
``BENCH_stream.json`` / ``BENCH_cluster.json`` / ``BENCH_io.json``
(shapes, reps, µs) so the perf trajectory is machine-readable across PRs.
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path as _Path

sys.path.insert(0, str(_Path(__file__).resolve().parents[1] / "src"))


def main() -> None:
    ap = argparse.ArgumentParser()
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--full", action="store_true")
    mode.add_argument("--fast", action="store_true",
                      help="explicit fast mode (the default)")
    mode.add_argument("--smoke", action="store_true",
                      help="CI gate: kernels + serve sections, fast mode")
    ap.add_argument("--only", default="")
    args, _ = ap.parse_known_args()
    fast = not args.full

    sections = ["kernels", "serve", "stream", "cluster", "io", "speedup",
                "pbec", "replication"]
    if args.smoke:
        sections = ["kernels", "serve", "stream", "cluster", "io"]
    if args.only:
        sections = [args.only]

    for name in sections:
        print(f"\n===== {name} =====", flush=True)
        t0 = time.perf_counter()
        if name == "kernels":
            from benchmarks import kernels

            kernels.run(fast=fast)
        elif name == "serve":
            from benchmarks import serve

            serve.run(fast=fast)
        elif name == "stream":
            from benchmarks import stream

            stream.run(fast=fast)
        elif name == "cluster":
            from benchmarks import cluster

            cluster.run(fast=fast)
        elif name == "io":
            from benchmarks import io

            io.run(fast=fast)
        elif name == "speedup":
            from benchmarks import speedup

            rows = speedup.run(fast=fast)
            speedup.summarize(rows)
        elif name == "pbec":
            from benchmarks import pbec_estimation

            pbec_estimation.run(fast=fast)
        elif name == "replication":
            from benchmarks import replication

            replication.run(fast=fast)
        print(f"[{name}: {time.perf_counter()-t0:.1f}s]", flush=True)

    # one cross-suite digest over everything the sections just wrote
    from benchmarks.report import bench_summary

    print("\n===== summary (BENCH_*.json) =====", flush=True)
    print(bench_summary())

    # persistent perf trajectory: one stamped BENCH_HISTORY.jsonl row per
    # suite this invocation (re)wrote — the obs_report history/regress input
    import json
    from pathlib import Path

    from repro.obs import perfdb  # noqa: E402 (src on sys.path above)

    bench_suites = [s for s in sections
                    if s in ("kernels", "serve", "stream", "cluster", "io")]
    for suite in bench_suites:
        f = Path(__file__).resolve().parents[1] / f"BENCH_{suite}.json"
        try:
            payload = json.loads(f.read_text())
        except (OSError, json.JSONDecodeError):
            continue
        meta = payload.get("meta") or {}
        row = perfdb.append(
            str(f.parent / perfdb.DEFAULT_PATH), suite,
            perfdb.bench_result_keys(payload),
            sha=meta.get("git_sha"), backend=meta.get("backend", ""),
            ts=meta.get("ts"),
        )
        print(f"[history += {suite}: {len(row['keys'])} keys @ "
              f"{row['sha'] or '?'}]", flush=True)


if __name__ == "__main__":
    main()

"""The cross-suite ``BENCH_*.json`` summary table (one row per benchmark
file: its headline scalars, with the regression-gated overhead/slowdown
ratios flagged — the same keys ``obs_report baseline`` exits non-zero on)."""
from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

REPO = Path(__file__).resolve().parents[1]

#: top-level BENCH keys that are configuration, not results
_CONFIG_KEYS = {"bench", "backend", "db", "fast", "reps", "block_tx",
                "n_blocks", "P", "window_blocks", "support", "meta"}


def bench_meta(backend: str = "", ts: str | None = None,
               sha: str | None = None) -> dict:
    """The shared provenance stamp every ``BENCH_*.json`` write carries.

    One helper so all five suite writers (and ``serve_load.merge_bench``)
    agree on the shape: ``{"git_sha", "backend", "ts"}``.  The caller
    passes its backend (and may pin ts/sha for determinism in tests);
    SHA/timestamp default to the surrounding checkout and current UTC
    time via :mod:`repro.obs.perfdb` — the same stamp the
    ``BENCH_HISTORY.jsonl`` rows carry, so a BENCH file and its history
    row are mutually attributable.
    """
    from repro.obs import perfdb

    return {
        "git_sha": sha if sha is not None else perfdb.git_sha(),
        "backend": backend,
        "ts": ts if ts is not None else perfdb.utc_stamp(),
    }


def _is_ratio(key: str) -> bool:
    """Measured-vs-baseline ratio keys (printed with an 'x' suffix)."""
    return "overhead" in key or "slowdown" in key


def _is_gate(key: str) -> bool:
    """Parity-type ratios (expected ≈1.0) flagged against the threshold —
    what CI gates via ``obs_report baseline --match overhead``; slowdown
    factors are bounded-by-design and only displayed."""
    return "overhead" in key


def _is_burn(key: str) -> bool:
    """SLO error-budget burn rates (BENCH_serve ``slo_burn_rate``): a
    sustained burn > 1.0 exhausts the budget within the window, so flag it
    directly against 1.0 rather than the ratio threshold."""
    return "burn_rate" in key


def _is_speedup(key: str) -> bool:
    """Higher-is-better multipliers (e.g. ``slo_microbatch_speedup``)."""
    return key.endswith("_speedup")


def bench_summary(root: Path = REPO, threshold: float = 0.05) -> str:
    """One markdown table over every ``BENCH_*.json`` under ``root``."""
    files = sorted(root.glob("BENCH_*.json"))
    if not files:
        return "(no BENCH_*.json files found)"
    out = ["| file | backend | entries | headline results |",
           "|---|---|---|---|"]
    n_gates = n_bad = 0
    for f in files:
        try:
            d = json.loads(f.read_text())
        except (OSError, json.JSONDecodeError) as e:
            out.append(f"| {f.name} | | | UNREADABLE: {e} |")
            continue
        cells = []
        for k, v in d.items():
            if k in _CONFIG_KEYS or isinstance(v, (bool, str, list, dict)):
                continue
            if isinstance(v, (int, float)):
                if _is_gate(k):
                    n_gates += 1
                    bad = v > 1.0 + threshold
                    n_bad += bad
                    cells.append(f"{k}={v:.3f}x"
                                 + (" ⚠" if bad else " ✓"))
                elif _is_burn(k):
                    n_gates += 1
                    bad = v > 1.0
                    n_bad += bad
                    cells.append(f"{k}={v:.3f}"
                                 + (" ⚠" if bad else " ✓"))
                elif _is_ratio(k) or _is_speedup(k):
                    cells.append(f"{k}={v:.3f}x")
                else:
                    cells.append(f"{k}={v:.4g}")
        n_entries = len(d.get("entries") or [])
        out.append(f"| {f.name} | {d.get('backend', '?')} | {n_entries} | "
                   f"{'  '.join(cells) or '—'} |")
    out.append(
        f"\n**{len(files)} benchmark files; {n_gates - n_bad}/{n_gates} "
        f"overhead/burn gates ok (overhead <= {1 + threshold:.2f}x, "
        f"burn <= 1.0)** "
        f"(gate mechanically: `python -m repro.launch.obs_report baseline "
        f"--match overhead --bench BENCH_*.json`)."
    )
    return "\n".join(out)


def main():
    print("## Benchmark suite summary (BENCH_*.json)\n")
    print(bench_summary())


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""The control of ``correct``: the reference in the program's place, with
the configuration's exactness guarantee broken, judged by the same
comparison as a run.  It has to come out as not correct.

The broken guarantee is the shortcut a sampling miner tempts one to take:
mine a uniform sample of ``n_db_sample`` rows (the program's own Phase-1
sample size) and scale its supports up to the database, with no exact
recount.  One reading per cell, snapshot of its work and seed, at the
cell's own size: itemsets missing, extra or with another support.

    python3 chipbench/control.py --seeds 1 2 3

Host only; needs the program beside this directory (for the store).
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import data  # noqa: E402
import reference  # noqa: E402


def mine_reading(config: dict, rows_seed: int, seed: int, cache: Path) -> int:
    ds = config["dataset"]
    dense = reference.unpack_rows(data.rows(config, rows_seed, cache),
                                  ds["n_items"])
    masks, supp = reference.sampled_table(
        dense, config["minsup"], config["mining"]["n_db_sample"], seed)
    got = reference.as_dict(masks, supp)
    return reference.table_mismatches(
        got, data.exact_table(config, rows_seed, cache), len(got))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE.parent / "src"))
    bench = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    files = {c["name"]: c["file"] for c in bench["configs"]}
    for cell in bench["workloads"]:
        config = json.loads((HERE.parent / files[cell["config"]]).read_text())
        traffic = json.loads(
            (HERE / "traffic" / f"{cell['traffic']}.json").read_text())
        for item in traffic["work"]:
            for seed in args.seeds:
                v = mine_reading(config, item["rows_seed"], seed, data.CACHE)
                print(json.dumps({"cell": cell["name"],
                                  "rows_seed": item["rows_seed"],
                                  "seed": seed, "mismatched_itemsets": v}),
                      flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

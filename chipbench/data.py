"""A snapshot of a configuration's log, and its exact FI table.

The configuration fixes the generator's table of potentially large itemsets;
a rows seed draws a snapshot's transactions from it (``gen/ibm_quest.py``).
The rows are spilled through the program's ``StoreWriter`` (the store is the
program's input format) and kept, packed, for the reference.  The exact
table is the plain reference's, computed once per snapshot.  All of it sits
under ``chipbench/.cache`` (git ignores it), in a directory named from the
configuration, a hash of its dataset parameters and the rows seed.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
from pathlib import Path

import numpy as np

import reference
from gen.ibm_quest import generate_blocks

CACHE = Path(__file__).resolve().parent / ".cache"


def _key(config: dict) -> str:
    spec = json.dumps([config["dataset"], config["minsup"]], sort_keys=True)
    return hashlib.sha256(spec.encode()).hexdigest()[:12]


def cache_dir(config: dict, seed: int, root: Path = CACHE) -> Path:
    return Path(root) / f"{config['name']}-{_key(config)}-s{seed}"


def _publish(tmp: Path, final: Path) -> None:
    """Move a finished directory into place; a concurrent maker may win."""
    try:
        os.replace(tmp, final)
    except OSError:
        shutil.rmtree(tmp, ignore_errors=True)
        if not final.exists():
            raise


def ensure(config: dict, seed: int, root: Path = CACHE) -> Path:
    """Generate and spill the snapshot unless the checkout has it."""
    from repro.store.store import StoreWriter

    final = cache_dir(config, seed, root)
    if (final / "rows.npy").exists():
        return final
    final.parent.mkdir(parents=True, exist_ok=True)
    tmp = final.with_name(final.name + ".partial")
    shutil.rmtree(tmp, ignore_errors=True)
    ds = config["dataset"]
    writer = StoreWriter(str(tmp / "store"), n_items=ds["n_items"],
                         block_tx=ds["block_tx"], flush_every=16,
                         source=f"chipbench:{config['name']}:{seed}")
    packed = []
    for block in generate_blocks(ds, seed):
        writer.append_dense(block)
        packed.append(reference.pack_rows(block))
    writer.close()
    np.save(tmp / "rows.npy", np.concatenate(packed))
    _publish(tmp, final)
    return final


def store(config: dict, seed: int, root: Path = CACHE):
    from repro.store.store import TxStore

    return TxStore.open(str(ensure(config, seed, root) / "store"))


def rows(config: dict, seed: int, root: Path = CACHE) -> np.ndarray:
    """The packed rows, uint32 ``[n_tx, n_words]``, as the generator made them."""
    return np.load(ensure(config, seed, root) / "rows.npy")


def exact_table(config: dict, seed: int, root: Path = CACHE) -> dict:
    """{mask bytes: support}: the plain reference's table, computed once."""
    path = ensure(config, seed, root) / "exact.npz"
    if not path.exists():
        ds = config["dataset"]
        dense = reference.unpack_rows(rows(config, seed, root), ds["n_items"])
        masks, supp = reference.mine(
            dense, reference.abs_minsup(config["minsup"], ds["n_tx"]))
        tmp = path.with_name("exact.partial.npz")
        np.savez(tmp, masks=masks, supports=supp)
        os.replace(tmp, path)
    z = np.load(path)
    return reference.as_dict(z["masks"], z["supports"])

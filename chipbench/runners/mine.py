"""Batch mining: the stored database mined again and again, as users of
``launch/mine.py`` run it.

Each mine is ``repro.core.fimi.run(store, None, params, key, P=P)`` and ends
when its FI table (masks and supports) is on the host.  The traffic's
``work`` lists the mines of one cycle: a snapshot of the log (``data.py``,
rows drawn with ``rows_seed``) and a plan key ``PRNGKey(plan_seed)``.  The
run's seed orders them.  Set-up mines each once, which compiles every shape
its plan needs (the program sizes its arrays by the plan), so the window
replays warm plans and compiles nothing.  Every seed does the same work:
the program's sample, and with it the time of a mine, changes with the rows
and the key, by a factor of two on the same configuration.

``mine_s`` is the time from the window's start to the end of the last mine
the window started, over the number of such mines.  The window closes at
the first whole cycle after ``--seconds``.

A traced run mines once per entry of ``TRACED``, each under its own
profiler session: the first from the mine's start, the second from the
program's entry to Phase 2 to the mine's end.  The device tracer drops its
buffers a few seconds into Phase 1, so the first covers the assembly and
Phase 1 up to the drop, the second Phases 2 to 4 whole.
"""
from __future__ import annotations

import contextlib
import gc
import time
from dataclasses import dataclass

import numpy as np

import data
import reference


@dataclass
class Table:
    masks: np.ndarray     # uint32[F, W] as the program returned them
    supports: np.ndarray  # int64[F]
    overflow: int         # stack, output-buffer and exchange overflows


def fimi_params(config: dict):
    from repro.core import eclat, fimi

    m = config["mining"]
    return fimi.FimiParams(
        variant=m["variant"], min_support_rel=config["minsup"],
        alpha=m["alpha"], scheduler=m["scheduler"],
        n_db_sample=min(m["n_db_sample"], config["dataset"]["n_tx"]),
        n_fi_sample=m["n_fi_sample"],
        eclat=eclat.EclatConfig(max_out=m["max_out"],
                                max_stack=m["max_stack"],
                                frontier_size=m["frontier_size"]),
    )


def mine_once(store, config: dict, params, key) -> Table:
    """One mine through the program's entry point; the table on the host."""
    import jax

    from repro.core import fimi

    m = config["mining"]
    res = fimi.run(store, None, params, key, P=m["P"],
                   host_budget_blocks=m["host_budget_blocks"])
    p4 = res.phase4
    items, supp, count, total, stack = jax.device_get(
        (p4.fi_items, p4.fi_supports, p4.fi_count, p4.fi_total, p4.overflow))
    minsup = reference.abs_minsup(config["minsup"], store.n_tx)
    anc = res.ancestor_supports >= minsup
    masks = np.concatenate(
        [items[p, : count[p]] for p in range(items.shape[0])]
        + [reference.pack_rows(res.ancestor_masks[anc])])
    supports = np.concatenate(
        [supp[p, : count[p]] for p in range(items.shape[0])]
        + [res.ancestor_supports[anc]]).astype(np.int64)
    overflow = (int(np.sum(stack)) + int(np.sum(total) - np.sum(count))
                + int(res.exchange_overflow))
    return Table(np.asarray(masks, np.uint32), supports, overflow)


TRACED = (None, "fimi/phase2_partition")


def cycle(traffic: dict, seed: int) -> list:
    """The traffic's work items, ``(rows_seed, plan key)``, in the order
    this seed mines them."""
    import jax

    work = traffic["work"]
    order = np.random.default_rng(seed % 2**64).permutation(len(work))
    return [(work[j]["rows_seed"], jax.random.PRNGKey(work[j]["plan_seed"]))
            for j in order]


def run(ctx) -> None:
    """Set up, drive the window, then judge every mine of the window."""
    config, traffic = ctx.config, ctx.traffic
    work = cycle(traffic, ctx.seed)
    stores = {rs: data.store(config, rs, ctx.cache) for rs, _ in work}
    params = fimi_params(config)
    for rs, key in work:
        mine_once(stores[rs], config, params, key)
    gc.collect()
    gc.freeze()     # set-up's objects stay out of the window's collections
    ctx.setup_done()

    tables, ends = [], []
    window = ctx.window()

    def more() -> bool:
        if ctx.trace:
            return len(tables) < len(TRACED)
        return not window.closed() or len(tables) % len(work) != 0

    with window:
        while more():
            i = len(tables)
            seg = (ctx.segment(TRACED[i]) if ctx.trace
                   else contextlib.nullcontext())
            with seg, ctx.annotate(f"mine.{i}"):
                rs, key = work[i % len(work)]
                tables.append((rs, mine_once(stores[rs], config, params,
                                             key)))
            ends.append(time.perf_counter())
    gc.unfreeze()
    mine_s = (ends[-1] - window.t0) / len(tables)
    ctx.metric("mine_s", mine_s, "s")
    ctx.layer_data["mines"] = len(tables)
    ctx.info(mines=len(tables), mine_end_s=[e - window.t0 for e in ends],
             minsup=config["minsup"],
             abs_minsup=reference.abs_minsup(config["minsup"],
                                             config["dataset"]["n_tx"]),
             rows_seeds=[rs for rs, _ in tables],
             n_fis=[len(t.supports) for _, t in tables],
             overflow=[t.overflow for _, t in tables])
    ctx.read_memory()
    del stores

    want = {rs: data.exact_table(config, rs, ctx.cache) for rs, _ in work}
    bad = [reference.table_mismatches(
        reference.as_dict(t.masks, t.supports), want[rs], len(t.supports))
        for rs, t in tables]
    ctx.attempted = len(tables)
    ctx.failed = sum(1 for b in bad if b)
    ctx.check("mismatched_itemsets", max(bad), 0)
    ctx.info(exact_n_fis={rs: len(w) for rs, w in want.items()})

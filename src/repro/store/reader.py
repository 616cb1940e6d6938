"""Streamed read side of the transaction store: disk → host → device.

:class:`BlockReader` is the double-buffer protocol (DESIGN.md, "Storage
subsystem"): while the consumer sweeps block *i* on device, a single reader
thread is already pulling block *i+1* off disk, and the ``jax.device_put``
dispatch for it is asynchronous — so at most **two** blocks are ever
resident on host, regardless of database size.  The reader accounts its
live host bytes and raises if they would exceed the configured budget, so
"O(block) host residency" is an enforced invariant, not a hope.

On top of it:

  * :func:`to_device_shards` — assemble the ``uint32[P, T, IW]`` device
    shards ``core.fimi.run`` / ``cluster.execute`` mine, block by block,
    bit-exact with ``fimi.shard_db(store.to_dense(), P)`` (same row order,
    same ``n_tx − n_tx mod P`` truncation).
  * :func:`sample_rows` — the Thm 6.1 i.i.d. database sample drawn off
    disk: identical indices (same key, same PRNG call) and therefore
    identical rows to ``bitmap.sample_transactions`` over the in-RAM DB.
  * :func:`streamed_itemset_supports` — exact containment supports of
    arbitrary packed itemset masks over the whole store, one block sweep
    at a time (the ``block_itemset_supports`` kernel per block).
"""
from __future__ import annotations

import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.store.retry import RetriesExhausted, RetryPolicy
from repro.store.store import StoreIntegrityError, TxStore

_U32 = jnp.uint32


class HostBudgetExceeded(RuntimeError):
    """The reader would hold more host bytes than the configured budget."""


class BlockReadError(RuntimeError):
    """A block failed to read/transfer; the message names block and path."""


#: Errors that already carry their own block context (or are the budget
#: invariant itself) — re-raised as-is at the consumer, never wrapped.
_PASSTHROUGH = (StoreIntegrityError, RetriesExhausted, HostBudgetExceeded)


class BlockReader:
    """Double-buffered host→device block iterator with residency accounting.

    ``host_budget_blocks`` is the store's host block budget in units of the
    largest block; double buffering needs 2 (read-ahead + in-flight).  The
    observed high-water mark is exposed as :attr:`peak_host_bytes` — the
    IO benchmark asserts it stays O(block) while the database grows.

    Fault behavior (DESIGN.md, "Failure model"): disk reads and the
    ``device_put`` dispatch run under ``retry`` (bounded exponential
    backoff, ``OSError`` only by default).  A failure on the prefetch
    thread is raised to the consumer at its next ``__next__`` — typed
    integrity errors pass through unchanged, anything else is wrapped in
    :class:`BlockReadError` naming the failing block index and path — and
    the worker thread is joined before the error propagates, so an
    aborted stream never leaks a thread or an unretrieved future.
    """

    def __init__(
        self,
        store: TxStore,
        host_budget_blocks: int = 2,
        *,
        retry: RetryPolicy = RetryPolicy(),
    ):
        if host_budget_blocks < 2:
            raise ValueError(
                "double buffering needs a host budget of >= 2 blocks "
                f"(got {host_budget_blocks})"
            )
        self.store = store
        self.host_budget_blocks = host_budget_blocks
        self.budget_bytes = host_budget_blocks * max(store.max_block_bytes, 1)
        self.peak_host_bytes = 0
        self.retry = retry
        self.read_attempts = 0      # telemetry: total read attempts made
        self._live: dict = {}
        self._lock = threading.Lock()

    # -- residency accounting -------------------------------------------------
    def _block_path(self, i: int) -> str:
        return os.path.join(
            self.store.directory, self.store.manifest.blocks[i].file
        )

    def _read_host(self, i: int) -> np.ndarray:
        # fault injection for the doctor's prefetch-stall self-test: a
        # per-block read delay the double buffer cannot hide on small DBs
        delay = float(os.environ.get("REPRO_STORE_READ_DELAY_S", "0") or 0)
        if delay > 0:
            time.sleep(delay)

        def attempt() -> np.ndarray:
            with self._lock:
                self.read_attempts += 1
            return self.store.read_block(i)

        arr = self.retry.call(
            attempt, describe=f"read block {i} ({self._block_path(i)})"
        )
        with self._lock:
            self._live[i] = arr.nbytes
            live = sum(self._live.values())
            self.peak_host_bytes = max(self.peak_host_bytes, live)
            obs_metrics.registry().gauge("store/host_bytes_peak").update_max(
                float(self.peak_host_bytes)
            )
            obs_trace.TRACER.counter(
                "host bytes", live=float(live),
                peak=float(self.peak_host_bytes))
            if live > self.budget_bytes:
                raise HostBudgetExceeded(
                    f"host residency {live}B exceeds budget "
                    f"{self.budget_bytes}B ({self.host_budget_blocks} blocks)"
                )
        return arr

    def _release(self, i: int) -> None:
        with self._lock:
            self._live.pop(i, None)
            obs_trace.TRACER.counter(
                "host bytes", live=float(sum(self._live.values())),
                peak=float(self.peak_host_bytes))

    # -- the double-buffered stream -------------------------------------------
    def device_blocks(
        self,
    ) -> Iterator[Tuple[int, int, jnp.ndarray, int]]:
        """Yield ``(block_index, row_offset, device_block, n_rows)``.

        The next block's disk read runs on a worker thread and its
        ``device_put`` is dispatched before the consumer finishes the
        current one — the overlap that hides I/O behind device sweeps.
        A prefetch failure raises here, at the iteration that needed the
        block, with the block's index/path in the message.
        """
        n = self.store.n_blocks
        if n == 0:
            return
        reg = obs_metrics.registry()
        stall_h = reg.histogram("store/prefetch_stall_s")
        blocks_c = reg.counter("store/blocks_read")
        off = 0
        ex = ThreadPoolExecutor(max_workers=1)
        fut = ex.submit(self._read_host, 0)
        try:
            for i in range(n):
                t_wait = time.perf_counter()
                try:
                    arr = fut.result()
                except _PASSTHROUGH:
                    raise
                except Exception as e:
                    raise BlockReadError(
                        f"prefetch of block {i} ({self._block_path(i)}) "
                        f"failed: {e!r}"
                    ) from e
                # stall = how long the consumer blocked on the prefetch: ~0
                # when the read hid behind the previous device sweep
                stall_h.record(time.perf_counter() - t_wait)
                blocks_c.inc()
                if i + 1 < n:
                    fut = ex.submit(self._read_host, i + 1)
                dev = self.retry.call(
                    lambda: jax.device_put(arr),   # async dispatch
                    describe=f"device_put block {i}",
                )
                n_rows = int(arr.shape[0])
                del arr  # drop the host reference; the transfer owns a copy
                yield i, off, dev, n_rows
                self._release(i)
                off += n_rows
        finally:
            # join the worker before any exception propagates: no leaked
            # thread, and the in-flight future's error (if any) is
            # retrieved so it cannot surface later as a bare warning
            ex.shutdown(wait=True)
            if not fut.cancelled():
                fut.exception()
            with self._lock:
                self._live.clear()


# ---------------------------------------------------------------------------
# Device assembly — the mining input, built one block at a time
# ---------------------------------------------------------------------------


def _place_impl(
    buf: jnp.ndarray, blk: jnp.ndarray, off: jnp.ndarray
) -> jnp.ndarray:
    return jax.lax.dynamic_update_slice(buf, blk, (off, jnp.int32(0)))


# Donating buf lets XLA write the block into the accumulating device buffer
# in place — without it every per-block update copies the whole O(n_tx) slab
# (O(n_blocks · n_tx) traffic + 2x transient memory).  CPU does not
# implement donation (jax warns and copies anyway), so only donate off-CPU.
if jax.default_backend() == "cpu":
    _place = jax.jit(_place_impl)
else:
    _place = jax.jit(_place_impl, donate_argnums=(0,))


def to_device_rows(
    store: TxStore,
    n_rows: Optional[int] = None,
    *,
    host_budget_blocks: int = 2,
    reader: Optional[BlockReader] = None,
) -> jnp.ndarray:
    """All (or the first ``n_rows``) packed rows as one device array.

    Host residency stays within the reader's budget; the device buffer is
    the packed working set (32× smaller than the dense bool matrix).
    Pass ``reader`` to account residency on a caller-owned
    :class:`BlockReader` (drivers report its ``peak_host_bytes``).
    """
    total = store.n_tx if n_rows is None else min(n_rows, store.n_tx)
    buf = jnp.zeros((total, store.n_words), _U32)
    reader = reader or BlockReader(store, host_budget_blocks)
    for _, off, dev, n_blk in reader.device_blocks():
        if off >= total:
            break
        take = min(n_blk, total - off)
        if take <= 0:      # empty block mid-stream: nothing to place
            continue
        blk = dev if take == n_blk else dev[:take]
        buf = _place(buf, blk, jnp.int32(off))
    return buf


def to_device_shards(
    store: TxStore,
    P: int,
    *,
    host_budget_blocks: int = 2,
    reader: Optional[BlockReader] = None,
) -> jnp.ndarray:
    """``uint32[P, T, IW]`` horizontal shards, bit-exact with
    ``fimi.shard_db(store.to_dense(), P)`` (row order preserved, the last
    ``n_tx mod P`` rows dropped) — but assembled block-by-block so the host
    never holds more than the reader's budget.  The result sits on the
    default device; :func:`place_on_mesh` spreads it over a miner mesh."""
    T = store.n_tx // P
    rows = to_device_rows(
        store, T * P, host_budget_blocks=host_budget_blocks, reader=reader
    )
    return rows.reshape(P, T, store.n_words)


def place_on_mesh(shards: jnp.ndarray, mesh) -> jnp.ndarray:
    """Put ``[P, ...]`` shards one per device of a 1-D miner mesh.

    A ``NamedSharding`` over the mesh axis: miner p's shard lives on mesh
    device p, so ``shard_map`` phases read it in place instead of moving it
    off the default device on every call.  A no-op when already so placed.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    return jax.device_put(
        shards, NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))
    )


# ---------------------------------------------------------------------------
# Off-disk sampling + streamed support counting (Phase-1/2, O(block))
# ---------------------------------------------------------------------------


def gather_rows(store: TxStore, indices: np.ndarray) -> np.ndarray:
    """Gather arbitrary row indices (duplicates allowed) in one block pass."""
    idx = np.asarray(indices, np.int64)
    assert idx.size == 0 or (idx.min() >= 0 and idx.max() < store.n_tx), (
        f"row index out of range [0, {store.n_tx})"
    )
    out = np.zeros((idx.shape[0], store.n_words), np.uint32)
    off = 0
    for blk in store.iter_blocks():
        nb = blk.shape[0]
        if nb:
            sel = np.nonzero((idx >= off) & (idx < off + nb))[0]
            if sel.size:
                out[sel] = blk[idx[sel] - off]
        off += nb
    return out


def sample_rows(
    store: TxStore,
    key: jax.Array,
    n_sample: int,
    n_tx: Optional[int] = None,
) -> jnp.ndarray:
    """Thm 6.1 i.i.d. (with replacement) transaction sample drawn off disk.

    Draws the **same indices** as ``bitmap.sample_transactions(rows, key,
    n_sample, n_tx)`` over the in-RAM row slab (same key, same
    ``jax.random.randint`` call — JAX PRNG results are jit-invariant), then
    gathers them in one block pass: the sample, and hence every plan built
    from it, is bit-exact with the in-memory path at O(block) host cost.
    """
    n_tx = store.n_tx if n_tx is None else n_tx
    idx = np.asarray(jax.random.randint(key, (n_sample,), 0, n_tx))
    return jnp.asarray(gather_rows(store, idx))


def streamed_itemset_supports(
    store: TxStore, masks: jnp.ndarray, *, force: Optional[str] = None
) -> np.ndarray:
    """Exact supports ``int64[F]`` of packed itemset masks over the store.

    One ``block_itemset_supports`` sweep per resident block, accumulated on
    host — O(block) memory at every tier, any database size.  Empty blocks
    are skipped (they support nothing).
    """
    from repro.kernels import ops

    masks = jnp.asarray(masks, _U32)
    total = np.zeros((masks.shape[0],), np.int64)
    for _, _, dev, n_rows in BlockReader(store).device_blocks():
        if n_rows == 0:
            continue
        counts = ops.block_itemset_supports(dev[None], masks, force=force)
        total += np.asarray(counts)[0].astype(np.int64)
    return total

"""Pallas TPU kernels: fused multi-prefix extension-support counting.

The frontier-batched Eclat (DESIGN.md, "Frontier-batched DFS") pops K PBEC
nodes per ``while_loop`` trip and needs, in **one** launch,

  ``S[k, i] = Σ_w popcount(item_bits[i, w] & prefix_tids[k, w])``

— the supports of every extension of every frontier node.  Launching the
single-prefix kernel K times wastes the grid: each launch re-streams the whole
``[I, W]`` bitmap slab from HBM and computes a skinny ``[I, 1]`` output.  Here
the K prefixes ride along as a second output axis, so each ``[BI, BW]`` item
tile fetched into VMEM is reused against all BK prefix rows of the step.

Two formulations, same grid ``(K/BK, I/BI, W/BW)`` with W minormost
(sequential on TPU) so the accumulator lives in the output block across W
steps — the pattern of ``pair_support.py``:

  * ``multi_extension_supports_pallas``      — VPU SWAR popcount of the
    3-D AND ``[BK, BI, BW]``; work per output element is W AND+popcount ops
    on 32-bit lanes.
  * ``multi_extension_supports_mxu_pallas``  — unpack both operands to 0/1
    bf16 inside the kernel, one bit plane at a time, and feed the 128×128
    MXU with ``dot(prefixes, itemsᵀ)``: popcount(AND) ≡ dot of indicator
    vectors, exact in f32 accumulation for supports < 2²⁴.  Preferable once
    K is large enough to fill MXU rows (K ≳ 64); for small frontiers the VPU
    form wins.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.bitmap import popcount_u32 as popcount

_U32 = jnp.uint32


def bitplane_dot(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """f32[M, N] = Σ_w popcount(a[m, w] & b[n, w]) on the MXU.

    popcount(AND) is the dot of 0/1 indicator vectors; the 32 bit planes of
    each packed word are fed as 32 bf16 dots contracting over W, so no lane
    reshape is needed.  Exact while counts < 2²⁴ (f32 accumulation).
    """

    def plane(words, s):
        bit = ((words >> s) & _U32(1)).astype(jnp.int32)
        return bit.astype(jnp.float32).astype(jnp.bfloat16)

    def body(s, acc):
        s = s.astype(_U32)
        return acc + jax.lax.dot_general(
            plane(a, s), plane(b, s), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    acc = jnp.zeros((a.shape[0], b.shape[0]), jnp.float32)
    return jax.lax.fori_loop(0, 32, body, acc)


def _vpu_kernel(tids_ref, items_ref, out_ref):
    w = pl.program_id(2)

    @pl.when(w == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    t = tids_ref[...]                               # [BK, BW]
    a = items_ref[...]                              # [BI, BW]
    inter = t[:, None, :] & a[None, :, :]           # [BK, BI, BW]
    out_ref[...] += popcount(inter).sum(axis=-1)


@functools.partial(
    jax.jit, static_argnames=("block_k", "block_i", "block_w", "interpret")
)
def multi_extension_supports_pallas(
    item_bits: jnp.ndarray,    # uint32[I, W]
    prefix_tids: jnp.ndarray,  # uint32[K, W]
    *,
    block_k: int = 8,
    block_i: int = 128,
    block_w: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """int32[K, I] multi-prefix supports via VPU SWAR popcount.

    Pads K, I and W to tile multiples; VMEM per step ≈ BK·BI·BW·4 B for the
    widened AND (1 MiB at defaults).
    """
    I, W = item_bits.shape
    K = prefix_tids.shape[0]
    bk = min(block_k, max(8, K))
    bi = min(block_i, max(8, I))
    bw = min(block_w, max(128, W))
    pk, pi, pw = (-K) % bk, (-I) % bi, (-W) % bw
    tids = jnp.pad(prefix_tids, ((0, pk), (0, pw)))
    items = jnp.pad(item_bits, ((0, pi), (0, pw)))
    Kp, Wp = tids.shape
    Ip = items.shape[0]

    out = pl.pallas_call(
        _vpu_kernel,
        grid=(Kp // bk, Ip // bi, Wp // bw),
        in_specs=[
            pl.BlockSpec((bk, bw), lambda k, i, w: (k, w)),
            pl.BlockSpec((bi, bw), lambda k, i, w: (i, w)),
        ],
        out_specs=pl.BlockSpec((bk, bi), lambda k, i, w: (k, i)),
        out_shape=jax.ShapeDtypeStruct((Kp, Ip), jnp.int32),
        interpret=interpret,
    )(tids, items)
    return out[:K, :I]


def _mxu_kernel(tids_ref, items_ref, out_ref):
    w = pl.program_id(2)

    @pl.when(w == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += bitplane_dot(tids_ref[...], items_ref[...])


@functools.partial(
    jax.jit, static_argnames=("block_k", "block_i", "block_w", "interpret")
)
def multi_extension_supports_mxu_pallas(
    item_bits: jnp.ndarray,    # uint32[I, W]
    prefix_tids: jnp.ndarray,  # uint32[K, W]
    *,
    block_k: int = 128,
    block_i: int = 128,
    block_w: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """int32[K, I] via fused unpack+MXU-dot.  Exact for supports < 2^24."""
    I, W = item_bits.shape
    K = prefix_tids.shape[0]
    bk = min(block_k, max(8, K))
    bi = min(block_i, max(8, I))
    bw = min(block_w, max(128, W))
    pk, pi, pw = (-K) % bk, (-I) % bi, (-W) % bw
    tids = jnp.pad(prefix_tids, ((0, pk), (0, pw)))
    items = jnp.pad(item_bits, ((0, pi), (0, pw)))
    Kp, Wp = tids.shape
    Ip = items.shape[0]

    out = pl.pallas_call(
        _mxu_kernel,
        grid=(Kp // bk, Ip // bi, Wp // bw),
        in_specs=[
            pl.BlockSpec((bk, bw), lambda k, i, w: (k, w)),
            pl.BlockSpec((bi, bw), lambda k, i, w: (i, w)),
        ],
        out_specs=pl.BlockSpec((bk, bi), lambda k, i, w: (k, i)),
        out_shape=jax.ShapeDtypeStruct((Kp, Ip), jnp.float32),
        interpret=interpret,
    )(tids, items)
    return out[:K, :I].astype(jnp.int32)

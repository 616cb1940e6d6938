"""Pallas TPU kernels: all-pairs itemset supports (the C2 counting step).

``S[i, j] = |T({i}) ∩ T({j})| = Σ_w popcount(bits_i[w] & bits_j[w])`` — the
Parallel-Eclat initialization (thesis Alg. 5 line 3) and the profit matrix of
DB-Repl-Min (Alg. 23).

Two TPU formulations, both tiled through VMEM with a shared accumulator
pattern (W is the minormost sequential grid axis):

  * ``pair_supports_pallas``      — VPU SWAR popcount over an AND of tiles.
    Work per output element: W AND+popcount ops on 32-bit lanes.
  * ``pair_supports_mxu_pallas``  — **beyond-paper TPU adaptation**: unpack the
    packed words to 0/1 bf16 inside the kernel (one bit plane per dot, see
    ``multi_support.bitplane_dot``) and feed the 128×128 MXU with
    ``dot(bits, bitsᵀ)``.  popcount(AND) ≡ dot-product of indicator vectors,
    exact in f32 accumulation for supports < 2²⁴.  This turns a VPU-bound
    bit-twiddle into an MXU matmul at 32 MACs per packed word — the itemset-
    mining analogue of quantized matmul.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.bitmap import popcount_u32 as popcount
from repro.kernels.multi_support import bitplane_dot


def _vpu_kernel(a_ref, b_ref, out_ref):
    w = pl.program_id(2)

    @pl.when(w == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = a_ref[...]                                  # [BI, BW]
    b = b_ref[...]                                  # [BJ, BW]
    inter = a[:, None, :] & b[None, :, :]           # [BI, BJ, BW]
    out_ref[...] += popcount(inter).sum(axis=-1)


@functools.partial(
    jax.jit, static_argnames=("block_i", "block_j", "block_w", "interpret")
)
def pair_supports_pallas(
    item_bits: jnp.ndarray,  # uint32[I, W]
    valid_tid: jnp.ndarray,  # uint32[W]
    *,
    block_i: int = 8,
    block_j: int = 128,
    block_w: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """int32[I, I] via VPU popcount.  VMEM/step ≈ BI·BJ·BW·4 B (1 MiB def.)."""
    I, W = item_bits.shape
    bi, bj = min(block_i, max(8, I)), min(block_j, max(8, I))
    bw = min(block_w, max(128, W))
    pi, pw = (-I) % bi, (-W) % bw
    pj = (-I) % bj
    masked = item_bits & valid_tid[None, :]
    a = jnp.pad(masked, ((0, pi), (0, pw)))
    b = jnp.pad(masked, ((0, pj), (0, pw)))
    Ip, Wp = a.shape
    Jp = b.shape[0]

    out = pl.pallas_call(
        _vpu_kernel,
        grid=(Ip // bi, Jp // bj, Wp // bw),
        in_specs=[
            pl.BlockSpec((bi, bw), lambda i, j, w: (i, w)),
            pl.BlockSpec((bj, bw), lambda i, j, w: (j, w)),
        ],
        out_specs=pl.BlockSpec((bi, bj), lambda i, j, w: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Ip, Jp), jnp.int32),
        interpret=interpret,
    )(a, b)
    return out[:I, :I]


def _mxu_kernel(a_ref, b_ref, out_ref):
    w = pl.program_id(2)

    @pl.when(w == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += bitplane_dot(a_ref[...], b_ref[...])


@functools.partial(
    jax.jit, static_argnames=("block_i", "block_j", "block_w", "interpret")
)
def pair_supports_mxu_pallas(
    item_bits: jnp.ndarray,
    valid_tid: jnp.ndarray,
    *,
    block_i: int = 128,
    block_j: int = 128,
    block_w: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """int32[I, I] via fused unpack+MXU-dot.  Exact for supports < 2^24."""
    I, W = item_bits.shape
    bi, bj = min(block_i, max(8, I)), min(block_j, max(8, I))
    bw = min(block_w, max(128, W))
    pi, pj, pw = (-I) % bi, (-I) % bj, (-W) % bw
    masked = item_bits & valid_tid[None, :]
    a = jnp.pad(masked, ((0, pi), (0, pw)))
    b = jnp.pad(masked, ((0, pj), (0, pw)))
    Ip, Wp = a.shape
    Jp = b.shape[0]

    out = pl.pallas_call(
        _mxu_kernel,
        grid=(Ip // bi, Jp // bj, Wp // bw),
        in_specs=[
            pl.BlockSpec((bi, bw), lambda i, j, w: (i, w)),
            pl.BlockSpec((bj, bw), lambda i, j, w: (j, w)),
        ],
        out_specs=pl.BlockSpec((bi, bj), lambda i, j, w: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Ip, Jp), jnp.float32),
        interpret=interpret,
    )(a, b)
    return out[:I, :I].astype(jnp.int32)

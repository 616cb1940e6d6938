"""Machine models: the hardware constants every roofline consumer shares.

One frozen :class:`MachineModel` per target — peak arithmetic throughput,
HBM/DRAM bandwidth, interconnect link bandwidth — so the mining-kernel
profiler (:mod:`repro.obs.profile`) and the report CLI price work against
the SAME constants instead of each hard-coding its own copy.  Stdlib-only
and jax-free (the layering rule of :mod:`repro.obs`): the report CLI
recomputes roofline terms from these numbers in contexts where jax never
loads.

Two units of "flops" coexist deliberately:

  * ``peak_flops`` is the bf16 MXU FLOP peak (for ``TPU_V5E`` the
    published 197 TFLOP/s bf16 figure), published beside the others as a
    ``kernels/machine/*`` gauge;
  * the mining kernels are integer word machines — one "op" is one 32-bit
    word operation (AND / popcount / add).  ``word_ops_peak`` is the
    sustained word-op throughput the kernels can reach on that target
    (VPU lanes on TPU, vectorized scalar units on CPU).

The **machine balance** ``word_ops_peak / hbm_bw`` (ops per byte) is what
classifies a kernel family as compute- or memory-bound: a family whose
arithmetic intensity (modeled word-ops per modeled byte) falls below the
balance is bandwidth-limited — exactly the single-prefix vs batched-frontier
distinction PR 1 exploited (DESIGN.md, "Performance attribution").
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class MachineModel:
    """Roofline constants of one execution target."""

    name: str
    peak_flops: float       # bf16 FLOP/s (dense-matmul peak)
    hbm_bw: float           # bytes/s main-memory bandwidth
    link_bw: float          # bytes/s per interconnect link
    word_ops_peak: float    # 32-bit word ops/s (mining-kernel peak)

    @property
    def balance_word_ops_per_byte(self) -> float:
        """Machine balance for the word-op kernels: ops/byte at the ridge."""
        return self.word_ops_peak / self.hbm_bw


#: TPU v5e (``device_kind`` "TPU v5 lite").  Peaks from the Google Cloud
#: documentation page "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s
#: inter-chip interconnect (= 200e9 B/s).  ``word_ops_peak`` is an estimate,
#: not a published figure: 8 sublanes × 128 lanes × ~1 op/cycle per VALU
#: slot at ~1.5 GHz is O(1e12); no chip run has measured it yet.
TPU_V5E = MachineModel(
    name="tpu-v5e",
    peak_flops=197e12,
    hbm_bw=819e9,
    link_bw=1600e9 / 8,
    word_ops_peak=1e12,
)

#: A container-class x86 host (``device_kind`` "cpu"; the test target):
#: XLA:CPU multithreaded.  ~50 G sustained 32-bit vector word-ops/s and
#: ~20 GB/s effective stream bandwidth are deliberately round estimates —
#: the profiler's verdicts compare *terms against each other*, so only their
#: ratio (the balance, 2.5 ops/byte) needs to be in the right regime.
CPU_HOST = MachineModel(
    name="cpu-host",
    peak_flops=2e11,
    hbm_bw=20e9,
    link_bw=10e9,
    word_ops_peak=5e10,
)

#: Every device this repository prices work on, keyed by the
#: ``device_kind`` jax reports.  A device missing here is an error.
MACHINES = {"TPU v5 lite": TPU_V5E, "cpu": CPU_HOST}


def machine_for_device_kind(device_kind: str) -> MachineModel:
    """The model to price kernels against on a ``device_kind``; raises for a
    device that has no entry rather than pricing it as another."""
    try:
        return MACHINES[device_kind]
    except KeyError:
        raise ValueError(
            f"no machine model for device_kind {device_kind!r}; "
            f"known: {sorted(MACHINES)}"
        ) from None

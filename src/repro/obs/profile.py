"""Kernel performance attribution: measured vs modeled time per family.

A :class:`KernelProfiler` sits around every ``repro.kernels.ops`` dispatch
and answers the question the post-hoc benchmarks cannot: *where did this
run's wall time actually go, and was that time well spent?*  Per kernel
family it accumulates

  * **measured** time — device-synced wall clock per eager call, bucketed
    by power-of-two-rounded shape, plus loop-attributed time for kernels
    that execute inside ``lax.while_loop`` (see below);
  * **modeled** time — an analytic word-op/byte cost model priced against
    the shared :mod:`repro.obs.machine` roofline constants, giving
    per-family compute and memory terms, ``modeled = max(compute,
    memory)``, an achieved fraction
    ``modeled / measured``, and a memory- vs compute-bound verdict.

Two measurement paths
---------------------
Eager dispatches (the serving subset sweep, streaming delta sweep, pair
counts, planner PBEC) pass through :meth:`KernelProfiler.call`, which times
``thunk`` → ``jax.block_until_ready`` on the host clock.  The frontier
mining kernels are different: ``core/eclat.mine_seeded`` is jit'd with the
support fn as a static argument, so the ops dispatch executes **once per
compilation** under tracing, then the compiled loop body runs thousands of
trips with no Python in sight.  ``call`` detects the traced case (the
output is a :class:`jax.core.Tracer`) and only notes the shape; the actual
work is attributed afterwards by the drivers — ``core/fimi.run`` and
``cluster/executor`` call :meth:`observe_loop` with the loop's trip count
and the phase-4 wall time they already measure.  Attribution, not a second
timer: the loop cost model says how much arithmetic those trips performed,
and the phase wall clock says how long they took.

Cost models (word-ops; one op = one 32-bit AND / popcount / add)
----------------------------------------------------------------
``W``/``IW`` = uint32 words per bitmap row.

  bitmap  (I, W)        flops 3·I·W            bytes 4·(I·W + W + I)
  multi   (K, I, W)     flops 3·K·I·W          bytes 4·(I·W + K·W + K·I)
  pair    (I, W)        flops 3·I²·W           bytes 4·(I·W + W + I²)
  subset  (Q, F, IW)    flops 8·Q·F·IW         bytes 4·((Q+F)·IW + 2·Q·F)
  delta   (S, T, F, IW) flops 4·S·T·F·IW       bytes 4·(S·T·IW + F·IW + S·F)

The constants are per-word operation counts of the reference algorithm
(AND + popcount + accumulate ≈ 3 ops; the subset sweep does both set
differences per pair; the delta sweep adds the containment compare), not
microarchitectural truth — what matters is that the *same* model prices
every family, so the bound-ness verdicts and the cross-family attribution
ranking are consistent, and that ``obs_report kernels --check-model`` can
recompute every term from the published flop/byte/constant gauges.

Disabled path: one attribute check in the ops wrapper, no allocation, no
clock read — same contract as the null tracer (gated <2 % in
``tests/test_profile.py``).
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Dict, Optional, Tuple

from repro.obs import metrics as obs_metrics
from repro.obs.machine import MachineModel, machine_for_device_kind

#: The five dispatch families of ``repro.kernels.ops``.
FAMILIES = ("bitmap", "multi", "pair", "subset", "delta")

#: Canonical dimension order per family (bucket labels, report rows).
DIM_ORDER: Dict[str, Tuple[str, ...]] = {
    "bitmap": ("I", "W"),
    "multi": ("K", "I", "W"),
    "pair": ("I", "W"),
    "subset": ("Q", "F", "IW"),
    "delta": ("S", "T", "F", "IW"),
}


def cost_model(family: str, dims: Dict[str, int]) -> Tuple[float, float]:
    """(word_ops, bytes) one execution of ``family`` at ``dims`` performs."""
    d = dims
    if family == "bitmap":
        flops = 3.0 * d["I"] * d["W"]
        nbytes = 4.0 * (d["I"] * d["W"] + d["W"] + d["I"])
    elif family == "multi":
        flops = 3.0 * d["K"] * d["I"] * d["W"]
        nbytes = 4.0 * (d["I"] * d["W"] + d["K"] * d["W"] + d["K"] * d["I"])
    elif family == "pair":
        flops = 3.0 * d["I"] * d["I"] * d["W"]
        nbytes = 4.0 * (d["I"] * d["W"] + d["W"] + d["I"] * d["I"])
    elif family == "subset":
        flops = 8.0 * d["Q"] * d["F"] * d["IW"]
        nbytes = 4.0 * ((d["Q"] + d["F"]) * d["IW"] + 2.0 * d["Q"] * d["F"])
    elif family == "delta":
        flops = 4.0 * d["S"] * d["T"] * d["F"] * d["IW"]
        nbytes = 4.0 * (
            d["S"] * d["T"] * d["IW"] + d["F"] * d["IW"] + d["S"] * d["F"]
        )
    else:
        raise ValueError(f"unknown kernel family: {family!r}")
    return flops, nbytes


def _pow2(n: int) -> int:
    """Round up to a power of two (≥ 1) — the shape-bucket resolution."""
    n = int(n)
    if n <= 1:
        return 1
    return 1 << (n - 1).bit_length()


def _bucket_label(family: str, dims: Dict[str, int]) -> str:
    parts = ",".join(f"{k}={_pow2(dims[k])}" for k in DIM_ORDER[family])
    return f"{family}[{parts}]"


class _Bucket:
    """Accumulator for one (family, pow2-shape) bucket."""

    __slots__ = (
        "calls", "loop_execs", "wall_s", "loop_wall_s",
        "flops", "bytes", "min_s", "max_s",
    )

    def __init__(self):
        self.calls = 0          # eager, individually timed dispatches
        self.loop_execs = 0     # while_loop-attributed executions
        self.wall_s = 0.0       # summed device-synced eager wall time
        self.loop_wall_s = 0.0  # wall time attributed by observe_loop
        self.flops = 0.0        # modeled word-ops across all executions
        self.bytes = 0.0        # modeled bytes across all executions
        self.min_s = float("inf")
        self.max_s = 0.0


class KernelProfiler:
    """Per-(family, shape-bucket) timing + roofline cost attribution.

    Thread-safe (the store prefetch thread and serve replicas dispatch
    kernels concurrently with the main loop).  All recording methods are
    no-ops while disabled; the ops-layer fast path additionally skips the
    method call entirely behind the :attr:`enabled` attribute check.
    """

    def __init__(self, machine: Optional[MachineModel] = None):
        self.enabled = False          # read directly by the ops wrapper
        self._machine = machine       # None → by device_kind, lazily
        self._buckets: Dict[Tuple[str, str], _Bucket] = {}
        self._traced: Dict[str, int] = {}   # family -> trace-time dispatches
        self._lock = threading.Lock()

    # -- lifecycle -----------------------------------------------------------
    def enable(self, machine: Optional[MachineModel] = None) -> None:
        if machine is not None:
            self._machine = machine
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def clear(self) -> None:
        with self._lock:
            self._buckets.clear()
            self._traced.clear()

    @property
    def machine(self) -> MachineModel:
        if self._machine is None:
            import jax

            self._machine = machine_for_device_kind(jax.devices()[0].device_kind)
        return self._machine

    # -- recording -----------------------------------------------------------
    def call(self, family: str, dims: Dict[str, int], thunk: Callable):
        """Run ``thunk`` with device-synced timing (the eager path).

        Under jit tracing the output is abstract and cannot be waited on;
        the dispatch is tallied as trace-time only and the real executions
        must be attributed via :meth:`observe_loop` by whoever runs the
        compiled loop.
        """
        if not self.enabled:
            return thunk()
        import jax

        t0 = time.monotonic()
        out = thunk()
        leaf = out[0] if isinstance(out, tuple) else out
        if isinstance(leaf, jax.core.Tracer):
            with self._lock:
                self._traced[family] = self._traced.get(family, 0) + 1
            return out
        jax.block_until_ready(out)
        self.record_call(family, dims, time.monotonic() - t0)
        return out

    def record_call(self, family: str, dims: Dict[str, int], wall_s: float) -> None:
        """Account one timed eager execution of ``family`` at ``dims``."""
        if not self.enabled:
            return
        flops, nbytes = cost_model(family, dims)
        label = _bucket_label(family, dims)
        with self._lock:
            b = self._buckets.setdefault((family, label), _Bucket())
            b.calls += 1
            b.wall_s += wall_s
            b.flops += flops
            b.bytes += nbytes
            b.min_s = min(b.min_s, wall_s)
            b.max_s = max(b.max_s, wall_s)
        obs_metrics.registry().histogram(
            f"kernels/{family}/call_us/{label}"
        ).record(wall_s * 1e6)

    def observe_loop(
        self, family: str, dims: Dict[str, int], n_exec: int, wall_s: float
    ) -> None:
        """Attribute ``n_exec`` in-loop executions covered by ``wall_s``.

        For kernels compiled into ``lax.while_loop`` bodies: the driver
        knows the trip count (``work_iters``) and the phase wall clock; the
        cost model per trip comes from ``dims`` exactly as for eager calls.
        """
        if not self.enabled or n_exec <= 0:
            return
        flops, nbytes = cost_model(family, dims)
        label = _bucket_label(family, dims)
        with self._lock:
            b = self._buckets.setdefault((family, label), _Bucket())
            b.loop_execs += int(n_exec)
            b.loop_wall_s += float(wall_s)
            b.flops += flops * n_exec
            b.bytes += nbytes * n_exec

    # -- reporting -----------------------------------------------------------
    def report(self) -> dict:
        """Measured-vs-modeled attribution, per family and per bucket."""
        m = self.machine
        with self._lock:
            items = [(k, b) for k, b in self._buckets.items()]
            traced = dict(self._traced)
        families: Dict[str, dict] = {}
        for (family, label), b in sorted(items):
            compute_s = b.flops / m.word_ops_peak
            memory_s = b.bytes / m.hbm_bw
            modeled_s = max(compute_s, memory_s)
            measured_s = b.wall_s + b.loop_wall_s
            fam = families.setdefault(
                family,
                {
                    "calls": 0, "loop_execs": 0, "measured_ms": 0.0,
                    "flops": 0.0, "bytes": 0.0,
                    "compute_ms": 0.0, "memory_ms": 0.0, "modeled_ms": 0.0,
                    "trace_dispatches": traced.get(family, 0),
                    "buckets": [],
                },
            )
            fam["calls"] += b.calls
            fam["loop_execs"] += b.loop_execs
            fam["measured_ms"] += measured_s * 1e3
            fam["flops"] += b.flops
            fam["bytes"] += b.bytes
            fam["compute_ms"] += compute_s * 1e3
            fam["memory_ms"] += memory_s * 1e3
            fam["modeled_ms"] += modeled_s * 1e3
            fam["buckets"].append(
                {
                    "bucket": label,
                    "calls": b.calls,
                    "loop_execs": b.loop_execs,
                    "measured_ms": measured_s * 1e3,
                    "modeled_ms": modeled_s * 1e3,
                    "compute_ms": compute_s * 1e3,
                    "memory_ms": memory_s * 1e3,
                    "min_us": (b.min_s * 1e6) if b.calls else None,
                    "max_us": (b.max_s * 1e6) if b.calls else None,
                }
            )
        for family in traced:
            families.setdefault(
                family,
                {
                    "calls": 0, "loop_execs": 0, "measured_ms": 0.0,
                    "flops": 0.0, "bytes": 0.0,
                    "compute_ms": 0.0, "memory_ms": 0.0, "modeled_ms": 0.0,
                    "trace_dispatches": traced[family],
                    "buckets": [],
                },
            )
        for fam in families.values():
            measured = fam["measured_ms"]
            fam["achieved_frac"] = (
                fam["modeled_ms"] / measured if measured > 0 else None
            )
            fam["mem_bound"] = fam["memory_ms"] > fam["compute_ms"]
        return {
            "machine": {
                "name": m.name,
                "peak_flops": m.peak_flops,
                "hbm_bw": m.hbm_bw,
                "link_bw": m.link_bw,
                "word_ops_peak": m.word_ops_peak,
            },
            "families": families,
        }

    def publish(self, reg: Optional[obs_metrics.MetricsRegistry] = None) -> dict:
        """Export the report as counters/gauges so it rides the run record.

        Gauge scheme (all consumed jax-free by ``obs_report kernels``)::

            kernels/machine/{word_ops_peak, hbm_bw, peak_flops}
            kernels/<family>/{measured_ms, modeled_ms, compute_ms,
                              memory_ms, flops, bytes, achieved_frac,
                              mem_bound}
            kernels/<family>/{calls, loop_execs}          (counters)
        """
        reg = reg or obs_metrics.registry()
        rep = self.report()
        for k, v in rep["machine"].items():
            if k != "name":
                reg.gauge(f"kernels/machine/{k}").set(float(v))
        for family, fam in rep["families"].items():
            reg.counter(f"kernels/{family}/calls").inc(fam["calls"])
            reg.counter(f"kernels/{family}/loop_execs").inc(fam["loop_execs"])
            for k in (
                "measured_ms", "modeled_ms", "compute_ms", "memory_ms",
                "flops", "bytes",
            ):
                reg.gauge(f"kernels/{family}/{k}").set(float(fam[k]))
            if fam["achieved_frac"] is not None:
                reg.gauge(f"kernels/{family}/achieved_frac").set(
                    float(fam["achieved_frac"])
                )
            reg.gauge(f"kernels/{family}/mem_bound").set(
                1.0 if fam["mem_bound"] else 0.0
            )
        return rep


#: The process-global profiler the ops layer checks on every dispatch.
PROFILER = KernelProfiler()


def profiler() -> KernelProfiler:
    return PROFILER

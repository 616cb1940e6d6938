"""Unified observability layer: metrics registry, span tracing, run records.

Zero-dependency (stdlib-only at import; jax strictly lazy) so it is usable
from every layer — kernels' host glue, the store's prefetch thread, the
jax-free report CLI.  See DESIGN.md, "Observability".

  * :mod:`repro.obs.metrics` — process-global counters / gauges /
    log-bucketed latency histograms, one canonical snapshot shape;
  * :mod:`repro.obs.trace`   — nested host spans, Chrome trace-event
    export (Perfetto), device ``sync`` helper, ``jax_profiler`` hook;
  * :mod:`repro.obs.runlog`  — per-run manifest + JSONL events + metrics
    snapshot, read back by ``launch/obs_report.py``;
  * :mod:`repro.obs.session` — the shared ``--trace`` / ``--metrics``
    driver glue (crash-safe: atexit/SIGTERM partial flush);
  * :mod:`repro.obs.slo`     — sliding-window histograms/counters and the
    SLO policy engine (windowed p50/p95/p99/QPS/shed-rate, error-budget
    burn-rate alerts with hysteresis) behind the serving front end;
  * :mod:`repro.obs.machine` — the shared roofline machine constants;
  * :mod:`repro.obs.profile` — the kernel profiler: per-dispatch-family
    measured-vs-modeled time attribution and bound-ness verdicts;
  * :mod:`repro.obs.progress` — the sample-grounded live progress/ETA
    estimator fed by planner loads and observed DFS trips;
  * :mod:`repro.obs.perfdb`  — the persistent perf trajectory
    (``BENCH_HISTORY.jsonl`` append / trend / regression check);
  * :mod:`repro.obs.critpath` — span-DAG reconstruction over a run
    record's ``trace.json``: critical path + exclusive self-time;
  * :mod:`repro.obs.speedup` — the additive speedup-loss waterfall
    (imbalance / Thm 6.1 estimation error / exchange / compile / host);
  * :mod:`repro.obs.doctor`  — the rules engine turning snapshot +
    critical path + waterfall into ranked findings with evidence keys.
"""
from repro.obs.critpath import SpanDag, critical_path  # noqa: F401
from repro.obs.doctor import Finding, Thresholds, diagnose  # noqa: F401
from repro.obs.machine import MachineModel, machine_for_device_kind  # noqa: F401
from repro.obs.metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    registry,
    snapshot,
)
from repro.obs.profile import KernelProfiler, cost_model, profiler  # noqa: F401
from repro.obs.perfdb import check_regressions, trends  # noqa: F401
from repro.obs.progress import ProgressEstimator, ProgressSnapshot  # noqa: F401
from repro.obs.runlog import RunLog, load_run  # noqa: F401
from repro.obs.speedup import LossTerm, Waterfall  # noqa: F401
from repro.obs.slo import (  # noqa: F401
    SLOPolicy,
    SLOStatus,
    SLOTracker,
    WindowedCounter,
    WindowedHistogram,
)
from repro.obs.trace import TRACER, Tracer, tracer  # noqa: F401

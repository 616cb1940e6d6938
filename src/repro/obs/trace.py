"""Host-side span tracing with Chrome trace-event export (Perfetto-loadable).

A :class:`Tracer` records nested wall-clock spans on any thread — the main
mining loop, the :class:`~repro.store.reader.BlockReader` prefetch worker,
the serving path — against one shared monotonic clock, and exports the
Chrome trace-event JSON that ``ui.perfetto.dev`` / ``chrome://tracing``
render as a per-thread timeline.  Three event flavors:

  * ``span(name, **args)`` — a context manager recording one complete
    ("ph": "X") event; nesting is by time containment per thread, exactly
    how the trace viewers stack them;
  * ``add_span(...)`` — a raw event on a *virtual* track (e.g. the
    executor's modeled per-shard mining lanes, one track per shard);
  * ``instant(name, **args)`` — a zero-duration marker ("ph": "i") for
    point events like drift triggers;
  * ``counter(name, **values)`` — a counter-track sample ("ph": "C") for
    live gauges (mining progress %, serve queue depth, host-bytes
    high-water) rendered as area/line tracks alongside the spans.

Device timing: JAX dispatch is asynchronous, so a host span around a
dispatch measures enqueue, not execution.  ``sync(value, name)`` closes the
gap — **only when tracing is enabled** it blocks on the value inside a
span, so the enclosing phase span covers real device time; when disabled it
returns the value untouched and the pipeline stays fully async (the
disabled path must not change execution).  ``jax_profiler(log_dir)`` is the
opt-in escape hatch to the real profiler (TensorBoard/XProf) when
op-level device detail is needed.

Profiler clock: while tracing, every ``span`` also enters a
``jax.profiler.TraceAnnotation`` of the same name (when the process has
imported jax; the tracer never imports it), so a profiler session records
the program's phases on the device trace's clock.  The tracer's own clock
stays ``time.monotonic()``, ``ts`` in µs from the zero ``clear()`` sets.

Counters on spans: ``with tr.span(name) as sp: ... sp.set(trips=n)`` adds
numeric args before the span is recorded; the disabled span's ``set`` does
nothing, so callers read device values for args only under
``if tr.enabled:``.

Compiles: while ``TRACER`` traces, each XLA backend compile (or load from
the persistent compile cache) is recorded as a host span ``jax/compile``
(arg ``fun``) on the compiling thread, from jax's
``/jax/core/compile/backend_compile_duration`` event; the listener is
registered by the first ``enable()`` after the process imported jax.

The disabled fast path is a single attribute check returning a shared
no-op context manager — no allocation, no clock read, no lock
(benchmarked in ``benchmarks/io.py``: streamed-mine overhead with
everything enabled is gated < 5 %; disabled is in the noise).
"""
from __future__ import annotations

import json
import sys
import threading
import time
from collections import deque
from typing import Deque, Dict, Optional

#: Default event-buffer cap.  Long soak runs (``serve_load``) otherwise grow
#: the buffer — and the exported trace.json — without bound; at the cap the
#: oldest events are dropped (the *recent* timeline is the diagnostic one)
#: and the drop is accounted: a ``trace/dropped_events`` counter plus a
#: ``truncated_events`` note in the exported JSON, which the doctor's
#: ``trace-truncated`` rule surfaces.
DEFAULT_MAX_EVENTS = 500_000

#: jax's monitoring event for one XLA backend compile.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class _NullSpan:
    """Shared do-nothing context manager — the disabled fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        pass


_NULL_SPAN = _NullSpan()


def _jax():
    """jax if this process has imported it, else None (never imports it)."""
    return sys.modules.get("jax")


class _Span:
    __slots__ = ("_tracer", "_name", "_args", "_t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str, args: Optional[dict]):
        self._tracer = tracer
        self._name = name
        self._args = args
        self._t0 = 0.0
        self._ann = None

    def __enter__(self):
        jax = _jax()
        if jax is not None:
            self._ann = jax.profiler.TraceAnnotation(self._name)
            self._ann.__enter__()
        self._t0 = time.monotonic()
        return self

    def set(self, **args) -> None:
        """Add numeric args to the span before it is recorded."""
        self._args = {**(self._args or {}), **args}

    def __exit__(self, *exc):
        t1 = time.monotonic()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        self._tracer._record(self._name, self._t0, t1 - self._t0, self._args)
        return False


class Tracer:
    """Thread-safe span recorder with Chrome trace-event JSON export."""

    def __init__(
        self, enabled: bool = False, max_events: int = DEFAULT_MAX_EVENTS
    ):
        self._enabled = enabled
        self._max_events = max(1, int(max_events))
        self._events: Deque[dict] = deque(maxlen=self._max_events)
        self._dropped = 0
        self._t_base = time.monotonic()
        self._track_names: Dict[int, str] = {}
        self._lock = threading.Lock()

    # -- lifecycle -----------------------------------------------------------
    @property
    def enabled(self) -> bool:
        return self._enabled

    def enable(self) -> None:
        self._enabled = True
        jax = _jax()
        if jax is not None:
            _listen_for_compiles(jax)

    def disable(self) -> None:
        self._enabled = False

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._track_names.clear()
            self._dropped = 0
        self._t_base = time.monotonic()

    def set_max_events(self, max_events: int) -> None:
        """Re-cap the buffer (keeping the newest events that still fit)."""
        with self._lock:
            self._max_events = max(1, int(max_events))
            old = self._events
            self._dropped += max(0, len(old) - self._max_events)
            self._events = deque(old, maxlen=self._max_events)

    @property
    def max_events(self) -> int:
        return self._max_events

    @property
    def dropped_events(self) -> int:
        with self._lock:
            return self._dropped

    def _append_locked(self, ev: dict) -> None:
        # deque(maxlen) silently evicts the oldest; account for it first
        if len(self._events) == self._max_events:
            self._dropped += 1
            from repro.obs import metrics as _metrics  # lazy: cold path only

            _metrics.registry().counter("trace/dropped_events").inc()
        self._events.append(ev)

    # -- recording -----------------------------------------------------------
    def span(self, name: str, **args):
        """Context manager timing one nested span on the calling thread."""
        if not self._enabled:
            return _NULL_SPAN
        return _Span(self, name, args or None)

    def _tid(self) -> int:
        t = threading.current_thread()
        tid = t.ident or 0
        if tid not in self._track_names:       # benign race: same value
            self._track_names[tid] = t.name
        return tid

    def _record(self, name, t0, dur_s, args, tid=None, cat="host"):
        ev = {
            "ph": "X",
            "name": name,
            "cat": cat,
            "pid": 0,
            "tid": self._tid() if tid is None else tid,
            "ts": (t0 - self._t_base) * 1e6,
            "dur": dur_s * 1e6,
        }
        if args:
            ev["args"] = args
        with self._lock:
            self._append_locked(ev)

    def add_span(
        self,
        name: str,
        t0: float,
        dur_s: float,
        *,
        track: str,
        cat: str = "modeled",
        args: Optional[dict] = None,
    ) -> None:
        """Record a span on a named virtual track (``t0`` from
        ``time.monotonic()``).  Used for modeled lanes — e.g. per-shard
        mining spans whose duration is apportioned from trip telemetry."""
        if not self._enabled:
            return
        tid = 1_000_000 + (hash(track) & 0xFFFF)
        if tid not in self._track_names:
            self._track_names[tid] = track
        self._record(name, t0, dur_s, args, tid=tid, cat=cat)

    def counter(self, name: str, **values) -> None:
        """A Chrome counter sample ("ph": "C") — renders as a counter track.

        Each call appends one sample of the named counter series; Perfetto
        draws the series as a stacked area/line track (one lane per key in
        ``values``).  Used for the live gauges worth seeing against the
        span timeline: mining progress %, serve queue depth, host-bytes
        high-water.  Values must be numeric."""
        if not self._enabled:
            return
        ev = {
            "ph": "C",
            "name": name,
            "cat": "counter",
            "pid": 0,
            "tid": self._tid(),
            "ts": (time.monotonic() - self._t_base) * 1e6,
            "args": {k: float(v) for k, v in values.items()},
        }
        with self._lock:
            self._append_locked(ev)

    def instant(self, name: str, **args) -> None:
        """A zero-duration marker event (drift fired, checkpoint saved…)."""
        if not self._enabled:
            return
        ev = {
            "ph": "i",
            "s": "t",
            "name": name,
            "cat": "event",
            "pid": 0,
            "tid": self._tid(),
            "ts": (time.monotonic() - self._t_base) * 1e6,
        }
        if args:
            ev["args"] = args
        with self._lock:
            self._append_locked(ev)

    # -- device helper -------------------------------------------------------
    def sync(self, value, name: str = "device_sync"):
        """Block on a JAX value inside a span — ONLY when tracing.

        The disabled path returns ``value`` untouched (no import, no sync):
        tracing must never change how the async pipeline executes when off.
        """
        if not self._enabled:
            return value
        import jax

        with self.span(name, cat="device"):
            return jax.block_until_ready(value)

    # -- export --------------------------------------------------------------
    @property
    def n_events(self) -> int:
        with self._lock:
            return len(self._events)

    def export(self) -> dict:
        """The Chrome trace-event object (Perfetto/chrome://tracing)."""
        with self._lock:
            events = list(self._events)
            tracks = dict(self._track_names)
            dropped = self._dropped
        meta = [
            {
                "ph": "M",
                "name": "thread_name",
                "pid": 0,
                "tid": tid,
                "args": {"name": tname},
            }
            for tid, tname in sorted(tracks.items())
        ]
        out = {"traceEvents": meta + events, "displayTimeUnit": "ms"}
        if dropped:
            out["truncated_events"] = dropped   # oldest `dropped` evicted
        return out

    def write(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.export(), f)
        return path


#: The process-global tracer every subsystem records into by default.
TRACER = Tracer()

_listening = False


def _on_jax_event(event: str, start: float, end: float, **kw) -> None:
    """jax monitoring listener: one ``jax/compile`` span per backend compile
    while ``TRACER`` is on, ending now and lasting the compile's duration."""
    if event != COMPILE_EVENT or not TRACER.enabled:
        return
    dur = end - start
    TRACER._record("jax/compile", time.monotonic() - dur, dur,
                   {"fun": str(kw.get("fun_name", ""))})


def _listen_for_compiles(jax) -> None:
    """Register :func:`_on_jax_event` with jax once per process."""
    global _listening
    if not _listening:
        _listening = True
        jax.monitoring.register_event_time_span_listener(_on_jax_event)


def tracer() -> Tracer:
    return TRACER


class jax_profiler:
    """Opt-in ``jax.profiler.trace`` hook (TensorBoard/XProf log dir).

    Complements the host tracer with op-level device timing; a context
    manager so drivers can hold it across the whole run::

        with obs_trace.jax_profiler(log_dir):
            ... mine ...
    """

    def __init__(self, log_dir: str):
        self.log_dir = log_dir

    def __enter__(self):
        import jax

        jax.profiler.start_trace(self.log_dir)
        return self

    def __exit__(self, *exc):
        import jax

        jax.profiler.stop_trace()
        return False

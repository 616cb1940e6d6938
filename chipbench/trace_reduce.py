"""The one reduction from a profiler trace to the benchmark's device numbers.

A ``--trace 1`` run writes an ``.xplane.pb`` under its trace directory.
:func:`load` streams it once (a mine's trace holds millions of op events)
into a :class:`DeviceTrace`:

* the window is the harness's own ``chipbench.window`` annotation on the
  host plane, cut short where the device tracer reports dropped buffers
  (line ``XLA TraceMe``), so that it covers only what was recorded.  A run
  may profile several segments, each its own session and file;
  :class:`Segments` reads them as one;
* busy time is the union of the intervals in which an XLA op ran on a chip
  (line ``XLA Ops`` of ``/device:TPU:<n>``; a loop's own event covers its
  body), clipped to the window and averaged over the cell's chips; the
  idle share is 1 minus busy over window;
* kernels are the ops that are Pallas calls (``tpu_custom_call``); an op's
  event name is its HLO text, so a kernel is found by its entry point's name
  and carries its operand shapes;
* each idle gap of 10 us or more is labelled with what the host was doing
  across it: the innermost covering interval among the harness's own
  annotations and the program's host spans (put on the profiler's clock
  through the window annotation).  Shorter gaps are summed under one label.
"""
from __future__ import annotations

import bisect
import glob
import os
from array import array
from dataclasses import dataclass, field
from functools import cached_property

import numpy as np

WINDOW = "chipbench.window"
OPS_LINE = "XLA Ops"
TRACEME_LINE = "XLA TraceMe"
DEVICE_PREFIX = "/device:TPU:"
KERNEL_MARK = "tpu_custom_call"
SHORT_GAP_NS = 10_000.0
SHORT_GAPS = "gaps under 10 us"


def op_label(name: str) -> str:
    """A short, stable label for an op: its HLO name and result shape."""
    head = name.split("{", 1)[0] if name.startswith("%") else name
    return head[:120]


@dataclass
class Chip:
    """One chip's op intervals inside the window."""

    starts: np.ndarray
    ends: np.ndarray
    by_op: dict               # op label -> ns inside the window
    kernels: list             # (name, start, end) of Pallas calls

    def merged(self):
        """Disjoint busy intervals, sorted: (starts, ends)."""
        if len(self.starts) == 0:
            return self.starts, self.ends
        order = np.argsort(self.starts, kind="stable")
        s, e = self.starts[order], self.ends[order]
        reach = np.maximum.accumulate(e)
        new = np.empty(len(s), bool)
        new[0] = True
        new[1:] = s[1:] > reach[:-1]
        first = np.flatnonzero(new)
        last = np.append(first[1:], len(s)) - 1
        return s[first], reach[last]


@dataclass
class DeviceTrace:
    window: tuple                 # (start_ns, end_ns) on the profiler clock
    chips: list                   # Chip per chip of the cell
    labels: list = field(default_factory=list)  # (start, end, label)
    dropped_ns: float = 0.0       # window time the tracer did not record

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds in which some op ran, averaged over the chips."""
        if not self.chips:
            return 0.0
        total = 0.0
        for chip in self.chips:
            s, e = chip.merged()
            total += float(np.sum(e - s))
        return total / len(self.chips) / 1e9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def events(self, match: str) -> list:
        """``(name, start, end)`` of the Pallas calls whose HLO text contains
        ``match``, on any chip of the cell."""
        return [k for chip in self.chips for k in chip.kernels
                if match in k[0]]

    def gaps(self, chip: int = 0):
        """Idle (starts, ends) of one chip inside the window."""
        w0, w1 = self.window
        s, e = self.chips[chip].merged()
        gs = np.concatenate([[w0], e])
        ge = np.concatenate([s, [w1]])
        keep = ge > gs
        return gs[keep], ge[keep]

    def label(self, t: float) -> str:
        """The innermost labelled host interval that covers time ``t``."""
        labels, starts = self._by_start
        i = bisect.bisect_right(starts, t) - 1
        while i >= 0:
            s, e, name = labels[i]
            if e >= t:
                return name
            i -= 1
        return "unlabelled"

    @cached_property
    def _by_start(self):
        labels = sorted(self.labels)
        return labels, [lab[0] for lab in labels]

    def op_seconds(self) -> dict:
        """Device seconds by op label, summed over the chips."""
        by_op: dict = {}
        for chip in self.chips:
            for k, v in chip.by_op.items():
                by_op[k] = by_op.get(k, 0.0) + v / 1e9
        return by_op

    def gap_seconds(self) -> dict:
        """Idle seconds of the first chip by what the host was doing."""
        by_gap: dict = {}
        if self.chips:
            gs, ge = self.gaps(0)
            short = (ge - gs) < SHORT_GAP_NS
            if short.any():
                by_gap[SHORT_GAPS] = float(np.sum((ge - gs)[short])) / 1e9
            for s, e in zip(gs[~short], ge[~short]):
                lab = self.label((s + e) / 2)
                by_gap[lab] = by_gap.get(lab, 0.0) + (e - s) / 1e9
        return by_gap


def _add(dicts) -> dict:
    out: dict = {}
    for d in dicts:
        for k, v in d.items():
            out[k] = out.get(k, 0.0) + v
    return out


@dataclass
class Segments:
    """The traced segments of one run, read as one: windows, busy time and
    kernel events add up."""

    parts: list                  # DeviceTrace per profiler session

    @property
    def window_s(self) -> float:
        return sum(p.window_s for p in self.parts)

    @property
    def busy_s(self) -> float:
        return sum(p.busy_s for p in self.parts)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def events(self, match: str) -> list:
        return [e for p in self.parts for e in p.events(match)]

    def breakdown(self, top: int = 10) -> dict:
        """Top device ops by time, and idle time by what the host did."""
        rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]  # noqa: E731
        ops = _add(p.op_seconds() for p in self.parts)
        gaps = _add(p.gap_seconds() for p in self.parts)
        return {"device_ops": [[k, v] for k, v in rank(ops)],
                "idle_gaps": [[k, v] for k, v in rank(gaps)]}


def _chip(events, window) -> Chip:
    """Reduce one chip's ``(name, start_ns, dur_ns)`` op events."""
    w0, w1 = window
    starts, ends = array("d"), array("d")
    by_op: dict = {}
    kernels = []
    for name, s, d in events:
        e = s + d
        if e <= w0 or s >= w1:
            continue
        s, e = max(s, w0), min(e, w1)
        starts.append(s)
        ends.append(e)
        by_op[name] = by_op.get(name, 0.0) + (e - s)
        if KERNEL_MARK in name:
            kernels.append((name, s, e))
    short: dict = {}
    for name, t in by_op.items():
        lab = op_label(name)
        short[lab] = short.get(lab, 0.0) + t
    return Chip(np.frombuffer(starts, np.float64),
                np.frombuffer(ends, np.float64), short, kernels)


def reduce(planes, n_chips: int, spans=(), span_offset_s: float = 0.0
           ) -> DeviceTrace:
    """Reduce planes to a :class:`DeviceTrace`.

    ``planes`` are ``{"name", "lines": [{"name", "events": [(name,
    start_ns, dur_ns), ...]}]}``; events may be any iterable, read once.
    ``spans`` are the program's host spans (Chrome trace events, ``ts`` and
    ``dur`` in microseconds from the tracer's zero); ``span_offset_s`` is
    how long after that zero the window annotation began.
    """
    planes = list(planes)
    window, labels, drops = None, [], []
    for plane in planes:
        if plane["name"].startswith(DEVICE_PREFIX):
            for line in plane["lines"]:
                if line["name"] == TRACEME_LINE:
                    drops += [(s, s + d) for n, s, d in line["events"]
                              if "Dropped" in n]
            continue
        for line in plane["lines"]:
            for n, s, d in line["events"]:
                if n == WINDOW:
                    window = (s, s + d)
                elif n.startswith("chipbench."):
                    labels.append((s, s + d, n[len("chipbench."):]))
    if window is None:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    w0, w1 = window
    lost = [(max(s, w0), min(e, w1)) for s, e in drops if e > w0 and s < w1]
    if lost:
        w1 = min(s for s, _ in lost)
    chips = [Chip(np.zeros(0), np.zeros(0), {}, []) for _ in range(n_chips)]
    for plane in planes:
        idx = plane["name"][len(DEVICE_PREFIX):]
        if not plane["name"].startswith(DEVICE_PREFIX) or not idx.isdigit():
            continue
        if int(idx) >= n_chips:
            continue
        for line in plane["lines"]:
            if line["name"] == OPS_LINE:
                chips[int(idx)] = _chip(line["events"], (w0, w1))
    for ev in spans:
        if ev.get("cat", "host") != "host":
            continue      # modeled lanes and per-request queue waits
        s = window[0] + (ev["ts"] / 1e6 - span_offset_s) * 1e9
        labels.append((s, s + ev["dur"] * 1e3, ev["name"]))
    return DeviceTrace(window=(w0, w1), chips=chips, labels=labels,
                       dropped_ns=sum(e - s for s, e in lost))


def _xplane_planes(pd):
    """Stream a ``ProfileData`` as the plain planes :func:`reduce` reads."""
    for plane in pd.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        if not device and not plane.name.startswith("/host:"):
            continue
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, TRACEME_LINE):
                continue
            events = ((e.name, e.start_ns, e.duration_ns)
                      for e in line.events)
            if not device:
                events = [ev for ev in events
                          if ev[0].startswith("chipbench.")]
            lines.append({"name": line.name, "events": events})
        yield {"name": plane.name, "lines": lines}


def load(trace_dir, n_chips: int, span_offset_s: float = 0.0, spans=()
         ) -> DeviceTrace:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    return reduce(_xplane_planes(pd), n_chips, spans, span_offset_s)


@dataclass
class Reading:
    """What a per-layer metric reader gets."""

    spans: list            # the program's host spans in the traced window
    device: Segments
    layer_data: dict       # what the runner measured itself
    config: dict
    traffic: dict
    peaks: dict            # this device kind's row of peaks.json

    def span_ms(self, name: str) -> list:
        return [ev["dur"] / 1e3 for ev in self.spans if ev["name"] == name]

    def per_mine_ms(self, name: str):
        """Total time in the program's span ``name`` per traced mine."""
        mines = self.layer_data.get("mines", 0)
        got = self.span_ms(name)
        return sum(got) / mines if got and mines else None

    def hbm_roofline(self, match: str, least_bytes) -> float | None:
        """Percent of its HBM roofline a kernel reached: the least bytes its
        events must move (``least_bytes(name)``) at peak bandwidth, over the
        time they took."""
        evs = self.device.events(match)
        busy = sum(e - s for _, s, e in evs)
        if not evs or busy <= 0:
            return None
        need = sum(least_bytes(name) for name, _, _ in evs)
        return 100.0 * need / self.peaks["hbm_bytes_per_s"] / (busy / 1e9)

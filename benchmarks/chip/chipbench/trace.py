"""Reduce a profiler trace of the window to what the metric readers need.

The traced run reads its own trace in its own process
(``jax.profiler.ProfileData``) and prints numbers, never the trace.  It
keeps:

* device operations: every event on the ``XLA Ops`` line of each device
  plane (``/device:TPU:<n>``), as start and end in nanoseconds and a name;
* where the device's trace buffer filled up: the start of the ``Trace
  Buffers Dropped`` event on its ``XLA TraceMe`` line.  A v5e holds about
  6 million operation events (about 40 s of an f64 TLR evaluation, 0.7 s of
  cokriging requests), so metrics over time use only the part of the
  window the trace covers;
* host spans: the benchmark's own ``jax.profiler.TraceAnnotation`` events
  (``window``, ``eval``, ``request``), on the same clock.

Busy time is the union of the operation intervals, so nested operations
(a loop and the operations of its body) and overlapping ones count once.
"""
from __future__ import annotations

import dataclasses
import glob
import math
import os
from array import array

import numpy as np

DEVICE_PLANE_PREFIX = "/device:TPU:"
DEVICE_OP_LINE = "XLA Ops"
DEVICE_NOTE_LINE = "XLA TraceMe"
DROPPED = "Trace Buffers Dropped"
HOST_PLANE = "/host:CPU"
SPANS = ("window", "eval", "request")
NAME_CHARS = 160      # of an HLO instruction's text, in the breakdown


@dataclasses.dataclass
class Ops:
    """One device's operations, sorted by start (ties: longest first)."""
    start: np.ndarray      # ns, float64
    end: np.ndarray
    name: np.ndarray       # int index into ``names``
    names: list
    covered_end: float = math.inf   # the trace holds nothing after this

    @classmethod
    def build(cls, start, end, name, names, covered_end=math.inf):
        start, end = np.asarray(start, float), np.asarray(end, float)
        order = np.lexsort((-end, start))
        return cls(start[order], end[order],
                   np.asarray(name, np.int64)[order], names, covered_end)

    def _in(self, lo: float, hi: float):
        keep = (self.end > lo) & (self.start < hi)
        return (np.maximum(self.start[keep], lo),
                np.minimum(self.end[keep], hi), self.name[keep])

    def busy_segments(self, lo: float, hi: float):
        """(starts, ends) of the union of the operations inside [lo, hi]."""
        s, e, _ = self._in(lo, hi)
        if not len(s):
            return np.empty(0), np.empty(0)
        reach = np.maximum.accumulate(e)
        new = np.ones(len(s), bool)
        new[1:] = s[1:] > reach[:-1]
        first = np.flatnonzero(new)
        last = np.append(first[1:] - 1, len(s) - 1)
        return s[first], reach[last]

    def busy_ns(self, lo: float, hi: float) -> float:
        s, e = self.busy_segments(lo, hi)
        return float(np.sum(e - s))

    def count(self, lo: float, hi: float) -> int:
        return int(np.count_nonzero((self.start >= lo) & (self.start < hi)))

    def leaf_time(self, lo: float, hi: float) -> dict:
        """{name: ns} over operations that contain no other operation (a
        loop's own event holds its body's, so only leaves are summed)."""
        s, e, n = self._in(lo, hi)
        if not len(s):
            return {}
        leaf = np.ones(len(s), bool)
        leaf[:-1] = s[1:] >= e[:-1]
        sums = np.bincount(n[leaf], weights=(e - s)[leaf],
                           minlength=len(self.names))
        return {self.names[i]: float(sums[i]) for i in np.flatnonzero(sums)}


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float

    @property
    def ns(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    devices: list            # [Ops], one per device plane
    spans: list              # [Span], sorted by start

    @property
    def covered_end(self) -> float:
        return min((d.covered_end for d in self.devices), default=math.inf)

    def named(self, name: str) -> list:
        return [s for s in self.spans if s.name == name]

    def covered(self, name: str) -> list:
        """Spans ``name`` that end before the device trace does."""
        return [s for s in self.named(name) if s.end <= self.covered_end]

    def window(self) -> Span | None:
        """The traced window: the ``window`` span, cut where the device
        trace ends."""
        spans = self.named("window")
        if not spans or not self.devices:
            return None
        w = spans[0]
        end = min(w.end, self.covered_end)
        return Span(w.name, w.start, end) if end > w.start else None

    def busy_ns(self, lo: float, hi: float) -> float:
        """Busy time in [lo, hi], averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(d.busy_ns(lo, hi) for d in self.devices) / len(self.devices)

    def op_count(self, lo: float, hi: float) -> float:
        if not self.devices:
            return 0.0
        return sum(d.count(lo, hi) for d in self.devices) / len(self.devices)

    def top_ops(self, lo: float, hi: float, k: int = 10) -> list:
        """[[name, seconds]]: device time of the operations that took most,
        leaves only, averaged over the devices."""
        tot: dict = {}
        for d in self.devices:
            for n, ns in d.leaf_time(lo, hi).items():
                n = n[:NAME_CHARS]
                tot[n] = tot.get(n, 0.0) + ns / len(self.devices)
        best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, s / 1e9] for n, s in best]

    def idle_gaps(self, lo: float, hi: float, k: int = 10) -> list:
        """[[label, seconds]]: the longest stretches of [lo, hi] in which
        the first device ran nothing, each labelled by the innermost host
        span around its middle (what the host was doing then)."""
        if not self.devices:
            return []
        s, e = self.devices[0].busy_segments(lo, hi)
        starts = np.concatenate([[lo], e])
        ends = np.concatenate([s, [hi]])
        length = ends - starts
        top = np.argsort(-length)[:k]
        return [[self.label((starts[i] + ends[i]) / 2), length[i] / 1e9]
                for i in top if length[i] > 0]

    def label(self, t: float) -> str:
        inner = None
        for s in self.spans:
            if s.start <= t <= s.end and (inner is None or s.ns < inner.ns):
                inner = s
        return inner.name if inner else "outside"


def _device(plane) -> Ops | None:
    start, end, name = array("d"), array("d"), array("q")
    names: dict = {}
    covered_end = math.inf
    for line in plane.lines:
        if line.name == DEVICE_OP_LINE:
            for e in line.events:
                start.append(e.start_ns)
                end.append(e.end_ns)
                name.append(names.setdefault(e.name, len(names)))
        elif line.name == DEVICE_NOTE_LINE:
            for e in line.events:
                if e.name == DROPPED:
                    covered_end = min(covered_end, e.start_ns)
    if not len(start):
        return None
    return Ops.build(start, end, name, list(names), covered_end)


def reduce(profile) -> Trace:
    """``profile``: a ``jax.profiler.ProfileData`` (or anything with its
    planes / lines / events shape)."""
    devices, spans = [], []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            ops = _device(plane)
            if ops is not None:
                devices.append(ops)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans += [Span(e.name, e.start_ns, e.end_ns)
                          for e in line.events if e.name in SPANS]
    spans.sort(key=lambda s: s.start)
    return Trace(devices, spans)


def options():
    """Profiler options of the traced run: host events at level 1 (the
    benchmark's own annotations), no Python function events."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    return opts


def read_dir(path: str) -> Trace:
    """Reduce the newest ``.xplane.pb`` that ``jax.profiler`` wrote under
    ``path``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return reduce(ProfileData.from_file(max(files, key=os.path.getmtime)))

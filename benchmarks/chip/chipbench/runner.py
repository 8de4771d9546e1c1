"""Run one cell once: set-up, the measured window, the comparison, the
result line.

Set-up is everything from process start to the window's start: loading or
compiling the cell's programs from the compile cache, simulating the field
and whatever else the traffic needs before its first request.  With
``trace`` the window runs under the profiler and the result carries the
cell's per-layer metrics; without it, its end-to-end metrics.  Either way
the outputs of the window are compared with the plain reference once the
window has closed and the device's peak memory has been read, and each
number compared is printed beside its limit.
"""
from __future__ import annotations

import dataclasses
import json
import math
import shutil
import sys
import tempfile
import time

from . import trace as tracing
from .bench import Bench, Cell
from .clock import CompileClock


@dataclasses.dataclass
class Reading:
    """What a per-layer metric reader gets."""
    trace: tracing.Trace
    cell: Cell
    work: tuple | None    # (operations, bytes) of one evaluation
    peaks: dict
    window: object        # the traffic kind's record of the window


class Probe:
    """What a window calls around its work: ``span(name)``, a host span on
    the profiler's clock, ``tick()`` after each evaluation or request, and
    ``wait(value)`` between dispatching work and reading it back.

    With a trace directory the profiler traces one slice of the window:
    it starts ``start_s`` seconds into the window and stops ``seconds``
    later, each at the first ``tick`` or ``wait`` past that time, and the
    ``window`` span marks the slice.  A slice that starts at a ``tick``
    holds whole evaluations or requests; ``wait`` starts and stops it while
    the device still computes, so a slice can lie inside one 63 s TLR
    evaluation.  A v5e's trace buffer holds about 6 million operation
    events (about 40 s of that evaluation, 0.7 s of cokriging requests),
    and stopping the profiler takes 10 to 30 s per million buffered
    operations, so the slice is kept short enough for the traced run to
    end in time (``traced/<cell>.json``).
    """

    def __init__(self, trace_dir: str | None = None, start_s: float = 0.0,
                 seconds: float = math.inf):
        self.trace_dir, self.start_s = trace_dir, start_s
        self.seconds = seconds
        self.active, self.done = False, trace_dir is None

    @staticmethod
    def span(name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)

    def start(self):
        """The window starts now."""
        self._t0 = time.perf_counter()
        self.tick()

    def tick(self):
        if self.done:
            return
        t = time.perf_counter() - self._t0
        if not self.active and t >= self.start_s:
            self._begin()
        if self.active and t >= self.start_s + self.seconds:
            self.stop()

    def wait(self, value):
        """In a traced run, ``tick`` until ``value`` is ready or the slice
        is over.  Untraced, return at once."""
        import jax

        leaves = jax.tree.leaves(value)
        while not self.done:
            self.tick()
            if all(x.is_ready() for x in leaves):
                return
            time.sleep(0.005)

    def _begin(self):
        import jax

        jax.profiler.start_trace(self.trace_dir,
                                 profiler_options=tracing.options())
        self._window = self.span("window")
        self._window.__enter__()
        self.active = True

    def stop(self):
        import jax

        self.done = True
        if not self.active:
            return
        self._window.__exit__(None, None, None)
        self.active = False
        t = time.perf_counter()
        jax.profiler.stop_trace()
        print(f"stop_trace {time.perf_counter() - t!r} s", file=sys.stderr,
              flush=True)


def memory_peak(devices) -> int:
    """Peak device memory of the fullest chip: buffers
    (``peak_bytes_in_use``) and the compiled programs' temporaries, which
    the TPU client reserves apart (``peak_bytes_reserved``)."""
    stats = [d.memory_stats() or {} for d in devices]
    return max(s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
               for s in stats)


def number(v) -> float | None:
    """A finite float, or None (JSON has no inf or NaN)."""
    v = float(v)
    return v if math.isfinite(v) else None


def within(value, limit) -> bool:
    return value is not None and value <= limit


def execute(bench: Bench, cell: Cell, devices, peaks: dict, *, seed: int,
            seconds: float, trace: bool, t0: float, kind=None) -> dict:
    """The result of one run (the dict printed as the last line)."""
    import jax

    kind = kind or bench.kind(cell.traffic)
    with CompileClock() as compiling:
        state = kind.setup(cell, seed)
    setup_s = time.perf_counter() - t0
    tdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    probe = Probe(tdir, cell.traced["start_s"], cell.traced["seconds"])
    probe.start()
    try:
        with CompileClock() as late:
            win = kind.window(state, seconds, probe)
    finally:
        probe.stop()
    if hasattr(kind, "note"):
        print(kind.note(win), file=sys.stderr, flush=True)
    print(f"setup_s {setup_s!r}, of it compiling or loading programs "
          f"{compiling.seconds!r} s; compile events in the window: "
          f"{len(late.spans)}", file=sys.stderr, flush=True)
    peak = memory_peak(devices)
    kind.release(state)
    reduced = None
    if trace:
        t = time.perf_counter()
        try:
            reduced = tracing.read_dir(tdir)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        print(f"trace read in {time.perf_counter() - t!r} s; device trace "
              f"covers the window to {reduced.covered_end!r} ns",
              file=sys.stderr, flush=True)
    numbers = kind.check(state, win, seed)
    check = {k: {"value": number(v), "limit": cell.limits[k]}
             for k, v in numbers.items()}
    correct = all(within(c["value"], c["limit"]) for c in check.values())

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    metrics, breakdown = {}, None
    if not trace:
        values = dict(kind.end_to_end(win), setup_s=setup_s)
        for m in cell.end_to_end:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        w = reduced.window()
        backend = cell.config.get("backend")
        work = bench.work(backend).work(cell.config) if backend else None
        reading = Reading(reduced, cell, work, peaks, win)
        for m in cell.per_layer:
            v = bench.metric(m["name"]).read(reading)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if w is not None:
            device["busy_s"] = reduced.busy_ns(w.start, w.end) / 1e9
            device["window_s"] = w.ns / 1e9
            breakdown = {"device_ops": reduced.top_ops(w.start, w.end),
                         "idle_gaps": reduced.idle_gaps(w.start, w.end)}
    result = {"correct": correct, "attempted": kind.attempted(win),
              "failed": kind.failed(win), "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = check
    return result


def report(result: dict, out=sys.stdout, err=sys.stderr):
    """The comparison's numbers as the last lines of standard error, and
    the result as the last line of standard output."""
    for name, c in result["check"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=err, flush=True)
    print(json.dumps(result), file=out, flush=True)

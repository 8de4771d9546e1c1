"""Reductions that several per-layer metrics share (``metrics/*.py``)."""
from __future__ import annotations


def idle_share(r) -> float | None:
    """Percent of the traced window in which the device ran nothing."""
    w = r.trace.window()
    if w is None:
        return None
    return 100.0 * (1.0 - r.trace.busy_ns(w.start, w.end) / w.ns)


def per_span(r, name: str, measure) -> float | None:
    """``measure(span)`` averaged over the host spans called ``name`` that
    the device trace covers."""
    spans = r.trace.covered(name)
    if not spans or not r.trace.devices:
        return None
    return sum(measure(s) for s in spans) / len(spans)


def device_busy_s(r, name: str) -> float | None:
    """Device busy seconds per host span ``name``; None where it is 0."""
    busy = per_span(r, name, lambda s: r.trace.busy_ns(s.start, s.end))
    return busy / 1e9 if busy else None

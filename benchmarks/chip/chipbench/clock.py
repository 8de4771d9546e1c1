"""Wall time JAX spends tracing, lowering and compiling, or loading from
the compile cache, while a block runs (copied from ``chip_smoke.py``'s
``SetupClock``): the union of its compile-event spans, so nested traces
count once.  The runner reports it for set-up, and the count of such spans
inside the window, which should be none."""
from __future__ import annotations


class CompileClock:
    EVENTS = ("/jax/core/compile/",)

    def __enter__(self):
        import jax.monitoring

        self.spans = []
        jax.monitoring.register_event_time_span_listener(self._span)
        return self

    def _span(self, event, start, end, **_):
        if event.startswith(self.EVENTS):
            self.spans.append((start, end))

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_time_span_listener(self._span)

    @property
    def seconds(self) -> float:
        total, reach = 0.0, float("-inf")
        for start, end in sorted(self.spans):
            if end > reach:
                total += end - max(start, reach)
                reach = end
        return total

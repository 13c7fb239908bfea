"""Seconds JAX spends compiling, read from its own monitoring spans."""
from __future__ import annotations


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or fetching a
    compiled program from the persistent cache), read from JAX's own
    monitoring spans, plus the count of persistent-cache hits.  Spans
    nest (an outer jit's trace contains its inner jits' traces), so the
    clock counts the union of their intervals."""

    def __init__(self, jax):
        self.spans = []
        self.hits = 0

        def on_span(name, start, end, **_):
            if name.startswith("/jax/core/compile/"):
                self.spans.append((start, end))

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        self._jax, self._on_span, self._on_event = jax, on_span, on_event
        jax.monitoring.register_event_time_span_listener(on_span)
        jax.monitoring.register_event_listener(on_event)

    def close(self):
        """Stop listening."""
        self._jax.monitoring.unregister_event_time_span_listener(
            self._on_span)
        self._jax.monitoring.unregister_event_listener(self._on_event)

    def seconds(self, first=0, last=None):
        """Union of the spans ``first:last``, in seconds."""
        total, reach = 0.0, float("-inf")
        for start, end in sorted(self.spans[first:last]):
            total += max(0.0, end - max(start, reach))
            reach = max(reach, end)
        return total

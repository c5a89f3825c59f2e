"""Compile seconds from JAX's monitoring events.

Compile seconds are the union of the intervals in which JAX traced,
lowered or compiled: tracing an outer jit also traces the jits it
calls, so a plain sum of the events would count that time twice.
"""

from __future__ import annotations

import threading
import time


class Clock:
    """Collects JAX's compile events and persistent-cache hits."""

    COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                      "/jax/core/compile/jaxpr_to_mlir_module_duration",
                      "/jax/core/compile/backend_compile_duration")
    BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self._lock = threading.Lock()
        self._spans: list = []          # (start, end, event)
        self._hits: list = []           # perf_counter of each cache hit
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event in self.COMPILE_EVENTS:
            end = time.perf_counter()
            with self._lock:
                self._spans.append((end - duration, end, event))

    def _on_event(self, event: str, **_) -> None:
        if event == self.CACHE_HIT:
            with self._lock:
                self._hits.append(time.perf_counter())

    def compile_seconds(self, t0: float, t1: float) -> float:
        """Wall seconds within ``[t0, t1]`` in which JAX was compiling."""
        with self._lock:
            spans = sorted((max(a, t0), min(b, t1)) for a, b, _ in self._spans
                           if b > t0 and a < t1)
        total, reach = 0.0, t0
        for a, b in spans:
            if b > reach:
                total += b - max(a, reach)
                reach = b
        return total

    def compiles(self, t0: float, t1: float) -> int:
        """Backend compiles (cache misses and hits) that ended in
        ``[t0, t1]``, and traces: every compile event counts."""
        with self._lock:
            return sum(1 for _, b, _ in self._spans if t0 <= b <= t1)

    def cache_hits(self, t0: float, t1: float) -> int:
        with self._lock:
            return sum(1 for t in self._hits if t0 <= t <= t1)

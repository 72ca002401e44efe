"""Host spans around the harness's calls into the program, and compiles.

A span is recorded in the profiler's trace (``TraceAnnotation``, so the
trace reduction can say what the host was doing while the device idled)
only while a trace is being taken; the untraced window pays nothing.
"""
from __future__ import annotations

import contextlib
import time
from typing import Iterator, List

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class Spans:
    def __init__(self, tracing: bool):
        self.tracing = tracing

    @contextlib.contextmanager
    def __call__(self, name: str) -> Iterator[None]:
        if self.tracing:
            import jax
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, and persistent
    cache hits and misses, from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        self.compile_times: List[float] = []  # when each backend compile ended
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in COMPILE_EVENTS:
            self.seconds += secs
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_times.append(time.perf_counter())

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def compiles_between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.compile_times if t0 <= t <= t1)


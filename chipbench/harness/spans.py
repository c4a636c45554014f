"""The benchmark's own host spans, recorded around its calls into the
program: set-up phases on the host clock, and window spans as profiler
``TraceAnnotation`` events (free when no trace is being taken), so that the
trace reduction can tag each idle gap of the device with the span that was
open on the host."""

from __future__ import annotations

import contextlib
import time


class Spans:
    def __init__(self):
        self.setup_s: dict = {}

    @contextlib.contextmanager
    def timed(self, name: str):
        """A set-up phase on the host clock, kept as ``setup_s[name]``."""
        t0 = time.perf_counter()
        with self.span(f"bench.{name}"):
            yield
        self.setup_s[name] = time.perf_counter() - t0

    @staticmethod
    def span(name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)


class CompileLog:
    """JAX's compile events (tracing, lowering, backend compile, persistent
    cache reads) as wall-clock spans, from a ``jax.monitoring`` listener."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
        "/jax/compilation_cache/cache_retrieval_time_sec",
    )

    def __init__(self):
        self.events: list = []
        self.active = False

    def install(self) -> None:
        from jax import monitoring

        def on_span(event, start, end, **_):
            if self.active and event in self.EVENTS[:3]:
                self.events.append((event, start, end))

        def on_duration(event, duration, **_):
            if self.active and event == self.EVENTS[3]:
                end = time.time()
                self.events.append((event, end - duration, end))

        monitoring.register_event_time_span_listener(on_span)
        monitoring.register_event_duration_secs_listener(on_duration)

    def total_s(self) -> float:
        return sum(e - s for _, s, e in self.events)

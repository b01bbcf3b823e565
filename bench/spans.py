"""The benchmark's own host spans around its calls into the program.

Each span is written to the profiler's trace as a ``TraceAnnotation`` (so a
traced run can label the device's idle gaps by it) and kept in memory with
its host-clock start and end (so the per-layer metrics read it without a
trace).
"""

from __future__ import annotations

import contextlib
import time


class Spans:
    def __init__(self):
        self.records: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))


class CompileCounter:
    """Backend compiles reported by ``jax.monitoring``, including programs
    loaded from the persistent compilation cache; ``mark`` starts a count."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.n, self.seconds = 0, 0.0
        self._n0 = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, duration, **_):
        if name == self.EVENT:
            self.n += 1
            self.seconds += duration

    def mark(self) -> None:
        self._n0 = self.n

    def since_mark(self) -> int:
        return self.n - self._n0

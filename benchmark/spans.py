"""The benchmark's own spans round its calls into the program: kept in memory
on the host's clock, and written into the profiler's trace (as
``bench:<name>``) when a trace is being taken, so that idle gaps of the device
can be named by what the host was doing."""

from __future__ import annotations

import contextlib
import threading
import time


class Recorder:
    def __init__(self):
        self.spans = []  # (name, start_s, end_s) on time.perf_counter()
        self.tracing = False
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def span(self, name):
        annotation = None
        if self.tracing:
            import jax

            annotation = jax.profiler.TraceAnnotation("bench:" + name)
            annotation.__enter__()
        start = time.perf_counter()
        try:
            yield
        finally:
            end = time.perf_counter()
            if annotation is not None:
                annotation.__exit__(None, None, None)
            with self._lock:
                self.spans.append((name, start, end))

    def durations(self, name, since=0.0):
        return [e - s for n, s, e in self.spans if n == name and s >= since]

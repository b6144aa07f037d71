"""The profiler around a part of the measured window.

``--trace 1`` starts ``jax.profiler`` at the window's start and stops it at
the first query boundary after the traffic mix's ``trace_min_seconds``. Each
query runs inside one ``TraceAnnotation`` written here, by the benchmark, so
that idle gaps on the device can be told apart as ``in_query`` or
``between_queries``. Finer attribution needs annotations inside the program.
"""

import os
import shutil
import threading
import time
from typing import Optional

QUERY_ANNOTATION = "bench.query"


class WindowTracer:
    """Off unless ``start`` is called; every method is safe from any client
    thread."""

    def __init__(self, trace_dir: str, min_seconds: float):
        self.trace_dir = trace_dir
        self.min_seconds = min_seconds
        self._lock = threading.Lock()
        self._started_at: Optional[float] = None
        self._active = False
        self._starts: list = []   # perf_counter() of every query sent traced
        self.stopped_at: Optional[float] = None
        self.stop_seconds = 0.0   # host time spent writing the trace out

    def start(self) -> None:
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0   # Python frames would dwarf the trace
        options.host_tracer_level = 1     # TraceAnnotations and little else
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        with self._lock:
            self._active = True
            self._started_at = time.perf_counter()

    @property
    def active(self) -> bool:
        return self._active

    def note_start(self, t_start: float) -> None:
        """The host-clock start of a query about to be sent annotated: the
        earliest of them ties the profiler's clock to the host's."""
        with self._lock:
            self._starts.append(t_start)

    def query(self):
        """The annotation around one query, on the thread that sends it."""
        import jax
        return jax.profiler.TraceAnnotation(QUERY_ANNOTATION)

    def query_done(self) -> None:
        """Called after each completed query: stops the profiler once the
        traced part is long enough."""
        with self._lock:
            if not self._active or (time.perf_counter() - self._started_at
                                    < self.min_seconds):
                return
            self._active = False
        self._stop()

    def finish(self) -> None:
        """End of the window: stop if still tracing."""
        with self._lock:
            if not self._active:
                return
            self._active = False
        self._stop()

    def _stop(self) -> None:
        import jax
        self.stopped_at = time.perf_counter()
        jax.profiler.stop_trace()
        self.stop_seconds = time.perf_counter() - self.stopped_at

    @property
    def started_at(self) -> Optional[float]:
        return self._started_at

    @property
    def annotated_starts(self) -> list:
        with self._lock:
            return list(self._starts)

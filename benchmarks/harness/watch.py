"""What the benchmark reads from JAX and from the program while it runs:
JAX's own compile and persistent-cache events, and the engine's process-wide
counter totals. The arithmetic is ``chip_smoke.py``'s (PR 21), copied so that
a later edit there cannot change what the benchmark counts."""

import threading
from typing import Dict, NamedTuple


class CompileCounts(NamedTuple):
    compiles: int        # backend compiles, persistent-cache loads included
    seconds: float
    cache_hits: int
    cache_misses: int

    def since(self, before: "CompileCounts") -> "CompileCounts":
        return CompileCounts(*(a - b for a, b in zip(self, before)))


class CompileWatch:
    """Counts every ``backend_compile_duration`` event JAX reports (one per
    program compiled or loaded from the persistent cache) and the persistent
    cache's hits and misses. It also sees a stray eager operation compile,
    which the engine's own jit-cache counters do not."""

    def __init__(self):
        from jax import monitoring
        self._lock = threading.Lock()
        self._counts = CompileCounts(0, 0.0, 0, 0)
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name: str, secs: float, **_kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                c = self._counts
                self._counts = c._replace(compiles=c.compiles + 1,
                                          seconds=c.seconds + secs)

    def _event(self, name: str, **_kw) -> None:
        with self._lock:
            c = self._counts
            if name == "/jax/compilation_cache/cache_hits":
                self._counts = c._replace(cache_hits=c.cache_hits + 1)
            elif name == "/jax/compilation_cache/cache_misses":
                self._counts = c._replace(cache_misses=c.cache_misses + 1)

    def snapshot(self) -> CompileCounts:
        with self._lock:
            return self._counts


def process_totals() -> Dict[str, int]:
    """Monotone totals over every metric registry the process ever made, live
    plans and retired ones: the only view that covers the queries a server
    ran for its clients. Timers are in nanoseconds."""
    from spark_rapids_tpu.telemetry.prometheus import aggregator
    return dict(aggregator().scrape()[0])


def delta(after: Dict[str, int], before: Dict[str, int]) -> Dict[str, int]:
    return {k: v - before.get(k, 0) for k, v in after.items()}

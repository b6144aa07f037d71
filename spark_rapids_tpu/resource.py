"""RAII-style resource helpers.

Equivalent of the reference's `Arm` trait (sql-plugin Arm.scala:23):
withResource/closeOnExcept used pervasively to tie device buffer lifetime to
scopes. JAX arrays are GC-managed, but the spill catalog and host buffers
still need deterministic release, and the idiom keeps operator code shaped
like the reference's.
"""

from __future__ import annotations

import contextlib
from typing import Any, Iterable, Iterator, TypeVar

T = TypeVar("T")


def _close(r: Any) -> None:
    close = getattr(r, "close", None)
    if callable(close):
        close()


@contextlib.contextmanager
def with_resource(resource: T) -> Iterator[T]:
    """Close `resource` (or each element if iterable of closables) on exit."""
    try:
        yield resource
    finally:
        if isinstance(resource, (list, tuple)):
            for r in resource:
                _close(r)
        else:
            _close(resource)


@contextlib.contextmanager
def close_on_except(resource: T) -> Iterator[T]:
    """Close `resource` only if the body raises (Arm.closeOnExcept)."""
    try:
        yield resource
    except BaseException:
        if isinstance(resource, (list, tuple)):
            for r in resource:
                _close(r)
        else:
            _close(resource)
        raise


class TpuSemaphore:
    """Throttles concurrent tasks touching the device (GpuSemaphore.scala:27).

    Bounds HBM pressure from parallel partitions: a task thread acquires
    before uploading/computing on device and releases once its device data
    is exhausted (C2R / serializer). Reentrant per thread, like the
    reference's per-task tracking. Wait time is reported to the caller's
    metric registry.
    """

    def __init__(self, permits: int):
        import threading
        self.permits = max(1, permits)
        self._in_use = 0
        self._cv = threading.Condition()
        self._held = threading.local()

    def acquire_if_necessary(self, metrics=None) -> None:
        """Idempotent while held (GpuSemaphore.acquireIfNecessary): repeated
        acquires on the same thread do NOT nest, so a single release frees
        the permit regardless of how many uploads the task performed.
        The wait is recorded as semaphoreWaitTime on ``metrics`` (the
        per-task collect path, the broadcast build, and the exchange
        drain all pass their registry) and as a span in the active
        trace."""
        if getattr(self._held, "count", 0) > 0:
            return
        from spark_rapids_tpu import metrics as M
        from spark_rapids_tpu import trace as _trace
        timer = (metrics.create(M.SEMAPHORE_WAIT_TIME)
                 if metrics is not None else None)
        with _trace.span("semaphoreWait", metrics=metrics, timer=timer), \
                self._cv:
            while self._in_use >= self.permits:
                # bounded wait + lifecycle checkpoint: a cancelled /
                # timed-out query must not park on the semaphore
                # forever (docs/serving.md "Query lifecycle"); raising
                # here leaves the permit count untouched
                self._cv.wait(timeout=0.05)
                if self._in_use >= self.permits:
                    from spark_rapids_tpu.lifecycle import checkpoint
                    checkpoint("semaphore")
            self._in_use += 1
        self._held.count = 1

    def release_if_necessary(self) -> None:
        """Fully release the thread's hold (reference releases the task's
        permit in one call at C2R / task end)."""
        if getattr(self._held, "count", 0) > 0:
            self._held.count = 0
            with self._cv:
                self._in_use -= 1
                self._cv.notify()

    def resize(self, permits: int) -> None:
        """Re-size the permit pool in place. Safe mid-flight: growing
        wakes waiters immediately; shrinking lets current holders drain
        (``_in_use`` may exceed the new bound transiently — no permit
        is revoked, new acquires just wait until the pool drains under
        the new cap). This fixes the sized-once-forever singleton: a
        later session with a different concurrentGpuTasks used to keep
        the first session's sizing silently."""
        with self._cv:
            self.permits = max(1, int(permits))
            self._cv.notify_all()

    @property
    def in_use(self) -> int:
        with self._cv:
            return self._in_use


_SEMAPHORE: "TpuSemaphore | None" = None
_SEMAPHORE_LOCK = None


def _sem_lock():
    global _SEMAPHORE_LOCK
    if _SEMAPHORE_LOCK is None:
        import threading
        _SEMAPHORE_LOCK = threading.Lock()
    return _SEMAPHORE_LOCK


_sem_lock()  # built at import time: the lazy branch is only a fallback


def get_semaphore(conf) -> TpuSemaphore:
    """Process-wide semaphore sized by spark.rapids.sql.concurrentGpuTasks
    (initialized lazily; Plugin.scala:199 does this at executor startup).
    A conf whose concurrentGpuTasks differs from the current sizing
    RE-SIZES the singleton in place (last conf wins, like the
    reference's executor restart — but without losing held permits).
    Init/resize are serialized: two concurrent first queries must not
    construct two semaphores (that would double the device bound)."""
    global _SEMAPHORE
    from spark_rapids_tpu.conf import CONCURRENT_TPU_TASKS
    want = max(1, int(conf.get(CONCURRENT_TPU_TASKS)))
    with _sem_lock():
        if _SEMAPHORE is None:
            _SEMAPHORE = TpuSemaphore(want)
        elif _SEMAPHORE.permits != want:
            _SEMAPHORE.resize(want)
        return _SEMAPHORE


def release_current_thread() -> None:
    """Release the calling thread's semaphore hold if the singleton
    exists (used before blocking on task pools/locks — a parked thread
    must not pin a device permit). No-op when no semaphore was built."""
    if _SEMAPHORE is not None:
        _SEMAPHORE.release_if_necessary()

"""Median of the server's ``queueWaitMs`` response header: the wait for an
admission slot, measured where it happens. Served cells only."""

from benchmarks.harness.stats import median


def read(window):
    waits = [r.queue_wait_ms / 1000.0 for r in window.completed
             if r.queue_wait_ms is not None]
    return median(waits)

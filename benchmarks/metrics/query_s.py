"""Median latency of the queries completed in the window, on the client's
side."""

from benchmarks.harness.stats import median


def read(window):
    return median(window.latencies)

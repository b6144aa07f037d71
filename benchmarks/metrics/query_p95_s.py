"""95th percentile (nearest rank) of the latencies of the queries completed
in the window, on the client's side."""

from benchmarks.harness.stats import percentile


def read(window):
    return percentile(window.latencies, 95)

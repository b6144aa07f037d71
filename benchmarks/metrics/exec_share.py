"""Median share of the client's latency that the server spent executing
(``execMs`` of the response header): what is left is admission, protocol,
payload and the client's decode. Served cells only."""

from benchmarks.harness.stats import median


def read(window):
    shares = [100.0 * (r.exec_ms / 1000.0) / r.latency_s
              for r in window.completed
              if r.exec_ms is not None and r.latency_s > 0]
    return median(shares)

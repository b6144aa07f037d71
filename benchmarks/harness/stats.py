"""The benchmark's statistics: median, percentile and the spread the bounds
are set from. Plain Python, so that every PR reduces its runs the same way."""

import math
import statistics
from typing import Optional, Sequence


def median(values: Sequence[float]) -> Optional[float]:
    return statistics.median(values) if values else None


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile: the smallest value with at least ``q`` percent
    of the sample at or below it. With few samples it is the maximum."""
    if not values:
        return None
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def iqr_spread(values: Sequence[float]) -> Optional[float]:
    """Distance between the first and third quartile, as
    ``statistics.quantiles(values, n=4)`` gives them, as a share of the
    median: the spread the contract sets bounds from."""
    if len(values) < 2:
        return None
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    mid = statistics.median(values)
    return (q3 - q1) / abs(mid) if mid else None

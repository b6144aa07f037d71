"""What one run measured, as the metric readers see it."""

import dataclasses
from typing import Dict, List, Optional

from benchmarks.harness.cell import QueryRecord
from benchmarks.harness.watch import CompileCounts


@dataclasses.dataclass
class Window:
    seconds: float                  # the length asked for
    t_start: float                  # perf_counter() at the window's start
    setup_s: float                  # process start to the window's start
    scan_rows: int                  # input rows of one query
    records: List[QueryRecord]      # every query started in the window
    counters: Dict[str, int]        # the engine's process totals, as deltas
    compiles: CompileCounts         # JAX's compile events inside the window
    trace: Optional[Dict] = None    # profiler.reduce_trace's result, if traced

    @property
    def completed(self) -> List[QueryRecord]:
        return [r for r in self.records if r.ok]

    @property
    def latencies(self) -> List[float]:
        return [r.latency_s for r in self.completed]

    def per_query(self, *counter_names: str) -> Optional[float]:
        """Sum of the named counters over the window, per completed query."""
        if not self.completed:
            return None
        return (sum(self.counters.get(n, 0) for n in counter_names)
                / len(self.completed))

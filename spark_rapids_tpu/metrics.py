"""Operator metrics (GpuMetric, GpuExec.scala:17-103 twin).

Three verbosity levels (ESSENTIAL/MODERATE/DEBUG) gated by
``spark.rapids.sql.metrics.level``; each Tpu exec owns a named metric map
surfaced by ``TpuExec.metrics``. Timers are wall-clock nanoseconds.

Every ``timed``/``timed_wall`` scope also mirrors its interval into the
span tracer (spark_rapids_tpu/trace.py) as a span named
``<owner>.<metric>`` under the registry's query id — the trace, the
event log, the profile artifact and a ``jax.profiler`` session read the
SAME measurement, so they can never disagree (docs/observability.md).
With no sink and no profiler on, the mirror is a ``TraceAnnotation``'s
flag test and one module-global None check.
"""

from __future__ import annotations

import contextlib
import threading
import time
import weakref
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, Optional

from spark_rapids_tpu import trace as _trace

ESSENTIAL = 0
MODERATE = 1
DEBUG = 2

_LEVELS = {"ESSENTIAL": ESSENTIAL, "MODERATE": MODERATE, "DEBUG": DEBUG}

# canonical metric names (GpuMetric object in GpuExec.scala)
NUM_OUTPUT_ROWS = "numOutputRows"
NUM_OUTPUT_BATCHES = "numOutputBatches"
NUM_INPUT_ROWS = "numInputRows"
NUM_INPUT_BATCHES = "numInputBatches"
OP_TIME = "opTime"
SEMAPHORE_WAIT_TIME = "semaphoreWaitTime"
PEAK_DEVICE_MEMORY = "peakDeviceMemory"
SPILL_BYTES = "spillBytes"
SORT_TIME = "sortTime"
AGG_TIME = "computeAggTime"
JOIN_TIME = "joinTime"
CONCAT_TIME = "concatTime"
PARTITION_TIME = "partitionTime"
COPY_TO_DEVICE_TIME = "copyToDeviceTime"
PACK_TIME = "packBatchTime"  # host-side staging half of an upload
COPY_FROM_DEVICE_TIME = "copyFromDeviceTime"
# stage-fusion metrics (TpuFusedStageExec + prelude-absorbing aggs)
DISPATCH_COUNT = "dispatchCount"        # device programs dispatched
STAGE_COMPILE_TIME = "stageCompileTime"  # first-call build+compile wall
FUSED_OPS = "fusedOps"                  # operators collapsed into a stage
COMPILE_CACHE_HITS = "compileCacheHits"
COMPILE_CACHE_MISSES = "compileCacheMisses"
# retry framework metrics (spark_rapids_tpu/retry.py, docs/robustness.md)
RETRY_COUNT = "retryCount"                # OOM retries that re-attempted
SPLIT_RETRY_COUNT = "splitRetryCount"     # input batches split in half
RETRY_BLOCK_TIME = "retryBlockTime"       # spill+backoff wall inside retries
SPILL_BYTES_ON_RETRY = "spillBytesOnRetry"  # HBM freed by retry spills
DEGRADED_CHIPS = "degradedChips"          # mesh chips demoted after failure
IO_RETRY_COUNT = "ioRetryCount"           # transient reader IO retries
DEVICE_DECODE_OOM_FALLBACKS = "deviceDecodeOomFallbacks"  # encoded-upload
#   OOMs that fell back to the pyarrow host decode for that batch
# planned out-of-core family (docs/out_of_core.md): the budget
# oracle's planning decisions, distinct from the reactive retry
# counters above
PLANNED_PARTITIONS = "plannedPartitions"  # spill-backed partitions planned
BUDGET_PRESSURE_PEAK = "budgetPressurePeak"  # worst estimate/share ratio
PLANNED_OOC_ESCALATIONS = "plannedOutOfCoreEscalations"  # re-plans
# per-query host intervals no operator timer covers (trace.QueryScope's
# own registry, owner "Query"; docs/observability.md)
PLAN_TIME = "planTime"                    # parse + rewrite, calling thread
FIRST_DISPATCH_TIME = "firstDispatchTime"  # query begin -> first enqueue
DEVICE_SYNC_TIME = "deviceSyncTime"       # blocked reading device values
SCAN_STARVED_TIME = "scanStarvedTime"     # every open scan stream waiting
# the interpreter's collections, process-wide (trace.install_gc_hook)
GC_PAUSE_TIME = "gcPauseTime"             # and gcCollections.<gen>
# the host steps of a unit's device-decode plan (io/device_decode.py)
SCAN_READ_TIME = "scanReadTime"
SCAN_READ_BYTES = "scanReadBytes"
SCAN_INFLATE_TIME = "scanInflateTime"
SCAN_INFLATED_BYTES = "scanInflatedBytes"
SCAN_PARSE_TIME = "scanParseTime"
SCAN_PAGES = "scanPages"
SCAN_STR_LENGTHS_TIME = "scanStrLengthsTime"  # a part of the parse
# what the device decode and the fused stages made of strings, booked on
# the host from static shapes (columnar/transfer.py, exec/fused.py)
DEVICE_DECODE_STRING_BYTES = "deviceDecodeStringBytes"
DEVICE_DECODE_STRING_WORDS = "deviceDecodeStringWords"
LIKE_CHAR_BYTES = "likeCharBytes"
# what the aggregates and joins of a plan did (exec/agg.py, exec/join.py):
# each is added where the number already exists, never by a device sync
AGG_MERGE_COUNT = "aggMergeCount"    # partial results concatenated + merged
AGG_GROUP_COUNT = "aggGroupCount"    # groups into the final aggregates
JOIN_BUILD_ROWS = "joinBuildRows"    # build-side rows, once per build
JOIN_OUTPUT_ROWS = "joinOutputRows"  # joined rows, where the count is known
JOIN_DEMOTED_COUNT = "joinDemotedCount"  # shuffled joins run as broadcast
JOIN_STREAM_CHUNKS = "joinStreamChunks"  # stream chunks probed (whole: 1)
JOIN_BUILD_COMPACTIONS = "joinBuildCompactions"  # builds held at their rows
JOIN_BUILD_LANES_DROPPED = "joinBuildLanesDropped"  # lanes those dropped
# semi/anti joins under a residual condition (a decorrelated EXISTS)
JOIN_CONDITIONAL_COUNT = "joinConditionalCount"  # conditional mask joins run
JOIN_CONDITION_PAIRS = "joinConditionPairs"  # candidate pairs evaluated
JOIN_CONDITION_TIME = "joinConditionTime"    # host wall around those joins
# what the window execs of a plan did (exec/window.py)
WINDOW_TIME = "windowTime"    # host wall: concat, key batching, enqueue
WINDOW_ROWS = "windowRows"    # rows handed to the window programs
WINDOW_DECIMAL_AGG_COUNT = "windowDecimalAggCount"  # decimal-source aggs
# what the grouping-sets expansion and the aggregates' keys cost
# (exec/basic.py, exec/agg.py), from row counts and static shapes
EXPAND_TIME = "expandTime"    # host wall enqueueing the expand program
EXPAND_ROWS = "expandRows"    # rows out of the grouping-sets expansion
AGG_KEY_WORDS = "aggKeyWords"  # lanes x group-key words an aggregate sorts
# what the analysis rules of a statement did (sql/session.py)
DECORRELATED_SUBQUERY_COUNT = "decorrelatedSubqueryCount"
JOIN_CONDITION_PUSHED_COUNT = "joinConditionPushedCount"


# ---------------------------------------------------------------------------
# Central metric description table (docs/tools/profile single source of
# truth). EVERY metric any exec registers — constants above AND the
# ad-hoc keys created inline — must have an entry here (exact key) or
# match a prefix in METRIC_PREFIX_DESCRIPTIONS (dynamic families like
# per-chip counters). tests/test_profile.py lints this against the
# registries of executed plans, so profile/docs/bench can never
# disagree on names.
# ---------------------------------------------------------------------------

METRIC_DESCRIPTIONS: Dict[str, str] = {
    NUM_OUTPUT_ROWS: "rows emitted by the operator",
    NUM_OUTPUT_BATCHES: "device batches emitted",
    NUM_INPUT_ROWS: "rows consumed",
    NUM_INPUT_BATCHES: "batches consumed",
    OP_TIME: "operator wall time (ns)",
    SEMAPHORE_WAIT_TIME: "wall blocked on the device semaphore (ns)",
    PEAK_DEVICE_MEMORY: "peak HBM bytes this operator held live in the "
                        "device store (owner-attributed accounting)",
    SPILL_BYTES: "HBM bytes of this operator's batches demoted "
                 "device->host by the store",
    SORT_TIME: "device sort wall (ns)",
    AGG_TIME: "aggregation update/merge wall (ns)",
    JOIN_TIME: "join probe/gather wall (ns)",
    CONCAT_TIME: "device batch concat wall (ns)",
    PARTITION_TIME: "exchange partition-split wall (ns)",
    COPY_TO_DEVICE_TIME: "host->HBM upload wall (ns)",
    PACK_TIME: "host-side upload staging wall (ns; overlaps transfer)",
    COPY_FROM_DEVICE_TIME: "HBM->host download wall (ns)",
    DISPATCH_COUNT: "device programs dispatched",
    STAGE_COMPILE_TIME: "first-call trace+XLA-compile wall (ns)",
    FUSED_OPS: "operators collapsed into this fused stage",
    COMPILE_CACHE_HITS: "jit-cache hits for this exec's programs",
    COMPILE_CACHE_MISSES: "jit-cache misses (compiles) for this exec",
    RETRY_COUNT: "OOM retries that re-attempted the operation",
    SPLIT_RETRY_COUNT: "input batches split in half after OOM",
    RETRY_BLOCK_TIME: "spill+backoff wall inside OOM retries (ns; also "
                      "counted inside the enclosing operator timer)",
    SPILL_BYTES_ON_RETRY: "HBM freed by retry spills",
    DEGRADED_CHIPS: "mesh chips demoted after persistent failure",
    IO_RETRY_COUNT: "transient reader IO retries",
    DEVICE_DECODE_OOM_FALLBACKS: "encoded uploads that fell back to the "
                                 "pyarrow host decode after OOM",
    PLANNED_PARTITIONS: "spill-backed partitions the out-of-core "
                        "budget oracle planned up front "
                        "(docs/out_of_core.md)",
    BUDGET_PRESSURE_PEAK: "worst working-set estimate observed at "
                          "planning, as bytes per 100 bytes of budget "
                          "share (>100 = the planned out-of-core tier "
                          "engaged)",
    PLANNED_OOC_ESCALATIONS: "planned out-of-core partition plans "
                             "escalated (re-partitioned at a doubled "
                             "modulus) after a partition still "
                             "overflowed its budget share",
    AGG_MERGE_COUNT: "times a final or complete aggregate held more than "
                     "one batch of partial results, concatenated them and "
                     "aggregated again",
    AGG_GROUP_COUNT: "groups the final aggregates received merged and "
                     "emitted, before any HAVING filter (counted where "
                     "the merge already read the number; a complete-mode "
                     "aggregate over raw rows adds nothing)",
    JOIN_BUILD_ROWS: "build-side rows of the joins, once per build (per "
                     "co-partition of a shuffled join, once for a "
                     "broadcast), where the count is known without a "
                     "device sync",
    JOIN_OUTPUT_ROWS: "rows the joins emitted, where the count is known "
                      "without a device sync (a semi or anti join only "
                      "flips the active mask and adds nothing)",
    JOIN_DEMOTED_COUNT: "shuffled hash joins that adaptive execution ran "
                        "as broadcast joins because the materialized "
                        "build side was under the threshold",
    JOIN_STREAM_CHUNKS: "stream chunks the joins probed their build sides "
                        "with, one per probe: a stream partition joined "
                        "whole adds 1, one joined max(batchSizeRows, "
                        "build capacity) rows at a time adds 1 a chunk",
    JOIN_BUILD_COMPACTIONS: "join build sides held at their rows' capacity "
                            "bucket: one batch whose row count was already "
                            "on the host, compacted where that bucket is at "
                            "most half its capacity",
    JOIN_BUILD_LANES_DROPPED: "lanes those compactions took off the join "
                              "build sides (capacity before minus capacity "
                              "after, summed)",
    JOIN_CONDITIONAL_COUNT: "left semi and left anti joins run under a "
                            "residual condition (a decorrelated [NOT] "
                            "EXISTS), once per stream chunk joined",
    JOIN_CONDITION_PAIRS: "candidate pairs (left row, build row of its "
                          "key) a semi or anti join's residual condition "
                          "was evaluated on; read back once per chunk "
                          "from the count program, after the mask "
                          "program is enqueued",
    JOIN_CONDITION_TIME: "host wall around the conditional semi/anti "
                         "joins: count program, mask program, the "
                         "pairs' read (ns; inside joinTime)",
    WINDOW_TIME: "host wall around the window execs' work (ns): "
                 "concatenating a partition's batches, key batching "
                 "over batchSizeRows, enqueueing the window program — "
                 "not device time",
    WINDOW_ROWS: "rows the window execs were handed, from the row "
                 "counts of their input batches (read back under "
                 "deviceSync site=windowRows only where the producer "
                 "attached none)",
    WINDOW_DECIMAL_AGG_COUNT: "window aggregates over a decimal source "
                              "run on the device, once per window exec "
                              "executed",
    EXPAND_TIME: "host wall around the grouping-sets expansion (ns): "
                 "building and enqueueing the one program that writes "
                 "every set of a batch — not device time, and not the "
                 "wait for the batch's row count (deviceSyncTime)",
    EXPAND_ROWS: "rows out of the grouping-sets expansion: sets x each "
                 "input batch's row count (read under deviceSync "
                 "site=expandRows where the producer attached none)",
    AGG_KEY_WORDS: "lanes x group-key words each dispatched aggregate "
                   "pass sorts or gathers (a string key: validity, "
                   "length and char_cap/8 words), from the program's "
                   "static shapes (no device read)",
    DECORRELATED_SUBQUERY_COUNT: "correlated [NOT] EXISTS subqueries "
                                 "the analysis rule turned into left "
                                 "semi or left anti joins, once per "
                                 "statement planned",
    JOIN_CONDITION_PUSHED_COUNT: "one-sided ON conjuncts the analysis "
                                 "rule moved beneath their join as a "
                                 "filter on the side they read, once per "
                                 "statement planned",
    PLAN_TIME: "host planning wall on the calling thread (ns): SQL "
               "parse, analysis, overrides, plan cache, fingerprints, up "
               "to execute_collect — once per query",
    FIRST_DISPATCH_TIME: "ns from the query's begin to its first "
                         "enqueue of any device program — once per query",
    DEVICE_SYNC_TIME: "thread-ns the host spent blocked reading a "
                      "device value back (row counts, fetches, "
                      "aggregate counts, join sizes); summed over task "
                      "threads",
    SCAN_STARVED_TIME: "ns in which every open scan stream of a query "
                       "waited on an empty prefetch ring at once: host "
                       "starvation of the scan streams, including time "
                       "the device is busy with queued work (not device "
                       "idle)",
    GC_PAUSE_TIME: "ns the interpreter spent in its garbage "
                   "collections, every generation, process-wide",
    SCAN_READ_TIME: "device-decode plans reading files: footers and "
                    "column chunks' seek + read (ns; interval union "
                    "over the scan's threads)",
    SCAN_READ_BYTES: "column-chunk bytes the device-decode plans read "
                     "(footers not counted)",
    SCAN_INFLATE_TIME: "device-decode plans decompressing pages, "
                       "dictionary pages included (ns; interval union)",
    SCAN_INFLATED_BYTES: "bytes the page decompression put out",
    SCAN_PARSE_TIME: "the rest of the device-decode plans' column "
                     "chunks: page headers, level/run tables, "
                     "dictionary pages, string lengths, DELTA runs "
                     "(ns; interval union, decompression left out)",
    SCAN_PAGES: "pages whose headers the device-decode plans parsed",
    SCAN_STR_LENGTHS_TIME: "device-decode plans finding the value "
                           "lengths of PLAIN and DELTA_LENGTH byte-array "
                           "pages, or decoding a string chunk with a "
                           "PLAIN page by pyarrow (ns; interval union; a "
                           "part of scanParseTime)",
    DEVICE_DECODE_STRING_BYTES: "lanes x char_cap of the string columns "
                                "the device decode materialises, booked "
                                "from each staged batch's plan",
    DEVICE_DECODE_STRING_WORDS: "staging words the device decode "
                                "gathers for the PLAIN and DELTA_LENGTH "
                                "string values of each staged batch: "
                                "lanes x (char_cap/4 + 1) a column, "
                                "booked from its plan",
    LIKE_CHAR_BYTES: "capacity x char_cap of every LIKE a dispatched "
                     "filter or fused stage evaluates, from the "
                     "program's static shapes (no device read)",
    # ad-hoc keys registered inline by individual operators
    "pipelineDrainTime": "wall where the partial agg drained the async "
                         "upstream pipeline (interval union)",
    "pythonEvalTime": "python worker-pool UDF evaluation wall (ns)",
    "externalShuffleWriteTime": "external-shuffle serialize+write wall",
    "externalShuffleReadTime": "external-shuffle read+re-upload wall",
    "externalShuffleBytes": "bytes shipped through the external shuffle",
    "broadcastBuilds": "broadcast build-side materializations",
    "subplanCacheHits": "join build tables reused from the subplan "
                        "cache instead of rebuilt (docs/caching.md)",
    "numIciExchanges": "all-to-all exchanges run over the ICI mesh",
    "aqeCoalescedPartitions": "tiny exchange partitions coalesced by AQE",
    "aqeBroadcastFlip": "shuffled joins flipped to broadcast at runtime",
    "aqeReplans": "adaptive runtime replans applied over measured "
                  "exchange stats (docs/adaptive.md)",
    "aqeSkewSplits": "skewed exchange partitions split by the adaptive "
                     "skew-join rewrite",
    "exchangeTotalBytes": "materialized exchange output bytes (all "
                          "partitions)",
    "exchangeMaxPartitionBytes": "largest materialized exchange "
                                 "partition",
    "exchangeMedianPartitionBytes": "median non-empty materialized "
                                    "exchange partition",
    "fkFastPathJoins": "joins taking the unique-build-key fast path",
    "meshPadWaste": "staged-minus-active rows padded by mesh stacking",
    # scan-side keys (CpuFileScanExec; kept here so the profile tree and
    # docs can annotate the whole plan, not only Tpu* nodes)
    "decodeTime": "host parquet/file decode wall (interval union)",
    "convertTime": "arrow->HostBatch conversion wall",
    "deviceDecodeTime": "host-side half of the device decode path "
                        "(IO, page headers, decode plans)",
    "deviceDecodedBatches": "scan batches decoded on device",
    "deviceFallbackUnits": "scan units that fell back to host decode",
    "deviceFallbackColumns": "columns that fell back to host decode",
    # scan pipeline (docs/scan.md): producer-thread prefetch + bounded
    # upload-ahead ring in TpuRowToColumnarExec
    "scanPrefetchTime": "scan producer-thread read+pack wall "
                        "(interval union; overlaps device compute)",
    "uploadAheadBatches": "scan batches whose raw-chunk upload was "
                          "issued ahead of the consuming stage",
    "prefetchRingShrinks": "upload-ahead rings drained after OOM on a "
                           "prefetched upload",
}

# dynamic metric families: any key starting with one of these prefixes
# is described by the entry (per-chip counters, per-encoding counts)
METRIC_PREFIX_DESCRIPTIONS: Dict[str, str] = {
    "dispatchCount.chip": "device programs dispatched on chip <N>",
    "meshScanUnits.chip": "scan units assigned to chip <N>'s stream",
    "deviceDecodedValues.": "values decoded on device per encoding",
    "hostDecodedValues.": "values host-decoded (fallback columns) per "
                          "encoding",
    "gcCollections.": "the interpreter's collections of generation "
                      "<gen>, process-wide",
}


def describe_metric(name: str) -> Optional[str]:
    """Description for a metric key, resolving dynamic per-chip /
    per-encoding families by prefix; None for an unknown key (the lint
    test fails on those)."""
    d = METRIC_DESCRIPTIONS.get(name)
    if d is not None:
        return d
    for prefix, desc in METRIC_PREFIX_DESCRIPTIONS.items():
        if name.startswith(prefix):
            return desc
    return None


@dataclass
class TpuMetric:
    """Thread-safe counter: task threads (taskParallelism/shuffle pools)
    update the same operator's metrics concurrently."""

    name: str
    level: int = MODERATE
    value: int = 0
    # mutation counter (one int += under the already-held lock): the
    # telemetry endpoint's registry-delta aggregator sums versions per
    # registry to decide whether a cached snapshot is still current, so
    # a scrape re-reads only registries that actually changed
    # (telemetry/prometheus.py)
    version: int = 0
    _lock: threading.Lock = field(default_factory=threading.Lock,
                                  repr=False, compare=False)
    # wall-union timer state (timed_wall): overlapping intervals from
    # concurrent threads count once
    _active: int = field(default=0, repr=False, compare=False)
    _wall_start: int = field(default=0, repr=False, compare=False)

    def add(self, v: int) -> None:
        with self._lock:
            self.value += int(v)
            self.version += 1

    def set_max(self, v: int) -> None:
        with self._lock:
            self.value = max(self.value, int(v))
            self.version += 1

    def enter_wall(self) -> None:
        with self._lock:
            if self._active == 0:
                self._wall_start = time.perf_counter_ns()
            self._active += 1

    def exit_wall(self) -> None:
        with self._lock:
            self._active -= 1
            if self._active == 0:
                self.value += time.perf_counter_ns() - self._wall_start
                self.version += 1


# every live registry, for registry_snapshot(); weak so plans release
# their metrics with themselves
_REGISTRIES: "weakref.WeakSet[MetricRegistry]" = weakref.WeakSet()

# process-LIFETIME totals: when a registry is garbage-collected with
# its plan, its final values fold in here (the finalizer holds the
# inner metrics dict, which needs no access to the dead registry), so
# the telemetry endpoint's counters stay monotone across plan
# lifetimes — a query that completed between two scrapes still counts
# (telemetry/prometheus.py layers live registries on top of this base)
_RETIRED_LOCK = threading.Lock()
_RETIRED_TOTALS: Dict[str, int] = {}
# finalizers run at arbitrary allocation points (possibly while a
# reader holds _RETIRED_LOCK on the same thread), so they must not
# lock: the handoff is an atomic deque append, drained by readers
_RETIRED_QUEUE: deque = deque()


def _retire_metrics(metrics_dict: Dict[str, "TpuMetric"]) -> None:
    _RETIRED_QUEUE.append(metrics_dict)


def is_watermark_metric(name: str) -> bool:
    """True for high-watermark (``set_max``-style) metrics: they fold
    across registries by MAX, not sum — 10k dead per-plan peaks summed
    would dwarf the pool budget and mean nothing (the telemetry
    endpoint exports these as gauges)."""
    return "peak" in name.lower()


def fold_metric(totals: Dict[str, int], name: str, value: int) -> None:
    """Fold one registry's value into cross-registry totals with the
    right semantics (max for watermarks, sum otherwise)."""
    if is_watermark_metric(name):
        totals[name] = max(totals.get(name, 0), value)
    else:
        totals[name] = totals.get(name, 0) + value


def retired_totals() -> Dict[str, int]:
    """Folded final values of every garbage-collected registry."""
    with _RETIRED_LOCK:
        while True:
            try:
                md = _RETIRED_QUEUE.popleft()
            except IndexError:
                break
            for k, m in md.items():
                fold_metric(_RETIRED_TOTALS, k, m.value)
        return dict(_RETIRED_TOTALS)

# registry epoch: process-wide counters (the weak set above, the device
# store peaks) otherwise bleed one bench leg's numbers into the next
# leg's snapshot. Each registry stamps the epoch current at its
# creation; begin_epoch() + registry_snapshot(epoch=...) scope a
# process-wide snapshot to registries created since.
_EPOCH = 0


def begin_epoch() -> int:
    """Start a new registry epoch and return it. Bench detail legs call
    this (plus DeviceStore.reset_peaks) at leg start so process-wide
    snapshots cover only the leg's own plans."""
    global _EPOCH
    _EPOCH += 1
    return _EPOCH


def current_epoch() -> int:
    return _EPOCH


class MetricRegistry:
    """Per-exec metric map; creation is gated by the configured level so
    disabled metrics cost a no-op (the reference wraps them in NoopMetric).
    ``owner`` labels this registry's spans in the trace (the exec class
    name)."""

    def __init__(self, conf_level: str = "MODERATE", owner: str = ""):
        self.enabled_level = _LEVELS.get(conf_level.upper(), MODERATE)
        self.metrics: Dict[str, TpuMetric] = {}
        self.owner = owner
        self.epoch = _EPOCH
        self._lock = threading.Lock()
        # the executing query (trace.QueryScope), stamped by
        # execute_plan on every registry of the plan (trace.stamp_plan)
        self._query = None
        _REGISTRIES.add(self)
        weakref.finalize(self, _retire_metrics, self.metrics)

    def clone_empty(self) -> "MetricRegistry":
        """A fresh registry with the same level/owner and the same
        PRE-CREATED (all-zero) metric names, for plan-cache clones: a
        cached template's registries are never updated (the template is
        never executed), so copying the names reproduces exactly the
        event-log-v2 pre-creation contract (numOutputRows: 0 present)."""
        r = MetricRegistry.__new__(MetricRegistry)
        r.enabled_level = self.enabled_level
        r.metrics = {}
        r.owner = self.owner
        r.epoch = _EPOCH
        r._lock = threading.Lock()
        r._query = None
        _REGISTRIES.add(r)
        weakref.finalize(r, _retire_metrics, r.metrics)
        for k, m in self.metrics.items():
            r.create(k, m.level)
        return r

    def create(self, name: str, level: int = MODERATE) -> TpuMetric:
        with self._lock:  # check-then-set must be atomic across tasks
            m = self.metrics.get(name)
            if m is None:
                m = TpuMetric(name, level)
                if level <= self.enabled_level:
                    self.metrics[name] = m
            return m

    def __getitem__(self, name: str) -> TpuMetric:
        return self.metrics.get(name) or TpuMetric(name)

    def value(self, name: str) -> int:
        m = self.metrics.get(name)
        return m.value if m else 0

    def _span_kind(self, name: str) -> str:
        return f"{self.owner}.{name}" if self.owner else name

    def timed(self, name: str, level: int = MODERATE,
              **attrs) -> "_trace.span":
        """``with metrics.timed(name):`` — the interval goes to the
        metric, to a ``<owner>.<name>`` span of the open trace sink and
        to the profiler's trace (trace.span), under this registry's
        query."""
        # tpu-lint: disable=span-scope(factory — every caller opens the returned span in its own with-statement)
        return _trace.span(self._span_kind(name), metrics=self,
                           timer=self.create(name, level), **attrs)

    @contextlib.contextmanager
    def timed_wall(self, name: str, level: int = MODERATE,
                   **attrs) -> Iterator[None]:
        """Union-of-intervals timer: when N pool threads run the same
        phase concurrently, the metric advances by WALL time, not by N
        stacked thread-times, so a stage breakdown sums against the
        query wall sensibly (round-5 issue: q1's drain metric read
        11.6s against a 5.4s wall). The mirrored trace span is this
        THREAD's interval — the trace shows per-thread lanes, the
        metric their union."""
        m = self.create(name, level)
        with _trace.span(self._span_kind(name), metrics=self, **attrs):
            m.enter_wall()
            try:
                yield
            finally:
                m.exit_wall()

    def snapshot(self) -> Dict[str, int]:
        return {k: m.value for k, m in self.metrics.items()}


def plan_registries(physical) -> Iterator["MetricRegistry"]:
    """Every metric registry of a physical plan: each node's, its
    fused constituents' and its children's (the walk the tenant and
    query stamps share)."""
    ms = getattr(physical, "metrics", None)
    if ms is not None:
        yield ms
    for op in getattr(physical, "fused_ops", []):
        fm = getattr(op, "metrics", None)
        if fm is not None:
            yield fm
    for c in getattr(physical, "children", []):
        yield from plan_registries(c)


_QUERY_REGISTRY = MetricRegistry(owner="Query")


def query_registry() -> "MetricRegistry":
    """The process-lifetime registry (owner ``Query``) of the host
    intervals no operator owns — planTime, firstDispatchTime,
    deviceSyncTime. ONE registry for every query, not one each: the
    process totals are what is read (the benchmark, the Prometheus
    endpoint), the per-query view is the spans' ``q``; and a registry
    that died with each query would leave the totals a gap between its
    death and its finalizer (a scrape in between reads low). A sync
    outside any query (a tool reading a batch back) lands here too."""
    return _QUERY_REGISTRY


def live_registries() -> list:
    """Every live MetricRegistry in the process (a stable list copy of
    the weak set) — the telemetry aggregator's iteration surface."""
    # a WeakSet guards its iteration against removals, not against
    # another thread ADDING a registry (one per query, plus every
    # plan's) mid-walk: retry the copy
    for _ in range(8):
        try:
            return list(_REGISTRIES)
        except RuntimeError:
            continue
    return list(_REGISTRIES)


def registry_snapshot(plans=None, epoch: Optional[int] = None
                      ) -> Dict[str, Any]:
    """Every metric as ONE dict: ``{"metrics": {name: summed value},
    "jitCaches": {cache: stats}}``. With ``plans`` given (captured
    physical plans), only their registries contribute — fused-stage
    constituents and children included — which is the bench's scraping
    shape; with None, every live registry in the process contributes
    (cross-query totals). ``epoch`` scopes the process-wide form to
    registries created at or after a ``begin_epoch()`` stamp, so bench
    detail legs stop inheriting earlier legs' registries."""
    vals: Dict[str, int] = {}

    def add_reg(ms) -> None:
        for k, v in ms.snapshot().items():
            vals[k] = vals.get(k, 0) + v

    if plans is None:
        for ms in list(_REGISTRIES):
            if epoch is not None and getattr(ms, "epoch", 0) < epoch:
                continue
            add_reg(ms)
    else:
        def walk(p) -> None:
            ms = getattr(p, "metrics", None)
            if ms is not None:
                add_reg(ms)
            for op in getattr(p, "fused_ops", []):
                fm = getattr(op, "metrics", None)
                if fm is not None:
                    add_reg(fm)
            for c in getattr(p, "children", []):
                walk(c)
        for plan in plans or []:
            walk(plan)
    from spark_rapids_tpu.jit_cache import cache_stats
    return {"metrics": vals, "jitCaches": cache_stats()}


def sum_plan_metrics(plans, prefix: str) -> Dict[str, int]:
    """Sum every metric whose key starts with ``prefix`` across captured
    physical plans, fused-stage constituents included. Per-chip counters
    (``dispatchCount.chip3``, ``meshScanUnits.chip0``) are dynamic keys,
    so callers aggregate by prefix (bench ``detail.multichip``, the
    multichip tests)."""
    out: Dict[str, int] = {}

    def add(p) -> None:
        ms = getattr(p, "metrics", None)
        if ms is None:
            return
        for k, v in ms.snapshot().items():
            if k.startswith(prefix):
                out[k] = out.get(k, 0) + v

    def walk(p) -> None:
        add(p)
        for op in getattr(p, "fused_ops", []):
            add(op)
        for c in getattr(p, "children", []):
            walk(c)

    for plan in plans or []:
        walk(plan)
    return out

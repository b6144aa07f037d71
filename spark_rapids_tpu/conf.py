"""Typed configuration registry.

Equivalent of the reference's RapidsConf (sql-plugin RapidsConf.scala:301-1400):
a DSL of typed config entries under ``spark.rapids.*`` with docs, defaults,
startup-vs-runtime distinction, and markdown doc generation
(RapidsConf.scala's `help`/docs generation for docs/configs.md).

Per-operator enable keys (``spark.rapids.sql.exec.<Op>``,
``spark.rapids.sql.expression.<Expr>``) are auto-derived by the rule registry
in overrides.py, mirroring ReplacementRule.confKey (GpuOverrides.scala:147).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional


@dataclass
class ConfEntry:
    """One typed config entry. Mirrors RapidsConf's ConfEntry builders."""

    key: str
    doc: str
    default: Any
    converter: Callable[[str], Any]
    is_startup: bool = False
    is_internal: bool = False

    def get(self, conf: Dict[str, str]) -> Any:
        raw = conf.get(self.key)
        if raw is None:
            return self.default
        if isinstance(raw, str):
            return self.converter(raw)
        return raw


_REGISTRY: Dict[str, ConfEntry] = {}


def _to_bool(s: str) -> bool:
    return s.strip().lower() in ("true", "1", "yes")


class _Builder:
    """conf("key").doc(...).booleanConf.createWithDefault(x) style DSL
    (RapidsConf.scala:103-240)."""

    def __init__(self, key: str):
        self._key = key
        self._doc = ""
        self._startup = False
        self._internal = False

    def doc(self, text: str) -> "_Builder":
        self._doc = text
        return self

    def startup_only(self) -> "_Builder":
        self._startup = True
        return self

    def internal(self) -> "_Builder":
        self._internal = True
        return self

    def _create(self, default: Any, conv: Callable[[str], Any]) -> ConfEntry:
        e = ConfEntry(self._key, self._doc, default, conv, self._startup,
                      self._internal)
        if self._key in _REGISTRY:
            raise ValueError(f"duplicate conf key {self._key}")
        _REGISTRY[self._key] = e
        return e

    def boolean(self, default: bool) -> ConfEntry:
        return self._create(default, _to_bool)

    def integer(self, default: int) -> ConfEntry:
        return self._create(default, int)

    def long(self, default: int) -> ConfEntry:
        return self._create(default, int)

    def double(self, default: float) -> ConfEntry:
        return self._create(default, float)

    def string(self, default: Optional[str]) -> ConfEntry:
        return self._create(default, str)

    def bytes(self, default: int) -> ConfEntry:
        return self._create(default, parse_bytes)


def conf(key: str) -> _Builder:
    return _Builder(key)


def parse_bytes(s: str) -> int:
    """Parse '512m', '16g' style byte sizes (ConfHelper byteFromString)."""
    s = s.strip().lower()
    mult = 1
    for suffix, m in (("k", 1 << 10), ("m", 1 << 20), ("g", 1 << 30),
                      ("t", 1 << 40), ("b", 1)):
        if s.endswith(suffix):
            mult = m
            s = s[: -len(suffix)]
            break
    return int(float(s) * mult)


# ---------------------------------------------------------------------------
# Core entries (subset of the reference's 122 spark.rapids.* keys;
# RapidsConf.scala:301 onward). Grown as features land.
# ---------------------------------------------------------------------------

SQL_ENABLED = conf("spark.rapids.sql.enabled").doc(
    "Enable (true) or disable (false) TPU acceleration of SQL plans. "
    "(RapidsConf.scala SQL_ENABLED)").boolean(True)

EXPLAIN = conf("spark.rapids.sql.explain").doc(
    "Explain why parts of a query were or were not placed on the TPU: "
    "NONE (silent), NOT_ON_TPU (print one line per operator/expression "
    "fallback with the reason and the offending expression subtree), or "
    "ALL (also list every operator that WILL run on TPU). NOT_ON_GPU is "
    "accepted as an alias of NOT_ON_TPU. The same report is aggregated "
    "per query into the profile artifact (spark.rapids.sql.profile.*) "
    "and the event log (GpuOverrides.scala:3609-3616).").string("NONE")

CONCURRENT_TPU_TASKS = conf("spark.rapids.sql.concurrentGpuTasks").doc(
    "Number of tasks that may use the TPU concurrently; bounds HBM pressure "
    "(GpuSemaphore.scala:27).").integer(2)

TASK_PARALLELISM = conf("spark.rapids.sql.taskParallelism").doc(
    "Driver-side partition-execution threads (the executor-cores "
    "analogue): partitions run concurrently so host syncs of one task "
    "overlap device compute of another; concurrentGpuTasks still bounds "
    "simultaneous device use. Default 1 (sequential); raise on real "
    "TPU backends where per-task host round trips dominate.").integer(1)

EVENT_LOG_DIR = conf("spark.rapids.sql.eventLog.dir").doc(
    "Directory for per-query JSON event logs (empty = disabled); the "
    "offline qualification/profiling tools read these "
    "(Qualification.scala:34 / Profiler.scala:31 data source).").string("")

SHUFFLE_MODE = conf("spark.rapids.shuffle.mode").doc(
    "Exchange transport: 'inprocess' (materialized partition lists, the "
    "JVM sort-shuffle analogue), 'ici' (HBM-resident all-to-all over "
    "the active jax device mesh — the RapidsShuffleManager/UCX "
    "replacement, GpuShuffleEnv.scala:26 role; activates a mesh over "
    "all visible devices at session start), or 'external' (SRTB-"
    "serialized partitions over a shared directory — the cross-process "
    "host-staged/DCN transport skeleton).").string("inprocess")

SHUFFLE_ICI_DEVICES = conf("spark.rapids.shuffle.ici.devices").doc(
    "Number of devices in the ICI shuffle mesh (0 = all visible "
    "devices).").integer(0)

AQE_ENABLED = conf("spark.sql.adaptive.enabled").doc(
    "Adaptive query execution v0: replan at exchange materialization "
    "using MEASURED output sizes - a shuffled hash join whose build "
    "side lands under the broadcast threshold flips to a broadcast-"
    "style join at runtime, and tiny exchange partitions coalesce "
    "toward the advisory size (GpuOverrides.scala:3550 "
    "GpuQueryStagePrepOverrides / GpuCustomShuffleReaderExec "
    "roles).").boolean(True)

AQE_ADVISORY_PARTITION_BYTES = conf(
    "spark.sql.adaptive.advisoryPartitionSizeInBytes").doc(
    "Target post-shuffle partition size for AQE partition coalescing "
    "(Spark's advisoryPartitionSizeInBytes).").bytes(64 << 20)

AUTO_BROADCAST_JOIN_THRESHOLD = conf(
    "spark.rapids.sql.autoBroadcastJoinThreshold").doc(
    "Maximum estimated build-side size in bytes for a join to use a "
    "broadcast exchange instead of a shuffled hash join; -1 disables "
    "broadcast selection (spark.sql.autoBroadcastJoinThreshold "
    "semantics; the reference consumes Spark's decision via "
    "GpuBroadcastHashJoinExec).").bytes(10 << 20)

ADAPTIVE_ENABLED = conf("spark.rapids.sql.adaptive.enabled").doc(
    "Adaptive query execution over MEASURED exchange statistics "
    "(docs/adaptive.md): every exchange materialization records exact "
    "per-partition byte/row counts, and before the probe side compiles "
    "the AQE pass may demote a shuffled hash join to broadcast "
    "(adaptive.autoBroadcastBytes), coalesce undersized partitions "
    "toward adaptive.targetPartitionBytes, or split skewed stream "
    "partitions above adaptive.skewFactor x the median. Results are "
    "bit-identical to the unadaptive plan. Composes with "
    "spark.sql.adaptive.enabled: BOTH must be on (turning either off "
    "disables every runtime replan).").boolean(True)

ADAPTIVE_AUTO_BROADCAST_BYTES = conf(
    "spark.rapids.sql.adaptive.autoBroadcastBytes").doc(
    "Runtime broadcast-demotion threshold: a shuffled hash join whose "
    "REALIZED build-side bytes (exchange stats, active-row refined) "
    "land at or under this flips to a broadcast-style join, bypassing "
    "the stream side's co-partitioning exchange. -1 inherits "
    "spark.rapids.sql.autoBroadcastJoinThreshold (docs/adaptive.md)."
    ).bytes(-1)

ADAPTIVE_TARGET_PARTITION_BYTES = conf(
    "spark.rapids.sql.adaptive.targetPartitionBytes").doc(
    "Target size AQE coalesces undersized exchange output partitions "
    "toward (fewer, fuller device programs). 0 inherits "
    "spark.sql.adaptive.advisoryPartitionSizeInBytes "
    "(docs/adaptive.md).").bytes(0)

ADAPTIVE_SKEW_FACTOR = conf("spark.rapids.sql.adaptive.skewFactor").doc(
    "Skewed-partition detection: a realized stream-side join partition "
    "larger than this factor times the median non-empty partition is "
    "split into sub-partitions (each re-joined against the same build "
    "partition) so one hot key stops serializing the probe stage and "
    "stops triggering OOM-retry. 0 disables skew splitting "
    "(docs/adaptive.md).").double(4.0)

BATCH_SIZE_BYTES = conf("spark.rapids.sql.batchSizeBytes").doc(
    "Target size in bytes of columnar batches fed to TPU operators "
    "(RapidsConf.scala GPU_BATCH_SIZE_BYTES).").bytes(128 << 20)

BATCH_SIZE_ROWS = conf("spark.rapids.sql.batchSizeRows").doc(
    "Target row capacity of a device columnar batch. Static XLA shapes are "
    "derived by bucketing row counts up to this ceiling. A join probes its "
    "build side with max(batchSizeRows, the build side's capacity) stream "
    "rows at a time: every probe sorts the build side's lanes with the "
    "chunk's, so a chunk is never smaller than what it is sorted with "
    "(joinStreamChunks counts the probes).").integer(1 << 20)

MAX_READER_BATCH_SIZE_ROWS = conf(
    "spark.rapids.sql.reader.batchSizeRows").doc(
    "Soft cap on rows per batch produced by file readers "
    "(RapidsConf.scala MAX_READER_BATCH_SIZE_ROWS).").integer(1 << 20)

HAS_NANS = conf("spark.rapids.sql.hasNans").doc(
    "Assume floating point data may contain NaN; affects agg/join support "
    "(RapidsConf.scala HAS_NANS).").boolean(True)

ENABLE_FLOAT_AGG = conf("spark.rapids.sql.variableFloatAgg.enabled").doc(
    "Allow float aggregations whose result can differ from CPU due to "
    "ordering (RapidsConf.scala:557 defaults this off; opt-in only)."
    ).boolean(False)

INCOMPATIBLE_OPS = conf("spark.rapids.sql.incompatibleOps.enabled").doc(
    "Enable ops that are not 100%% compatible with Spark semantics "
    "(RapidsConf.scala INCOMPATIBLE_OPS).").boolean(False)

ANSI_ENABLED = conf("spark.sql.ansi.enabled").doc(
    "ANSI SQL mode: overflow/invalid-cast raise instead of null/wrap "
    "(Spark conf honored by the rewrite like GpuOverrides does).").boolean(False)

CASE_SENSITIVE = conf("spark.sql.caseSensitive").doc(
    "Case sensitivity of column resolution (Spark SQLConf).").boolean(False)

SESSION_TIMEZONE = conf("spark.sql.session.timeZone").doc(
    "Session timezone for timestamp/date expressions.").string("UTC")

SHUFFLE_PARTITIONS = conf("spark.sql.shuffle.partitions").doc(
    "Default partition count for exchanges (Spark SQLConf).").integer(8)

DEVICE_SHUFFLE_PARTITIONS = conf(
    "spark.rapids.sql.shuffle.devicePartitions").doc(
    "Partition count for DEVICE hash/range exchanges; 0 = auto (the "
    "active ICI mesh size, or 1 in-process). One chip executes all "
    "partitions' programs serially anyway, so extra in-process "
    "partitions only add split programs and count syncs — the AQE "
    "coalesce-shuffle-partitions decision made statically for the TPU "
    "(GpuShuffleExchangeExecBase partitioning role).").integer(0)

METRICS_LEVEL = conf("spark.rapids.sql.metrics.level").doc(
    "ESSENTIAL, MODERATE or DEBUG op metric verbosity "
    "(RapidsConf.scala:491, GpuExec.scala:17-103).").string("MODERATE")

CPU_RANGE_PARTITIONING = conf(
    "spark.rapids.sql.rangePartitioning.sampleOnCpu").internal().doc(
    "Sample range-partition bounds on CPU (GpuRangePartitioner).").boolean(True)

DEVICE_MEMORY_LIMIT = conf("spark.rapids.memory.tpu.poolSize").doc(
    "HBM budget (bytes) managed by the device store; 0 = 80%% of the "
    "device's reported memory (GpuDeviceManager.initializeRmm, "
    "GpuDeviceManager.scala:216).").startup_only().bytes(0)

HOST_SPILL_STORAGE_SIZE = conf("spark.rapids.memory.host.spillStorageSize").doc(
    "Bytes of host memory used to spill device batches before disk "
    "(RapidsConf.scala HOST_SPILL_STORAGE_SIZE).").startup_only().bytes(1 << 30)

SPILL_DIR = conf("spark.rapids.memory.spillDirectory").doc(
    "Directory for the disk spill tier (RapidsDiskStore).").string("/tmp/srt_spill")

MEMORY_DEBUG = conf("spark.rapids.memory.tpu.debug").doc(
    "Log device allocation/free events (RapidsConf.scala:307).").boolean(False)

DEVICE_BUDGET_BYTES = conf("spark.rapids.sql.memory.deviceBudgetBytes").doc(
    "Planned out-of-core budget in bytes: the working-set ceiling the "
    "memory oracle hands operators BEFORE they materialize, so a join "
    "build side or aggregation estimated over its budget share "
    "partitions/spills up front instead of riding the reactive "
    "OOM-retry protocol. 0 probes the device (80%% of reported HBM, "
    "the pool default); set low on CPU for deterministic out-of-core "
    "tests (docs/out_of_core.md).").bytes(0)

OUT_OF_CORE_ENABLED = conf("spark.rapids.sql.outOfCore.enabled").doc(
    "Planned out-of-core execution (docs/out_of_core.md): operators "
    "consult the memory budget oracle before materializing and choose "
    "a spill-friendly shape up front — partitioned hash join, "
    "bucketed aggregation, budget-capped exchange coalesce — keeping "
    "the OOM-retry protocol as a last-resort backstop instead of the "
    "steady-state execution mode. Results are bit-identical to the "
    "in-memory paths.").boolean(True)

OUT_OF_CORE_BUDGET_SHARE = conf("spark.rapids.sql.outOfCore.budgetShare").doc(
    "Fraction of the device budget one operator's working set may "
    "claim before the planned out-of-core tier engages (several "
    "operators hold batches concurrently under taskParallelism, so "
    "one operator never plans for the whole budget).").double(0.5)

OUT_OF_CORE_MAX_PARTITIONS = conf(
    "spark.rapids.sql.outOfCore.maxPartitions").doc(
    "Ceiling on the spill-backed partition count the budget oracle "
    "plans UP FRONT (pow2-rounded estimate/share). A partition that "
    "still overflows past the ceiling re-partitions recursively "
    "(bounded by outOfCore.maxRecursion) instead of planning "
    "thousands of tiny splits from a bad estimate.").integer(64)

OUT_OF_CORE_MAX_RECURSION = conf(
    "spark.rapids.sql.outOfCore.maxRecursion").doc(
    "Bound on recursive re-partitioning depth when a planned "
    "partition still overflows its budget share (each level doubles "
    "the partition modulus; pmod(hash, 2N) refines pmod(hash, N)). "
    "Past the bound the partition falls back to the OOM-retry "
    "backstop.").integer(3)

SHUFFLE_COMPRESSION_CODEC = conf("spark.rapids.shuffle.compression.codec").doc(
    "Codec for serialized batch payloads (disk spill tier and any "
    "host-staged shuffle leg): none, zlib or zstd "
    "(TableCompressionCodec framework analogue).").string("none")

ALLOW_DISABLE_ENTIRE_PLAN = conf(
    "spark.rapids.allowDisableEntirePlan").internal().doc(
    "Allow the rewrite to bail out entirely when the whole plan would fall "
    "back (GpuOverrides).").boolean(True)

CBO_ENABLED = conf("spark.rapids.sql.optimizer.enabled").doc(
    "Cost-based optimizer: revert subtrees to CPU when transition costs "
    "outweigh speedup (CostBasedOptimizer.scala:52). Off by default, as in "
    "the reference.").boolean(False)

TEST_FORCE_DEVICE = conf("spark.rapids.sql.test.forceDevice").internal().doc(
    "Testing: fail instead of falling back to CPU when an op is "
    "unsupported (integration test TEST_CONF analogue).").boolean(False)

UDF_COMPILER_ENABLED = conf("spark.rapids.sql.udfCompiler.enabled").doc(
    "Compile Python lambda UDFs to Catalyst-style expressions "
    "(udf-compiler/ Plugin.scala:27-37).").boolean(False)

PARQUET_READER_TYPE = conf("spark.rapids.sql.format.parquet.reader.type").doc(
    "PERFILE, MULTITHREADED or COALESCING parquet reader strategy "
    "(RapidsConf.scala:719-733).").string("MULTITHREADED")

CONCURRENT_PYTHON_WORKERS = conf(
    "spark.rapids.python.concurrentPythonWorkers").doc(
    "Max concurrent python worker processes for pandas UDFs "
    "(PythonConfEntries.scala:32 twin; the pool is the throttle the "
    "reference implements as PythonWorkerSemaphore).").integer(2)

MULTITHREADED_READ_NUM_THREADS = conf(
    "spark.rapids.sql.format.parquet.multiThreadedRead.numThreads").doc(
    "Thread pool size for the multithreaded reader "
    "(GpuMultiFileReader.scala:300).").integer(8)

STAGE_FUSION_ENABLED = conf("spark.rapids.sql.stageFusion.enabled").doc(
    "Fuse maximal linear chains of per-batch device operators "
    "(filter -> project -> partial hash-aggregate update) into ONE "
    "jitted XLA program per batch (TpuFusedStageExec) — the whole-"
    "stage-codegen / GpuTieredProject analogue. Cuts per-operator "
    "dispatch and intermediate HBM materialization; results are "
    "bit-identical to the unfused plan. Per-operator metrics still "
    "report: fused nodes fan updates back to their constituent "
    "execs (see docs/fusion.md).").boolean(True)

STAGE_FUSION_MAX_IN_FLIGHT = conf(
    "spark.rapids.sql.stageFusion.maxInFlight").doc(
    "Async pipeline window of a fused stage: how many batches may be "
    "in flight (dispatched to the device but not yet yielded "
    "downstream) at once. Batch k+1's dispatch overlaps batch k's "
    "device compute; the value bounds HBM held by outstanding "
    "batches. 1 = sequential per-batch draining.").integer(2)

MULTICHIP_SCAN_ENABLED = conf(
    "spark.rapids.sql.multichip.scan.enabled").doc(
    "Shard the SCAN itself across the active shuffle mesh: partition "
    "units (parquet row groups / orc stripes / files) are assigned "
    "round-robin-by-bytes to one reader stream per chip, and each "
    "stream's batches (encoded pages or decoded rows) upload directly "
    "to that chip's HBM — no gather to chip 0. Downstream per-batch "
    "stages (filter/project/partial aggregate, fused stages) then run "
    "data-parallel on each chip's resident batches, and the ICI "
    "exchange consumes them without a host-side stacking round trip "
    "(docs/multichip.md). Effective only while a multi-device mesh is "
    "active (spark.rapids.shuffle.mode=ici); single-device behavior "
    "and the CPU engine are unchanged and results are bit-identical."
    ).boolean(True)

MULTICHIP_SERIALIZE_SERVED = conf(
    "spark.rapids.sql.multichip.serializeServedQueries").doc(
    "Serialize ICI-mesh collective sections across concurrently served "
    "queries behind a per-process mesh mutex. Two concurrent XLA CPU "
    "collectives over one device set deadlock at rendezvous (the PR 13 "
    "soak-documented limit), so served sessions take the mutex around "
    "each mesh exchange by default — other queries keep executing "
    "their non-collective stages, and waiting queries remain "
    "cancellable. Non-served (single-user) sessions never contend and "
    "skip the mutex entirely. Disable only on runtimes with per-query "
    "collective isolation.").boolean(True)

RETRY_MAX_RETRIES = conf("spark.rapids.sql.retry.maxRetries").doc(
    "Maximum OOM retries of one device allocation/operation before the "
    "failure escalates (split-and-retry where the operator supports "
    "splitting its input, abort otherwise). Each retry spills the "
    "device store down and backs off exponentially "
    "(RmmRapidsRetryIterator.scala:243 withRetry role).").integer(3)

RETRY_BACKOFF_MS = conf("spark.rapids.sql.retry.backoffMs").doc(
    "Base backoff in milliseconds between OOM retries; doubles per "
    "attempt up to spark.rapids.sql.retry.maxBackoffMs. The block time "
    "is reported as the retryBlockTime metric.").integer(1)

RETRY_MAX_BACKOFF_MS = conf("spark.rapids.sql.retry.maxBackoffMs").doc(
    "Upper bound in milliseconds on the exponential OOM-retry "
    "backoff.").integer(100)

READER_MAX_RETRIES = conf("spark.rapids.sql.reader.maxRetries").doc(
    "Maximum retries of a transient IO error in the file readers "
    "(PERFILE / MULTITHREADED / COALESCING and the mesh-sharded "
    "streams); the original error re-raises after exhaustion.").integer(3)

READER_RETRY_BACKOFF_MS = conf("spark.rapids.sql.reader.retryBackoffMs").doc(
    "Base backoff in milliseconds between reader IO retries; doubles "
    "per attempt (bounded at 1s).").integer(5)

INJECT_OOM = conf("spark.rapids.sql.test.injectOOM").internal().doc(
    "Testing: deterministic synthetic-OOM schedule for the retry "
    "framework. 'N' = every Nth wrapped allocation throws TpuRetryOOM; "
    "'N:K' = K consecutive failures at every Nth allocation; "
    "'split:N' = TpuSplitAndRetryOOM every Nth; 'seed:S:P' = seeded "
    "random with probability P; 'site:NAME:SPEC' scopes any form to "
    "the named site — site:cancel counts lifecycle checkpoints and "
    "injects cooperative cancels, site:budget makes every Nth "
    "budget-oracle query report half the real headroom "
    "(docs/robustness.md site catalog).").string("")

INJECT_IO_ERROR = conf("spark.rapids.sql.test.injectIOError").internal().doc(
    "Testing: deterministic synthetic IO-error schedule for the file "
    "readers; same 'N' / 'N:K' grammar as injectOOM.").string("")

INJECT_CHIP_FAILURE = conf(
    "spark.rapids.sql.test.injectChipFailure").internal().doc(
    "Testing: comma-separated mesh chip ids whose dispatches "
    "persistently fail; the mesh degrades to the surviving chips "
    "(docs/robustness.md degradation ladder).").string("")

PLAN_CACHE_ENABLED = conf("spark.rapids.sql.planCache.enabled").doc(
    "Cross-query plan-rewrite cache: the finished physical plan "
    "(Planner + TpuOverrides rewrite + CBO + whole-stage fusion) is "
    "cached per normalized logical-plan signature, and repeated query "
    "shapes clone the cached template instead of re-running the "
    "rewrite pipeline. Results are bit-identical (each execution gets "
    "fresh operator instances and metric registries); the cache is the "
    "bounded LRU 'planRewrite' in the jit-cache registry. Off by "
    "default; the query server enables it for its sessions "
    "(docs/serving.md).").boolean(False)

RESULT_CACHE_ENABLED = conf("spark.rapids.sql.resultCache.enabled").doc(
    "Serve-tier result cache (docs/caching.md): the final Arrow IPC "
    "payload of a finished query is kept in a bounded LRU keyed on "
    "(plan-signature digest, extracted literal bindings, input-file "
    "fingerprint set). A hit is detected BEFORE admission and served "
    "straight from memory — zero device work, zero queue wait, zero "
    "admission slot — and any input-file fingerprint mismatch "
    "(path/size/mtime) invalidates the entry and falls through to "
    "normal execution, so served bytes are always bit-identical to a "
    "fresh run. Off by default.").boolean(False)

RESULT_CACHE_MAX_ENTRIES = conf(
    "spark.rapids.sql.resultCache.maxEntries").doc(
    "Bound on distinct cached results; least-recently-served entries "
    "are evicted past it (docs/caching.md).").integer(256)

RESULT_CACHE_MAX_BYTES = conf(
    "spark.rapids.sql.resultCache.maxBytes").doc(
    "Bound on total cached Arrow IPC payload bytes held by the result "
    "cache; LRU eviction keeps the sum under it (docs/caching.md)."
    ).integer(256 << 20)

SUBPLAN_CACHE_ENABLED = conf(
    "spark.rapids.sql.subplanCache.enabled").doc(
    "Cross-query broadcast build-table cache (docs/caching.md): the "
    "device-resident build side of a broadcast hash join is kept keyed "
    "on the build subtree's structural signature + its input-file "
    "fingerprint set and reused across queries and tenants, lifting "
    "the reference's within-plan GpuBroadcastExchangeExec reuse across "
    "query boundaries. Entries register in the device store as "
    "evict-FIRST: pool pressure drops cached build tables before any "
    "live query's batches spill. Fingerprints are re-checked on every "
    "reuse; a mismatch drops the entry and rebuilds. Off by default."
    ).boolean(False)

SUBPLAN_CACHE_MAX_ENTRIES = conf(
    "spark.rapids.sql.subplanCache.maxEntries").doc(
    "Bound on distinct cached build tables; least-recently-reused "
    "entries are dropped past it (docs/caching.md).").integer(32)

SUBPLAN_CACHE_MAX_BYTES = conf(
    "spark.rapids.sql.subplanCache.maxBytes").doc(
    "Bound on total device bytes the subplan cache may pin; LRU drops "
    "keep the sum under it. The device store may additionally drop "
    "entries at any moment under pool pressure (docs/caching.md)."
    ).integer(64 << 20)

SERVE_MAX_CONCURRENT = conf(
    "spark.rapids.sql.serve.maxConcurrentQueries").doc(
    "Queries the server executes simultaneously across all tenants; "
    "admitted queries still contend on concurrentGpuTasks for actual "
    "device access — this bounds whole-query concurrency the way "
    "GpuSemaphore bounds task concurrency (docs/serving.md)."
    ).integer(4)

SERVE_MAX_QUEUED = conf("spark.rapids.sql.serve.maxQueued").doc(
    "Bound on queries waiting for admission; a request arriving with "
    "the queue full is REJECTED immediately (backpressure — the client "
    "sees status=rejected and retries with its own policy) instead of "
    "growing an unbounded queue (docs/serving.md).").integer(32)

SERVE_MAX_PER_TENANT = conf(
    "spark.rapids.sql.serve.maxConcurrentPerTenant").doc(
    "Per-tenant in-flight query limit: one tenant cannot occupy every "
    "execution slot no matter how fast it submits (docs/serving.md)."
    ).integer(2)

SERVE_FAIR_SHARE_FACTOR = conf(
    "spark.rapids.sql.serve.fairShareFactor").doc(
    "Fair-share HBM arbitration: a tenant whose live device-store "
    "bytes exceed factor * (pool budget / live tenants) is over share "
    "— its batches spill FIRST under pool pressure (billing the spill "
    "to the offender, not an LRU victim) and its queued queries are "
    "passed over while other tenants wait (docs/serving.md)."
    ).double(1.5)

SERVE_BATCH_FUSION_ENABLED = conf(
    "spark.rapids.sql.serve.batchFusion.enabled").doc(
    "Same-signature batch fusion (docs/adaptive.md): concurrent "
    "queries whose SQL differs only in literal bindings are collected "
    "within batchFusion.windowMs and executed under ONE admission "
    "slot; identical texts share a single execution, distinct "
    "bindings ride the same cached plan template and compiled device "
    "programs back-to-back. Per-tenant results stay bit-identical and "
    "each member bills its own tenant ledger and queue wait; the "
    "window engages only while the server is saturated, so an idle "
    "server adds no latency.").boolean(True)

SERVE_BATCH_FUSION_WINDOW_MS = conf(
    "spark.rapids.sql.serve.batchFusion.windowMs").doc(
    "Collection window for batch fusion: the first query of a shape "
    "holds its batch open this long (only while the server is "
    "saturated) so same-shape peers can join before execution "
    "(docs/adaptive.md).").integer(10)

SERVE_BATCH_FUSION_MAX_BATCH = conf(
    "spark.rapids.sql.serve.batchFusion.maxBatch").doc(
    "Maximum member queries one fused batch accepts; the next arrival "
    "opens a fresh batch (docs/adaptive.md).").integer(16)

SERVE_HOST = conf("spark.rapids.sql.serve.host").doc(
    "Interface the query server binds (local serving; the cross-host "
    "tier is ROADMAP item 5).").string("127.0.0.1")

SERVE_PORT = conf("spark.rapids.sql.serve.port").doc(
    "Port the query server binds (0 = ephemeral; the bound port is "
    "printed/returned for clients).").integer(0)

SERVE_QUERY_TIMEOUT_MS = conf(
    "spark.rapids.sql.serve.queryTimeoutMs").doc(
    "Per-query deadline in milliseconds, enforced from request "
    "admission (queue wait counts against the budget): a query that "
    "exceeds it is cooperatively cancelled at the engine's lifecycle "
    "checkpoints and returns status=cancelled (reason=deadline) on "
    "the wire. 0 disables. Per-tenant override: set "
    "spark.rapids.sql.serve.queryTimeoutMs.<tenant>; a client may "
    "TIGHTEN the deadline (or set one where the operator set none) "
    "per request via the sql header's timeoutMs field — it can never "
    "loosen or disable an operator-enforced bound "
    "(docs/serving.md 'Query lifecycle').").integer(0)

SERVE_WATCHDOG_FACTOR = conf(
    "spark.rapids.sql.serve.watchdogFactor").doc(
    "Stuck-query watchdog: a running query whose elapsed wall exceeds "
    "this factor times its plan-cache signature's observed p99 wall "
    "fires a stuckQuery slow-query bundle through the telemetry "
    "trigger engine (and, with serve.watchdogCancel, a cooperative "
    "cancel). Signatures with fewer than 5 observed walls are never "
    "flagged. 0 disables (docs/serving.md 'Query lifecycle')."
    ).double(0.0)

SERVE_WATCHDOG_CANCEL = conf(
    "spark.rapids.sql.serve.watchdogCancel").doc(
    "When the stuck-query watchdog flags a query, also CANCEL it "
    "(reason=watchdog) instead of only emitting the stuckQuery "
    "bundle. Off by default — observation first, enforcement opt-in "
    "(docs/serving.md 'Query lifecycle').").boolean(False)

SERVE_QUARANTINE_THRESHOLD = conf(
    "spark.rapids.sql.serve.quarantineThreshold").doc(
    "Poison-query quarantine: a plan-cache signature that fails this "
    "many CONSECUTIVE times with a runtime-fatal error (cancellations "
    "and deadline timeouts never count) is blacklisted — further "
    "submissions fail fast with status=quarantined before touching "
    "the device, instead of re-wedging the runtime. One success "
    "clears the streak; a restart clears the blacklist. 0 disables "
    "(docs/serving.md 'Query lifecycle').").integer(0)

SERVE_DRAIN_TIMEOUT_MS = conf(
    "spark.rapids.sql.serve.drainTimeoutMs").doc(
    "Graceful-drain deadline for `tools serve` shutdown (SIGTERM or "
    "the shutdown verb): admission stops immediately, in-flight "
    "queries get this long to finish, then stragglers are "
    "cooperatively cancelled (reason=shutdown) so the process exits "
    "with the store empty and all permits restored "
    "(docs/serving.md 'Query lifecycle').").integer(60000)

SERVE_TENANT_ID = conf("spark.rapids.sql.serve.tenantId").internal().doc(
    "Session-scoped tenant id the server sets on each tenant's "
    "session; threads through trace files, event-log lines, profile "
    "artifacts, and the store's per-tenant HBM ledger.").string("")

TELEMETRY_DIR = conf("spark.rapids.sql.telemetry.dir").doc(
    "Directory for slow-query bundles emitted by the telemetry trigger "
    "engine (bundle-<pid>-<n>-<trigger>.json + the flight-recorder "
    "dump trace-ring-<pid>-<n>.json it references; "
    "docs/observability.md 'Live telemetry').").string("/tmp/srt_telemetry")

TELEMETRY_SLOW_QUERY_MS = conf("spark.rapids.sql.telemetry.slowQueryMs").doc(
    "Slow-query trigger: a query whose wall exceeds this many "
    "milliseconds emits a slow-query bundle (flight-recorder dump + "
    "profile artifact path + server stats + the condition) into "
    "spark.rapids.sql.telemetry.dir. 0 disables the trigger."
    ).integer(0)

TELEMETRY_RETRY_COUNT_THRESHOLD = conf(
    "spark.rapids.sql.telemetry.retryCountThreshold").doc(
    "Per-query retry trigger: a query whose plan accumulates MORE than "
    "this many retryCount (OOM retries) emits a slow-query bundle. "
    "0 disables the trigger.").integer(0)

TELEMETRY_RETRY_STORM_THRESHOLD = conf(
    "spark.rapids.sql.telemetry.retryStormThreshold").doc(
    "Process-wide retry-storm trigger: MORE than this many OOM retries "
    "inside one 60-second window emits a retryStorm bundle (evaluated "
    "at retry time, not query end — a storm is visible while the "
    "storm is happening). 0 disables the trigger.").integer(0)

TELEMETRY_HBM_WATERMARK = conf(
    "spark.rapids.sql.telemetry.hbmWatermark").doc(
    "HBM-occupancy trigger: a device-store sample whose live bytes "
    "exceed this fraction of the pool budget emits an hbmWatermark "
    "bundle (evaluated at every store transition). 0 disables the "
    "trigger. Arm it via any session that sets a telemetry conf "
    "(triggers.configure).").double(0.0)

TELEMETRY_QUEUE_WATERMARK = conf(
    "spark.rapids.sql.telemetry.queueWatermark").doc(
    "Admission-saturation trigger: an admission queue whose depth "
    "exceeds this fraction of serve.maxQueued emits a queueSaturation "
    "bundle (evaluated at every enqueue). 0 disables the trigger."
    ).double(0.0)

TELEMETRY_MIN_INTERVAL_S = conf(
    "spark.rapids.sql.telemetry.triggerMinIntervalS").doc(
    "Per-trigger rate limit: after a trigger fires, further firings of "
    "the SAME trigger inside this many seconds are counted "
    "(rateLimited in the engine stats, srt_telemetry_triggers_rate_"
    "limited_total on the endpoint) but emit no bundle — a storm "
    "cannot flood the disk.").double(60.0)

TELEMETRY_MAX_BUNDLES = conf(
    "spark.rapids.sql.telemetry.maxBundles").doc(
    "Retention bound on telemetry artifacts in "
    "spark.rapids.sql.telemetry.dir: trigger bundles "
    "(bundle-*.json) and flight-recorder dumps (trace-ring-*.json) "
    "beyond this count are pruned OLDEST-FIRST by the bundle-worker "
    "thread after each write (never under a hot-path lock). Pruned "
    "counts show in the engine stats, the server stats telemetry "
    "section, and srt_telemetry_bundles_pruned_total. 0 disables "
    "count-based retention.").integer(256)

TELEMETRY_MAX_BUNDLE_BYTES = conf(
    "spark.rapids.sql.telemetry.maxBundleBytes").doc(
    "Retention bound on the TOTAL bytes of telemetry artifacts "
    "(bundles + ring dumps) in spark.rapids.sql.telemetry.dir, pruned "
    "oldest-first alongside spark.rapids.sql.telemetry.maxBundles. "
    "0 disables byte-based retention.").bytes(0)

TELEMETRY_HISTORY_DIR = conf(
    "spark.rapids.sql.telemetry.history.dir").doc(
    "Directory of the persistent query-history store: one compact "
    "JSONL record per finished query (signature, tenant, terminal "
    "status/reason, wall/queue-wait, retry/spill/jit counters, "
    "fallback coverage, peak HBM, artifact paths), appended at query "
    "close by session.execute_plan and the query server, rotated into "
    "bounded segments and compacted by telemetry.history.maxBytes / "
    "maxAgeDays. The store is the cross-run performance memory behind "
    "server warm-start, SLO tracking, `tools history`, and `tools "
    "doctor` (docs/observability.md 'Query history'). Empty = "
    "disabled.").string("")

TELEMETRY_HISTORY_MAX_BYTES = conf(
    "spark.rapids.sql.telemetry.history.maxBytes").doc(
    "Size bound on the query-history store: segments are rotated at a "
    "fraction of this and the OLDEST whole segments are deleted once "
    "the store's total size exceeds it (each record is one JSON line, "
    "so compaction never truncates a record mid-line)."
    ).bytes(64 << 20)

TELEMETRY_HISTORY_MAX_AGE_DAYS = conf(
    "spark.rapids.sql.telemetry.history.maxAgeDays").doc(
    "Age bound on the query-history store: a rotated segment whose "
    "newest record is older than this many days is deleted at "
    "compaction. 0 disables age-based compaction.").double(14.0)

TELEMETRY_HISTORY_WARM_START = conf(
    "spark.rapids.sql.telemetry.history.warmStart").doc(
    "Seed the serving tier's lifecycle state from the query-history "
    "store at server start: per-signature wall reservoirs (so the "
    "stuck-query watchdog has a p99 from query one after a restart) "
    "and consecutive-failure streaks / quarantine blacklisting (so a "
    "poison signature stays fail-fast across restarts). Effective "
    "only when spark.rapids.sql.telemetry.history.dir is set "
    "(docs/observability.md 'Query history').").boolean(True)

SERVE_SLO_P99_MS = conf("spark.rapids.sql.serve.slo.p99Ms").doc(
    "Per-tenant latency objective: the tenant's observed p99 wall over "
    "the spark.rapids.sql.serve.slo.window seconds of query history "
    "must stay under this many milliseconds. Evaluated over the "
    "persistent history store (telemetry.history.dir must be set), "
    "exported as the srt_slo_* Prometheus families, and — when the "
    "observed p99 exceeds the objective — fires a rate-limited "
    "sloBurn bundle through the telemetry trigger engine. Per-tenant "
    "override: spark.rapids.sql.serve.slo.p99Ms.<tenant>. 0 disables "
    "(docs/observability.md 'SLO tracking').").integer(0)

SERVE_SLO_WINDOW = conf("spark.rapids.sql.serve.slo.window").doc(
    "SLO evaluation window in seconds: objectives under "
    "spark.rapids.sql.serve.slo.p99Ms are checked against the query "
    "history's finished records newer than this."
    ).double(3600.0)

SERVE_TUNING_ENABLED = conf("spark.rapids.sql.serve.tuning.enabled").doc(
    "History-driven feedback control (docs/tuning.md): the server "
    "embeds a TuningController that scores the query history through "
    "the signature-aggregate + doctor verdict pipeline at start and "
    "on a periodic tick, and applies bounded, logged, reversible "
    "per-signature actions from the declared ACTION_CATALOG — cache "
    "pre-warm for compile storms, admission narrowing / out-of-core "
    "seeding for retry-spill shapes, "
    "and per-tenant admission weight shifts for SLO burn. Every "
    "action lands in the history store as a tuning record, exports "
    "as srt_tuning_* Prometheus families, and auto-reverts when the "
    "post-action baseline regresses (tools tuning inspects/pins/"
    "reverts). Requires telemetry.history.dir; off by default."
    ).boolean(False)

SERVE_TUNING_INTERVAL_S = conf(
    "spark.rapids.sql.serve.tuning.intervalS").doc(
    "Seconds between TuningController scan ticks (history scoring + "
    "action application + guardrail evaluation). The start-of-server "
    "scan always runs regardless (docs/tuning.md).").double(30.0)

SERVE_TUNING_MAX_ACTIONS = conf(
    "spark.rapids.sql.serve.tuning.maxActionsPerTick").doc(
    "Ceiling on NEW tuning actions one scan tick may apply — the "
    "controller converges knob by knob instead of rewriting the whole "
    "server's posture from one noisy window (docs/tuning.md)."
    ).integer(4)

SERVE_TUNING_GUARD_WINDOW = conf(
    "spark.rapids.sql.serve.tuning.guardWindowQueries").doc(
    "Guardrail sample window: an applied action is judged once this "
    "many post-action finished records exist for its scope — p50/p99 "
    "over the window diffed against the pre-action baseline captured "
    "in the action's evidence; a regression past "
    "serve.tuning.revertThreshold auto-reverts the action "
    "(docs/tuning.md).").integer(5)

SERVE_TUNING_REVERT_THRESHOLD = conf(
    "spark.rapids.sql.serve.tuning.revertThreshold").doc(
    "Relative p50/p99 regression past which the guardrail reverts an "
    "applied action ((baseline - observed) / baseline, lower being "
    "better; docs/tuning.md).").double(0.25)

SERVE_TUNING_MAX_PREWARM = conf(
    "spark.rapids.sql.serve.tuning.maxPrewarm").doc(
    "Ceiling on the signatures the compile-storm pre-warm action may "
    "hold in its replay ledger (and therefore on the planning replays "
    "a server start performs) — startup cost stays bounded no matter "
    "how storm-prone the history looks (docs/tuning.md).").integer(8)

PARQUET_DEVICE_DECODE = conf(
    "spark.rapids.sql.format.parquet.deviceDecode.enabled").doc(
    "Decode Parquet pages ON DEVICE (the default scan path, the "
    "cuDF-decode role of GpuParquetScanBase.scala:82): host threads "
    "read raw column-chunk bytes, decompress pages and parse headers "
    "only; bit-unpacking of RLE/bit-packed runs, dictionary gather, "
    "PLAIN fixed-width reinterpret, string offset+bytes assembly "
    "(segmented prefix-sum over the lengths + bytes gather), "
    "DELTA_BINARY_PACKED reconstruction, BYTE_STREAM_SPLIT "
    "reinterleave and definition-level expansion run as XLA kernels. "
    "Columns with genuinely unsupported shapes (nested, INT96, "
    "DELTA_BYTE_ARRAY) fall back per column to the pyarrow host "
    "decode; results are bit-identical either way. See "
    "docs/supported_ops.md for the encoding matrix and docs/scan.md "
    "for the async scan pipeline.").boolean(True)

PARQUET_DEVICE_DECODE_BYTE_ARRAY = conf(
    "spark.rapids.sql.format.parquet.deviceDecode.byteArray.enabled"
    ).doc(
    "Device-decode PLAIN / DELTA_LENGTH byte-array (string/binary) "
    "pages: the host extracts only the per-value byte lengths; the "
    "offsets column is built ON DEVICE by a per-page segmented "
    "prefix-sum and the bytes column is gathered into the padded char "
    "matrix (SURVEY.md §7 hard part (c)). Off = those columns fall "
    "back to the pyarrow host decode (dictionary-encoded strings "
    "still device-decode).").boolean(True)

PARQUET_DEVICE_DECODE_DELTA = conf(
    "spark.rapids.sql.format.parquet.deviceDecode.delta.enabled").doc(
    "Device-decode DELTA_BINARY_PACKED (and the length half of "
    "DELTA_LENGTH_BYTE_ARRAY): the host parses block/miniblock "
    "headers only; bit-unpacking of the packed deltas and the "
    "prefix-sum reconstruction run on device. Off = DELTA_* columns "
    "fall back to the pyarrow host decode.").boolean(True)

PARQUET_DEVICE_DECODE_BSS = conf(
    "spark.rapids.sql.format.parquet.deviceDecode.byteStreamSplit."
    "enabled").doc(
    "Device-decode BYTE_STREAM_SPLIT pages (float/double/int32/int64): "
    "the byte-plane reinterleave is a strided device gather. Off = "
    "those columns fall back to the pyarrow host decode.").boolean(True)

PARQUET_DEVICE_DECODE_MAX_IN_FLIGHT = conf(
    "spark.rapids.sql.format.parquet.deviceDecode.maxInFlight").doc(
    "Scan upload pipeline depth: how many staged scan batches may have "
    "their raw-chunk upload in flight (device_put issued, decode "
    "program not yet dispatched) ahead of the consuming stage, per "
    "reader stream and per chip. A producer thread prefetches + packs "
    "batch k+1 while batch k's bytes move and batch k-1 computes, so "
    "the scan never idles a chip (docs/scan.md). 1 = upload-ahead off "
    "(still prefetch-threaded); 0 = fully synchronous scan uploads "
    "(the A/B baseline bench.py measures).").integer(2)


class TpuConf:
    """Bound view over a conf dict; the RapidsConf class equivalent.

    Usage: ``TpuConf({"spark.rapids.sql.enabled": "true"}).get(SQL_ENABLED)``
    or attribute-style helpers below.
    """

    def __init__(self, settings: Optional[Dict[str, Any]] = None):
        self.settings: Dict[str, Any] = dict(settings or {})

    def get(self, entry: ConfEntry) -> Any:
        return entry.get(self.settings)

    def get_key(self, key: str, default: Any = None) -> Any:
        e = _REGISTRY.get(key)
        if e is not None:
            return e.get(self.settings)
        return self.settings.get(key, default)

    def set(self, key: str, value: Any) -> None:
        self.settings[key] = value

    def is_op_enabled(self, conf_key: str, default: bool = True) -> bool:
        raw = self.settings.get(conf_key)
        if raw is None:
            return default
        return raw if isinstance(raw, bool) else _to_bool(str(raw))

    # Frequently used helpers
    @property
    def sql_enabled(self) -> bool:
        return self.get(SQL_ENABLED)

    @property
    def batch_size_rows(self) -> int:
        return self.get(BATCH_SIZE_ROWS)

    @property
    def batch_size_bytes(self) -> int:
        return self.get(BATCH_SIZE_BYTES)

    @property
    def ansi_enabled(self) -> bool:
        return self.get(ANSI_ENABLED)

    @property
    def shuffle_partitions(self) -> int:
        return int(self.get(SHUFFLE_PARTITIONS))

    @property
    def explain(self) -> str:
        return str(self.get(EXPLAIN)).upper()


def registered_entries() -> List[ConfEntry]:
    return list(_REGISTRY.values())


def generate_docs() -> str:
    """Markdown config table; the docs/configs.md generator equivalent
    (RapidsConf.scala `help`)."""
    lines = ["# spark-rapids-tpu configuration", "",
             "| Key | Default | Startup | Description |",
             "|---|---|---|---|"]
    for e in sorted(_REGISTRY.values(), key=lambda e: e.key):
        if e.is_internal:
            continue
        lines.append(
            f"| {e.key} | {e.default} | {e.is_startup} | {e.doc} |")
    return "\n".join(lines) + "\n"

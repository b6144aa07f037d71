"""Pipeline-wide span tracer with Chrome-trace export (Dapper-style).

The reference explains *where a query's time went* with NVTX ranges fed
into Nsight plus the Qualification/Profiler tools; after whole-stage
fusion, mesh-parallel scan, and the async in-flight dispatch window the
hot path here is concurrent in three dimensions (reader pool threads,
``stageFusion.maxInFlight`` dispatches, per-chip mesh execution) and
wall-clock counters alone cannot attribute time.  This module is the
missing layer: a low-overhead, thread-safe span stream

    (query_id, batch_id, chip, thread, kind, t0, t1, attrs)

recorded at the engine's existing choke points and exported as
Chrome-trace-event JSON — one file per query under
``spark.rapids.sql.trace.dir`` — that loads directly in Perfetto /
chrome://tracing.  ``tools.py trace <file>`` analyzes the same stream
offline (critical path, exclusive self-time, per-chip enqueue
occupancy).  Every span is also a ``jax.profiler.TraceAnnotation``
over the same interval, so any profiler session shows the engine's
spans on the host planes beside the device's operations, on one clock
(``tools.py trace <profile dir>`` reads those: true device busy/idle,
idle gaps put down to host spans, device time by program and scope).
Every record carries its query's id (``QueryScope``).

Integration contract (docs/observability.md):

- ``MetricRegistry.timed``/``timed_wall`` mirror every metric timer
  into a span with the SAME interval, so the event log, the profiler,
  and the trace agree on one set of numbers by construction.
- Sites without a metric timer (fused/agg dispatch, semaphore waits,
  spills, JIT compiles) measure ONCE and feed both channels.
- Retry/backoff/split/chip-failure events are instant markers; the
  retry recovery block (spill + backoff) is a nested ``retryBlock``
  span so the offline analyzer's *exclusive* self-time report undoes
  the documented retryBlockTime-inside-opTime double count.

Overhead discipline: when no trace sink is open (``trace.enabled``
off, or the query was not sampled per ``trace.sampleRate``) a span
costs two clock reads, its ``TraceAnnotation`` (a flag test outside a
profiler session, about a microsecond in all) and one module-global
``None`` check; span recording itself is a tuple append under the GIL
(no lock on the hot path).
"""

from __future__ import annotations

import itertools
import json
import os
import random
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

from spark_rapids_tpu.conf import conf

TRACE_ENABLED = conf("spark.rapids.sql.trace.enabled").doc(
    "Record per-query span traces (reader IO/decode, host pack, upload, "
    "per-chip device dispatch, exchange, JIT compiles, semaphore waits, "
    "spills, retries) and write one Chrome-trace JSON file per query "
    "under spark.rapids.sql.trace.dir. Open the files in Perfetto "
    "(https://ui.perfetto.dev) or analyze offline with `python -m "
    "spark_rapids_tpu.tools trace <file>` (docs/observability.md)."
    ).boolean(False)

TRACE_DIR = conf("spark.rapids.sql.trace.dir").doc(
    "Directory for per-query Chrome-trace files "
    "(trace-<pid>-q<n>.json).").string("/tmp/srt_traces")

TRACE_SAMPLE_RATE = conf("spark.rapids.sql.trace.sampleRate").doc(
    "Fraction of queries to trace (1.0 = every query). Sampling is "
    "deterministic for a fixed spark.rapids.sql.trace.sampleSeed: the "
    "Nth traced-candidate query of the process is sampled iff the Nth "
    "draw of the seeded stream is below the rate — production use "
    "traces a stable subset at bounded overhead.").double(1.0)

TRACE_SAMPLE_SEED = conf("spark.rapids.sql.trace.sampleSeed").doc(
    "Seed of the deterministic query-sampling stream used by "
    "spark.rapids.sql.trace.sampleRate.").integer(0)

TRACE_MODE = conf("spark.rapids.sql.trace.mode").doc(
    "Trace sink: 'file' writes one Chrome-trace JSON per sampled query "
    "(the per-query exporter); 'ring' is the FLIGHT RECORDER — an "
    "always-on, fixed-size, lock-free per-thread ring buffer that "
    "survives across queries with bounded memory (the last "
    "spark.rapids.sql.trace.ringSpans records per thread) and dumps on "
    "demand — slow-query triggers (spark.rapids.sql.telemetry.*) or "
    "telemetry.dump_ring() — as the SAME Chrome-trace JSON, so `tools "
    "trace`/`tools hotspots` work unchanged on dumps. Query server "
    "sessions default to 'ring' (docs/observability.md 'Live "
    "telemetry').").string("file")

TRACE_RING_SPANS = conf("spark.rapids.sql.trace.ringSpans").doc(
    "Flight-recorder capacity in trace.mode=ring: spans (and instants "
    "/ counter samples) retained PER THREAD before the oldest are "
    "overwritten. Bounds recorder memory on a long-lived server; a "
    "dump reconstructs the most recent window of work."
    ).integer(4096)


# ---------------------------------------------------------------------------
# Span catalog (docs/observability.md; the tpu-lint `span-kind` rule
# checks every literal span/instant kind recorded in the package
# against these tables, so a dump's vocabulary can never drift from
# the documentation). Metric-mirror spans are the dynamic family
# `<Exec>.<metric>` — every member resolves via metrics.describe_metric
# and is covered by the `metric-key` rule instead.
# ---------------------------------------------------------------------------

SPAN_CATALOG: Dict[str, str] = {
    "scanPrefetch": "scan producer thread reading+packing one staged "
                    "batch (mirrors scanPrefetchTime)",
    "uploadAhead": "async raw-chunk device_put issued ahead of the "
                   "consuming stage (docs/scan.md)",
    "finishUpload": "host->device upload completion per staging mode "
                    "and chip",
    "TpuFusedStageExec.dispatch": "one fused-stage device program "
                                  "dispatch (chip, batch seq, compile "
                                  "flag)",
    "TpuHashAggregateExec.dispatch": "one aggregation device program "
                                     "dispatch (mode, compile flag)",
    "exchangeMaterialize": "exchange input drain + partition "
                           "materialization",
    "meshStack": "per-device shard assembly into the globally-sharded "
                 "stack (ICI exchange)",
    "meshSizeExchange": "all-to-all partition-size exchange over the "
                        "mesh",
    "meshExchange": "HBM-resident all-to-all data exchange over the "
                    "mesh",
    "compile": "JIT build+compile on a cache miss (cache= names the "
               "LRU, program= the srt_ program built)",
    "srt.query": "root span of one query, session.execute_plan start "
                 "to end (q=, tenant=, parent= for a scalar subquery)",
    "plan": "host planning on the calling thread (mirrors planTime): "
            "phase=parse in session.sql, phase=rewrite in execute_plan "
            "up to execute_collect (cacheHit= plan-cache outcome); "
            "nested in it and untimed, phase=subquery: a comma list, "
            "an IN (subquery) or an EXISTS rewritten into joins "
            "(sql/logical.py), and inside that phase=decorrelate: one "
            "correlated [NOT] EXISTS lifted into a join's condition",
    "deviceSync": "the calling thread blocked reading a device value "
                  "back (mirrors deviceSyncTime; site= names the read)",
    "semaphoreWait": "wall blocked on the device semaphore",
    "serveQueueWait": "admission-queue wait of a served query "
                      "(docs/serving.md)",
    "spillToHost": "device->host store demotion",
    "spillToDisk": "host->disk store demotion",
    "promoteFromDisk": "disk->host store promotion",
    "promoteToDevice": "host->device store promotion",
    "retryBlock": "spill+backoff recovery inside an OOM retry (the "
                  "retryBlockTime interval)",
    "aqeReplan": "an adaptive runtime replan over measured exchange "
                 "stats (action= broadcastDemotion/skewSplit; "
                 "docs/adaptive.md)",
    "resultCacheHit": "a query served verbatim from the result cache "
                      "— zero device work, zero queue wait, zero "
                      "admission slot (docs/caching.md)",
    "cacheEntryDrop": "the device pool dropped a cache-tier entry "
                      "under pressure instead of spilling a live "
                      "query's batch (docs/caching.md)",
    "scanRead": "the device-decode plan reading a unit's file: "
                "what=footer (the footer opened and parsed) or "
                "what=chunk (one column chunk's seek + read); bytes= "
                "(mirrors scanReadTime; nested in "
                "FileScan.deviceDecodeTime)",
    "scanInflate": "one column chunk's pages decompressed, its "
                   "dictionary page included; bytes= out, pages= "
                   "(mirrors scanInflateTime; nested in scanParse)",
    "scanStrLengths": "one PLAIN or DELTA_LENGTH byte-array page's "
                      "value lengths found on the host, or one string "
                      "chunk with a PLAIN page decoded by pyarrow (its "
                      "values and their lengths); values=, bytes= "
                      "(mirrors scanStrLengthsTime; nested in "
                      "scanParse, which counts it too)",
    "scanParse": "the rest of one column chunk's device-decode plan: "
                 "page headers, level/run tables, dictionary pages, "
                 "string lengths, DELTA runs (mirrors scanParseTime, "
                 "which leaves its scanInflate spans out)",
    "scanWait": "a scan stream's consumer blocked on an empty prefetch "
                "ring (chip=); while every open stream of the query "
                "waits at once the query books scanStarvedTime (host "
                "starvation, not device idle: the device may still run "
                "queued work)",
    "gcPause": "a collection of the interpreter's generation 1 or 2 "
               "(gen=), on the thread that triggered it (mirrors "
               "gcPauseTime, which counts generation 0 too)",
}

INSTANT_CATALOG: Dict[str, str] = {
    "firstDispatch": "the query's first enqueue of any device program "
                     "(program= names it; firstDispatchTime holds the "
                     "nanoseconds since the query began)",
    "retryOOM": "an OOM retry re-attempted the operation",
    "splitRetry": "an input batch split in half after OOM exhaustion",
    "ioRetry": "a transient reader IO error was retried",
    "chipFailure": "a mesh chip was demoted after persistent failure",
    "compileCacheContention": "a thread blocked on another thread's "
                              "in-progress compile of the same key",
    "queryEnd": "a query finished while the ring recorder was active "
                "(wallSeconds/rows/error attrs)",
    "telemetryTrigger": "a telemetry trigger fired (trigger= names it; "
                        "docs/observability.md 'Live telemetry')",
    "queryCancelled": "a query's CancelToken was cancelled (reason= "
                      "cancel/deadline/disconnect/watchdog/shutdown/"
                      "injected; docs/serving.md 'Query lifecycle')",
    "oocJoinPlan": "the budget oracle partitioned a hash join into "
                   "spill-backed buckets (modulus=/depth=; depth > 0 "
                   "is a recursive escalation — docs/out_of_core.md)",
    "oocAggPlan": "the budget oracle bucketed an aggregation by "
                  "grouping-key hash (modulus=/depth=; "
                  "docs/out_of_core.md)",
}


# Who reads each kind (docs/observability.md renders this beside the
# catalogs): tracing is code on the hot path, so a kind nobody reads —
# no `tools` report, no trigger, no benchmark metric — is deleted with
# its catalog line, not kept. Every kind is also in `tools trace`'s
# generic sections (critical path, self-time, slowest spans, marker
# counts) and, when annotated, a candidate owner of a device idle gap in
# `tools trace <profile dir>`; what is listed here is the SPECIFIC use.
_GENERIC = "`tools trace` critical path / self-time / idle-gap owner"
KIND_READERS: Dict[str, str] = {
    "scanPrefetch": "`tools doctor` scan stage; mirrors scanPrefetchTime "
                    "-> benchmark `scan_host_s`; idle-gap owner while "
                    "the scan starts",
    "uploadAhead": "`tools doctor` scan stage; " + _GENERIC,
    "finishUpload": _GENERIC + " (per staging mode and chip)",
    "TpuFusedStageExec.dispatch": "`tools trace` enqueue occupancy per "
                                  "chip; program= ties a host enqueue "
                                  "to the device's program",
    "TpuHashAggregateExec.dispatch": "`tools trace` enqueue occupancy "
                                     "per chip; program= ties a host "
                                     "enqueue to the device's program",
    "exchangeMaterialize": _GENERIC + " (the exchange's drain wall)",
    "meshStack": _GENERIC + " (four-chip runs)",
    "meshSizeExchange": _GENERIC + " (four-chip runs)",
    "meshExchange": _GENERIC + " (four-chip runs)",
    "compile": "`tools doctor` compileStorm verdict (compile stage)",
    "srt.query": "`tools trace` per-query table and `--query`; the "
                 "window of `tools trace <profile dir>`",
    "plan": "mirrors planTime -> benchmark `plan_host_s`; idle-gap owner "
            "before the first dispatch",
    "deviceSync": "mirrors deviceSyncTime -> benchmark "
                  "`device_wait_host_s`; idle-gap owner (site= says "
                  "which read)",
    "semaphoreWait": _GENERIC + " (mirrors semaphoreWaitTime)",
    "serveQueueWait": _GENERIC + " of served queries (admission wait)",
    "spillToHost": _GENERIC + " (store tier movement)",
    "spillToDisk": _GENERIC + " (store tier movement)",
    "promoteFromDisk": _GENERIC + " (store tier movement)",
    "promoteToDevice": _GENERIC + " (store tier movement)",
    "retryBlock": "`tools doctor` retrySpill verdict (divergent stage); "
                  "subtracted from operator self-time",
    "aqeReplan": _GENERIC + " (adaptive replans)",
    "resultCacheHit": _GENERIC + " of served queries (a hit's whole "
                      "wall)",
    "cacheEntryDrop": _GENERIC + " (cache-tier pressure)",
    "scanRead": "mirrors scanReadTime -> benchmark `scan_read_host_s`; "
                "idle-gap owner inside FileScan.deviceDecodeTime",
    "scanInflate": "mirrors scanInflateTime -> benchmark "
                   "`scan_inflate_host_s`; idle-gap owner inside "
                   "scanParse",
    "scanStrLengths": "mirrors scanStrLengthsTime -> benchmark "
                      "`string_lengths_host_s`; idle-gap owner inside "
                      "scanParse",
    "scanParse": "mirrors scanParseTime -> benchmark "
                 "`scan_parse_host_s`; idle-gap owner inside "
                 "FileScan.deviceDecodeTime",
    "scanWait": "its starved part is scanStarvedTime -> benchmark "
                "`scan_starved_s`; idle-gap owner only where no work "
                "span covers the instant",
    "gcPause": "mirrors gcPauseTime -> benchmark `gc_pause_s`; idle-gap "
               "owner (a collection inside a gap)",
    "firstDispatch": "mirrors firstDispatchTime -> benchmark "
                     "`first_dispatch_s`; marks the end of the first "
                     "device gap",
    "retryOOM": "`tools trace` marker counts (retry storms)",
    "splitRetry": "`tools trace` marker counts (retry storms)",
    "ioRetry": "`tools trace` marker counts",
    "chipFailure": "`tools trace` marker counts (degraded mesh)",
    "compileCacheContention": "`tools trace` marker counts "
                              "(single-flight waits)",
    "queryEnd": "query boundaries in a flight-recorder dump (slow-query "
                "bundles, `tools trace` on ring dumps)",
    "telemetryTrigger": "marks in a ring dump where a trigger fired "
                        "(bundle forensics)",
    "queryCancelled": "`tools trace` marker counts (lifecycle forensics)",
    "oocJoinPlan": "`tools trace` marker counts (out-of-core plans)",
    "oocAggPlan": "`tools trace` marker counts (out-of-core plans)",
}


# ---------------------------------------------------------------------------
# The per-query record: one id on every span, whichever sink is open
# ---------------------------------------------------------------------------

_QSEQ = itertools.count(1)      # next() is atomic under the GIL
_FIRST_LOCK = threading.Lock()  # QueryScope.first_dispatch
_SCAN_LOCK = threading.Lock()   # QueryScope.scan_streams
_QTLS = threading.local()


class QueryScope:
    """One query's identity for the tracing layer: ``q`` (process-wide
    id), ``tenant``, ``t_begin`` (``perf_counter_ns`` at
    ``execute_plan``'s start), ``parent`` (the enclosing query's id for
    a scalar subquery). Its host intervals (planTime,
    firstDispatchTime, deviceSyncTime) go to the process's
    ``metrics.query_registry``.

    It reaches the work two ways. ``execute_plan`` stamps it on every
    registry of the executing plan (``stamp_plan``), so code that holds
    a registry — on whatever pool thread — finds it as
    ``scope_of(metrics)``; and it is the calling thread's
    ``current_scope()``, which ``attach`` re-enters on the task, scan
    and shuffle pool threads for the sites that hold no registry.

    ``pending`` marks a scope opened ahead of execution (``session.sql``
    for the parse, the server before admission): the next
    ``execute_plan`` on that plan or thread adopts it instead of
    nesting under it.

    It also counts the query's open scan streams and those of them
    waiting on an empty prefetch ring (``scan_streams``)."""

    __slots__ = ("q", "tenant", "t_begin", "parent", "pending",
                 "running", "_dispatched", "_streams", "_waiting",
                 "_starved_at")

    def __init__(self, tenant: Optional[str] = None,
                 pending: bool = False):
        outer = getattr(_QTLS, "scope", None)
        self.q = next(_QSEQ)
        self.tenant = tenant
        self.t_begin = time.perf_counter_ns()
        # a parent is a query whose execute_plan is on this thread's
        # stack right now (scalar subqueries run inside its planning)
        self.parent = (outer.q if outer is not None and outer.running
                       else None)
        self.pending = pending
        self.running = False
        self._dispatched = False
        self._streams = 0
        self._waiting = 0
        self._starved_at: Optional[int] = None

    def first_dispatch(self, program: Optional[str]) -> None:
        """Called at every enqueue of a device program; the first one
        of the query books firstDispatchTime (ns since ``t_begin``)
        and the ``firstDispatch`` instant — exactly once: the task
        threads race for it under one process-wide lock that only
        first dispatches ever take."""
        with _FIRST_LOCK:
            if self._dispatched:
                return
            self._dispatched = True
        from spark_rapids_tpu import metrics as M
        M.query_registry().create(
            M.FIRST_DISPATCH_TIME, M.ESSENTIAL).add(
            time.perf_counter_ns() - self.t_begin)
        instant("firstDispatch", scope=self, program=program)

    def scan_streams(self, opened: int = 0, waiting: int = 0) -> None:
        """One change to the query's scan streams: ``opened`` is +1 at a
        stream consumer's first pull and -1 once it has handed on its
        last batch, ``waiting`` +1 when a pull finds the prefetch ring
        empty and -1 when the item comes. While every open stream waits
        at once no stream has a batch it could hand the device: that
        interval is booked to scanStarvedTime."""
        now = time.perf_counter_ns()
        with _SCAN_LOCK:
            self._streams += opened
            self._waiting += waiting
            starved = 0 < self._streams == self._waiting
            since = self._starved_at
            if starved and since is None:
                self._starved_at = now
            if starved or since is None:
                return
            self._starved_at = None
        from spark_rapids_tpu import metrics as M
        M.query_registry().create(M.SCAN_STARVED_TIME, M.ESSENTIAL).add(
            now - since)


def current_scope() -> Optional[QueryScope]:
    """The calling thread's query (None outside any)."""
    return getattr(_QTLS, "scope", None)


def scope_of(metrics) -> Optional[QueryScope]:
    """The query a metric registry was stamped with, else the calling
    thread's."""
    sc = getattr(metrics, "_query", None)
    return sc if sc is not None else getattr(_QTLS, "scope", None)


class attach:
    """``with attach(scope):`` — make ``scope`` the calling thread's
    query for the block (pool threads re-enter the creating thread's
    scope, exactly like ``lifecycle.token_scope``); None is a no-op."""

    __slots__ = ("scope", "prev")

    def __init__(self, scope: Optional[QueryScope]):
        self.scope = scope

    def __enter__(self):
        self.prev = getattr(_QTLS, "scope", None)
        if self.scope is not None:
            _QTLS.scope = self.scope
        return self.scope

    def __exit__(self, *exc):
        _QTLS.scope = self.prev
        return False


def stamp_plan(physical, scope: Optional[QueryScope]) -> None:
    """Tag every metric registry of ``physical`` (fused constituents
    included) with the executing query, the way
    ``memory.stamp_plan_tenant`` tags the tenant: the registry travels
    with the exec's closures into whatever pool thread does the work."""
    if scope is None or physical is None:
        return
    from spark_rapids_tpu.metrics import plan_registries
    for reg in plan_registries(physical):
        reg._query = scope


def first_dispatch(metrics, fn) -> None:
    """Dispatch-site hook: ``fn`` (a ``named_jit`` program) is about to
    be enqueued on behalf of ``metrics``' query."""
    sc = scope_of(metrics)
    if sc is not None and not sc._dispatched:
        from spark_rapids_tpu.jit_cache import program_of
        sc.first_dispatch(program_of(fn))


def device_sync(site: str, metrics=None) -> "span":
    """``with device_sync("rowCount", metrics):`` around a host read of
    a device value (``np.asarray``, ``int()``, ``bool()`` of a device
    array): the calling thread blocks there until the device has
    produced it. Books thread-nanoseconds into the query's
    deviceSyncTime and a ``deviceSync`` span (``site=``)."""
    from spark_rapids_tpu import metrics as M
    return span("deviceSync", scope=scope_of(metrics), site=site,
                timer=M.query_registry().create(M.DEVICE_SYNC_TIME,
                                                M.ESSENTIAL))


def annotation(kind: str, scope: Optional[QueryScope] = None,
               batch=None, attrs: Optional[dict] = None
               ) -> TraceAnnotation:
    """The span ``kind`` as a ``jax.profiler.TraceAnnotation`` (enter
    and exit it on ONE thread): any profiler session then shows the
    engine's spans on the host planes beside the device's ``XLA Ops``,
    on one clock. Not gated by ``trace.enabled`` — outside a profiler
    session entering it is a flag test."""
    kw = {}
    if scope is not None:
        kw["q"] = scope.q
    if batch is not None:
        kw["batch"] = batch
    if attrs:
        for k, v in attrs.items():
            if v is not None:
                kw[k] = v
    return TraceAnnotation(kind, **kw)


# ---------------------------------------------------------------------------
# Active-trace state (process-wide, like the DeviceStore / FaultInjector)
# ---------------------------------------------------------------------------

class QueryTrace:
    """Span sink for one traced query. ``add``/``mark`` are called from
    task/pool threads concurrently; CPython ``list.append`` is atomic
    under the GIL, so the hot path takes no lock."""

    __slots__ = ("query_id", "t0", "wall_t0", "spans", "instants",
                 "counters", "_thread_names", "tenant")

    def __init__(self, query_id: int, tenant: Optional[str] = None):
        self.query_id = query_id
        # serving tenancy: the tenant of the session that OPENED the
        # trace (concurrent queries from other sessions fold their
        # spans into this file — the documented process-timeline
        # limitation — but the root attribution names its owner)
        self.tenant = tenant
        self.t0 = time.perf_counter_ns()
        self.wall_t0 = time.time()
        # span record: (kind, t0_ns, t1_ns, thread_ident, batch, chip,
        #               attrs-or-None)
        self.spans: List[Tuple] = []
        # instant record: (kind, t_ns, thread_ident, attrs-or-None)
        self.instants: List[Tuple] = []
        # counter sample: (series, t_ns, value) — Chrome "C" events;
        # the device/host pool occupancy timeline (docs/observability.md)
        self.counters: List[Tuple] = []
        self._thread_names: Dict[int, str] = {}

    def _thread(self) -> int:
        t = threading.current_thread()
        ident = t.ident or 0
        if ident not in self._thread_names:
            self._thread_names[ident] = t.name
        return ident

    def add(self, kind: str, t0: int, t1: int, batch=None, chip=None,
            **attrs) -> None:
        self.spans.append((kind, t0, t1, self._thread(), batch, chip,
                           _clean(_with_q(attrs))))

    def mark(self, kind: str, **attrs) -> None:
        self.instants.append((kind, time.perf_counter_ns(),
                              self._thread(), _clean(_with_q(attrs))))

    def count(self, series: str, value) -> None:
        self.counters.append((series, time.perf_counter_ns(), value))


def _with_q(attrs: dict, scope=None) -> dict:
    """Every record says whose it is: ``q`` (and ``parent`` for a
    scalar subquery) from the ``scope`` the caller holds, else from
    the recording thread's (``attach`` carries it onto pool threads)."""
    if "q" not in attrs:
        sc = scope if scope is not None else getattr(_QTLS, "scope", None)
        if sc is not None:
            attrs["q"] = sc.q
            if sc.parent is not None:
                attrs["parent"] = sc.parent
    return attrs


def _clean(attrs: dict) -> Optional[dict]:
    if not attrs:
        return None
    out = {k: v for k, v in attrs.items() if v is not None}
    return out or None


# Hot-path flag: hooks read this module global directly (one attribute
# load when tracing is off). Guarded by _LOCK only for begin/end.
_ACTIVE: Optional[QueryTrace] = None
_LOCK = threading.Lock()
# an installed flight recorder parked while a file-mode root query
# owns _ACTIVE: the ring is process-lifetime state and a file trace
# must not destroy it (restored when the file trace closes)
_RING_STASH: Optional[QueryTrace] = None
_DEPTH = 0           # nested execute_plan calls (scalar subqueries)
_SEQ = 0             # traced-candidate query counter (sampling stream)
_RNG: Optional[random.Random] = None
_RNG_SEED: Optional[int] = None


def active() -> Optional[QueryTrace]:
    return _ACTIVE


def ring_active():
    """The installed flight recorder (telemetry.ring.RingTrace) when
    trace.mode=ring has been activated, else None."""
    qt = _ACTIVE
    return qt if getattr(qt, "is_ring", False) else None


def reset_tracing() -> None:
    """Drop the sampling stream + query counter so the next query sees
    a fresh deterministic schedule (tests call this between runs, like
    retry.reset_fault_injection). Uninstalls an active ring recorder
    too."""
    global _ACTIVE, _DEPTH, _SEQ, _RNG, _RNG_SEED, _RING_STASH
    with _LOCK:
        _ACTIVE = None
        _RING_STASH = None
        _DEPTH = 0
        _SEQ = 0
        _RNG = None
        _RNG_SEED = None
    _QTLS.scope = None


def begin_query(conf_obj) -> Optional[str]:
    """Start (or join) a query trace. Returns an opaque token for
    ``end_query`` — ``None`` when tracing is disabled, ``"root"`` when
    this call opened the trace, ``"ring"`` when the flight recorder is
    the sink (trace.mode=ring — installed on first use, shared by
    every query for the process life), ``"nested"``/``"unsampled"``
    otherwise. Nested queries (scalar subqueries executed during
    planning) fold their spans into the outer query's trace; so does a
    concurrent query from another session thread (documented
    limitation — span streams are a property of the process
    timeline)."""
    global _ACTIVE, _DEPTH, _SEQ, _RNG, _RNG_SEED, _RING_STASH
    if conf_obj is None or not bool(conf_obj.get(TRACE_ENABLED)):
        return None
    if str(conf_obj.get(TRACE_MODE)).lower() == "ring":
        # flight recorder: always on once installed, never sampled,
        # never cleared at query end — the interesting query is the
        # one you didn't pre-instrument. A query that begins while a
        # file-mode trace is open folds into that trace instead (the
        # nested-scope contract above).
        with _LOCK:
            if _ACTIVE is None:
                from spark_rapids_tpu.telemetry.ring import RingTrace
                from spark_rapids_tpu.conf import SERVE_TENANT_ID
                _ACTIVE = RingTrace(
                    int(conf_obj.get(TRACE_RING_SPANS)),
                    tenant=str(conf_obj.get(SERVE_TENANT_ID)) or None)
            elif not getattr(_ACTIVE, "is_ring", False):
                # a file-mode trace is open: fold into it WITHOUT
                # touching its depth bookkeeping (the "folded" token
                # is a no-op at end_query)
                return "folded"
            _ACTIVE.queries_begun += 1
            return "ring"
    with _LOCK:
        _DEPTH += 1
        if _DEPTH > 1:
            return "nested"
        _SEQ += 1
        rate = float(conf_obj.get(TRACE_SAMPLE_RATE))
        if rate < 1.0:
            seed = int(conf_obj.get(TRACE_SAMPLE_SEED))
            if _RNG is None or _RNG_SEED != seed:
                _RNG = random.Random(seed)
                _RNG_SEED = seed
            if _RNG.random() >= rate:
                return "unsampled"
        from spark_rapids_tpu.conf import SERVE_TENANT_ID
        if getattr(_ACTIVE, "is_ring", False):
            # park the process-lifetime flight recorder for the file
            # trace's duration — a file-mode query must not destroy
            # the ring's accumulated history (restored at end_query)
            _RING_STASH = _ACTIVE
        _ACTIVE = QueryTrace(
            _SEQ, tenant=str(conf_obj.get(SERVE_TENANT_ID)) or None)
        return "root"


def end_query(conf_obj, token: Optional[str], wall_s: float = 0.0,
              rows: int = 0, error: bool = False) -> Optional[str]:
    """Close a ``begin_query`` scope; on the outermost sampled close,
    write the Chrome-trace file and return its path. Failures never
    break the query (observability must not take down execution)."""
    global _ACTIVE, _DEPTH, _RING_STASH
    if token is None:
        return None
    if token == "folded":
        return None
    if token == "ring":
        # the recorder stays installed; the query leaves only a
        # boundary marker (the trigger engine receives wall/rows via
        # its own query-end hook, telemetry/triggers.py)
        qt = ring_active()
        if qt is not None:
            qt.mark("queryEnd", wallSeconds=round(wall_s, 6), rows=rows,
                    error=bool(error) or None)  # q= from the thread's scope
        return None
    with _LOCK:
        _DEPTH = max(0, _DEPTH - 1)
        if token != "root":
            return None
        # reinstall a parked flight recorder, if any
        qt, _ACTIVE, _RING_STASH = _ACTIVE, _RING_STASH, None
    if qt is None:
        return None
    try:
        trace_dir = str(conf_obj.get(TRACE_DIR))
        os.makedirs(trace_dir, exist_ok=True)
        path = os.path.join(
            trace_dir, f"trace-{os.getpid()}-q{qt.query_id:05d}.json")
        write_chrome_trace(path, qt, wall_s=wall_s, rows=rows,
                           error=error)
        return path
    except Exception:
        return None


# ---------------------------------------------------------------------------
# Recording helpers (the instrumentation surface)
# ---------------------------------------------------------------------------

class span:
    """``with span(kind, ...):`` — one interval into both channels: the
    profiler's trace (a ``TraceAnnotation``, always) and the host span
    stream (when a trace sink is open). ``scope`` (or ``metrics``, a
    stamped registry) names the query; the calling thread's scope is
    the fallback. ``timer`` additionally books the interval into that
    always-on ``MetricRegistry`` timer — measured once, so the metric,
    the span and the annotation agree. Must be entered and left on one
    thread."""

    __slots__ = ("kind", "scope", "batch", "chip", "attrs", "timer",
                 "t0", "t1", "_ann")

    def __init__(self, kind: str, batch=None, chip=None, scope=None,
                 metrics=None, timer=None, **attrs):
        self.kind = kind
        self.scope = scope if scope is not None else scope_of(metrics)
        self.batch = batch
        self.chip = chip
        self.attrs = attrs
        self.timer = timer

    def __enter__(self):
        self._ann = annotation(self.kind, self.scope, self.batch,
                               self.attrs)
        self._ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def annotate(self, **attrs) -> None:
        """Attributes known only inside the interval (a footer's size
        once it is read), on the annotation and the host record."""
        self.attrs.update(attrs)
        self._ann.set_metadata(**attrs)

    def __exit__(self, *exc):
        t1 = self.t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        if self.timer is not None:
            self.timer.add(t1 - self.t0)
        qt = _ACTIVE
        if qt is not None:
            record(qt, self.kind, self.t0, t1, self.scope, self.batch,
                   self.chip, self.attrs)
        return False


def record(qt, kind: str, t0: int, t1: int, scope=None, batch=None,
           chip=None, attrs: Optional[dict] = None) -> None:
    """Append one finished interval to the open sink ``qt`` under
    ``scope``'s query id (host stream only: the interval is over, so
    the profiler cannot be told — sites that can, use ``span``)."""
    qt.add(kind, t0, t1, batch=batch, chip=chip,
           **_with_q(dict(attrs) if attrs else {}, scope))


def instant(kind: str, scope=None, **attrs) -> None:
    """Point-in-time marker (retry/backoff/split/chip-failure events):
    a zero-length annotation in the profiler's trace, a marker in the
    host stream."""
    if scope is None:
        scope = getattr(_QTLS, "scope", None)
    with annotation(kind, scope, None, attrs):
        pass
    qt = _ACTIVE
    if qt is not None:
        qt.mark(kind, **_with_q(attrs, scope))


def counter(series: str, value) -> None:
    """Counter sample (Chrome "C" event): Perfetto renders each series
    as a stepped occupancy track next to the span lanes. Used by the
    DeviceStore so the HBM/host pool timeline sits beside the query's
    spans. One None check when tracing is off."""
    qt = _ACTIVE
    if qt is not None:
        qt.count(series, value)


def chip_of(batch) -> Optional[int]:
    """The chip a device batch is resident on, for span attribution —
    None (and no device query at all) when tracing is off."""
    if _ACTIVE is None:
        return None
    try:
        from spark_rapids_tpu.columnar.device import batch_device
        d = batch_device(batch)
        return d.id if d is not None else None
    except Exception:
        return None


# ---------------------------------------------------------------------------
# The interpreter's collections (one process-wide gc.callbacks hook)
# ---------------------------------------------------------------------------

# (t0, annotation or None, scope) of the collection under way: CPython
# runs one collection at a time, its callbacks on the collecting thread
_GC_OPEN: list = []
_GC_TIMERS: list = []   # [gcPauseTime, gcCollections.0, .1, .2]


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook: every collection books gcPauseTime and
    gcCollections.<gen>; one of generation 1 or 2 is also a
    ``gcPause`` span (gen=) under the collecting thread's query. The
    metrics are created at install: a registry's create() takes a lock
    the interrupted thread may hold."""
    gen = info["generation"]
    if phase == "start":
        sc = getattr(_QTLS, "scope", None)
        ann = None
        if gen:
            ann = annotation("gcPause", sc, None, {"gen": gen})
            ann.__enter__()
        _GC_OPEN[:] = (time.perf_counter_ns(), ann, sc)
        return
    if not _GC_OPEN:
        return  # installed mid-collection
    t0, ann, sc = _GC_OPEN
    del _GC_OPEN[:]
    t1 = time.perf_counter_ns()
    _GC_TIMERS[0].add(t1 - t0)
    _GC_TIMERS[1 + min(gen, 2)].add(1)
    if ann is not None:
        ann.__exit__(None, None, None)
        qt = _ACTIVE
        if qt is not None:
            record(qt, "gcPause", t0, t1, sc, attrs={"gen": gen})


def install_gc_hook() -> None:
    """Register ``_on_gc`` once per process (engine start:
    ``device_manager.initialize``); its totals live in the query
    registry, which the telemetry aggregator folds."""
    import gc
    from spark_rapids_tpu import metrics as M
    with _LOCK:
        if _on_gc in gc.callbacks:
            return
        reg = M.query_registry()
        _GC_TIMERS[:] = [reg.create(M.GC_PAUSE_TIME, M.ESSENTIAL)] + [
            reg.create(f"gcCollections.{g}", M.ESSENTIAL)
            for g in range(3)]
        gc.callbacks.append(_on_gc)


# ---------------------------------------------------------------------------
# Chrome trace-event export
# ---------------------------------------------------------------------------
#
# Spans are emitted as matched B/E pairs (ph "B"/"E"), instants as ph
# "i". Within one recording thread, context-manager spans are properly
# nested (LIFO); a span that spans a generator yield can resume on a
# different consumer thread and partially overlap its lane's stack, so
# the writer assigns spans greedily to LANES: a span joins the first
# lane whose open spans all fully contain it, otherwise it opens an
# overflow lane (tid "<thread>!k"). Every lane's event stream is
# strictly nested and time-ordered, which is exactly what the Chrome
# B/E semantics (and the schema test) require.

def _us(t_ns: int, base_ns: int) -> float:
    return round((t_ns - base_ns) / 1000.0, 3)


def _lane_events(spans: List[Tuple], base: int, pid: int,
                 tid0: int) -> Tuple[List[dict], int]:
    """Per-source-thread span list -> correctly nested B/E streams over
    one or more lanes. Returns (events, lanes_used)."""
    events: List[dict] = []
    # lane state: list of stacks; each stack holds (t1, kind) of opens
    lanes: List[List[Tuple[int, str]]] = []
    lane_ev: List[List[dict]] = []
    for kind, t0, t1, _ident, batch, chip, attrs in sorted(
            spans, key=lambda s: (s[1], -s[2])):
        args: Dict[str, Any] = {}
        if batch is not None:
            args["batch"] = batch
        if chip is not None:
            args["chip"] = chip
        if attrs:
            args.update(attrs)
        placed = False
        for li in range(len(lanes)):
            stack, ev = lanes[li], lane_ev[li]
            while stack and stack[-1][0] <= t0:
                ct1, ckind = stack.pop()
                ev.append({"name": ckind, "ph": "E", "pid": pid,
                           "tid": tid0 + li, "ts": _us(ct1, base)})
            if not stack or stack[-1][0] >= t1:
                b = {"name": kind, "ph": "B", "pid": pid,
                     "tid": tid0 + li, "ts": _us(t0, base)}
                if args:
                    b["args"] = args
                ev.append(b)
                stack.append((t1, kind))
                placed = True
                break
        if not placed:
            li = len(lanes)
            b = {"name": kind, "ph": "B", "pid": pid, "tid": tid0 + li,
                 "ts": _us(t0, base)}
            if args:
                b["args"] = args
            lanes.append([(t1, kind)])
            lane_ev.append([b])
    for li, stack in enumerate(lanes):
        while stack:
            ct1, ckind = stack.pop()
            lane_ev[li].append({"name": ckind, "ph": "E", "pid": pid,
                                "tid": tid0 + li, "ts": _us(ct1, base)})
    for ev in lane_ev:
        events.extend(ev)
    return events, max(1, len(lanes))


def write_chrome_trace(path: str, qt: QueryTrace, wall_s: float = 0.0,
                       rows: int = 0, error: bool = False) -> None:
    base = qt.t0
    pid = os.getpid()
    events: List[dict] = [{
        "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
        "args": {"name": f"spark-rapids-tpu q{qt.query_id}"}}]
    by_thread: Dict[int, List[Tuple]] = {}
    for s in qt.spans:
        by_thread.setdefault(s[3], []).append(s)
    tid = 1
    for ident in sorted(by_thread):
        ev, lanes = _lane_events(by_thread[ident], base, pid, tid)
        name = qt._thread_names.get(ident, str(ident))
        for li in range(lanes):
            events.append({"name": "thread_name", "ph": "M", "pid": pid,
                           "tid": tid + li,
                           "args": {"name": name if li == 0
                                    else f"{name}!{li}"}})
        events.extend(ev)
        tid += lanes
    # instants get a dedicated lane per source thread, time-sorted:
    # sharing the span lane would interleave timestamps out of order
    # (a ring dump always carries markers older than the lane's last
    # span end), breaking the per-tid monotonicity the schema test —
    # and Perfetto's track model — expect
    ins_by_thread: Dict[int, List[Tuple]] = {}
    for ins in qt.instants:
        ins_by_thread.setdefault(ins[2], []).append(ins)
    for ident in sorted(ins_by_thread):
        name = qt._thread_names.get(ident, str(ident))
        events.append({"name": "thread_name", "ph": "M", "pid": pid,
                       "tid": tid, "args": {"name": f"{name}!i"}})
        for kind, t_ns, _ident, attrs in sorted(
                ins_by_thread[ident], key=lambda i: i[1]):
            ev = {"name": kind, "ph": "i", "s": "t", "pid": pid,
                  "tid": tid, "ts": _us(t_ns, base)}
            if attrs:
                ev["args"] = attrs
            events.append(ev)
        tid += 1
    if qt.counters:
        # counter tracks get a lane of their own: samples from many
        # threads interleave in append order, so sort by time to keep
        # the per-tid stream monotone (the schema test's invariant)
        ctid = tid
        events.append({"name": "thread_name", "ph": "M", "pid": pid,
                       "tid": ctid, "args": {"name": "counters"}})
        for series, t_ns, value in sorted(qt.counters,
                                          key=lambda c: c[1]):
            events.append({"name": series, "ph": "C", "pid": pid,
                           "tid": ctid, "ts": _us(t_ns, base),
                           "args": {"value": value}})
    doc = {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {
            "version": 1,
            "queryId": qt.query_id,
            "pid": pid,
            "wallSeconds": round(wall_s, 6),
            "outputRows": rows,
            "error": bool(error),
            "startUnixTime": qt.wall_t0,
            "spanCount": len(qt.spans),
            "instantCount": len(qt.instants),
            "counterCount": len(qt.counters),
        },
    }
    if qt.tenant:
        doc["otherData"]["tenant"] = qt.tenant
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        # default=str: attr values are normally JSON scalars, but an
        # exotic attr must degrade to its repr, never kill the write
        json.dump(doc, f, default=str)
    os.replace(tmp, path)


# ---------------------------------------------------------------------------
# Loader (tools.py's data source)
# ---------------------------------------------------------------------------

def load_trace(path: str) -> Dict[str, Any]:
    """Parse a written trace back into spans/instants (timestamps in
    microseconds from trace start). B/E pairs are matched per tid with
    a stack, exactly the Chrome semantics."""
    with open(path) as f:
        doc = json.load(f)
    spans: List[dict] = []
    instants: List[dict] = []
    counters: List[dict] = []
    tid_names: Dict[int, str] = {}
    stacks: Dict[int, List[dict]] = {}
    for ev in doc.get("traceEvents", []):
        ph = ev.get("ph")
        tid = ev.get("tid", 0)
        if ph == "M":
            if ev.get("name") == "thread_name":
                tid_names[tid] = ev.get("args", {}).get("name", str(tid))
        elif ph == "B":
            stacks.setdefault(tid, []).append(ev)
        elif ph == "E":
            st = stacks.get(tid)
            if not st:
                raise ValueError(f"unmatched E event at ts={ev.get('ts')}")
            b = st.pop()
            if b.get("name") != ev.get("name"):
                raise ValueError(
                    f"B/E name mismatch: {b.get('name')} vs "
                    f"{ev.get('name')}")
            spans.append({"name": b["name"], "t0": float(b["ts"]),
                          "t1": float(ev["ts"]), "tid": tid,
                          "args": b.get("args", {})})
        elif ph in ("i", "I"):
            instants.append({"name": ev.get("name"),
                             "ts": float(ev.get("ts", 0)), "tid": tid,
                             "args": ev.get("args", {})})
        elif ph == "C":
            counters.append({"name": ev.get("name"),
                             "ts": float(ev.get("ts", 0)),
                             "value": ev.get("args", {}).get("value")})
    leftover = {t: st for t, st in stacks.items() if st}
    if leftover:
        raise ValueError(f"unmatched B events on tids {sorted(leftover)}")
    return {"spans": spans, "instants": instants, "counters": counters,
            "meta": doc.get("otherData", {}), "tidNames": tid_names}

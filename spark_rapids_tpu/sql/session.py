"""TpuSparkSession: the SparkSession-shaped entry point.

Mirrors the role Spark's session + the plugin's ColumnarOverrideRules hook
play in the reference (Plugin.scala:44-50): after CPU physical planning,
`spark.rapids.sql.enabled` routes the plan through the TpuOverrides rewrite
before execution.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Iterable, List, Optional, Sequence, Union

import numpy as np

from spark_rapids_tpu.conf import TpuConf
from spark_rapids_tpu.columnar.host import HostBatch, HostColumn
from spark_rapids_tpu.sql import expressions as E
from spark_rapids_tpu.sql import logical as L
from spark_rapids_tpu.sql import types as T
from spark_rapids_tpu.sql.dataframe import DataFrame
from spark_rapids_tpu.sql.planner import Planner


class RuntimeConfApi:
    """spark.conf facade."""

    def __init__(self, conf: TpuConf):
        self._conf = conf

    def set(self, key: str, value: Any) -> None:
        self._conf.set(key, value)

    def get(self, key: str, default: Any = None) -> Any:
        return self._conf.get_key(key, default)

    def unset(self, key: str) -> None:
        self._conf.settings.pop(key, None)


class TpuSparkSession:
    _active: Optional["TpuSparkSession"] = None
    _lock = threading.Lock()

    def __init__(self, conf: Optional[Dict[str, Any]] = None):
        self.conf_obj = TpuConf(conf)
        self._owns_mesh = False
        if self.conf_obj.sql_enabled:
            from spark_rapids_tpu import device_manager
            device_manager.initialize()
            from spark_rapids_tpu.conf import (HAS_NANS,
                                               SHUFFLE_ICI_DEVICES,
                                               SHUFFLE_MODE)
            from spark_rapids_tpu.ops import groupby as _G
            _G.set_has_nans(bool(self.conf_obj.get(HAS_NANS)))
            if str(self.conf_obj.get(SHUFFLE_MODE)).lower() == "ici":
                # executor-plugin-init analogue: activate the shuffle
                # mesh once per session (GpuShuffleEnv.initShuffleManager
                # role; jax already knows the topology). Check-then-act
                # under the class lock: concurrent server threads
                # constructing tenant sessions must not both build (and
                # later both tear down) the process mesh
                from spark_rapids_tpu.parallel import mesh as PM
                with TpuSparkSession._lock:
                    if PM.get_active_mesh() is None:
                        n = int(self.conf_obj.get(
                            SHUFFLE_ICI_DEVICES)) or None
                        PM.set_active_mesh(PM.build_mesh(n))
                        self._owns_mesh = True
        # live telemetry (docs/observability.md): a session that sets
        # any spark.rapids.sql.telemetry.* conf arms the process
        # trigger engine's conf-less hooks (HBM watermark, admission
        # saturation, retry storm); default sessions never disarm it
        from spark_rapids_tpu.telemetry import triggers as _telemetry
        _telemetry.configure(self.conf_obj)
        self.conf = RuntimeConfApi(self.conf_obj)
        self.catalog_views: Dict[str, L.LogicalPlan] = {}
        self._plan_capture: List = []  # ExecutionPlanCaptureCallback twin
        self._capture_enabled = False
        self.last_rewrite_report = None
        self.last_profile_path: Optional[str] = None
        # per-thread mirrors of last_rewrite_report/last_profile_path:
        # concurrent queries on ONE session (the server shares a
        # session per tenant) race the session-level attributes; each
        # worker thread plans AND executes on its own thread, so the
        # profile/event-log sinks read the thread's own report and the
        # server reads thread_profile_path
        self._tls = threading.local()
        # serving tenant id (docs/serving.md): threads through the
        # store's per-tenant HBM ledger, trace files, event-log lines,
        # and profile artifacts; "" = untenanted
        from spark_rapids_tpu.conf import SERVE_TENANT_ID
        self.tenant: Optional[str] = \
            str(self.conf_obj.get(SERVE_TENANT_ID)) or None
        # the previously-active session is REMEMBERED, not clobbered:
        # stop() restores it, so interleaved session lifetimes (the
        # server keeps one live session per tenant) leave active()
        # pointing at a live session instead of None/stale
        self._stopped = False
        with TpuSparkSession._lock:
            self._prev_active = TpuSparkSession._active
            TpuSparkSession._active = self

    # -- builder-compatible constructor
    class Builder:
        def __init__(self):
            self._conf: Dict[str, Any] = {}

        def config(self, key: str, value: Any) -> "TpuSparkSession.Builder":
            self._conf[key] = value
            return self

        def appName(self, name: str) -> "TpuSparkSession.Builder":
            return self

        def master(self, m: str) -> "TpuSparkSession.Builder":
            return self

        def getOrCreate(self) -> "TpuSparkSession":
            return TpuSparkSession(self._conf)

    builder = None  # set below

    @staticmethod
    def active() -> "TpuSparkSession":
        if TpuSparkSession._active is None:
            TpuSparkSession._active = TpuSparkSession()
        return TpuSparkSession._active

    # -- data sources ------------------------------------------------------
    def createDataFrame(self, data, schema=None,
                        num_partitions: int = 2) -> DataFrame:
        batch = _infer_batch(data, schema)
        # split into partitions for realistic multi-partition plans
        np_ = max(1, min(num_partitions, max(1, batch.num_rows)))
        if np_ == 1 or batch.num_rows == 0:
            batches = [batch]
        else:
            per = (batch.num_rows + np_ - 1) // np_
            batches = [batch.slice(i * per, (i + 1) * per)
                       for i in range(np_)
                       if batch.slice(i * per, (i + 1) * per).num_rows > 0]
        rel = L.LocalRelation(batch.schema, batches, len(batches))
        return DataFrame(rel, self)

    def range(self, start: int, end: Optional[int] = None, step: int = 1,
              numPartitions: int = 2) -> DataFrame:
        if end is None:
            start, end = 0, start
        return DataFrame(L.Range(start, end, step, numPartitions), self)

    @property
    def read(self):
        from spark_rapids_tpu.io.readers import DataFrameReader
        return DataFrameReader(self)

    def table(self, name: str) -> DataFrame:
        # qualify outputs by the table name (Spark does the same), so
        # `SELECT t.col FROM t JOIN u ...` resolves unambiguously
        return DataFrame(
            L.SubqueryAlias(name, self.catalog_views[name.lower()]), self)

    def sql(self, query: str) -> DataFrame:
        from spark_rapids_tpu import metrics as M
        from spark_rapids_tpu import trace as TR
        from spark_rapids_tpu.sql.parser import parse_sql
        # the query's record opens HERE, pending, so the parse is the
        # first part of its planTime and its `plan` span carries its
        # id (the server's request scope, when there is one);
        # execute_plan adopts it when this very plan is collected next
        # on this thread (any other use leaves the id unused)
        cur = TR.current_scope()
        scope = (cur if cur is not None and cur.pending
                 else TR.QueryScope(tenant=self.tenant, pending=True))
        with TR.span("plan", scope=scope, phase="parse",
                     timer=M.query_registry().create(M.PLAN_TIME,
                                                     M.ESSENTIAL)):
            df = parse_sql(query, self)
        self._tls.pending_scope = (df.plan, scope)
        return df

    # -- execution ---------------------------------------------------------
    def plan_physical(self, plan: L.LogicalPlan,
                      execute_subqueries: bool = True):
        """CPU physical plan, then the plugin rewrite when enabled.
        ``execute_subqueries=False`` (the explain path) substitutes
        scalar subqueries with unevaluated placeholders — rendering a
        plan must never run the query's subqueries (Spark's explain
        does not either)."""
        from spark_rapids_tpu import udf_compiler
        from spark_rapids_tpu.sql.expressions import \
            materialize_scalar_subqueries
        # comma lists -> inner joins, IN (subquery) -> left semi join,
        # correlated [NOT] EXISTS -> left semi / anti join with its
        # lifted condition (sql/logical.py); a statement with none gets
        # its own plan back from one walk, and no span
        if L.needs_rewrite(plan):
            from spark_rapids_tpu import trace as TR
            with TR.span("plan", phase="subquery"):
                plan = L.rewrite_joins_and_subqueries(plan)
        plan = materialize_scalar_subqueries(
            plan, self if execute_subqueries else None)
        plan = udf_compiler.rewrite_plan(plan, self.conf_obj)
        # cross-query plan-rewrite cache (docs/serving.md): AFTER
        # subquery materialization (their results must be fresh per
        # submission) a repeated query shape skips the whole
        # Planner + apply_overrides + CBO + fusion pipeline and clones
        # the cached template. Scoped to the execute path — the explain
        # path plans with unevaluated placeholders and must not pollute
        # (or hit) the executable cache.
        from spark_rapids_tpu.conf import PLAN_CACHE_ENABLED
        use_cache = (execute_subqueries
                     and bool(self.conf_obj.get(PLAN_CACHE_ENABLED)))
        if use_cache:
            from spark_rapids_tpu import plan_cache as PC
            sig = PC.plan_signature(plan, self.conf_obj)
            # lifecycle keying (docs/serving.md "Query lifecycle"): the
            # signature DIGEST identifies this query shape for the
            # watchdog's p99 history, the poison-query quarantine, and
            # the persistent query history (compact enough to persist
            # per record); threaded per-thread (concurrent queries
            # share this session) and onto the live CancelToken for
            # the watchdog's scan. The plan cache keys on the full
            # string — a digest collision must never alias two plans.
            sig_key = PC.signature_digest(sig)
            self._tls.plan_signature = sig_key
            from spark_rapids_tpu import lifecycle as LC
            ltok = LC.current_token()
            if ltok is not None:
                ltok.signature = sig_key
            # single-flight build: concurrent cold misses of one shape
            # (a burst of identical queries on a fresh server) run the
            # rewrite once; everyone executes a clone of the template
            physical, report, was_miss = PC.get_or_clone(
                sig, lambda: self._rewrite_fresh(plan),
                conf_obj=self.conf_obj)
            self.last_rewrite_report = report
            self._tls.rewrite_report = report
            self._tls.plan_cache_hit = not was_miss
            if not was_miss and report is not None:
                # sql.explain output replays from the cached report
                # (the building thread printed inside apply_overrides)
                report.print_explain(self.conf_obj)
        else:
            self._tls.plan_signature = None
            self._tls.plan_cache_hit = None
            template, report = self._rewrite_fresh(plan)
            physical = template
            self.last_rewrite_report = report
            self._tls.rewrite_report = report
        if self._capture_enabled:
            self._plan_capture.append(physical)
        return physical

    def _rewrite_fresh(self, plan):
        """Run the full rewrite pipeline (CPU planning, TpuOverrides,
        CBO, fusion, broadcast reuse); returns ``(physical, report)``.
        The plan-cache build callback — must not touch session state
        (it may run under the cache's single-flight on behalf of
        another thread's identical query)."""
        physical = Planner(self.conf_obj, session=self).plan(plan)
        report = None
        if self.conf_obj.sql_enabled:
            from spark_rapids_tpu.overrides import (RewriteReport,
                                                    apply_overrides)
            report = RewriteReport()
            physical = apply_overrides(physical, self.conf_obj, report)
        physical = _reuse_broadcast_exchanges(physical)
        return physical, report

    def _open_scope(self, plan):
        """This execution's per-query record (trace.QueryScope): the
        pending one ``sql()`` opened for this plan on this thread, else
        the pending one the server opened before admission, else a new
        one (a scalar subquery's carries its parent's id)."""
        import time as _time

        from spark_rapids_tpu import trace as TR
        pend = getattr(self._tls, "pending_scope", None)
        self._tls.pending_scope = None
        scope = pend[1] if pend is not None and pend[0] is plan else None
        if scope is None:
            cur = TR.current_scope()
            scope = cur if cur is not None and cur.pending else None
        if scope is None:
            return TR.QueryScope(tenant=self.tenant)
        scope.pending = False
        scope.t_begin = _time.perf_counter_ns()
        return scope

    def execute_plan(self, plan: L.LogicalPlan) -> HostBatch:
        from spark_rapids_tpu import trace as TR
        scope = self._open_scope(plan)
        # one root annotation per query in the profiler's trace; the
        # host stream's root span is recorded where the sink closes
        scope.running = True
        try:
            with TR.attach(scope), TR.annotation(
                    "srt.query", scope,
                    attrs={"tenant": scope.tenant,
                           "parent": scope.parent}):
                return self._execute_scoped(plan, scope)
        finally:
            scope.running = False

    def _execute_scoped(self, plan: L.LogicalPlan, scope) -> HostBatch:
        import time as _time

        from spark_rapids_tpu import metrics as M
        from spark_rapids_tpu import trace as TR
        from spark_rapids_tpu.conf import EVENT_LOG_DIR, TASK_PARALLELISM
        if self.conf_obj.sql_enabled:
            # re-assert THIS session's kernel flags before executing:
            # another session constructed since __init__ may have set a
            # different hasNans (kernel_salt keys the program caches, so
            # flips only change which cached trace is used)
            from spark_rapids_tpu.conf import HAS_NANS
            from spark_rapids_tpu.ops import groupby as _G
            _G.set_has_nans(bool(self.conf_obj.get(HAS_NANS)))
        # profiling: re-base the process store's pool + per-owner peak
        # watermarks at query START so each artifact's memory section
        # covers THIS query, not a high-watermark inherited from
        # earlier queries (concurrent queries still share the process
        # store — same documented limitation as the span stream)
        from spark_rapids_tpu import profile as PROF
        if bool(self.conf_obj.get(PROF.PROFILE_ENABLED)):
            from spark_rapids_tpu import memory as _memory
            _memory.reset_store_peaks()
        # span tracing (docs/observability.md): the trace scope opens
        # BEFORE planning so compile spans and scalar-subquery execution
        # (nested execute_plan calls fold into this query's trace) are
        # attributed; one Chrome-trace file per sampled query
        # lifecycle (docs/serving.md "Query lifecycle"): materialize
        # the process fault injector up front so checkpoint-level
        # site:cancel schedules fire even before the first wrapped
        # allocation, and read the quarantine threshold once
        from spark_rapids_tpu import lifecycle as LC
        from spark_rapids_tpu import retry as _retry
        from spark_rapids_tpu.conf import SERVE_QUARANTINE_THRESHOLD
        _retry.get_fault_injector(self.conf_obj)
        quar_thr = int(self.conf_obj.get(SERVE_QUARANTINE_THRESHOLD))
        sig = None
        physical = None
        t_begin = _time.perf_counter()
        tok = TR.begin_query(self.conf_obj)

        def end_trace(**kw):
            # the host stream's root span goes in BEFORE the sink
            # closes (file mode writes the file at end_query)
            qt = TR._ACTIVE
            if qt is not None:
                TR.record(qt, "srt.query", scope.t_begin,
                          _time.perf_counter_ns(), scope,
                          attrs={"tenant": scope.tenant})
            return TR.end_query(self.conf_obj, tok, **kw)

        try:
            # planTime / the `plan` span: everything the calling thread
            # does between the query's begin and execute_collect (a
            # scalar subquery's whole execution included: it runs
            # inside plan_physical)
            with TR.span("plan", scope=scope, phase="rewrite",
                         timer=M.query_registry().create(
                             M.PLAN_TIME, M.ESSENTIAL)) as plan_span:
                physical = self.plan_physical(plan)
                plan_span.attrs["cacheHit"] = getattr(
                    self._tls, "plan_cache_hit", None)
                sig = getattr(self._tls, "plan_signature", None)
                if quar_thr > 0 and sig is not None \
                        and LC.is_quarantined(sig):
                    # poison-query quarantine: fail fast BEFORE
                    # touching the device — the signature already
                    # wedged the runtime quar_thr consecutive times
                    raise LC.TpuQueryQuarantined(
                        sig, LC.quarantined_failures(sig))
                # THIS thread's rewrite report: a concurrent query on
                # the same session may overwrite last_rewrite_report
                # before the profile/event-log writes below run
                report = getattr(self._tls, "rewrite_report",
                                 self.last_rewrite_report)
                # serving tenancy (docs/serving.md): stamp every
                # registry of THIS execution's plan with the session
                # tenant so store registrations from any pool thread
                # bill the right ledger — and with the query's record,
                # so every span and timer from any pool thread says
                # whose it is (docs/observability.md)
                from spark_rapids_tpu import memory as _mem
                _mem.stamp_plan_tenant(physical, self.tenant)
                TR.stamp_plan(physical, scope)
                # serve-tier caching (docs/caching.md): fingerprint
                # every file-scan input BEFORE execution reads it — a
                # file mutated mid-query then mismatches at lookup
                # time instead of going stale. Captured on this thread
                # for the server's result-cache population and the
                # join build-reuse hooks; skipped (and cleared) when
                # neither cache is on.
                from spark_rapids_tpu.conf import (RESULT_CACHE_ENABLED,
                                                   SUBPLAN_CACHE_ENABLED)
                from spark_rapids_tpu.serve import result_cache as _RC
                if (bool(self.conf_obj.get(RESULT_CACHE_ENABLED))
                        or bool(self.conf_obj.get(
                            SUBPLAN_CACHE_ENABLED))):
                    _RC.set_execution_fingerprints(
                        _RC.capture_fingerprints(physical))
                else:
                    _RC.set_execution_fingerprints(None)
            t0 = _time.perf_counter()
            with _mem.tenant_scope(self.tenant):
                result = physical.execute_collect(
                    int(self.conf_obj.get(TASK_PARALLELISM)))
            wall_s = _time.perf_counter() - t0
        except LC.TpuQueryCancelled as e:
            end_trace(error=True)
            # a cancelled/timed-out query's HBM frees NOW: close the
            # dead plan's spillable handles deterministically instead
            # of waiting for plan GC (cancellation never counts toward
            # quarantine — it is not a runtime-fatal failure)
            from spark_rapids_tpu import memory as _mem
            _mem.release_plan_handles(physical)
            self._record_terminal(
                ("timed-out" if e.reason == LC.REASON_DEADLINE
                 else "cancelled"), e.reason, physical, sig,
                _time.perf_counter() - t_begin)
            raise
        except LC.TpuQueryQuarantined:
            end_trace(error=True)
            self._record_terminal(
                "quarantined", None, physical, sig,
                _time.perf_counter() - t_begin)
            raise  # never ran: neither a failure nor a success
        except BaseException:
            end_trace(error=True)
            if quar_thr > 0 and sig is not None:
                LC.record_runtime_failure(sig, quar_thr)
            self._record_terminal(
                "failed", None, physical, sig,
                _time.perf_counter() - t_begin)
            raise
        trace_path = end_trace(wall_s=wall_s, rows=result.num_rows)
        if sig is not None:
            # the watchdog's per-signature p99 history; one success
            # also clears the signature's quarantine streak
            LC.record_wall(sig, wall_s)
            if quar_thr > 0:
                LC.record_success(sig)
        # profile artifact (docs/observability.md "Reading a query
        # profile"): the executed plan's registries + the store's
        # owner-attributed HBM ledger + the rewrite explain, one JSON
        # per query; the path is kept for tests/tools. ONE query id is
        # allocated for both sinks so the artifact and the event-log
        # line for this query correlate by queryId
        from spark_rapids_tpu import event_log
        from spark_rapids_tpu.conf import TELEMETRY_HISTORY_DIR
        log_dir = str(self.conf_obj.get(EVENT_LOG_DIR))
        profiling = bool(self.conf_obj.get(PROF.PROFILE_ENABLED))
        history_on = bool(str(
            self.conf_obj.get(TELEMETRY_HISTORY_DIR) or ""))
        qid = event_log.next_query_id() \
            if (log_dir or profiling or history_on) else None
        self.last_profile_path = PROF.write_profile(
            self.conf_obj, physical, report,
            wall_s, result.num_rows, query_id=qid)
        self._tls.profile_path = self.last_profile_path
        if log_dir:
            from spark_rapids_tpu import memory
            store = memory._STORE
            event_log.write_event(
                log_dir, id(self) & 0xFFFF, physical, report,
                wall_s, result.num_rows,
                store.stats() if store is not None else None,
                conf=self.conf_obj,
                memory_by_op=(store.owner_stats()
                              if store is not None else None),
                query_id=qid, tenant=self.tenant)
        # telemetry query-close triggers (slow query, per-query retry /
        # kernel-fallback deltas): evaluated AFTER the profile write so
        # a fired bundle can reference this query's artifact
        from spark_rapids_tpu.telemetry import triggers as _telemetry
        _telemetry.on_query_end(
            self.conf_obj, wall_s, plan=physical, tenant=self.tenant,
            query_id=qid,
            # THIS thread's artifact: a concurrent query on the shared
            # tenant session may overwrite last_profile_path before
            # the hook runs — the bundle must reference its own query
            profile_path=self.thread_profile_path())
        # persistent query history (docs/observability.md "Query
        # history"): one compact record per finished query, the
        # cross-run memory behind warm-start / SLO burn / tools
        # history / tools doctor. Appended AFTER the profile/trace
        # writes so the record can reference both artifacts.
        from spark_rapids_tpu.telemetry import history as _history
        # the WIRE queryId wins when the server supplied one (same
        # rule as the cancelled/failed paths): the id the client saw
        # in its response must resolve in `tools doctor`
        wire_qid = self._wire_query_id()
        _history.record_query_close(
            self.conf_obj, status=_history.STATUS_FINISHED,
            signature=sig, tenant=self.tenant,
            query_id=(wire_qid if wire_qid is not None else qid),
            wall_s=wall_s, queue_wait_s=self._queue_wait(),
            rows=result.num_rows, physical=physical, report=report,
            profile_path=self.thread_profile_path(),
            trace_path=trace_path)
        return result

    @staticmethod
    def _queue_wait() -> float:
        """The calling thread's admission-queue wait (0 outside a
        served query) — the lifecycle token records admission time."""
        from spark_rapids_tpu import lifecycle as LC
        tok = LC.current_token()
        if tok is None or tok.admitted is None:
            return 0.0
        return max(0.0, tok.admitted - tok.started)

    @staticmethod
    def _wire_query_id():
        from spark_rapids_tpu import lifecycle as LC
        tok = LC.current_token()
        return tok.query_id if tok is not None else None

    def _record_terminal(self, status: str, reason, physical, sig,
                         wall_s: float) -> None:
        """Event-log + history sinks for a NON-finished terminal
        outcome (cancelled / timed-out / quarantined / failed), so the
        two surfaces agree on query outcomes. Never raises — the
        original exception is already propagating."""
        try:
            from spark_rapids_tpu import event_log
            from spark_rapids_tpu import memory
            from spark_rapids_tpu.conf import (EVENT_LOG_DIR,
                                               TELEMETRY_HISTORY_DIR)
            from spark_rapids_tpu.telemetry import history as _history
            log_dir = str(self.conf_obj.get(EVENT_LOG_DIR))
            history_on = bool(str(
                self.conf_obj.get(TELEMETRY_HISTORY_DIR) or ""))
            # ONE id for both sinks, so the failure's event line and
            # history record correlate (same contract as success);
            # the wire queryId wins when the server supplied one
            qid = self._wire_query_id()
            if qid is None and (log_dir or history_on):
                qid = event_log.next_query_id()
            if log_dir:
                store = memory._STORE
                event_log.write_event(
                    log_dir, id(self) & 0xFFFF, physical, None,
                    wall_s, 0,
                    store.stats() if store is not None else None,
                    conf=self.conf_obj, tenant=self.tenant,
                    query_id=qid, status=status, reason=reason)
            _history.record_query_close(
                self.conf_obj, status=status, reason=reason,
                signature=sig, tenant=self.tenant,
                query_id=qid, wall_s=wall_s,
                queue_wait_s=self._queue_wait(), rows=0,
                physical=physical)
        except Exception:
            pass  # observability must not mask the real failure

    def explain_string(self, plan: L.LogicalPlan, physical=None) -> str:
        if physical is None:
            physical = self.plan_physical(plan, execute_subqueries=False)
        return f"== Logical ==\n{plan!r}\n== Physical ==\n{physical!r}"

    def thread_profile_path(self) -> Optional[str]:
        """The profile artifact written by the CALLING thread's last
        query on this session (None when none) — race-free under the
        server's shared-session-per-tenant concurrency."""
        return getattr(self._tls, "profile_path", None)

    def thread_plan_signature(self) -> Optional[str]:
        """The plan-signature digest of the CALLING thread's last
        planned query on this session (None when planning ran without
        the plan cache) — the server's result-cache population reads
        this after _execute() on the same thread (docs/caching.md)."""
        return getattr(self._tls, "plan_signature", None)

    # -- plan capture (ExecutionPlanCaptureCallback, Plugin.scala:268-390)
    def start_capture(self) -> None:
        self._plan_capture.clear()
        self._capture_enabled = True

    def get_captured_plans(self) -> List:
        self._capture_enabled = False
        return list(self._plan_capture)

    def stop(self) -> None:
        if self._owns_mesh:
            from spark_rapids_tpu.parallel import mesh as PM
            PM.set_active_mesh(None)
            self._owns_mesh = False
        with TpuSparkSession._lock:
            if TpuSparkSession._active is self:
                # restore the session that was active before this one
                # (global-singleton satellite: concurrent server
                # sessions must not clobber each other's active slot) —
                # skipping any already-stopped ancestor in the chain
                prev = self._prev_active
                while prev is not None and getattr(prev, "_stopped",
                                                   False):
                    prev = prev._prev_active
                TpuSparkSession._active = prev
            self._stopped = True


class _BuilderFactory:
    def __get__(self, obj, objtype=None):
        return TpuSparkSession.Builder()


TpuSparkSession.builder = _BuilderFactory()


def _infer_batch(data, schema) -> HostBatch:
    if isinstance(data, HostBatch):
        return data
    if isinstance(schema, str):
        schema = _parse_ddl_schema(schema)
    if isinstance(data, dict):
        if schema is None:
            schema = T.StructType([
                T.StructField(k, _infer_type_from_values(v))
                for k, v in data.items()])
        return HostBatch.from_pydict(data, schema)
    rows = list(data)
    if schema is None:
        if not rows:
            raise ValueError("cannot infer schema from empty data")
        first = rows[0]
        if isinstance(first, dict):
            names = list(first.keys())
            cols = {n: [r.get(n) for r in rows] for n in names}
            schema = T.StructType([
                T.StructField(n, _infer_type_from_values(cols[n]))
                for n in names])
            return HostBatch.from_pydict(cols, schema)
        names = [f"_{i + 1}" for i in range(len(first))]
        cols = {n: [r[i] for r in rows] for i, n in enumerate(names)}
        schema = T.StructType([
            T.StructField(n, _infer_type_from_values(cols[n]))
            for n in names])
        return HostBatch.from_pydict(cols, schema)
    if isinstance(schema, (list, tuple)):
        names = list(schema)
        if not rows:
            raise ValueError("cannot infer schema from empty data")
        cols = {n: [r[i] for r in rows] for i, n in enumerate(names)}
        schema = T.StructType([
            T.StructField(n, _infer_type_from_values(cols[n]))
            for n in names])
        return HostBatch.from_pydict(cols, schema)
    cols = {f.name: [r[i] for r in rows]
            for i, f in enumerate(schema.fields)}
    return HostBatch.from_pydict(cols, schema)


def _infer_type_from_values(values: Iterable[Any]) -> T.DataType:
    import datetime
    for v in values:
        if v is None:
            continue
        if isinstance(v, bool):
            return T.BooleanT
        if isinstance(v, int):
            return T.LongT
        if isinstance(v, float):
            return T.DoubleT
        if isinstance(v, str):
            return T.StringT
        if isinstance(v, datetime.datetime):
            return T.TimestampT
        if isinstance(v, datetime.date):
            return T.DateT
        if isinstance(v, bytes):
            return T.BinaryT
    return T.StringT


def _parse_ddl_schema(ddl: str) -> T.StructType:
    from spark_rapids_tpu.sql.functions import _parse_type, split_top_level
    # split on commas not inside parens (decimal(10,2) etc.)
    fields = []
    for part in split_top_level(ddl):
        name, _, tp = part.strip().partition(" ")
        fields.append(T.StructField(name.strip(), _parse_type(tp.strip())))
    return T.StructType(fields)


def _reuse_broadcast_exchanges(plan):
    """ReuseExchange (GpuBroadcastExchangeExec.scala:280 reuse
    semantics): structurally equal broadcast subtrees in one query plan
    collapse to ONE shared node instance, so the build side
    materializes once no matter how many joins consume it."""
    from spark_rapids_tpu.exec.exchange import TpuBroadcastExchangeExec
    from spark_rapids_tpu.sql import physical as P

    seen = {}

    def params(p):
        # node parameters beyond simple_string: limits, ranges, expr
        # lists (exprs repr with their ids). Unknown object-valued
        # attrs key by IDENTITY — conservative: equal-content-but-
        # distinct objects just skip reuse, never alias wrongly.
        out = []
        for k in sorted(vars(p)):
            if k in ("children", "conf", "metrics") or k.startswith("_"):
                continue
            v = vars(p)[k]
            if isinstance(v, (int, str, bool, float, type(None))):
                out.append((k, v))
            elif isinstance(v, (list, tuple)) and all(
                    isinstance(x, (int, str, bool, float)) for x in v):
                out.append((k, tuple(v)))
            elif isinstance(v, E.Expression) or (
                    isinstance(v, (list, tuple)) and v and all(
                        isinstance(x, E.Expression) for x in v)):
                out.append((k, repr(v)))
            else:
                out.append((k, id(v)))
        return tuple(out)

    def sig(p):
        # simple_string alone is NOT identity (two equal-shaped
        # LocalScans or Limits print identically); output attr EXPR IDS
        # plus the node's own parameters are
        return (type(p).__name__, p.simple_string(), params(p),
                tuple((a.name, a.expr_id, repr(a.data_type))
                      for a in p.output),
                tuple(sig(c) for c in p.children))

    def walk(p):
        p.children = [walk(c) for c in p.children]
        if isinstance(p, (P.CpuBroadcastExchangeExec,
                          TpuBroadcastExchangeExec)):
            key = (type(p).__name__, sig(p.child))
            hit = seen.get(key)
            if hit is not None:
                return hit
            seen[key] = p
        return p

    return walk(plan)

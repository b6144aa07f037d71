"""Span tracing corpus (docs/observability.md): Chrome-trace schema
well-formedness (matched B/E pairs, monotone per-tid timestamps),
bit-identical results with tracing on vs off (including under injected
OOM so retry markers appear), deterministic sampling at a fixed seed,
the tracing-overhead bound, the `tools trace` CLI, and the
metric-name-in-docs drift guard plus the event-log v2 /
registry_snapshot satellites."""

from __future__ import annotations

import glob
import json
import os

import pytest

from spark_rapids_tpu import metrics as M
from spark_rapids_tpu import retry as R
from spark_rapids_tpu import trace as TR
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.sql.session import TpuSparkSession

from tests.datagen import (IntegerGen, KeyStringGen, LongGen, SmallIntGen,
                           gen_batch)

# "C" = counter samples (device/host pool occupancy, PR 6 profile work)
VALID_PH = {"M", "B", "E", "i", "I", "X", "C"}


@pytest.fixture(autouse=True)
def _fresh_tracing():
    """Deterministic sampling streams + no cross-test trace bleed."""
    TR.reset_tracing()
    R.reset_fault_injection()
    yield
    TR.reset_tracing()
    R.reset_fault_injection()


def _conf(trace_dir=None, **extra):
    conf = {"spark.rapids.sql.enabled": "true",
            "spark.rapids.sql.batchSizeRows": "512"}
    if trace_dir is not None:
        conf["spark.rapids.sql.trace.enabled"] = "true"
        conf["spark.rapids.sql.trace.dir"] = str(trace_dir)
    conf.update(extra)
    return conf


def _q1_silhouette(s):
    """scan-shaped filter -> 2-key groupBy -> orderBy (q1 at test
    scale)."""
    df = s.createDataFrame(
        gen_batch([("flag", KeyStringGen(cardinality=3)),
                   ("status", SmallIntGen()),
                   ("qty", LongGen()), ("price", IntegerGen())],
                  3000, 21),
        num_partitions=4)
    return (df.filter(F.col("qty") % 5 != 0)
            .groupBy("flag", "status")
            .agg(F.sum("qty").alias("sq"), F.min("price").alias("mn"),
                 F.max("price").alias("mx"), F.count("*").alias("c"))
            .orderBy("flag", "status"))


def _q3_silhouette(s):
    fact = s.createDataFrame(
        gen_batch([("k", SmallIntGen()), ("item", IntegerGen()),
                   ("amt", LongGen())], 2500, 22),
        num_partitions=3)
    dim = s.createDataFrame(
        gen_batch([("item2", IntegerGen()),
                   ("brand", KeyStringGen(cardinality=5))], 400, 23),
        num_partitions=2)
    return (fact.join(dim, fact["item"] == dim["item2"], "inner")
            .groupBy("brand").agg(F.sum("amt").alias("sa"),
                                  F.count("*").alias("c"))
            .orderBy("brand").limit(50))


def _run(df_fn, conf):
    spark = TpuSparkSession(conf)
    try:
        return df_fn(spark)._execute().to_pydict()
    finally:
        spark.stop()


def _trace_files(trace_dir) -> list:
    return sorted(glob.glob(os.path.join(str(trace_dir),
                                         "trace-*.json")))


def _write_parquet(tmp_path):
    gen = TpuSparkSession({"spark.rapids.sql.enabled": "false"})
    try:
        path = str(tmp_path / "t")
        gen.createDataFrame(
            gen_batch([("k", SmallIntGen()), ("v", LongGen())], 1500, 24),
            num_partitions=3).write.mode("overwrite").parquet(path)
        return path
    finally:
        gen.stop()


# ---------------------------------------------------------------------------
# Schema well-formedness
# ---------------------------------------------------------------------------

def _check_wellformed(doc) -> set:
    """Valid Chrome trace: known phases, monotone per-tid timestamps,
    matched B/E pairs (names agree, stacks empty at EOF). Returns the
    set of span names."""
    events = doc["traceEvents"]
    assert isinstance(events, list) and events
    stacks, last_ts, names = {}, {}, set()
    for ev in events:
        assert ev.get("ph") in VALID_PH, ev
        if ev["ph"] == "M":
            continue
        tid = ev["tid"]
        ts = float(ev["ts"])
        assert ts >= last_ts.get(tid, -1e18) - 1e-6, (
            f"non-monotone ts on tid {tid}: {ts} after {last_ts[tid]}")
        last_ts[tid] = ts
        if ev["ph"] == "B":
            stacks.setdefault(tid, []).append(ev)
            names.add(ev["name"])
        elif ev["ph"] == "E":
            st = stacks.get(tid)
            assert st, f"E without B on tid {tid}: {ev}"
            b = st.pop()
            assert b["name"] == ev["name"], (b, ev)
    leftover = {t: st for t, st in stacks.items() if st}
    assert not leftover, f"unmatched B events: {leftover}"
    return names


def test_trace_file_wellformed_with_expected_kinds(tmp_path):
    data = _write_parquet(tmp_path)
    tdir = tmp_path / "traces"
    spark = TpuSparkSession(_conf(tdir))
    try:
        df = (spark.read.parquet(data).filter(F.col("v") % 3 != 0)
              .groupBy("k").agg(F.sum("v").alias("sv"),
                                F.count("*").alias("c"))
              .orderBy("k"))
        df._execute()
    finally:
        spark.stop()
    files = _trace_files(tdir)
    assert len(files) == 1, files
    with open(files[0]) as f:
        doc = json.load(f)
    names = _check_wellformed(doc)
    meta = doc["otherData"]
    assert meta["queryId"] == 1 and meta["outputRows"] > 0
    # every stage of a batch's life is represented: reader decode plan
    # (device decode is the default scan path), producer-thread
    # prefetch, host pack, upload-ahead + decode-program completion
    # (chip-attributed), device dispatch, exchange, JIT compile,
    # semaphore wait
    for expected in ("FileScan.deviceDecodeTime",
                     "scanPrefetch",
                     "uploadAhead",
                     "TpuRowToColumnarExec.packBatchTime",
                     "TpuRowToColumnarExec.copyToDeviceTime",
                     "TpuHashAggregateExec.dispatch",
                     "exchangeMaterialize",
                     "compile",
                     "semaphoreWait"):
        assert expected in names, (expected, sorted(names))
    # the loader round-trips the same stream
    tr = TR.load_trace(files[0])
    assert len(tr["spans"]) == meta["spanCount"]


def test_scan_pipeline_trace_and_critical_path(tmp_path):
    """The ISSUE 9 acceptance probe at test scale: a traced parquet
    aggregation's Chrome stream stays well-formed with the pipeline
    spans present, and the critical path contains no host
    FileScan.decodeTime (the scan is off the critical path — decode
    rides the device program / prefetch threads)."""
    from spark_rapids_tpu.tools import analyze_trace
    data = _write_parquet(tmp_path)
    tdir = tmp_path / "traces"
    spark = TpuSparkSession(_conf(tdir))
    try:
        df = (spark.read.parquet(data).filter(F.col("v") % 3 != 0)
              .groupBy("k").agg(F.sum("v").alias("sv"))
              .orderBy("k"))
        df._execute()
    finally:
        spark.stop()
    files = _trace_files(tdir)
    assert files
    with open(files[-1]) as f:
        _check_wellformed(json.load(f))
    analysis = analyze_trace(files[-1])
    cp = analysis.get("criticalPath_s", {})
    assert cp, analysis
    assert "FileScan.decodeTime" not in cp, cp


# ---------------------------------------------------------------------------
# Bit-identical results, tracing on vs off (incl. under injected OOM)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("df_fn", [_q1_silhouette, _q3_silhouette],
                         ids=["q1", "q3"])
def test_traced_results_bit_identical(df_fn, tmp_path):
    clean = _run(df_fn, _conf())
    traced = _run(df_fn, _conf(tmp_path / "tr"))
    assert traced == clean
    assert _trace_files(tmp_path / "tr")


@pytest.mark.fault
def test_traced_results_bit_identical_under_injected_oom(tmp_path):
    clean = _run(_q1_silhouette, _conf())
    R.reset_fault_injection()
    tdir = tmp_path / "tr"
    traced = _run(_q1_silhouette, _conf(
        tdir,
        **{"spark.rapids.sql.test.injectOOM": "3",
           "spark.rapids.sql.retry.backoffMs": "1",
           "spark.rapids.sql.retry.maxBackoffMs": "4"}))
    assert traced == clean
    tr = TR.load_trace(_trace_files(tdir)[-1])
    marks = {i["name"] for i in tr["instants"]}
    assert "retryOOM" in marks, marks
    # the recovery block is a nested span (the exclusive-time fix)
    assert any(s["name"] == "retryBlock" for s in tr["spans"])


# ---------------------------------------------------------------------------
# Sampling determinism
# ---------------------------------------------------------------------------

def _run_sampled_queries(trace_dir, n=8):
    TR.reset_tracing()
    spark = TpuSparkSession(_conf(
        trace_dir,
        **{"spark.rapids.sql.trace.sampleRate": "0.5",
           "spark.rapids.sql.trace.sampleSeed": "7"}))
    try:
        for _ in range(n):
            spark.range(0, 64).selectExpr("id + 1 as x")._execute()
    finally:
        spark.stop()
    return [os.path.basename(f) for f in _trace_files(trace_dir)]


def test_sampling_deterministic_at_fixed_seed(tmp_path):
    first = _run_sampled_queries(tmp_path / "a")
    second = _run_sampled_queries(tmp_path / "b")
    assert first == second
    assert 0 < len(first) < 8  # the rate actually samples


# ---------------------------------------------------------------------------
# Overhead bound (acceptance: traced q1 wall <= 1.15x untraced)
# ---------------------------------------------------------------------------

def test_tracing_overhead_bound(tmp_path):
    import time

    def wall(df):
        t0 = time.perf_counter()
        df._execute()
        return time.perf_counter() - t0

    # INTERLEAVED best-of-5: measuring all untraced walls then all
    # traced walls lets a load shift between the phases (GC, another
    # suite's leftovers) masquerade as tracing overhead on these
    # millisecond-scale smoke walls; alternating exposes both modes to
    # the same machine state
    off = TpuSparkSession(_conf())
    on = TpuSparkSession(_conf(tmp_path / "tr"))
    try:
        q_off, q_on = _q1_silhouette(off), _q1_silhouette(on)
        q_off._execute()  # compile warm-up (caches are process-wide)
        q_on._execute()
        offs, ons = [], []
        for _ in range(5):
            offs.append(wall(q_off))
            ons.append(wall(q_on))
        t_off, t_on = min(offs), min(ons)
    finally:
        on.stop()
        off.stop()
    # 1.15x per the acceptance bound, plus a tiny absolute allowance so
    # millisecond-scale smoke walls don't flake on scheduler noise
    assert t_on <= t_off * 1.15 + 0.05, (t_on, t_off)


# ---------------------------------------------------------------------------
# tools: trace CLI + analyzer + docs drift guard
# ---------------------------------------------------------------------------

def test_tools_trace_cli_smoke(tmp_path, capsys):
    from spark_rapids_tpu.tools import _main, analyze_trace
    tdir = tmp_path / "tr"
    _run(_q1_silhouette, _conf(tdir))
    path = _trace_files(tdir)[0]
    assert _main(["trace", path]) == 0
    out = capsys.readouterr().out
    assert "critical path" in out
    assert "per-chip enqueue occupancy" in out
    assert "exclusive self-time" in out
    # directory mode reports every trace in it
    assert _main(["trace", str(tdir)]) == 0
    # machine-readable form (bench detail.trace)
    a = analyze_trace(path)
    assert a["spanCount"] > 0
    assert a["criticalPath_s"]
    assert abs(sum(a["criticalPath_s"].values())
               + a["criticalPathIdle_s"] - a["criticalPathSpan_s"]) \
        <= 0.01 * max(1.0, a["criticalPathSpan_s"])


# The metric-constant-in-generated-docs drift guard that lived here is
# now STATIC: tpu-lint's `metric-key` rule checks every metrics.py
# constant against METRIC_DESCRIPTIONS and `docs-drift` diffs
# docs/observability.md against the generator (tests/test_lint.py runs
# both over the real package every tier-1).


# ---------------------------------------------------------------------------
# Satellites: registry_snapshot, event-log v2, semaphore-wait coverage
# ---------------------------------------------------------------------------

def test_registry_snapshot_merges_plan_registries():
    spark = TpuSparkSession(_conf())
    try:
        spark.start_capture()
        _q1_silhouette(spark)._execute()
        plans = spark.get_captured_plans()
    finally:
        spark.stop()
    snap = M.registry_snapshot(plans)
    assert snap["metrics"].get(M.NUM_OUTPUT_ROWS, 0) > 0
    assert snap["metrics"].get(M.DISPATCH_COUNT, 0) > 0
    assert "jitCaches" in snap and snap["jitCaches"]
    # process-wide form includes at least the same names
    whole = M.registry_snapshot()
    assert whole["metrics"].get(M.NUM_OUTPUT_ROWS, 0) \
        >= snap["metrics"][M.NUM_OUTPUT_ROWS]


def test_event_log_v2_zero_metrics_conf_and_injector(tmp_path):
    from spark_rapids_tpu.event_log import read_events
    log_dir = str(tmp_path / "events")
    conf = _conf(**{"spark.rapids.sql.eventLog.dir": log_dir,
                    "spark.rapids.sql.test.injectOOM": "4",
                    "spark.rapids.sql.retry.backoffMs": "1",
                    "spark.rapids.sql.retry.maxBackoffMs": "4"})
    _run(_q1_silhouette, conf)
    events = list(read_events(log_dir))
    assert len(events) == 1
    ev = events[0]
    assert ev["version"] == 2
    # conf snapshot: the session's explicit settings ride along
    assert ev["conf"]["spark.rapids.sql.enabled"] == "true"
    assert ev["conf"]["spark.rapids.sql.test.injectOOM"] == "4"
    # fault-injector summary
    assert ev["faultInjector"]["oomInjected"] > 0
    # zero-valued metrics are now present (distinguishable from absent)
    all_metrics = [m for o in ev["ops"]
                   for m in o.get("metrics", {}).items()]
    assert any(v == 0 for _k, v in all_metrics), (
        "expected at least one zero-valued metric in the v2 event")
    # old lines (no version field) normalize to 1
    legacy = tmp_path / "events" / "events-0-legacy.jsonl"
    with open(legacy, "w") as f:
        f.write(json.dumps({"event": "queryCompleted", "ts": 0.0,
                            "queryId": 99, "wallSeconds": 0.1,
                            "outputRows": 1, "plan": "", "ops": []})
                + "\n")
    versions = {e["queryId"]: e["version"] for e in read_events(log_dir)}
    assert versions[99] == 1


def test_semaphore_wait_timed_on_exchange_and_broadcast_paths():
    """Satellite: semaphoreWaitTime must be recorded on the exchange
    drain and the broadcast build too, not only the per-task collect
    path."""
    from spark_rapids_tpu.exec.exchange import (TpuBroadcastExchangeExec,
                                                TpuShuffleExchangeExec)
    conf = _conf(**{"spark.rapids.sql.taskParallelism": "2",
                    "spark.rapids.sql.autoBroadcastJoinThreshold":
                        str(10 << 20)})
    spark = TpuSparkSession(conf)
    try:
        spark.start_capture()
        _q3_silhouette(spark)._execute()
        plans = spark.get_captured_plans()
    finally:
        spark.stop()
    found = {"exchange": False, "broadcast": False}

    def walk(p):
        if isinstance(p, TpuShuffleExchangeExec):
            if M.SEMAPHORE_WAIT_TIME in p.metrics.metrics:
                found["exchange"] = True
        if isinstance(p, TpuBroadcastExchangeExec):
            if M.SEMAPHORE_WAIT_TIME in p.metrics.metrics:
                found["broadcast"] = True
        for c in p.children:
            walk(c)

    for p in plans:
        walk(p)
    assert found["exchange"] or found["broadcast"], (
        "semaphoreWaitTime recorded on neither the exchange drain nor "
        "the broadcast build")


# ---------------------------------------------------------------------------
# One id per query on every record; the same spans on the profiler's clock;
# the three per-query timers (docs/observability.md)
# ---------------------------------------------------------------------------

def _all_records(doc) -> list:
    return [(s["name"], s["args"]) for s in doc["spans"]] + \
        [(i["name"], i["args"]) for i in doc["instants"]]


def test_every_record_carries_its_query_id(tmp_path):
    """One traced query: every span and instant says whose it is, and
    the file holds its root, its plan and its first dispatch."""
    _run(_q1_silhouette, _conf(tmp_path / "tr", **{
        "spark.rapids.sql.taskParallelism": "2"}))
    doc = TR.load_trace(_trace_files(tmp_path / "tr")[0])
    recs = _all_records(doc)
    assert recs and all("q" in a for _n, a in recs), [
        n for n, a in recs if "q" not in a]
    assert len({a["q"] for _n, a in recs}) == 1
    names = {n for n, _a in recs}
    assert {"srt.query", "plan", "firstDispatch", "deviceSync"} <= names
    first = [a for n, a in recs if n == "firstDispatch"]
    assert len(first) == 1 and first[0]["program"].startswith("srt_")
    dispatch = [a for n, a in recs if n.endswith(".dispatch")]
    assert dispatch and all(a["program"].startswith("srt_")
                            for a in dispatch)


def test_concurrent_queries_record_disjoint_query_ids(tmp_path):
    """Two queries at once fold into ONE open file trace (the sinks are
    process-wide) — but no record is without a `q`, the two ids are
    distinct, and each query's spans stay its own: `--query` filters
    one out."""
    import threading
    from spark_rapids_tpu.tools import analyze_trace
    s1 = TpuSparkSession(_conf(tmp_path / "tr", **{
        "spark.rapids.sql.taskParallelism": "2"}))
    s2 = TpuSparkSession(_conf(tmp_path / "tr", **{
        "spark.rapids.sql.taskParallelism": "2"}))
    gate = threading.Barrier(2, timeout=60)
    scopes, errors = {}, []

    def run(name, spark, df_fn):
        try:
            df = df_fn(spark)
            gate.wait()
            for _ in range(3):
                df._execute()
            scopes[name] = True
        except BaseException as e:  # surfaced below
            errors.append(e)

    try:
        ts = [threading.Thread(target=run, args=("a", s1, _q1_silhouette)),
              threading.Thread(target=run, args=("b", s2, _q3_silhouette))]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in ts) and not errors, errors
    finally:
        s1.stop()
        s2.stop()
    files = _trace_files(tmp_path / "tr")
    assert files
    seen = set()
    for f in files:
        doc = TR.load_trace(f)
        recs = _all_records(doc)
        assert all("q" in a for _n, a in recs), [
            (n, a) for n, a in recs if "q" not in a][:5]
        qs = {a["q"] for _n, a in recs}
        seen |= qs
        # every query id owns exactly one root, and its records never
        # claim another's
        roots = [a["q"] for n, a in recs if n == "srt.query"]
        assert len(roots) == len(set(roots))
        for q in qs:
            only = analyze_trace(f, query=q)
            assert only["queries"] == [q]
            assert 0 < only["spanCount"] <= len(doc["spans"])
    # a query that begins while another's file is open folds into that
    # file and is cut off when it closes, so not all six executions
    # (2 threads x 3) leave records — but the two that overlap do
    assert 2 <= len(seen) <= 6, seen


def test_scalar_subquery_spans_carry_their_parents_id(tmp_path):
    spark = TpuSparkSession(_conf(tmp_path / "tr"))
    try:
        spark.createDataFrame(
            {"a": list(range(100))}, "a int").createOrReplaceTempView("t")
        rows = spark.sql("SELECT count(*) c FROM t WHERE a > "
                         "(SELECT avg(a) FROM t)").collect()
        assert rows[0][0] == 50
    finally:
        spark.stop()
    doc = TR.load_trace(_trace_files(tmp_path / "tr")[0])
    roots = {a["q"]: a.get("parent") for n, a in _all_records(doc)
             if n == "srt.query"}
    assert len(roots) == 2
    (outer,) = [q for q, p in roots.items() if p is None]
    (inner,) = [q for q, p in roots.items() if p is not None]
    assert roots[inner] == outer
    assert all(a.get("parent") == outer for _n, a in _all_records(doc)
               if a.get("q") == inner)


def test_engine_spans_reach_the_profilers_trace(tmp_path):
    """With NO trace conf set, any jax.profiler session gets the
    engine's spans as TraceAnnotations on the host planes, `q` set:
    the root, the plan, a dispatch and a device sync."""
    import jax
    from spark_rapids_tpu.tools import load_profile, profile_files
    spark = TpuSparkSession(_conf())       # tracing conf OFF
    try:
        df = _q1_silhouette(spark)
        df._execute()                      # compile outside the trace
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path / "prof"),
                                 profiler_options=options)
        try:
            df._execute()
        finally:
            jax.profiler.stop_trace()
    finally:
        spark.stop()
    (xplane,) = profile_files(str(tmp_path / "prof"))
    spans = load_profile(xplane)["spans"]
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    assert {"srt.query", "plan", "deviceSync"} <= set(by_name), \
        sorted(by_name)
    dispatch = [s for n, ss in by_name.items() if n.endswith(".dispatch")
                for s in ss]
    assert dispatch
    (root,) = by_name["srt.query"]
    q = root["args"]["q"]
    for s in by_name["plan"] + by_name["deviceSync"] + dispatch:
        assert s["args"]["q"] == q
        assert root["t0"] <= s["t0"] and s["t1"] <= root["t1"] + 1.0
    assert all(str(s["args"]["program"]).startswith("srt_")
               for s in dispatch)
    assert {s["args"]["site"] for s in by_name["deviceSync"]}
    # the CLI reads the directory (no device plane on the CPU)
    from spark_rapids_tpu.tools import _main
    assert _main(["trace", str(tmp_path / "prof")]) == 0


def test_query_timers_in_the_process_totals(tmp_path):
    """planTime, firstDispatchTime and deviceSyncTime are always-on
    registry timers: they show in the process totals the benchmark
    reads (no trace conf needed) and grow with every query;
    firstDispatchTime is booked once per query."""
    from spark_rapids_tpu.telemetry.prometheus import aggregator
    timers = (M.PLAN_TIME, M.FIRST_DISPATCH_TIME, M.DEVICE_SYNC_TIME)

    def totals():
        return dict(aggregator().scrape()[0])

    spark = TpuSparkSession(_conf(**{
        "spark.rapids.sql.taskParallelism": "4"}))   # tracing conf OFF
    try:
        df = _q1_silhouette(spark)
        df._execute()
        seen = [totals()]
        for _ in range(3):
            df._execute()
            seen.append(totals())
    finally:
        spark.stop()
    for k in timers:
        assert M.describe_metric(k)
        vals = [t.get(k, 0) for t in seen]
        assert vals[0] > 0 and vals == sorted(set(vals)), (k, vals)
    # once per query: each traced query's file holds ONE firstDispatch,
    # though four task threads race to the first enqueue
    traced = TpuSparkSession(_conf(tmp_path / "tr", **{
        "spark.rapids.sql.taskParallelism": "4"}))
    try:
        df = _q1_silhouette(traced)
        for _ in range(3):
            df._execute()
    finally:
        traced.stop()
    files = _trace_files(tmp_path / "tr")
    assert len(files) == 3
    for f in files:
        firsts = [i for i in TR.load_trace(f)["instants"]
                  if i["name"] == "firstDispatch"]
        assert len(firsts) == 1, firsts


def test_annotation_path_overhead_bound_with_no_profiler_on():
    """The annotation every span now enters costs, with no profiler
    session, on the order of a microsecond: 20k spans through
    MetricRegistry.timed (metric + annotation, no sink) stay under
    10 us each on average — at q1's few hundred spans a query that is
    far below 0.1% of a 21 s wall."""
    import time
    TR.reset_tracing()
    reg = M.MetricRegistry(owner="Overhead")
    n = 20000
    t0 = time.perf_counter()
    for i in range(n):
        with reg.timed(M.OP_TIME):
            pass
    per_span = (time.perf_counter() - t0) / n
    assert reg.value(M.OP_TIME) > 0
    assert per_span < 10e-6, per_span
    t0 = time.perf_counter()
    for i in range(n):
        with TR.device_sync("rowCount", reg):
            pass
    assert (time.perf_counter() - t0) / n < 15e-6


def test_every_span_kind_names_its_reader():
    """A kind nobody reads is deleted with its catalog line: the
    readers' table covers exactly the two catalogs, and the generated
    doc prints it."""
    kinds = set(TR.SPAN_CATALOG) | set(TR.INSTANT_CATALOG)
    assert set(TR.KIND_READERS) == kinds
    assert all(TR.KIND_READERS[k].strip() for k in kinds)
    from spark_rapids_tpu.tools import generate_observability_docs
    doc = generate_observability_docs()
    assert "| Span kind | Meaning | Read by |" in doc
    for k in ("plan", "deviceSync", "firstDispatch", "srt.query"):
        assert f"| `{k}` |" in doc

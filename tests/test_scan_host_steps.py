"""PR 38: the scan's host path, measured where the device's idle seconds
go. The three host steps of a unit's device-decode plan (``scanRead``,
``scanInflate``, ``scanParse``) inside ``FileScan.deviceDecodeTime``; the
consumer's wait on an empty prefetch ring (``scanWait``) and the query's
``scanStarvedTime``; the interpreter's collections (``gcPause``,
``gcPauseTime``, ``gcCollections.<gen>``); the readers of the five new
per-layer metrics; and the repair of ``tools trace``'s gap owner, under
which a wait span no longer takes a gap from the work it waits for."""

from __future__ import annotations

import gc
import glob
import os
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu import metrics as M
from spark_rapids_tpu import tools as TL
from spark_rapids_tpu import trace as TR
from spark_rapids_tpu.io import device_decode as DD
from spark_rapids_tpu.sql.session import TpuSparkSession
from spark_rapids_tpu.telemetry.prometheus import aggregator

STEP_TIMERS = (M.SCAN_READ_TIME, M.SCAN_INFLATE_TIME, M.SCAN_PARSE_TIME)


def _totals():
    return dict(aggregator().scrape()[0])


def _delta(before):
    after = _totals()
    return {k: v - before.get(k, 0) for k, v in after.items()}


def _write_table(tmp_path, compression, n=200_000, name="t.parquet"):
    """Four columns, one row group: dictionary-encoded ints and strings,
    a PLAIN int64 and a nullable int64 (levels on every page)."""
    rng = np.random.default_rng(7)
    big = rng.integers(0, 1 << 40, n)
    tbl = pa.table({
        "k": rng.integers(0, 500, n),
        "v": big,
        "s": pa.array(rng.choice(["AIR", "MAIL", "SHIP", "TRUCK"], n)),
        "w": pa.array(np.where(rng.random(n) < 0.1, None, big).tolist(),
                      pa.int64())})
    path = os.path.join(str(tmp_path), name)
    pq.write_table(tbl, path, compression=compression)
    return path


def _session(tmp_path, **extra):
    conf = {"spark.rapids.sql.enabled": "true",
            "spark.rapids.sql.taskParallelism": "1",
            "spark.rapids.sql.trace.enabled": "true",
            "spark.rapids.sql.trace.dir": os.path.join(str(tmp_path), "tr")}
    conf.update(extra)
    return TpuSparkSession(conf)


def _page_sizes(path):
    """(sum of the column chunks' total_compressed_size, sum of their
    pages' uncompressed_page_size), walking the pages' own headers."""
    md = pq.ParquetFile(path).metadata.row_group(0)
    compressed = usize = 0
    with open(path, "rb") as f:
        for ci in range(md.num_columns):
            col = md.column(ci)
            start = col.data_page_offset
            if col.dictionary_page_offset is not None:
                start = min(start, col.dictionary_page_offset)
            f.seek(start)
            raw = f.read(col.total_compressed_size)
            compressed += len(raw)
            pos = 0
            while pos < len(raw):
                hdr, off = DD.parse_page_header(raw, pos)
                usize += hdr.get(2, 0)
                pos = off + hdr.get(3, 0)
    return compressed, usize


SQL = "SELECT s, sum(k), max(v), count(w) FROM t GROUP BY s"


@pytest.mark.parametrize("compression", ["snappy", "none"])
def test_host_steps_cover_the_device_decode(tmp_path, compression):
    """One stream at taskParallelism 1: read + inflate + parse are
    90-100% of deviceDecodeTime (best of three warm runs: the first pays
    imports), and the byte counters are the row group's compressed size
    and its pages' uncompressed size (nothing to inflate uncompressed)."""
    path = _write_table(tmp_path, compression)
    spark = _session(tmp_path)
    try:
        spark.read.parquet(path).createOrReplaceTempView("t")
        spark.sql(SQL).collect()                      # warm-up
        shares = []
        for _ in range(3):
            before = _totals()
            spark.sql(SQL).collect()
            d = _delta(before)
            assert d["deviceDecodedBatches"] == 1, d
            shares.append(sum(d[k] for k in STEP_TIMERS)
                          / d["deviceDecodeTime"])
        compressed, usize = _page_sizes(path)
        assert d[M.SCAN_READ_BYTES] == compressed
        assert d[M.SCAN_INFLATED_BYTES] == (
            usize if compression == "snappy" else 0)
        assert d[M.SCAN_PAGES] > 4
        if compression == "none":
            assert d[M.SCAN_INFLATE_TIME] == 0
    finally:
        spark.stop()
    assert 0.9 <= max(shares) <= 1.0, shares


def test_step_spans_nest_in_the_decode_span_and_carry_the_query(tmp_path):
    """A chunk is one scanRead and one scanParse with its scanInflate
    inside; the footer a scanRead of its own; every span has q=. The
    MULTITHREADED reader's pool threads (two files in one partition)
    record them under the query too."""
    d = tmp_path / "two"
    d.mkdir()
    for i in range(2):
        _write_table(d, "snappy", n=20_000, name=f"p{i}.parquet")
    spark = _session(tmp_path, **{
        "spark.sql.files.maxPartitionBytes": str(1 << 30),
        "spark.rapids.sql.format.parquet.reader.type": "MULTITHREADED"})
    try:
        spark.read.parquet(str(d)).createOrReplaceTempView("t")
        spark.sql(SQL).collect()
    finally:
        spark.stop()
    f = sorted(glob.glob(os.path.join(str(tmp_path), "tr", "*.json")))[-1]
    tr = TR.load_trace(f)
    spans = tr["spans"]
    q = {s["args"]["q"] for s in spans if s["name"] == "srt.query"}
    steps = [s for s in spans if s["name"] in ("scanRead", "scanInflate",
                                               "scanParse")]
    assert {s["args"].get("q") for s in steps} == q
    reads = [s["args"]["what"] for s in spans if s["name"] == "scanRead"]
    assert sorted(reads) == ["chunk"] * 8 + ["footer"] * 2
    assert [s["name"] for s in steps].count("scanParse") == 8
    assert [s["name"] for s in steps].count("scanInflate") == 8
    outer = [s for s in spans if s["name"] == "FileScan.deviceDecodeTime"]
    for s in steps:
        assert any(o["t0"] <= s["t0"] and s["t1"] <= o["t1"]
                   for o in outer), s
    for s in (s for s in steps if s["name"] == "scanInflate"):
        assert any(p["name"] == "scanParse" and p["t0"] <= s["t0"]
                   and s["t1"] <= p["t1"] for p in steps), s
    names = {tr["tidNames"].get(s["tid"], "") for s in steps}
    assert any(n.startswith("srt-multifile") for n in names), names


# ---------------------------------------------------------------------------
# the consumer's wait and the query's starved seconds
# ---------------------------------------------------------------------------

SCAN_SEQUENCES = {
    # (opened, waiting) steps; starved while every open stream waits
    "two_streams_both_wait": ([(1, 0), (1, 0), (0, 1), (0, 1), (0, -1),
                               (0, -1), (-1, 0), (-1, 0)], 1),
    "one_stream_alone": ([(1, 0), (0, 1), (0, -1), (-1, 0)], 1),
    "one_of_two_waits": ([(1, 0), (1, 0), (0, 1), (0, -1), (-1, 0),
                          (-1, 0)], 0),
    # the stream that had a batch closes: the waiting one is now alone
    "the_other_closes": ([(1, 0), (1, 0), (0, 1), (-1, 0), (0, -1),
                          (-1, 0)], 1),
}


@pytest.mark.parametrize("case", sorted(SCAN_SEQUENCES))
def test_scan_streams_book_only_while_every_stream_waits(case):
    steps, starved_intervals = SCAN_SEQUENCES[case]
    timer = M.query_registry().create(M.SCAN_STARVED_TIME, M.ESSENTIAL)
    sc = TR.QueryScope()
    v0 = timer.value
    for opened, waiting in steps:
        sc.scan_streams(opened=opened, waiting=waiting)
        time.sleep(0.005)
    booked = timer.value - v0
    if starved_intervals:
        assert 4e6 <= booked <= 60e6, booked
    else:
        assert booked == 0
    assert sc._streams == sc._waiting == 0 and sc._starved_at is None


def _stream_exec():
    from spark_rapids_tpu.conf import TpuConf
    from spark_rapids_tpu.exec.base import TpuRowToColumnarExec
    from spark_rapids_tpu.sql import types as T

    class Child:
        output = []
        schema = T.StructType([T.StructField("x", T.LongType(), True)])
    ex = TpuRowToColumnarExec.__new__(TpuRowToColumnarExec)
    TpuRowToColumnarExec.__init__(
        ex, Child(), TpuConf({"spark.rapids.sql.concurrentGpuTasks": "4"}),
        goal_rows=1)
    return ex, Child.schema


def _run_streams(plans):
    """Each plan: (batches, producer sleep, consumer sleep) a batch.
    Runs one pipelined stream a plan, each consumer on its own thread,
    under one query; returns (scanStarvedTime booked, the scanWait
    intervals by stream)."""
    from spark_rapids_tpu.columnar.host import HostBatch
    from spark_rapids_tpu.resource import get_semaphore
    ex, schema = _stream_exec()
    sc = TR.QueryScope()
    ex.metrics._query = sc

    def thunk(n, sleep):
        def run():
            for i in range(n):
                time.sleep(sleep)
                yield HostBatch.from_pydict({"x": [i, i + 1]}, schema)
        return run
    sem = get_semaphore(ex.conf)
    streams = [ex._make_pipelined(thunk(n, ps), sem, ex.metrics, None, 2)
               for n, ps, _cs in plans]
    timer = M.query_registry().create(M.SCAN_STARVED_TIME, M.ESSENTIAL)
    v0 = timer.value
    waits = [[] for _ in plans]
    orig = TR.QueryScope.scan_streams

    def consume(i, cs):
        with TR.attach(sc):
            try:
                for _b in streams[i]():
                    time.sleep(cs)
            finally:
                sem.release_if_necessary()

    def traced(self, opened=0, waiting=0):
        # each stream's waits, from the consumer's own thread
        if waiting:
            i = int(threading.current_thread().name[len("consumer"):])
            waits[i].append((waiting, time.perf_counter_ns()))
        orig(self, opened, waiting)
    TR.QueryScope.scan_streams = traced
    try:
        ts = [threading.Thread(target=consume, args=(i, p[2]),
                               name=f"consumer{i}")
              for i, p in enumerate(plans)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
    finally:
        TR.QueryScope.scan_streams = orig
    intervals = [[(a[1], b[1]) for a, b in zip(w[::2], w[1::2])]
                 for w in waits]
    return timer.value - v0, intervals


def _common_ns(a, b):
    return sum(max(0, min(a1, b1) - max(a0, b0))
               for a0, a1 in a for b0, b1 in b)


@pytest.mark.parametrize("case", ["both_slow", "fast_beside_slow"])
def test_starved_time_of_two_streams(case):
    """A stub producer sleeping 50 ms a batch: two such streams book
    about their common wait; a stream whose batches come at once (its
    consumer busy 50 ms with each) beside one slow stream books about
    nothing: it always has a batch for the device."""
    if case == "both_slow":
        booked, waits = _run_streams([(4, 0.05, 0.0), (4, 0.05, 0.0)])
        common = _common_ns(*waits)
        assert common > 100e6, waits
        assert abs(booked - common) <= 0.2 * common + 10e6, (booked, common)
    else:
        booked, waits = _run_streams([(8, 0.0, 0.05), (4, 0.05, 0.0)])
        assert booked < 40e6, booked


# ---------------------------------------------------------------------------
# the interpreter's collections
# ---------------------------------------------------------------------------

def test_a_collection_inside_a_query_is_booked_and_spanned(tmp_path,
                                                           monkeypatch):
    path = _write_table(tmp_path, "snappy", n=5_000)
    spark = _session(tmp_path)
    orig = DD.plan_unit_encoded

    def collecting(*a, **k):
        gc.collect()
        return orig(*a, **k)
    monkeypatch.setattr(DD, "plan_unit_encoded", collecting)
    try:
        assert TR._on_gc in gc.callbacks   # registered at engine start
        spark.read.parquet(path).createOrReplaceTempView("t")
        before = _totals()
        spark.sql(SQL).collect()
        d = _delta(before)
    finally:
        spark.stop()
    assert d["gcCollections.2"] >= 1 and d[M.GC_PAUSE_TIME] > 0, d
    f = sorted(glob.glob(os.path.join(str(tmp_path), "tr", "*.json")))[-1]
    spans = TR.load_trace(f)["spans"]
    (root,) = [s for s in spans if s["name"] == "srt.query"]
    pauses = [s for s in spans if s["name"] == "gcPause"
              and s["args"].get("gen") == 2]
    assert pauses and all(s["args"]["q"] == root["args"]["q"]
                          for s in pauses)


# ---------------------------------------------------------------------------
# the readers of the five new per-layer metrics
# ---------------------------------------------------------------------------

NEW_READERS = {"scan_starved_s": "scanStarvedTime",
               "scan_read_host_s": "scanReadTime",
               "scan_inflate_host_s": "scanInflateTime",
               "scan_parse_host_s": "scanParseTime",
               "gc_pause_s": "gcPauseTime"}


@pytest.mark.parametrize("name", sorted(NEW_READERS))
def test_reader_reports_nothing_without_its_counter(name):
    from benchmarks.harness import cell as C
    from benchmarks.harness.watch import CompileCounts
    from benchmarks.harness.window import Window
    reader = C.load_module(os.path.join(C.BENCH_DIR, "metrics",
                                        name + ".py"), "metric_" + name)
    recs = [C.QueryRecord(client=0, binding=0, t_start=0.0, t_end=1.0,
                          ok=True) for _ in range(2)]
    base = dict(seconds=1.0, t_start=0.0, setup_s=1.0, scan_rows=1,
                records=recs, compiles=CompileCounts(0, 0.0, 0, 0))
    assert reader.read(Window(counters={"dispatchCount": 3}, **base)) \
        is None
    got = reader.read(Window(counters={NEW_READERS[name]: 3_000_000_000},
                             **base))
    assert got == pytest.approx(1.5)


def test_new_kinds_are_catalogued_and_name_their_metric():
    """The span-kind lint passes with the new kinds, and each kind's
    reader line names the benchmark metric that reads it."""
    from spark_rapids_tpu.lint import load_config, run_lint
    from spark_rapids_tpu.lint.engine import default_root
    for kind, metric in (("scanRead", "scan_read_host_s"),
                         ("scanInflate", "scan_inflate_host_s"),
                         ("scanParse", "scan_parse_host_s"),
                         ("scanWait", "scan_starved_s"),
                         ("gcPause", "gc_pause_s")):
        assert kind in TR.SPAN_CATALOG
        assert f"`{metric}`" in TR.KIND_READERS[kind]
    root = default_root()
    r = run_lint(root, load_config(root))
    assert [f for f in r.findings if f.rule in ("span-kind", "metric-key")] \
        == []


# ---------------------------------------------------------------------------
# tools trace: a wait span owns a gap only where no work span covers it
# ---------------------------------------------------------------------------

def _span(name, t0, t1, q=1):
    return {"name": name, "t0": float(t0), "t1": float(t1), "tid": 0,
            "args": {"q": q}}


WAIT_CASES = {
    # the consumer began waiting after its producer's parse began: the
    # parse keeps the gap (before the repair the wait, started last,
    # took all of it)
    "wait_inside_parse": (
        [(20_000, 30_000)],
        [_span("srt.query", 0, 100_000),
         _span("FileScan.deviceDecodeTime", 10_000, 40_000),
         _span("scanParse", 12_000, 35_000),
         _span("scanWait", 18_000, 45_000)],
        {"scanParse": 10_000.0}),
    # where only the wait covers an instant, it owns it; the root never
    # beats a wait
    "wait_past_the_work": (
        [(30_000, 50_000)],
        [_span("srt.query", 0, 100_000),
         _span("scanInflate", 25_000, 40_000),
         _span("semaphoreWait", 20_000, 60_000)],
        {"scanInflate": 10_000.0, "semaphoreWait": 10_000.0}),
}


@pytest.mark.parametrize("case", sorted(WAIT_CASES))
def test_a_wait_owns_a_gap_only_where_no_work_covers_it(case):
    gaps, spans, by_kind = WAIT_CASES[case]
    att = TL.attribute_gaps(gaps, spans)
    assert att["byKind"] == by_kind

"""Live-telemetry corpus (docs/observability.md "Live telemetry"):
flight recorder (ring mode bit-identity, bounded memory, Chrome-schema
dumps loading in `tools trace`), the trigger engine (forced slow-query
bundle round trip under the server, per-trigger rate limiting, HBM /
queue / retry-storm units), the Prometheus endpoint (exposition
parseability, describe_metric coverage, monotone counters across
registry GC, the protocol verb + HTTP twin), `tools top`, the
empty-trace-dir CLI contract, the stats-under-concurrent-mutation
satellite, and lint fixtures for the span-kind / prom-family rules."""

from __future__ import annotations

import glob
import json
import os
import re
import threading
import time

import pytest

from spark_rapids_tpu import trace as TR
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.sql.session import TpuSparkSession
from spark_rapids_tpu.telemetry import triggers as TEL

from tests.datagen import (IntegerGen, KeyStringGen, LongGen, SmallIntGen,
                           gen_batch)
from tests.test_trace import _check_wellformed


@pytest.fixture(autouse=True)
def _fresh_state():
    TR.reset_tracing()
    TEL.engine().reset()
    yield
    TR.reset_tracing()
    TEL.engine().reset()


def _base_conf(**extra):
    conf = {"spark.rapids.sql.enabled": "true",
            "spark.rapids.sql.batchSizeRows": "512"}
    conf.update(extra)
    return conf


def _agg_df(s):
    df = s.createDataFrame(
        gen_batch([("flag", KeyStringGen(cardinality=3)),
                   ("status", SmallIntGen()),
                   ("qty", LongGen()), ("price", IntegerGen())],
                  3000, 41),
        num_partitions=4)
    return (df.filter(F.col("qty") % 5 != 0)
            .groupBy("flag", "status")
            .agg(F.sum("qty").alias("sq"), F.count("*").alias("c"))
            .orderBy("flag", "status"))


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------

def test_ring_mode_bit_identical_and_writes_no_files(tmp_path):
    clean = None
    s = TpuSparkSession(_base_conf())
    try:
        clean = _agg_df(s)._execute().to_pydict()
    finally:
        s.stop()
    TR.reset_tracing()
    tdir = tmp_path / "should-stay-empty"
    s = TpuSparkSession(_base_conf(**{
        "spark.rapids.sql.trace.enabled": "true",
        "spark.rapids.sql.trace.mode": "ring",
        "spark.rapids.sql.trace.dir": str(tdir)}))
    try:
        ringed = _agg_df(s)._execute().to_pydict()
    finally:
        s.stop()
    assert ringed == clean
    # ring mode never writes per-query files; the recorder holds spans
    assert not glob.glob(str(tdir / "*.json"))
    ring = TR.ring_active()
    assert ring is not None
    counts = ring.record_counts()
    assert counts["spans"] > 0 and counts["queriesBegun"] >= 1


def test_ring_dump_schema_and_tools_trace(tmp_path, capsys):
    from spark_rapids_tpu.telemetry import dump_ring
    from spark_rapids_tpu.tools import _main, analyze_trace
    s = TpuSparkSession(_base_conf(**{
        "spark.rapids.sql.trace.enabled": "true",
        "spark.rapids.sql.trace.mode": "ring"}))
    try:
        _agg_df(s)._execute()
        _agg_df(s)._execute()
    finally:
        s.stop()
    path = dump_ring(str(tmp_path / "dumps"))
    assert path is not None and os.path.basename(path).startswith(
        "trace-ring-")
    with open(path) as f:
        names = _check_wellformed(json.load(f))
    # dispatch + compile + queryEnd survive in the window
    assert any(n.endswith(".dispatch") or n == "compile"
               for n in names), names
    tr = TR.load_trace(path)
    assert {i["name"] for i in tr["instants"]} >= {"queryEnd"}
    # the offline analyzers work unchanged on dumps
    assert analyze_trace(path)["spanCount"] == len(tr["spans"])
    assert _main(["trace", path]) == 0
    assert "critical path" in capsys.readouterr().out
    assert _main(["hotspots", str(tmp_path / "dumps")]) == 0


def test_ring_memory_is_bounded():
    s = TpuSparkSession(_base_conf(**{
        "spark.rapids.sql.trace.enabled": "true",
        "spark.rapids.sql.trace.mode": "ring",
        "spark.rapids.sql.trace.ringSpans": "64"}))
    try:
        for _ in range(3):
            _agg_df(s)._execute()
    finally:
        s.stop()
    ring = TR.ring_active()
    assert ring is not None and ring.capacity == 64
    for rings in (ring._span_rings, ring._instant_rings):
        for dq in rings.values():
            assert len(dq) <= 64
    assert len(ring._counter_ring) <= 64


def test_file_mode_query_parks_and_restores_the_ring(tmp_path):
    """A file-mode traced query must not destroy the process-lifetime
    flight recorder: the ring is parked for the file trace's duration
    and reinstalled when it closes (review fix)."""
    s_ring = TpuSparkSession(_base_conf(**{
        "spark.rapids.sql.trace.enabled": "true",
        "spark.rapids.sql.trace.mode": "ring"}))
    try:
        _agg_df(s_ring)._execute()
    finally:
        s_ring.stop()
    ring = TR.ring_active()
    assert ring is not None
    begun = ring.record_counts()["queriesBegun"]
    tdir = tmp_path / "file-traces"
    s_file = TpuSparkSession(_base_conf(**{
        "spark.rapids.sql.trace.enabled": "true",
        "spark.rapids.sql.trace.dir": str(tdir)}))
    try:
        _agg_df(s_file)._execute()
    finally:
        s_file.stop()
    # the file trace was written AND the same recorder is back
    assert glob.glob(str(tdir / "trace-*.json"))
    assert TR.ring_active() is ring
    assert ring.record_counts()["queriesBegun"] == begun


def test_server_respects_explicit_file_trace_choice(tmp_path):
    """An operator who sets ONLY trace.enabled=true gets the
    documented default (per-query files), not a silent ring flip
    (review fix)."""
    from spark_rapids_tpu.serve import QueryServer
    srv = QueryServer({"spark.rapids.sql.enabled": "true",
                       "spark.rapids.sql.trace.enabled": "true"})
    assert "spark.rapids.sql.trace.mode" not in srv._base_conf
    srv2 = QueryServer({"spark.rapids.sql.enabled": "true"})
    assert srv2._base_conf["spark.rapids.sql.trace.mode"] == "ring"


# ---------------------------------------------------------------------------
# Trigger engine
# ---------------------------------------------------------------------------

def test_trigger_rate_limit_unit():
    eng = TEL.TriggerEngine()
    assert eng._maybe_fire("slowQuery", {"x": 1}, out_dir="/tmp",
                           min_interval=3600.0) is True
    assert eng._maybe_fire("slowQuery", {"x": 2}, out_dir="/tmp",
                           min_interval=3600.0) is False
    # a DIFFERENT trigger is not limited by slowQuery's window
    assert eng._maybe_fire("hbmWatermark", {"x": 3}, out_dir="/tmp",
                           min_interval=3600.0) is True
    assert eng.drain(10.0)
    st = eng.stats()
    assert st["fired"] == {"slowQuery": 1, "hbmWatermark": 1}
    assert st["rateLimited"] == {"slowQuery": 1}


def test_watermark_triggers_unit(tmp_path):
    from spark_rapids_tpu.conf import TpuConf
    eng = TEL.TriggerEngine()
    eng.configure(TpuConf({
        "spark.rapids.sql.telemetry.dir": str(tmp_path),
        "spark.rapids.sql.telemetry.hbmWatermark": "0.8",
        "spark.rapids.sql.telemetry.queueWatermark": "0.5",
        "spark.rapids.sql.telemetry.retryStormThreshold": "3",
        "spark.rapids.sql.telemetry.triggerMinIntervalS": "3600"}))
    assert eng.armed
    eng.on_store_sample(70, 100)    # under: no fire
    eng.on_store_sample(90, 100)    # over the 0.8 watermark
    eng.on_admission(1, 10)         # under
    eng.on_admission(8, 10)         # over the 0.5 watermark
    for _ in range(5):
        eng.on_retry()              # 5 > 3 in the window
    assert eng.drain(10.0)
    fired = eng.stats()["fired"]
    assert fired.get("hbmWatermark") == 1
    assert fired.get("queueSaturation") == 1
    assert fired.get("retryStorm") == 1
    bundles = sorted(os.listdir(tmp_path))
    assert [b.split("-")[-1] for b in bundles
            if b.startswith("bundle-")] == \
        ["hbmWatermark.json", "queueSaturation.json",
         "retryStorm.json"]
    with open(tmp_path / [b for b in bundles
                          if "hbmWatermark" in b][0]) as f:
        b = json.load(f)
    assert b["condition"]["occupancy"] == 0.9
    assert b["trigger"] == "hbmWatermark"


def test_default_sessions_never_disarm_a_configured_engine(tmp_path):
    from spark_rapids_tpu.conf import TpuConf
    eng = TEL.TriggerEngine()
    eng.configure(TpuConf({
        "spark.rapids.sql.telemetry.hbmWatermark": "0.5",
        "spark.rapids.sql.telemetry.dir": str(tmp_path)}))
    assert eng.armed and eng._hbm_watermark == 0.5
    eng.configure(TpuConf({"spark.rapids.sql.enabled": "true"}))
    assert eng.armed and eng._hbm_watermark == 0.5


# ---------------------------------------------------------------------------
# Shared serving fixtures (slow-query bundle + endpoint + S4)
# ---------------------------------------------------------------------------

Q1S = """
SELECT flag, status, sum(qty) AS sq, min(price) AS mn,
       max(price) AS mx, count(*) AS c
FROM lineitem WHERE qty % 5 != 0
GROUP BY flag, status ORDER BY flag, status
"""

Q3S = """
SELECT brand, sum(amt) AS sa, count(*) AS c
FROM fact JOIN dim ON item = item2
GROUP BY brand ORDER BY brand LIMIT 50
"""


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("telemetry_data")
    gen = TpuSparkSession({"spark.rapids.sql.enabled": "false"})
    try:
        gen.createDataFrame(gen_batch(
            [("flag", KeyStringGen(cardinality=3)),
             ("status", SmallIntGen()), ("qty", LongGen()),
             ("price", IntegerGen())], 3000, 42),
            num_partitions=4).write.mode("overwrite") \
            .parquet(str(d / "lineitem"))
        gen.createDataFrame(gen_batch(
            [("k", SmallIntGen()), ("item", IntegerGen()),
             ("amt", LongGen())], 2500, 43),
            num_partitions=3).write.mode("overwrite") \
            .parquet(str(d / "fact"))
        gen.createDataFrame(gen_batch(
            [("item2", IntegerGen()),
             ("brand", KeyStringGen(cardinality=5))], 400, 44),
            num_partitions=2).write.mode("overwrite") \
            .parquet(str(d / "dim"))
    finally:
        gen.stop()
    return d


def _serial_rows(data_dir, sql):
    spark = TpuSparkSession(_base_conf())
    try:
        spark.read.parquet(str(data_dir / "lineitem")) \
            .createOrReplaceTempView("lineitem")
        spark.read.parquet(str(data_dir / "fact")) \
            .createOrReplaceTempView("fact")
        spark.read.parquet(str(data_dir / "dim")) \
            .createOrReplaceTempView("dim")
        return [tuple(r) for r in spark.sql(sql)._execute().rows()]
    finally:
        spark.stop()


@pytest.fixture(scope="module")
def oracle(data_dir):
    return {"q1": _serial_rows(data_dir, Q1S),
            "q3": _serial_rows(data_dir, Q3S)}


def _server(data_dir, **extra):
    from spark_rapids_tpu.serve import QueryServer
    conf = _base_conf(**extra)
    srv = QueryServer(conf).start()
    srv.register_view("lineitem", str(data_dir / "lineitem"))
    srv.register_view("fact", str(data_dir / "fact"))
    srv.register_view("dim", str(data_dir / "dim"))
    return srv


def test_forced_slow_query_bundle_roundtrip_under_server(
        data_dir, oracle, tmp_path):
    """ISSUE 12 acceptance: a forced slow-query trigger under the
    server produces a bundle whose ring dump passes the Chrome-trace
    schema check and loads in `tools trace`."""
    from spark_rapids_tpu.serve import ServeClient
    from spark_rapids_tpu.tools import _main
    tdir = tmp_path / "telemetry"
    pdir = tmp_path / "profiles"
    srv = _server(data_dir, **{
        "spark.rapids.sql.telemetry.dir": str(tdir),
        "spark.rapids.sql.telemetry.slowQueryMs": "1",
        "spark.rapids.sql.telemetry.triggerMinIntervalS": "3600",
        "spark.rapids.sql.profile.enabled": "true",
        "spark.rapids.sql.profile.dir": str(pdir)})
    try:
        with ServeClient(srv.port, tenant="probe") as c:
            batch, header = c.sql(Q1S)
            assert [tuple(r) for r in batch.rows()] == oracle["q1"]
        assert TEL.engine().drain(30.0)
        bundles = sorted(glob.glob(str(tdir / "bundle-*.json")))
        assert len(bundles) == 1, bundles
        with open(bundles[0]) as f:
            b = json.load(f)
        assert b["trigger"] == "slowQuery"
        assert b["condition"]["tenant"] == "probe"
        assert b["condition"]["wallMs"] > 1
        # the bundle ties all three surfaces together
        assert b["profile"] and os.path.exists(b["profile"])
        assert b["serverStats"]["admission"]["admitted"] >= 1
        assert b["storeStats"] is not None
        ring_dump = b["ringDump"]
        assert ring_dump and os.path.exists(ring_dump)
        with open(ring_dump) as f:
            _check_wellformed(json.load(f))
        assert _main(["trace", ring_dump]) == 0
    finally:
        srv.shutdown()


def test_server_metrics_verb_and_http_twin(data_dir, oracle):
    import urllib.request
    from spark_rapids_tpu.serve import ServeClient
    srv = _server(data_dir)
    try:
        http_port = srv.start_metrics_http(0)
        with ServeClient(srv.port, tenant="alpha") as c:
            batch, _ = c.sql(Q1S)
            assert [tuple(r) for r in batch.rows()] == oracle["q1"]
            text = c.metrics()
        _assert_prometheus_wellformed(text)
        assert "srt_queries_ok_total 1" in text
        assert 'srt_tenant_admitted_total{tenant="alpha"} 1' in text
        assert re.search(r"^srt_undescribed_metric_keys 0$", text,
                         re.M), "endpoint exported an undescribed key"
        # the HTTP twin serves the same exposition
        with urllib.request.urlopen(
                f"http://127.0.0.1:{http_port}/metrics",
                timeout=10) as resp:
            assert resp.status == 200
            http_text = resp.read().decode("utf-8")
        _assert_prometheus_wellformed(http_text)
        assert "srt_queries_ok_total" in http_text
    finally:
        srv.shutdown()


_SAMPLE_RE = re.compile(
    r"^[a-z_][a-z0-9_]*(\{[a-zA-Z0-9_]+=\"[^\"]*\""
    r"(,[a-zA-Z0-9_]+=\"[^\"]*\")*\})? -?[0-9][0-9.e+-]*$")


def _assert_prometheus_wellformed(text: str) -> None:
    seen_type = {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP ") or line.startswith("# TYPE "):
            parts = line.split(" ", 3)
            assert len(parts) >= 4 and parts[2], line
            if parts[1] == "TYPE":
                seen_type[parts[2]] = parts[3]
            continue
        assert _SAMPLE_RE.match(line), f"unparseable sample: {line!r}"
        fam = line.split("{", 1)[0].split(" ", 1)[0]
        assert fam in seen_type, f"sample before TYPE: {line!r}"


def test_prometheus_engine_families_from_described_keys():
    s = TpuSparkSession(_base_conf())
    try:
        _agg_df(s)._execute()
    finally:
        s.stop()
    from spark_rapids_tpu.metrics import MetricRegistry
    from spark_rapids_tpu.telemetry.prometheus import render_prometheus
    scan = MetricRegistry(owner="PrefixProbe")
    scan.create("deviceDecodedValues.PLAIN").add(3)
    text = render_prometheus()
    _assert_prometheus_wellformed(text)
    assert re.search(r"^srt_num_output_rows_total \d+$", text, re.M)
    assert re.search(r"^srt_op_time_seconds_total \d", text, re.M)
    assert re.search(r"^srt_undescribed_metric_keys 0$", text, re.M)
    # prefix families carry their member as a label
    assert re.search(
        r'^srt_device_decoded_values_total\{key="PLAIN"\} \d+$',
        text, re.M)


def test_prometheus_counters_monotone_across_registry_gc():
    import gc
    from spark_rapids_tpu.metrics import MetricRegistry
    from spark_rapids_tpu.telemetry.prometheus import aggregator
    reg = MetricRegistry(owner="GcProbe")
    reg.create("numOutputRows").add(7)
    before = aggregator().scrape()[0].get("numOutputRows", 0)
    assert before >= 7
    del reg
    gc.collect()
    after = aggregator().scrape()[0].get("numOutputRows", 0)
    # the retired base keeps the dead registry's contribution
    assert after >= before


def test_prometheus_delta_aggregator_reuses_unchanged_snapshots():
    from spark_rapids_tpu.metrics import MetricRegistry
    from spark_rapids_tpu.telemetry.prometheus import RegistryAggregator
    agg = RegistryAggregator()
    reg = MetricRegistry(owner="DeltaProbe")
    m = reg.create("numOutputRows")
    m.add(1)
    totals, _ = agg.scrape()
    assert totals.get("numOutputRows", 0) >= 1
    # nothing changed in THIS registry: its cached snapshot is reused
    _, changed_idle = agg.scrape()
    m.add(1)
    _, changed_after = agg.scrape()
    assert changed_after >= 1
    assert reg is not None  # keep it alive through the scrapes


# ---------------------------------------------------------------------------
# S4: stats/metrics under concurrent mutation
# ---------------------------------------------------------------------------

def test_server_stats_consistent_under_concurrent_mutation(
        data_dir, oracle):
    """Hammer stats+metrics from the main thread while c=8 mixed
    queries run: snapshots are internally consistent (complete
    per-tenant rows, counters monotone) and every query result stays
    bit-identical to serial."""
    from spark_rapids_tpu.serve import ServeClient
    srv = _server(data_dir, **{
        "spark.rapids.sql.serve.maxConcurrentQueries": "8",
        "spark.rapids.sql.serve.maxConcurrentPerTenant": "8",
        "spark.rapids.sql.serve.maxQueued": "64"})
    mismatches: list = []
    errors: list = []

    def worker(i):
        try:
            with ServeClient(srv.port, tenant=f"t{i % 3}") as c:
                kind = "q1" if i % 2 == 0 else "q3"
                batch, _ = c.sql(Q1S if kind == "q1" else Q3S)
                rows = [tuple(r) for r in batch.rows()]
                if rows != oracle[kind]:
                    mismatches.append((i, kind))
        except Exception as e:  # noqa: BLE001 - asserted below
            errors.append(repr(e))

    try:
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(16)]
        for t in threads:
            t.start()
        snapshots = []
        ok_series = []
        with ServeClient(srv.port, tenant="observer") as sc:
            while True:
                st = sc.stats()
                snapshots.append(st)
                m = re.search(r"^srt_queries_ok_total (\d+)$",
                              sc.metrics(), re.M)
                ok_series.append(int(m.group(1)))
                if not any(t.is_alive() for t in threads):
                    break
                time.sleep(0.01)
        for t in threads:
            t.join()
    finally:
        srv.shutdown()
    assert not errors, errors[:3]
    assert not mismatches, mismatches
    assert snapshots
    prev = None
    for st in snapshots:
        adm = st["admission"]
        # bounds hold in every snapshot (no torn counter pairs)
        assert 0 <= adm["inFlight"] <= adm["maxConcurrentQueries"]
        assert adm["queued"] >= 0
        for tenant, row in adm["tenants"].items():
            # no torn per-tenant rows: every field present and sane
            assert set(row) >= {"admitted", "rejected", "inFlight",
                                "queueWaitMs"}, (tenant, row)
            assert row["admitted"] >= 0 and row["inFlight"] >= 0
        if prev is not None:
            padm = prev["admission"]
            assert adm["admitted"] >= padm["admitted"]
            assert adm["rejected"] >= padm["rejected"]
            assert st["queriesOk"] >= prev["queriesOk"]
            for tenant, row in padm["tenants"].items():
                cur = adm["tenants"].get(tenant)
                assert cur is not None, f"tenant {tenant} vanished"
                assert cur["admitted"] >= row["admitted"]
        prev = st
    assert ok_series == sorted(ok_series), "endpoint counter not " \
        "monotone under load"


# ---------------------------------------------------------------------------
# tools top
# ---------------------------------------------------------------------------

def test_tools_top_format_and_live_poll(data_dir, oracle):
    from spark_rapids_tpu.serve import ServeClient
    from spark_rapids_tpu.telemetry.top import format_top, run_top
    srv = _server(data_dir)
    try:
        with ServeClient(srv.port, tenant="topten") as c:
            batch, _ = c.sql(Q1S)
            assert [tuple(r) for r in batch.rows()] == oracle["q1"]
        frame = format_top(srv.stats())
        assert "topten" in frame and "qps" in frame and "p99ms" in frame
        # per-tenant QPS from an admitted-count delta between frames
        prev = srv.stats()
        cur = json.loads(json.dumps(prev))
        cur["admission"]["tenants"]["topten"]["admitted"] += 5
        delta_frame = format_top(cur, prev=prev, interval=1.0)
        assert re.search(r"topten\s+5\.00", delta_frame), delta_frame
        assert run_top(srv.port, interval=0.1, iterations=1) == 0
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# S1: trace/hotspots CLI on empty or span-free inputs
# ---------------------------------------------------------------------------

def test_hotspots_cli_exit_contract(tmp_path):
    # PR 12 contract, through the real `python -m` entry: an EXISTING
    # but empty trace dir is a normal answer ("no spans found", exit
    # 0) — an idle ring recorder must not fail automation tailing it;
    # a missing path stays an error
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-m", "spark_rapids_tpu.tools", "hotspots",
         str(tmp_path)],
        capture_output=True, text=True, cwd=repo)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "no spans found" in out.stdout
    out = subprocess.run(
        [sys.executable, "-m", "spark_rapids_tpu.tools", "hotspots",
         str(tmp_path / "does-not-exist")],
        capture_output=True, text=True, cwd=repo)
    assert out.returncode == 1
    assert "no such trace file or directory" in out.stdout



def test_trace_cli_empty_dir_and_missing_path(tmp_path, capsys):
    from spark_rapids_tpu.tools import _main
    empty = tmp_path / "empty"
    empty.mkdir()
    for cmd in ("trace", "hotspots"):
        assert _main([cmd, str(empty)]) == 0
        assert "no spans found" in capsys.readouterr().out
        assert _main([cmd, str(tmp_path / "missing")]) == 1
        assert "no such trace file" in capsys.readouterr().out
    # a span-free trace FILE is also a clean answer
    from spark_rapids_tpu.trace import QueryTrace, write_chrome_trace
    qt = QueryTrace(1)
    spanfree = empty / "trace-1-q00001.json"
    write_chrome_trace(str(spanfree), qt)
    assert _main(["trace", str(empty)]) == 0
    assert "no spans recorded" in capsys.readouterr().out
    assert _main(["hotspots", str(empty)]) == 0
    assert "no spans recorded" in capsys.readouterr().out
    # garbage input: clean error, not a stack trace
    bad = empty / "trace-2-q00002.json"
    bad.write_text("{not json")
    assert _main(["trace", str(bad)]) == 1
    assert "not a readable Chrome-trace file" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# Lint fixtures: span-kind + prom-family
# ---------------------------------------------------------------------------

def _lint_tree(tmp_path, files):
    import textwrap
    root = tmp_path / "fixture"
    for rel, src in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src).lstrip("\n"))
    for d in ("spark_rapids_tpu", "spark_rapids_tpu/telemetry"):
        if (root / d).is_dir():
            init = root / d / "__init__.py"
            if not init.exists():
                init.write_text("")
    return str(root)


def _lint(root):
    from spark_rapids_tpu.lint import LintConfig, run_lint
    return run_lint(root, LintConfig(check_docs=False))


def test_lint_span_kind_bad_and_good(tmp_path):
    root = _lint_tree(tmp_path, {
        "spark_rapids_tpu/trace.py": """
            SPAN_CATALOG = {"goodSpan": "a documented span"}
            INSTANT_CATALOG = {"goodMark": "a documented instant"}
        """,
        "spark_rapids_tpu/x.py": """
            from spark_rapids_tpu import trace as TR

            def f(qt):
                with TR.span("goodSpan"):
                    pass
                with TR.span("rogueSpan"):
                    pass
                TR.instant("goodMark")
                TR.instant("rogueMark")
                qt.add("goodSpan", 0, 1)
                qt.add("rogueQt", 0, 1)
                qt.mark("goodMark")
        """})
    r = _lint(root)
    kinds = sorted(f.message.split("'")[1] for f in r.findings
                   if f.rule == "span-kind")
    assert kinds == ["rogueMark", "rogueQt", "rogueSpan"], r.findings


def test_lint_prom_family_bad_and_good(tmp_path):
    root = _lint_tree(tmp_path, {
        "spark_rapids_tpu/trace.py": """
            SPAN_CATALOG = {}
            INSTANT_CATALOG = {}
        """,
        "spark_rapids_tpu/telemetry/prometheus.py": """
            SERVER_FAMILY_HELP = {
                "srt_good_total": ("counter", "fine"),
                "srt-BAD-name": ("counter", "violates naming"),
            }

            def _emit_server(out, name, value, labels=None):
                pass

            def render(out):
                _emit_server(out, "srt_good_total", 1)
                _emit_server(out, "srt_unlisted_total", 1)
        """})
    r = _lint(root)
    msgs = [f.message for f in r.findings if f.rule == "prom-family"]
    assert len(msgs) == 2, r.findings
    assert any("srt-BAD-name" in m for m in msgs)
    assert any("srt_unlisted_total" in m for m in msgs)

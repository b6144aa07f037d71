"""Persistent query-history corpus (docs/observability.md "Query
history" / "SLO tracking" / "tools doctor"): store units (rotation,
compaction, crash-safe reads), per-signature aggregates + trends, the
session/server write paths, event-log status/reason agreement, the
RESTART ROUND TRIP acceptance (warm watchdog p99 + warm quarantine on
a fresh server over the same history dir), the retry-storm doctor
acceptance (retryBlock named as the divergent stage), SLO families +
the sloBurn trigger, telemetry-artifact retention, a Prometheus scrape
racing graceful drain, the tools history/doctor CLI contracts, and the
`history-field` lint fixtures."""

from __future__ import annotations

import glob
import json
import os
import threading
import time

import pytest

from spark_rapids_tpu import lifecycle as LC
from spark_rapids_tpu import retry as R
from spark_rapids_tpu import trace as TR
from spark_rapids_tpu.sql.session import TpuSparkSession
from spark_rapids_tpu.telemetry import history as H
from spark_rapids_tpu.telemetry import triggers as TEL

from tests.datagen import (IntegerGen, KeyStringGen, LongGen,
                           SmallIntGen, gen_batch)


@pytest.fixture(autouse=True)
def _fresh_state():
    TR.reset_tracing()
    R.reset_fault_injection()
    LC.reset_lifecycle()
    H.reset_history()
    TEL.engine().reset()
    yield
    TR.reset_tracing()
    R.reset_fault_injection()
    LC.reset_lifecycle()
    H.reset_history()
    TEL.engine().reset()


Q1S = """
SELECT flag, status, sum(qty) AS sq, min(price) AS mn,
       max(price) AS mx, count(*) AS c
FROM lineitem WHERE qty % 5 != 0
GROUP BY flag, status ORDER BY flag, status
"""


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("history_data")
    gen = TpuSparkSession({"spark.rapids.sql.enabled": "false"})
    try:
        li = gen.createDataFrame(gen_batch(
            [("flag", KeyStringGen(cardinality=3)),
             ("status", SmallIntGen()), ("qty", LongGen()),
             ("price", IntegerGen())], 3000, 31), num_partitions=4)
        li.write.mode("overwrite").parquet(str(d / "lineitem"))
    finally:
        gen.stop()
    return d


@pytest.fixture(scope="module")
def oracle(data_dir):
    spark = TpuSparkSession({"spark.rapids.sql.enabled": "true",
                             "spark.rapids.sql.batchSizeRows": "512"})
    try:
        spark.read.parquet(str(data_dir / "lineitem")) \
            .createOrReplaceTempView("lineitem")
        return [tuple(r) for r in spark.sql(Q1S)._execute().rows()]
    finally:
        spark.stop()


def _session(data_dir, **conf):
    base = {"spark.rapids.sql.enabled": "true",
            "spark.rapids.sql.batchSizeRows": "512",
            "spark.rapids.sql.planCache.enabled": "true"}
    base.update({k: str(v) for k, v in conf.items()})
    s = TpuSparkSession(base)
    s.read.parquet(str(data_dir / "lineitem")) \
        .createOrReplaceTempView("lineitem")
    return s


def _server(data_dir, **conf):
    from spark_rapids_tpu.serve import QueryServer
    base = {"spark.rapids.sql.enabled": "true",
            "spark.rapids.sql.batchSizeRows": "512"}
    base.update({k: str(v) for k, v in conf.items()})
    srv = QueryServer(base).start()
    srv.register_view("lineitem", str(data_dir / "lineitem"))
    return srv


# ---------------------------------------------------------------------------
# Store units
# ---------------------------------------------------------------------------

def _rec(ts, sig="a" * 40, status="finished", wall=0.1, **kw):
    r = {"version": 1, "ts": ts, "signature": sig, "status": status,
         "wallSeconds": wall, "queueWaitSeconds": 0.0,
         "outputRows": 10}
    r.update(kw)
    return r


def test_store_roundtrip_and_crash_safety(tmp_path):
    d = str(tmp_path / "hist")
    store = H.HistoryStore(d, max_bytes=1 << 20, max_age_days=14)
    # ts in the PAST (like real append-time records): the since-filter
    # skips whole segments by mtime, which tracks the last append
    t0 = time.time() - 10
    for i in range(10):
        store.append(_rec(t0 + i, wall=0.1 * (i + 1),
                          tenant=("a" if i % 2 else "b")))
    # a torn tail line (crash mid-append) must be skipped, not fatal
    seg = sorted(glob.glob(os.path.join(d, "history-*.jsonl")))[-1]
    with open(seg, "a") as f:
        f.write('{"version": 1, "ts": 99, "trunc')
    recs = H.read_records(d)
    assert len(recs) == 10
    assert [r["wallSeconds"] for r in recs] == \
        pytest.approx([0.1 * (i + 1) for i in range(10)])
    # filters
    assert len(H.read_records(d, tenant="a")) == 5
    assert len(H.read_records(d, since=t0 + 7.5)) == 2
    st = store.stats()
    assert st["appended"] == 10 and st["segments"] >= 1


def test_store_rotation_and_size_compaction(tmp_path):
    d = str(tmp_path / "hist")
    store = H.HistoryStore(d, max_bytes=2048, max_age_days=0)
    assert store.segment_target == 64 << 10  # floor respected
    store.SEGMENT_FLOOR = 512  # tiny segments for the unit
    t0 = time.time()
    for i in range(200):
        store.append(_rec(t0 + i, extra_pad="x" * 64))
    store.compact()
    segs = glob.glob(os.path.join(d, "history-*.jsonl"))
    total = sum(os.path.getsize(p) for p in segs)
    assert len(segs) > 1, "rotation must produce segments"
    # total bounded at maxBytes + one active segment's slack
    assert total <= store.max_bytes + store.segment_target
    assert store.pruned_segments > 0
    # the NEWEST records survive compaction
    recs = H.read_records(d)
    assert recs and recs[-1]["ts"] == pytest.approx(t0 + 199)


def test_store_age_compaction(tmp_path):
    d = str(tmp_path / "hist")
    store = H.HistoryStore(d, max_bytes=1 << 30, max_age_days=1)
    store.append(_rec(time.time() - 90000))
    # rotate so the old segment is not the active one
    with store._lock:
        store._open_segment_locked()
    store.append(_rec(time.time()))
    old_seg = sorted(glob.glob(os.path.join(d, "history-*.jsonl")))[0]
    past = time.time() - 2 * 86400
    os.utime(old_seg, (past, past))
    assert store.compact() == 1
    assert not os.path.exists(old_seg)
    assert len(H.read_records(d)) == 1


def test_signature_aggregates_and_trend(tmp_path):
    t0 = time.time()
    recs = [_rec(t0 + i * 3600, wall=0.1 + 0.05 * i, tenant="t",
                 retryCount=(1 if i == 3 else 0))
            for i in range(4)]
    recs.append(_rec(t0 + 5 * 3600, status="failed", wall=0.0))
    # a record an older process wrote: it loads, and the fields no
    # reader knows any more (the kernel tier's, gone since PR 32) are
    # carried and ignored
    store = H.HistoryStore(str(tmp_path / "hist"), 1 << 20, 14)
    store.append(_rec(t0, sig="b" * 40, kernelDispatches=9,
                      kernelFallbacks=2,
                      kernelFallbacksByName={"groupbyHash": 2}))
    old = H.read_records(str(tmp_path / "hist"))
    assert len(old) == 1 and old[0]["kernelFallbacks"] == 2
    recs += old
    aggs = H.signature_aggregates(recs)
    a = aggs["a" * 40]
    assert a["count"] == 5 and a["finished"] == 4
    assert a["statuses"] == {"finished": 4, "failed": 1}
    # wall grows 0.05 s per hour of history
    assert a["trendSlopePerHour"] == pytest.approx(0.05, rel=1e-3)
    assert a["retryRate"] == pytest.approx(0.25)
    assert a["tenants"] == ["t"]
    b = aggs["b" * 40]
    assert b["count"] == b["finished"] == 1 and "fallbackRate" not in b
    assert "b" * 12 in H.format_history(recs)
    # display digest: 40-hex signatures show their own prefix
    assert H.sig_digest("a" * 40) == "a" * 12


# ---------------------------------------------------------------------------
# Write paths: session terminal statuses + event-log agreement
# ---------------------------------------------------------------------------

def test_session_appends_finished_and_failed_records(
        tmp_path, data_dir, oracle):
    hdir = str(tmp_path / "hist")
    # reader.maxRetries rides in BOTH confs: it is a planning-visible
    # key (in the signature), unlike the test.inject* schedule
    spark = _session(
        data_dir,
        **{"spark.rapids.sql.telemetry.history.dir": hdir,
           "spark.rapids.sql.profile.enabled": "true",
           "spark.rapids.sql.profile.dir": str(tmp_path / "prof"),
           "spark.rapids.sql.reader.maxRetries": "1"})
    try:
        assert [tuple(r) for r in
                spark.sql(Q1S)._execute().rows()] == oracle
    finally:
        spark.stop()
    recs = H.read_records(hdir)
    assert len(recs) == 1
    rec = recs[0]
    assert rec["status"] == "finished"
    assert rec["outputRows"] == len(oracle)
    assert rec["wallSeconds"] > 0
    assert len(rec["signature"]) == 40  # the digest, not the plan
    assert rec["retryCount"] == 0 and rec["jitMisses"] >= 0
    assert os.path.exists(rec["profilePath"])
    # a runtime-fatal failure appends status=failed with the SAME
    # signature (test.inject* confs are excluded from the signature)
    fail = _session(
        data_dir,
        **{"spark.rapids.sql.telemetry.history.dir": hdir,
           "spark.rapids.sql.profile.enabled": "true",
           "spark.rapids.sql.profile.dir": str(tmp_path / "prof"),
           "spark.rapids.sql.test.injectIOError": "1:99",
           "spark.rapids.sql.reader.maxRetries": "1"})
    try:
        with pytest.raises(OSError):
            fail.sql(Q1S)._execute()
    finally:
        fail.stop()
    recs = H.read_records(hdir)
    assert [r["status"] for r in recs] == ["finished", "failed"]
    assert recs[1]["signature"] == rec["signature"]


def test_event_log_and_history_agree_on_cancelled_outcome(
        tmp_path, data_dir):
    from spark_rapids_tpu.event_log import read_events
    hdir = str(tmp_path / "hist")
    log_dir = str(tmp_path / "events")
    spark = _session(
        data_dir,
        **{"spark.rapids.sql.telemetry.history.dir": hdir,
           "spark.rapids.sql.eventLog.dir": log_dir})
    try:
        tok = LC.CancelToken(tenant="t", query_id="q-7")
        tok.set_deadline(0.0)
        time.sleep(0.01)
        with LC.token_scope(tok):
            with pytest.raises(LC.TpuQueryCancelled):
                spark.sql(Q1S)._execute()
    finally:
        spark.stop()
    recs = H.read_records(hdir)
    assert [r["status"] for r in recs] == ["timed-out"]
    assert recs[0]["reason"] == "deadline"
    assert recs[0]["queryId"] == "q-7"
    evs = [e for e in read_events(log_dir)
           if e.get("event") == "queryCompleted"]
    assert [e["status"] for e in evs] == ["timed-out"]
    assert evs[0]["reason"] == "deadline"
    # a pre-status line (older writer) normalizes to finished
    with open(os.path.join(log_dir, "events-1-1.jsonl"), "w") as f:
        f.write(json.dumps({"event": "queryCompleted", "version": 2,
                            "ts": 1.0, "queryId": 1,
                            "wallSeconds": 0.1, "outputRows": 5,
                            "plan": "p", "ops": []}) + "\n")
    old = [e for e in read_events(log_dir)
           if e.get("queryId") == 1]
    assert old[0]["status"] == "finished"


# ---------------------------------------------------------------------------
# THE acceptance: restart round trip (warm watchdog + warm quarantine)
# ---------------------------------------------------------------------------

def _hook_parked_after_planning(srv, slow_tenant, started, release):
    orig_session = srv._session

    def hook(tenant):
        s = orig_session(tenant)
        if tenant == slow_tenant and not getattr(s, "_pp_hook", None):
            orig_pp = s.plan_physical

            def parked_pp(plan, execute_subqueries=True):
                out = orig_pp(plan,
                              execute_subqueries=execute_subqueries)
                started.set()
                end = time.monotonic() + 60
                while not release.is_set() and time.monotonic() < end:
                    LC.checkpoint("batch")
                    time.sleep(0.01)
                return out

            s._pp_hook = True
            s.plan_physical = parked_pp
        return s

    srv._session = hook


def test_restart_round_trip_warm_watchdog(data_dir, oracle, tmp_path,
                                          capsys):
    """Run N served queries, stop the server, start a FRESH one on the
    same telemetry.history.dir: the watchdog p99 is warm (a parked
    query fires stuckQuery with ZERO post-restart samples) and `tools
    history` shows the pre-restart signatures."""
    from spark_rapids_tpu.serve import ServeClient
    from spark_rapids_tpu.serve.client import ServeCancelled
    from spark_rapids_tpu.tools import _main as tools_main
    hdir = str(tmp_path / "hist")
    tel_dir = str(tmp_path / "tel")
    # non-serve confs must MATCH across both servers (they enter the
    # plan signature); the watchdog knobs are serve.* (excluded)
    shared = {"spark.rapids.sql.telemetry.history.dir": hdir,
              "spark.rapids.sql.telemetry.dir": tel_dir,
              "spark.rapids.sql.telemetry.triggerMinIntervalS": "0"}
    srv = _server(data_dir, **shared)
    try:
        with ServeClient(srv.port, tenant="warm") as c:
            for _ in range(6):
                assert c.collect(Q1S) == oracle
    finally:
        srv.shutdown()
    recs = H.read_records(hdir)
    assert len(recs) == 6
    sig = recs[0]["signature"]
    assert all(r["signature"] == sig for r in recs)
    assert all(r["tenant"] == "warm" for r in recs)
    assert recs[0]["queueWaitSeconds"] >= 0

    # --- "restart": lifecycle state dies with the process ---
    LC.reset_lifecycle()
    assert LC.signature_p99(sig) is None

    srv2 = _server(
        data_dir,
        **{**shared,
           "spark.rapids.sql.serve.watchdogFactor": "3",
           "spark.rapids.sql.serve.watchdogCancel": "true"})
    started = threading.Event()
    release = threading.Event()
    _hook_parked_after_planning(srv2, "stuck", started, release)
    try:
        assert srv2.warm_start_summary["enabled"] is True
        assert srv2.warm_start_summary["walls"] == 6
        # warm: the p99 exists with ZERO post-restart samples
        assert LC.signature_p99(sig) is not None
        with ServeClient(srv2.port, tenant="stuck") as c:
            with pytest.raises(ServeCancelled) as ei:
                c.sql(Q1S)
            assert ei.value.reason == "watchdog"
        st = srv2.stats()
        assert st["lifecycle"]["watchdogFlagged"] >= 1
        assert st["history"]["appended"] >= 6
        assert st["history"]["warmStart"]["walls"] == 6
        assert TEL.engine().drain(timeout=15)
        assert glob.glob(os.path.join(tel_dir,
                                      "bundle-*-stuckQuery.json"))
    finally:
        release.set()
        srv2.shutdown()

    # `tools history` renders the pre-restart signatures
    assert tools_main(["history", hdir]) == 0
    out = capsys.readouterr().out
    assert H.sig_digest(sig) in out
    assert "warm" in out


def test_quarantine_survives_restart_via_warm_start(data_dir,
                                                    tmp_path):
    from spark_rapids_tpu.serve import ServeClient
    from spark_rapids_tpu.serve.client import (ServeError,
                                               ServeQuarantined)
    hdir = str(tmp_path / "hist")
    # reader.maxRetries is planning-visible (in the signature) so it
    # rides in BOTH servers' confs; the test.inject* schedule is not
    shared = {"spark.rapids.sql.telemetry.history.dir": hdir,
              "spark.rapids.sql.serve.quarantineThreshold": "2",
              "spark.rapids.sql.reader.maxRetries": "1"}
    srv = _server(data_dir, **shared,
                  **{"spark.rapids.sql.test.injectIOError": "1:99"})
    try:
        with ServeClient(srv.port, tenant="poison") as c:
            for _ in range(2):
                with pytest.raises(ServeError):
                    c.sql(Q1S)
    finally:
        srv.shutdown()
    recs = H.read_records(hdir)
    assert [r["status"] for r in recs] == ["failed", "failed"]
    sig = recs[0]["signature"]

    # --- "restart" ---
    LC.reset_lifecycle()
    R.reset_fault_injection()
    assert not LC.is_quarantined(sig)

    # the fresh server has NO injection conf — test.inject* keys are
    # excluded from the signature, so the shape still matches
    srv2 = _server(data_dir, **shared)
    try:
        assert srv2.warm_start_summary["quarantined"] == 1
        assert LC.is_quarantined(sig)
        t0 = time.perf_counter()
        with ServeClient(srv2.port, tenant="poison") as c:
            with pytest.raises(ServeQuarantined):
                c.sql(Q1S)
        assert time.perf_counter() - t0 < 2.0, "must fail FAST"
        recs = H.read_records(hdir)
        assert recs[-1]["status"] == "quarantined"
    finally:
        srv2.shutdown()


def test_server_records_queued_cancellation(data_dir, tmp_path):
    """A query cancelled while still QUEUED never reaches the session:
    the SERVER path appends its terminal record."""
    from spark_rapids_tpu.serve import ServeClient, protocol
    from spark_rapids_tpu.serve.client import ServeCancelled
    import socket
    hdir = str(tmp_path / "hist")
    srv = _server(
        data_dir,
        **{"spark.rapids.sql.telemetry.history.dir": hdir,
           "spark.rapids.sql.serve.maxConcurrentQueries": "1",
           "spark.rapids.sql.serve.maxQueued": "8"})
    started = threading.Event()
    release = threading.Event()
    orig_session = srv._session

    def hook(tenant):
        s = orig_session(tenant)
        if tenant == "slow" and not getattr(s, "_park", None):
            orig_sql = s.sql

            def parked_sql(text):
                started.set()
                end = time.monotonic() + 60
                while not release.is_set() and time.monotonic() < end:
                    LC.checkpoint("batch")
                    time.sleep(0.01)
                return orig_sql(text)

            s._park = True
            s.sql = parked_sql
        return s

    srv._session = hook
    try:
        slow_sock = socket.create_connection(("127.0.0.1", srv.port),
                                             timeout=30)
        protocol.send_msg(slow_sock, {"op": "sql", "sql": Q1S,
                                      "tenant": "slow"})
        assert started.wait(timeout=60)
        # the second query queues behind the parked one and times out
        # IN THE QUEUE
        with ServeClient(srv.port, tenant="queued") as c:
            with pytest.raises(ServeCancelled) as ei:
                c.sql(Q1S, timeout_ms=150, query_id="q-queued")
            assert ei.value.where == "queued"
        recs = [r for r in H.read_records(hdir)
                if r.get("tenant") == "queued"]
        assert len(recs) == 1
        assert recs[0]["status"] == "timed-out"
        assert recs[0]["queryId"] == "q-queued"
        assert recs[0]["queueWaitSeconds"] > 0
        slow_sock.close()
    finally:
        release.set()
        srv.shutdown()


# ---------------------------------------------------------------------------
# THE acceptance: doctor on an injected retry storm
# ---------------------------------------------------------------------------

def test_doctor_retry_storm_names_retry_block(data_dir, oracle,
                                              tmp_path, capsys):
    from spark_rapids_tpu.telemetry.doctor import (diagnose,
                                                   format_diagnosis)
    from spark_rapids_tpu.tools import _main as tools_main
    hdir = str(tmp_path / "hist")
    base_conf = {
        "spark.rapids.sql.telemetry.history.dir": hdir,
        "spark.rapids.sql.profile.enabled": "true",
        "spark.rapids.sql.profile.dir": str(tmp_path / "prof"),
        "spark.rapids.sql.trace.enabled": "true",
        "spark.rapids.sql.trace.dir": str(tmp_path / "traces"),
        # consulted only when retries happen: harmless on the clean
        # baseline runs, and keeping it in BOTH confs keeps the plan
        # signature identical across baseline and storm sessions
        "spark.rapids.sql.retry.backoffMs": "30",
        "spark.rapids.sql.retry.maxBackoffMs": "200",
    }
    spark = _session(data_dir, **base_conf)
    try:
        for _ in range(3):
            assert [tuple(r) for r in
                    spark.sql(Q1S)._execute().rows()] == oracle
    finally:
        spark.stop()
    TR.reset_tracing()

    storm = _session(
        data_dir, **base_conf,
        **{"spark.rapids.sql.test.injectOOM": "2:2"})
    try:
        assert [tuple(r) for r in
                storm.sql(Q1S)._execute().rows()] == oracle
    finally:
        storm.stop()
        R.reset_fault_injection()

    recs = H.read_records(hdir)
    assert len(recs) == 4
    sig = recs[0]["signature"]
    assert all(r["signature"] == sig for r in recs), \
        "injection confs must not change the plan signature"
    storm_rec = recs[-1]
    assert storm_rec["retryCount"] > 0
    assert os.path.exists(storm_rec["tracePath"])

    d = diagnose(hdir, str(storm_rec["queryId"]))
    assert d.get("error") is None
    assert d["baseline"]["count"] == 3
    assert d["verdict"] == "retrySpill", d["verdicts"]
    assert d["divergentStage"] == "retryBlock", d["stageDiff"][:4]
    text = format_diagnosis(d)
    assert "retrySpill" in text and "retryBlock" in text

    # CLI contract: selector resolves -> exit 0; bogus -> exit 1
    assert tools_main(["doctor", str(storm_rec["queryId"]),
                       "--history", hdir]) == 0
    out = capsys.readouterr().out
    assert "retrySpill" in out
    assert tools_main(["doctor", "no-such-query",
                       "--history", hdir]) == 1
    # the signature digest is a selector too
    assert tools_main(["doctor", H.sig_digest(sig),
                       "--history", hdir, "--json"]) == 0


# ---------------------------------------------------------------------------
# SLO burn tracking
# ---------------------------------------------------------------------------

def test_slo_tracking_families_and_burn_trigger(data_dir, oracle,
                                                tmp_path):
    from spark_rapids_tpu.serve import ServeClient
    hdir = str(tmp_path / "hist")
    tel_dir = str(tmp_path / "tel")
    srv = _server(
        data_dir,
        **{"spark.rapids.sql.telemetry.history.dir": hdir,
           "spark.rapids.sql.telemetry.dir": tel_dir,
           "spark.rapids.sql.telemetry.triggerMinIntervalS": "0",
           # 1 ms objective: every real query violates -> burn
           "spark.rapids.sql.serve.slo.p99Ms.gold": "1",
           # generous objective: no violation for this tenant
           "spark.rapids.sql.serve.slo.p99Ms.lead": "3600000"})
    try:
        with ServeClient(srv.port, tenant="gold") as c:
            assert c.collect(Q1S) == oracle
            assert c.collect(Q1S) == oracle
        with ServeClient(srv.port, tenant="lead") as c:
            assert c.collect(Q1S) == oracle
        time.sleep(1.1)  # step past the tracker's 1 s result cache
        st = srv.stats()
        slo = st["slo"]
        assert slo["gold"]["objectiveP99Ms"] == 1
        assert slo["gold"]["windowQueries"] == 2
        assert slo["gold"]["violations"] == 2
        assert slo["gold"]["burnRatio"] == 1.0
        assert slo["gold"]["observedP99Ms"] > 1
        assert slo["lead"]["violations"] == 0
        # Prometheus families (scrape parses; family names are
        # SERVER_FAMILY_HELP entries by the prom-family lint)
        text = srv.metrics_text()
        assert 'srt_slo_objective_p99_ms{tenant="gold"} 1' in text
        assert 'srt_slo_burn_ratio{tenant="gold"} 1.0' in text
        assert 'srt_slo_window_violations{tenant="lead"} 0' in text
        # the sloBurn bundle fired (rate limit 0)
        assert TEL.engine().drain(timeout=15)
        bundles = glob.glob(os.path.join(tel_dir,
                                         "bundle-*-sloBurn.json"))
        assert bundles
        with open(bundles[0]) as f:
            b = json.load(f)
        assert b["condition"]["tenant"] == "gold"
        assert b["condition"]["observedP99Ms"] > 1
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# Telemetry artifact retention (satellite)
# ---------------------------------------------------------------------------

def test_bundle_retention_prunes_oldest_first(tmp_path):
    from spark_rapids_tpu.conf import TpuConf
    tel_dir = str(tmp_path / "tel")
    os.makedirs(tel_dir)
    # pre-existing ring dumps count toward retention and are OLDER
    # than every bundle -> pruned first
    for i in range(2):
        p = os.path.join(tel_dir, f"trace-ring-1-{i:05d}.json")
        with open(p, "w") as f:
            f.write("{}")
        past = time.time() - 1000 + i
        os.utime(p, (past, past))
    eng = TEL.engine()
    eng.configure(TpuConf({
        "spark.rapids.sql.telemetry.dir": tel_dir,
        "spark.rapids.sql.telemetry.maxBundles": "3",
        "spark.rapids.sql.telemetry.triggerMinIntervalS": "0"}))
    for i in range(5):
        assert eng._maybe_fire("slowQuery", {"i": i},
                               out_dir=tel_dir, min_interval=0.0)
        assert eng.drain(timeout=15)  # prune runs per write
    files = sorted(os.listdir(tel_dir))
    assert len(files) == 3, files
    # oldest-first: the ring dumps and the earliest bundles are gone,
    # the NEWEST bundles survive
    assert all(f.startswith("bundle-") for f in files)
    assert eng.stats()["pruned"] == 4
    # server stats surface the pruned count
    assert eng.stats()["fired"]["slowQuery"] == 5


def test_bundle_retention_byte_bound(tmp_path):
    from spark_rapids_tpu.conf import TpuConf
    tel_dir = str(tmp_path / "tel")
    eng = TEL.engine()
    eng.configure(TpuConf({
        "spark.rapids.sql.telemetry.dir": tel_dir,
        "spark.rapids.sql.telemetry.maxBundles": "0",
        "spark.rapids.sql.telemetry.maxBundleBytes": "1",
        "spark.rapids.sql.telemetry.triggerMinIntervalS": "0"}))
    for i in range(6):
        assert eng._maybe_fire("retryStorm", {"i": i},
                               out_dir=tel_dir, min_interval=0.0)
    assert eng.drain(timeout=15)
    # a 1-byte bound prunes everything but (at most) the bundle whose
    # write raced the sweep — the point is the BYTE bound engages
    assert len(os.listdir(tel_dir)) <= 1
    assert eng.stats()["pruned"] >= 5


# ---------------------------------------------------------------------------
# Prometheus scrape racing graceful drain (satellite)
# ---------------------------------------------------------------------------

def _parse_exposition(text):
    """Minimal Prometheus text parser: {family: {sample_key: value}};
    asserts completeness (every sample's family declared with HELP +
    TYPE before its samples, no partial tail line)."""
    assert text.endswith("\n"), "truncated exposition"
    declared = {}
    samples = {}
    for ln in text.splitlines():
        if ln.startswith("# HELP "):
            declared.setdefault(ln.split()[2], set()).add("help")
        elif ln.startswith("# TYPE "):
            parts = ln.split()
            declared.setdefault(parts[2], set()).add("type")
            samples.setdefault(parts[2], {})[
                "__type__"] = parts[3]
        elif ln and not ln.startswith("#"):
            name_lab, _, val = ln.rpartition(" ")
            fam = name_lab.split("{", 1)[0]
            assert fam in declared and declared[fam] == \
                {"help", "type"}, f"undeclared family in {ln!r}"
            float(val)  # parseable
            samples.setdefault(fam, {})[name_lab] = float(val)
    return samples


def test_prometheus_scrape_racing_graceful_drain(data_dir, oracle):
    """A scrape racing shutdown() must return a complete, parseable
    exposition with MONOTONE counters — never an error or a partial
    family."""
    from spark_rapids_tpu.serve import ServeClient
    from spark_rapids_tpu.serve.client import ServeError
    srv = _server(data_dir)
    started = threading.Event()
    release = threading.Event()
    _hook_parked_after_planning(srv, "slow", started, release)
    scrapes = []
    errors = []
    stop = threading.Event()

    def scraper():
        while not stop.is_set():
            try:
                scrapes.append(srv.metrics_text())
            except Exception as e:  # noqa: BLE001 - the assertion
                errors.append(repr(e))
            time.sleep(0.01)

    def submit():
        try:
            with ServeClient(srv.port, tenant="slow") as c:
                c.sql(Q1S)
        except ServeError:
            pass  # drain cancels the straggler

    try:
        with ServeClient(srv.port, tenant="warm") as c:
            assert c.collect(Q1S) == oracle
        t = threading.Thread(target=submit)
        t.start()
        assert started.wait(timeout=60)
        sc = threading.Thread(target=scraper)
        sc.start()
        time.sleep(0.05)
        assert srv.shutdown(timeout=0.5) is True
        time.sleep(0.05)
        stop.set()
        sc.join(timeout=30)
        t.join(timeout=30)
    finally:
        release.set()
        stop.set()
        srv.shutdown(timeout=5)
    assert not errors, errors
    assert len(scrapes) >= 2, "scrapes must keep succeeding mid-drain"
    prev = None
    for text in scrapes:
        fams = _parse_exposition(text)
        if prev is not None:
            for fam, entries in prev.items():
                if entries.get("__type__") != "counter":
                    continue
                for key, v in entries.items():
                    if key == "__type__" or fam not in fams:
                        continue
                    cur = fams[fam].get(key)
                    if cur is not None:
                        assert cur >= v, \
                            f"counter {key} went backwards mid-drain"
        prev = fams


# ---------------------------------------------------------------------------
# tools history CLI contract
# ---------------------------------------------------------------------------

def test_tools_history_cli_contract(tmp_path, capsys):
    from spark_rapids_tpu.tools import _main as tools_main
    # missing path -> error, exit 1
    assert tools_main(["history", str(tmp_path / "nope")]) == 1
    assert "no such history" in capsys.readouterr().out
    # empty store -> a normal answer, exit 0
    d = tmp_path / "hist"
    d.mkdir()
    assert tools_main(["history", str(d)]) == 0
    assert "no history records" in capsys.readouterr().out
    # populated: table + filters + json
    store = H.HistoryStore(str(d), 1 << 20, 14)
    t0 = time.time()
    for i in range(4):
        store.append(_rec(t0 - 7200 + i * 3600, tenant="acme",
                          wall=0.2))
    assert tools_main(["history", str(d)]) == 0
    out = capsys.readouterr().out
    assert "acme" in out and H.sig_digest("a" * 40) in out
    assert tools_main(["history", str(d), "--since", "5400",
                       "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["records"] == 3  # ts -7200 filtered, -3600/0/+3600 kept
    assert tools_main(["history", str(d), "--tenant", "nobody"]) == 0
    assert "no history records" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# Lint fixtures: history-field
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


def test_lint_history_field_bad_and_good(tmp_path):
    from spark_rapids_tpu.lint import LintConfig, run_lint
    root = _lint_tree(tmp_path, {
        "spark_rapids_tpu/telemetry/history.py": """
            HISTORY_FIELD_CATALOG = {
                "goodField": "a documented field",
                "ts": "timestamp",
                "bad_snake_case": "violates naming",
            }

            def build(x):
                rec = {"goodField": 1, "rogueField": 2}
                rec["ts"] = 3
                rec["rogueStore"] = 4
                other = {"notRec": 5}  # unchecked: not the rec dict
                return rec, other
        """})
    r = run_lint(root, LintConfig(check_docs=False))
    msgs = [f.message for f in r.findings if f.rule == "history-field"]
    assert len(msgs) == 3, r.findings
    assert any("rogueField" in m for m in msgs)
    assert any("rogueStore" in m for m in msgs)
    assert any("bad_snake_case" in m for m in msgs)
    # (the real package's zero-findings gate in test_lint.py now
    # covers history-field too)

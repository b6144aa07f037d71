"""Adaptive-query-execution corpus (docs/adaptive.md): unit coverage of
the replan calculus (exchange stats, coalesce grouping, skew plans, the
batch-fusion key), the skewed-join property sweep (hot key at 10x/100x
the median, nulls in join keys, empty partitions after coalesce)
asserting bit-identity adaptive-on vs adaptive-off vs the CPU oracle,
broadcast demotion and partition coalescing end-to-end, plan-signature
invariance under adaptive/batchFusion confs, the doctor's
``skewedShuffle`` verdict, and same-signature batch fusion under the
server (one admission slot, per-member billing, member-only eviction on
cancel)."""

from __future__ import annotations

import threading
import time

import pytest

from spark_rapids_tpu import adaptive as A
from spark_rapids_tpu import retry as R
from spark_rapids_tpu import trace as TR
from spark_rapids_tpu.metrics import registry_snapshot
from spark_rapids_tpu.sql.session import TpuSparkSession

from tests.datagen import (IntegerGen, KeyStringGen, LongGen, SmallIntGen,
                           gen_batch)
from tests.harness import _rows, _sort_key
from tests.test_device_join import _STREAMED_JOINS, _one_build_batch_join


@pytest.fixture(autouse=True)
def _fresh_state():
    TR.reset_tracing()
    R.reset_fault_injection()
    yield
    TR.reset_tracing()
    R.reset_fault_injection()


# ---------------------------------------------------------------------------
# Unit: the replan calculus
# ---------------------------------------------------------------------------

def test_exchange_stats_median_ignores_empty_partitions():
    st = A.ExchangeStats((0, 100, 0, 300, 100), (0, 10, 0, 30, 10))
    assert st.num_partitions == 5
    assert st.total_bytes == 500
    assert st.max_bytes == 300
    # median over NON-EMPTY partitions {100, 100, 300} = 100, not the
    # zero-dragged median over all five
    assert st.median_bytes == 100
    assert st.skew_ratio == 3.0


def test_exchange_stats_all_empty():
    st = A.ExchangeStats((0, 0), (0, 0))
    assert st.median_bytes == 0
    assert st.skew_ratio == 0.0
    assert A.skew_splits(st, 4.0) == {}


def test_skew_splits_thresholds_and_cap():
    st = A.ExchangeStats((10, 10, 10, 200), (1, 1, 1, 20))
    assert st.median_bytes == 10
    # 200/10 = 20x the median -> capped at MAX_SKEW_SPLITS
    assert A.skew_splits(st, 4.0) == {3: A.MAX_SKEW_SPLITS}
    # a 5x partition aims back at the median: ceil(50/10) = 5 slices
    st2 = A.ExchangeStats((10, 10, 10, 50), (1, 1, 1, 5))
    assert A.skew_splits(st2, 4.0) == {3: 5}
    # at or under the factor: no replan
    assert A.skew_splits(st2, 5.0) == {}
    # factor <= 0 disables the pass entirely
    assert A.skew_splits(st, 0.0) == {}
    assert A.skew_splits(st, -1.0) == {}


def test_coalesce_groups_adjacent_up_to_target():
    assert A.coalesce_groups((10, 10, 10, 100), 40) == [[0, 1, 2], [3]]
    # an oversize partition still gets its own group (never dropped)
    assert A.coalesce_groups((100, 5, 5), 40) == [[0], [1, 2]]
    # already-fat partitions pass through unmerged
    assert A.coalesce_groups((50, 50), 40) == [[0], [1]]
    assert A.coalesce_groups((), 40) == []


def test_slice_groups_contiguous_and_bounded():
    assert A.slice_groups([5] * 6, 3) == [[0, 1], [2, 3], [4, 5]]
    # never more than k groups even with pathological weights
    for k in (1, 2, 3, 7):
        gs = A.slice_groups([1, 1, 1, 100, 1], k)
        assert len(gs) <= k
        assert [i for g in gs for i in g] == list(range(5))
    # k > n clamps to one item per group
    assert A.slice_groups([3, 3], 16) == [[0], [1]]
    assert A.slice_groups([], 4) == [[]]


def test_fusion_key_normalizes_literals():
    a = A.fusion_key("SELECT a FROM t WHERE b = 5 AND c = 'x'")
    b = A.fusion_key("SELECT a FROM t  WHERE b = 17 AND c = 'yy'")
    assert a[0] == b[0] == "SELECT a FROM t WHERE b = ? AND c = ?"
    assert a[1] == ("'x'", "5")
    assert b[1] == ("'yy'", "17")
    # identical text => identical binding vector (one execution)
    assert A.fusion_key("SELECT 1") == A.fusion_key("SELECT  1")
    # embedded '' quote stays inside ONE string literal
    t, lits = A.fusion_key("SELECT * FROM t WHERE s = 'it''s' AND x = 2")
    assert lits == ("'it''s'", "2")
    # numbers inside identifiers/qualified names are NOT literals
    t2, lits2 = A.fusion_key("SELECT col2 FROM t2 WHERE col2 > 9")
    assert "col2" in t2 and lits2 == ("9",)


# ---------------------------------------------------------------------------
# Engine: skewed-join sweep, broadcast demotion, coalesce
# ---------------------------------------------------------------------------

def _collect(df_fn, conf):
    """Run one DataFrame lambda in its own session; returns
    (sorted rows, summed plan metrics)."""
    spark = TpuSparkSession({k: str(v) for k, v in conf.items()})
    try:
        spark.start_capture()
        batch = df_fn(spark)._execute()
        rows = sorted(_rows(batch.to_pydict()), key=_sort_key)
        mets = registry_snapshot(spark.get_captured_plans())["metrics"]
    finally:
        spark.stop()
    return rows, mets


_SKEW_BASE = {
    "spark.rapids.sql.batchSizeRows": "256",
    # -1 disables BOTH broadcast paths (adaptive.autoBroadcastBytes
    # inherits it), so the skew-split replan is the one that can fire
    "spark.rapids.sql.autoBroadcastJoinThreshold": "-1",
    "spark.rapids.sql.shuffle.devicePartitions": "4",
}


def _skew_frames(spark, hot_mult, with_nulls):
    """A shuffled-join pair whose left side carries ONE hot key at
    ``hot_mult`` x the median partition size (48 base keys spread the
    other partitions evenly); optional None join keys on both sides."""
    rep = 12
    lk = [100 + (i % 48) for i in range(48 * rep)]
    hot_n = hot_mult * rep * 12  # ~hot_mult x the per-partition base
    lk += [7] * hot_n
    lv = list(range(len(lk)))
    rk = list(range(100, 148)) * 2 + [7, 7]
    rw = [i * 10 for i in range(len(rk))]
    if with_nulls:
        lk += [None] * 25
        lv += list(range(25))
        rk += [None] * 5
        rw += list(range(5))
    left = spark.createDataFrame({"k": lk, "v": lv}, "k int, v long",
                                 num_partitions=3)
    right = spark.createDataFrame({"k2": rk, "w": rw}, "k2 int, w long",
                                  num_partitions=2)
    return left, right


@pytest.mark.parametrize("hot_mult", [10, 100], ids=["10x", "100x"])
@pytest.mark.parametrize("jt", ["inner", "left"])
@pytest.mark.parametrize("with_nulls", [False, True],
                         ids=["dense", "nullkeys"])
def test_skewed_join_sweep_bit_identical(hot_mult, jt, with_nulls):
    """The satellite property sweep: adaptive-on, adaptive-off and the
    CPU oracle must agree bit-for-bit on skewed shapes, the adaptive
    run must actually have split (aqeSkewSplits > 0), and the clean
    adaptive run takes zero retries."""
    if hot_mult == 100 and with_nulls:
        pytest.skip("covered by the 10x null sweep; 100x adds rows, "
                    "not a new null path")

    def fn(s):
        l, r = _skew_frames(s, hot_mult, with_nulls)
        return l.join(r, l["k"] == r["k2"], jt)

    cpu, _ = _collect(fn, {**_SKEW_BASE,
                           "spark.rapids.sql.enabled": "false"})
    off, m_off = _collect(fn, {**_SKEW_BASE,
                               "spark.rapids.sql.enabled": "true",
                               "spark.rapids.sql.adaptive.enabled":
                               "false"})
    on, m_on = _collect(fn, {**_SKEW_BASE,
                             "spark.rapids.sql.enabled": "true"})

    assert on == off == cpu, (
        f"adaptive replan changed results ({jt}, {hot_mult}x, "
        f"nulls={with_nulls})")
    assert m_on.get("aqeSkewSplits", 0) > 0, m_on
    assert m_on.get("aqeReplans", 0) > 0
    assert m_off.get("aqeSkewSplits", 0) == 0
    assert m_on.get("retryCount", 0) == 0
    assert m_on.get("splitRetryCount", 0) == 0


def test_skewed_join_injected_oom_contrast():
    """The retry contrast from the acceptance bar, with injection
    standing in for a real HBM OOM storm (the CPU backend spills
    instead of raising, so an organic monolithic-partition OOM is not
    reproducible here): the UNADAPTIVE run retries under an injected
    OOM schedule and stays correct; the adaptive run of the same shape
    with no injection completes with retryCount == 0."""
    def fn(s):
        l, r = _skew_frames(s, 10, False)
        return l.join(r, l["k"] == r["k2"], "inner")

    cpu, _ = _collect(fn, {**_SKEW_BASE,
                           "spark.rapids.sql.enabled": "false"})
    off, m_off = _collect(fn, {
        **_SKEW_BASE,
        "spark.rapids.sql.enabled": "true",
        "spark.rapids.sql.adaptive.enabled": "false",
        "spark.rapids.sql.test.injectOOM": "2:2",
        "spark.rapids.sql.retry.backoffMs": "5",
        "spark.rapids.sql.retry.maxBackoffMs": "20"})
    R.reset_fault_injection()
    on, m_on = _collect(fn, {**_SKEW_BASE,
                             "spark.rapids.sql.enabled": "true"})

    assert m_off.get("retryCount", 0) > 0, m_off
    assert off == cpu, "retried unadaptive run diverged"
    assert on == cpu, "adaptive run diverged"
    assert m_on.get("retryCount", 0) == 0
    assert m_on.get("aqeSkewSplits", 0) > 0


def test_broadcast_demotion_fires_and_matches():
    """A shuffled join whose realized build side is tiny demotes to
    broadcast at runtime (aqeBroadcastFlip) and stays bit-identical to
    both the unadaptive plan and the CPU oracle."""
    def fn(s):
        l = s.createDataFrame(
            gen_batch([("k", SmallIntGen()), ("a", IntegerGen())],
                      400, 11), num_partitions=2)
        r = s.createDataFrame(
            gen_batch([("k2", SmallIntGen()), ("b", LongGen())],
                      60, 12), num_partitions=2)
        return l.join(r.repartition(3), l["k"] == r["k2"], "inner")

    cpu, _ = _collect(fn, {"spark.rapids.sql.enabled": "false"})
    off, m_off = _collect(fn, {"spark.rapids.sql.enabled": "true",
                               "spark.rapids.sql.adaptive.enabled":
                               "false"})
    on, m_on = _collect(fn, {"spark.rapids.sql.enabled": "true"})

    assert on == off == cpu
    assert m_on.get("aqeBroadcastFlip", 0) >= 1, m_on
    assert m_on.get("aqeReplans", 0) >= 1
    assert m_off.get("aqeBroadcastFlip", 0) == 0


@pytest.mark.parametrize("family", sorted(_STREAMED_JOINS))
def test_demoted_sparse_build_is_held_at_its_rows(family, monkeypatch):
    """A shuffled join whose build side is ONE batch of 4,096 lanes with
    40 rows active after a filter: its bytes are over the threshold, so
    the demotion reads the active rows to decide, and the build side it
    then broadcasts is compacted to those rows' bucket (64 lanes): the
    stream is probed max(batchSizeRows, 64) rows at a time. A full outer
    join is never demoted and nothing reads its count, so its build side
    stays as it is. Adaptive on, off and the CPU oracle agree."""
    from spark_rapids_tpu.exec import join as J
    from spark_rapids_tpu.sql import functions as F
    jt = _STREAMED_JOINS[family][0]
    fn = _one_build_batch_join(family, 4096, 40,
                               lambda r, n: r.filter(F.col("k2") < n))
    real, goals = J.stream_chunks, []

    def recording(handles, goal):
        goals.append(goal)
        return real(handles, goal)
    monkeypatch.setattr(J, "stream_chunks", recording)
    conf = {"spark.rapids.sql.batchSizeRows": "128",
            # 40 rows of two longs are under it, 4,096 lanes are not
            "spark.rapids.sql.autoBroadcastJoinThreshold": "1000"}
    cpu, _ = _collect(fn, {**conf, "spark.rapids.sql.enabled": "false"})
    off, m_off = _collect(fn, {**conf, "spark.rapids.sql.enabled": "true",
                               "spark.rapids.sql.adaptive.enabled":
                               "false"})
    goals.clear()
    on, m_on = _collect(fn, {**conf, "spark.rapids.sql.enabled": "true"})
    assert on == off == cpu
    assert m_off.get("joinBuildCompactions", 0) == 0
    demoted = jt != "full"
    assert m_on.get("joinDemotedCount", 0) == int(demoted), m_on
    assert m_on.get("joinBuildCompactions", 0) == int(demoted), m_on
    assert m_on.get("joinBuildLanesDropped", 0) == (
        4096 - 64 if demoted else 0)
    assert goals == [128 if demoted else 4096], goals


def test_coalesce_merges_undersized_partitions():
    """An aggregation over a many-partition exchange with mostly-empty
    partitions coalesces toward targetPartitionBytes (empty partitions
    disappear into their neighbours) without changing results."""
    conf = {"spark.rapids.sql.batchSizeRows": "512",
            "spark.rapids.sql.shuffle.devicePartitions": "8"}

    def fn(s):
        # 3 distinct groups hashed over 8 partitions: most are EMPTY
        df = s.createDataFrame(
            {"g": [i % 3 for i in range(600)],
             "v": list(range(600))}, "g int, v long",
            num_partitions=4)
        from spark_rapids_tpu.sql import functions as F
        return df.groupBy("g").agg(F.sum("v").alias("sv"))

    cpu, _ = _collect(fn, {**conf, "spark.rapids.sql.enabled": "false"})
    off, m_off = _collect(fn, {**conf,
                               "spark.rapids.sql.enabled": "true",
                               "spark.rapids.sql.adaptive.enabled":
                               "false"})
    on, m_on = _collect(fn, {**conf, "spark.rapids.sql.enabled": "true"})

    assert on == off == cpu
    assert m_on.get("aqeCoalescedPartitions", 0) > 0, m_on
    assert m_off.get("aqeCoalescedPartitions", 0) == 0


# ---------------------------------------------------------------------------
# Shared parquet data (signature / doctor / serving tests)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("adaptive_data")
    gen = TpuSparkSession({"spark.rapids.sql.enabled": "false"})
    try:
        li = gen.createDataFrame(gen_batch(
            [("flag", KeyStringGen(cardinality=3)),
             ("status", SmallIntGen()), ("qty", LongGen()),
             ("price", IntegerGen())], 2000, 71), num_partitions=4)
        li.write.mode("overwrite").parquet(str(d / "lineitem"))
    finally:
        gen.stop()
    return d


QA = """
SELECT status, sum(qty) AS sq, count(*) AS c
FROM lineitem WHERE qty % 7 != 0
GROUP BY status ORDER BY status
"""


def _run_sql(data_dir, sql, **conf):
    spark = TpuSparkSession({"spark.rapids.sql.enabled": "true",
                             **{k: str(v) for k, v in conf.items()}})
    try:
        spark.read.parquet(str(data_dir / "lineitem")) \
            .createOrReplaceTempView("lineitem")
        return [tuple(r) for r in spark.sql(sql)._execute().rows()]
    finally:
        spark.stop()


def test_plan_signature_excludes_adaptive_and_fusion_confs(
        data_dir, tmp_path):
    """Satellite: adaptive.* and serve.batchFusion.* confs gate RUNTIME
    behaviour, not plan shape — runs differing only in them must land
    on ONE history signature (shared baselines/quarantine/doctor
    attribution), while a real planning conf still splits it."""
    from spark_rapids_tpu.telemetry import history as H
    hdir = str(tmp_path / "hist")
    base = {"spark.rapids.sql.telemetry.history.dir": hdir,
            "spark.rapids.sql.planCache.enabled": "true"}

    _run_sql(data_dir, QA, **base)
    _run_sql(data_dir, QA, **base,
             **{"spark.rapids.sql.adaptive.enabled": "false",
                "spark.rapids.sql.adaptive.skewFactor": "9.5",
                "spark.rapids.sql.adaptive.autoBroadcastBytes": "123",
                "spark.rapids.sql.adaptive.targetPartitionBytes": "1m",
                "spark.rapids.sql.serve.batchFusion.enabled": "false",
                "spark.rapids.sql.serve.batchFusion.windowMs": "99",
                "spark.rapids.sql.serve.batchFusion.maxBatch": "4"})
    _run_sql(data_dir, QA, **base,
             **{"spark.rapids.sql.batchSizeRows": "333"})

    recs = H.read_records(hdir)
    assert len(recs) == 3
    sigs = [r["signature"] for r in recs]
    assert sigs[0] == sigs[1], (
        "adaptive/batchFusion confs must not change the signature")
    assert sigs[0] != sigs[2], (
        "a planning conf (batchSizeRows) must change the signature")


def test_doctor_skewed_shuffle_verdict(tmp_path):
    """The doctor reads the exchange-stat metrics out of the profile
    artifact and raises a ``skewedShuffle`` verdict when one partition
    dwarfs the median; the adaptive-off run leaves aqeActions empty so
    the evidence points at the adaptive confs."""
    from spark_rapids_tpu.telemetry import history as H
    from spark_rapids_tpu.telemetry.doctor import (diagnose,
                                                   format_diagnosis)
    hdir = str(tmp_path / "hist")

    def fn(s):
        l, r = _skew_frames(s, 10, False)
        return l.join(r, l["k"] == r["k2"], "inner")

    spark = TpuSparkSession({k: str(v) for k, v in {
        **_SKEW_BASE,
        "spark.rapids.sql.enabled": "true",
        "spark.rapids.sql.adaptive.enabled": "false",
        "spark.rapids.sql.telemetry.history.dir": hdir,
        "spark.rapids.sql.profile.enabled": "true",
        "spark.rapids.sql.profile.dir": str(tmp_path / "prof"),
    }.items()})
    try:
        fn(spark)._execute()
    finally:
        spark.stop()

    recs = H.read_records(hdir)
    assert len(recs) == 1
    rec = recs[0]
    assert "aqeActions" not in rec, (
        "adaptive-off run must not record aqeActions")

    d = diagnose(hdir, str(rec["queryId"]))
    assert d.get("error") is None
    assert d["exchangeSkew"].get("ratio", 0) >= 4.0, d["exchangeSkew"]
    classes = [v["class"] for v in d["verdicts"]]
    assert "skewedShuffle" in classes, d["verdicts"]
    sv = next(v for v in d["verdicts"] if v["class"] == "skewedShuffle")
    assert any("adaptive" in e for e in sv["evidence"]), sv
    assert "skewedShuffle" in format_diagnosis(d)


def test_history_records_aqe_actions(tmp_path):
    """The adaptive-on run of the same skewed shape lands its replan
    counters in the history record's aqeActions field, and the doctor
    evidence flips to 'pre-split'."""
    from spark_rapids_tpu.telemetry import history as H
    from spark_rapids_tpu.telemetry.doctor import diagnose
    hdir = str(tmp_path / "hist")

    def fn(s):
        l, r = _skew_frames(s, 10, False)
        return l.join(r, l["k"] == r["k2"], "inner")

    spark = TpuSparkSession({k: str(v) for k, v in {
        **_SKEW_BASE,
        "spark.rapids.sql.enabled": "true",
        "spark.rapids.sql.telemetry.history.dir": hdir,
        "spark.rapids.sql.profile.enabled": "true",
        "spark.rapids.sql.profile.dir": str(tmp_path / "prof"),
    }.items()})
    try:
        fn(spark)._execute()
    finally:
        spark.stop()

    rec = H.read_records(hdir)[0]
    acts = rec.get("aqeActions")
    assert acts and acts.get("aqeSkewSplits", 0) > 0, rec
    assert acts.get("aqeReplans", 0) > 0

    d = diagnose(hdir, str(rec["queryId"]))
    assert d["aqeActions"] == acts
    if any(v["class"] == "skewedShuffle" for v in d["verdicts"]):
        sv = next(v for v in d["verdicts"]
                  if v["class"] == "skewedShuffle")
        assert any("pre-split" in e for e in sv["evidence"]), sv


# ---------------------------------------------------------------------------
# Serving: same-signature batch fusion
# ---------------------------------------------------------------------------

def _server(data_dir, **conf):
    from spark_rapids_tpu.serve import QueryServer
    base = {"spark.rapids.sql.enabled": "true",
            "spark.rapids.sql.batchSizeRows": "512"}
    base.update({k: str(v) for k, v in conf.items()})
    srv = QueryServer(base).start()
    srv.register_view("lineitem", str(data_dir / "lineitem"))
    return srv


def _park(srv, slow_tenant, started, release):
    """Park ``slow_tenant`` queries at a lifecycle checkpoint between
    admission and planning (the test_lifecycle hook)."""
    from spark_rapids_tpu import lifecycle as LC
    orig_session = srv._session

    def hook(tenant):
        s = orig_session(tenant)
        if tenant == slow_tenant and not getattr(s, "_park_hook", None):
            orig_sql = s.sql

            def parked_sql(text):
                started.set()
                end = time.monotonic() + 60
                while not release.is_set() and time.monotonic() < end:
                    LC.checkpoint("batch")
                    time.sleep(0.01)
                return orig_sql(text)

            s._park_hook = True
            s.sql = parked_sql
        return s

    srv._session = hook


def _variant(i):
    return ("SELECT status, sum(qty) AS sq, count(*) AS c "
            f"FROM lineitem WHERE qty % 7 != {i} "
            "GROUP BY status ORDER BY status")


def test_batch_fusion_same_signature_burst(data_dir):
    """16 same-template queries (distinct literal bindings) blocked
    behind one busy slot fuse into ONE admission slot and split results
    per requester, bit-identical to serial execution; every member is
    billed on its own tenant ledger."""
    from spark_rapids_tpu.serve import ServeClient
    oracles = {i: _run_sql(data_dir, _variant(i)) for i in range(3)}

    srv = _server(
        data_dir,
        **{"spark.rapids.sql.serve.maxConcurrentQueries": 1,
           "spark.rapids.sql.serve.maxQueued": 64,
           "spark.rapids.sql.serve.maxConcurrentPerTenant": 32,
           "spark.rapids.sql.serve.batchFusion.windowMs": "2000",
           "spark.rapids.sql.serve.batchFusion.maxBatch": "16"})
    started, release = threading.Event(), threading.Event()
    _park(srv, "slow", started, release)
    errors: list = []
    results: dict = {}

    def blocker():
        try:
            with ServeClient(srv.port, tenant="slow") as c:
                c.collect(_variant(0))
        except Exception as e:  # noqa: BLE001
            errors.append(("blocker", repr(e)))

    def worker(i):
        try:
            with ServeClient(srv.port, tenant=f"t{i % 4}") as c:
                results[i] = c.collect(_variant(i % 3))
        except Exception as e:  # noqa: BLE001
            errors.append((i, repr(e)))

    try:
        bt = threading.Thread(target=blocker)
        bt.start()
        assert started.wait(timeout=60)
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(16)]
        for t in threads:
            t.start()
        # the window closes early at maxBatch=16; free the slot once
        # everyone has had time to join the batch
        time.sleep(0.5)
        release.set()
        bt.join(timeout=120)
        for t in threads:
            t.join(timeout=120)
        assert not errors, errors
        assert len(results) == 16
        for i, rows in results.items():
            assert rows == oracles[i % 3], (
                f"member {i} diverged from serial execution")
        st = srv.stats()
        bf = st.get("batchFusion")
        assert bf is not None
        assert bf["fusedQueries"] >= 16, bf
        assert bf["fusedBatches"] >= 1
        # blocker + every fused member is billed admitted exactly once
        assert st["admission"]["admitted"] == 17, st["admission"]
        assert st["admission"]["rejected"] == 0
    finally:
        release.set()
        srv.shutdown()


def test_batch_fusion_cancel_evicts_only_member(data_dir):
    """Satellite: cancelling ONE fused member while the batch is queued
    evicts that member alone — survivors still execute, bit-identical,
    and the evicted member is neither billed admitted nor counted in
    the fused totals."""
    from spark_rapids_tpu.serve import ServeClient
    from spark_rapids_tpu.serve.client import ServeCancelled
    oracle = _run_sql(data_dir, _variant(1))

    srv = _server(
        data_dir,
        **{"spark.rapids.sql.serve.maxConcurrentQueries": 1,
           "spark.rapids.sql.serve.maxQueued": 64,
           "spark.rapids.sql.serve.maxConcurrentPerTenant": 32,
           "spark.rapids.sql.serve.batchFusion.windowMs": "800",
           "spark.rapids.sql.serve.batchFusion.maxBatch": "16"})
    started, release = threading.Event(), threading.Event()
    _park(srv, "slow", started, release)
    errors: list = []
    out: dict = {}

    def blocker():
        try:
            with ServeClient(srv.port, tenant="slow") as c:
                c.collect(_variant(0))
        except Exception as e:  # noqa: BLE001
            errors.append(("blocker", repr(e)))

    def member(name):
        try:
            with ServeClient(srv.port, tenant=name) as c:
                batch, _hdr = c.sql(_variant(1),
                                    query_id=f"m-{name}")
                out[name] = [tuple(r) for r in batch.rows()]
        except ServeCancelled as e:
            out[name] = ("cancelled", e.reason)
        except Exception as e:  # noqa: BLE001
            errors.append((name, repr(e)))

    try:
        bt = threading.Thread(target=blocker)
        bt.start()
        assert started.wait(timeout=60)
        threads = [threading.Thread(target=member, args=(n,))
                   for n in ("ta", "tb", "tc")]
        for t in threads:
            t.start()
        time.sleep(0.25)  # inside the 800ms fusion window
        with ServeClient(srv.port) as cc:
            assert cc.cancel(query_id="m-tb", tenant="tb") == 1
        release.set()
        bt.join(timeout=120)
        for t in threads:
            t.join(timeout=120)
        assert not errors, errors
        assert out.get("tb") == ("cancelled", "cancel"), out
        assert out.get("ta") == oracle
        assert out.get("tc") == oracle
        st = srv.stats()
        assert st["batchFusion"]["fusedQueries"] == 2, st["batchFusion"]
        # blocker + ta + tc admitted; the evicted tb never billed
        assert st["admission"]["admitted"] == 3, st["admission"]
        assert st["queriesCancelled"] == 1
    finally:
        release.set()
        srv.shutdown()


def test_batch_fusion_disabled_conf(data_dir):
    """batchFusion.enabled=false removes the coordinator: stats carry
    no batchFusion block and queries run the unfused path."""
    from spark_rapids_tpu.serve import ServeClient
    oracle = _run_sql(data_dir, _variant(1))
    srv = _server(
        data_dir,
        **{"spark.rapids.sql.serve.batchFusion.enabled": "false"})
    try:
        with ServeClient(srv.port, tenant="a") as c:
            assert c.collect(_variant(1)) == oracle
        st = srv.stats()
        assert "batchFusion" not in st
    finally:
        srv.shutdown()

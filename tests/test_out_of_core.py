"""Planned out-of-core execution (docs/out_of_core.md): budget-oracle
partition planning, spill-backed partitioned joins/aggs, recursive
re-partitioning, and the degradation ladder.

The acceptance contract: a working set far over the device budget
streams through partitioned buckets BIT-IDENTICAL to the in-memory
path with retryCount == 0 — the retry protocol stays a backstop, never
the steady state — and ``tools doctor`` classifies a correctly-planned
big-input run as ``biggerInput``, not ``retrySpill``.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from spark_rapids_tpu import retry as R
from spark_rapids_tpu.conf import TpuConf
from spark_rapids_tpu.memory import get_budget_oracle
from spark_rapids_tpu.metrics import registry_snapshot
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.sql.session import TpuSparkSession

from tests.harness import assert_tpu_and_cpu_equal_collect

NO_BCAST = {"spark.rapids.sql.autoBroadcastJoinThreshold": "-1"}
TINY_BUDGET = {"spark.rapids.sql.memory.deviceBudgetBytes": "8192"}

_OOC_KEYS = ("plannedPartitions", "plannedOutOfCoreEscalations",
             "budgetPressurePeak", "retryCount", "splitRetryCount")


@pytest.fixture(autouse=True)
def _fresh_injection():
    R.reset_fault_injection()
    yield
    R.reset_fault_injection()


def _run_counters(df_fn, conf):
    """Run once on the TPU engine and return the plan counter deltas
    the out-of-core acceptance asserts over."""
    spark = TpuSparkSession({"spark.rapids.sql.enabled": "true", **conf})
    try:
        spark.start_capture()
        df_fn(spark)._execute()
        vals = registry_snapshot(
            plans=spark.get_captured_plans())["metrics"]
    finally:
        spark.stop()
    return {k: int(vals.get(k, 0)) for k in _OOC_KEYS}


def _join_data(spark, n=1000, seed=5, nulls=False, strings=False,
               skew=False, parts=3):
    rng = np.random.RandomState(seed)
    lk = rng.randint(0, 300, n)
    rk = rng.randint(0, 300, n)
    if skew:  # one hot key owns most rows: rehashing cannot split it
        lk[: n * 9 // 10] = 7
        rk[: n // 2] = 7
    def col(keys):
        out = []
        for i, v in enumerate(keys):
            if nulls and i % 11 == 0:
                out.append(None)
            elif strings:
                out.append(f"k{int(v):03d}")
            else:
                out.append(int(v))
        return out
    l = spark.createDataFrame(
        {"k": col(lk), "v": [int(i) for i in range(n)]},
        num_partitions=parts)
    r = spark.createDataFrame(
        {"k2": col(rk), "w": [int(i * 3) for i in range(n)]},
        num_partitions=parts)
    return l, r


# ---------------------------------------------------------------------------
# Partitioned join: bit-identical to the in-memory oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("jt", ["inner", "left", "leftsemi", "full"])
def test_ooc_join_parity(jt):
    def fn(s):
        l, r = _join_data(s, nulls=True)
        return l.join(r, l.k == r.k2, jt)
    assert_tpu_and_cpu_equal_collect(
        fn, conf={**NO_BCAST, **TINY_BUDGET},
        expect_execs=["TpuShuffledHashJoin"])
    c = _run_counters(fn, {**NO_BCAST, **TINY_BUDGET})
    assert c["plannedPartitions"] > 0, c
    assert c["retryCount"] == 0 and c["splitRetryCount"] == 0, c


def test_ooc_join_parity_string_keys():
    def fn(s):
        l, r = _join_data(s, strings=True, nulls=True)
        return l.join(r, l.k == r.k2, "inner")
    assert_tpu_and_cpu_equal_collect(
        fn, conf={**NO_BCAST, **TINY_BUDGET},
        expect_execs=["TpuShuffledHashJoin"])


def test_ooc_join_skewed_keys_recursion_backstop():
    """One hot key owns 90% of the build rows: doubling the modulus
    can never split it, so the plan recurses to maxRecursion and the
    backstop tier takes the bucket — results still bit-identical."""
    def fn(s):
        l, r = _join_data(s, skew=True)
        return l.join(r, l.k == r.k2, "inner")
    conf = {**NO_BCAST, **TINY_BUDGET,
            "spark.rapids.sql.outOfCore.maxRecursion": "1"}
    assert_tpu_and_cpu_equal_collect(
        fn, conf=conf, expect_execs=["TpuShuffledHashJoin"])
    c = _run_counters(fn, conf)
    assert c["plannedPartitions"] > 0, c
    assert c["plannedOutOfCoreEscalations"] > 0, c


def test_ooc_join_recursive_repartition():
    """maxPartitions=2 makes the first plan far too coarse: buckets
    must recursively re-partition (doubled modulus) until they fit,
    with the escalation counter recording every re-plan."""
    def fn(s):
        l, r = _join_data(s)
        return l.join(r, l.k == r.k2, "inner")
    conf = {**NO_BCAST, **TINY_BUDGET,
            "spark.rapids.sql.outOfCore.maxPartitions": "2"}
    assert_tpu_and_cpu_equal_collect(
        fn, conf=conf, expect_execs=["TpuShuffledHashJoin"])
    c = _run_counters(fn, conf)
    assert c["plannedOutOfCoreEscalations"] > 0, c
    assert c["retryCount"] == 0, c


def test_ooc_disabled_stays_in_memory():
    def fn(s):
        l, r = _join_data(s)
        return l.join(r, l.k == r.k2, "inner")
    conf = {**NO_BCAST, **TINY_BUDGET,
            "spark.rapids.sql.outOfCore.enabled": "false"}
    assert_tpu_and_cpu_equal_collect(
        fn, conf=conf, expect_execs=["TpuShuffledHashJoin"])
    c = _run_counters(fn, conf)
    assert c["plannedPartitions"] == 0, c


# ---------------------------------------------------------------------------
# Aggregation: hash-bucketed sort fallback
# ---------------------------------------------------------------------------

def test_ooc_agg_parity():
    def fn(s):
        rng = np.random.RandomState(9)
        t = s.createDataFrame(
            {"g": [int(v) for v in rng.randint(0, 200, 1600)],
             "x": [int(v) for v in range(1600)]},
            num_partitions=3)
        return t.groupBy("g").agg(F.sum("x").alias("s"),
                                  F.count("*").alias("c"),
                                  F.min("x").alias("mn"),
                                  F.max("x").alias("mx"))
    assert_tpu_and_cpu_equal_collect(
        fn, conf=TINY_BUDGET, expect_execs=["TpuHashAggregate"])
    c = _run_counters(fn, TINY_BUDGET)
    assert c["plannedPartitions"] > 0, c
    assert c["retryCount"] == 0 and c["splitRetryCount"] == 0, c


def test_ooc_agg_parity_string_keys_with_nulls():
    def fn(s):
        rng = np.random.RandomState(2)
        g = [None if i % 13 == 0 else f"g{int(v):03d}"
             for i, v in enumerate(rng.randint(0, 150, 1200))]
        t = s.createDataFrame(
            {"g": g, "x": [int(v) for v in range(1200)]},
            num_partitions=3)
        return t.groupBy("g").agg(F.sum("x").alias("s"),
                                  F.count("*").alias("c"))
    assert_tpu_and_cpu_equal_collect(
        fn, conf=TINY_BUDGET, expect_execs=["TpuHashAggregate"])


# ---------------------------------------------------------------------------
# 8x-over-budget end-to-end: steady occupancy, zero retries
# ---------------------------------------------------------------------------

def test_ooc_e2e_8x_over_budget_q1_shape():
    """q1-shaped (filter + grouped agg + sort) over a working set >8x
    the device budget: bit-identical to CPU and retryCount == 0 — the
    planned path, not the retry ladder, absorbs the pressure."""
    n = 4000  # ~96KB of key+value columns vs an 8KB budget
    def fn(s):
        rng = np.random.RandomState(4)
        t = s.createDataFrame(
            {"flag": [int(v) for v in rng.randint(0, 3, n)],
             "status": [int(v) for v in rng.randint(0, 5, n)],
             "qty": [int(v) for v in rng.randint(0, 50, n)]},
            num_partitions=4)
        return (t.filter(F.col("qty") % 5 != 0)
                .groupBy("flag", "status")
                .agg(F.sum("qty").alias("sq"), F.count("*").alias("c"))
                .orderBy("flag", "status"))
    assert_tpu_and_cpu_equal_collect(fn, conf=TINY_BUDGET)
    c = _run_counters(fn, TINY_BUDGET)
    assert c["plannedPartitions"] > 0, c
    assert c["retryCount"] == 0 and c["splitRetryCount"] == 0, c


def test_ooc_e2e_8x_over_budget_q3_shape():
    """q3-shaped (join + grouped agg + limit) over-budget run: the
    join AND the downstream agg both ride the planned tier with zero
    retries."""
    def fn(s):
        l, r = _join_data(s, n=1600, parts=4)
        return (l.join(r, l.k == r.k2, "inner")
                .groupBy("k").agg(F.sum("w").alias("sw"),
                                  F.count("*").alias("c"))
                .orderBy("k").limit(50))
    conf = {**NO_BCAST, **TINY_BUDGET}
    assert_tpu_and_cpu_equal_collect(fn, conf=conf)
    c = _run_counters(fn, conf)
    assert c["plannedPartitions"] > 0, c
    assert c["retryCount"] == 0 and c["splitRetryCount"] == 0, c


# ---------------------------------------------------------------------------
# Budget oracle + site:budget fault grammar
# ---------------------------------------------------------------------------

def test_budget_oracle_pow2_plan():
    conf = TpuConf({"spark.rapids.sql.memory.deviceBudgetBytes": "1024"})
    o = get_budget_oracle(conf)
    share = o.operator_share()
    assert share == 512
    assert o.plan_partitions(100) == 1  # fits: no partitioning
    n = o.plan_partitions(10 * share)
    assert n == 16 and (n & (n - 1)) == 0  # pow2-rounded up
    assert o.plan_partitions(10 ** 9) == o.max_partitions


def test_budget_oracle_disabled_never_partitions():
    conf = TpuConf({"spark.rapids.sql.memory.deviceBudgetBytes": "1024",
                    "spark.rapids.sql.outOfCore.enabled": "false"})
    o = get_budget_oracle(conf)
    assert o.plan_partitions(10 ** 9) == 1


@pytest.mark.fault
def test_site_budget_fault_halves_headroom():
    conf = TpuConf({"spark.rapids.sql.memory.deviceBudgetBytes": "4096",
                    "spark.rapids.sql.test.injectOOM": "site:budget:2"})
    o = get_budget_oracle(conf)
    rooms = [o.headroom() for _ in range(4)]
    # every 2nd oracle query reports HALF the real headroom
    assert rooms[0] == 4096 and rooms[1] == 2048, rooms
    assert rooms[2] == 4096 and rooms[3] == 2048, rooms
    inj = R.get_fault_injector(conf)
    assert inj is not None and inj.stats()["budgetFaultsInjected"] == 2


@pytest.mark.fault
def test_site_budget_fault_escalates_without_retries():
    """Injected budget lies (half headroom on every oracle query) make
    the plan MORE conservative — more partitions — but never push the
    run onto the retry ladder, and results stay bit-identical."""
    def fn(s):
        l, r = _join_data(s)
        return l.join(r, l.k == r.k2, "inner")
    clean_conf = {**NO_BCAST, **TINY_BUDGET}
    fault_conf = {**clean_conf,
                  "spark.rapids.sql.test.injectOOM": "site:budget:1"}
    clean = _run_counters(fn, clean_conf)
    R.reset_fault_injection()
    assert_tpu_and_cpu_equal_collect(fn, conf=fault_conf)
    faulted = _run_counters(fn, fault_conf)
    assert faulted["plannedPartitions"] >= clean["plannedPartitions"], \
        (clean, faulted)
    assert faulted["retryCount"] == 0 and \
        faulted["splitRetryCount"] == 0, faulted
    inj = R.get_fault_injector(TpuConf(fault_conf))
    assert inj is not None and inj.stats()["budgetFaultsInjected"] > 0


# ---------------------------------------------------------------------------
# Doctor: planned big-input is biggerInput, never retrySpill
# ---------------------------------------------------------------------------

def _hist_record(qid, *, wall, rows, retries=0, spill=0, poc=None):
    rec = {"queryId": qid, "signature": "sig-ooc",
           "status": "finished", "tenant": "t", "wallSeconds": wall,
           "queueWaitSeconds": 0.0, "outputRows": rows,
           "retryCount": retries, "splitRetryCount": 0,
           "spillBytes": spill, "jitMisses": 0}
    if poc:
        rec["plannedOutOfCore"] = poc
    return rec


def _write_history(tmp_path, recs):
    hdir = tmp_path / "hist"
    hdir.mkdir(exist_ok=True)
    with open(hdir / "history-0-0-0000.jsonl", "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    return str(hdir)


def test_doctor_planned_big_input_is_bigger_input(tmp_path):
    """A correctly-planned 10x-over-budget run spills by DESIGN with
    zero retries: the doctor must rank biggerInput over retrySpill
    (the planned-out-of-core record field is the tiebreaker)."""
    from spark_rapids_tpu.telemetry.doctor import diagnose
    recs = [_hist_record(f"b{i}", wall=1.0, rows=1000)
            for i in range(3)]
    recs.append(_hist_record(
        "target", wall=3.0, rows=10000, retries=0,
        spill=50_000_000,
        poc={"plannedPartitions": 16, "budgetPressurePeak": 1000}))
    hdir = _write_history(tmp_path, recs)
    d = diagnose(hdir, "target")
    assert d.get("error") is None
    assert d["verdict"] == "biggerInput", d["verdicts"]
    by_class = {v["class"]: v for v in d["verdicts"]}
    assert by_class["biggerInput"]["score"] > \
        by_class.get("retrySpill", {"score": 0.0})["score"]
    assert any("planned out-of-core" in e
               for e in by_class["biggerInput"]["evidence"])


def test_doctor_retry_storm_recommends_planned_out_of_core(tmp_path):
    """An UNplanned retry storm (high retries, no plannedOutOfCore on
    record) keeps its retrySpill verdict and the evidence now names
    the confs that move the workload onto the planned tier."""
    from spark_rapids_tpu.telemetry.doctor import diagnose
    recs = [_hist_record(f"b{i}", wall=1.0, rows=1000)
            for i in range(3)]
    recs.append(_hist_record(
        "storm", wall=4.0, rows=1000, retries=9,
        spill=50_000_000))
    hdir = _write_history(tmp_path, recs)
    d = diagnose(hdir, "storm")
    assert d.get("error") is None
    by_class = {v["class"]: v for v in d["verdicts"]}
    assert "retrySpill" in by_class, d["verdicts"]
    assert any("deviceBudgetBytes" in e
               for e in by_class["retrySpill"]["evidence"])

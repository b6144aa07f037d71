"""Behavioral coverage for session-level conf keys wired in round 4:
hasNans as a float-sort-key kernel hint, memory.tpu.debug store logging,
and the device shuffle-partition coalescing knob."""

from __future__ import annotations

import logging

import numpy as np

from spark_rapids_tpu.sql import functions as F

from tests.datagen import DoubleGen, IntegerGen, gen_batch
from tests.harness import assert_tpu_and_cpu_equal_collect


def _df(s, cols, n=512, seed=77, parts=2):
    return s.createDataFrame(gen_batch(cols, n, seed), num_partitions=parts)


def test_no_kernel_tier_conf_key_is_registered():
    # the Pallas kernel tier went with its eleven keys (PR 32); a
    # session given one behaves as for any unknown key
    from spark_rapids_tpu.conf import _REGISTRY, TpuConf
    assert not [k for k in _REGISTRY
                if k.startswith("spark.rapids.sql.kernel.")
                or k == "spark.rapids.sql.telemetry."
                        "kernelFallbackThreshold"]
    conf = TpuConf({"spark.rapids.sql.kernel.enabled": "false"})
    assert conf.settings["spark.rapids.sql.kernel.enabled"] == "false"


def test_has_nans_false_same_results():
    """With NaN-free data, hasNans=false (drops the is-NaN sort word —
    one fewer radix pass per float key) must give identical sort/group
    results; kernel_salt() keeps compiled programs distinct per flag."""
    nonan = DoubleGen(special=False)  # no NaN/inf specials
    for flag in ("true", "false"):
        assert_tpu_and_cpu_equal_collect(
            lambda s: _df(s, [("f", nonan), ("i", IntegerGen())])
            .groupBy("f").agg(F.sum("i").alias("s"))
            .orderBy("f"),
            conf={"spark.rapids.sql.hasNans": flag},
            expect_execs=["TpuHashAggregate", "TpuSort"])


def test_has_nans_true_handles_nans():
    """Default hasNans=true keeps exact NaN grouping (all NaNs one
    group, NaN sorts greatest)."""
    assert_tpu_and_cpu_equal_collect(
        lambda s: s.createDataFrame(
            {"f": [1.0, float("nan"), 2.0, float("nan"), None],
             "i": [1, 2, 3, 4, 5]}, "f double, i long")
        .groupBy("f").agg(F.sum("i").alias("s")).orderBy("f"),
        expect_execs=["TpuHashAggregate", "TpuSort"])


def test_memory_debug_logs_spill(caplog):
    from spark_rapids_tpu import memory
    from spark_rapids_tpu.sql.session import TpuSparkSession
    spark = TpuSparkSession({
        "spark.rapids.sql.enabled": "true",
        "spark.rapids.memory.tpu.poolSize": str(1 << 16),
        "spark.rapids.memory.tpu.debug": "true",
    })
    try:
        with caplog.at_level(logging.INFO, "spark_rapids_tpu.memory"):
            df = spark.createDataFrame(
                {"k": (np.arange(4096) % 7).tolist(),
                 "v": np.arange(4096).tolist()}, "k long, v long")
            df.repartition(4, F.col("k")).groupBy("k").agg(
                F.sum("v").alias("s")).collect()
        assert memory._STORE is not None
        if memory._STORE.spill_count:
            assert any("spill device->host" in r.message
                       for r in caplog.records)
    finally:
        spark.stop()


def test_device_partitions_conf_controls_exchange():
    """devicePartitions=4 keeps a real multi-partition device split;
    auto (default) coalesces to 1 in-process — results identical."""
    for conf in ({}, {"spark.rapids.sql.shuffle.devicePartitions": "4"}):
        assert_tpu_and_cpu_equal_collect(
            lambda s: _df(s, [("i", IntegerGen())])
            .groupBy("i").agg(F.count("*").alias("c")).orderBy("i"),
            conf=dict(conf),
            expect_execs=["TpuExchange", "TpuHashAggregate"])


def test_cbo_reverts_small_device_island():
    """spark.rapids.sql.optimizer.enabled: a CPU-sandwiched single
    project island loses its transition cost and reverts to CPU; with
    the optimizer off the island stays on device (CostBasedOptimizer
    v0)."""
    from spark_rapids_tpu.sql.session import TpuSparkSession

    def plan_for(cbo: str):
        sp = TpuSparkSession({
            "spark.rapids.sql.enabled": "true",
            "spark.rapids.sql.optimizer.enabled": cbo,
            # make the island minimal: a single device-able projection
            # over a CPU source, collected straight back to rows
        })
        try:
            df = sp.createDataFrame(
                {"a": list(range(64))}, "a int").select(
                (F.col("a") + 1).alias("b"))
            sp.start_capture()
            df.collect()
            return "\n".join(p.tree_string()
                             for p in sp.get_captured_plans())
        finally:
            sp.stop()

    on = plan_for("true")
    off = plan_for("false")
    assert "TpuProject" in off, off
    assert "TpuProject" not in on and "Project" in on, on


def test_cbo_keeps_wide_islands():
    """Aggregation islands repay their transitions and must survive the
    optimizer pass."""
    from spark_rapids_tpu.sql.session import TpuSparkSession
    sp = TpuSparkSession({"spark.rapids.sql.enabled": "true",
                          "spark.rapids.sql.optimizer.enabled": "true"})
    try:
        df = sp.createDataFrame(
            {"k": [i % 5 for i in range(64)], "v": list(range(64))},
            "k int, v long").groupBy("k").agg(F.sum("v").alias("s"))
        sp.start_capture()
        df.collect()
        pstr = "\n".join(p.tree_string()
                         for p in sp.get_captured_plans())
        assert "TpuHashAggregate" in pstr, pstr
    finally:
        sp.stop()


def test_cbo_keeps_regex_island_on_large_input():
    """CBO v1: a SINGLE regex-heavy filter island over a large scan
    stays on device (the python re loop dwarfs the wire cost) — the v0
    pattern-match wrongly reverted every 1-op island."""
    import numpy as np
    from spark_rapids_tpu.sql.session import TpuSparkSession
    import os, shutil, tempfile
    d = tempfile.mkdtemp()
    try:
        gen = TpuSparkSession({"spark.rapids.sql.enabled": "false"})
        n = 300_000
        gen.createDataFrame(
            {"s": [f"row{i:07d}" for i in range(n)]},
            "s string").write.mode("overwrite").parquet(d)
        gen.stop()
        sp = TpuSparkSession({"spark.rapids.sql.enabled": "true",
                              "spark.rapids.sql.optimizer.enabled": "true"})
        try:
            sp.start_capture()
            df = sp.read.parquet(d).filter("s LIKE 'row00%'")
            got = df.collect()
            pstr = "\n".join(p.tree_string()
                             for p in sp.get_captured_plans())
        finally:
            sp.stop()
        assert len(got) == 100_000
        assert "TpuFilter" in pstr, pstr
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_cbo_reverts_multi_op_island_on_tiny_input():
    """CBO v1: even a TWO-op cheap island over tiny data reverts (the
    flat per-island sync latency dominates) — v0 only caught 1-op
    islands."""
    from spark_rapids_tpu.sql.session import TpuSparkSession
    sp = TpuSparkSession({"spark.rapids.sql.enabled": "true",
                          "spark.rapids.sql.optimizer.enabled": "true"})
    try:
        sp.start_capture()
        df = sp.createDataFrame({"a": list(range(64))}, "a int") \
            .filter(F.col("a") > 3).select((F.col("a") + 1).alias("b"))
        out = df.collect()
        pstr = "\n".join(p.tree_string() for p in sp.get_captured_plans())
    finally:
        sp.stop()
    assert len(out) == 60
    assert "TpuProject" not in pstr and "TpuFilter" not in pstr, pstr


# -- metric timers (ISSUE 1 satellite: drain-time overlap) ------------------

def test_timed_wall_unions_concurrent_intervals():
    """N pool threads timing the same phase concurrently must advance
    the metric by WALL time (interval union), not N stacked
    thread-times — the round-5 bench reported an 11.6s drain against a
    5.4s wall because of exactly this overlap."""
    import threading
    import time

    from spark_rapids_tpu.metrics import MetricRegistry

    reg = MetricRegistry("MODERATE")

    def work():
        with reg.timed_wall("pipelineDrainTime"):
            time.sleep(0.15)

    threads = [threading.Thread(target=work) for _ in range(4)]
    start = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - start
    got = reg.value("pipelineDrainTime") / 1e9
    # concurrent intervals count once: metric <= actual wall, and far
    # below the 0.6s a per-thread sum would report
    assert got <= wall + 0.02, (got, wall)
    assert got < 0.45, got


def test_timed_wall_sums_disjoint_intervals():
    import time

    from spark_rapids_tpu.metrics import MetricRegistry

    reg = MetricRegistry("MODERATE")
    for _ in range(3):
        with reg.timed_wall("decodeTime"):
            time.sleep(0.03)
    got = reg.value("decodeTime") / 1e9
    assert 0.09 <= got < 0.3, got

"""Device sort / TopN / range-partitioning tests through the dual-session
harness (GpuSortExec + GpuTopN + GpuRangePartitioner coverage; reference
integration pattern: integration_tests sort_test.py over asserts.py:434).
Order-sensitive assertions use ignore_order=False so a wrong permutation
fails, not just wrong membership.
"""

import pytest

from spark_rapids_tpu.sql import functions as F

from tests.datagen import (BooleanGen, DateGen, DoubleGen, FloatGen,
                           IntegerGen, KeyStringGen, LongGen, SmallIntGen,
                           StringGen, TimestampGen, gen_batch)
from tests.harness import (assert_tpu_and_cpu_equal_collect,
                           assert_tpu_fallback_collect)

N = 512


def _df(spark, gens, n=N, seed=11, parts=3):
    return spark.createDataFrame(gen_batch(gens, n, seed),
                                 num_partitions=parts)


@pytest.mark.parametrize("gen", [
    IntegerGen(), LongGen(), DoubleGen(), FloatGen(), BooleanGen(),
    StringGen(), DateGen(), TimestampGen()],
    ids=["int", "long", "double", "float", "bool", "string", "date", "ts"])
def test_orderby_single_key(gen):
    assert_tpu_and_cpu_equal_collect(
        lambda s: _df(s, [("a", gen), ("b", IntegerGen())]).orderBy("a"),
        ignore_order=False,
        expect_execs=["TpuSort"])


@pytest.mark.parametrize("gen", [IntegerGen(), DoubleGen(), StringGen()],
                         ids=["int", "double", "string"])
def test_orderby_desc(gen):
    assert_tpu_and_cpu_equal_collect(
        lambda s: _df(s, [("a", gen), ("b", IntegerGen())])
        .orderBy(F.col("a").desc()),
        ignore_order=False,
        expect_execs=["TpuSort"])


def test_orderby_nulls_variants():
    for order in (F.col("a").asc_nulls_last(), F.col("a").desc_nulls_first(),
                  F.col("a").asc(), F.col("a").desc()):
        assert_tpu_and_cpu_equal_collect(
            lambda s, o=order: _df(s, [("a", IntegerGen()),
                                       ("b", LongGen())]).orderBy(o),
            ignore_order=False,
            expect_execs=["TpuSort"])


def test_orderby_multi_key():
    assert_tpu_and_cpu_equal_collect(
        lambda s: _df(s, [("k", SmallIntGen()), ("v", DoubleGen()),
                          ("s", KeyStringGen())])
        .orderBy(F.col("k").asc(), F.col("v").desc(), F.col("s").asc()),
        ignore_order=False,
        expect_execs=["TpuSort"])


def test_orderby_expression_key():
    assert_tpu_and_cpu_equal_collect(
        lambda s: _df(s, [("a", IntegerGen()), ("b", IntegerGen())])
        .orderBy((F.col("a") + F.col("b")).asc(), F.col("a").desc()),
        ignore_order=False,
        expect_execs=["TpuSort"])


def test_global_sort_fully_on_device():
    """Global sort: range-partitioning exchange AND sort both on device."""
    assert_tpu_and_cpu_equal_collect(
        lambda s: _df(s, [("a", LongGen()), ("b", StringGen())], n=1000,
                      parts=4).orderBy("a", "b"),
        ignore_order=False,
        conf={"spark.rapids.sql.test.forceDevice": "true"},
        expect_execs=["TpuSort", "TpuExchange"])


def test_sort_within_partitions():
    assert_tpu_and_cpu_equal_collect(
        lambda s: _df(s, [("a", IntegerGen()), ("b", DoubleGen())], parts=1)
        .sortWithinPartitions(F.col("b").desc_nulls_first()),
        ignore_order=False,
        expect_execs=["TpuSort"])


def test_topn_fusion():
    """orderBy().limit() fuses LocalLimit(Sort) into TpuTopN."""
    assert_tpu_and_cpu_equal_collect(
        lambda s: _df(s, [("a", LongGen()), ("b", StringGen())], n=900,
                      parts=4).orderBy(F.col("a").desc()).limit(17),
        ignore_order=False,
        expect_execs=["TpuTopN"])


@pytest.mark.parametrize("n, limit", [(200_000, 100), (500, 100),
                                      (500, 7), (90, 1000)])
def test_topn_yields_its_limits_own_bucket(n, limit):
    """A TopN's kept rows are a prefix of its sorted input; the program
    emits them at the limit's capacity bucket (never more than the
    input's), so that a collect does not fetch the input's capacity for
    a hundred rows."""
    import numpy as np
    from spark_rapids_tpu.columnar.device import bucket_capacity
    from spark_rapids_tpu.exec.sort import TpuTopNExec
    from spark_rapids_tpu.sql.session import TpuSparkSession
    spark = TpuSparkSession({"spark.rapids.sql.enabled": "true"})
    try:
        rng = np.random.default_rng(n)
        df = spark.createDataFrame(
            {"a": rng.permutation(n).tolist(),
             "b": rng.integers(0, 1 << 40, n).tolist(),
             "c": [f"s{i % 13}" for i in range(n)]},
            "a long, b long, c string", num_partitions=1)
        node = spark.plan_physical(df.orderBy("a").limit(limit).plan)
        while not isinstance(node, TpuTopNExec):
            node = node.children[0]
        (thunk,) = node.device_partitions()
        (out,) = list(thunk())
        assert out.row_count() == min(n, limit)
        assert out.capacity == min(bucket_capacity(n),
                                   bucket_capacity(limit))
        assert out.to_host().to_pydict()["a"] == list(range(min(n, limit)))
    finally:
        spark.stop()


def test_sort_after_filter_keeps_masked_rows_out():
    assert_tpu_and_cpu_equal_collect(
        lambda s: _df(s, [("a", IntegerGen()), ("b", IntegerGen())])
        .filter(F.col("a") > 2).orderBy(F.col("b").asc(), F.col("a").asc()),
        ignore_order=False,
        expect_execs=["TpuFilter", "TpuSort"])


def test_sort_decimal_on_device():
    """Round 4: decimal sort keys run on device (unscaled int64 /
    limb-word radix keys); this used to assert a CPU fallback."""
    import decimal
    assert_tpu_and_cpu_equal_collect(
        lambda s: s.createDataFrame(
            {"d": [decimal.Decimal("1.23"), None, decimal.Decimal("-4.5")]},
            "d decimal(10,2)").orderBy("d"),
        ignore_order=False,
        expect_execs=["TpuSort"])


def test_sort_empty_input():
    assert_tpu_and_cpu_equal_collect(
        lambda s: s.createDataFrame({"a": []}, "a int",
                                    num_partitions=2).orderBy("a"),
        ignore_order=False,
        require_device=False)

"""Device window-function tests through the dual-session harness
(GpuWindowExec coverage; reference pattern: window_function_test.py).
"""

import pytest

from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.sql.functions import Window

from tests.datagen import (DoubleGen, IntegerGen, KeyStringGen, LongGen,
                           SmallIntGen, StringGen, gen_batch)
from tests.harness import (assert_tpu_and_cpu_equal_collect,
                           assert_tpu_fallback_collect)

N = 400


def _df(spark, gens, n=N, seed=13, parts=2):
    return spark.createDataFrame(gen_batch(gens, n, seed),
                                 num_partitions=parts)


def _w(order=True):
    w = Window.partitionBy("k")
    return w.orderBy("o") if order else w


@pytest.mark.parametrize("fn_col", [
    lambda: F.row_number(), lambda: F.rank(), lambda: F.dense_rank(),
    lambda: F.ntile(3)],
    ids=["row_number", "rank", "dense_rank", "ntile"])
def test_ranking_functions(fn_col):
    assert_tpu_and_cpu_equal_collect(
        lambda s: _df(s, [("k", SmallIntGen()), ("o", IntegerGen())])
        .select("k", "o", fn_col().over(_w()).alias("r")),
        expect_execs=["TpuWindow"])


@pytest.mark.parametrize("agg", [
    lambda c: F.sum(c), lambda c: F.count(c), lambda c: F.min(c),
    lambda c: F.max(c)], ids=["sum", "count", "min", "max"])
def test_running_aggregates(agg):
    assert_tpu_and_cpu_equal_collect(
        lambda s: _df(s, [("k", SmallIntGen()), ("o", IntegerGen()),
                          ("v", LongGen())])
        .select("k", "v", agg("v").over(_w()).alias("a"),
                F.row_number().over(_w()).alias("rn")),
        expect_execs=["TpuWindow"])


@pytest.mark.parametrize("agg", [
    lambda c: F.sum(c), lambda c: F.count(c), lambda c: F.min(c),
    lambda c: F.max(c), lambda c: F.avg(c)],
    ids=["sum", "count", "min", "max", "avg"])
def test_whole_partition_aggregates(agg):
    # avg over ints is exact only under the float-agg knob on this backend
    conf = {"spark.rapids.sql.variableFloatAgg.enabled": "true"}
    assert_tpu_and_cpu_equal_collect(
        lambda s: _df(s, [("k", SmallIntGen()), ("v", IntegerGen())])
        .select("k", "v", agg("v").over(Window.partitionBy("k"))
                .alias("a")),
        conf=conf, approx=True,
        expect_execs=["TpuWindow"])


def test_bounded_rows_frame_sum_count():
    w = _w().rowsBetween(-2, 1)
    assert_tpu_and_cpu_equal_collect(
        lambda s: _df(s, [("k", SmallIntGen()), ("o", IntegerGen()),
                          ("v", LongGen())])
        .select("k", "o", F.sum("v").over(w).alias("s"),
                F.count("v").over(w).alias("c"),
                F.row_number().over(_w()).alias("rn")),
        expect_execs=["TpuWindow"])


def test_rows_running_frame():
    w = _w().rowsBetween(Window.unboundedPreceding, 0)
    assert_tpu_and_cpu_equal_collect(
        lambda s: _df(s, [("k", SmallIntGen()), ("o", IntegerGen()),
                          ("v", LongGen())])
        .select("k", F.sum("v").over(w).alias("s"),
                F.row_number().over(_w()).alias("rn")),
        expect_execs=["TpuWindow"])


def test_lag_lead():
    assert_tpu_and_cpu_equal_collect(
        lambda s: _df(s, [("k", SmallIntGen()), ("o", IntegerGen()),
                          ("v", LongGen())])
        .select("k", "o", F.lag("v", 1).over(_w()).alias("lg"),
                F.lead("v", 2).over(_w()).alias("ld"),
                F.lag("v", 1, 0).over(_w()).alias("lgd"),
                F.row_number().over(_w()).alias("rn")),
        expect_execs=["TpuWindow"])


def test_lag_string_values():
    assert_tpu_and_cpu_equal_collect(
        lambda s: _df(s, [("k", SmallIntGen()), ("o", IntegerGen()),
                          ("v", KeyStringGen())])
        .select("k", "o", F.lag("v", 1).over(_w()).alias("lg"),
                F.row_number().over(_w()).alias("rn")),
        expect_execs=["TpuWindow"])


def test_first_last_over_partition():
    assert_tpu_and_cpu_equal_collect(
        lambda s: _df(s, [("k", SmallIntGen()), ("o", IntegerGen()),
                          ("v", LongGen())])
        .select("k", F.first("v").over(_w()).alias("f"),
                F.last("v").over(_w()).alias("l"),
                F.row_number().over(_w()).alias("rn")),
        expect_execs=["TpuWindow"])


def test_window_no_partition():
    """Empty partitionBy: the whole dataset is one window partition."""
    assert_tpu_and_cpu_equal_collect(
        lambda s: _df(s, [("o", IntegerGen()), ("v", LongGen())], n=200)
        .select("o", "v",
                F.row_number().over(Window.orderBy("o", "v")).alias("rn")),
        expect_execs=["TpuWindow"])


def test_window_string_partition_keys():
    assert_tpu_and_cpu_equal_collect(
        lambda s: _df(s, [("k", KeyStringGen()), ("o", IntegerGen()),
                          ("v", LongGen())])
        .select("k", F.sum("v").over(_w()).alias("s"),
                F.row_number().over(_w()).alias("rn")),
        expect_execs=["TpuWindow"])


def test_float_window_sum_falls_back():
    assert_tpu_fallback_collect(
        lambda s: _df(s, [("k", SmallIntGen()), ("o", IntegerGen()),
                          ("v", DoubleGen())])
        .select("k", F.sum("v").over(_w()).alias("s")),
        fallback_exec="CpuWindowExec")


def test_bounded_min_on_device():
    """Round 4: bounded-frame min/max runs on device (sparse-table RMQ);
    this used to assert a CPU fallback."""
    assert_tpu_and_cpu_equal_collect(
        lambda s: _df(s, [("k", SmallIntGen()), ("o", IntegerGen()),
                          ("v", LongGen())])
        .select("k", "o", "v",
                F.min("v").over(_w().rowsBetween(-1, 1)).alias("m")),
        expect_execs=["TpuWindow"])


def test_window_then_filter_pipeline():
    def fn(s):
        df = _df(s, [("k", SmallIntGen()), ("o", IntegerGen()),
                     ("v", LongGen())])
        return (df.withColumn("rn", F.row_number().over(_w()))
                .filter(F.col("rn") <= 3))
    assert_tpu_and_cpu_equal_collect(fn, expect_execs=["TpuWindow",
                                                       "TpuFilter"])


def test_lag_string_with_default():
    assert_tpu_and_cpu_equal_collect(
        lambda s: _df(s, [("k", SmallIntGen()), ("o", IntegerGen()),
                          ("v", KeyStringGen())])
        .select("k", "o", F.lag("v", 1, "DFLT").over(_w()).alias("lg"),
                F.row_number().over(_w()).alias("rn")),
        expect_execs=["TpuWindow"])


# -- round 4: bounded min/max, value-bounded RANGE, key batching -----------

def test_bounded_rows_min_max():
    assert_tpu_and_cpu_equal_collect(
        lambda s: _df(s, [("k", KeyStringGen()), ("o", IntegerGen()),
                          ("v", IntegerGen())])
        .select("k", "o", "v",
                F.min("v").over(Window.partitionBy("k").orderBy("o", "v")
                                .rowsBetween(-3, 2)).alias("mn"),
                F.max("v").over(Window.partitionBy("k").orderBy("o", "v")
                                .rowsBetween(0, 4)).alias("mx")),
        expect_execs=["TpuWindow"])


def test_value_bounded_range_frames():
    assert_tpu_and_cpu_equal_collect(
        lambda s: _df(s, [("k", KeyStringGen()), ("o", IntegerGen()),
                          ("v", IntegerGen())])
        .select("k", "o", "v",
                F.sum("v").over(Window.partitionBy("k").orderBy("o")
                                .rangeBetween(-10, 10)).alias("s"),
                F.count("v").over(Window.partitionBy("k").orderBy("o")
                                  .rangeBetween(0, 25)).alias("c"),
                F.min("v").over(Window.partitionBy("k").orderBy("o")
                                .rangeBetween(-50, 0)).alias("mn")),
        expect_execs=["TpuWindow"])


def test_value_bounded_range_desc_and_nulls():
    assert_tpu_and_cpu_equal_collect(
        lambda s: _df(s, [("k", KeyStringGen()),
                          ("o", IntegerGen(null_prob=0.2)),
                          ("v", IntegerGen())])
        .select("k", "o", "v",
                F.max("v").over(Window.partitionBy("k")
                                .orderBy(F.col("o").desc())
                                .rangeBetween(-7, 3)).alias("mx")),
        expect_execs=["TpuWindow"])


def test_window_key_batching_over_budget():
    """Giant partitions stream through the key-batching iterator (chunks
    split only at partition-key boundaries) under a tiny batch goal and
    HBM budget — GpuKeyBatchingIterator + spill-framework contract."""
    assert_tpu_and_cpu_equal_collect(
        lambda s: _df(s, [("k", KeyStringGen()), ("o", IntegerGen()),
                          ("v", LongGen())], n=2000)
        .select("k", "o", "v",
                F.row_number().over(Window.partitionBy("k").orderBy("o", "v"))
                .alias("rn"),
                F.sum("v").over(Window.partitionBy("k").orderBy("o", "v"))
                .alias("rs")),
        conf={"spark.rapids.sql.batchSizeRows": "256",
              "spark.rapids.memory.tpu.poolSize": str(1 << 16)},
        expect_execs=["TpuWindow"])


def test_value_bounded_range_nan_order_values():
    """NaN order values form their own peer block (Spark total order:
    all NaNs equal, greatest): NaN rows frame the NaN block, finite
    rows' value frames exclude it — on both engines, ASC and DESC."""
    nan = float("nan")
    rows = {"k": ["a"] * 10 + ["b"] * 6,
            "o": [1.0, 2.0, 3.0, nan, nan, None, 4.0, 5.0, nan, None,
                  2.0, nan, 1.0, None, 3.0, nan],
            "v": list(range(16))}
    assert_tpu_and_cpu_equal_collect(
        lambda s: s.createDataFrame(rows, "k string, o double, v int")
        .select("k", "o", "v",
                F.sum("v").over(Window.partitionBy("k").orderBy("o")
                                .rangeBetween(-1, 1)).alias("s"),
                F.sum("v").over(Window.partitionBy("k")
                                .orderBy(F.col("o").desc())
                                .rangeBetween(-1, 1)).alias("sd"),
                F.count("v").over(
                    Window.partitionBy("k").orderBy("o")
                    .rangeBetween(Window.unboundedPreceding, 0))
                .alias("cu")),
        expect_execs=["TpuWindow"])


def test_lag_lead_decimal128_on_device():
    """lag/lead over DECIMAL128 columns now runs on device (two-limb
    gather in exec/window.py _offset_fn) — formerly a CPU fallback."""
    from decimal import Decimal


    def q(spark):
        vals = [None if i % 7 == 0 else
                Decimal(10 ** 20 + i * 137) / Decimal(100)
                for i in range(60)]
        df = spark.createDataFrame(
            {"g": [i % 4 for i in range(60)],
             "o": list(range(60)), "d": vals},
            "g int, o int, d decimal(25,2)")
        w = Window.partitionBy("g").orderBy("o")
        return df.select(
            "g", "o",
            F.lag("d", 1).over(w).alias("lg"),
            F.lead("d", 2).over(w).alias("ld"),
            F.lag("d", 1, Decimal("0.55")).over(w).alias("lgd"))
    assert_tpu_and_cpu_equal_collect(q)


# ---------------------------------------------------------------------------
# Window aggregates over decimal sources (PR 36): 64-bit and two-limb
# sources, 128-bit accumulators; the CPU engine is the oracle
# ---------------------------------------------------------------------------

_DEC_TYPES = {"decimal(7,2)": 7, "decimal(17,2)": 17, "decimal(27,2)": 27}
_DEC_FRAMES = {
    "whole": lambda w: w.rowsBetween(Window.unboundedPreceding,
                                     Window.unboundedFollowing),
    "running": lambda w: w.rowsBetween(Window.unboundedPreceding, 0),
    "bounded": lambda w: w.rowsBetween(-2, 1),
    # empty at a partition's last row, and in every one-row partition
    "following": lambda w: w.rowsBetween(1, 3),
}
_DEC_AGGS = {
    "sum_count": lambda: [F.sum("v"), F.count("v"), F.count("*")],
    "min_max": lambda: [F.min("v"), F.max("v")],
    "first_last": lambda: [F.first("v"), F.last("v"),
                           F.first("v", ignorenulls=True),
                           F.last("v", ignorenulls=True)],
}


def _decimal_rows(precision: int, n: int = 300, seed: int = 36):
    """Partitions of 1 to some 40 rows (keys 90.. have one row each),
    a unique order key, values over the whole of the type with both
    signs, a fifth of them null and one partition null throughout."""
    import random
    from decimal import Decimal
    rng = random.Random(seed * 100 + precision)
    k = [90 + i if i < 6 else rng.randrange(8) for i in range(n)]
    o = list(range(n))
    rng.shuffle(o)
    top = 10 ** precision - 1
    v = [None if (rng.random() < 0.2 or key == 3)
         else Decimal(rng.randint(-top, top)).scaleb(-2) for key in k]
    return {"k": k, "o": o, "v": v}


# bounded frames run first/last on the CPU engine, for every source type
_DEC_CASES = [(t, f, a) for t in sorted(_DEC_TYPES)
              for f in sorted(_DEC_FRAMES) for a in sorted(_DEC_AGGS)
              if not (a == "first_last" and f in ("bounded", "following"))]


@pytest.mark.parametrize("dec_type,frame,aggs", _DEC_CASES)
def test_decimal_window_aggregates(dec_type, frame, aggs):
    rows = _decimal_rows(_DEC_TYPES[dec_type])
    w = _DEC_FRAMES[frame](_w())

    def q(spark):
        df = spark.createDataFrame(rows, f"k int, o int, v {dec_type}",
                                   num_partitions=2)
        return df.select("k", "o", "v", *[
            a.over(w).alias(f"a{i}")
            for i, a in enumerate(_DEC_AGGS[aggs]())])
    assert_tpu_and_cpu_equal_collect(
        q, conf={"spark.rapids.sql.test.forceDevice": "true"},
        expect_execs=["TpuWindow"])


@pytest.mark.parametrize("dec_type,sum_type", [
    ("decimal(7,2)", "decimal(17,2)"), ("decimal(8,0)", "decimal(18,0)"),
    ("decimal(9,0)", "decimal(19,0)"), ("decimal(17,2)", "decimal(27,2)"),
    ("decimal(27,2)", "decimal(37,2)"), ("decimal(30,4)", "decimal(38,4)")])
def test_decimal_window_result_types(dec_type, sum_type):
    """sum is decimal(min(38, p + 10), s); min/max/first/last keep the
    source's type; count is a long."""
    from spark_rapids_tpu.sql.session import TpuSparkSession
    spark = TpuSparkSession({})
    try:
        df = spark.createDataFrame({"k": [1], "o": [1], "v": [None]},
                                   f"k int, o int, v {dec_type}")
        out = df.select(F.sum("v").over(_w()).alias("s"),
                        F.max("v").over(_w()).alias("m"),
                        F.first("v").over(_w()).alias("f"),
                        F.count("v").over(_w()).alias("c"))
        got = [f.data_type.simple_string for f in out.schema.fields]
        assert got == [sum_type, dec_type, dec_type, "bigint"]
    finally:
        spark.stop()


@pytest.mark.parametrize("frame", ["whole", "running", "bounded"])
def test_decimal_window_sum_overflows_to_null(frame):
    """Past decimal(38, s) a sum is null (non-ANSI), also where the true
    value no longer fits 128 bits and the limbs wrap to a small number:
    three times 9e37 is 2.7e38, -0.7e38 mod 2**128."""
    from decimal import Decimal
    big = Decimal(9 * 10 ** 37)
    rows = {"k": [1, 1, 1, 1, 2, 2, 2, 2, 3],
            "o": list(range(9)),
            "v": [big, big, big, -big, -big, -big, -big, big, big]}
    w = _DEC_FRAMES[frame](_w())
    from spark_rapids_tpu.sql.session import TpuSparkSession

    def q(spark):
        return spark.createDataFrame(rows, "k int, o int, v decimal(38,0)") \
            .select("k", "o", F.sum("v").over(w).alias("s"))
    assert_tpu_and_cpu_equal_collect(
        q, conf={"spark.rapids.sql.test.forceDevice": "true"},
        expect_execs=["TpuWindow"])
    spark = TpuSparkSession({"spark.rapids.sql.enabled": "false"})
    try:
        got = {r[1]: r[2] for r in q(spark).collect()}
    finally:
        spark.stop()
    want = {"whole": [2 * big, 2 * big, 2 * big, 2 * big, -2 * big,
                      -2 * big, -2 * big, -2 * big, big],
            "running": [big, 2 * big, 3 * big, 2 * big, -big, -2 * big,
                        -3 * big, -2 * big, big],
            "bounded": [2 * big, 3 * big, 2 * big, big, -2 * big, -3 * big,
                        -2 * big, -big, big]}[frame]
    limit = Decimal(10 ** 38)
    assert [got[i] for i in range(9)] == [
        None if abs(v) >= limit else v for v in want]


def test_decimal_window_key_batching_over_budget():
    """Over batchSizeRows the key batching runs: every partition lands
    whole in one chunk, and a chunk's 128-bit sums see only its rows."""
    rows = _decimal_rows(17, n=2000)

    def q(spark):
        df = spark.createDataFrame(rows, "k int, o int, v decimal(17,2)",
                                   num_partitions=3)
        w = Window.partitionBy("k").orderBy("o")
        return df.select("k", "o", F.sum("v").over(w).alias("s"),
                         F.max("v").over(w).alias("m"),
                         F.sum(F.col("v").cast("decimal(27,2)")).over(w)
                         .alias("s2"))
    assert_tpu_and_cpu_equal_collect(
        q, conf={"spark.rapids.sql.batchSizeRows": "256",
                 "spark.rapids.sql.test.forceDevice": "true",
                 "spark.rapids.memory.tpu.poolSize": str(1 << 16)},
        expect_execs=["TpuWindow"])


@pytest.mark.parametrize("make,reason", [
    (lambda: F.avg("v"), "window average over DecimalType"),
    (lambda: F.max("s"), "window aggregate over string"),
    (lambda: F.count("s"), "window aggregate over string")],
    ids=["avg_decimal", "max_string", "count_string"])
def test_window_aggregates_refused_by_name(make, reason):
    """What stays off the device says so in the fallback report (and
    the CPU engine, which has no decimal division for a window's
    average either, refuses that one by name too)."""
    from decimal import Decimal
    from spark_rapids_tpu.sql.session import TpuSparkSession
    spark = TpuSparkSession({"spark.rapids.sql.enabled": "true"})
    try:
        df = spark.createDataFrame(
            {"k": [1, 1, 2], "o": [1, 2, 3],
             "v": [Decimal("1.50"), None, Decimal("2.25")],
             "s": ["a", "b", None]}, "k int, o int, v decimal(7,2), s string")
        out = df.select("k", make().over(_w()).alias("a"))
        if "average" in reason:
            with pytest.raises(NotImplementedError, match=reason):
                out.collect()
        else:
            out.collect()
        report = spark.last_rewrite_report.format()
        assert reason in report, report
    finally:
        spark.stop()

"""Constant folding at planning (``sql/logical.py: fold_constants``): every
maximal literal-only subtree of a statement's expressions becomes one
literal, evaluated once on the host by the CPU engine's own ``eval``.

``DATE '1998-09-02'`` parses to a ``Cast`` of a string; folded, the filter
compares a date column with one traced int32 scalar, and the device
program holds no byte matrix and no gather. Around it: the answers at the
boundary dates on the device path against the CPU engine, one compiled
program for two dates, the nodes that never fold, an ANSI cast error that
still surfaces at run time, a non-ANSI one that folds to a typed null, and
the ``constantFoldCount`` of each benchmark statement.
"""

from __future__ import annotations

import datetime
import os

import jax
import jax.numpy as jnp
import pytest

from benchmarks.harness import cell as C
from spark_rapids_tpu import jit_cache as JC
from spark_rapids_tpu.columnar.device import DeviceColumn
from spark_rapids_tpu.ops import exprs as X
from spark_rapids_tpu.sql import expressions as E
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.sql import logical as L
from spark_rapids_tpu.sql import types as T
from spark_rapids_tpu.sql.dataframe import Column
from spark_rapids_tpu.sql.planner import _pushable_predicates
from spark_rapids_tpu.sql.session import TpuSparkSession

CPU_CONF = {"spark.rapids.sql.enabled": "false"}
ON_DEVICE = {"spark.rapids.sql.enabled": "true"}
DAY = datetime.date(1998, 9, 2)
EPOCH = datetime.date(1970, 1, 1)
Q1_FILTER = "SELECT count(*) AS n, sum(v) AS s FROM t WHERE d <= DATE '{}'"


def _table(spark: TpuSparkSession) -> None:
    """Dates on both sides of 1998-09-02, each day several times, and a
    null date that no comparison keeps."""
    days = [DAY + datetime.timedelta(days=k) for k in range(-3, 4)] * 5
    spark.createDataFrame(
        {"d": days + [None], "v": list(range(len(days))) + [1000]},
        "d date, v int").createOrReplaceTempView("t")


@pytest.fixture
def sessions():
    cpu, dev = TpuSparkSession(dict(CPU_CONF)), TpuSparkSession(
        dict(ON_DEVICE))
    for s in (cpu, dev):
        _table(s)
    yield cpu, dev
    cpu.stop()
    dev.stop()


def _walk(p: L.LogicalPlan):
    yield p
    for c in p.children:
        yield from _walk(c)


def _filter_condition(plan: L.LogicalPlan) -> E.Expression:
    (f,) = [p for p in _walk(plan) if isinstance(p, L.Filter)]
    return f.condition


def _totals() -> dict:
    from spark_rapids_tpu.telemetry.prometheus import aggregator
    return dict(aggregator().scrape()[0])


# -- the date literal --------------------------------------------------------

@pytest.mark.parametrize("day", ["1998-09-01", "1998-09-02", "1998-09-03"])
def test_a_date_literal_folds_to_one_date_and_answers_as_the_cpu_engine(
        sessions, day):
    cpu, dev = sessions
    text = Q1_FILTER.format(day)
    parsed = _filter_condition(dev.sql(text).plan)
    assert parsed.collect(lambda e: isinstance(e, E.Cast))
    folded, n = L.fold_constants(dev.sql(text).plan)
    cond = _filter_condition(folded)
    assert n == 1
    assert not cond.collect(lambda e: isinstance(e, E.Cast))
    (lit,) = cond.collect(lambda e: isinstance(e, E.Literal))
    assert lit.data_type == T.DateT
    assert lit.value == datetime.date.fromisoformat(day)
    assert X._is_traced_literal(lit)
    # the parquet footer pushdown reads the folded literal's storage
    assert _pushable_predicates(cond) == [
        ("d", "le", (datetime.date.fromisoformat(day) - EPOCH).days)]
    want = [tuple(r) for r in cpu.sql(text).collect()]
    assert [tuple(r) for r in dev.sql(text).collect()] == want
    assert want[0][0] == 5 * (4 + (datetime.date.fromisoformat(day)
                                   - DAY).days)
    assert list(dev.last_rewrite_report.fallbacks) == []


def _lowered(cond: E.Expression, attrs) -> str:
    """StableHLO of a program that evaluates ``cond`` over a date column,
    as a filter's prelude does."""
    bound = E.bind_references(cond, attrs)
    cap = 1024

    def program(data, validity, lit_vals):
        ctx = X.Ctx([DeviceColumn(T.DateT, data, validity)], cap, [bound],
                    lit_vals=lit_vals)
        return X.dev_eval(bound, ctx).data

    return jax.jit(program).lower(
        jnp.zeros(cap, jnp.int32), jnp.ones(cap, bool),
        X.literal_values([bound])).as_text()


def test_the_filter_program_holds_no_byte_matrix_and_no_gather(sessions):
    _cpu, dev = sessions
    plan = dev.sql(Q1_FILTER.format("1998-09-02")).plan
    (scan,) = [p for p in _walk(plan) if not p.children]
    attrs = [a for a in scan.output if a.name == "d"]
    cond = _filter_condition(plan)
    d_only = cond.transform(lambda e: None)
    unfolded = _lowered(d_only, attrs)
    folded = _lowered(_filter_condition(L.fold_constants(plan)[0]), attrs)
    # the parsed Cast parses the string on every lane: the check can see it
    assert "xui8>" in unfolded and "gather" in unfolded
    assert "xui8>" not in folded and "ui8" not in folded
    assert "gather" not in folded


def test_two_dates_compile_one_program(sessions):
    cpu, dev = sessions

    def misses() -> int:
        return sum(s["misses"] for s in JC.cache_stats().values())

    # dates no other test of this file uses: the cache is the process's
    first = Q1_FILTER.format("1998-08-30")
    second = Q1_FILTER.format("1998-08-31")
    dev.sql(first).collect()
    before = misses()
    got = [tuple(r) for r in dev.sql(second).collect()]
    assert misses() == before
    assert got == [tuple(r) for r in cpu.sql(second).collect()]


def test_the_plan_cache_keys_a_folded_literal_by_its_value(sessions):
    """A folded date or decimal is a value the plan cache's signature
    spells out: the same text plans to one signature, another date to
    another."""
    from spark_rapids_tpu import plan_cache as PC
    _cpu, dev = sessions

    def sig(day: str) -> str:
        text = (f"SELECT v * CAST(3 AS DECIMAL(5,1)) AS w FROM t "
                f"WHERE d <= DATE '{day}'")
        folded, n = L.fold_constants(dev.sql(text).plan)
        assert n == 2
        return PC.plan_signature(folded, dev.conf_obj)

    assert sig("1998-09-02") == sig("1998-09-02") != sig("1998-09-03")


def test_a_wide_decimal_literal_is_a_traced_input_too():
    """Q18's HAVING: ``sum(decimal(15,2)) > 300`` compares a decimal(25,2)
    (two int64 limbs a lane) with ``cast(300 as decimal(25,2))``, which
    folds to one wide literal; two thresholds share one program."""
    from decimal import Decimal
    rows = {"k": [i % 7 for i in range(70)],
            "v": [Decimal(f"{(i * 37) % 101}.25") for i in range(70)]}
    text = ("SELECT k, sum(v) AS s FROM q GROUP BY k HAVING sum(v) > {} "
            "ORDER BY k")
    cpu, dev = TpuSparkSession(dict(CPU_CONF)), TpuSparkSession(
        dict(ON_DEVICE))
    try:
        for spark in (cpu, dev):
            spark.createDataFrame(rows, "k int, v decimal(15,2)") \
                .createOrReplaceTempView("q")
        folded, n = L.fold_constants(dev.sql(text.format(300)).plan)
        (lit,) = [x for p in _walk(folded) if isinstance(p, L.Filter)
                  for x in p.condition.collect(
                      lambda e: isinstance(e, E.Literal))]
        assert n == 1 and T.is_limb_decimal(lit.data_type)
        assert X._is_traced_literal(lit)
        answers = []
        for threshold in (446, 513):
            got = [tuple(r) for r in dev.sql(text.format(threshold))
                   .collect()]
            if not answers:
                before = sum(st["misses"]
                             for st in JC.cache_stats().values())
            answers.append(got)
            assert got == [tuple(r) for r in cpu.sql(
                text.format(threshold)).collect()]
        assert sum(st["misses"] for st in JC.cache_stats().values()) \
            == before
        assert answers[0] != answers[1]
    finally:
        cpu.stop()
        dev.stop()


# -- what never folds --------------------------------------------------------

def _df_count_star(spark):
    return spark.sql("SELECT count(*) AS n FROM t")


def _df_monotonic(spark):
    return spark.table("t").select(
        (F.monotonically_increasing_id() + 1).alias("x"))


def _df_partition_id(spark):
    return spark.table("t").select((F.spark_partition_id() + 1).alias("x"))


def _df_scalar_subquery(spark):
    return spark.sql("SELECT (SELECT max(v) FROM t) + 1 AS x FROM t")


def _df_udf(spark):
    plus = F.udf(lambda a, b: a + b, "int")
    return spark.table("t").select(plus(F.lit(1), F.lit(2)).alias("x"))


@pytest.mark.parametrize("make,kind", [
    (_df_count_star, E.AggregateExpression),
    (_df_monotonic, E.MonotonicallyIncreasingID),
    (_df_partition_id, E.SparkPartitionID),
    (_df_scalar_subquery, E.ScalarSubquery),
    (_df_udf, E.PythonUDF),
], ids=["count_star", "monotonically_increasing_id", "spark_partition_id",
        "scalar_subquery", "udf"])
def test_what_is_evaluated_over_rows_by_a_plan_or_by_python_never_folds(
        sessions, make, kind):
    """Its literal operands may fold (``+ 1`` is a cast of 1 to long
    beside a long); the node and the expression over it stay, and the
    device path answers as the CPU engine."""
    cpu, dev = sessions
    df = make(cpu)
    folded, _n = L.fold_constants(df.plan)
    over = [e for p in _walk(folded) for x in L.node_expressions(p)
            for e in x.collect(lambda e: e.collect(
                lambda k: isinstance(k, kind)))]
    assert over and not any(isinstance(e, E.Literal) for e in over)
    assert len(over) == len([
        e for p in _walk(df.plan) for x in L.node_expressions(p)
        for e in x.collect(lambda e: e.collect(
            lambda k: isinstance(k, kind)))])
    want = sorted(tuple(r) for r in df.collect())
    assert sorted(tuple(r) for r in make(dev).collect()) == want


@pytest.mark.parametrize("value, error", [
    ("x", ValueError), (3_000_000_000, ArithmeticError)])
def test_an_ansi_cast_error_still_raises_at_run_time(sessions, value, error):
    """A malformed string or an int overflow under ANSI raises on the
    host, so the literal-only cast stays and raises when the query runs,
    as before."""
    cpu, dev = sessions
    bad = Column(E.Cast(E.Literal(value), T.IntegerT, ansi=True))
    for spark in (cpu, dev):
        df = spark.table("t").select(bad.alias("c"))
        assert L.fold_constants(df.plan) == (df.plan, 0)
        with pytest.raises(error):
            df.collect()


def test_a_non_ansi_cast_error_folds_to_a_typed_null(sessions):
    cpu, dev = sessions
    cast = E.Cast(E.Literal("x"), T.IntegerT)
    rows = []
    for spark in (cpu, dev):
        df = spark.table("t").select(
            Column(E.Add(cast, E.Literal(1))).alias("c"))
        folded, n = L.fold_constants(df.plan)
        (alias,) = folded.project_list
        assert n == 1
        assert isinstance(alias.child, E.Literal)
        assert alias.child.value is None
        assert alias.child.data_type == T.IntegerT
        # the alias keeps its name and id; only its child folds
        (orig,) = df.plan.project_list
        assert (alias.name, alias.expr_id) == (orig.name, orig.expr_id)
        rows.append([tuple(r) for r in df.collect()])
    assert rows[0] == rows[1] == [(None,)] * 36


def test_the_fold_leaves_the_dataframes_plan_as_it_was(sessions):
    _cpu, dev = sessions
    df = dev.sql("SELECT v + (1 + 2) AS a, CAST('7' AS INT) * 2 AS b FROM t")
    before = repr(df.plan)
    folded, n = L.fold_constants(df.plan)
    assert n == 2
    assert repr(df.plan) == before
    a, b = folded.project_list
    assert repr(a.child.children[1]) == "lit(3)"
    assert isinstance(b.child, E.Literal) and b.child.value == 14
    assert sorted(tuple(r) for r in df.collect())[:2] == [(3, 14), (4, 14)]


# -- the benchmark's statements ----------------------------------------------

@pytest.mark.parametrize("config,want", [
    ("tpch_q1_sf1", 4), ("tpcds_q51_sf1", 2), ("tpch_q18_sf1", 1),
    ("tpcds_star_2m", 0), ("tpch_q13_sf1", 0), ("tpch_q21_sf1", 0),
    ("tpcds_q67_sf1", 1),
])
def test_constant_fold_count_of_each_benchmark_statement(config, want):
    """q1: its date and the three decimal casts of ``1 - l_discount``
    (twice) and ``1 + l_tax``; q51: ``1200 + 11`` in each table
    expression; Q18: the HAVING's ``cast(300 as decimal(25,2))``; q67:
    ``1200 + 11``."""
    d = os.path.join(C.BENCH_DIR, "configs", config)
    cfg = C.load_json(os.path.join(d, "config.json"))
    with open(os.path.join(d, cfg["statement_file"])) as f:
        text = f.read().format(**cfg["default_binding"])
    spark = TpuSparkSession(dict(CPU_CONF))
    try:
        for table, spec in cfg["tables"].items():
            spark.createDataFrame(
                {c: [] for c, _t in spec["columns"]},
                ", ".join(f"{c} {t}" for c, t in spec["columns"])
            ).createOrReplaceTempView(table)
        before = _totals().get("constantFoldCount", 0)
        spark.plan_physical(spark.sql(text).plan, execute_subqueries=False)
        assert _totals().get("constantFoldCount", 0) - before == want
        assert spark._tls.constant_folds == want
    finally:
        spark.stop()

"""TPC-H Q18 in the specification's own text (clause 2.4.18): a comma-
separated FROM, ``IN (SELECT ... GROUP BY ... HAVING ...)``, a five-column
GROUP BY, ORDER BY and LIMIT, through ``sql(text).collect()``.

The benchmark's configuration ``tpch_q18_sf1`` supplies the generator and
the statement; here they run at a tiny size (3,000 orders, the threshold
lowered so that some forty orders qualify) on the device path against a
plain copy of the reference and against the CPU engine, which plans the
same logical plan. ``o_orderdate`` is a real date column here (the
benchmark writes it as days, ``config.json`` says why). The parser cases
hold what the dialect now takes and what it refuses by name.
"""

from __future__ import annotations

import datetime
import decimal
import os

import numpy as np
import pytest

from benchmarks.harness import cell as C
from benchmarks.harness import tables as TB
from spark_rapids_tpu.sql.session import TpuSparkSession

CONFIG_DIR = os.path.join(C.BENCH_DIR, "configs", "tpch_q18_sf1")
ROWS = {"customer": 300, "orders": 3000, "lineitem": 12000}
QUANTITY = 230
SEEDS = (5, 2147483659, 3000000019)
EPOCH = datetime.date(1970, 1, 1)
# the cell's conf, and a broadcast threshold under lineitem's 30 KB, so that
# the join with lineitem is a TpuShuffledHashJoinExec as it is at scale
# factor 1 and the small build sides are demoted at run time
DEVICE_CONF = dict(C.load_json(os.path.join(CONFIG_DIR, "config.json"))["conf"],
                   **{"spark.sql.autoBroadcastJoinThreshold": "4096"})


def _statement(quantity: int = QUANTITY) -> str:
    with open(os.path.join(CONFIG_DIR, "statement.sql")) as f:
        return f.read().format(quantity=quantity)


def plain_reference(tables: dict, quantity: int) -> list:
    """Q18 as dictionaries and Python integers; nothing of the engine."""
    cust, orders, lines = (tables["customer"], tables["orders"],
                           tables["lineitem"])
    per_order: dict = {}
    for key, qty in zip(lines["l_orderkey"].tolist(),
                        lines["l_quantity"].tolist()):
        per_order.setdefault(key, []).append(qty)
    large = {k for k, q in per_order.items() if sum(q) > quantity * 100}
    names = dict(zip(cust["c_custkey"].tolist(), cust["c_name"].tolist()))
    rows = []
    for okey, ckey, price, day in zip(
            orders["o_orderkey"].tolist(), orders["o_custkey"].tolist(),
            orders["o_totalprice"].tolist(), orders["o_orderdate"].tolist()):
        if okey in large and ckey in names:
            rows.append((str(names[ckey]), ckey, okey,
                         EPOCH + datetime.timedelta(days=day),
                         decimal.Decimal(price).scaleb(-2),
                         decimal.Decimal(sum(per_order[okey])).scaleb(-2)))
    rows.sort(key=lambda r: (-r[4], r[3]))
    return rows[:100]


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    """seed -> (tables, paths): the configuration's generator and writer,
    with ``o_orderdate`` written as a date."""
    gen = C.load_module(os.path.join(CONFIG_DIR, "generator.py"),
                        "tpch_q18_generator")
    specs = C.load_json(os.path.join(CONFIG_DIR, "config.json"))["tables"]
    specs = {t: dict(s, rows=ROWS[t]) for t, s in specs.items()}
    for s in specs.values():
        s.pop("layout", None)
    specs["orders"]["columns"] = [
        [c, "date" if c == "o_orderdate" else t]
        for c, t in specs["orders"]["columns"]]
    out = {}
    for seed in SEEDS:
        tables = gen.generate(seed, ROWS)
        out[seed] = (tables, TB.write_tables(
            tables, specs, str(tmp_path_factory.mktemp(f"q18-{seed}"))))
    return out


def _session(conf: dict, paths: dict) -> TpuSparkSession:
    spark = TpuSparkSession(dict(conf))
    for table, path in paths.items():
        spark.read.parquet(path).createOrReplaceTempView(table)
    return spark


def _totals() -> dict:
    from spark_rapids_tpu.telemetry.prometheus import aggregator
    return dict(aggregator().scrape()[0])


@pytest.mark.parametrize("seed", SEEDS)
def test_q18_on_the_device_equals_the_reference_and_the_cpu_engine(data, seed):
    tables, paths = data[seed]
    want = plain_reference(tables, QUANTITY)
    assert 20 <= len(want) <= 60, len(want)
    assert len({(r[4], r[3]) for r in want}) == len(want), "a tie in the order"
    cpu = _session({"spark.rapids.sql.enabled": "false"}, paths)
    dev = _session(DEVICE_CONF, paths)
    try:
        on_cpu = [tuple(r) for r in cpu.sql(_statement()).collect()]
        before = _totals()
        on_dev = [tuple(r) for r in dev.sql(_statement()).collect()]
        after = _totals()
        assert on_cpu == want
        assert on_dev == want
        assert list(dev.last_rewrite_report.fallbacks) == []
        moved = {k: after.get(k, 0) - before.get(k, 0)
                 for k in ("aggMergeCount", "aggGroupCount", "joinBuildRows",
                           "joinOutputRows", "joinDemotedCount")}
        # the subquery's final aggregate merges the scan partitions'
        # partial results into one row an order; the outer one, one a group
        assert moved["aggMergeCount"] >= 1
        assert moved["aggGroupCount"] == ROWS["orders"] + len(want)
        assert moved["joinDemotedCount"] >= 1
        assert moved["joinBuildRows"] >= len(want)
        # customer x large orders, then their lines
        assert moved["joinOutputRows"] >= len(want)
    finally:
        cpu.stop()
        dev.stop()


def _joins(plan) -> list:
    """(depth, join type, repr of the keys) of every join, top down."""
    out = []

    def walk(p, depth):
        if hasattr(p, "join_type"):
            out.append((depth, p.join_type,
                        repr(getattr(p, "left_keys", ""))
                        + repr(getattr(p, "right_keys", ""))))
        for c in p.children:
            walk(c, depth + 1)
    walk(plan, 0)
    return out


@pytest.mark.parametrize("enabled", ["false", "true"])
def test_the_semi_join_lies_beneath_the_joins_with_customer_and_lineitem(
        data, enabled):
    _tables, paths = data[SEEDS[0]]
    conf = DEVICE_CONF if enabled == "true" else \
        {"spark.rapids.sql.enabled": "false"}
    spark = _session(conf, paths)
    try:
        df = spark.sql(_statement())
        joins = _joins(spark.plan_physical(df.plan, execute_subqueries=False))
        assert [j[1] for j in joins] == ["inner", "inner", "leftsemi"]
        (d_line, _, k_line), (d_cust, _, k_cust), (d_semi, _, k_semi) = joins
        assert "l_orderkey" in k_line and "c_custkey" in k_cust
        assert "o_orderkey" in k_semi and "l_orderkey" in k_semi
        assert d_line < d_cust < d_semi
        # the logical plan a DataFrame holds is not rewritten in place
        assert "Join cross" in repr(df.plan)
    finally:
        spark.stop()


@pytest.fixture(scope="module")
def small():
    spark = TpuSparkSession({"spark.rapids.sql.enabled": "false"})
    spark.createDataFrame({"k": [1, 2, 3, 4], "v": [10, 20, 30, 40]},
                          "k long, v long").createOrReplaceTempView("a")
    spark.createDataFrame({"k2": [2, 3, 3, 5], "w": [1, 2, 3, 4]},
                          "k2 long, w long").createOrReplaceTempView("b")
    spark.createDataFrame({"k3": [3, 4, None], "z": [7, 8, 9]},
                          "k3 long, z long").createOrReplaceTempView("c")
    yield spark
    spark.stop()


PARSER_CASES = [
    ("comma list of two relations",
     "select k, w from a, b where k = k2 order by k, w",
     [(2, 1), (3, 2), (3, 3)]),
    ("comma list of three relations",
     "select k, w, z from a, b, c where k = k2 and k2 = k3 order by w",
     [(3, 2, 7), (3, 3, 7)]),
    ("the third relation connects before the second",
     "select k, w, z from a, b, c where k = k3 and k3 = k2 order by w",
     [(3, 2, 7), (3, 3, 7)]),
    ("select * keeps the text's column order after a reorder",
     "select * from a, b, c where k = k3 and k3 = k2 and w = 2",
     [(3, 30, 3, 2, 3, 7)]),
    ("a relation nothing connects stays a cross join",
     "select k, z from a, c where k = 1 and z < 9 order by z",
     [(1, 7), (1, 8)]),
    ("comma list mixed with JOIN",
     "select k, w, z from a, b join c on k2 = k3 where k = k2 order by w",
     [(3, 2, 7), (3, 3, 7)]),
    ("IN (subquery) keeps a row once",
     "select k from a where k in (select k2 from b) order by k",
     [(2,), (3,)]),
    ("IN (subquery) with GROUP BY and HAVING",
     "select k from a where k in (select k2 from b group by k2 "
     "having sum(w) > 1) order by k",
     [(3,)]),
    ("IN (subquery) over a table the outer query reads too",
     "select k, w from a, b where k = k2 and k in "
     "(select k2 from b where w > 1) order by w",
     [(3, 2), (3, 3)]),
    ("IN (subquery) with a NULL in the subquery drops no match",
     "select k from a where k in (select k3 from c) order by k",
     [(3,), (4,)]),
    ("IN (subquery) beneath an explicit JOIN",
     "select k, w from a join b on k = k2 where v in "
     "(select v from a where k > 2) order by w",
     [(3, 2), (3, 3)]),
    ("IN (subquery) in HAVING",
     "select k2, sum(w) s from b group by k2 having k2 in "
     "(select k from a) order by k2",
     [(2, 1), (3, 5)]),
    ("IN literal list unchanged",
     "select k from a where k in (1, 2) and v not in (10) order by k",
     [(2,)]),
    ("NOT IN (subquery) refused by name",
     "select k from a where k not in (select k2 from b)",
     (NotImplementedError, "NOT IN (subquery)")),
    ("IN (subquery) under OR refused by name",
     "select k from a where k in (select k2 from b) or v = 10",
     (NotImplementedError, "only as a conjunct")),
    ("correlated subquery refused by name",
     "select k from a where k in (select k2 from b where w = v)",
     (NotImplementedError, "correlated subquery: 'v'")),
    ("a misspelt column in a subquery is no correlation",
     "select k from a where k in (select k2 from b where w = nope)",
     (KeyError, "cannot resolve 'nope'")),
    ("a stray token is named",
     "select k from a b c where k = 1",
     (ValueError, "unexpected token 'c'")),
    ("a stray token after the statement is named",
     "select k from a where k = 1 1",
     (ValueError, "trailing tokens near '1'")),
]


@pytest.mark.parametrize("what,sql,want", PARSER_CASES,
                         ids=[c[0] for c in PARSER_CASES])
def test_the_dialect(small, what, sql, want):
    if isinstance(want, tuple):
        error, says = want
        with pytest.raises(error, match=says.replace("(", r"\(")
                           .replace(")", r"\)")):
            small.sql(sql).collect()
    else:
        assert [tuple(r) for r in small.sql(sql).collect()] == want

"""TPC-DS query 67 in the template's own text (``query67.tpl``): a
``GROUP BY ROLLUP`` over eight keys, five of them strings, of a year of
store sales joined to ``date_dim``, ``store`` and ``item``, then ``rank()``
per category over the rolled-up groups, ``rk <= 100``, a ten-column ORDER
BY with nulls first and LIMIT, through ``sql(text).collect()``.

The benchmark's configuration ``tpcds_q67_sf1`` supplies the generator, the
statement and the plain reference; here they run at a few thousand rows on
the device path against the reference and against the CPU engine, which
plans the same logical plan. The grouping-sets cases hold ROLLUP and CUBE to
a brute-force Python grouping on both engines: a key outside an aggregate
function is its nulled copy, inside one the original value (Spark's
ResolveGroupingAnalytics), and a genuine null key keeps a group of its own.
"""

from __future__ import annotations

import itertools
import os
from collections import Counter
from decimal import Decimal

import pytest

from benchmarks.harness import cell as C
from benchmarks.harness import tables as TB
from spark_rapids_tpu.sql import types as T
from spark_rapids_tpu.sql.session import TpuSparkSession

CONFIG_DIR = os.path.join(C.BENCH_DIR, "configs", "tpcds_q67_sf1")
CONFIG = C.load_json(os.path.join(CONFIG_DIR, "config.json"))
ROWS = {"date_dim": 73049, "store_sales": 6000, "store": 12, "item": 300}
CASES = ((5, 1200), (2147483659, 1188))
DEVICE_CONF = dict(CONFIG["conf"])
CPU_CONF = {"spark.rapids.sql.enabled": "false"}
KEYS = ("i_category", "i_class", "i_brand", "i_product_name", "d_year",
        "d_qoy", "d_moy", "s_store_id")


def _statement(dms: int = 1200) -> str:
    with open(os.path.join(CONFIG_DIR, "statement.sql")) as f:
        return f.read().format(dms=dms)


def _reference():
    return C.load_module(os.path.join(CONFIG_DIR, "reference.py"),
                         "tpcds_q67_reference")


def _as_reference_rows(rows):
    """Engine rows in the reference's form: a decimal as (unscaled, 2)."""
    def norm(v):
        if isinstance(v, Decimal):
            assert v.as_tuple().exponent == -2, v
            return (int(v.scaleb(2)), 2)
        return v
    return [tuple(norm(v) for v in r) for r in rows]


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    """(seed, dms) -> (tables, paths): the configuration's generator and
    writer at a few thousand fact rows."""
    gen = C.load_module(os.path.join(CONFIG_DIR, "generator.py"),
                        "tpcds_q67_generator")
    specs = {t: dict(s, rows=ROWS[t]) for t, s in CONFIG["tables"].items()}
    for s in specs.values():
        s.pop("layout", None)
    out = {}
    for seed, dms in CASES:
        tables = gen.generate(seed, ROWS)
        out[seed, dms] = (tables, TB.write_tables(
            tables, specs, str(tmp_path_factory.mktemp(f"q67-{seed}"))))
    return out


def _session(conf: dict, paths: dict) -> TpuSparkSession:
    spark = TpuSparkSession(dict(conf))
    for table, path in paths.items():
        spark.read.parquet(path).createOrReplaceTempView(table)
    return spark


def _totals() -> dict:
    from spark_rapids_tpu.telemetry.prometheus import aggregator
    return dict(aggregator().scrape()[0])


@pytest.mark.parametrize("seed,dms", CASES)
def test_q67_on_the_device_equals_the_reference_and_the_cpu_engine(
        data, seed, dms):
    tables, paths = data[seed, dms]
    ref = _reference()
    want = ref.answer(tables, {"dms": dms})
    year_rows = len(ref.joined(tables, {"dms": dms})[2])
    assert len(want) == 100 and want[0][:8] == (None,) * 8
    assert any(r[9] > 1 for r in want)
    for broken in (ref.finest_only_control_answer,
                   ref.unpartitioned_rank_control_answer,
                   ref.nulls_last_control_answer):
        assert broken(tables, {"dms": dms}) != want, broken
    cpu = _session(CPU_CONF, paths)
    dev = _session(DEVICE_CONF, paths)
    try:
        on_cpu = cpu.sql(_statement(dms)).collect()
        before = _totals()
        df = dev.sql(_statement(dms))
        on_dev = df.collect()
        after = _totals()
        assert _as_reference_rows(on_cpu) == want
        assert [tuple(r) for r in on_dev] == [tuple(r) for r in on_cpu]
        assert list(dev.last_rewrite_report.fallbacks) == []
        assert [f.data_type for f in df.schema.fields] == (
            [T.StringT] * 4 + [T.IntegerT] * 3 + [T.StringT,
                                                  T.DecimalType(28, 2),
                                                  T.IntegerT])
        moved = {k: after.get(k, 0) - before.get(k, 0)
                 for k in ("expandRows", "expandTime", "aggKeyWords",
                           "windowRows", "retryCount", "splitRetryCount")}
        assert moved["expandRows"] == 9 * year_rows
        assert moved["expandTime"] > 0
        groups = sum(ref.level_groups(tables, {"dms": dms}))
        assert moved["windowRows"] == groups
        # every pass sorts at least its lanes x 23 key words (nine keys'
        # validity and a word each, the five strings' lengths)
        assert moved["aggKeyWords"] >= 23 * (9 * year_rows + groups)
        assert moved["retryCount"] == moved["splitRetryCount"] == 0
    finally:
        cpu.stop()
        dev.stop()


def test_q67_plans_a_pruned_expand_under_the_aggregates_a_window_and_a_topn(
        data):
    """The device plan of the statement: the Expand carries the two
    columns the sum reads and the eight keys, not the joins' seventeen;
    ``srt_expand`` is the program ``tools trace`` and the ledger show."""
    from spark_rapids_tpu import jit_cache
    _tables, paths = data[CASES[0]]
    dev = _session(DEVICE_CONF, paths)
    try:
        dev.start_capture()
        dev.sql(_statement()).collect()
        plans = dev.get_captured_plans()
        plan = "\n".join(p.tree_string() for p in plans)
    finally:
        dev.stop()
    assert "TpuExpand [9 sets]" in plan, plan
    assert "TpuHashAggregate mode=partial" in plan, plan
    assert "TpuHashAggregate mode=final" in plan, plan
    assert "TpuWindow [Rank()" in plan, plan
    assert "TpuTopN n=100" in plan, plan
    assert "Cpu" not in plan, plan

    def find(node):
        if type(node).__name__ == "TpuExpandExec":
            return node
        for c in node.children:
            hit = find(c)
            if hit is not None:
                return hit
        return None
    expand = next(e for e in map(find, plans) if e is not None)
    assert [a.name for a in expand.output] == (
        ["ss_sales_price", "ss_quantity"] + list(KEYS)
        + ["spark_grouping_id"])
    assert [a.name for a in expand.child.output] == (
        ["ss_sales_price", "ss_quantity"] + list(KEYS))
    programs = {jit_cache.program_in(v)
                for cache in jit_cache._CACHES.values()
                for v in cache._data.values()}
    assert "srt_expand" in programs, sorted(p for p in programs if p)
    # the grouping ids are constants of the program: a batch's dispatch
    # uploads no scalar
    from spark_rapids_tpu.exec import basic as B
    from spark_rapids_tpu.ops import exprs as X
    assert X.collect_literals(B._expand_exprs(expand.projections)) == ([], [])


# -- grouping sets against a brute-force grouping -------------------------

ROWS_T = [("a", "x", 1, Decimal("10.50"), 3), ("a", "x", 2, None, 1),
          ("a", "y", 1, Decimal("2.25"), 2), ("b", "x", 1, Decimal("7.00"), 5),
          ("b", None, 2, Decimal("1.00"), 1), (None, "y", 1, Decimal("4.00"), 2),
          ("c", "z", None, Decimal("99999.99"), 100)]
SCHEMA_T = "k1 string, k2 string, k3 int, v decimal(7,2), q int"


def _views(conf):
    spark = TpuSparkSession(dict(conf))
    spark.createDataFrame(ROWS_T, SCHEMA_T).createOrReplaceTempView("t")
    return spark


@pytest.fixture(scope="module")
def engines():
    out = {"cpu": _views(CPU_CONF), "dev": _views(DEVICE_CONF)}
    yield out
    for spark in out.values():
        spark.stop()


def brute_force(keys, sets, value):
    """One row a (grouping set, distinct values of its keys): the keys out
    of the set None, ``value(rows)`` over the rows of the group; a set's
    position keeps a genuine None apart from a rolled-up one."""
    idx = {"k1": 0, "k2": 1, "k3": 2}
    out = []
    for s in sets:
        groups: dict = {}
        for r in ROWS_T:
            groups.setdefault(tuple(r[idx[k]] for k in s), []).append(r)
        for key, rows in groups.items():
            at = dict(zip(s, key))
            out.append(tuple(at.get(k) for k in keys) + value(rows))
    return Counter(out)


def _rows(spark, text) -> Counter:
    return Counter(tuple(r) for r in spark.sql(text).collect())


def _rollup_sets(keys):
    return [keys[:n] for n in range(len(keys), -1, -1)]


GROUPINGS = {
    "rollup_of_one_key": ("rollup(k1)", ("k1",), _rollup_sets(("k1",))),
    "rollup_of_two_keys": ("rollup(k1, k2)", ("k1", "k2"),
                           _rollup_sets(("k1", "k2"))),
    "cube_of_two_keys": ("cube(k1, k3)", ("k1", "k3"),
                         [s for n in range(2, -1, -1)
                          for s in itertools.combinations(("k1", "k3"), n)]),
}


@pytest.mark.parametrize("case", sorted(GROUPINGS))
def test_grouping_sets_equal_a_brute_force_grouping(engines, case):
    spec, keys, sets = GROUPINGS[case]
    text = (f"select {', '.join(keys)}, sum(coalesce(v * q, 0)) s, "
            f"count(*) n from t group by {spec}")
    want = brute_force(keys, sets, lambda rows: (
        sum((r[3] * r[4] for r in rows if r[3] is not None), Decimal(0)),
        len(rows)))
    for engine, spark in engines.items():
        assert _rows(spark, text) == want, engine
    assert list(engines["dev"].last_rewrite_report.fallbacks) == []


def test_a_rollup_of_eight_keys_equals_a_brute_force_grouping(engines):
    """Eight keys, the statement's count and shape: three strings, an
    int, and four more computed from them, every level a row of its own."""
    keys = ("k1", "k2", "k3", "a", "b", "c", "d", "e")
    text = ("select k1, k2, k3, a, b, c, d, e, sum(v) s from (select k1, k2, "
            "k3, upper(k1) a, k3 + 1 b, concat(k1, k2) c, q d, lower(k2) e, v "
            "from t) x group by rollup(k1, k2, k3, a, b, c, d, e)")

    def derived(r):
        k1, k2, k3, v, q = r
        cat = None if k1 is None or k2 is None else k1 + k2
        return (k1, k2, k3, None if k1 is None else k1.upper(),
                None if k3 is None else k3 + 1, cat, q,
                None if k2 is None else k2.lower())
    want = []
    for n in range(8, -1, -1):
        groups: dict = {}
        for r in ROWS_T:
            groups.setdefault(derived(r)[:n], []).append(r)
        for key, rows in groups.items():
            vals = [r[3] for r in rows if r[3] is not None]
            want.append(key + (None,) * (8 - n)
                        + (sum(vals) if vals else None,))
    for engine, spark in engines.items():
        assert _rows(spark, text) == Counter(want), engine
    assert list(engines["dev"].last_rewrite_report.fallbacks) == []


def test_a_genuine_null_key_stays_apart_from_a_rolled_up_one(engines):
    """``k1`` is null on one row: its group (None, 'y') and the level that
    rolls ``k1`` up, (None, None), are two rows, and ``count`` tells them
    apart; the grand total covers all seven rows."""
    text = ("select k1, k2, count(*) n from t group by rollup(k1, k2) "
            "having k1 is null")
    for engine, spark in engines.items():
        assert _rows(spark, text) == Counter(
            [(None, None, 1), (None, None, 7), (None, "y", 1)]), engine


def test_a_key_inside_an_aggregate_reads_its_original_values(engines):
    """Spark's ResolveGroupingAnalytics: outside an aggregate function a
    key is its nulled copy, inside one the value the row has, so the grand
    total's max(k1) is 'c' and not null; HAVING reads the nulled key."""
    text = ("select k1, max(k1) m, min(k3) lo from t group by rollup(k1) "
            "having k1 is null or k1 = 'c'")
    for engine, spark in engines.items():
        assert _rows(spark, text) == Counter(
            [(None, "c", 1), (None, None, 1), ("c", "c", None)]), engine


def test_a_null_price_goes_through_coalesce(engines):
    """``coalesce(v * q, 0)`` turns the null price into 0 before the sum:
    the group of k1 = 'a', k2 = 'x' sums 31.50, not null, and the sum
    without coalesce skips the null."""
    text = ("select k1, k2, sum(coalesce(v * q, 0)) s, sum(v * q) raw "
            "from t where k1 = 'a' group by rollup(k1, k2)")
    want = [(None, None, Decimal("36.00"), Decimal("36.00")),
            ("a", None, Decimal("36.00"), Decimal("36.00")),
            ("a", "x", Decimal("31.50"), Decimal("31.50")),
            ("a", "y", Decimal("4.50"), Decimal("4.50"))]
    for engine, spark in engines.items():
        assert _rows(spark, text) == Counter(want), engine
    only_null = ("select k3, sum(coalesce(v * q, 0)) s, sum(v * q) raw "
                 "from t where k1 = 'a' and k3 = 2 group by rollup(k3)")
    for engine, spark in engines.items():
        assert _rows(spark, only_null) == Counter(
            [(2, Decimal("0.00"), None), (None, Decimal("0.00"), None)]), \
            engine


def test_rank_ties_and_a_null_category_partition(engines):
    """rank() per category over a rollup, as Spark partitions it: on the
    nulled key, so the grand total shares the null partition with the
    genuine null category's rows (q67's item has no null category, so
    there its total is alone); equal sums share a rank and leave a gap."""
    text = ("select k1, k3, s, rank() over (partition by k1 order by s "
            "desc) rk from (select k1, k3, sum(q) s from t "
            "group by rollup(k1, k3)) x order by k1, k3, s, rk")
    want = [(None, None, 2, 2), (None, None, 114, 1), (None, 1, 2, 2),
            ("a", None, 6, 1), ("a", 1, 5, 2), ("a", 2, 1, 3),
            ("b", None, 6, 1), ("b", 1, 5, 2), ("b", 2, 1, 3),
            ("c", None, 100, 1), ("c", None, 100, 1)]
    for engine, spark in engines.items():
        got = [tuple(r) for r in spark.sql(text).collect()]
        assert got == want, engine
    tie = ("select k1, s, rank() over (partition by k3 order by s desc) rk "
           "from (select k1, k3, sum(q) s from t group by rollup(k1, k3)) "
           "x where k3 = 1 order by rk, k1")
    for engine, spark in engines.items():
        got = [tuple(r) for r in spark.sql(tie).collect()]
        assert got == [("a", 5, 1), ("b", 5, 1), (None, 2, 3)], engine


REFUSED = {
    "grouping_sets": ("select k1, count(*) from t group by grouping sets "
                      "((k1), ())", "GROUPING SETS"),
    "grouping": ("select k1, grouping(k1), count(*) from t "
                 "group by rollup(k1)", "grouping()"),
    "grouping_id": ("select k1, grouping_id(), count(*) from t "
                    "group by cube(k1)", "grouping_id()"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_what_grouping_sets_refuse_by_name(engines, case):
    text, says = REFUSED[case]
    for spark in engines.values():
        with pytest.raises(NotImplementedError, match=says.replace(
                "(", r"\(").replace(")", r"\)")):
            spark.sql(text).collect()

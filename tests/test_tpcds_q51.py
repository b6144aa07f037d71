"""TPC-DS query 51 in the template's own text (``query51.tpl``): two common
table expressions, each a running ``sum(sum(decimal))`` window over a grouped
aggregate, a full outer join between them, two running ``max(decimal)``
windows over its output, a filter on the windows' columns, ORDER BY and
LIMIT, through ``sql(text).collect()``.

The benchmark's configuration ``tpcds_q51_sf1`` supplies the generator and
the statement; here they run at a few thousand rows over 40 items, with
``d_date`` as a real ``date`` column, on the device path against a
brute-force loop, against the configuration's plain reference and against
the CPU engine, which plans the same logical plan. The dialect cases hold
what the parser now takes (``WITH``, a window over a grouped aggregate) and
what it refuses by name.
"""

from __future__ import annotations

import datetime
import os
from decimal import Decimal

import pytest

from benchmarks.harness import cell as C
from benchmarks.harness import tables as TB
from spark_rapids_tpu.sql import types as T
from spark_rapids_tpu.sql.session import TpuSparkSession

CONFIG_DIR = os.path.join(C.BENCH_DIR, "configs", "tpcds_q51_sf1")
CONFIG = C.load_json(os.path.join(CONFIG_DIR, "config.json"))
ROWS = {"date_dim": 73049, "store_sales": 6000, "web_sales": 1500, "item": 40}
CASES = ((5, 1200), (2147483659, 1200), (3000000019, 1188))
DEVICE_CONF = dict(CONFIG["conf"])
EPOCH = datetime.date(1970, 1, 1)


def _statement(dms: int = 1200) -> str:
    with open(os.path.join(CONFIG_DIR, "statement.sql")) as f:
        return f.read().format(dms=dms)


def brute_force(tables: dict, dms: int):
    """Query 51 row by row; nothing of the engine, nothing of the
    reference. Returns the rows and the rows each window sees."""
    date = tables["date_dim"]
    day_of = {sk: day for sk, day, seq in zip(
        date["d_date_sk"].tolist(), date["d_date"].tolist(),
        date["d_month_seq"].tolist()) if dms <= seq <= dms + 11}

    def cume(fact: dict, prefix: str) -> dict:
        sums: dict = {}
        for sk, item, price in zip(fact[f"{prefix}_sold_date_sk"].tolist(),
                                   fact[f"{prefix}_item_sk"].tolist(),
                                   fact[f"{prefix}_sales_price"].tolist()):
            if sk in day_of:
                key = (item, day_of[sk])
                sums[key] = sums.get(key, 0) + price
        return {(item, day): sum(v for (i2, d2), v in sums.items()
                                 if i2 == item and d2 <= day)
                for item, day in sums}

    web = cume(tables["web_sales"], "ws")
    store = cume(tables["store_sales"], "ss")
    joined = sorted(set(web) | set(store))
    out = []
    for item, day in joined:
        seen = [(web.get((i2, d2)), store.get((i2, d2)))
                for i2, d2 in joined if i2 == item and d2 <= day]
        web_best = max((w for w, _ in seen if w is not None), default=None)
        store_best = max((s for _, s in seen if s is not None), default=None)
        if web_best is not None and store_best is not None \
                and web_best > store_best:
            out.append((item, day, web.get((item, day)),
                        store.get((item, day)), web_best, store_best))
    return out[:100], len(web) + len(store) + len(joined)


def _as_reference_rows(rows):
    """Engine rows in the reference's form: a date as days since 1970, a
    decimal as (unscaled, scale)."""
    def norm(v):
        if isinstance(v, datetime.date):
            return (v - EPOCH).days
        if isinstance(v, Decimal):
            assert v.as_tuple().exponent == -2, v
            return (int(v.scaleb(2)), 2)
        return v
    return [tuple(norm(v) for v in r) for r in rows]


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    """(seed, dms) -> (tables, paths): the configuration's generator and
    writer, ``d_date`` written as a real date column."""
    gen = C.load_module(os.path.join(CONFIG_DIR, "generator.py"),
                        "tpcds_q51_generator")
    specs = {t: dict(s, rows=ROWS[t]) for t, s in CONFIG["tables"].items()}
    for s in specs.values():
        s.pop("layout", None)
    specs["date_dim"]["columns"] = [
        [c, "date" if c == "d_date" else t]
        for c, t in specs["date_dim"]["columns"]]
    out = {}
    for seed, dms in CASES:
        tables = gen.generate(seed, ROWS)
        out[seed, dms] = (tables, TB.write_tables(
            tables, specs, str(tmp_path_factory.mktemp(f"q51-{seed}"))))
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
def test_q51_on_the_device_equals_the_reference_and_the_cpu_engine(
        data, seed, dms):
    tables, paths = data[seed, dms]
    brute, window_rows = brute_force(tables, dms)
    want = [r[:2] + tuple(None if v is None else (v, 2) for v in r[2:])
            for r in brute]
    assert len(want) >= 10, want
    assert any(r[2] is None for r in want) and any(r[3] is None for r in want)
    reference = C.load_module(os.path.join(CONFIG_DIR, "reference.py"),
                              "tpcds_q51_reference")
    assert reference.answer(tables, {"dms": dms}) == want
    # (control_answer, float32, goes wrong only at the cell's own size)
    for broken in (reference.whole_frame_answer,
                   reference.outer_control_answer):
        assert broken(tables, {"dms": dms}) != want, broken
    cpu = _session({"spark.rapids.sql.enabled": "false"}, paths)
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
        assert [f.data_type for f in df.schema.fields] == [
            T.LongT, T.DateT] + [T.DecimalType(27, 2)] * 4
        moved = {k: after.get(k, 0) - before.get(k, 0)
                 for k in ("windowRows", "windowDecimalAggCount",
                           "windowTime", "retryCount", "splitRetryCount")}
        assert moved["windowRows"] == window_rows
        assert moved["windowDecimalAggCount"] == 4    # sum, sum, max, max
        assert moved["windowTime"] > 0
        assert moved["retryCount"] == moved["splitRetryCount"] == 0
    finally:
        cpu.stop()
        dev.stop()


def test_q51_plans_three_window_execs_two_aggregates_and_a_full_outer_join(
        data):
    """The device plan of the statement, and the program its windows run:
    ``srt_window`` is what the ledger's ``breakdown`` and ``tools trace``
    show."""
    from spark_rapids_tpu import jit_cache
    _tables, paths = data[CASES[0]]
    dev = _session(DEVICE_CONF, paths)
    try:
        dev.start_capture()
        dev.sql(_statement()).collect()
        plan = "\n".join(p.tree_string() for p in dev.get_captured_plans())
    finally:
        dev.stop()
    assert plan.count("TpuWindow ") == 3, plan
    assert plan.count("TpuHashAggregate mode=final") == 2, plan
    assert "HashJoin full" in plan, plan
    assert "Cpu" not in plan, plan
    programs = {jit_cache.program_in(v)
                for cache in jit_cache._CACHES.values()
                for v in cache._data.values()}
    assert "srt_window" in programs, sorted(p for p in programs if p)


def _small_session(conf: dict) -> TpuSparkSession:
    """Hand-made rows with the nulls dsdgen leaves in the fact tables'
    foreign keys, and a date_dim of January 2000."""
    spark = TpuSparkSession(dict(conf))
    first = datetime.date(2000, 1, 1)
    spark.createDataFrame(
        [(2451545 + i, first + datetime.timedelta(days=i), 1200)
         for i in range(10)] + [(None, None, 1200)],
        "d_date_sk long, d_date date, d_month_seq int"
    ).createOrReplaceTempView("date_dim")
    price = Decimal("70000.25")
    spark.createDataFrame(
        [(2451545, 1, price), (2451545, 1, price), (2451547, 1, None),
         (None, 1, price), (2451546, None, price), (2451548, 2, price),
         (2451549, 1, price)],
        "ss_sold_date_sk long, ss_item_sk long, ss_sales_price decimal(7,2)"
    ).createOrReplaceTempView("store_sales")
    spark.createDataFrame(
        [(2451545, 1, price * 3), (2451546, 1, price), (None, 2, price),
         (2451546, None, price), (2451547, 2, price), (2451549, 2, price)],
        "ws_sold_date_sk long, ws_item_sk long, ws_sales_price decimal(7,2)"
    ).createOrReplaceTempView("web_sales")
    return spark


def test_q51_with_null_keys_and_null_prices():
    """A null date key joins nothing, a null item is filtered by the
    statement's own conjunct, a group of null prices sums to null and the
    running sum skips it; the engines agree and the rows are these."""
    cpu = _small_session({"spark.rapids.sql.enabled": "false"})
    dev = _small_session(DEVICE_CONF)
    try:
        on_cpu = [tuple(r) for r in cpu.sql(_statement()).collect()]
        on_dev = [tuple(r) for r in dev.sql(_statement()).collect()]
        assert list(dev.last_rewrite_report.fallbacks) == []
    finally:
        cpu.stop()
        dev.stop()
    d = [datetime.date(2000, 1, i) for i in range(1, 6)]
    p = Decimal("70000.25")
    assert on_cpu == on_dev == [
        (1, d[0], 3 * p, 2 * p, 3 * p, 2 * p),
        (1, d[1], 4 * p, None, 4 * p, 2 * p),
        (1, d[2], None, 2 * p, 4 * p, 2 * p),    # store: a null-price group
        (1, d[4], None, 3 * p, 4 * p, 3 * p),
        (2, d[4], 2 * p, None, 2 * p, p)]


# -- the dialect -----------------------------------------------------------

@pytest.fixture(scope="module")
def views():
    out = {}
    for name, conf in (("cpu", {"spark.rapids.sql.enabled": "false"}),
                       ("dev", DEVICE_CONF)):
        spark = TpuSparkSession(dict(conf))
        spark.createDataFrame(
            {"k": [1, 1, 2, 2, 3], "d": [1, 2, 1, 3, 1],
             "v": [Decimal("10.50"), Decimal("2.25"), None,
                   Decimal("-4.00"), Decimal("99999.99")]},
            "k int, d int, v decimal(7,2)").createOrReplaceTempView("t")
        spark.createDataFrame({"k": [1, 2, 9], "name": ["a", "b", "z"]},
                              "k int, name string"
                              ).createOrReplaceTempView("u")
        out[name] = spark
    yield out
    for spark in out.values():
        spark.stop()


_D = Decimal
DIALECT = {
    "cte_used_twice": (
        "with s as (select k, sum(v) total from t group by k) "
        "select a.k, a.total, b.total from s a join s b on a.k = b.k + 1 "
        "order by a.k",
        [(2, _D("-4.00"), _D("12.75")), (3, _D("99999.99"), _D("-4.00"))]),
    "cte_shadows_a_view": (
        "with u as (select k, d from t where d = 3) "
        "select k, d from u order by k", [(2, 3)]),
    "cte_reads_an_earlier_cte": (
        "with a as (select k, v from t where v is not null), "
        "b as (select k, count(*) n from a group by k) "
        "select u.name, b.n from b, u where b.k = u.k order by u.name",
        [("a", 2), ("b", 1)]),
    "cte_inside_a_derived_table": (
        "select x.k from (with a as (select k from u where k < 9) "
        "select k from a) x order by x.k", [(1,), (2,)]),
    "cte_named_recursive": (
        "with recursive as (select k from u) select count(*) from recursive",
        [(3,)]),
    "window_over_a_grouped_aggregate": (
        "select k, sum(v) s, sum(sum(v)) over (order by k rows between "
        "unbounded preceding and current row) running, "
        "rank() over (order by sum(v) desc) r from t group by k order by k",
        [(1, _D("12.75"), _D("12.75"), 2), (2, _D("-4.00"), _D("8.75"), 3),
         (3, _D("99999.99"), _D("100008.74"), 1)]),
    "aggregates_in_a_windows_keys": (
        "select k, row_number() over (partition by count(*) order by k) rn, "
        "count(*) + sum(count(*)) over () total from t group by k "
        "order by k", [(1, 1, 7), (2, 2, 7), (3, 1, 6)]),
    "window_under_having": (
        "select k, min(sum(v)) over () lowest from t group by k "
        "having count(*) > 1 order by k",
        [(1, _D("-4.00")), (2, _D("-4.00"))]),
}


@pytest.mark.parametrize("case", sorted(DIALECT))
def test_the_dialect_on_both_engines(views, case):
    text, want = DIALECT[case]
    for engine, spark in views.items():
        got = [tuple(r) for r in spark.sql(text).collect()]
        assert got == want, (engine, got)
    assert list(views["dev"].last_rewrite_report.fallbacks) == []


REFUSED = {
    "with_recursive": (
        "with recursive r as (select k from u) select k from r",
        NotImplementedError, "WITH RECURSIVE"),
    "cte_column_list": (
        "with r (a, b) as (select k, name from u) select a from r",
        NotImplementedError, "column list"),
    "window_in_having": (
        "select k from t group by k having sum(sum(v)) over () > 0",
        NotImplementedError, "window function in HAVING"),
    "window_in_group_by": (
        "select count(*) from t group by rank() over (order by k)",
        NotImplementedError, "window function in GROUP BY"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_what_the_dialect_refuses_by_name(views, case):
    text, error, says = REFUSED[case]
    for spark in views.values():
        with pytest.raises(error, match=says):
            spark.sql(text).collect()


@pytest.mark.parametrize("text,says", [
    ("select k, avg(v) over (partition by k order by d) from t",
     "window average over DecimalType"),
    ("select k, max(name) over (partition by k) from u",
     "window aggregate over string")], ids=["avg_decimal", "max_string"])
def test_window_aggregates_the_device_refuses_by_name(views, text, says):
    """Under the cell's conf (``forceDevice``) a fallback is an error that
    names what cannot run. The CPU engine answers for the string; it has
    no decimal division for a window's average and says so too."""
    with pytest.raises(Exception, match=says):
        views["dev"].sql(text).collect()
    if "avg" in text:
        with pytest.raises(NotImplementedError, match="window average over"):
            views["cpu"].sql(text).collect()
    else:
        assert views["cpu"].sql(text).collect()


def test_a_cte_is_in_scope_for_its_statement_only(views):
    spark = views["cpu"]
    spark.sql("with gone as (select k from u) select k from gone").collect()
    with pytest.raises(KeyError):
        spark.sql("select k from gone").collect()

"""TPC-H Q21 in the specification's own text (clause 2.4.21): a correlated
``EXISTS`` and a correlated ``NOT EXISTS`` over two more aliases of
``lineitem``, each with the residual ``l_suppkey <> l1.l_suppkey``, a
comma-separated FROM of four relations, GROUP BY, ORDER BY and LIMIT,
through ``sql(text).collect()``.

The benchmark's configuration ``tpch_q21_sf1`` supplies the generator and
the statement; here they run at a tiny size (3,000 orders, 100 suppliers,
so that two lines of an order share a supplier now and then and the ``<>``
decides) on the device path against a brute-force loop and against the CPU
engine, which plans the same logical plan. The dialect cases hold what the
parser and the decorrelation rule now take and what they refuse by name.
"""

from __future__ import annotations

import os

import pytest

from benchmarks.harness import cell as C
from benchmarks.harness import tables as TB
from spark_rapids_tpu.sql.session import TpuSparkSession

CONFIG_DIR = os.path.join(C.BENCH_DIR, "configs", "tpch_q21_sf1")
ROWS = {"supplier": 100, "orders": 3000, "lineitem": 12000, "nation": 25}
NATION = "SAUDI ARABIA"
SEEDS = (5, 2147483659, 3000000019)
# the cell's conf, and a broadcast threshold under lineitem's files, so that
# the semi and the anti join are TpuShuffledHashJoinExecs as they are at
# scale factor 1
DEVICE_CONF = dict(C.load_json(os.path.join(CONFIG_DIR, "config.json"))["conf"],
                   **{"spark.rapids.sql.autoBroadcastJoinThreshold": "4096"})


def _statement(nation: str = NATION) -> str:
    with open(os.path.join(CONFIG_DIR, "statement.sql")) as f:
        return f.read().format(nation=nation)


def brute_force(tables: dict, nation: str):
    """Q21 line by line over each order's lines; nothing of the engine.
    Returns the rows and the candidate pairs each subquery's condition
    is evaluated on (the engine's ``joinConditionPairs``)."""
    li, orders, supp, nat = (tables["lineitem"], tables["orders"],
                             tables["supplier"], tables["nation"])
    lines: dict = {}
    for okey, skey, commit, receipt in zip(
            li["l_orderkey"].tolist(), li["l_suppkey"].tolist(),
            li["l_commitdate"].tolist(), li["l_receiptdate"].tolist()):
        lines.setdefault(okey, []).append((skey, receipt > commit))
    finished = {k for k, s in zip(orders["o_orderkey"].tolist(),
                                  orders["o_orderstatus"].tolist()) if s == "F"}
    nations = {k for k, n in zip(nat["n_nationkey"].tolist(),
                                 nat["n_name"].tolist()) if n == nation}
    names = {k: str(n) for k, n, nk in zip(
        supp["s_suppkey"].tolist(), supp["s_name"].tolist(),
        supp["s_nationkey"].tolist()) if nk in nations}
    numwait: dict = {}
    pairs = 0
    for okey, of_order in lines.items():
        for skey, late in of_order:
            if not late:
                continue
            pairs += len(of_order)                       # l1 x l2
            if not any(s2 != skey for s2, _ in of_order):
                continue
            pairs += sum(1 for _, late3 in of_order if late3)   # l1 x l3
            if any(s3 != skey and late3 for s3, late3 in of_order):
                continue
            if okey in finished and skey in names:
                numwait[names[skey]] = numwait.get(names[skey], 0) + 1
    rows = sorted(numwait.items(), key=lambda nc: (-nc[1], nc[0]))[:100]
    return rows, pairs


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    """seed -> (tables, paths): the configuration's generator and writer."""
    gen = C.load_module(os.path.join(CONFIG_DIR, "generator.py"),
                        "tpch_q21_generator")
    specs = C.load_json(os.path.join(CONFIG_DIR, "config.json"))["tables"]
    specs = {t: dict(s, rows=ROWS[t]) for t, s in specs.items()}
    for s in specs.values():
        s.pop("layout", None)
    out = {}
    for seed in SEEDS:
        tables = gen.generate(seed, ROWS)
        out[seed] = (tables, TB.write_tables(
            tables, specs, str(tmp_path_factory.mktemp(f"q21-{seed}"))))
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
def test_q21_on_the_device_equals_the_reference_and_the_cpu_engine(data, seed):
    tables, paths = data[seed]
    want, pairs = brute_force(tables, NATION)
    assert len(want) >= 2, want
    reference = C.load_module(os.path.join(CONFIG_DIR, "reference.py"),
                              "tpch_q21_reference")
    assert reference.answer(tables, {"nation": NATION}) == want
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
                 for k in ("decorrelatedSubqueryCount", "joinConditionalCount",
                           "joinConditionPairs", "joinConditionTime",
                           "retryCount", "splitRetryCount")}
        assert moved["decorrelatedSubqueryCount"] == 2
        assert moved["joinConditionalCount"] >= 2
        assert moved["joinConditionPairs"] == pairs
        assert moved["joinConditionTime"] > 0
        assert moved["retryCount"] == moved["splitRetryCount"] == 0
    finally:
        cpu.stop()
        dev.stop()


def test_the_conditional_mask_runs_under_its_own_program_name(data):
    """``srt_join_cond_mask`` is what the ledger's ``breakdown`` and ``tools
    trace`` show; the unconditioned ``srt_join_mask`` keeps its name and is
    not what a residual runs."""
    from spark_rapids_tpu import jit_cache as JC
    from spark_rapids_tpu.ops import join as J
    for cache in (J._COND_MASK_CACHE, J._MASK_CACHE):
        cache.clear()
    _tables, paths = data[SEEDS[0]]
    dev = _session(DEVICE_CONF, paths)
    try:
        dev.sql(_statement()).collect()
    finally:
        dev.stop()
    with J._COND_MASK_CACHE._lock:
        built = {JC.program_in(v) for v in J._COND_MASK_CACHE._data.values()}
    assert built == {"srt_join_cond_mask"}
    # one program a join type: the two subqueries' conditions differ in
    # nothing but the literal-free structure they share
    assert len(J._COND_MASK_CACHE._data) == 2
    assert not J._MASK_CACHE._data


def _joins(plan) -> list:
    """(depth, join type, keys, condition) of every join, top down."""
    out = []

    def walk(p, depth):
        if hasattr(p, "join_type"):
            out.append((depth, p.join_type,
                        repr(getattr(p, "left_keys", ""))
                        + repr(getattr(p, "right_keys", "")),
                        repr(getattr(p, "condition", None))))
        for c in p.children:
            walk(c, depth + 1)
    walk(plan, 0)
    return out


@pytest.mark.parametrize("enabled", ["false", "true"])
def test_the_semi_and_the_anti_join_lie_beneath_the_inner_joins(data, enabled):
    _tables, paths = data[SEEDS[0]]
    conf = DEVICE_CONF if enabled == "true" else \
        {"spark.rapids.sql.enabled": "false"}
    spark = _session(conf, paths)
    try:
        df = spark.sql(_statement())
        physical = spark.plan_physical(df.plan, execute_subqueries=False)
        joins = _joins(physical)
        assert [j[1] for j in joins] == ["inner", "inner", "inner",
                                         "leftanti", "leftsemi"]
        nation, orders, supplier, anti, semi = joins
        assert "n_nationkey" in nation[2] and "o_orderkey" in orders[2]
        assert "s_suppkey" in supplier[2]
        assert nation[0] < orders[0] < supplier[0] < anti[0] < semi[0]
        for join in (anti, semi):
            # the equality keys the join, the <> is its residual
            assert join[2].count("l_orderkey") == 2, join
            assert "Not(EqualTo(l_suppkey" in join[3], join
        for join in (nation, orders, supplier):
            assert join[3] == "None"
        if enabled == "true":
            text = physical.tree_string()
            assert "TpuShuffledHashJoin leftsemi" in text
            assert "TpuShuffledHashJoin leftanti" in text
            assert list(spark.last_rewrite_report.fallbacks) == []
        # the logical plan a DataFrame holds is not rewritten in place
        assert "EXISTS (subquery)" in repr(df.plan)
    finally:
        spark.stop()


@pytest.fixture(scope="module")
def small():
    """Both engines over the same three tables, with nulls in the keys and
    in the columns the residual conditions read."""
    sessions = [TpuSparkSession({"spark.rapids.sql.enabled": "false"}),
                TpuSparkSession({"spark.rapids.sql.enabled": "true",
                                 "spark.rapids.sql.test.forceDevice": "true",
                                 "spark.rapids.sql.explain": "NOT_ON_GPU"})]
    for spark in sessions:
        spark.createDataFrame(
            {"k": [1, 2, 3, 4, None, 3], "v": [10, 20, 30, 40, 50, None]},
            "k long, v long").createOrReplaceTempView("a")
        spark.createDataFrame(
            {"k2": [2, 3, 3, 5, None, 4], "w": [1, 2, 30, 4, 5, None],
             "u": [20, 1, 1, 1, 1, 1]},
            "k2 long, w long, u long").createOrReplaceTempView("b")
        spark.createDataFrame({"k3": [3, 4, None], "z": [7, 8, 9]},
                              "k3 long, z long").createOrReplaceTempView("c")
    yield sessions
    for spark in sessions:
        spark.stop()


_REFUSED = (NotImplementedError, "correlated subquery")
DIALECT_CASES = [
    ("Q4's EXISTS: an equality alone",
     "select k, v from a where exists (select * from b where k2 = k) "
     "order by k, v",
     [(2, 20), (3, None), (3, 30), (4, 40)]),
    ("Q22's NOT EXISTS: a null key has no partner and stays",
     "select k, v from a where not exists (select * from b where k2 = k) "
     "order by k, v",
     [(None, 50), (1, 10)]),
    ("EXISTS with a residual <>: a null on either side is no pass",
     "select k, v from a where exists (select * from b where k2 = k "
     "and w <> v) order by k, v",
     [(2, 20), (3, 30)]),
    ("NOT EXISTS with a residual <>: a null on either side keeps the row",
     "select k, v from a where not exists (select * from b where k2 = k "
     "and w <> v) order by k, v",
     [(None, 50), (1, 10), (3, None), (4, 40)]),
    ("EXISTS with a residual <",
     "select k, v from a where exists (select 1 from b where b.k2 = a.k "
     "and w < v) order by k, v",
     [(2, 20), (3, 30)]),
    ("NOT EXISTS with a residual <",
     "select k, v from a where not exists (select 1 from b where "
     "b.k2 = a.k and w < v) order by k, v",
     [(None, 50), (1, 10), (3, None), (4, 40)]),
    ("EXISTS with a residual over two columns of the subquery",
     "select k, v from a where exists (select * from b where k2 = k "
     "and w + u > v) order by k, v",
     [(2, 20), (3, 30)]),
    ("NOT EXISTS with a residual over two columns of the subquery",
     "select k, v from a where not exists (select * from b where k2 = k "
     "and w + u > v) order by k, v",
     [(None, 50), (1, 10), (3, None), (4, 40)]),
    ("a conjunct that reads the subquery alone stays beneath its side",
     "select k, v from a where exists (select * from b where k2 = k "
     "and w > 1 and w <> v) order by k, v",
     [(3, 30)]),
    ("a self-join keeps the two sides apart",
     "select x.k2, x.w from b x where exists (select * from b y where "
     "y.k2 = x.k2 and y.w <> x.w) and not exists (select * from b z "
     "where z.k2 = x.k2 and z.w <> x.w and z.w > 2) order by k2, w",
     [(3, 30)]),
    ("EXISTS beneath a comma list goes to the relation it reads",
     "select k, w from a, b where k = k2 and exists (select * from c "
     "where k3 = k and z < w + 10) order by k, w",
     [(3, 2), (3, 2), (3, 30), (3, 30)]),
    ("EXISTS that reads two relations of a comma list sits over their join",
     "select k, w from a, b where k = k2 and exists (select * from c "
     "where k3 = k and z + w > v) order by k, w",
     [(3, 30)]),
    ("EXISTS in HAVING",
     "select k2, sum(w) s from b group by k2 having exists "
     "(select * from a where k = k2 and v > 25) order by k2",
     [(3, 32), (4, None)]),
    ("EXISTS beside IN (subquery)",
     "select k, v from a where k in (select k3 from c) and exists "
     "(select * from b where k2 = k and w <> v) order by k, v",
     [(3, 30)]),
    ("an outer column in the select list", 
     "select k from a where exists (select v from b where k2 = k)",
     _REFUSED),
    ("an outer column in an aggregate",
     "select k from a where exists (select sum(w + v) from b where k2 = k)",
     _REFUSED),
    ("an outer column in HAVING",
     "select k from a where exists (select k2 from b group by k2 "
     "having k2 = k)",
     _REFUSED),
    ("an outer column beneath an aggregate",
     "select k from a where exists (select count(*) from b where k2 = k)",
     (NotImplementedError, "beneath the subquery's WHERE")),
    ("an outer column under OR",
     "select k from a where exists (select * from b where k2 = k "
     "and (w = v or w = 1))",
     (NotImplementedError, "under OR or NOT")),
    ("an outer column under NOT",
     "select k from a where exists (select * from b where k2 = k "
     "and not (w = v and w = 1))",
     (NotImplementedError, "under OR or NOT")),
    ("an outer column two levels up",
     "select k from a where exists (select * from b where k2 = k and "
     "exists (select * from c where k3 = k2 and z = v))",
     (NotImplementedError, "two levels up")),
    ("a correlated scalar subquery (Q2, Q17, Q20)",
     "select k from a where v = (select min(w) from b where k2 = k)",
     (NotImplementedError, "correlated scalar subquery")),
    ("a correlated IN (subquery)",
     "select k from a where k in (select k2 from b where w = v)",
     (NotImplementedError, "correlated IN subquery")),
    ("EXISTS under OR",
     "select k from a where exists (select * from b where k2 = k) or v = 10",
     (NotImplementedError, "EXISTS (subquery) is supported only as")),
    ("EXISTS under NOT NOT",
     "select k from a where not (not exists (select * from b "
     "where k2 = k))",
     (NotImplementedError, "EXISTS (subquery) is supported only as")),
    ("EXISTS in a select list",
     "select exists (select * from b where w > 1) from a",
     (NotImplementedError, "EXISTS (subquery) is supported only as")),
    ("correlation with no equality (a nested-loop semi join)",
     "select k from a where exists (select * from b where w < v)",
     (NotImplementedError, "no outer = inner equality")),
    ("an uncorrelated EXISTS",
     "select k from a where exists (select * from b where w > 1)",
     (NotImplementedError, "uncorrelated EXISTS")),
    ("NOT IN (subquery) as before",
     "select k from a where k not in (select k2 from b)",
     (NotImplementedError, "NOT IN (subquery)")),
    ("a misspelt column in an EXISTS subquery is no correlation",
     "select k from a where exists (select * from b where k2 = nope)",
     (KeyError, "cannot resolve 'nope'")),
]


@pytest.mark.parametrize("what,sql,want", DIALECT_CASES,
                         ids=[c[0] for c in DIALECT_CASES])
def test_the_dialect(small, what, sql, want):
    for spark in small:
        if isinstance(want, tuple):
            error, says = want
            with pytest.raises(error, match=says.replace("(", r"\(")
                               .replace(")", r"\)")):
                spark.sql(sql).collect()
        else:
            assert [tuple(r) for r in spark.sql(sql).collect()] == want
            report = spark.last_rewrite_report
            assert report is None or list(report.fallbacks) == []


def test_an_outer_join_with_a_residual_is_tagged_to_the_cpu_by_name(small):
    _cpu, dev = small
    with pytest.raises(Exception, match="conditional left join runs on CPU"):
        dev.sql("select k, w from a left join b on k = k2 and w <> v"
                ).collect()

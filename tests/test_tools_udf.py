"""Qualification/profiling tools + Python UDF surface tests
(reference `tools` module + PythonUDF placement)."""

from spark_rapids_tpu import tools
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.sql.session import TpuSparkSession

from tests.harness import (assert_tpu_and_cpu_equal_collect,
                           assert_tpu_fallback_collect)
from tests.datagen import IntegerGen, SmallIntGen, gen_batch


def _session():
    return TpuSparkSession({"spark.rapids.sql.enabled": "true"})


def test_qualify_reports_placement_and_reasons():
    s = _session()
    try:
        df = s.createDataFrame({"k": [1, 2], "v": [1.0, 2.0]},
                               "k int, v double")
        q = df.filter(F.col("k") > 0).groupBy("k").agg(
            F.sum("v").alias("sv"))  # float sum falls back by default
        rep = tools.qualify(s, q)
        assert "TpuFilter" in rep.device_ops
        assert any("HashAggregate" in n for n, _ in rep.cpu_ops)
        assert 0.0 < rep.op_coverage < 1.0
        assert "Qualification" in rep.format()
    finally:
        s.stop()


def test_profile_surfaces_metrics():
    s = _session()
    try:
        df = s.createDataFrame({"k": [1, 2, 1], "v": [10, 20, 30]},
                               "k int, v int")
        prof = tools.profile(s, df.filter(F.col("v") > 5).groupBy("k")
                             .agg(F.count("*").alias("c")))
        assert prof.rows == 2
        names = [n for n, _ in prof.operators]
        assert any("TpuHashAggregate" in n for n in names)
        all_metrics = {k for _n, m in prof.operators for k in m}
        assert "numOutputRows" in all_metrics
    finally:
        s.stop()


def test_udf_executes_and_falls_back():
    double_it = F.udf(lambda x: None if x is None else x * 2, "bigint")
    assert_tpu_fallback_collect(
        lambda s: s.createDataFrame(
            gen_batch([("a", IntegerGen())], 100, 3))
        .select(double_it("a").alias("d")),
        fallback_exec="CpuProjectExec")


def test_udf_values():
    s = TpuSparkSession({"spark.rapids.sql.enabled": "false"})
    try:
        up = F.udf(lambda a, b: (a or 0) + (b or 0), "bigint")
        df = s.createDataFrame({"a": [1, None, 3], "b": [10, 20, None]},
                               "a int, b int")
        got = [r.s for r in df.select(up("a", "b").alias("s")).collect()]
        assert got == [11, 20, 3]
    finally:
        s.stop()


def test_udf_decorator_with_type():
    @F.udf("bigint")
    def plus1(x):
        return None if x is None else x + 1
    s = TpuSparkSession({"spark.rapids.sql.enabled": "false"})
    try:
        df = s.createDataFrame({"v": [1, None, 3]}, "v int")
        got = [r.p for r in df.select(plus1("v").alias("p")).collect()]
        assert got == [2, None, 4]
    finally:
        s.stop()


def test_rollup_agg_over_grouping_column():
    s = TpuSparkSession({"spark.rapids.sql.enabled": "true"})
    try:
        df = s.createDataFrame({"k": [1, 1, 2], "v": [5, 6, 7]},
                               "k int, v int")
        rows = {(r.k, r.mk) for r in
                df.rollup("k").agg(F.max("k").alias("mk")).collect()}
        # inside an aggregate function the key reads its original values
        # (Spark's ResolveGroupingAnalytics), so the total row's max is 2
        assert rows == {(1, 1), (2, 2), (None, 2)}
    finally:
        s.stop()


def test_event_log_and_offline_tools(tmp_path):
    """Per-query event logs + offline qualify/profile with NO live
    session (Qualification.scala:34 / Profiler.scala:31 roles)."""
    import subprocess
    import sys

    from spark_rapids_tpu import event_log
    from spark_rapids_tpu.sql import functions as F
    from spark_rapids_tpu.sql.session import TpuSparkSession
    from spark_rapids_tpu import tools

    log_dir = str(tmp_path / "events")
    spark = TpuSparkSession({
        "spark.rapids.sql.enabled": "true",
        "spark.rapids.sql.eventLog.dir": log_dir,
    })
    try:
        df = spark.createDataFrame(
            {"k": [1, 2, 1, 3], "v": [10, 20, 30, 40]},
            "k int, v bigint")
        df.groupBy("k").agg(F.sum("v").alias("s")).collect()
        df.filter(F.col("v") > 15).collect()
    finally:
        spark.stop()

    events = list(event_log.read_events(log_dir))
    assert len(events) == 2
    assert all(e["event"] == "queryCompleted" for e in events)
    assert events[0]["outputRows"] == 3
    assert any(o.get("device") for o in events[0]["ops"])

    q = tools.qualify_log(log_dir)
    assert "queries: 2" in q and "operator coverage" in q
    p = tools.profile_log(log_dir)
    assert "timeline" in p and "aggregate operator metrics" in p

    # CLI entry, offline (no session)
    out = subprocess.run(
        [sys.executable, "-m", "spark_rapids_tpu.tools", "qualify",
         "--log", log_dir],
        capture_output=True, text=True, timeout=120,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr
    assert "Qualification Report (offline)" in out.stdout


# -- round 4: udf-compiler (CatalystExpressionBuilder twin) ----------------

def test_udf_compiler_device_placement():
    """F.udf(lambda x: x + 1) compiles to an expression tree and the
    projection runs on device (udf-compiler Plugin.scala:27-37 role)."""
    from spark_rapids_tpu.sql.session import TpuSparkSession
    sp = TpuSparkSession({"spark.rapids.sql.enabled": "true",
                          "spark.rapids.sql.udfCompiler.enabled": "true"})
    try:
        df = sp.createDataFrame({"a": [1, 5, 9]}, "a int")
        plus1 = F.udf(lambda x: x + 1, "int")
        sp.start_capture()
        r = df.select(plus1(F.col("a")).alias("u")).collect()
        pstr = "\n".join(p.tree_string()
                         for p in sp.get_captured_plans())
        assert [row[0] for row in r] == [2, 6, 10]
        assert "TpuProject" in pstr, pstr
    finally:
        sp.stop()


def test_udf_compiler_conditionals_and_fallback():
    from spark_rapids_tpu.sql.session import TpuSparkSession
    results = {}
    for on in ("false", "true"):
        sp = TpuSparkSession({
            "spark.rapids.sql.enabled": "true",
            "spark.rapids.sql.udfCompiler.enabled": on})
        try:
            df = sp.createDataFrame(
                {"a": [1, 2, 5, -3], "b": [2.0, 0.5, 1.0, 4.0]},
                "a int, b double")
            cond = F.udf(lambda x: x * 2 if x > 1 else -x, "int")
            # uses a call -> NOT compilable; must silently stay Python
            hard = F.udf(lambda x: int(str(x)) + 1, "int")
            results[on] = df.select(
                cond(F.col("a")).alias("c"),
                hard(F.col("a")).alias("h")).collect()
        finally:
            sp.stop()
    assert results["false"] == results["true"]


def test_udf_compiler_v1_mod_math_strings_locals():
    """udf-compiler v1 (CatalystExpressionBuilder.scala:29-43 role):
    Python %, builtin abs/min/max, math.* calls, string methods, and
    local-variable dataflow all compile; results match row-at-a-time
    Python execution exactly."""
    import math
    import random

    from spark_rapids_tpu.sql import types as T
    from spark_rapids_tpu.sql.session import TpuSparkSession

    random.seed(4)
    n = 200
    rows = {"x": [random.randint(-50, 50) or 1 for _ in range(n)],
            "f": [random.uniform(0.5, 100.0) for _ in range(n)],
            "s": [random.choice([" Ab ", "cd", "EEf "])
                  for _ in range(n)]}

    def local_fn(x):
        t = x * 2
        u = t + 1
        return u if t > 0 else -u

    def run(enabled, compiler):
        s = TpuSparkSession({
            "spark.rapids.sql.enabled": enabled,
            "spark.rapids.sql.udfCompiler.enabled": compiler,
            "spark.rapids.sql.incompatibleOps.enabled": "true",
            "spark.rapids.sql.variableFloatAgg.enabled": "true"})
        df = s.createDataFrame(rows, "x int, f double, s string")
        u1 = F.udf(lambda x: x % 7 - (-x) % 3, T.IntegerT)
        u2 = F.udf(lambda s_: s_.upper().strip(), T.StringT)
        u3 = F.udf(local_fn, T.IntegerT)
        u4 = F.udf(lambda x: abs(x) + min(x, 3) + max(x, 0), T.IntegerT)
        u5 = F.udf(lambda f: math.sqrt(f) + math.log(f), T.DoubleT)
        q = df.select(u1(F.col("x")).alias("m"),
                      u2(F.col("s")).alias("u"),
                      u3(F.col("x")).alias("l"),
                      u4(F.col("x")).alias("a"),
                      u5(F.col("f")).alias("sq"), "x")
        out = [tuple(r) for r in q.collect()]
        s.stop()
        return out

    plain = run("false", "false")   # row-at-a-time = ground truth
    cpu = run("false", "true")
    dev = run("true", "true")

    def close(p, q):
        return all(
            (a == b) or (isinstance(a, float)
                         and abs(a - b) <= 1e-9 * max(abs(a), abs(b)))
            for a, b in zip(p, q))
    assert all(close(p, q) for p, q in zip(plain, cpu))
    assert all(close(p, q) for p, q in zip(plain, dev))


def test_supported_ops_docs_generation():
    """docs generator derives from the LIVE registries (SupportedOpsDocs
    role): every exec and expression rule appears with its conf key."""
    from spark_rapids_tpu import overrides as O
    from spark_rapids_tpu.tools import generate_supported_ops
    md = generate_supported_ops()
    for rule in O._EXEC_RULES.values():
        assert rule.conf_key in md, rule.conf_key
    for rule in list(O._EXPR_RULES.values())[:20]:
        assert rule.conf_key in md, rule.conf_key
    assert "ArrowEvalPythonExec" in md

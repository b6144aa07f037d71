"""TPC-H Q13 in the specification's own text (clause 2.4.13): a left outer
join whose ON clause holds ``o_comment NOT LIKE '%special%requests%'``, an
aggregate over an aggregate through a derived table with a column alias
list, and a two-key descending sort, through ``sql(text).collect()``.

The benchmark's configuration ``tpch_q13_sf1`` supplies the generator, the
reference and the statement; here they run at a tiny size on the device
path against the plain reference and the CPU engine. Around it: the rule
that moves a join's one-sided ON conjuncts beneath it, held to the answer
of the unmoved plan for every join type; the derived table's column alias
list; LIKE over strings wide enough for its loop; PLAIN byte-array pages
of long distinct strings decoded on the device; the left join's gather
over sparse and dense stream batches; and the counters the cell reads.
"""

from __future__ import annotations

import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from benchmarks.harness import cell as C
from benchmarks.harness import tables as TB
from spark_rapids_tpu.sql import logical as L
from spark_rapids_tpu.sql.session import TpuSparkSession

CONFIG_DIR = os.path.join(C.BENCH_DIR, "configs", "tpch_q13_sf1")
ROWS = {"customer": 300, "orders": 3000}
BINDING = {"word1": "special", "word2": "requests"}
SEEDS = (5, 2147483659, 3000000019)
# the cell's conf, and a broadcast threshold under orders' files, so that
# the outer join is a TpuShuffledHashJoinExec as it is at scale factor 1
DEVICE_CONF = dict(C.load_json(os.path.join(CONFIG_DIR, "config.json"))["conf"],
                   **{"spark.rapids.sql.autoBroadcastJoinThreshold": "4096"})
CPU_CONF = {"spark.rapids.sql.enabled": "false"}
ON_DEVICE = {"spark.rapids.sql.enabled": "true"}


def _statement(binding: dict = BINDING) -> str:
    with open(os.path.join(CONFIG_DIR, "statement.sql")) as f:
        return f.read().format(**binding)


def _reference():
    return C.load_module(os.path.join(CONFIG_DIR, "reference.py"),
                         "tpch_q13_reference")


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    """seed -> (tables, paths): the configuration's generator and writer."""
    gen = C.load_module(os.path.join(CONFIG_DIR, "generator.py"),
                        "tpch_q13_generator")
    specs = C.load_json(os.path.join(CONFIG_DIR, "config.json"))["tables"]
    specs = {t: dict(s, rows=ROWS[t]) for t, s in specs.items()}
    for s in specs.values():
        s.pop("layout", None)
    out = {}
    for seed in SEEDS:
        tables = gen.generate(seed, ROWS)
        out[seed] = (tables, TB.write_tables(
            tables, specs, str(tmp_path_factory.mktemp(f"q13-{seed}"))))
    return out


def _session(conf: dict, paths: dict) -> TpuSparkSession:
    spark = TpuSparkSession(dict(conf))
    for table, path in paths.items():
        spark.read.parquet(path).createOrReplaceTempView(table)
    return spark


def _totals() -> dict:
    from spark_rapids_tpu.telemetry.prometheus import aggregator
    return dict(aggregator().scrape()[0])


def _char_bytes(path: str) -> int:
    """lanes x char_cap of one string column over a table's files, one
    batch a file: what the scan stages and a filter over it reads."""
    from spark_rapids_tpu.columnar.device import (bucket_capacity,
                                                  bucket_char_cap)
    total = 0
    for name in sorted(os.listdir(path)):
        col = pq.read_table(os.path.join(path, name)).column("o_comment")
        longest = max(len(s.encode()) for s in col.to_pylist())
        total += bucket_capacity(len(col)) * bucket_char_cap(longest)
    return total


@pytest.mark.parametrize("seed", SEEDS)
def test_q13_on_the_device_equals_the_reference_and_the_cpu_engine(data, seed):
    tables, paths = data[seed]
    want = _reference().answer(tables, BINDING)
    assert want[0][0] == 0 and len(want) >= 5, want
    cpu = _session(CPU_CONF, paths)
    dev = _session(DEVICE_CONF, paths)
    try:
        on_cpu = [tuple(r) for r in cpu.sql(_statement()).collect()]
        before = _totals()
        df = dev.sql(_statement())
        on_dev = [tuple(r) for r in df.collect()]
        after = _totals()
        assert on_cpu == want
        assert on_dev == want
        assert list(dev.last_rewrite_report.fallbacks) == []
        moved = {k: after.get(k, 0) - before.get(k, 0)
                 for k in ("joinConditionPushedCount", "likeCharBytes",
                           "deviceDecodeStringBytes", "retryCount",
                           "splitRetryCount")}
        assert moved["joinConditionPushedCount"] == 1
        assert moved["likeCharBytes"] == _char_bytes(paths["orders"])
        assert moved["deviceDecodeStringBytes"] == moved["likeCharBytes"]
        assert moved["retryCount"] == moved["splitRetryCount"] == 0
    finally:
        cpu.stop()
        dev.stop()


def test_the_plan_holds_the_outer_join_the_like_both_aggregates_and_the_sort(
        data):
    _tables, paths = data[SEEDS[0]]
    dev = _session(DEVICE_CONF, paths)
    try:
        plan = dev.plan_physical(dev.sql(_statement()).plan).tree_string()
    finally:
        dev.stop()
    assert "TpuShuffledHashJoin left" in plan, plan
    assert "cond=None" in plan, plan
    assert "TpuFilter Not(Like(o_comment" in plan, plan
    assert plan.count("TpuHashAggregate mode=final") == 2, plan
    assert "TpuSort" in plan, plan
    assert "Cpu" not in plan, plan


def test_controls_differ_from_the_answer(data):
    tables, _paths = data[SEEDS[1]]
    ref = _reference()
    want = ref.answer(tables, BINDING)
    got = {name: getattr(ref, name)(tables, BINDING) for name in (
        "control_answer", "inner_join_answer", "filter_after_join_answer",
        "count_star_answer", "either_word_answer")}
    for name, rows in got.items():
        assert rows != want, name
    assert len(got["control_answer"]) == len(want)
    assert got["inner_join_answer"] == [r for r in want if r[0]]
    assert got["filter_after_join_answer"] == got["inner_join_answer"]


@pytest.mark.parametrize("comment,excluded", [
    ("special requests", True),
    ("foxes special, final requests.", True),
    ("requests are special", False),          # WORD2 before WORD1 only
    ("specialrequests", True),
    ("special", False),
    ("requests special requests", True),      # the second requests counts
    ("special requestsx", True),
    ("", False),
])
def test_the_references_like_is_greedy_left_to_right(comment, excluded):
    assert _reference().matches(comment, "special", "requests") is excluded


# -- one-sided ON conjuncts beneath the join ---------------------------------

JOIN_SQL = {"left": "left outer join", "right": "right outer join",
            "full": "full outer join", "inner": "join",
            "leftsemi": "left semi join", "leftanti": "left anti join"}
CONJUNCTS = {"left_only": "t1.a > 1", "right_only": "t2.b > 1",
             "both_sides": "t1.a <> t2.b"}
# where the rule may move a conjunct of each kind: a join type's outer
# side decides survival and keeps its conjuncts, a full join keeps all
MOVES = {("left", "right_only"), ("leftsemi", "right_only"),
         ("leftanti", "right_only"), ("right", "left_only"),
         ("inner", "left_only"), ("inner", "right_only")}


@pytest.fixture(scope="module")
def two_tables():
    t1 = {"k": [1, 1, 2, 3, None, 4, 5], "a": [0, 2, None, 3, 1, 5, 2],
          "s": ["x", "y", None, "z", "w", "v", "u"]}
    t2 = {"k": [1, 2, 2, None, 4, 6, 5], "b": [2, None, 3, 1, 0, 4, 2],
          "t": ["p", "q", "r", None, "s", "t", "o"]}
    return t1, t2


def _rows(conf: dict, tables, sql: str):
    spark = TpuSparkSession(dict(conf))
    try:
        for name, cols, ddl in (("t1", tables[0], "k int, a int, s string"),
                                ("t2", tables[1], "k int, b int, t string")):
            spark.createDataFrame(cols, ddl).createOrReplaceTempView(name)
        df = spark.sql(sql)
        rows = sorted((tuple(r) for r in df.collect()),
                      key=lambda r: tuple((v is None, v) for v in r))
        return rows, df.plan
    finally:
        spark.stop()


@pytest.mark.parametrize("side", sorted(CONJUNCTS))
@pytest.mark.parametrize("join_type", sorted(JOIN_SQL))
def test_a_one_sided_on_conjunct_moves_only_where_the_answer_keeps(
        two_tables, join_type, side, monkeypatch):
    cols = "t1.k, t1.a, t1.s" if join_type in ("leftsemi", "leftanti") \
        else "*"
    sql = (f"select {cols} from t1 {JOIN_SQL[join_type]} t2 "
           f"on t1.k = t2.k and {CONJUNCTS[side]}")
    got, plan = _rows(CPU_CONF, two_tables, sql)
    joins = [p for p in _walk(L.rewrite_joins_and_subqueries(plan))
             if isinstance(p, L.Join)]
    assert len(joins) == 1
    kept = L.split_conjuncts(joins[0].condition)
    assert len(kept) == (1 if (join_type, side) in MOVES else 2), kept
    on_device, _ = _rows(ON_DEVICE, two_tables, sql)
    assert on_device == got
    # the same statement with the rule switched off: the unmoved plan
    monkeypatch.setattr(L, "_PUSHABLE_SIDES", {})
    unmoved, _ = _rows(CPU_CONF, two_tables, sql)
    assert got == unmoved


def _walk(p):
    yield p
    for c in p.children:
        yield from _walk(c)


def test_an_outer_join_keeps_a_two_sided_residual_on_the_cpu_by_name(
        two_tables):
    """A residual that reads both sides still decides matching inside an
    outer join: the rule leaves it, and forceDevice names why."""
    sql = ("select * from t1 left outer join t2 on t1.k = t2.k "
           "and t2.b > 1 and t1.a <> t2.b")
    conf = dict(ON_DEVICE, **{"spark.rapids.sql.test.forceDevice": "true"})
    with pytest.raises(AssertionError, match="conditional left join"):
        _rows(conf, two_tables, sql)


def test_the_accepted_statements_have_no_one_sided_on_conjunct():
    """The five statements the benchmark held before Q13 plan as they did:
    the rule finds nothing to move in any of them."""
    for config in ("tpch_q1_sf1", "tpcds_star_2m", "tpch_q18_sf1",
                   "tpch_q21_sf1", "tpcds_q51_sf1"):
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
            before = _totals().get("joinConditionPushedCount", 0)
            plan = L.rewrite_joins_and_subqueries(spark.sql(text).plan)
            assert _totals().get("joinConditionPushedCount", 0) == before
            assert not any(L._one_sided_conjuncts(p) for p in _walk(plan)
                           if isinstance(p, L.Join)), config
        finally:
            spark.stop()


# -- the derived table's column alias list -----------------------------------

def test_a_derived_tables_column_alias_list_renames_in_order():
    spark = TpuSparkSession(dict(CPU_CONF))
    try:
        spark.createDataFrame({"a": [1, 2, 2], "b": [5, 6, 7]},
                              "a int, b int").createOrReplaceTempView("t")
        rows = spark.sql(
            "select x.p, q from (select a, sum(b) from t group by a) "
            "as x (p, q) order by p").collect()
        assert [tuple(r) for r in rows] == [(1, 5), (2, 13)]
        with pytest.raises(ValueError,
                           match="names 3 columns, and its subquery gives 2"):
            spark.sql("select * from (select a, b from t) as x (p, q, r)")
    finally:
        spark.stop()


# -- LIKE over wide strings ----------------------------------------------------

def test_like_over_strings_wide_enough_for_the_loop():
    """``char_cap - len(chunk) + 1`` offsets times the chunk's length past
    8,192 takes ``_like_search``'s ``fori_loop``; the answer is Python's."""
    rng = np.random.default_rng(13)
    words = ["special", "requests", "pending", "foxes", "sly", " ", "x"]
    rows = ["".join(rng.choice(words, size=int(rng.integers(150, 400))))
            for _ in range(40)]
    rows += ["requests" * 150, "special" + "y" * 1200 + "requests", None]
    pattern = "%special%requests%"

    def like(s):
        if s is None:
            return None
        i = s.find("special")
        return i >= 0 and s.find("requests", i + 7) >= 0

    spark = TpuSparkSession(dict(ON_DEVICE))
    try:
        spark.createDataFrame({"i": list(range(len(rows))), "s": rows},
                              "i int, s string").createOrReplaceTempView("w")
        got = spark.sql(f"select i, s like '{pattern}', s not like "
                        f"'{pattern}' from w order by i").collect()
    finally:
        spark.stop()
    assert max(len(s) for s in rows if s) > 1200
    assert [(r[0], r[1], r[2]) for r in got] == [
        (i, like(s), None if like(s) is None else not like(s))
        for i, s in enumerate(rows)]


def test_like_char_bytes_are_booked_at_every_dispatch_of_a_fused_stage():
    """The tally is taken while the stage traces and booked from the
    inputs' shapes at each dispatch, the first and a cached one alike."""
    from spark_rapids_tpu.columnar.device import (bucket_capacity,
                                                  bucket_char_cap)
    rows = [f"{'special ' * (i % 5)}requests {i}" for i in range(300)]
    spark = TpuSparkSession(dict(ON_DEVICE))
    try:
        spark.createDataFrame({"i": list(range(300)), "s": rows},
                              "i int, s string").createOrReplaceTempView("w")
        sql = "select i + 1 from w where s like '%special%requests%'"
        df = spark.sql(sql)
        plan = spark.plan_physical(df.plan).tree_string()
        assert "TpuFusedStage [TpuFilter+TpuProject]" in plan, plan
        moved = []
        for _ in range(2):
            before = _totals().get("likeCharBytes", 0)
            got = spark.sql(sql).collect()
            moved.append(_totals().get("likeCharBytes", 0) - before)
    finally:
        spark.stop()
    assert len(got) == 240
    longest = max(len(s) for s in rows)
    assert moved == [bucket_capacity(300) * bucket_char_cap(longest)] * 2


# -- PLAIN byte-array pages of long distinct strings ---------------------------

def test_plain_long_distinct_strings_decode_on_the_device_bit_equal(tmp_path):
    from spark_rapids_tpu.columnar.device import bucket_capacity
    rng = np.random.default_rng(40)
    gen = C.load_module(os.path.join(CONFIG_DIR, "generator.py"),
                        "tpch_q13_generator_plain")
    pool = gen.text_pool(rng, 1 << 16)
    comments = gen.comments(rng, pool, 5000).tolist()
    comments[::97] = [None] * len(comments[::97])
    path = os.path.join(str(tmp_path), "c.parquet")
    pq.write_table(pa.table({"c": pa.array(comments, type=pa.string())}),
                   path, use_dictionary=False, data_page_size=16 << 10)
    meta = pq.ParquetFile(path).metadata.row_group(0).column(0)
    assert "RLE_DICTIONARY" not in meta.encodings
    spark = TpuSparkSession(dict(ON_DEVICE))
    try:
        spark.read.parquet(path).createOrReplaceTempView("c")
        before = _totals()
        got = spark.sql("select c from c").collect()
        after = _totals()
    finally:
        spark.stop()
    assert [r[0] for r in got] == pq.read_table(path).column("c").to_pylist()
    moved = {k: after.get(k, 0) - before.get(k, 0)
             for k in ("scanStrLengthsTime", "scanParseTime",
                       "deviceDecodeStringBytes", "deviceFallbackColumns")}
    assert 0 < moved["scanStrLengthsTime"] <= moved["scanParseTime"]
    assert moved["deviceDecodeStringBytes"] == bucket_capacity(5000) * 80
    assert moved["deviceFallbackColumns"] == 0


@pytest.mark.parametrize("dictionary", [False, True])
def test_a_chunks_value_lengths_from_its_file_equal_the_pages_own(
        tmp_path, dictionary):
    """``plan_unit_encoded`` plans a string chunk with a PLAIN page from
    pyarrow's decode of the chunk: its lengths are the values' own, and the
    pages' own length prefixes, walked, give the same at every PLAIN lane,
    nulls and a dictionary overflow included."""
    import types

    from spark_rapids_tpu.columnar.transfer import _Packer
    from spark_rapids_tpu.io import device_decode as DD
    from spark_rapids_tpu.io.readers import ScanUnit
    from spark_rapids_tpu.sql import types as T
    rng = np.random.default_rng(41)
    gen = C.load_module(os.path.join(CONFIG_DIR, "generator.py"),
                        "tpch_q13_generator_lengths")
    comments = gen.comments(rng, gen.text_pool(rng, 1 << 16), 3000).tolist()
    comments[5::11] = [None] * len(comments[5::11])
    path = os.path.join(str(tmp_path), "c.parquet")
    pq.write_table(pa.table({"c": pa.array(comments, type=pa.string())}),
                   path, use_dictionary=dictionary, data_page_size=8 << 10,
                   dictionary_pagesize_limit=16 << 10)
    schema = T.StructType([T.StructField("c", T.StringT, True)])
    enc = DD.plan_unit_encoded(ScanUnit(path, os.path.getsize(path), [0]),
                               schema)
    plan = enc.plans[0]
    assert plan.encoding_values.get("PLAIN", 0) > 0, plan.encoding_values
    assert plan.pg_enc == [DD.PGE_DL_STR]
    want = [len(c.encode()) for c in comments if c is not None]
    assert sum(plan.encoding_values.values()) == len(want)
    assert plan.str_lens[:len(want)].tolist() == want
    meta = pq.ParquetFile(path).metadata.row_group(0).column(0)
    with open(path, "rb") as f:
        start = min(s for s in (meta.data_page_offset,
                                meta.dictionary_page_offset) if s is not None)
        f.seek(start)
        raw = f.read(meta.total_compressed_size)
    leaf = pq.ParquetFile(path).schema.column(0)
    walked = DD._plan_column(raw, types.SimpleNamespace(
        compression=meta.compression), leaf, T.StringT, len(comments),
        _Packer())
    assert walked.encoding_values == plan.encoding_values
    plain = [k for k, e in enumerate(walked.pg_enc) if e == DD.PGE_PLAIN_STR]
    assert plain
    for k in plain:
        lo, hi = walked.pg_dense_start[k], walked.pg_dense_start[k + 1]
        assert walked.str_lens[lo:hi].tolist() == want[lo:hi]
    assert walked.char_cap == plan.char_cap


# -- the left join's gather ------------------------------------------------------

@pytest.mark.parametrize("stream", ["dense", "sparse"])
def test_the_left_join_gathers_its_rows_as_the_cpu_engine(stream):
    """Stream rows with no match, one, or many, under a filter that leaves
    most lanes of the stream batch inactive (sparse) or none (dense)."""
    rng = np.random.default_rng(7)
    n = 2000
    left = {"k": rng.integers(0, 300, n).tolist(),
            "v": rng.integers(0, 1000, n).tolist()}
    right = {"k": rng.integers(100, 400, 900).tolist(),
             "w": rng.integers(0, 50, 900).tolist()}
    # a filter beneath the join leaves 9 of 10 stream lanes inactive
    src = "(select * from l where v % 10 = 3) l" if stream == "sparse" \
        else "l"
    sql = f"select l.k, l.v, r.w from {src} left outer join r on l.k = r.k"
    out = []
    for conf in (CPU_CONF, ON_DEVICE):
        spark = TpuSparkSession(dict(conf))
        try:
            spark.createDataFrame(left, "k int, v int") \
                .createOrReplaceTempView("l")
            spark.createDataFrame(right, "k int, w int") \
                .createOrReplaceTempView("r")
            out.append(sorted(
                (tuple(r) for r in spark.sql(sql).collect()),
                key=lambda r: tuple((v is None, v) for v in r)))
        finally:
            spark.stop()
    assert out[0] == out[1]
    assert any(r[2] is None for r in out[0])
    assert len(out[0]) > (200 if stream == "sparse" else n)

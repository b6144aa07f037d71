"""Device columnar layer: host<->device round trips, compaction, murmur3
bit-parity with the host reference, and device-vs-CPU expression equality.

Plays the role of the reference's FuzzerUtils-driven unit suites
(tests/ GpuCoalesceBatchesSuite etc.) at the kernel-library level.
"""

import numpy as np
import pytest

from support import assert_pydicts_equal, lists_equal

from spark_rapids_tpu.columnar import murmur3
from spark_rapids_tpu.columnar.device import (
    DeviceBatch, bucket_capacity, compact, concat_device)
from spark_rapids_tpu.columnar.host import HostBatch, HostColumn
from spark_rapids_tpu.ops import exprs as X
from spark_rapids_tpu.ops import hashing
from spark_rapids_tpu.sql import expressions as E
from spark_rapids_tpu.sql import types as T

import jax.numpy as jnp


def _mk_batch(data, schema):
    return HostBatch.from_pydict(data, schema)


MIXED_SCHEMA = T.StructType([
    T.StructField("i", T.IntegerT),
    T.StructField("l", T.LongT),
    T.StructField("d", T.DoubleT),
    T.StructField("s", T.StringT),
    T.StructField("b", T.BooleanT),
])

MIXED_DATA = {
    "i": [1, None, -3, 2147483647, 0, -2147483648],
    "l": [10, 20, None, 9223372036854775807, -1, 0],
    "d": [1.5, float("nan"), -0.0, None, float("inf"), -2.25],
    "s": ["hello", "", None, "a much longer string here", "Ω≈ç√", "x"],
    "b": [True, False, None, True, False, True],
}


def test_round_trip_mixed():
    hb = _mk_batch(MIXED_DATA, MIXED_SCHEMA)
    db = DeviceBatch.from_host(hb)
    assert db.capacity == bucket_capacity(6) == 64
    assert db.row_count() == 6
    back = db.to_host()
    assert_pydicts_equal(back.to_pydict(), hb.to_pydict())


def test_compact_and_concat():
    hb = _mk_batch(MIXED_DATA, MIXED_SCHEMA)
    db = DeviceBatch.from_host(hb)
    # knock out rows 1, 3 via the active mask
    active = np.asarray(db.active).copy()
    active[1] = False
    active[3] = False
    db2 = DeviceBatch(db.schema, db.columns, jnp.asarray(active), None)
    assert db2.row_count() == 4
    c = compact(db2)
    back = c.to_host()
    expect = hb.take(np.array([0, 2, 4, 5]))
    assert_pydicts_equal(back.to_pydict(), expect.to_pydict())

    cc = concat_device([c, c])
    assert cc.row_count() == 8
    expect2 = HostBatch.concat([expect, expect])
    assert_pydicts_equal(cc.to_host().to_pydict(), expect2.to_pydict())


@pytest.mark.parametrize("dtype,name", [
    (T.IntegerT, "i"), (T.LongT, "l"), (T.DoubleT, "d"),
    (T.StringT, "s"), (T.BooleanT, "b")])
def test_murmur3_device_matches_host(dtype, name):
    hb = _mk_batch(MIXED_DATA, MIXED_SCHEMA)
    db = DeviceBatch.from_host(hb)
    ci = hb.schema.field_index(name)
    attr = E.AttributeReference(name, dtype, True)
    expect = E.Murmur3Hash(
        [E.BoundReference(ci, dtype, True)]).eval(hb)
    got = hashing.murmur3_columns([db.columns[ci]], db.capacity)
    np.testing.assert_array_equal(np.asarray(got)[:6], expect.data)


def test_murmur3_multi_column_fold():
    hb = _mk_batch(MIXED_DATA, MIXED_SCHEMA)
    db = DeviceBatch.from_host(hb)
    bound = [E.BoundReference(i, f.data_type, True)
             for i, f in enumerate(MIXED_SCHEMA.fields)]
    expect = E.Murmur3Hash(bound).eval(hb)
    got = hashing.murmur3_columns(db.columns, db.capacity)
    np.testing.assert_array_equal(np.asarray(got)[:6], expect.data)


def test_murmur3_string_edge_lengths():
    # lengths 0..9 cover word + tail code paths
    vals = ["", "a", "ab", "abc", "abcd", "abcde", "abcdef", "abcdefg",
            "abcdefgh", "abcdefghi"]
    schema = T.StructType([T.StructField("s", T.StringT)])
    hb = _mk_batch({"s": vals}, schema)
    db = DeviceBatch.from_host(hb)
    expect = [murmur3.hash_bytes_one(v.encode(), 42) for v in vals]
    got = np.asarray(hashing.murmur3_columns([db.columns[0]], db.capacity))
    np.testing.assert_array_equal(got[:10], np.array(expect, np.int32))


DEC_15_2 = T.DecimalType(15, 2)
_HASH_BATTERY_TYPES = ["b", "i", "l", "f", "d", "dt", "ts", "dec", "s"]


def _hash_battery(n=200, seed=9):
    """HostBatch covering every murmur3-hashable type, 15% nulls each,
    with the edge cases the host/device twins must agree on: -0.0,
    empty strings, embedded NUL bytes, and high-bit (negative-as-int8)
    trailing bytes."""
    rng = np.random.default_rng(seed)
    strs = np.empty(n, dtype=object)
    pool = ["", "a", "ab", "abc", "abcd", "abcde", "\x00", "x\x00y",
            "\x7f\x00", "éä", "ÿþ", "0123456789abcdef",
            "tailé"]
    for i in range(n):
        strs[i] = pool[rng.integers(0, len(pool))]
    cols = [
        ("b", T.BooleanT, rng.integers(0, 2, n).astype(bool)),
        ("i", T.IntegerT, rng.integers(-2**31, 2**31, n,
                                       dtype=np.int64).astype(np.int32)),
        ("l", T.LongT, rng.integers(-2**62, 2**62, n)),
        ("f", T.FloatT, np.where(rng.random(n) < 0.1, -0.0,
                                 rng.standard_normal(n)
                                 ).astype(np.float32)),
        ("d", T.DoubleT, np.where(rng.random(n) < 0.1, -0.0,
                                  rng.standard_normal(n))),
        ("dt", T.DateT, rng.integers(-11000, 47000, n
                                     ).astype(np.int32)),
        ("ts", T.TimestampT, rng.integers(-10**15, 10**15, n)),
        ("dec", DEC_15_2, rng.integers(-10**10, 10**10, n)),
        ("s", T.StringT, strs),
    ]
    assert [c[0] for c in cols] == _HASH_BATTERY_TYPES
    fields, hcols = [], []
    for name, dt, vals in cols:
        valid = rng.random(n) > 0.15
        fields.append(T.StructField(name, dt))
        hcols.append(HostColumn(dt, vals, valid).normalized())
    return HostBatch(T.StructType(fields), hcols, n)


@pytest.mark.parametrize("which", _HASH_BATTERY_TYPES + ["chain"])
def test_murmur3_host_device_twin_parity(which):
    """Device murmur3 (ops/hashing.py) against the host implementation
    (columnar/murmur3.py through expressions._hash_column): one case a
    column type, so a failure names the type, and the chain of all
    nine folded left to right as the exchange folds its keys."""
    import jax

    from spark_rapids_tpu.sql.expressions import _hash_column
    hb = _hash_battery()
    n = hb.num_rows
    db = DeviceBatch.from_host(hb)  # capacity-bucketed: compare prefix
    picked = (list(range(len(hb.columns))) if which == "chain"
              else [hb.schema.field_index(which)])
    host = np.full(n, 42, dtype=np.int32)
    for ci in picked:
        host = _hash_column(hb.columns[ci], host)
    dcols = [db.columns[ci] for ci in picked]
    dev = np.asarray(jax.jit(
        lambda: hashing.murmur3_columns(dcols, db.capacity, 42))())
    np.testing.assert_array_equal(dev[:n], host)


APPROX_EXPRS = (E.Exp, E.Log, E.Log10, E.Sin, E.Cos, E.Tan, E.Asin,
                E.Acos, E.Atan, E.Sinh, E.Cosh, E.Tanh, E.Pow)


def _assert_expr_matches(expr, hb: HostBatch):
    """Evaluate bound expr on CPU and device; compare values + validity."""
    bound = E.bind_references(
        expr, [E.AttributeReference(f.name, f.data_type, True, i + 1000)
               for i, f in enumerate(hb.schema.fields)])
    # rebind: build attrs that map by position
    attrs = [E.AttributeReference(f.name, f.data_type, True)
             for f in hb.schema.fields]
    bound = E.bind_references(_sub_attrs(expr, attrs), attrs)
    cpu = bound.eval(hb)
    db = DeviceBatch.from_host(hb)
    out = X.run_project([bound], db)[0]
    got = DeviceBatch(
        T.StructType([T.StructField("r", bound.data_type)]), [out],
        db.active, None).to_host()
    exp_col = HostColumn(bound.data_type, cpu.data, cpu.validity)
    got_col = got.columns[0]
    approx = isinstance(expr, APPROX_EXPRS)
    assert lists_equal(got_col.to_pylist(), exp_col.to_pylist(), approx), (
        f"{expr!r}: {got_col.to_pylist()} != {exp_col.to_pylist()}")


def _sub_attrs(expr, attrs):
    def rule(e):
        if isinstance(e, E.UnresolvedAttribute):
            for a in attrs:
                if a.name == e.name:
                    return a
        return None
    return expr.transform(rule)


def col(name):
    return E.UnresolvedAttribute(name)


NUM_SCHEMA = T.StructType([
    T.StructField("a", T.IntegerT), T.StructField("b", T.IntegerT),
    T.StructField("x", T.DoubleT), T.StructField("y", T.DoubleT),
    T.StructField("s", T.StringT), T.StructField("t", T.StringT),
])

NUM_DATA = {
    "a": [1, -5, None, 2147483647, 0, 17, -2147483648, 3],
    "b": [3, 0, 7, 1, None, -4, -1, 3],
    "x": [1.5, -0.0, float("nan"), None, float("inf"), 2.5, -3.75, 0.0],
    "y": [2.0, 0.0, 1.0, 4.0, float("nan"), None, -1.0, 0.0],
    "s": ["apple", "Banana split", "", None, "  pad  ", "Zq va", "z", "ab"],
    "t": ["app", "nana", "x", "y", None, "a", "z", "ab"],
}


@pytest.mark.parametrize("expr", [
    E.Add(col("a"), col("b")),
    E.Subtract(col("a"), col("b")),
    E.Multiply(col("a"), col("b")),
    E.Divide(col("x"), col("y")),
    E.IntegralDivide(col("a"), col("b")),
    E.Remainder(col("a"), col("b")),
    E.Pmod(col("a"), col("b")),
    E.UnaryMinus(col("a")),
    E.Abs(col("a")),
    E.EqualTo(col("a"), col("b")),
    E.LessThan(col("x"), col("y")),
    E.GreaterThanOrEqual(col("x"), col("y")),
    E.EqualNullSafe(col("a"), col("b")),
    E.EqualTo(col("s"), col("t")),
    E.LessThan(col("s"), col("t")),
    E.GreaterThan(col("s"), col("t")),
    E.And(E.GreaterThan(col("a"), E.Literal(0)),
          E.LessThan(col("b"), E.Literal(5))),
    E.Or(E.IsNull(col("a")), E.GreaterThan(col("b"), E.Literal(0))),
    E.Not(E.EqualTo(col("a"), col("b"))),
    E.In(col("a"), [E.Literal(1), E.Literal(17), E.Literal(None, T.IntegerT)]),
    E.IsNull(col("x")), E.IsNotNull(col("x")), E.IsNan(col("x")),
    E.Coalesce([col("a"), col("b"), E.Literal(99)]),
    E.If(E.GreaterThan(col("a"), E.Literal(0)), col("a"), col("b")),
    E.CaseWhen([(E.GreaterThan(col("a"), E.Literal(10)), E.Literal(1)),
                (E.GreaterThan(col("b"), E.Literal(0)), E.Literal(2))],
               E.Literal(3)),
    E.Sqrt(col("x")), E.Exp(col("y")), E.Log(col("x")), E.Log10(col("x")),
    E.Sin(col("x")), E.Cos(col("y")), E.Tanh(col("y")),
    E.Floor(col("y")), E.Ceil(col("y")), E.Pow(col("x"), col("y")),
    E.Round(col("x"), E.Literal(1)),
    E.Signum(col("x")),
    E.Length(col("s")),
    E.Upper(col("s")), E.Lower(col("s")),
    E.StringTrim(col("s")),
    E.ConcatStr([col("s"), E.Literal("-"), col("t")]),
    E.Substring(col("s"), E.Literal(2), E.Literal(3)),
    E.Substring(col("s"), E.Literal(-3), E.Literal(2)),
    E.StartsWith(col("s"), col("t")),
    E.EndsWith(col("s"), col("t")),
    E.Contains(col("s"), col("t")),
    E.Murmur3Hash([col("a"), col("s")]),
    E.Cast(col("a"), T.LongT), E.Cast(col("x"), T.IntegerT),
    E.Cast(col("a"), T.DoubleT), E.Cast(col("a"), T.BooleanT),
])
def test_expr_device_matches_cpu(expr):
    hb = _mk_batch(NUM_DATA, NUM_SCHEMA)
    _assert_expr_matches(expr, hb)


def test_datetime_exprs():
    import datetime as dt
    schema = T.StructType([T.StructField("d", T.DateT),
                           T.StructField("ts", T.TimestampT)])
    hb = _mk_batch({
        "d": [dt.date(2020, 2, 29), dt.date(1969, 12, 31), None,
              dt.date(1582, 10, 15), dt.date(2038, 1, 19)],
        "ts": [dt.datetime(2021, 6, 1, 13, 45, 59), dt.datetime(1970, 1, 1),
               None, dt.datetime(1900, 1, 1, 0, 0, 1),
               dt.datetime(2100, 12, 31, 23, 59, 59)],
    }, schema)
    for expr in [E.Year(col("d")), E.Month(col("d")), E.DayOfMonth(col("d")),
                 E.Year(col("ts")), E.Hour(col("ts")), E.Minute(col("ts")),
                 E.Second(col("ts")),
                 E.DateAdd(col("d"), E.Literal(40)),
                 E.DateSub(col("d"), E.Literal(40)),
                 E.DateDiff(col("d"), col("d")),
                 E.Cast(col("d"), T.TimestampT),
                 E.Cast(col("ts"), T.DateT)]:
        _assert_expr_matches(expr, hb)


def test_utf8_exact_string_ops():
    """Non-ASCII strings through the ops that are exact for any UTF-8
    (byte-level semantics match codepoint semantics)."""
    schema = T.StructType([T.StructField("s", T.StringT),
                           T.StructField("t", T.StringT)])
    hb = _mk_batch({
        "s": ["Ωmega", "çava", "日本語テキスト", None, "naïve", "  ü  "],
        "t": ["Ω", "va", "語", "x", None, "ü"],
    }, schema)
    for expr in [E.Length(col("s")), E.EqualTo(col("s"), col("t")),
                 E.LessThan(col("s"), col("t")),
                 E.ConcatStr([col("s"), col("t")]),
                 E.StringTrim(col("s")),
                 E.StartsWith(col("s"), col("t")),
                 E.EndsWith(col("s"), col("t")),
                 E.Contains(col("s"), col("t")),
                 E.Murmur3Hash([col("s")])]:
        _assert_expr_matches(expr, hb)


def test_filter_masks_without_moving_data():
    hb = _mk_batch(NUM_DATA, NUM_SCHEMA)
    db = DeviceBatch.from_host(hb)
    attrs = [E.AttributeReference(f.name, f.data_type, True)
             for f in NUM_SCHEMA.fields]
    cond = E.bind_references(
        E.GreaterThan(col("a"), E.Literal(0)).transform(
            lambda e: next((a for a in attrs if isinstance(
                e, E.UnresolvedAttribute) and a.name == e.name), None)),
        attrs)
    out = X.run_filter(cond, db)
    assert out.capacity == db.capacity  # no reshape
    kept = out.to_host()
    assert kept.to_pydict()["a"] == [1, 2147483647, 17, 3]


def test_varbytes_packed_upload_round_trip():
    """Scan-path string columns carry compact Arrow bytes (varbytes);
    the packed upload must ship those and rebuild the char matrix on
    device bit-identically to the object-array path — including nulls,
    empties, multi-byte UTF-8, and table slices (io/arrow_convert.py
    _string_varbytes + transfer.py 'vstr' decode)."""
    import pyarrow as pa

    from spark_rapids_tpu.columnar.transfer import (PACKED_MIN_ROWS,
                                                    upload_batch)
    from spark_rapids_tpu.io.arrow_convert import (arrow_schema_to_sql,
                                                   arrow_to_host_batch)

    n = PACKED_MIN_ROWS + 257
    vals = []
    for i in range(n):
        r = i % 7
        vals.append(None if r == 0 else "" if r == 1 else
                    f"héllo∆{i % 13}" if r == 2 else "A" if r == 3 else
                    "x" * (i % 17))
    tbl = pa.table({"s": pa.array(vals, type=pa.string()),
                    "v": np.arange(n, dtype=np.int64)})
    for t in (tbl, tbl.slice(1000, PACKED_MIN_ROWS + 5)):
        hb = arrow_to_host_batch(t, arrow_schema_to_sql(t.schema))
        assert hb.columns[0].varbytes is not None
        db = upload_batch(hb, bucket_capacity(t.num_rows))
        got = db.to_host().columns[0].to_pylist()
        exp = hb.columns[0].to_pylist()
        assert got == exp
    # concat keeps varbytes (the R2C goal-coalesce path)
    hb = arrow_to_host_batch(tbl, arrow_schema_to_sql(tbl.schema))
    cc = HostBatch.concat([hb, hb])
    assert cc.columns[0].varbytes is not None
    db = upload_batch(cc, bucket_capacity(2 * n))
    assert db.to_host().columns[0].to_pylist() == 2 * vals

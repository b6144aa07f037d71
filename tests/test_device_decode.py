"""Device parquet decode parity corpus (ISSUE 1 tentpole).

Every test asserts the device-decode path (raw page upload + XLA
decode, io/device_decode.py) produces results BIT-IDENTICAL to the
pyarrow host decode over files with controlled encodings: PLAIN,
RLE_DICTIONARY, dictionary-overflow (mixed encodings in one chunk),
nulls at page boundaries, multi-page chunks — plus the per-column
fallback for unsupported encodings, and unit tests of the ops/rle.py
kernels against numpy oracles.
"""

from __future__ import annotations

import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.sql.session import TpuSparkSession

DEV_CONF = "spark.rapids.sql.format.parquet.deviceDecode.enabled"


def _collect(path, device_decode: bool, extra_conf=None, sql=None):
    """Read ``path`` through the engine with a device op above the scan
    (so TpuRowToColumnarExec is the scan's consumer) and return
    (pydict, scan_metrics)."""
    conf = {"spark.rapids.sql.enabled": "true",
            DEV_CONF: str(device_decode).lower()}
    conf.update(extra_conf or {})
    spark = TpuSparkSession(conf)
    try:
        spark.read.parquet(path).createOrReplaceTempView("t")
        df = spark.sql(sql or "SELECT * FROM t")
        spark.start_capture()
        out = df._execute().to_pydict()
        # whole-plan metric snapshot: the scan's decode counters plus
        # the R2C transition's pipeline counters (uploadAheadBatches,
        # prefetchRingShrinks) ride the same dict
        from spark_rapids_tpu.metrics import registry_snapshot
        metrics = registry_snapshot(spark.get_captured_plans())["metrics"]
        return out, metrics
    finally:
        spark.stop()


def _assert_parity(path, expect_device=True, expect_fallback_cols=0,
                   sql=None):
    host, _m0 = _collect(path, False, sql=sql)
    dev, m = _collect(path, True, sql=sql)
    assert list(host) == list(dev)
    for k in host:
        assert host[k] == dev[k], (
            f"column {k} differs: {host[k][:5]} vs {dev[k][:5]}")
    if expect_device:
        assert m.get("deviceDecodedBatches", 0) >= 1, m
    assert m.get("deviceFallbackColumns", 0) == expect_fallback_cols, m
    return m


def _write(tmp_path, tbl, name="t.parquet", **kw):
    path = os.path.join(str(tmp_path), name)
    pq.write_table(tbl, path, **kw)
    return path


def _mixed_table(n=4000, seed=0, with_nulls=True):
    rng = np.random.default_rng(seed)
    null_every = 7 if with_nulls else 0

    def maybe_null(vals):
        if not null_every:
            return list(vals)
        return [None if i % null_every == 0 else v
                for i, v in enumerate(vals)]

    return pa.table({
        "i64": pa.array(maybe_null(rng.integers(-(1 << 40), 1 << 40, n)
                                   .tolist()), type=pa.int64()),
        "i32": pa.array(maybe_null(rng.integers(-(1 << 30), 1 << 30, n)
                                   .tolist()), type=pa.int32()),
        "f32": pa.array(maybe_null(
            rng.random(n).astype("float32").tolist()), type=pa.float32()),
        "dec": pa.array(maybe_null(rng.integers(-10**9, 10**9, n)
                                   .tolist()), type=pa.decimal128(15, 2)),
        "s": pa.array([None if null_every and i % null_every == 3
                       else f"word{i % 11}" for i in range(n)]),
        "d": pa.array(maybe_null(rng.integers(1000, 20000, n)
                                 .astype("int32").tolist()),
                      type=pa.date32()),
        "b": pa.array(maybe_null((rng.integers(0, 2, n) > 0).tolist()),
                      type=pa.bool_()),
    })


# -- parity corpus ---------------------------------------------------------

def test_plain_encoding_parity(tmp_path):
    tbl = _mixed_table(with_nulls=False).drop_columns(["s"])
    path = _write(tmp_path, tbl, use_dictionary=False)
    m = _assert_parity(path)
    assert m.get("deviceDecodedValues.PLAIN", 0) > 0, m


def test_rle_dictionary_parity(tmp_path):
    n = 4000
    rng = np.random.default_rng(1)
    tbl = pa.table({
        "i": pa.array(rng.integers(0, 50, n), type=pa.int64()),
        "s": pa.array([f"cat{int(v)}" for v in rng.integers(0, 20, n)]),
        "dec": pa.array(rng.integers(0, 100, n).tolist(),
                        type=pa.decimal128(9, 2)),
    })
    path = _write(tmp_path, tbl)
    m = _assert_parity(path)
    assert m.get("deviceDecodedValues.RLE_DICTIONARY", 0) > 0, m


def test_nulls_at_page_boundaries(tmp_path):
    # tiny pages + null runs that straddle page boundaries: the
    # definition-level runs then split/lean across pages
    n = 6000
    vals = [None if (i // 50) % 2 == 0 else i * 3 for i in range(n)]
    svals = [None if (i // 37) % 3 == 1 else f"s{i % 5}"
             for i in range(n)]
    tbl = pa.table({"v": pa.array(vals, type=pa.int64()),
                    "s": pa.array(svals)})
    path = _write(tmp_path, tbl, data_page_size=512)
    _assert_parity(path)


def test_multi_page_chunks_dict_overflow(tmp_path):
    # small dict limit + small pages: the writer starts RLE_DICTIONARY,
    # overflows, and finishes the SAME chunk with PLAIN pages
    n = 30_000
    rng = np.random.default_rng(2)
    tbl = pa.table({"x": pa.array(rng.integers(0, 1 << 40, n),
                                  type=pa.int64())})
    path = _write(tmp_path, tbl, dictionary_pagesize_limit=20_000,
                  data_page_size=4096)
    m = _assert_parity(path)
    assert m.get("deviceDecodedValues.PLAIN", 0) > 0, m
    assert m.get("deviceDecodedValues.RLE_DICTIONARY", 0) > 0, m


def test_mixed_types_with_nulls_snappy(tmp_path):
    path = _write(tmp_path, _mixed_table(), compression="snappy",
                  data_page_size=8192)
    _assert_parity(path)


@pytest.mark.parametrize("case", ["plain", "dict"])
def test_mixed_table_one_page_parity(tmp_path, case):
    # every column type of the corpus in ONE file and one page a
    # chunk: all PLAIN with the strings left in (byte-array and
    # fixed-width lanes side by side), and the writer's defaults
    # (dictionary pages with nulls)
    if case == "plain":
        path = _write(tmp_path, _mixed_table(with_nulls=False),
                      use_dictionary=False)
    else:
        path = _write(tmp_path, _mixed_table())
    _assert_parity(path)


def test_zstd_compression(tmp_path):
    path = _write(tmp_path, _mixed_table(seed=3), compression="zstd")
    _assert_parity(path)


def test_decimal128_flba(tmp_path):
    n = 2000
    rng = np.random.default_rng(4)
    big = [None if i % 11 == 0 else
           int(rng.integers(-10**9, 10**9)) * 10**10 + i
           for i in range(n)]
    tbl = pa.table({"d": pa.array(big, type=pa.decimal128(25, 2))})
    path = _write(tmp_path, tbl)
    _assert_parity(path)


def test_timestamp_micros(tmp_path):
    n = 1500
    rng = np.random.default_rng(5)
    us = rng.integers(0, 2_000_000_000_000_000, n)
    tbl = pa.table({"ts": pa.array(us, type=pa.timestamp("us"))})
    path = _write(tmp_path, tbl, use_dictionary=False)
    _assert_parity(path)


def test_multi_row_group_aggregate(tmp_path):
    n = 20_000
    rng = np.random.default_rng(6)
    tbl = pa.table({
        "k": pa.array(rng.integers(0, 9, n), type=pa.int32()),
        "v": pa.array(rng.integers(0, 10**6, n).tolist(),
                      type=pa.decimal128(12, 2)),
    })
    path = _write(tmp_path, tbl, row_group_size=3000)
    _assert_parity(
        path, sql="SELECT k, sum(v) s, count(*) c FROM t "
                  "GROUP BY k ORDER BY k")


# -- full encoding matrix (ISSUE 9 tentpole) -------------------------------

def test_delta_binary_packed_device_decode(tmp_path):
    # DELTA_BINARY_PACKED int64/int32: miniblock runs decoded on device
    # + segmented prefix-sum reconstruction, vs the pyarrow oracle
    n = 30_000
    rng = np.random.default_rng(7)
    tbl = pa.table({
        "i64": pa.array(rng.integers(-(1 << 50), 1 << 50, n),
                        type=pa.int64()),
        "i32": pa.array(rng.integers(-(1 << 30), 1 << 30, n)
                        .astype("int32"), type=pa.int32()),
        "sorted": pa.array(np.cumsum(rng.integers(0, 9, n)),
                           type=pa.int64()),
    })
    path = _write(tmp_path, tbl, use_dictionary=False,
                  column_encoding="DELTA_BINARY_PACKED",
                  data_page_size=8192)
    m = _assert_parity(path)
    assert m.get("deviceDecodedValues.DELTA_BINARY_PACKED", 0) >= 3 * n, m


def test_delta_binary_packed_nulls_and_page_boundaries(tmp_path):
    n = 9000
    vals = [None if (i // 41) % 3 == 0 else (i * 7919) % (1 << 40) - 17
            for i in range(n)]
    tbl = pa.table({"v": pa.array(vals, type=pa.int64())})
    path = _write(tmp_path, tbl, use_dictionary=False,
                  column_encoding="DELTA_BINARY_PACKED",
                  data_page_size=1024)
    _assert_parity(path)


@pytest.mark.parametrize("n", [5000, 11_000])
def test_nullable_bool_and_delta_at_odd_capacity(tmp_path, n):
    # row counts whose capacity bucket is no power of two (5,120 and
    # 12,288 lanes): definition levels, bool bits and DELTA miniblocks
    # all read their run's fields lane by lane over such a cap
    rng = np.random.default_rng(n)
    tbl = pa.table({
        "i": pa.array([None if (i // 29) % 4 == 1 else int(v) for i, v
                       in enumerate(rng.integers(0, 300, n))],
                      type=pa.int64()),
        "b": pa.array([None if i % 13 == 5 else bool(v) for i, v
                       in enumerate(rng.integers(0, 2, n))],
                      type=pa.bool_()),
        "d": pa.array([None if (i // 53) % 5 == 0 else int(v) for i, v
                       in enumerate(rng.integers(-(1 << 45), 1 << 45, n))],
                      type=pa.int64()),
    })
    path = _write(tmp_path, tbl, use_dictionary=["i"],
                  column_encoding={"d": "DELTA_BINARY_PACKED"},
                  data_page_size=2048)
    m = _assert_parity(path)
    assert m.get("deviceDecodedValues.DELTA_BINARY_PACKED", 0) > 0, m
    assert m.get("deviceDecodedValues.RLE_DICTIONARY", 0) > 0, m


def test_delta_decimal_int_physical(tmp_path):
    # decimal with INT32/INT64 physical storage rides the delta path
    n = 4000
    rng = np.random.default_rng(17)
    tbl = pa.table({
        "d": pa.array(rng.integers(0, 10**6, n).tolist(),
                      type=pa.decimal128(9, 2)),
    })
    import pyarrow.parquet as _pq
    path = os.path.join(str(tmp_path), "d.parquet")
    try:
        _pq.write_table(tbl, path, use_dictionary=False,
                        store_decimal_as_integer=True,
                        column_encoding="DELTA_BINARY_PACKED")
    except (OSError, TypeError) as e:
        pytest.skip(f"writer cannot emit delta decimal: {e}")
    enc = _pq.ParquetFile(path).metadata.row_group(0).column(0).encodings
    if "DELTA_BINARY_PACKED" not in enc:
        pytest.skip(f"writer did not emit delta for decimal: {enc}")
    _assert_parity(path, sql="SELECT sum(d) s, count(*) c FROM t")


def test_plain_byte_array_device_decode(tmp_path):
    # PLAIN string pages: host extracts lengths only; the offsets
    # column is a device segmented prefix-sum, the bytes a gather
    n = 2500
    tbl = pa.table({
        "s": pa.array([f"value-{i}" for i in range(n)]),
        "i": pa.array(np.arange(n), type=pa.int64()),
    })
    path = _write(tmp_path, tbl, use_dictionary=False)
    m = _assert_parity(path)
    assert m.get("deviceDecodedValues.PLAIN", 0) >= 2 * n, m


def test_plain_strings_empty_and_nulls_at_page_boundaries(tmp_path):
    # empty strings, nulls straddling tiny pages, variable lengths
    n = 6000
    vals = []
    for i in range(n):
        if (i // 37) % 3 == 1:
            vals.append(None)
        elif i % 11 == 0:
            vals.append("")
        else:
            vals.append("x" * (i % 23) + f"#{i}")
    tbl = pa.table({"s": pa.array(vals)})
    path = _write(tmp_path, tbl, use_dictionary=False,
                  data_page_size=512)
    _assert_parity(path)


def test_string_dict_overflow_to_plain_mid_chunk(tmp_path):
    # the writer starts RLE_DICTIONARY, overflows the dict-page limit,
    # and finishes the SAME chunk with PLAIN byte-array pages: both
    # lanes decode on device, selected per page
    n = 12_000
    rng = np.random.default_rng(13)
    vals = [f"prefix-{int(v)}-suffix" for v in rng.integers(0, 6000, n)]
    tbl = pa.table({"s": pa.array(vals)})
    path = _write(tmp_path, tbl, dictionary_pagesize_limit=8_000,
                  data_page_size=4096)
    import pyarrow.parquet as _pq
    encs = _pq.ParquetFile(path).metadata.row_group(0).column(0).encodings
    m = _assert_parity(path)
    if "PLAIN" in encs:  # overflow really happened
        assert m.get("deviceDecodedValues.PLAIN", 0) > 0, (encs, m)
        assert m.get("deviceDecodedValues.RLE_DICTIONARY", 0) > 0, m


def test_binary_plain_device_decode(tmp_path):
    n = 1500
    rng = np.random.default_rng(14)
    vals = [rng.bytes(int(rng.integers(0, 19))) for _ in range(n)]
    tbl = pa.table({"b": pa.array(vals, type=pa.binary()),
                    "k": pa.array(np.arange(n) % 7, type=pa.int64())})
    path = _write(tmp_path, tbl, use_dictionary=False)
    _assert_parity(path, sql="SELECT k, count(b) c FROM t GROUP BY k "
                             "ORDER BY k")


@pytest.mark.parametrize("case", ["all_null", "all_empty", "utf8",
                                  "binary", "declined"])
def test_plain_string_chunk_from_pyarrow_decode(tmp_path, monkeypatch,
                                                case):
    # a string chunk with PLAIN pages is planned from pyarrow's decode of
    # it as one concatenated-bytes page; where that declines the chunk,
    # its pages are inflated and walked as before. Either way the device
    # decodes it bit-equal to the host
    from spark_rapids_tpu.io import device_decode as DD
    n = 3000
    vals = {"all_null": [None] * n,
            "all_empty": [""] * n,
            "utf8": [None if i % 9 == 4 else "é€" * (i % 7) + f"ü{i}"
                     for i in range(n)],
            "binary": [None if i % 5 == 1 else bytes([i % 256]) * (i % 13)
                       for i in range(n)],
            "declined": [f"row-{i}-" + "z" * (i % 31) for i in range(n)],
            }[case]
    typ = pa.binary() if case == "binary" else pa.string()
    path = _write(tmp_path, pa.table({"s": pa.array(vals, type=typ),
                                      "i": pa.array(np.arange(n))}),
                  use_dictionary=False, data_page_size=2048)
    if case == "declined":
        monkeypatch.setattr(DD, "_arrow_values", lambda *a: None)
    m = _assert_parity(path)
    assert m.get("deviceDecodedValues.PLAIN", 0) == n + sum(
        v is not None for v in vals), m
    from spark_rapids_tpu.io.readers import ScanUnit
    from spark_rapids_tpu.sql import types as T
    schema = T.StructType([T.StructField(
        "s", T.BinaryT if case == "binary" else T.StringT, True)])
    plan = DD.plan_unit_encoded(ScanUnit(path, os.path.getsize(path), [0]),
                                schema).plans[0]
    assert (plan.pg_enc == [DD.PGE_DL_STR]) == (case != "declined")
    assert (plan.dl is None) == (case in ("all_empty", "declined"))


def test_plain_long_strings_with_high_bytes_parity(tmp_path):
    # a no-dictionary PLAIN string chunk of 19-78-byte values (Q13's
    # comments) holding bytes >= 0x80 at every phase: each value's
    # window of staging words gathers bit-equal to the host's decode
    rng = np.random.default_rng(43)
    n = 3000
    chars = np.array(list("abcdefgh ,.é€üß"))
    vals = []
    for i in range(n):
        s = "".join(rng.choice(chars, int(rng.integers(8, 40))))
        s = s.encode()[:int(rng.integers(19, 79))].decode(errors="ignore")
        vals.append((s + "x" * 19)[:max(19, len(s))])
    raw = [rng.integers(0, 256, int(rng.integers(19, 79)),
                        dtype=np.uint8).tobytes() for _ in range(n)]
    path = _write(tmp_path, pa.table({
        "s": pa.array(vals), "b": pa.array(raw, type=pa.binary())}),
        use_dictionary=False)
    m = _assert_parity(path)
    assert m.get("deviceDecodedValues.PLAIN", 0) == 2 * n, m


@pytest.mark.parametrize("dictionary", [False, True])
def test_string_words_counter(tmp_path, dictionary):
    # deviceDecodeStringWords books lanes x (char_cap/4 + 1) for every
    # string column whose values are gathered from the staging words,
    # and nothing for a chunk that is dictionary-coded throughout
    from spark_rapids_tpu.columnar.device import (bucket_capacity,
                                                  bucket_char_cap)
    n = 2000
    short = [f"s{i % 40 if dictionary else i}" for i in range(n)]
    long = [f"long-{i % 7 if dictionary else i}-" + "q" * (i % 50)
            for i in range(n)]
    path = _write(tmp_path, pa.table({"a": pa.array(short),
                                      "b": pa.array(long)}),
                  use_dictionary=dictionary)
    m = _assert_parity(path)
    assert m.get("deviceDecodedValues.RLE_DICTIONARY", 0) == \
        (2 * n if dictionary else 0), m
    cap = bucket_capacity(n)
    want = 0 if dictionary else sum(
        cap * (bucket_char_cap(max(map(len, col))) // 4 + 1)
        for col in (short, long))
    assert m.get("deviceDecodeStringWords", 0) == want, m
    assert m.get("deviceDecodeStringBytes", 0) == sum(
        cap * bucket_char_cap(max(map(len, col))) for col in (short, long))


def test_delta_length_byte_array(tmp_path):
    n = 5000
    vals = ["" if i % 13 == 0 else
            None if i % 17 == 0 else f"dl-{i % 97}-{'y' * (i % 9)}"
            for i in range(n)]
    tbl = pa.table({"s": pa.array(vals),
                    "i": pa.array(np.arange(n), type=pa.int64())})
    path = _write(tmp_path, tbl, use_dictionary=False,
                  column_encoding={"s": "DELTA_LENGTH_BYTE_ARRAY",
                                   "i": "PLAIN"},
                  data_page_size=2048)
    m = _assert_parity(path)
    assert m.get("deviceDecodedValues.DELTA_LENGTH_BYTE_ARRAY", 0) > 0, m


def test_byte_stream_split_float_and_int(tmp_path):
    n = 4000
    rng = np.random.default_rng(15)
    cols = {
        "f": pa.array(rng.random(n).astype("float32"),
                      type=pa.float32()),
        "i64": pa.array(rng.integers(-(1 << 50), 1 << 50, n),
                        type=pa.int64()),
        "i32": pa.array(rng.integers(-(1 << 30), 1 << 30, n)
                        .astype("int32"), type=pa.int32()),
    }
    tbl = pa.table(cols)
    path = _write(tmp_path, tbl, use_dictionary=False,
                  column_encoding="BYTE_STREAM_SPLIT")
    m = _assert_parity(path, sql="SELECT i64, i32 FROM t")
    assert m.get("deviceDecodedValues.BYTE_STREAM_SPLIT", 0) >= 2 * n, m


def test_byte_stream_split_double_matches_backend(tmp_path):
    from spark_rapids_tpu.device_caps import f64_bitcast_exact
    n = 2000
    rng = np.random.default_rng(16)
    tbl = pa.table({"d": pa.array(rng.random(n), type=pa.float64())})
    path = _write(tmp_path, tbl, use_dictionary=False,
                  column_encoding="BYTE_STREAM_SPLIT")
    expect_fb = 0 if f64_bitcast_exact() else 1
    _assert_parity(path, expect_device=expect_fb == 0,
                   expect_fallback_cols=expect_fb,
                   sql="SELECT d FROM t WHERE d >= 0")


def test_data_page_v2(tmp_path):
    # v2 pages: uncompressed level section, RLE boolean values
    tbl = _mixed_table(n=3000, seed=18)
    path = _write(tmp_path, tbl, data_page_version="2.0",
                  data_page_size=2048)
    _assert_parity(path)


# -- fallback behavior -----------------------------------------------------

def test_unsupported_encoding_falls_back_per_column(tmp_path):
    # DELTA_BYTE_ARRAY (prefix/suffix strings) is genuinely
    # unsupported: that column host-decodes, the sibling stays on
    # device, and the host fallback is visible per encoding
    n = 3000
    tbl = pa.table({
        "dba": pa.array([f"prefix-common-{i}" for i in range(n)]),
        "ok": pa.array(np.arange(n), type=pa.int64()),
    })
    path = _write(tmp_path, tbl, use_dictionary=False,
                  column_encoding={"dba": "DELTA_BYTE_ARRAY",
                                   "ok": "PLAIN"})
    m = _assert_parity(path, expect_fallback_cols=1)
    # the supported sibling column still decoded on device
    assert m.get("deviceDecodedValues.PLAIN", 0) >= n, m
    assert m.get("hostDecodedValues.DELTA_BYTE_ARRAY", 0) >= n, m


def test_per_encoding_enable_confs(tmp_path):
    # each deviceDecode.<enc>.enabled=false turns exactly that lane
    # into a per-column host fallback, bit-identical either way
    n = 2000
    rng = np.random.default_rng(19)
    tbl = pa.table({
        "s": pa.array([f"v{i}" for i in range(n)]),
        "d": pa.array(rng.integers(0, 10**6, n), type=pa.int64()),
        "b": pa.array(rng.random(n).astype("float32"),
                      type=pa.float32()),
    })
    path = _write(tmp_path, tbl, use_dictionary=False,
                  column_encoding={"s": "PLAIN",
                                   "d": "DELTA_BINARY_PACKED",
                                   "b": "BYTE_STREAM_SPLIT"})
    base = "spark.rapids.sql.format.parquet.deviceDecode."
    for key, col in ((base + "byteArray.enabled", "s"),
                     (base + "delta.enabled", "d"),
                     (base + "byteStreamSplit.enabled", "b")):
        host, _ = _collect(path, False)
        dev, m = _collect(path, True, {key: "false"})
        assert host == dev, (key, col)
        assert m.get("deviceFallbackColumns", 0) >= 1, (key, m)


def test_double_fallback_matches_backend(tmp_path):
    from spark_rapids_tpu.device_caps import f64_bitcast_exact
    n = 2000
    rng = np.random.default_rng(8)
    tbl = pa.table({"f": pa.array(rng.random(n), type=pa.float64())})
    path = _write(tmp_path, tbl, use_dictionary=False)
    expect_fb = 0 if f64_bitcast_exact() else 1
    _assert_parity(path, expect_device=expect_fb == 0,
                   expect_fallback_cols=expect_fb,
                   sql="SELECT f FROM t WHERE f >= 0")


def test_cpu_consumer_never_sees_encoded_batches(tmp_path):
    # rapids disabled: the same conf key must be inert — the scan's
    # emit_encoded gate only opens under a TpuRowToColumnarExec
    path = _write(tmp_path, _mixed_table(n=500, seed=9))
    spark = TpuSparkSession({"spark.rapids.sql.enabled": "false",
                             DEV_CONF: "true"})
    try:
        out = spark.read.parquet(path)._execute().to_pydict()
        assert len(out["i64"]) == 500
    finally:
        spark.stop()


def test_partitioned_dataset_device_decode(tmp_path):
    base = str(tmp_path / "part")
    for g in (1, 2):
        os.makedirs(f"{base}/g={g}", exist_ok=True)
        n = 800
        tbl = pa.table({
            "v": pa.array(np.arange(n) * g, type=pa.int64()),
            "s": pa.array([f"p{g}x{i % 3}" for i in range(n)]),
        })
        pq.write_table(tbl, f"{base}/g={g}/part-0.parquet")
    _assert_parity(base,
                   sql="SELECT g, count(*) c, sum(v) s FROM t "
                       "GROUP BY g ORDER BY g")


def test_reader_type_multithreaded_device_decode(tmp_path):
    base = str(tmp_path / "many")
    os.makedirs(base, exist_ok=True)
    rng = np.random.default_rng(10)
    for i in range(6):
        n = 2000
        tbl = pa.table({
            "v": pa.array(rng.integers(0, 10**6, n).tolist(),
                          type=pa.decimal128(10, 2)),
            "k": pa.array(rng.integers(0, 5, n), type=pa.int32()),
        })
        pq.write_table(tbl, f"{base}/f{i}.parquet")
    for rt in ("PERFILE", "MULTITHREADED"):
        host, _ = _collect(
            base, False,
            {"spark.rapids.sql.format.parquet.reader.type": rt},
            sql="SELECT k, sum(v) s FROM t GROUP BY k ORDER BY k")
        dev, m = _collect(
            base, True,
            {"spark.rapids.sql.format.parquet.reader.type": rt},
            sql="SELECT k, sum(v) s FROM t GROUP BY k ORDER BY k")
        assert host == dev
        assert m.get("deviceDecodedBatches", 0) >= 1, (rt, m)


# -- scan pipeline (async read->decode->compute, docs/scan.md) -------------

MAXIF_CONF = "spark.rapids.sql.format.parquet.deviceDecode.maxInFlight"


def _write_q1_shaped(tmp_path, n=24_000):
    """A lineitem-shaped dataset (decimal money, low-cardinality
    strings, dates) across several row groups — the bench smoke's
    schema at corpus scale."""
    rng = np.random.default_rng(20)
    tbl = pa.table({
        "qty": pa.array(rng.integers(100, 5100, n).tolist(),
                        type=pa.decimal128(15, 2)),
        "price": pa.array(rng.integers(90100, 10494951, n).tolist(),
                          type=pa.decimal128(15, 2)),
        "flag": pa.array([("A", "N", "R")[int(v)]
                          for v in rng.integers(0, 3, n)]),
        "status": pa.array([("O", "F")[int(v)]
                            for v in rng.integers(0, 2, n)]),
        "ship": pa.array(rng.integers(8000, 10500, n).astype("int32"),
                         type=pa.date32()),
    })
    path = os.path.join(str(tmp_path), "lineitem.parquet")
    pq.write_table(tbl, path, row_group_size=4000)
    return path


Q1_SHAPED_SQL = ("SELECT flag, status, sum(qty) sq, sum(price) sp, "
                 "count(*) c FROM t WHERE ship <= date '1998-09-02' "
                 "GROUP BY flag, status ORDER BY flag, status")


def _plan_metrics(spark):
    from spark_rapids_tpu.metrics import registry_snapshot
    return registry_snapshot(spark.get_captured_plans())["metrics"]


def test_q1_shaped_bit_identical_across_decode_and_pipeline(tmp_path):
    # the acceptance sweep: device decode on/off x pipeline depth
    # 0 (sync) / 1 (prefetch only) / 3 (upload-ahead) all bit-identical
    path = _write_q1_shaped(tmp_path)
    want, _ = _collect(path, False, sql=Q1_SHAPED_SQL)
    for depth in ("0", "1", "3"):
        got, m = _collect(path, True, {MAXIF_CONF: depth},
                          sql=Q1_SHAPED_SQL)
        assert got == want, depth
        assert m.get("deviceDecodedBatches", 0) >= 1, (depth, m)
        assert m.get("deviceFallbackColumns", 0) == 0, (depth, m)


def test_pipelined_scan_metrics_and_spans(tmp_path):
    # default depth: uploads are issued ahead, the producer thread's
    # prefetch wall is interval-union (never exceeds the query wall)
    path = _write_q1_shaped(tmp_path)
    from spark_rapids_tpu.sql.session import TpuSparkSession
    spark = TpuSparkSession({"spark.rapids.sql.enabled": "true",
                             DEV_CONF: "true"})
    try:
        import time
        spark.read.parquet(path).createOrReplaceTempView("t")
        df = spark.sql(Q1_SHAPED_SQL)
        spark.start_capture()
        t0 = time.perf_counter_ns()
        df._execute()
        wall = time.perf_counter_ns() - t0
        m = _plan_metrics(spark)
        assert m.get("uploadAheadBatches", 0) >= 1, m
        assert m.get("scanPrefetchTime", 0) > 0, m
        # the timed_wall audit: prefetch threads must not re-introduce
        # the PR 1 decodeTime > wall over-count
        assert m["scanPrefetchTime"] <= wall, (m["scanPrefetchTime"],
                                               wall)
        assert m.get("deviceDecodeTime", 0) <= wall, m
    finally:
        spark.stop()


@pytest.mark.fault
def test_pipelined_scan_injected_io_error_cancels_cleanly(tmp_path):
    # an IO error that exhausts reader retries must surface as the
    # query error (not hang the ring), and the next query on a clean
    # injector must succeed — prefetch state drained
    from spark_rapids_tpu import retry as R
    path = _write_q1_shaped(tmp_path)
    R.reset_fault_injection()
    try:
        with pytest.raises(Exception) as ei:
            _collect(path, True,
                     {"spark.rapids.sql.test.injectIOError": "1:99",
                      "spark.rapids.sql.reader.maxRetries": "1"},
                     sql=Q1_SHAPED_SQL)
        assert "injected IO error" in str(ei.value)
    finally:
        R.reset_fault_injection()
    want, _ = _collect(path, False, sql=Q1_SHAPED_SQL)
    got, _ = _collect(path, True, sql=Q1_SHAPED_SQL)
    assert got == want


@pytest.mark.fault
def test_oom_during_prefetched_upload_shrinks_ring(tmp_path):
    # site:upload:N targets exactly the prefetched raw-chunk uploads:
    # the in-flight ring must SHRINK (drain + synchronous retry), not
    # deadlock, and results stay bit-identical
    from spark_rapids_tpu import retry as R
    path = _write_q1_shaped(tmp_path)
    want, _ = _collect(path, False, sql=Q1_SHAPED_SQL)
    R.reset_fault_injection()
    try:
        got, m = _collect(
            path, True,
            {"spark.rapids.sql.test.injectOOM": "site:upload:2"},
            sql=Q1_SHAPED_SQL)
    finally:
        R.reset_fault_injection()
    assert got == want
    assert m.get("prefetchRingShrinks", 0) >= 1, m


def test_site_scoped_injection_grammar():
    from spark_rapids_tpu.retry import FaultInjector, TpuRetryOOM
    inj = FaultInjector(oom_spec="site:upload:2")
    inj.on_alloc()          # untagged: never counts
    inj.on_alloc("other")   # other site: never counts
    inj.on_alloc("upload")  # 1st upload event
    with pytest.raises(TpuRetryOOM):
        inj.on_alloc("upload")  # 2nd fires
    assert inj.oom_injected == 1
    assert FaultInjector(oom_spec="site:upload:split:3")._oom.split


# -- kernel unit tests (ops/rle.py against numpy oracles) ------------------

def _hybrid_stream(values: np.ndarray, width: int):
    """Encode values as one parquet RLE/bit-packed hybrid stream and
    parse it back with the host-side planner, returning the pieces the
    device kernel consumes."""
    from spark_rapids_tpu.io.device_decode import (RunTable,
                                                   _parse_hybrid_runs)
    out = bytearray()
    i, n = 0, len(values)
    while i < n:
        run = 1
        while i + run < n and values[i + run] == values[i]:
            run += 1
        if run >= 8:
            out += _uvarint(run << 1)
            out += int(values[i]).to_bytes((width + 7) // 8, "little")
            i += run
        else:
            j = min(n, i + 8)
            group = list(values[i:j]) + [0] * (8 - (j - i))
            out += _uvarint((1 << 1) | 1)
            bits = 0
            for k, v in enumerate(group):
                bits |= int(v) << (k * width)
            out += bits.to_bytes(width, "little")
            i = j
    runs = RunTable()
    _parse_hybrid_runs(bytes(out), 0, len(out), width, n, 0, 0, runs)
    return np.frombuffer(bytes(out), dtype=np.uint8), runs


def _uvarint(v: int) -> bytes:
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


@pytest.mark.parametrize("width", range(1, 33))
def test_hybrid_lookup_kernel_matches_oracle(width):
    # every width of read_packed's contract (<= 32): value k of a
    # packed group sits at bit k * width, so the odd widths put values
    # at every phase of a staging word, across its edge among them
    import jax.numpy as jnp

    from spark_rapids_tpu.ops import rle as R
    rng = np.random.default_rng(11 + width)
    vals = rng.integers(0, 1 << width, 300)
    vals[40:200] = vals[40]  # force an RLE run
    payload, runs = _hybrid_stream(vals, width)
    words = _words_arr(payload)
    arrs = [jnp.asarray(a) for a in runs.arrays(
        max(8, 1 << (len(runs) - 1).bit_length()))]
    pos = jnp.arange(len(vals), dtype=jnp.int64)
    got = np.asarray(R.hybrid_lookup(words, pos, *arrs))
    # unsigned: a 32-bit value may come back through an int32 field
    assert np.array_equal(got.astype(np.int64) & 0xFFFFFFFF, vals)


def test_dense_ranks_kernel():
    import jax.numpy as jnp

    from spark_rapids_tpu.ops import rle as R
    v = np.array([True, False, True, True, False, True])
    got = np.asarray(R.dense_ranks(jnp.asarray(v)))
    assert got.tolist() == [0, 0, 1, 2, 2, 3]


def _words_arr(payload: bytes):
    import jax.numpy as jnp

    words = np.zeros((len(payload) + 3) // 4 * 4, dtype=np.uint8)
    words[:len(payload)] = np.frombuffer(payload, dtype=np.uint8)
    return jnp.asarray(words.view(np.int32))


def _bytes_arr(payload: bytes):
    from spark_rapids_tpu.ops import rle as R
    return R.bytes_of_words(_words_arr(payload))


@pytest.mark.parametrize("width", [33, 40, 47, 48, 56, 63, 64])
def test_read_packed64_wide_widths(width):
    import jax.numpy as jnp

    from spark_rapids_tpu.ops import rle as R
    rng = np.random.default_rng(21 + width)
    vals = [int(v) for v in
            rng.integers(0, 1 << 62, 40)] if width < 64 else \
        [int(v) for v in rng.integers(-(1 << 62), 1 << 62, 40)]
    vals = [v & ((1 << width) - 1) for v in vals]
    bits = 0
    for k, v in enumerate(vals):
        bits |= v << (k * width)
    payload = bits.to_bytes((len(vals) * width + 7) // 8 + 8, "little")
    words = _words_arr(payload)
    off = jnp.asarray(np.arange(len(vals), dtype=np.int64) * width)
    w = jnp.full(len(vals), width, dtype=jnp.int64)
    got = np.asarray(R.read_packed64(words, off, w)).astype(np.uint64)
    want = np.array(vals, dtype=np.uint64)
    assert np.array_equal(got, want)


def test_delta_host_decoder_matches_pyarrow(tmp_path):
    # the host DELTA decoder (used for DELTA_LENGTH lengths) against
    # pyarrow's own decode of a DELTA_BINARY_PACKED file
    import pyarrow.parquet as _pq

    from spark_rapids_tpu.io.device_decode import (_delta_decode_host,
                                                   parse_page_header)
    rng = np.random.default_rng(22)
    n = 5000
    vals = rng.integers(-(1 << 45), 1 << 45, n)
    tbl = pa.table({"v": pa.array(vals, type=pa.int64())})
    path = os.path.join(str(tmp_path), "d.parquet")
    _pq.write_table(tbl, path, use_dictionary=False,
                    column_encoding="DELTA_BINARY_PACKED",
                    compression="NONE")
    meta = _pq.ParquetFile(path).metadata.row_group(0).column(0)
    with open(path, "rb") as f:
        f.seek(meta.data_page_offset)
        raw = f.read(meta.total_compressed_size)
    decoded = []
    pos = 0
    while pos < len(raw) and len(decoded) < n:
        hdr, body_off = parse_page_header(raw, pos)
        csize = hdr.get(3, 0)
        body = raw[body_off:body_off + csize]
        pos = body_off + csize
        if hdr.get(1) != 0:
            continue
        # optional column: skip the length-prefixed def-level section
        dl_len = int.from_bytes(body[0:4], "little")
        val_off = 4 + dl_len
        got, _end = _delta_decode_host(body, val_off, len(body))
        decoded.extend(got.tolist())
    assert decoded == vals.tolist()


def test_plain_str_lengths_oracle():
    from spark_rapids_tpu.io.device_decode import _plain_str_lengths
    rng = np.random.default_rng(23)
    vals = [b"x" * int(rng.integers(0, 37)) for _ in range(500)]
    body = b"".join(len(v).to_bytes(4, "little") + v for v in vals)
    lens = _plain_str_lengths(body, 0, len(body), len(vals))
    assert lens.tolist() == [len(v) for v in vals]


def test_gather_chars_and_seg_cumsum_kernels():
    import jax.numpy as jnp

    from spark_rapids_tpu.ops import rle as R
    data = b"heyworldabc!"
    starts = jnp.asarray(np.array([0, 3, 8], dtype=np.int64))
    lens = jnp.asarray(np.array([3, 5, 4], dtype=np.int32))
    out = np.asarray(R.gather_char_words(_words_arr(data), starts, lens, 8))
    assert bytes(out[0][:3]) == b"hey" and out[0][3:].tolist() == [0] * 5
    assert bytes(out[1][:5]) == b"world"
    assert bytes(out[2][:4]) == b"abc!"
    # segmented exclusive cumsum: two segments starting at lanes 0, 3
    contrib = jnp.asarray(np.array([2, 3, 4, 10, 20, 30],
                                   dtype=np.int64))
    seg = jnp.asarray(np.array([0, 0, 0, 3, 3, 3], dtype=np.int64))
    got = np.asarray(R.seg_excl_cumsum(contrib, seg))
    assert got.tolist() == [0, 2, 5, 0, 10, 30]


@pytest.mark.parametrize("char_cap", [8, 24, 80, 256])
@pytest.mark.parametrize("phase", [0, 1, 2, 3])
def test_gather_char_words_matches_numpy(char_cap, phase):
    # values end to end from a byte of the given phase, as a
    # concatenated-bytes page lays them: every length from 0 to char_cap
    # that matters, bytes 0x80-0xFF at each of a word's four positions
    # (an arithmetic shift of the int32 words would smear their sign),
    # and the last value, one byte in the buffer's last word, whose
    # window reaches char_cap/4 words past the buffer (clipped word by
    # word; a window clamped as a whole would shift the value)
    import jax.numpy as jnp

    from spark_rapids_tpu.ops import rle as R
    rng = np.random.default_rng(42 * char_cap + phase)
    lens = [0, 1, char_cap - 1, char_cap, 0, 3, char_cap // 2]
    lens += rng.integers(0, char_cap + 1, 40).tolist()
    lens += [char_cap - 1, char_cap, 1]
    vals = []
    for k, n in enumerate(lens):
        hi = rng.integers(0x80, 0x100, n, dtype=np.uint8)
        lo = rng.integers(0, 0x80, n, dtype=np.uint8)
        # value k sets the top bit on every (k % 5)-th byte, or on all
        vals.append(np.where(np.arange(n) % 5 == k % 5 if k % 6 else True,
                             hi, lo).astype(np.uint8).tobytes())
    base = 4 * 3 + phase
    payload = bytes(rng.integers(0, 256, base, dtype=np.uint8)) \
        + b"".join(vals)
    starts = base + np.concatenate([[0], np.cumsum(lens[:-1])])
    assert (len(payload) - 1) // 4 == (starts[-1] + lens[-1] - 1) // 4
    want = np.zeros((len(lens), char_cap), dtype=np.uint8)
    for i, v in enumerate(vals):
        want[i, :len(v)] = np.frombuffer(v, dtype=np.uint8)
    got = np.asarray(R.gather_char_words(
        _words_arr(payload),
        jnp.asarray(starts.astype(np.int64)),
        jnp.asarray(np.array(lens, dtype=np.int32)), char_cap))
    assert got.dtype == np.uint8 and got.shape == want.shape
    assert np.array_equal(got, want)


def test_read_bss_kernel():
    from spark_rapids_tpu.ops import rle as R
    import jax.numpy as jnp
    rng = np.random.default_rng(24)
    vals = rng.integers(-(1 << 60), 1 << 60, 17)
    raw = vals.astype("<i8").tobytes()
    # split the byte planes the BYTE_STREAM_SPLIT way
    planes = b"".join(raw[j::8] for j in range(8))
    ba = _bytes_arr(planes)
    n = len(vals)
    base = jnp.zeros(n, dtype=jnp.int64)
    stride = jnp.full(n, n, dtype=jnp.int64)
    local = jnp.asarray(np.arange(n, dtype=np.int64))
    got = np.asarray(R.read_bss(ba, base, stride, local, 8))
    assert got.tolist() == vals.tolist()


# -- the PLAIN region: staged end to end, read at a fixed stride -------------

def _plan_file(path):
    """The file's one row group through ``plan_unit_encoded``."""
    from spark_rapids_tpu.io import device_decode as DD
    from spark_rapids_tpu.io.arrow_convert import arrow_schema_to_sql
    from spark_rapids_tpu.io.readers import ScanUnit
    schema = arrow_schema_to_sql(pq.read_schema(path))
    enc = DD.plan_unit_encoded(ScanUnit(path, os.path.getsize(path), [0]),
                               schema)
    assert enc is not None and not enc.fallbacks, enc and enc.fallbacks
    return enc


def _decode_encoded(enc):
    from spark_rapids_tpu.columnar import transfer as TR
    from spark_rapids_tpu.columnar.device import bucket_capacity
    cap = bucket_capacity(enc.num_rows)
    staged = TR.prepare_encoded_upload(enc, cap)
    return staged, TR.finish_upload(staged).to_host().to_pydict()


def _plain_column(kind, n, rng):
    """(arrow array, bytes of one stored value as PLAIN writes it)."""
    import decimal
    import struct
    if kind == "int64":
        v = rng.integers(-(1 << 62), 1 << 62, n)
        return pa.array(v, type=pa.int64()), \
            lambda x: int(x).to_bytes(8, "little", signed=True)
    if kind == "date32":
        v = rng.integers(-30_000, 60_000, n).astype("int32")
        return pa.array(v, type=pa.date32()), \
            lambda x: int(x).to_bytes(4, "little", signed=True)
    if kind == "float32":
        v = rng.normal(size=n).astype("float32")
        return pa.array(v), lambda x: struct.pack("<f", x)
    if kind == "float64":
        v = rng.normal(size=n)
        return pa.array(v), lambda x: struct.pack("<d", x)
    prec, width = {"decimal15_2": (15, 7), "decimal7_2": (7, 4),
                   "decimal30_4": (30, 13)}[kind]
    v = rng.integers(-10 ** min(prec, 18) + 1, 10 ** min(prec, 18), n)
    scale = 4 if prec == 30 else 2
    arr = pa.array([decimal.Decimal(int(x)).scaleb(-scale) for x in v],
                   type=pa.decimal128(prec, scale))
    return arr, lambda x: int(x).to_bytes(width, "big", signed=True)


_PLAIN_KINDS = ["int64", "date32", "float32", "float64", "decimal15_2",
                "decimal7_2", "decimal30_4"]
# how the chunk comes to hold PLAIN pages, and what else it holds
_PLAIN_CHUNKS = ["overflow_mid_chunk", "plain_from_first_page",
                 "overflow_with_nulls", "overflow_v2_pages",
                 "plain_v2_with_nulls"]


@pytest.mark.parametrize("chunk", _PLAIN_CHUNKS)
@pytest.mark.parametrize("kind", _PLAIN_KINDS)
def test_plain_region_is_contiguous_aligned_and_in_dense_order(
        tmp_path, kind, chunk):
    """What ``rle.read_plain`` relies on, on written files: the bytes
    from ``plain_base`` ARE the PLAIN pages' stored values, end to end
    and in dense order, from a 4-aligned byte; the pages are dictionary
    pages then PLAIN ones, so no page table rides; and the decoded
    column is pyarrow's. 20,011 rows of tiny pages: a last page of odd
    length, which at W = 7 and 13 ends off a word."""
    n = 20_011
    rng = np.random.default_rng(len(kind) * 31 + len(chunk))
    arr, stored = _plain_column(kind, n, rng)
    nulls = "nulls" in chunk
    if nulls:
        mask = rng.random(n) < 0.3
        mask[:40] = True      # a page of nulls only, then mixed pages
        arr = pa.array([None if m else x
                        for m, x in zip(mask, arr.to_pylist())],
                       type=arr.type)
    opts = {"data_page_size": 4096, "compression": "snappy",
            "data_page_version": "2.0" if "v2" in chunk else "1.0"}
    if chunk.startswith("overflow"):
        opts["dictionary_pagesize_limit"] = 12_000
    else:
        opts["use_dictionary"] = False
    path = _write(tmp_path, pa.table({"c": arr}), **opts)
    enc = _plan_file(path)
    plan = enc.plans[0]
    from spark_rapids_tpu.io.device_decode import PGE_DICT, PGE_PLAIN
    n_dict = plan.pg_enc.count(PGE_DICT)
    assert plan.pg_enc == [PGE_DICT] * n_dict \
        + [PGE_PLAIN] * (len(plan.pg_enc) - n_dict)
    assert bool(n_dict) == chunk.startswith("overflow")
    assert plan.has_plain and not plan.paged
    assert (plan.dl is not None) == nulls
    d0 = plan.pg_dense_start[n_dict]
    assert plan.plain_dense0 == d0 and plan.plain_base % 4 == 0
    # stored (non-null) values from dense lane d0 on, as PLAIN writes them
    dense = [x for x in pq.read_table(path).column("c").to_pylist()
             if x is not None]
    if kind.startswith("decimal"):
        dense = [int(x.scaleb(arr.type.scale)) for x in dense]
    elif kind == "date32":
        import datetime
        dense = [(x - datetime.date(1970, 1, 1)).days for x in dense]
    want = b"".join(stored(x) for x in dense[d0:])
    assert plan.n_dense == len(dense) and len(dense) > d0
    raw = enc.words.tobytes()
    assert raw[plan.plain_base:plan.plain_base + len(want)] == want
    # nothing behind the region but its padding to a word, then zero
    # words up to the bucket the upload sends
    from spark_rapids_tpu.columnar.device import bucket_capacity
    end = plan.plain_base + len(want) + (-len(want)) % 4
    assert not raw[plan.plain_base + len(want):].strip(b"\0")
    assert len(raw) == 4 * bucket_capacity(end // 4)
    staged, got = _decode_encoded(enc)
    assert [ent[5] for ent in staged[6]] == [0]    # npg: no page table
    assert got["c"] == pq.read_table(path).column("c").to_pylist()


def _thrift(fields):
    """A compact-protocol struct of i32 fields and nested structs:
    ``[(field id, int | list)]`` in ascending ids."""
    out, last = bytearray(), 0
    for fid, v in fields:
        nested = isinstance(v, list)
        out.append(((fid - last) << 4) | (12 if nested else 5))
        last = fid
        out += _thrift(v) if nested else _uvarint((v << 1) ^ (v >> 31))
    return bytes(out) + b"\x00"


def _page(ptype, body, header_field, header):
    return _thrift([(1, ptype), (2, len(body)), (3, len(body)),
                    (header_field, header)]) + body


def test_plain_pages_around_a_dictionary_page_share_one_region():
    """A chunk no writer produces, built by hand: dictionary, PLAIN,
    dictionary, PLAIN pages of a required decimal(15,2). ONE device
    path serves it: the region spans the dense lanes from the first
    PLAIN value to the last with the dictionary page's slots as zeros,
    the page table rides and says which lanes read it."""
    import types
    from spark_rapids_tpu.columnar.transfer import _Packer
    from spark_rapids_tpu.io import device_decode as DD
    from spark_rapids_tpu.sql import types as T
    w = 7
    dictionary = [10 ** 12 + 7, -5, 123_456_789_012_345, -(10 ** 14)]

    def flba(x):
        return int(x).to_bytes(w, "big", signed=True)

    def dict_page(idx):      # bit width 2, one bit-packed run
        groups = -(-len(idx) // 8)
        bits = sum(v << (2 * i) for i, v in enumerate(idx))
        body = b"\x02" + _uvarint((groups << 1) | 1) \
            + bits.to_bytes(groups * 2, "little")
        return _page(DD.PAGE_DATA, body, 5,
                     [(1, len(idx)), (2, DD.ENC_RLE_DICTIONARY), (3, 3),
                      (4, 3)])

    def plain_page(vals):
        return _page(DD.PAGE_DATA, b"".join(map(flba, vals)), 5,
                     [(1, len(vals)), (2, DD.ENC_PLAIN), (3, 3), (4, 3)])

    i1, p1 = [0, 1, 2, 3, 3, 2, 1, 0, 1, 1, 3], [7, -8, 2 ** 40 + 1]
    i2, p2 = [2, 2, 0, 3, 1], [-(2 ** 50), 99, 0, -1, 5 * 10 ** 13]
    raw = _page(DD.PAGE_DICTIONARY, b"".join(map(flba, dictionary)), 7,
                [(1, len(dictionary)), (2, DD.ENC_PLAIN)]) \
        + dict_page(i1) + plain_page(p1) + dict_page(i2) + plain_page(p2)
    want = [dictionary[i] for i in i1] + p1 \
        + [dictionary[i] for i in i2] + p2
    dt = T.DecimalType(15, 2)
    leaf = types.SimpleNamespace(
        max_repetition_level=0, max_definition_level=0,
        physical_type="FIXED_LEN_BYTE_ARRAY", length=w,
        logical_type="Decimal(precision=15, scale=2)")
    chunk = types.SimpleNamespace(compression="UNCOMPRESSED")
    packer = _Packer()
    packer.add(np.arange(5, dtype=np.uint8))   # the region is not first
    plan = DD._plan_column(raw, chunk, leaf, dt, len(want), packer)
    assert plan.pg_enc == [DD.PGE_DICT, DD.PGE_PLAIN] * 2
    assert plan.paged and plan.plain_dense0 == len(i1)
    assert plan.plain_base % 4 == 0
    region = packer.words().tobytes()[plan.plain_base:]
    assert region[:(len(want) - len(i1)) * w] == b"".join(
        map(flba, p1)) + bytes(len(i2) * w) + b"".join(map(flba, p2))
    enc = DD.EncodedBatch(T.StructType([T.StructField("c", dt, False)]),
                          len(want), packer.words(), {0: plan}, {}, [])
    staged, got = _decode_encoded(enc)
    assert [ent[5] for ent in staged[6]] == [8]    # the page table rides
    import decimal
    assert got["c"] == [decimal.Decimal(v).scaleb(-2) for v in want]


def test_one_decode_program_serves_files_that_overflow_at_other_lanes(
        tmp_path):
    """``plain_base`` and ``plain_dense0`` are runtime values: two
    files of one shape whose dictionaries overflow at different lanes
    (and whose regions start elsewhere) stage the same layout and run
    the same compiled program."""
    from spark_rapids_tpu.columnar import transfer as TR
    staged = []
    for seed, n_head in ((1, 9_000), (2, 11_000)):
        rng = np.random.default_rng(seed)
        head = rng.integers(0, 600, n_head)
        tail = rng.integers(1 << 40, 1 << 41, 20_000 - n_head)
        tbl = pa.table({"k": pa.array(np.concatenate([head, tail]),
                                      type=pa.int64()),
                        "x": pa.array(rng.normal(size=20_000))})
        path = _write(tmp_path, tbl, name=f"f{seed}.parquet",
                      data_page_size=4096, use_dictionary=["k"],
                      dictionary_pagesize_limit=40_000)
        enc = _plan_file(path)
        st, got = _decode_encoded(enc)
        assert got == tbl.to_pydict()
        staged.append((enc.plans[0], st))
    (plan_a, st_a), (plan_b, st_b) = staged
    assert plan_a.plain_dense0 != plan_b.plain_dense0
    assert plan_a.plain_base != plan_b.plain_base
    assert st_a[6] == st_b[6] and st_a[4].shape == st_b[4].shape
    assert TR._chain_fn(st_a[6], st_a[3], st_a[4].nbytes) \
        is TR._chain_fn(st_b[6], st_b[3], st_b[4].nbytes)
    assert TR._chain_fn(st_a[6], st_a[3], st_a[4].nbytes)._cache_size() == 1

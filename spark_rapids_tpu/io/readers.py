"""File scans: DataFrameReader + CpuFileScanExec.

Mirrors the reference's scan architecture (GpuParquetScanBase.scala:82):
the host side lists files, parses footers, and plans partition units
(row-group granularity for Parquet, like the reference's copy-filtered
row-group blocks), then each partition decodes with one of three reader
strategies selected by ``spark.rapids.sql.format.parquet.reader.type``
(RapidsConf.scala:719-733):

- PERFILE       — decode units one by one (reference ParquetPartitionReader)
- MULTITHREADED — prefetch units with a thread pool, overlap IO with
                  downstream compute (MultiFileCloudParquetPartitionReader)
- COALESCING    — stitch all units of the partition into one decode
                  (MultiFileParquetPartitionReader)

Decode is Arrow on the host; device residency begins at the coalesced
upload in TpuRowToColumnarExec (HostColumnarToGpu's role).
"""

from __future__ import annotations

import glob
import os
import re
import threading
from collections import OrderedDict
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Any, Dict, Iterator, List, Optional, Sequence

from spark_rapids_tpu import trace as TR
from spark_rapids_tpu.columnar.host import HostBatch
from spark_rapids_tpu.conf import (MAX_READER_BATCH_SIZE_ROWS,
                                   MULTITHREADED_READ_NUM_THREADS,
                                   PARQUET_DEVICE_DECODE,
                                   PARQUET_READER_TYPE, TASK_PARALLELISM,
                                   TpuConf)
from spark_rapids_tpu.io.arrow_convert import (arrow_schema_to_sql,
                                               arrow_to_host_batch,
                                               sql_type_to_arrow)
from spark_rapids_tpu.sql import logical as L
from spark_rapids_tpu.sql import physical as P
from spark_rapids_tpu.sql import types as T

DEFAULT_MAX_PARTITION_BYTES = 128 << 20

HIVE_DEFAULT_PARTITION = "__HIVE_DEFAULT_PARTITION__"


def list_files(paths: Sequence[str]) -> List[tuple]:
    """Directory/glob expansion with Hive partition-directory discovery
    (PartitioningAwareFileIndex role): returns ``(file, part_values)``
    pairs where part_values maps partition column -> raw string value
    parsed from ``k=v`` path components under a directory input."""
    out: List[tuple] = []
    for p in paths:
        if os.path.isdir(p):
            base = os.path.abspath(p)
            for root, dirs, names in os.walk(base):
                dirs.sort()
                dirs[:] = [d for d in dirs if not d.startswith((".", "_"))]
                pv: Dict[str, str] = {}
                rel = os.path.relpath(root, base)
                if rel != ".":
                    from urllib.parse import unquote
                    for comp in rel.split(os.sep):
                        if "=" in comp:
                            k, v = comp.split("=", 1)
                            pv[k] = (v if v == HIVE_DEFAULT_PARTITION
                                     else unquote(v))
                for n in sorted(names):
                    if n.startswith(("_", ".")):
                        continue
                    out.append((os.path.join(root, n), pv))
        elif any(ch in p for ch in "*?["):
            out.extend((f, {}) for f in sorted(glob.glob(p)))
        elif os.path.exists(p):
            out.append((p, {}))
        else:
            raise FileNotFoundError(p)
    if not out:
        raise FileNotFoundError(f"no input files in {list(paths)}")
    return out


def file_fingerprints(files: Sequence[str]):
    """``(path, size, mtime_ns)`` per input file — the invalidation
    currency of the serve-tier caches (docs/caching.md). ``None`` when
    any file cannot be statted (vanished between listing and here): an
    unfingerprintable input set is simply uncacheable, never stale."""
    try:
        return tuple(
            (f, st.st_size, st.st_mtime_ns)
            for f, st in ((f, os.stat(f)) for f in files))
    except OSError:
        return None


def discovered_partition_fields(files: List[tuple]) -> List[T.StructField]:
    """Partition columns + value-inferred types (Spark's
    PartitioningUtils.inferPartitionColumnValue: int -> long -> double ->
    string, null for the Hive default marker)."""
    names: List[str] = []
    values: Dict[str, List[str]] = {}
    for _f, pv in files:
        for k, v in pv.items():
            if k not in values:
                names.append(k)
                values[k] = []
            values[k].append(v)
    fields = []
    for n in names:
        fields.append(T.StructField(n, _infer_part_type(values[n])))
    return fields


_INT_RE = re.compile(r"-?\d+\Z")
_FLOAT_RE = re.compile(r"-?(\d+\.\d*|\.\d+|\d+)([eE][-+]?\d+)?\Z")


def _infer_part_type(raw: List[str]) -> T.DataType:
    """Strict numeric parse (Long.parseLong/parseDouble shape): values
    Python's int()/float() accept but Arrow's cast rejects ('1_0', '+5',
    ' 7') must stay strings or the scan crashes casting later."""
    vals = [v for v in raw if v != HIVE_DEFAULT_PARTITION]
    if not vals:
        return T.StringT
    if all(_INT_RE.match(v) for v in vals):
        ints = [int(v) for v in vals]
        if all(-(1 << 31) <= i < (1 << 31) for i in ints):
            return T.IntegerT
        if all(-(1 << 63) <= i < (1 << 63) for i in ints):
            return T.LongT
        # beyond int64: Spark widens numerically rather than to string
        return T.DoubleT
    if all(_FLOAT_RE.match(v) for v in vals):
        return T.DoubleT
    return T.StringT


@dataclass
class ScanUnit:
    """One decode unit: a file, or a row-group range of a parquet file
    (the reference's filtered-block unit, GpuParquetScanBase.scala:1363).

    ``stats`` holds per-column footer statistics for predicate pushdown:
    {name: (min, max, null_count, num_rows)} with None min/max when the
    footer has none (the reference prunes on the same footer stats,
    GpuParquetScanBase filterBlocks)."""

    path: str
    size_bytes: int
    row_groups: Optional[List[int]] = None  # parquet only; None = whole file
    part_values: Optional[Dict[str, str]] = None  # Hive dir values
    stats: Optional[Dict[str, tuple]] = None


# Footer-parse results memoized per (fmt, file set), invalidated by the
# files' stat signature, so re-planning the same DataFrame (every
# collect()) doesn't re-read every parquet footer — the reference caches
# its file index per relation. Bounded LRU so sessions reading many
# distinct/growing datasets don't accumulate stale listings.
# tpu-lint: disable=jit-module-cache(holds unit-assignment tuples, not compiled programs; hand-bounded at _UNITS_CACHE_MAX below)
_UNITS_CACHE: "OrderedDict[tuple, tuple]" = OrderedDict()
_UNITS_CACHE_MAX = 64


def plan_scan_units(fmt: str, files: List[tuple]) -> List[ScanUnit]:
    key = (fmt, tuple(f for f, _ in files))
    sig = tuple((tuple(sorted(pv.items())),
                 os.path.getmtime(f), os.path.getsize(f))
                for f, pv in files)
    cached = _UNITS_CACHE.get(key)
    if cached is not None and cached[0] == sig:
        _UNITS_CACHE.move_to_end(key)
        return cached[1]
    units: List[ScanUnit] = []
    if fmt == "parquet":
        import pyarrow.parquet as pq
        for f, pv in files:
            try:
                meta = pq.ParquetFile(f).metadata
            except Exception:
                units.append(ScanUnit(f, os.path.getsize(f),
                                      part_values=pv))
                continue
            for rg in range(meta.num_row_groups):
                rgm = meta.row_group(rg)
                stats: Dict[str, tuple] = {}
                for ci in range(rgm.num_columns):
                    col = rgm.column(ci)
                    name = col.path_in_schema.split(".")[0]
                    try:
                        st = col.statistics
                        if st is None:
                            stats[name] = (None, None, None,
                                           rgm.num_rows)
                        else:
                            stats[name] = (
                                st.min if st.has_min_max else None,
                                st.max if st.has_min_max else None,
                                st.null_count if st.has_null_count
                                else None,
                                rgm.num_rows)
                    except Exception:
                        # some physical/logical combos (e.g. decimal
                        # stored as integer) cannot extract stats —
                        # pruning is optional, the scan is not
                        stats[name] = (None, None, None, rgm.num_rows)
                units.append(ScanUnit(
                    f, rgm.total_byte_size, [rg], pv, stats))
            if meta.num_row_groups == 0:
                units.append(ScanUnit(f, 0, [], pv))
    elif fmt == "orc":
        # stripe-granularity units (GpuOrcScanBase.scala:66 stripe-copy
        # role): each stripe decodes independently, so a multi-stripe
        # file fans out across the task pool like parquet row groups
        import pyarrow.orc as po
        for f, pv in files:
            try:
                of = po.ORCFile(f)
                ns = of.nstripes
            except Exception:
                units.append(ScanUnit(f, os.path.getsize(f),
                                      part_values=pv))
                continue
            if ns <= 1:
                units.append(ScanUnit(f, os.path.getsize(f),
                                      part_values=pv))
                continue
            per = max(1, os.path.getsize(f) // ns)
            for st in range(ns):
                units.append(ScanUnit(f, per, [st], pv))
    else:
        for f, pv in files:
            units.append(ScanUnit(f, os.path.getsize(f), part_values=pv))
    _UNITS_CACHE[key] = (sig, units)
    if len(_UNITS_CACHE) > _UNITS_CACHE_MAX:
        _UNITS_CACHE.popitem(last=False)
    return units


def shard_units_by_bytes(units: List[ScanUnit], n: int
                         ) -> List[List[ScanUnit]]:
    """Round-robin-by-bytes unit scheduler for the mesh scan: each unit
    goes to the stream with the least accumulated bytes (ties resolve
    by lowest stream index, so equal-sized units round-robin), which
    keeps skewed row-group sizes balanced across chips — the task->
    executor placement Spark's scheduler gives the reference for free.
    Streams may come back empty (fewer units than chips); callers keep
    them so per-chip structure is stable."""
    streams: List[List[ScanUnit]] = [[] for _ in range(n)]
    loads = [0] * n
    for u in units:
        i = min(range(n), key=lambda d: (loads[d], d))
        streams[i].append(u)
        # +1 so zero-byte units (empty row groups) still spread instead
        # of all landing on stream 0
        loads[i] += u.size_bytes + 1
    return streams


def pack_partitions(units: List[ScanUnit], max_bytes: int,
                    open_cost: int = 0) -> List[List[ScanUnit]]:
    """Bin-pack units into partitions (FilePartition.getFilePartitions;
    each unit weighs its bytes PLUS openCostInBytes, like Spark)."""
    parts: List[List[ScanUnit]] = []
    cur: List[ScanUnit] = []
    cur_bytes = 0
    for u in units:
        w = u.size_bytes + open_cost
        if cur and cur_bytes + w > max_bytes:
            parts.append(cur)
            cur, cur_bytes = [], 0
        cur.append(u)
        cur_bytes += w
    if cur:
        parts.append(cur)
    return parts


# ---------------------------------------------------------------------------
# Decoders (host side)
# ---------------------------------------------------------------------------

def _read_unit(fmt: str, unit: ScanUnit, schema: T.StructType,
               options: Dict[str, Any]):
    """Decode one unit to a pyarrow Table with `schema`'s columns."""
    import pyarrow as pa
    names = [f.name for f in schema.fields]
    if fmt == "parquet":
        import pyarrow.parquet as pq
        pf = pq.ParquetFile(unit.path)
        if unit.row_groups is not None:
            if not unit.row_groups:
                return pa.table(
                    {n: pa.array([], type=sql_type_to_arrow(f.data_type))
                     for n, f in zip(names, schema.fields)})
            return pf.read_row_groups(unit.row_groups, columns=names)
        return pf.read(columns=names)
    if fmt == "orc":
        import pyarrow.orc as po
        of = po.ORCFile(unit.path)
        if unit.row_groups:  # stripe indices
            batches = [of.read_stripe(st, columns=names)
                       for st in unit.row_groups]
            return pa.Table.from_batches(
                batches) if batches else of.read(columns=names)
        return of.read(columns=names)
    if fmt == "csv":
        return _read_csv(unit.path, schema, options)
    if fmt == "json":
        import pyarrow.json as pj
        tbl = pj.read_json(unit.path)
        return _conform(tbl, schema)
    if fmt == "text":
        import pyarrow.csv as pc
        tbl = pc.read_csv(unit.path, parse_options=pc.ParseOptions(
            delimiter="\x01", quote_char=False, escape_char=False),
            read_options=pc.ReadOptions(column_names=[names[0]]))
        return tbl
    raise NotImplementedError(f"format {fmt}")


def _read_csv(path: str, schema: T.StructType, options: Dict[str, Any]):
    import pyarrow as pa
    import pyarrow.csv as pc
    header = str(options.get("header", "false")).lower() == "true"
    sep = options.get("sep", options.get("delimiter", ","))
    null_value = options.get("nullValue", "")
    names = [f.name for f in schema.fields]
    null_values = [null_value] if null_value else [""]
    parse_opts = pc.ParseOptions(delimiter=sep)
    convert_opts = pc.ConvertOptions(
        column_types={f.name: sql_type_to_arrow(f.data_type)
                      for f in schema.fields},
        null_values=null_values,
        strings_can_be_null=True,
        timestamp_parsers=[pc.ISO8601, "%Y-%m-%d %H:%M:%S"])
    try:
        tbl = pc.read_csv(
            path,
            read_options=pc.ReadOptions(
                column_names=None if header else names, skip_rows=0),
            parse_options=parse_opts,
            convert_options=convert_opts)
    except pa.lib.ArrowInvalid:
        # PERMISSIVE-mode tolerance: the file's column count differs from
        # the schema — re-read with positional names (same null semantics,
        # types conformed by cast below)
        tbl = pc.read_csv(
            path,
            read_options=pc.ReadOptions(autogenerate_column_names=True,
                                        skip_rows=1 if header else 0),
            parse_options=parse_opts,
            convert_options=pc.ConvertOptions(
                null_values=null_values, strings_can_be_null=True,
                timestamp_parsers=[pc.ISO8601, "%Y-%m-%d %H:%M:%S"]))
    # align by position when file header names/column count differ from
    # the schema; extra columns are dropped, missing ones become null
    n = min(len(names), tbl.num_columns)
    tbl = tbl.select(list(range(n))).rename_columns(names[:n])
    return _conform(tbl, schema)


def _partition_value_array(f: T.StructField, raw: Optional[str], n: int):
    """One partition field's constant column: parse the raw directory
    value ONCE, then broadcast the scalar
    (PartitioningUtils.castPartValueToDesiredType role)."""
    import pyarrow as pa
    at = sql_type_to_arrow(f.data_type)
    if raw is None or raw == HIVE_DEFAULT_PARTITION:
        return pa.nulls(n, type=at)
    return pa.repeat(pa.scalar(raw, type=pa.string()).cast(at), n)


def _append_partition_columns(tbl, part_fields: List[T.StructField],
                              part_values: Dict[str, str]):
    """Attach directory-derived partition values as constant columns."""
    for f in part_fields:
        tbl = tbl.append_column(f.name, _partition_value_array(
            f, part_values.get(f.name), tbl.num_rows))
    return tbl


def _conform(tbl, schema: T.StructType):
    """Reorder/cast a table to the requested schema (schema evolution)."""
    import pyarrow as pa
    cols = []
    for f in schema.fields:
        if f.name in tbl.column_names:
            cols.append(tbl.column(f.name).cast(
                sql_type_to_arrow(f.data_type)))
        else:
            cols.append(pa.nulls(tbl.num_rows,
                                 type=sql_type_to_arrow(f.data_type)))
    return pa.Table.from_arrays(cols, names=[f.name for f in schema.fields])


def _extend_with_partition_cols(enc, schema: T.StructType,
                                part_fields: List[T.StructField],
                                part_values: Dict[str, str]):
    """Remap an EncodedBatch built against the data schema onto the full
    scan schema, adding directory-derived partition values as constant
    host columns (they never touch the file bytes)."""
    from spark_rapids_tpu.io.arrow_convert import arrow_column_to_host
    data_idx = {f.name: i for i, f in enumerate(enc.schema.fields)}
    plans = {}
    host_cols = {}
    n = enc.num_rows
    for fi, f in enumerate(schema.fields):
        di = data_idx.get(f.name)
        if di is not None:
            if di in enc.plans:
                plans[fi] = enc.plans[di]
            else:
                host_cols[fi] = enc.host_cols[di]
            continue
        host_cols[fi] = arrow_column_to_host(
            _partition_value_array(f, part_values.get(f.name), n),
            f.data_type)
    enc.schema = schema
    enc.plans = plans
    enc.host_cols = host_cols
    return enc


# ---------------------------------------------------------------------------
# Physical scan
# ---------------------------------------------------------------------------

_READ_POOL: Optional[ThreadPoolExecutor] = None
_POOL_SIZE: int = 0
_POOL_LOCK = threading.Lock()


def _shared_pool(n_threads: int) -> ThreadPoolExecutor:
    global _READ_POOL, _POOL_SIZE
    with _POOL_LOCK:
        if _READ_POOL is None or _POOL_SIZE != n_threads:
            if _READ_POOL is not None:
                _READ_POOL.shutdown(wait=False)
            _READ_POOL = ThreadPoolExecutor(
                max_workers=n_threads, thread_name_prefix="srt-multifile")
            _POOL_SIZE = n_threads
        return _READ_POOL


def _stat_storage(v, dt: T.DataType):
    """Footer stat value -> the engine's storage form (days/micros/
    unscaled int); None when not convertible (disables pruning)."""
    from spark_rapids_tpu.columnar.host import _to_storage
    try:
        out = _to_storage(v, dt)
        if isinstance(out, (int, float, str)):
            return out
        return None
    except Exception:
        return None


def unit_can_match(u: ScanUnit, preds: List[tuple],
                   fields: Dict[str, T.DataType]) -> bool:
    """False when this row-group's footer stats PRECLUDE any row
    matching every pushed conjunct (GpuParquetScanBase filterBlocks /
    parquet-mr StatisticsFilter shape). Conservative: missing stats or
    unconvertible values keep the unit."""
    if u.stats is None:
        return True
    for name, op, val in preds:
        st = u.stats.get(name)
        if st is None:
            continue
        mn, mx, nulls, n_rows = st
        dt = fields.get(name)
        if op == "notnull":
            if nulls is not None and n_rows and nulls == n_rows:
                return False
            continue
        if op == "isnull":
            if nulls is not None and nulls == 0 and n_rows:
                return False
            continue
        if mn is None or mx is None or dt is None:
            continue
        lo, hi = _stat_storage(mn, dt), _stat_storage(mx, dt)
        if lo is None or hi is None:
            continue
        try:
            if op == "eq" and (val < lo or val > hi):
                return False
            if op == "lt" and lo >= val:
                return False
            if op == "le" and lo > val:
                return False
            if op == "gt" and hi <= val:
                return False
            if op == "ge" and hi < val:
                return False
        except TypeError:
            continue  # cross-type compare: keep the unit
    return True


class CpuFileScanExec(P.PhysicalPlan):
    """File source scan; feeds the device through the transparent R2C
    transition (GpuFileSourceScanExec's role, host-decode variant)."""

    def __init__(self, output, fmt: str, paths: List[str],
                 options: Dict[str, Any], conf: TpuConf):
        from spark_rapids_tpu import metrics as M
        from spark_rapids_tpu.conf import METRICS_LEVEL
        self.children = []
        self._output = output
        self.fmt = fmt
        self.paths = paths
        self.options = options or {}
        self.conf = conf
        # decodeTime/convertTime surface in the bench stage breakdown —
        # round-4 verdict: the dominant cost must never be invisible
        self.metrics = M.MetricRegistry(str(conf.get(METRICS_LEVEL)),
                                        owner="FileScan")
        listed = list_files(paths)
        self.files = [f for f, _ in listed]
        # input-file fingerprints (path, size, mtime_ns) captured at
        # scan planning: the serve-tier caches (docs/caching.md) key on
        # these and re-stat before every reuse, so ANY change to the
        # inputs — append, rewrite, touch, delete — invalidates instead
        # of serving stale bytes
        self.fingerprints = file_fingerprints(self.files)
        part_names = {k for _f, pv in listed for k in pv}
        self._part_fields = [f for f in self.schema.fields
                             if f.name in part_names]
        max_bytes = int(
            conf.get_key("spark.sql.files.maxPartitionBytes",
                         DEFAULT_MAX_PARTITION_BYTES))
        open_cost = int(
            conf.get_key("spark.sql.files.openCostInBytes", 4 << 20))
        self._units = plan_scan_units(fmt, listed)
        # Spark's FilePartition.maxSplitBytes: size splits so the scan
        # fans out across the configured task parallelism instead of
        # packing one giant partition — bytesPerCore floored by
        # openCostInBytes, capped by maxPartitionBytes. Without this a
        # 60MB dataset became ONE partition and serialized the whole
        # decode/upload/compute pipeline on a single task thread.
        parallelism = max(1, int(conf.get(TASK_PARALLELISM)))
        total = sum(u.size_bytes for u in self._units) \
            + open_cost * len(self._units)
        self._max_bytes = min(max_bytes,
                              max(open_cost, total // parallelism))
        self._pushed: List[tuple] = []  # (col, op, storage value)
        self.pruned_units = 0  # observability (tools/tests)
        self._open_cost = open_cost
        self._parts = pack_partitions(self._units, self._max_bytes,
                                      open_cost)
        # set by the planner when input_file_name() sits above this scan
        self.force_perfile = False
        # set (at execution time) by TpuRowToColumnarExec when IT is the
        # direct consumer: only then may partitions() emit EncodedBatch
        # staging objects instead of HostBatches — CPU consumers always
        # see decoded rows
        self.emit_encoded = False
        # mesh scan (docs/multichip.md): set by TpuRowToColumnarExec at
        # execution time to the active mesh's devices; partitions() then
        # returns ONE reader stream per chip (units assigned round-
        # robin-by-bytes) and publishes the per-stream target device in
        # partition_devices so the upload lands each stream on its chip
        self._mesh_devices: List = []
        self.partition_devices: List = []

    def set_scan_mesh(self, devices: List) -> None:
        self._mesh_devices = list(devices or [])

    def set_pushdown(self, preds: List[tuple]) -> None:
        """Install pushed-down predicates (name, op, storage-value) and
        prune row-group units whose footer stats preclude matches. The
        enclosing Filter node still runs, so pruning is free to be
        conservative."""
        self._pushed = preds
        if not preds or self.fmt != "parquet":
            return
        fields = {f.name: f.data_type for f in self.schema.fields}
        kept = [u for u in self._units
                if unit_can_match(u, preds, fields)]
        self.pruned_units = len(self._units) - len(kept)
        # always at least one (possibly empty) partition so global
        # aggregates still see a partition to produce their one row in
        self._parts = pack_partitions(kept, self._max_bytes,
                              self._open_cost) \
            if kept else [[]]

    @property
    def output(self):
        return self._output

    def simple_string(self):
        s = (f"FileScan {self.fmt} [{len(self.files)} files, "
             f"{len(self._parts)} partitions")
        if self._pushed:
            s += (f", pushed {len(self._pushed)} filters, "
                  f"pruned {self.pruned_units} units")
        return s + "]"

    def partitions(self):
        reader_type = str(self.conf.get(PARQUET_READER_TYPE)).upper()
        if self.force_perfile:
            reader_type = "PERFILE"
        max_rows = int(self.conf.get(MAX_READER_BATCH_SIZE_ROWS))
        schema = self.schema
        part_fields = self._part_fields
        part_names = {f.name for f in part_fields}
        data_schema = T.StructType(
            [f for f in schema.fields if f.name not in part_names])
        # the device-decode path stitches no tables, so COALESCING keeps
        # the host decode (its whole point is the one-table stitch)
        device_decode = (self.fmt == "parquet"
                         and reader_type != "COALESCING"
                         and self.emit_encoded
                         and bool(self.conf.get(PARQUET_DEVICE_DECODE)))

        metrics = self.metrics

        def decode(u: ScanUnit):
            from spark_rapids_tpu import retry as R
            with metrics.timed_wall("decodeTime", path=u.path,
                                    bytes=u.size_bytes):
                # transient IO errors retry with bounded exponential
                # backoff (spark.rapids.sql.reader.maxRetries /
                # retryBackoffMs), re-raising the original after
                # exhaustion; covers PERFILE, MULTITHREADED (pool
                # threads), COALESCING, and the mesh-sharded streams,
                # which all decode through here
                tbl = R.io_with_retry(
                    lambda: _read_unit(self.fmt, u, data_schema,
                                       self.options),
                    self.conf, metrics, path=u.path)
                if part_fields:
                    tbl = _append_partition_columns(tbl, part_fields,
                                                    u.part_values or {})
                    tbl = tbl.select([f.name for f in schema.fields])
            return tbl

        def emit(tbl) -> Iterator[HostBatch]:
            for lo in range(0, max(1, tbl.num_rows), max_rows):
                with metrics.timed_wall("convertTime"):
                    hb = arrow_to_host_batch(tbl.slice(lo, max_rows),
                                             schema)
                yield hb

        def plan_device(u: ScanUnit):
            """ScanUnit -> EncodedBatch (host does IO/decompress/header
            parse only), or None when the unit must host-decode."""
            from spark_rapids_tpu.io import device_decode as DD
            if u.row_groups is None or len(u.row_groups) != 1:
                # whole-file / multi-row-group units host-decode; count
                # them so the bench attribution can't mistake an
                # all-fallback run for "nothing to decode"
                metrics.create("deviceFallbackUnits").add(1)
                return None
            from spark_rapids_tpu import retry as R
            with metrics.timed_wall("deviceDecodeTime", path=u.path):
                try:
                    # the device plan's file reads ride the same
                    # transient-IO retry protocol (and fault-injection
                    # checkpoints) as the host decode; a genuine IO
                    # failure after retries fails the query either way
                    enc = R.io_with_retry(
                        lambda: DD.plan_unit_encoded(
                            u, data_schema, self.conf, metrics),
                        self.conf, metrics, path=u.path)
                except OSError:
                    raise  # exhausted retries: a real reader failure
                except Exception:
                    enc = None  # corrupt chunk: the host decode decides
            if enc is None or enc.num_rows > max_rows:
                metrics.create("deviceFallbackUnits").add(1)
                return None
            if part_fields:
                enc = _extend_with_partition_cols(
                    enc, schema, part_fields, u.part_values or {})
            # OOM recovery: the upload can fall back to the pyarrow
            # host decode of this unit for just this batch
            enc.host_fallback = lambda u=u: list(emit(decode(u)))
            metrics.create("deviceDecodedBatches").add(1)
            for name, _reason in enc.fallbacks:
                metrics.create("deviceFallbackColumns").add(1)
            for ename, nvals in enc.fallback_encodings.items():
                metrics.create(f"hostDecodedValues.{ename}").add(nvals)
            for plan in enc.plans.values():
                for ename, nvals in plan.encoding_values.items():
                    metrics.create(
                        f"deviceDecodedValues.{ename}").add(nvals)
            return enc

        def decode_unit(u: ScanUnit) -> List:
            """One unit -> MATERIALIZED batches (EncodedBatch or
            HostBatches) for the prefetch pool: the arrow->HostBatch
            conversion (string object arrays, casts) runs IN the pool
            thread so the consumer thread only packs/uploads
            (MultiFileCloudParquetPartitionReader keeps its host-side
            decode off the task thread the same way). The PERFILE path
            streams instead (one batch in flight, not a whole file)."""
            if device_decode:
                enc = plan_device(u)
                if enc is not None:
                    return [enc]
            return list(emit(decode(u)))

        from spark_rapids_tpu.sql import expressions as E

        def _set_file(path: str) -> None:
            # input_file_name() context: valid for scan-adjacent
            # projects on this thread (InputFileBlockRule role)
            E._PART_CTX.input_file = path

        def make(units: List[ScanUnit]):
            def run() -> Iterator[HostBatch]:
                if reader_type == "COALESCING" and len(units) > 1:
                    import pyarrow as pa
                    tbl = pa.concat_tables([decode(u) for u in units])
                    _set_file("")  # batches span files after the stitch
                    yield from emit(tbl)
                elif reader_type == "MULTITHREADED" and units:
                    # one unit or many, the decode runs on the shared
                    # pool's threads, which outlive the query: a thread
                    # made for one stream would leave its allocator heap
                    # behind when it exits, and every query's buffers
                    # would be fresh pages (on a TPU v5e host, +240 MB
                    # of resident memory and a slower scan start a query)
                    n_threads = int(
                        self.conf.get(MULTITHREADED_READ_NUM_THREADS))
                    pool = _shared_pool(n_threads)
                    scope = TR.scope_of(metrics)

                    def decode_scoped(u: ScanUnit) -> List:
                        # the pool threads' spans carry the query's q=
                        with TR.attach(scope):
                            return decode_unit(u)
                    # sliding prefetch window: decoded-and-converted
                    # HostBatches are several times their arrow size, so
                    # bound in-flight units instead of materializing the
                    # whole partition's decode output at once
                    from collections import deque
                    from itertools import islice
                    it = iter(units)
                    futures = deque(pool.submit(decode_scoped, u)
                                    for u in islice(it, n_threads + 2))
                    done = iter(units)
                    try:
                        while futures:
                            f = futures.popleft()
                            nxt = next(it, None)
                            if nxt is not None:
                                futures.append(
                                    pool.submit(decode_scoped, nxt))
                            _set_file(next(done).path)
                            for hb in f.result():
                                yield hb
                    finally:
                        # a decode error (or a closed consumer) must not
                        # leak pool work: unstarted prefetches are
                        # cancelled so the shared pool drains promptly
                        # and later queries see a clean queue
                        for f in futures:
                            f.cancel()
                else:  # PERFILE: streamed, one host batch in flight
                    for u in units:
                        if device_decode:
                            enc = plan_device(u)
                            if enc is not None:
                                _set_file(u.path)
                                yield enc
                                continue
                        tbl = decode(u)
                        _set_file(u.path)
                        yield from emit(tbl)
            return run

        if len(self._mesh_devices) >= 2:
            # mesh scan: one reader stream per chip over the (pruned)
            # unit list, round-robin-by-bytes; empty streams are kept so
            # a chip with zero units still yields an (empty) partition
            # and the per-chip pipeline structure stays stable
            units = [u for part in self._parts for u in part]
            streams = shard_units_by_bytes(units, len(self._mesh_devices))
            self.partition_devices = list(self._mesh_devices)

            def chip_stream(st: List[ScanUnit]):
                # a chip's share still honors the max_bytes bin packing
                # (COALESCING concatenates one TABLE per sub-partition,
                # not the chip's whole share; MULTITHREADED windows per
                # sub-partition) — the stream just chains them
                subs = pack_partitions(st, self._max_bytes,
                                       self._open_cost) if st else [[]]
                runs = [make(us) for us in subs]

                def run():
                    for r in runs:
                        yield from r()
                return run

            for d, st in zip(self._mesh_devices, streams):
                metrics.create(f"meshScanUnits.chip{d.id}").add(len(st))
            return [chip_stream(st) for st in streams]
        self.partition_devices = []
        return [make(us) for us in self._parts]


# ---------------------------------------------------------------------------
# DataFrameReader
# ---------------------------------------------------------------------------

class DataFrameReader:
    """spark.read facade (pyspark DataFrameReader shape)."""

    def __init__(self, session):
        self._session = session
        self._format = "parquet"
        self._schema: Optional[T.StructType] = None
        self._options: Dict[str, Any] = {}

    def format(self, fmt: str) -> "DataFrameReader":
        self._format = fmt.lower()
        return self

    def schema(self, schema) -> "DataFrameReader":
        if isinstance(schema, str):
            from spark_rapids_tpu.sql.session import _parse_ddl_schema
            schema = _parse_ddl_schema(schema)
        self._schema = schema
        return self

    def option(self, key: str, value: Any) -> "DataFrameReader":
        self._options[key] = value
        return self

    def options(self, **opts) -> "DataFrameReader":
        self._options.update(opts)
        return self

    def load(self, path=None):
        from spark_rapids_tpu.sql.dataframe import DataFrame
        paths = [path] if isinstance(path, str) else list(path)
        listed = list_files(paths)  # one walk for infer + discovery
        schema = self._schema or self._infer_schema_from(listed)
        # append Hive-style partition columns discovered from k=v dirs
        have = {f.name for f in schema.fields}
        extra = [f for f in discovered_partition_fields(listed)
                 if f.name not in have]
        if extra:
            schema = T.StructType(list(schema.fields) + extra)
        plan = L.FileScan(self._format, paths, schema, dict(self._options))
        return DataFrame(plan, self._session)

    def parquet(self, *paths: str):
        return self.format("parquet").load(list(paths))

    def orc(self, *paths: str):
        return self.format("orc").load(list(paths))

    def csv(self, path, schema=None, header=None, sep=None,
            inferSchema=None, nullValue=None):
        if schema is not None:
            self.schema(schema)
        if header is not None:
            self.option("header", str(header).lower())
        if sep is not None:
            self.option("sep", sep)
        if inferSchema is not None:
            self.option("inferSchema", str(inferSchema).lower())
        if nullValue is not None:
            self.option("nullValue", nullValue)
        return self.format("csv").load(path)

    def json(self, path, schema=None):
        if schema is not None:
            self.schema(schema)
        return self.format("json").load(path)

    def text(self, path):
        self._schema = T.StructType([T.StructField("value", T.StringT)])
        return self.format("text").load(path)

    def table(self, name: str):
        return self._session.table(name)

    # -- schema inference --------------------------------------------------

    def _infer_schema_from(self, listed: List[tuple]) -> T.StructType:
        first = listed[0][0]
        fmt = self._format
        if fmt == "parquet":
            import pyarrow.parquet as pq
            return arrow_schema_to_sql(
                pq.ParquetFile(first).schema_arrow)
        if fmt == "orc":
            import pyarrow.orc as po
            return arrow_schema_to_sql(po.ORCFile(first).schema)
        if fmt == "json":
            import pyarrow.json as pj
            return arrow_schema_to_sql(pj.read_json(first).schema)
        if fmt == "csv":
            return self._infer_csv_schema(first)
        raise ValueError(
            f"cannot infer schema for format {fmt}; pass .schema(...)")

    def _infer_csv_schema(self, path: str) -> T.StructType:
        import pyarrow.csv as pc
        header = str(self._options.get("header", "false")).lower() == "true"
        sep = self._options.get("sep", self._options.get("delimiter", ","))
        infer = str(self._options.get("inferSchema",
                                      "false")).lower() == "true"
        tbl = pc.read_csv(
            path,
            read_options=pc.ReadOptions(),
            parse_options=pc.ParseOptions(delimiter=sep))
        names = (tbl.column_names if header
                 else [f"_c{i}" for i in range(tbl.num_columns)])
        if not header:
            # first row was data; re-read without consuming it as header
            tbl = pc.read_csv(
                path,
                read_options=pc.ReadOptions(column_names=names),
                parse_options=pc.ParseOptions(delimiter=sep))
        if infer:
            fields = []
            for n, col in zip(names, tbl.columns):
                try:
                    dt = arrow_type_to_sql_for_csv(col.type)
                except TypeError:
                    dt = T.StringT
                fields.append(T.StructField(n, dt))
            return T.StructType(fields)
        return T.StructType([T.StructField(n, T.StringT) for n in names])


def arrow_type_to_sql_for_csv(at) -> T.DataType:
    """CSV inference maps ints to LONG and floats to DOUBLE (Spark's
    CSVInferSchema tightest types)."""
    import pyarrow as pa
    if pa.types.is_boolean(at):
        return T.BooleanT
    if pa.types.is_integer(at):
        return T.LongT
    if pa.types.is_floating(at):
        return T.DoubleT
    if pa.types.is_timestamp(at):
        return T.TimestampT
    if pa.types.is_date(at):
        return T.DateT
    return T.StringT

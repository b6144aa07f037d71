"""Device-resident columnar batches (the GpuColumnVector / cudf Table twin).

TPU-first design, not a translation of the reference's device model:

- Every column is a pair of JAX arrays in HBM: fixed-width ``data`` plus a
  ``validity`` bool mask (Arrow-style; reference keeps the same split in
  GpuColumnVector.java over cudf buffers).
- Strings/binary are padded byte matrices ``uint8[capacity, char_cap]`` with
  a ``lengths`` vector — tensor-shaped so XLA can tile them (the reference
  gets offset+bytes columns from cudf; offsets fight static shapes on TPU).
- **Static shapes everywhere**: a batch has a ``capacity`` bucketed to a
  power of two; the real row count is tracked by an ``active`` row mask and
  a lazily-fetched host count. Filters only flip mask bits (no data
  movement); compaction happens on explicit request with a fixed-shape
  argsort-gather. This is how the build avoids XLA recompilation storms on
  data-dependent row counts (SURVEY.md section 7 "hard parts" (a)).
- A row is *padding* iff ``active[i]`` is False. Padding rows also carry
  validity=False in every column so masked reductions never see them.

Null slots hold deterministic zeros (normalized), mirroring
HostColumn.normalized(), so bitwise comparisons and hashing are stable.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import trace as _trace
from spark_rapids_tpu.columnar.host import HostBatch, HostColumn
from spark_rapids_tpu.jit_cache import JitCache, named_jit
from spark_rapids_tpu.sql import types as T

# Minimum capacity bucket: small enough for tests, large enough that op
# compile caches stay tiny (log2 buckets between MIN and max batch rows).
MIN_CAPACITY = 64
DEFAULT_CHAR_CAP = 32


# tpu-lint: disable=jit-direct(single fixed count program — jax's own signature cache bounds it by capacity bucket)
_count_active = named_jit("srt_count_active",
                          lambda active: jnp.sum(active))


def bucket_capacity(n: int) -> int:
    """Smallest {1, 1.25, 1.5, 1.75} x 2^k capacity >= n, floored at
    MIN_CAPACITY. Quarter-step buckets bound padding waste at 25% (pure
    powers of two waste up to 100% — the round-2 bench put 1.25M rows in
    a 2M bucket) for 4x the program-cache keys."""
    if n <= MIN_CAPACITY:
        return MIN_CAPACITY
    base = 1 << (n.bit_length() - 1)
    if base == n:
        return n
    for num in (5, 6, 7):
        cap = (base >> 2) * num
        if cap >= n:
            return cap
    return base << 1


def bucket_char_cap(max_len: int) -> int:
    """Byte-matrix width bucket: multiple-of-8 padding, floor 8."""
    if max_len <= 8:
        return 8
    return 8 * math.ceil(max_len / 8)


def is_string_like(dt: T.DataType) -> bool:
    return isinstance(dt, (T.StringType, T.BinaryType))


def storage_jnp_dtype(dt: T.DataType) -> jnp.dtype:
    """Device storage dtype for fixed-width types."""
    return jnp.dtype(T.numpy_dtype(dt))


@dataclass
class DeviceColumn:
    """Fixed-width device column: data[capacity] + validity[capacity]."""

    dtype: T.DataType
    data: jax.Array
    validity: jax.Array  # bool

    @property
    def capacity(self) -> int:
        return self.data.shape[0]

    def arrays(self) -> Tuple[jax.Array, ...]:
        return (self.data, self.validity)

    @staticmethod
    def from_arrays(dtype: T.DataType, arrs: Sequence[jax.Array]
                    ) -> "DeviceColumn":
        data, validity = arrs
        return DeviceColumn(dtype, data, validity)


@dataclass
class DeviceDecimal128Column:
    """DECIMAL128 device column: unscaled value as two int64 limbs
    (``hi`` signed high, ``lo`` holding the uint64 low bit pattern) —
    the ops/int128 representation, resident in HBM. The reference keeps
    these as cudf DECIMAL128 columns (decimalExpressions.scala); two
    plain int64 arrays are the XLA-friendly shape of the same idea."""

    dtype: T.DataType  # DecimalType, precision > 18
    hi: jax.Array      # int64[capacity]
    lo: jax.Array      # int64[capacity] (uint64 bit pattern)
    validity: jax.Array

    @property
    def capacity(self) -> int:
        return self.hi.shape[0]

    @property
    def data(self) -> jax.Array:
        # sort/compact payload convenience: callers that need the limbs
        # use .hi/.lo; generic code paths must go through arrays()
        raise AttributeError("DeviceDecimal128Column has limbs, not data")

    def arrays(self) -> Tuple[jax.Array, ...]:
        return (self.hi, self.lo, self.validity)

    @staticmethod
    def from_arrays(dtype: T.DataType, arrs: Sequence[jax.Array]
                    ) -> "DeviceDecimal128Column":
        hi, lo, validity = arrs
        return DeviceDecimal128Column(dtype, hi, lo, validity)


@dataclass
class DeviceStringColumn:
    """String/binary device column: padded byte matrix + lengths.

    ``chars`` is uint8[capacity, char_cap], zero-padded past ``lengths[i]``;
    zero-padding keeps plain lexicographic comparison of rows equal to
    UTF-8 binary order (shorter string sorts before its extensions), which
    the sort/join kernels rely on.

    Rows longer than char_cap cannot be represented; the host->device
    transfer picks char_cap from the actual max length, and TypeSig gating
    falls back to CPU for columns beyond ``MAX_DEVICE_STRING`` bytes.
    """

    dtype: T.DataType
    chars: jax.Array    # uint8[capacity, char_cap]
    lengths: jax.Array  # int32[capacity]
    validity: jax.Array

    MAX_DEVICE_STRING = 1 << 14

    @property
    def capacity(self) -> int:
        return self.chars.shape[0]

    @property
    def char_cap(self) -> int:
        return self.chars.shape[1]

    def arrays(self) -> Tuple[jax.Array, ...]:
        return (self.chars, self.lengths, self.validity)

    @staticmethod
    def from_arrays(dtype: T.DataType, arrs: Sequence[jax.Array]
                    ) -> "DeviceStringColumn":
        chars, lengths, validity = arrs
        return DeviceStringColumn(dtype, chars, lengths, validity)


@dataclass
class DeviceArrayColumn:
    """Array column: per-row (start, length) views into a shared element
    pool (the offsets+child model of Arrow/cudf list columns, made
    gather-friendly: after a row gather, starts may alias/point anywhere
    in the pool, so no contiguity is assumed).

    ``child`` is the element pool (a device column of its own capacity);
    its validity marks null ELEMENTS. ``validity`` marks null arrays.
    Nested columns are confined to upload -> project/filter ->
    generate/collect paths; exchanges, sorts, joins, and aggregations
    tag nested inputs back to CPU (TpuOverrides).
    """

    dtype: T.ArrayType
    starts: jax.Array   # int32[capacity]
    lengths: jax.Array  # int32[capacity]
    child: "AnyDeviceColumn"
    validity: jax.Array

    @property
    def capacity(self) -> int:
        return self.starts.shape[0]

    def arrays(self) -> Tuple[jax.Array, ...]:
        return (self.starts, self.lengths) + self.child.arrays() \
            + (self.validity,)

    @staticmethod
    def from_arrays(dtype: T.ArrayType, arrs: Sequence[jax.Array]
                    ) -> "DeviceArrayColumn":
        child = make_column(dtype.element_type, arrs[2:-1])
        return DeviceArrayColumn(dtype, arrs[0], arrs[1], child, arrs[-1])


@dataclass
class DeviceStructColumn:
    """Struct column as column-of-columns (the Arrow/cudf struct model,
    GpuColumnVector.java nested handling): each field is its own device
    column at the SAME capacity; ``validity`` marks null structs (null
    structs also null every field slot, kept normalized)."""

    dtype: T.StructType
    fields: List["AnyDeviceColumn"]
    validity: jax.Array

    @property
    def capacity(self) -> int:
        return self.validity.shape[0]

    def arrays(self) -> Tuple[jax.Array, ...]:
        out: Tuple[jax.Array, ...] = ()
        for f in self.fields:
            out = out + f.arrays()
        return out + (self.validity,)

    @staticmethod
    def from_arrays(dtype: T.StructType, arrs: Sequence[jax.Array]
                    ) -> "DeviceStructColumn":
        fields = []
        off = 0
        for f in dtype.fields:
            k = column_arity(f.data_type)
            fields.append(make_column(f.data_type, arrs[off:off + k]))
            off += k
        return DeviceStructColumn(dtype, fields, arrs[off])


AnyDeviceColumn = Union[DeviceColumn, DeviceStringColumn,
                        DeviceDecimal128Column, "DeviceArrayColumn",
                        DeviceStructColumn]


def column_arity(dtype: T.DataType) -> int:
    """Number of flat arrays a device column of `dtype` carries."""
    if isinstance(dtype, T.ArrayType):
        return 3 + column_arity(dtype.element_type)
    if isinstance(dtype, T.StructType):
        return 1 + sum(column_arity(f.data_type) for f in dtype.fields)
    if is_string_like(dtype) or T.is_limb_decimal(dtype):
        return 3  # (chars, lengths, validity) / (hi, lo, validity)
    return 2


def make_column(dtype: T.DataType, arrs: Sequence[jax.Array]
                ) -> AnyDeviceColumn:
    if isinstance(dtype, T.ArrayType):
        return DeviceArrayColumn.from_arrays(dtype, arrs)
    if isinstance(dtype, T.StructType):
        return DeviceStructColumn.from_arrays(dtype, arrs)
    if is_string_like(dtype):
        return DeviceStringColumn.from_arrays(dtype, arrs)
    if T.is_limb_decimal(dtype):
        return DeviceDecimal128Column.from_arrays(dtype, arrs)
    return DeviceColumn.from_arrays(dtype, arrs)


@dataclass
class DeviceBatch:
    """A columnar batch resident in device HBM.

    ``active`` marks real rows; everything at i >= original row count (and
    everything filtered out since) is False. ``_num_rows`` caches the host
    row count; ``row_count()`` materializes it (one tiny transfer) when a
    sizing decision needs it.
    """

    schema: T.StructType
    columns: List[AnyDeviceColumn]
    active: jax.Array  # bool[capacity]
    _num_rows: Optional[int] = None
    # optional device-resident count scalar, attached by producers that
    # compute it anyway (e.g. the FK fast-path join): row_count()
    # resolves it with a prefetched read instead of dispatching a fresh
    # _count_active program + flat roundtrip
    _num_rows_dev: Optional[jax.Array] = None

    @property
    def capacity(self) -> int:
        return int(self.active.shape[0])

    @property
    def num_cols(self) -> int:
        return len(self.columns)

    def column(self, i: int) -> AnyDeviceColumn:
        return self.columns[i]

    def row_count(self, site: str = "rowCount") -> int:
        if self._num_rows is None:
            # the host blocks here until the device has the value:
            # counted as deviceSyncTime (site= names the reader)
            with _trace.device_sync(site):
                if self._num_rows_dev is not None:
                    self._num_rows = int(np.asarray(self._num_rows_dev))
                else:
                    # jitted: an EAGER jnp.sum pays a per-op dispatch
                    self._num_rows = int(_count_active(self.active))
        return self._num_rows

    def with_columns(self, schema: T.StructType,
                     columns: List[AnyDeviceColumn]) -> "DeviceBatch":
        return DeviceBatch(schema, columns, self.active, self._num_rows,
                           self._num_rows_dev)

    def sizeof(self) -> int:
        """Device bytes held by this batch (for HBM accounting)."""
        total = self.active.size * 1
        for c in self.columns:
            for a in c.arrays():
                total += a.size * a.dtype.itemsize
        return total

    # -- transfer ----------------------------------------------------------

    @staticmethod
    def from_host(batch: HostBatch, capacity: Optional[int] = None,
                  device: Optional[jax.Device] = None) -> "DeviceBatch":
        cap = capacity or bucket_capacity(max(1, batch.num_rows))
        assert cap >= batch.num_rows, (cap, batch.num_rows)
        # packed codec: narrowed/bit-packed columns ride ONE int32
        # staging buffer + ONE device_put; a single jitted program
        # decodes to full-width padded columns in HBM (transfer.py)
        from spark_rapids_tpu.columnar.transfer import upload_batch
        # NOT retried here: the DeviceStore promote path (memory.py
        # _access) calls this while HOLDING the store lock — a spill +
        # backoff sleep inside it would stall every task in the
        # process. OOM propagates to the caller's own retry scope.
        # tpu-lint: disable=retry-coverage(runs under DeviceStore._lock on the promote path; spilling/sleeping there blocks the whole store — callers own the retry)
        return upload_batch(batch, cap, device)

    def to_host(self) -> HostBatch:
        """Gather active rows back to a HostBatch (device -> host copy).
        Buffers ride per-dtype concatenated transfers: each D2H fetch
        is a device sync, so a batch of N arrays moves in
        len(distinct dtypes) fetches, not N."""
        return finish_to_host(self.start_to_host())

    def start_to_host(self):
        """Non-blocking half of to_host: dispatches the pack program and
        the async D2H copies, returns a token for finish_to_host. Lets a
        consumer overlap the ~100ms flat fetch latency of batch k+1 with
        batch k's host-side conversion (TpuColumnarToRowExec lookahead)."""
        flat, spec = flatten_batch(self)
        return (self, spec, start_fetch([self.active] + flat))

    @staticmethod
    def empty(schema: T.StructType, capacity: int = MIN_CAPACITY
              ) -> "DeviceBatch":
        return DeviceBatch.from_host(HostBatch.empty(schema), capacity)


def _prefetch_host(arrays: List[jax.Array]) -> bool:
    """NON-BLOCKING: enqueue async D2H copies so a later np.asarray
    finds the bytes already local. The per-fetch latency overlaps
    with whatever runs between the prefetch and the blocking read.
    Returns False when the
    backend has no async copies — callers that replaced a single batched
    fetch with per-item reads must fall back to batching then."""
    for a in arrays:
        try:
            a.copy_to_host_async()
        except Exception:
            return False  # backend without async copies
    return True


def finish_to_host(token) -> HostBatch:
    """Blocking half of DeviceBatch.start_to_host."""
    batch, spec, fetch_tok = token
    np_arrs = finish_fetch(fetch_tok)
    active = np_arrs[0]
    idx = np.nonzero(active)[0]
    cols: List[HostColumn] = []
    i = 1
    for f, (dt, n_arr) in zip(batch.schema.fields, spec):
        cols.append(_np_col_to_host(dt, np_arrs[i:i + n_arr], idx))
        i += n_arr
    return HostBatch(batch.schema, cols, len(idx))


_FETCH_PACK_CACHE = JitCache("fetchPack")


def start_fetch(arrays: List[jax.Array]):
    """Non-blocking: dispatch the per-dtype concat program (one
    transfer per distinct dtype instead of one per array) and the async
    copies; returns a token for finish_fetch."""
    key = tuple((a.shape, str(a.dtype)) for a in arrays)
    if len(arrays) <= 2:
        _prefetch_host(list(arrays))
        return ("raw", arrays, None)
    cached = _FETCH_PACK_CACHE.get(key)
    if cached is None:
        groups: dict = {}
        for i, (_shape, dt) in enumerate(key):
            groups.setdefault(dt, []).append(i)
        order = list(groups.items())

        def _fn(*arrs):
            return tuple(
                jnp.concatenate([arrs[i].reshape(-1) for i in idxs])
                if len(idxs) > 1 else arrs[idxs[0]].reshape(-1)
                for _dt, idxs in order)
        cached = _FETCH_PACK_CACHE.put(
            key, (named_jit("srt_fetch_pack", _fn), order))
    jfn, order = cached
    packed = jfn(*arrays)
    _prefetch_host(list(packed))
    return ("packed", arrays, (order, packed))


def finish_fetch(token) -> List[np.ndarray]:
    kind, arrays, extra = token
    # blocking device->host reads: deviceSyncTime (site=fetch)
    if kind == "raw":
        with _trace.device_sync("fetch"):
            return [np.asarray(a) for a in arrays]
    order, packed = extra
    with _trace.device_sync("fetch"):
        bufs = [np.asarray(buf) for buf in packed]
    out: List[Optional[np.ndarray]] = [None] * len(arrays)
    for (_dt, idxs), b in zip(order, bufs):
        off = 0
        for i in idxs:
            shape = arrays[i].shape
            size = int(np.prod(shape))
            out[i] = b[off:off + size].reshape(shape)
            off += size
    return out


def _fetch_arrays(arrays: List[jax.Array]) -> List[np.ndarray]:
    return finish_fetch(start_fetch(arrays))


def _np_col_to_host(dt: T.DataType, arrs: List[np.ndarray],
                    idx: np.ndarray) -> HostColumn:
    """Numpy twin of _device_col_to_host over already-fetched arrays."""
    if isinstance(dt, T.StructType):
        from spark_rapids_tpu.columnar.host import struct_storage_rows
        validity = arrs[-1][idx].astype(bool)
        fcols = []
        off = 0
        for f in dt.fields:
            k = column_arity(f.data_type)
            fcols.append(_np_col_to_host(f.data_type, arrs[off:off + k],
                                         idx))
            off += k
        return HostColumn(dt, struct_storage_rows(fcols, validity),
                          validity)
    if isinstance(dt, T.ArrayType):
        starts, lengths, validity = arrs[0], arrs[1], arrs[-1]
        child_arrs = arrs[2:-1]
        pool_n = child_arrs[0].shape[0]
        pc = _np_col_to_host(dt.element_type, list(child_arrs),
                             np.arange(pool_n))
        # storage-form pool values (to_pylist would convert dates etc.,
        # diverging from the CPU engine's canonical element form)
        pool = [pc.data[i].item() if isinstance(pc.data[i], np.generic)
                else pc.data[i]
                for i in range(len(pc.data))]
        pool = [v if ok else None
                for v, ok in zip(pool, pc.validity.tolist())]
        validity = validity[idx]
        data = np.empty(len(idx), dtype=object)
        for out_i, i in enumerate(idx):
            if validity[out_i]:
                s, ln = int(starts[i]), int(lengths[i])
                data[out_i] = tuple(pool[s:s + ln])
            else:
                data[out_i] = ()
        return HostColumn(dt, data, validity)
    if is_string_like(dt):
        chars, lengths, validity = arrs
        validity = validity[idx]
        data = np.empty(len(idx), dtype=object)
        is_binary = isinstance(dt, T.BinaryType)
        for out_i, i in enumerate(idx):
            raw = chars[i, :lengths[i]].tobytes()
            if is_binary:
                data[out_i] = raw if validity[out_i] else b""
            else:
                data[out_i] = (raw.decode("utf-8", errors="replace")
                               if validity[out_i] else "")
        return HostColumn(dt, data, validity)
    if T.is_limb_decimal(dt):
        hi, lo, validity = arrs
        data = np.stack([hi[idx], lo[idx]], axis=1)
        return HostColumn(dt, data, validity[idx].copy()).normalized()
    data, validity = arrs
    return HostColumn(dt, data[idx].copy(),
                      validity[idx].copy()).normalized()


def _put(arr: np.ndarray, device: Optional[jax.Device]) -> jax.Array:
    if device is not None:
        from spark_rapids_tpu import retry as R
        return R.with_retry(lambda: jax.device_put(arr, device))
    return jnp.asarray(arr)


def batch_device(b: DeviceBatch) -> Optional[jax.Device]:
    """The single device this batch's buffers live on, or None when the
    buffers are sharded/replicated across several (e.g. the landed
    output of a mesh exchange). The mesh scan pins each reader stream's
    batches to one chip; residency-aware consumers (exchange slotting,
    broadcast alignment) group by this."""
    try:
        ds = b.active.devices()
    except Exception:  # non-Array stand-ins in unit tests
        return None
    return next(iter(ds)) if len(ds) == 1 else None


def batch_to_device(b: DeviceBatch, device: jax.Device) -> DeviceBatch:
    """Copy a batch's buffers to ``device`` (device-to-device; a cheap
    no-op when already resident there)."""
    from spark_rapids_tpu import retry as R
    flat, spec = flatten_batch(b)
    moved = R.with_retry(lambda: jax.device_put(flat + [b.active],
                                                device))
    return DeviceBatch(b.schema, rebuild_columns(spec, moved[:-1]),
                       moved[-1], b._num_rows)


# One fused program per (input shape-set, output capacity): eager
# op-by-op dispatch pays a host round trip per op, so the whole
# concatenation must be a single XLA executable.
_CONCAT_CACHE = JitCache("concat")


def concat_device(batches: Sequence[DeviceBatch]) -> DeviceBatch:
    """Device-side Table.concatenate: compact all actives into one batch.

    Output capacity = bucket(total active rows). ONE jitted program
    (cached on input shapes + output capacity): each compacted input is
    written at its traced row offset in FORWARD order, so every write
    repairs the previous input's zero padding — full-capacity updates
    with dynamic offsets, no dynamic shapes. A sum-of-capacities scratch
    guards against XLA's update-slice start clamping, then a static
    slice takes the bucketed prefix.
    """
    assert batches
    if len(batches) == 1:
        return batches[0]
    # inputs spanning chips (a broadcast build or a global merge over
    # the mesh-sharded scan) must land on ONE device first: a jitted
    # program over differently-committed arrays is a placement error.
    # Merge onto the chip holding the most rows (capacity is static —
    # no count sync) so the skewed case moves the small side only
    devs = [batch_device(b) for b in batches]
    if any(d is not None for d in devs):
        load: dict = {}
        for b, d in zip(batches, devs):
            if d is not None:
                load[d] = load.get(d, 0) + b.capacity
        tgt = max(load, key=lambda d: (load[d], -d.id))
        if any(d is not None and d.id != tgt.id for d in devs):
            batches = [b if d is None or d.id == tgt.id
                       else batch_to_device(b, tgt)
                       for b, d in zip(batches, devs)]
    schema = batches[0].schema
    counts = [b.row_count() for b in batches]
    total = sum(counts)
    cap = bucket_capacity(max(1, total))
    compacted = [compact(b) for b in batches]
    flats = []
    specs = []
    for b in compacted:
        flat, spec = flatten_batch(b)
        flats.append(flat)
        specs.append(spec)
    shapes = tuple(tuple((a.shape, str(a.dtype)) for a in flat)
                   for flat in flats)
    key = (shapes, cap)
    fn = _CONCAT_CACHE.get(key)
    if fn is None:
        n_arrays = len(flats[0])
        # scratch must cover BOTH the forward-write extent (sum of input
        # capacities) and the output bucket (which can exceed it when
        # inputs are fully active)
        caps_sum = max(sum(b.capacity for b in compacted), cap)
        # per-FLAT-ARRAY char width: inputs may disagree on a 2-D byte
        # matrix width (incl. string fields nested in structs); writes
        # of a narrower input into the max-width zeros matrix leave the
        # correct zero padding
        arr_widths = [
            max(flats[bi][ai].shape[1] for bi in range(len(flats)))
            if flats[0][ai].ndim == 2 else 0
            for ai in range(n_arrays)]

        def _fn(counts_arr, *all_flat):
            offs = jnp.concatenate([
                jnp.zeros(1, jnp.int64), jnp.cumsum(counts_arr)])
            outs = []
            for ai in range(n_arrays):
                first = all_flat[ai]
                if first.ndim == 2:
                    cc = arr_widths[ai]
                    big = jnp.zeros((caps_sum, cc), dtype=first.dtype)
                    for bi in range(len(flats)):
                        a = all_flat[bi * n_arrays + ai]
                        big = jax.lax.dynamic_update_slice(
                            big, a, (offs[bi], jnp.int64(0)))
                    outs.append(big[:cap])
                else:
                    big = jnp.zeros(caps_sum, dtype=first.dtype)
                    for bi in range(len(flats)):
                        a = all_flat[bi * n_arrays + ai]
                        big = jax.lax.dynamic_update_slice(
                            big, a, (offs[bi],))
                    outs.append(big[:cap])
            total_t = offs[len(flats)]
            active = jnp.arange(cap) < total_t
            return active, tuple(outs)
        fn = _CONCAT_CACHE.put(key, named_jit("srt_concat", _fn))
    counts_arr = jnp.asarray(np.asarray(counts, dtype=np.int64))
    all_flat = [a for flat in flats for a in flat]
    active, outs = fn(counts_arr, *all_flat)
    cols = rebuild_columns(specs[0], outs)
    return DeviceBatch(schema, cols, active, total)


def mask_col(c: AnyDeviceColumn, keep: jax.Array) -> AnyDeviceColumn:
    """Null out rows outside `keep` (normalized zeros underneath)."""
    if isinstance(c, DeviceStructColumn):
        v = c.validity & keep
        return DeviceStructColumn(c.dtype,
                                  [mask_col(f, v) for f in c.fields], v)
    if isinstance(c, DeviceArrayColumn):
        v = c.validity & keep
        z = jnp.zeros((), c.starts.dtype)
        return DeviceArrayColumn(c.dtype, jnp.where(v, c.starts, z),
                                 jnp.where(v, c.lengths, z), c.child, v)
    if isinstance(c, DeviceStringColumn):
        v = c.validity & keep
        return DeviceStringColumn(
            c.dtype, jnp.where(v[:, None], c.chars, 0),
            jnp.where(v, c.lengths, 0), v)
    if isinstance(c, DeviceDecimal128Column):
        v = c.validity & keep
        z = jnp.zeros((), jnp.int64)
        return DeviceDecimal128Column(c.dtype, jnp.where(v, c.hi, z),
                                      jnp.where(v, c.lo, z), v)
    v = c.validity & keep
    return DeviceColumn(c.dtype, jnp.where(v, c.data,
                                           jnp.zeros((), c.data.dtype)), v)


_SORT_SIGN64 = 0x8000000000000000


def _order_u64(a: jax.Array) -> Optional[jax.Array]:
    """Order-preserving uint64 encoding of one sort-key array, or None
    when the dtype has no such encoding on this backend (f64: 64-bit
    float bitcasts do not lower)."""
    if a.dtype == jnp.bool_:
        return a.astype(jnp.uint64)
    if a.dtype == jnp.uint64:
        return a
    if jnp.issubdtype(a.dtype, jnp.unsignedinteger):
        return a.astype(jnp.uint64)
    if a.dtype == jnp.float64:
        return None
    if a.dtype == jnp.float32:
        u = jax.lax.bitcast_convert_type(a, jnp.int32).view(jnp.uint32)
        u = jnp.where(a < 0, ~u, u | jnp.uint32(0x80000000))
        return u.astype(jnp.uint64)
    return a.astype(jnp.int64).view(jnp.uint64) ^ jnp.uint64(_SORT_SIGN64)


def sort_with_payload(keys: Sequence[jax.Array],
                      payload: Sequence[jax.Array]):
    """Stable lexicographic sort by `keys`; `payload` arrays follow via
    gathers on the resulting order. Returns (sorted_keys, order,
    sorted_payload), `order` total/stable (original index tiebreak).

    XLA's sort compile time on this TPU stack grows superlinearly with
    operand count (measured round 3: a 2-operand sort compiles in ~30s,
    6 operands in ~135s, 8+ operands effectively hangs the compiler).
    So multi-key sorts run as LSD radix passes: each key is encoded as
    an order-preserving uint64 word and a ``lax.scan`` performs one
    STABLE 2-operand sort per key, least-significant first — exactly
    one compiled sort instance regardless of key count. f64 keys (no
    order-preserving 64-bit encoding without a float bitcast) fall back
    to per-key unrolled passes."""
    cap = keys[0].shape[0]
    pos = jnp.arange(cap, dtype=jnp.int32)
    enc = [_order_u64(k) for k in keys]

    def stable_pass(k, order):
        kp = jnp.take(k, order)
        _s, o2 = jax.lax.sort((kp, order), num_keys=1, is_stable=True)
        return o2.astype(jnp.int32)

    if all(e is not None for e in enc):
        if len(enc) == 1:
            order = stable_pass(enc[0], pos)
        else:
            rev = enc[::-1]  # least significant first
            # first pass outside the scan: its output carries the vma
            # (varying-manual-axes) type the scan carry needs when this
            # runs inside a shard_map
            order0 = stable_pass(rev[0], pos)
            stacked = jnp.stack(rev[1:])

            def body(order, k):
                return stable_pass(k, order), None
            order, _ = jax.lax.scan(body, order0, stacked)
    else:
        order = pos
        for k in reversed(keys):
            order = stable_pass(k, order)
    from spark_rapids_tpu.ops.lanes import fused_take
    # ONE lane-matrix gather for keys + payload together (a gather is
    # a fusion-breaking op: one beats one per array)
    gathered = fused_take(list(keys) + list(payload), order)
    sorted_keys = tuple(gathered[:len(keys)])
    sorted_payload = gathered[len(keys):]
    return sorted_keys, order, sorted_payload


def _compaction_order(active: jax.Array) -> jax.Array:
    """Stable permutation moving active rows to the front."""
    # stable argsort of (!active): False (active) sorts first, order kept
    return jnp.argsort(~active, stable=True)


def take_columns(columns: Sequence[AnyDeviceColumn], idx: jax.Array,
                 valid_at: Optional[jax.Array] = None
                 ) -> List[AnyDeviceColumn]:
    """Gather rows by index; when valid_at is given, rows where it is
    False become null (outer-join style null rows use idx clamped to 0).
    All columns ride ONE fused lane-matrix gather (ops/lanes.py) — the
    per-gather cost on this backend is a flat ~25-40ms regardless of
    width."""
    from spark_rapids_tpu.ops.lanes import fused_take
    arrays: List[jax.Array] = []
    for c in columns:
        if isinstance(c, DeviceArrayColumn):
            # the element pool is shared, not gathered
            arrays += [c.starts, c.lengths, c.validity]
        else:
            arrays += list(c.arrays())  # structs flatten recursively
    g = fused_take(arrays, idx)
    out: List[AnyDeviceColumn] = []
    off = 0
    for c in columns:
        if isinstance(c, DeviceArrayColumn):
            starts, lengths, validity = g[off:off + 3]
            off += 3
            if valid_at is not None:
                validity = validity & valid_at
            starts = jnp.where(validity, starts, 0)
            lengths = jnp.where(validity, lengths, 0)
            out.append(DeviceArrayColumn(c.dtype, starts, lengths,
                                         c.child, validity))
        elif isinstance(c, DeviceStringColumn):
            chars, lengths, validity = g[off:off + 3]
            off += 3
            if valid_at is not None:
                validity = validity & valid_at
                lengths = jnp.where(validity, lengths, 0)
                chars = jnp.where(validity[:, None], chars, 0)
            out.append(DeviceStringColumn(c.dtype, chars, lengths,
                                          validity))
        elif isinstance(c, DeviceDecimal128Column):
            hi, lo, validity = g[off:off + 3]
            off += 3
            if valid_at is not None:
                validity = validity & valid_at
                z = jnp.zeros((), jnp.int64)
                hi = jnp.where(validity, hi, z)
                lo = jnp.where(validity, lo, z)
            out.append(DeviceDecimal128Column(c.dtype, hi, lo, validity))
        elif isinstance(c, DeviceStructColumn):
            k = column_arity(c.dtype)
            sc = DeviceStructColumn.from_arrays(c.dtype, g[off:off + k])
            off += k
            if valid_at is not None:
                sc = mask_col(sc, valid_at)
            out.append(sc)
        else:
            data, validity = g[off:off + 2]
            off += 2
            if valid_at is not None:
                validity = validity & valid_at
                data = jnp.where(validity, data,
                                 jnp.zeros((), dtype=data.dtype))
            out.append(DeviceColumn(c.dtype, data, validity))
    return out


@jax.named_scope("compact")
def _compact_body(active: jax.Array, flat, cap: Optional[int] = None):
    """Stable compaction (active rows to the front) into ``cap`` lanes,
    the input's by default: ONE 2-operand sort pass for the permutation
    + ONE fused lane-matrix gather, for all arrays, of its first ``cap``
    entries only. (A searchsorted-based variant was tried in round 5: XLA
    lowers searchsorted to ~log2(cap) gather iterations on this backend,
    costing more than the sort pass it saved.)"""
    from spark_rapids_tpu.ops.lanes import fused_take
    pos = jnp.arange(active.shape[0], dtype=jnp.int32)
    _k, idx = jax.lax.sort((~active, pos), num_keys=1, is_stable=True)
    if cap is not None:
        pos, idx = pos[:cap], idx[:cap]
    new_active = pos < jnp.sum(active)
    outs = []
    for g in fused_take(list(flat), idx):
        # zero out the padding tail for determinism
        if g.ndim == 2:
            g = jnp.where(new_active[:, None], g, 0)
        else:
            g = jnp.where(new_active, g, jnp.zeros((), dtype=g.dtype))
        outs.append(g)
    return new_active, tuple(outs)


# tpu-lint: disable=jit-direct(single fixed compaction program — jax's own signature cache bounds it by shape set)
_compact_arrays = named_jit(
    "srt_compact", lambda active, *flat: _compact_body(active, flat))


def flatten_columns(columns: Sequence[AnyDeviceColumn]
                    ) -> Tuple[List[jax.Array], List[Tuple[T.DataType, int]]]:
    """Flatten column arrays + per-column (dtype, arity) spec; inverse is
    rebuild_columns."""
    flat: List[jax.Array] = []
    spec: List[Tuple[T.DataType, int]] = []
    for c in columns:
        arrs = c.arrays()
        spec.append((c.dtype, len(arrs)))
        flat.extend(arrs)
    return flat, spec


def flatten_batch(batch: DeviceBatch
                  ) -> Tuple[List[jax.Array], List[Tuple[T.DataType, int]]]:
    """Flatten a batch's column arrays (see flatten_columns). Shared by
    compaction and the split/serialize kernels."""
    return flatten_columns(batch.columns)


def rebuild_columns(spec: Sequence[Tuple[T.DataType, int]],
                    outs: Sequence[jax.Array]) -> List[AnyDeviceColumn]:
    cols: List[AnyDeviceColumn] = []
    i = 0
    for dt, n_arr in spec:
        cols.append(make_column(dt, outs[i:i + n_arr]))
        i += n_arr
    return cols


def compact(batch: DeviceBatch) -> DeviceBatch:
    """Move active rows to the front (fixed-shape compaction)."""
    flat, spec = flatten_batch(batch)
    new_active, outs = _compact_arrays(batch.active, *flat)
    cols = rebuild_columns(spec, outs)
    return DeviceBatch(batch.schema, cols, new_active, batch._num_rows)


_SHRINK_CACHE = JitCache("shrink")


def _shrink_impl(batch: DeviceBatch, n: int, compact_first: bool
                 ) -> DeviceBatch:
    """Slice down to n's capacity bucket as ONE jitted program per
    (shape-set, target capacity, compact?), compacting first unless the
    caller guarantees active rows already form a prefix."""
    cap = bucket_capacity(max(1, n))
    if cap >= batch.capacity:
        return compact(batch) if compact_first else batch
    flat, spec = flatten_batch(batch)
    key = (tuple((a.shape, str(a.dtype)) for a in flat), cap,
           compact_first)
    fn = _SHRINK_CACHE.get(key)
    if fn is None:
        def _fn(active, *arrs):
            if compact_first:
                return _compact_body(active, arrs, cap)
            return active[:cap], tuple(
                (a[:cap] if a.ndim == 1 else a[:cap, :]) for a in arrs)
        fn = _SHRINK_CACHE.put(key, named_jit("srt_shrink", _fn))
    new_active, outs = fn(batch.active, *flat)
    return DeviceBatch(batch.schema, rebuild_columns(spec, outs),
                       new_active, n)


def shrink_to_bucket(batch: DeviceBatch) -> DeviceBatch:
    """Compact, then if the active count fits a smaller capacity bucket,
    slice down to it (keeps shuffle payloads tight)."""
    n = batch.row_count()  # the one necessary host sync (sizes the bucket)
    return _shrink_impl(batch, n, compact_first=True)


def slice_compacted_to_bucket(batch: DeviceBatch) -> DeviceBatch:
    """Slice an ALREADY-COMPACTED batch (active rows form a prefix,
    ``_num_rows`` known) down to its capacity bucket — a pure static
    slice, no sort and no host sync (unlike shrink_to_bucket)."""
    n = batch.row_count()  # cached: caller set _num_rows
    return _shrink_impl(batch, n, compact_first=False)

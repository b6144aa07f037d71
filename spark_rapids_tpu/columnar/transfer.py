"""Packed host->device transfer codec (the bytes-on-the-wire discipline).

H2D pays a fixed cost per buffer and moves exactly the bytes it is
given (nothing on the way compresses); the rates are not measured on
the directly attached chip. So the upload path

  (a) narrows integer columns to the smallest int dtype that holds their
      value range (Parquet-style bit-width reduction), shipping each as
      its own buffer — the decode is then a pure elementwise astype.
      (Weaving them into the staging words would decode via (n,2)
      reshapes, whose TPU tiling pads the minor dim 2 -> 128: a 64x HBM
      blowup that OOMs wide batches.)
  (b) bit-packs booleans and validity masks into the int32 staging
      words (skipping all-valid masks entirely), alongside the string
      byte matrices,
  (c) ships only the real rows (no capacity padding on the wire), and
  (d) moves the staging words + raw buffers in ONE device_put, with a
      single jitted program rebuilding full-width, capacity-padded
      columns in HBM.

The reference's scan path uses the same idea at the file level: copy the
compact encoded bytes to the device once, decode there
(GpuParquetScanBase.scala:82 row-group copy + cudf decode). Here it is
applied to every row->columnar upload.

float64 columns bypass the packed buffer (their reconstruction would
need a 64-bit float bitcast, which this TPU lowering stack rejects) and
ride the same device_put as extra raw buffers.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import trace as _trace
from spark_rapids_tpu.sql import types as T

# layout entry kinds
_INT_KINDS = ("i8", "i16", "i32", "i64")


def _narrow_kind(mn: int, mx: int) -> str:
    if -128 <= mn and mx <= 127:
        return "i8"
    if -32768 <= mn and mx <= 32767:
        return "i16"
    if -(1 << 31) <= mn and mx <= (1 << 31) - 1:
        return "i32"
    return "i64"


_KIND_WIDTH = {"i8": 1, "i16": 2, "i32": 4, "i64": 8}


class _Packer:
    """Accumulates 4-byte-aligned byte regions into one staging buffer."""

    def __init__(self):
        self.parts: List[np.ndarray] = []
        self.off = 0

    def add(self, arr: np.ndarray) -> int:
        return self.add_parts([arr])

    def add_parts(self, arrs: Sequence[np.ndarray]) -> int:
        """One region of several arrays laid end to end: aligned at
        its start, padded only behind the last."""
        start = self.off
        for arr in arrs:
            b = np.ascontiguousarray(arr).view(np.uint8).reshape(-1)
            self.parts.append(b)
            self.off += b.nbytes
        pad = (-self.off) % 4
        if pad:
            self.parts.append(np.zeros(pad, np.uint8))
            self.off += pad
        return start

    def words(self) -> np.ndarray:
        if not self.parts:
            return np.zeros(1, dtype=np.int32)
        return np.concatenate(self.parts).view(np.int32)


def _encode_strings(data: np.ndarray, validity: np.ndarray, n: int,
                    is_binary: bool) -> Tuple[np.ndarray, np.ndarray]:
    """Object array of str/bytes -> (uint8[n, char_cap], int32 lengths).
    ASCII string columns take a vectorized numpy path (codepoints via a
    U-dtype view); anything else falls back to per-row encoding."""
    from spark_rapids_tpu.columnar.device import bucket_char_cap
    if n == 0:
        return np.zeros((0, 8), np.uint8), np.zeros(0, np.int32)
    if not is_binary:
        try:
            u = data.astype(np.str_)
        except (TypeError, ValueError):
            u = None
        if u is not None and u.dtype.itemsize == 0:
            return np.zeros((n, 8), np.uint8), np.zeros(n, np.int32)
        if u is not None:
            k = u.dtype.itemsize // 4
            u32 = np.ascontiguousarray(u).view(np.uint32).reshape(n, k)
            if (u32 < 128).all():
                # pure-ASCII fast path: UTF-32 codepoints ARE the bytes
                lengths = np.char.str_len(u).astype(np.int32)
                char_cap = bucket_char_cap(int(lengths.max(initial=1)))
                chars = np.zeros((n, char_cap), np.uint8)
                w = min(k, char_cap)
                chars[:, :w] = u32[:, :w].astype(np.uint8)
                lengths = np.where(validity, lengths, 0)
                chars[~validity] = 0
                return chars, lengths
    encoded: List[bytes] = []
    max_len = 1
    for i in range(n):
        if validity[i]:
            v = data[i]
            b = v.encode("utf-8") if isinstance(v, str) else bytes(v)
        else:
            b = b""
        encoded.append(b)
        max_len = max(max_len, len(b))
    char_cap = bucket_char_cap(max_len)
    chars = np.zeros((n, char_cap), np.uint8)
    lengths = np.zeros(n, np.int32)
    for i, b in enumerate(encoded):
        chars[i, :len(b)] = np.frombuffer(b, dtype=np.uint8)
        lengths[i] = len(b)
    return chars, lengths


def pack_batch(batch) -> Tuple[np.ndarray, List[np.ndarray], Tuple]:
    """Stage a HostBatch: returns (int32 staging words, extra raw buffers,
    static layout descriptor). Layout is hashable and, with (n, cap),
    fully determines the decode program."""
    from spark_rapids_tpu.columnar.device import is_string_like
    n = batch.num_rows
    pk = _Packer()
    extras: List[np.ndarray] = []
    layout: List[Tuple] = []
    for f, c in zip(batch.schema.fields, batch.columns):
        dt = f.data_type
        validity = np.ascontiguousarray(c.validity[:n])
        if validity.all():
            vdesc: Tuple = ("av",)
        else:
            vdesc = ("vb", pk.add(np.packbits(validity, bitorder="little")))
        if is_string_like(dt):
            vb = getattr(c, "varbytes", None)
            if vb is not None and len(vb[1]) == n and len(vb[0]) > 0:
                # compact Arrow bytes ride the wire as-is; the decode
                # program rebuilds the padded char matrix on device
                # (cumsum starts + gather) — no host re-encode, no
                # char_cap padding on the wire. The byte payload is
                # padded to a bucketed size so the layout tuple (and
                # with it every later column's c_off) repeats across
                # batches — an exact len(bts) would compile a fresh
                # decode program per batch.
                from spark_rapids_tpu.columnar.device import (
                    bucket_capacity, bucket_char_cap)
                bts, raw_lengths = vb
                masked_max = int(raw_lengths[validity].max()) \
                    if validity.any() else 1
                char_cap = bucket_char_cap(max(1, masked_max))
                nb = bucket_capacity(len(bts))
                if nb > len(bts):
                    bts = np.concatenate(
                        [bts, np.zeros(nb - len(bts), np.uint8)])
                c_off = pk.add(bts)
                raw_max = int(raw_lengths.max(initial=0))
                lk = ("i8" if raw_max <= 127 else
                      "i16" if raw_max <= 32767 else "i32")
                l_idx = len(extras)
                extras.append(raw_lengths.astype(
                    {"i8": np.int8, "i16": np.int16,
                     "i32": np.int32}[lk]))
                layout.append(("vstr", char_cap, c_off, nb,
                               lk, l_idx, vdesc))
                continue
            chars, lengths = _encode_strings(
                c.data, validity, n, isinstance(dt, T.BinaryType))
            # invalid slots already zeroed by _encode_strings
            char_cap = chars.shape[1] if n else 8
            c_off = pk.add(chars)
            lk = ("i8" if char_cap <= 127 else
                  "i16" if char_cap <= 32767 else "i32")
            l_idx = len(extras)
            extras.append(lengths.astype(
                {"i8": np.int8, "i16": np.int16, "i32": np.int32}[lk]))
            layout.append(("str", char_cap, c_off, lk, l_idx, vdesc))
            continue
        if T.is_limb_decimal(dt):
            limbs = c.data[:n]
            if not validity.all():
                limbs = limbs.copy()
                limbs[~validity] = 0
            ent = ["dec128"]
            for li in range(2):  # hi then lo, each narrowed like an int
                ld = np.ascontiguousarray(limbs[:, li])
                mn, mx = (int(ld.min()), int(ld.max())) if n else (0, 0)
                kind = _narrow_kind(mn, mx)
                ent.append(len(extras))
                extras.append(ld.astype(
                    np.dtype(kind.replace("i", "int"))))
            ent.append(vdesc)
            layout.append(tuple(ent))
            continue
        np_dt = T.numpy_dtype(dt)
        data = np.ascontiguousarray(c.data[:n])
        if not validity.all():
            # normalized zeros at invalid slots (narrowing + determinism)
            data = data.copy()
            data[~validity] = (False if np_dt == np.dtype(bool) else
                               np_dt.type(0))
        if np_dt == np.dtype(bool):
            layout.append(("bool", pk.add(np.packbits(
                data.astype(bool), bitorder="little")), vdesc))
        elif np_dt == np.dtype(np.float64):
            layout.append(("f64", len(extras), vdesc))
            # asarray: already-f64 contiguous data ships without a copy
            extras.append(np.asarray(data, np.float64))
        elif np_dt == np.dtype(np.float32):
            layout.append(("f32", pk.add(np.asarray(data, np.float32)),
                           vdesc))
        else:
            if n:
                mn, mx = int(data.min()), int(data.max())
            else:
                mn = mx = 0
            kind = _narrow_kind(mn, mx)
            # don't widen on the wire (e.g. int8 storage stays int8)
            kind = kind if _KIND_WIDTH[kind] <= np_dt.itemsize else \
                {1: "i8", 2: "i16", 4: "i32", 8: "i64"}[np_dt.itemsize]
            narrow = data.astype(np.dtype(kind.replace("i", "int")))
            # narrowed ints ride as their OWN buffers: widening back is
            # a pure elementwise astype. Weaving them through the int32
            # staging words would decode via (n,2)-shaped reshapes whose
            # TPU tiling pads the minor dim 2 -> 128 (a 64x HBM blowup
            # that OOMs multi-column batches).
            layout.append(("int", str(np_dt), len(extras), vdesc))
            extras.append(narrow)
    return pk.words(), extras, tuple(layout)


# -- device-side decode ----------------------------------------------------

# Bounded LRU: every distinct (layout, n, cap, nbytes) compiles its own
# decode program; long sessions with varying batch sizes must not retain
# them all.
from spark_rapids_tpu.jit_cache import JitCache, named_jit

_DECODE_CACHE = JitCache("uploadDecode", capacity=64)


def _pad_cap(x: jax.Array, n: int, cap: int) -> jax.Array:
    if cap == n:
        return x
    pad = [(0, cap - n)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad)


def _build_decode(layout: Tuple, n: int, cap: int) -> Callable:
    """One XLA program: staging words -> per-column (data, validity)
    arrays at full capacity, plus the active mask."""

    def fn(words, *extras):
        bytes_all = None

        def get_bytes():
            nonlocal bytes_all
            if bytes_all is None:
                shifts = jnp.arange(4, dtype=jnp.int32) * 8
                bytes_all = ((words[:, None] >> shifts) & 0xFF).reshape(-1)
            return bytes_all

        def decode_bits(off: int, count: int) -> jax.Array:
            nbytes = (count + 7) // 8
            b = jax.lax.slice(get_bytes(), (off,), (off + nbytes,))
            bits = ((b[:, None] >> jnp.arange(8, dtype=jnp.int32)) & 1)
            return bits.reshape(-1)[:count].astype(jnp.bool_)

        active = jnp.arange(cap) < n
        outs: List[jax.Array] = []
        for ent in layout:
            vdesc = ent[-1]
            if vdesc[0] == "av":
                validity = active
            else:
                validity = _pad_cap(decode_bits(vdesc[1], n), n, cap)
            kind = ent[0]
            if kind == "vstr":
                # compact bytes -> (cap, char_cap) matrix on device:
                # starts are the cumsum of the raw lengths, each row
                # gathers its window, nulls/tails mask to 0
                _, char_cap, c_off, nbytes, _lk, l_idx, _v = ent
                raw_len = extras[l_idx].astype(jnp.int32)
                starts = jnp.cumsum(raw_len) - raw_len
                src = jax.lax.slice(get_bytes(), (c_off,),
                                    (c_off + max(1, nbytes),))
                idx = starts[:, None] + jnp.arange(char_cap,
                                                   dtype=jnp.int32)
                out_len = jnp.where(validity[:n], raw_len, 0)
                mask = jnp.arange(char_cap, dtype=jnp.int32) \
                    < out_len[:, None]
                gathered = src[jnp.clip(idx, 0, max(0, nbytes - 1))]
                chars = jnp.where(mask, gathered, 0).astype(jnp.uint8)
                outs.extend([_pad_cap(chars, n, cap),
                             _pad_cap(out_len, n, cap), validity])
            elif kind == "str":
                _, char_cap, c_off, lk, l_idx, _ = ent
                chars = _pad_cap(
                    jax.lax.slice(get_bytes(), (c_off,),
                                  (c_off + n * char_cap,))
                    .reshape(n, char_cap).astype(jnp.uint8), n, cap)
                lengths = _pad_cap(
                    extras[l_idx].astype(jnp.int32), n, cap)
                outs.extend([chars, lengths, validity])
            elif kind == "dec128":
                _, i_hi, i_lo, _v = ent
                hi = extras[i_hi].astype(jnp.int64)
                lo = extras[i_lo].astype(jnp.int64)
                outs.extend([_pad_cap(hi, n, cap), _pad_cap(lo, n, cap),
                             validity])
            elif kind == "bool":
                outs.extend([_pad_cap(decode_bits(ent[1], n), n, cap),
                             validity])
            elif kind == "f64":
                outs.extend([_pad_cap(extras[ent[1]], n, cap), validity])
            elif kind == "f32":
                w = ent[1] // 4
                raw = jax.lax.slice(words, (w,), (w + n,))
                outs.extend([_pad_cap(jax.lax.bitcast_convert_type(
                    raw, jnp.float32), n, cap), validity])
            else:  # "int": own narrowed buffer, widen elementwise
                _, np_dt, idx, _v = ent
                data = extras[idx].astype(jnp.dtype(np_dt))
                outs.extend([_pad_cap(data, n, cap), validity])
        return active, tuple(outs)

    return named_jit("srt_upload_decode", fn)


# Below this row count the packed codec's per-(layout, n, cap) decode
# compile outweighs the wire savings; small batches ride a plain padded
# device_put (no program at all).
PACKED_MIN_ROWS = 1 << 16


def _col_from_storage_values(vals, dt: T.DataType):
    """Storage-form python values (None = null) -> HostColumn, without
    the from_pylist value conversion (dates/decimals already sit in
    storage ints inside struct tuples)."""
    from spark_rapids_tpu.columnar.host import HostColumn
    n = len(vals)
    validity = np.array([v is not None for v in vals], dtype=bool)
    if T.is_limb_decimal(dt):
        from spark_rapids_tpu.ops import int128 as I
        hi, lo = I.from_pyints([0 if v is None else int(v) for v in vals])
        return HostColumn(dt, np.stack([hi, lo], axis=1), validity)
    np_dt = T.numpy_dtype(dt)
    if np_dt == np.dtype(object):
        data = np.empty(n, dtype=object)
        for i, v in enumerate(vals):
            data[i] = v if v is not None else ""
        return HostColumn(dt, data, validity)
    fill = False if np_dt == np.dtype(bool) else np_dt.type(0)
    data = np.array([fill if v is None else v for v in vals],
                    dtype=np_dt)
    return HostColumn(dt, data, validity)


def _stage_column(c, dt: T.DataType, cap: int) -> List[np.ndarray]:
    """Full-width staging buffers for one column, matching the device
    column's arrays() layout; recurses into array element pools."""
    from spark_rapids_tpu.columnar import device as D
    from spark_rapids_tpu.columnar.host import HostColumn
    n = len(c)
    validity = np.zeros(cap, dtype=bool)
    validity[:n] = c.validity
    if isinstance(dt, T.ArrayType):
        starts = np.zeros(cap, dtype=np.int32)
        lengths = np.zeros(cap, dtype=np.int32)
        elems: List = []
        off = 0
        for i in range(n):
            if c.validity[i]:
                row = c.data[i]
                starts[i] = off
                lengths[i] = len(row)
                elems.extend(row)
                off += len(row)
        child_cap = D.bucket_capacity(max(1, off))
        child_col = HostColumn.from_pylist(elems, dt.element_type)
        return [starts, lengths] + \
            _stage_column(child_col, dt.element_type, child_cap) + \
            [validity]
    if isinstance(dt, T.StructType):
        validity = np.zeros(cap, dtype=bool)
        validity[:n] = c.validity
        parts: List[np.ndarray] = []
        from spark_rapids_tpu.columnar.host import struct_field_values
        for fi, f in enumerate(dt.fields):
            # field values are ALREADY storage-form (struct tuples hold
            # storage ints); build the host column without re-converting
            parts.extend(_stage_column(
                _col_from_storage_values(
                    struct_field_values(c, fi)[:n], f.data_type),
                f.data_type, cap))
        return parts + [validity]
    if D.is_string_like(dt):
        ch, ln = _encode_strings(c.data, c.validity, n,
                                 isinstance(dt, T.BinaryType))
        char_cap = ch.shape[1] if n else 8
        chars = np.zeros((cap, char_cap), dtype=np.uint8)
        chars[:n] = ch
        lengths = np.zeros(cap, dtype=np.int32)
        lengths[:n] = ln
        return [chars, lengths, validity]
    if T.is_limb_decimal(dt):
        limbs = np.zeros((cap, 2), dtype=np.int64)
        limbs[:n] = c.normalized().data
        return [np.ascontiguousarray(limbs[:, 0]),
                np.ascontiguousarray(limbs[:, 1]), validity]
    np_dt = T.numpy_dtype(dt)
    data = np.zeros(cap, dtype=np_dt)
    data[:n] = c.normalized().data
    return [data, validity]


def _stage_direct(batch, cap: int):
    """Host staging for the small-batch / nested-column path."""
    n = batch.num_rows
    np_arrays: List[np.ndarray] = []
    spec: List[Tuple[T.DataType, int]] = []
    for f, c in zip(batch.schema.fields, batch.columns):
        parts = _stage_column(c, f.data_type, cap)
        spec.append((f.data_type, len(parts)))
        np_arrays.extend(parts)
    active_np = np.zeros(cap, dtype=bool)
    active_np[:n] = True
    np_arrays.append(active_np)
    return ("direct", batch.schema, n, spec, np_arrays)


def prepare_upload(batch, cap: int, metrics=None):
    """Host-side half of an upload (pack/stage, NO device touch): the
    returned opaque token feeds finish_upload. Splitting the phases lets
    a producer thread pack batch k+1 while batch k's bytes move.
    ``metrics`` (scan path) names the query whose first dispatch the
    decode program may be."""
    from spark_rapids_tpu.io.device_decode import EncodedBatch
    if isinstance(batch, EncodedBatch):
        return prepare_encoded_upload(batch, cap, metrics=metrics)
    n = batch.num_rows
    if n < PACKED_MIN_ROWS or any(
            isinstance(f.data_type, (T.ArrayType, T.StructType))
            for f in batch.schema.fields):
        return _stage_direct(batch, cap)
    words, extras, layout = pack_batch(batch)
    return ("packed", batch.schema, n, cap, words, extras, layout)


def finish_upload(staged, device: Optional[jax.Device] = None):
    """Device-side half: one device_put (+ one decode program on the
    packed and encoded paths). Traced per staging mode with the target
    chip, nested inside the R2C transition's copyToDeviceTime span."""
    with _trace.span("finishUpload", mode=staged[0],
                     chip=(device.id if device is not None else None)):
        return finish_started(start_upload(staged, device))


def start_upload(staged, device: Optional[jax.Device] = None):
    """Issue a staged token's host->device copies ASYNCHRONOUSLY (jax
    device_put returns once the transfers are enqueued) and return an
    upload token for :func:`finish_started`. The split is the scan
    pipeline's upload-ahead hook (docs/scan.md): batch k+1's raw-chunk
    bytes move while batch k's decode program / downstream compute
    runs, bounded by deviceDecode.maxInFlight tokens in flight."""
    def put(bufs):
        return (jax.device_put(bufs, device) if device is not None
                else jax.device_put(bufs))

    if staged[0] == "direct":
        _tag, schema, n, spec, np_arrays = staged
        return ("direct", schema, n, spec, put(np_arrays))
    if staged[0] == "encoded":
        (_tag, schema, n, cap, words, extras, layout, spec,
         metrics) = staged
        dev = put([words, np.asarray(n, dtype=np.int64)] + list(extras))
        return ("encoded", schema, n, cap, words.nbytes, layout, spec,
                dev, metrics)
    _tag, schema, n, cap, words, extras, layout = staged
    return ("packed", schema, n, cap, words.nbytes, layout,
            put([words] + extras))


def finish_started(token):
    """Complete a :func:`start_upload` token: run the decode program
    (packed/encoded paths) and assemble the DeviceBatch. Safe to
    re-invoke after an OOM retry — the device buffers are still
    resident, only the program dispatch repeats."""
    from spark_rapids_tpu.columnar import device as D
    if token[0] == "direct":
        _tag, schema, n, spec, dev = token
        return D.DeviceBatch(schema, D.rebuild_columns(spec, dev[:-1]),
                             dev[-1], n)
    if token[0] == "encoded":
        return _finish_encoded_upload(token)
    _tag, schema, n, cap, nbytes, layout, dev = token
    key = (layout, n, cap, nbytes)
    fn = _DECODE_CACHE.get(key)
    if fn is None:
        fn = _DECODE_CACHE.put(key, _build_decode(layout, n, cap))
    _trace.first_dispatch(None, fn)
    active, outs = fn(dev[0], *dev[1:])
    spec = [(f.data_type,
             3 if (D.is_string_like(f.data_type)
                   or T.is_limb_decimal(f.data_type)) else 2)
            for f in schema.fields]
    return D.DeviceBatch(schema, D.rebuild_columns(spec, outs),
                         active, n)


def upload_batch(batch, cap: int, device: Optional[jax.Device] = None):
    """HostBatch -> DeviceBatch via the packed codec (one device_put,
    one decode program); small batches skip the codec."""
    return finish_upload(prepare_upload(batch, cap), device)


# -- device parquet decode (EncodedBatch path) -----------------------------
#
# The scan's raw-page staging: the wire carries the *still-encoded*
# page bytes (dict indices at their bit width, packed validity runs,
# PLAIN fixed-width bytes) plus small host-parsed plan tables; one XLA
# program per (layout, n, cap) expands everything into device columns
# (the reference's copy-compact-bytes-then-cudf-decode shape,
# GpuParquetScanBase.scala:82, applied to the scan itself).

def _pad_pow2(n: int, floor: int = 8) -> int:
    if n <= floor:
        return floor
    return 1 << (n - 1).bit_length()


def prepare_encoded_upload(enc, cap: int, metrics=None):
    """EncodedBatch -> staged token: pads plan tables to pow2 buckets so
    the decode-program cache keys repeat across row groups (the row
    count itself rides as a device scalar, so row groups of any size
    share one program per layout/capacity bucket)."""
    n = enc.num_rows
    extras: List[np.ndarray] = []
    layout: List[Tuple] = []
    spec: List[Tuple[T.DataType, int]] = []
    for fi, f in enumerate(enc.schema.fields):
        dt = f.data_type
        plan = enc.plans.get(fi)
        if plan is None:
            parts = _stage_column(enc.host_cols[fi], dt, cap)
            layout.append(("host", len(parts)))
            spec.append((dt, len(parts)))
            extras.extend(parts)
            continue
        # the page tables ride only where a lane has to look its page
        # up: not for dictionary pages followed by PLAIN ones (npg 0)
        n_pages = len(plan.pg_enc)
        npg = _pad_pow2(n_pages) if plan.paged else 0
        if npg:
            dense_start = np.full(npg + 1, 1 << 62, dtype=np.int64)
            dense_start[:n_pages + 1] = plan.pg_dense_start
            plain_byte = np.zeros(npg, dtype=np.int64)
            plain_byte[:n_pages] = plan.pg_plain_byte
            pg_enc = np.zeros(npg, dtype=np.int32)
            pg_enc[:n_pages] = plan.pg_enc
            extras.extend([dense_start, plain_byte, pg_enc])
        if plan.has_plain:
            # where the PLAIN region starts (in words) and which dense
            # lane its first value is: device values, like the row count
            extras.append(np.array(
                [plan.plain_base // 4, plan.plain_dense0], dtype=np.int32))
        if plan.has_delta:
            pg_first = np.zeros(npg, dtype=np.int64)
            pg_first[:n_pages] = plan.pg_first
            extras.append(pg_first)
        ndl = _pad_pow2(len(plan.dl)) if plan.dl is not None else 0
        if plan.dl is not None:
            extras.extend(plan.dl.arrays(ndl))
        nvr = _pad_pow2(len(plan.vr)) if plan.vr is not None else 0
        if plan.vr is not None:
            extras.extend(plan.vr.arrays(nvr))
        ndr = _pad_pow2(len(plan.dr)) if plan.dr is not None else 0
        if plan.dr is not None:
            extras.extend(plan.dr.arrays(ndr))
        has_slen = plan.str_lens is not None
        if has_slen:
            slen = np.zeros(cap, dtype=np.int32)
            slen[:plan.str_lens.shape[0]] = plan.str_lens
            extras.append(slen)
        dict_shapes: List[Tuple] = []
        for da in plan.dict_arrays:
            pad = _pad_pow2(da.shape[0], floor=1)
            if pad > da.shape[0]:
                padded = np.zeros((pad,) + da.shape[1:], dtype=da.dtype)
                padded[:da.shape[0]] = da
                da = padded
            dict_shapes.append((da.shape, str(da.dtype)))
            extras.append(da)
        layout.append(("dev", plan.kind, plan.np_dtype, plan.elem_bytes,
                       plan.char_cap, npg, ndl, nvr, ndr,
                       tuple(dict_shapes), plan.has_plain,
                       plan.has_delta, plan.has_bss, has_slen))
        arity = 3 if plan.kind in ("str", "dec128") else 2
        spec.append((dt, arity))
    # bucket the page buffer so same-shaped row groups share one
    # decode program (exact sizes would compile per unit)
    from spark_rapids_tpu.columnar.device import bucket_capacity
    words = enc.words
    nw = bucket_capacity(len(words))
    if nw > len(words):
        words = np.concatenate([words,
                                np.zeros(nw - len(words), np.int32)])
    return ("encoded", enc.schema, n, cap, words, extras,
            tuple(layout), tuple(spec), metrics)


def _encoded_decode_body(layout: Tuple, cap: int, words, n_arr, extras):
    """The encoded-decode arithmetic, jitted by
    ``_build_encoded_decode``.

    Every column is decoded in DENSE coordinates: lane i of
    ``arange(cap)`` is the i-th stored (non-null) value of the chunk.
    Its page comes from ``rle.run_index`` (one scatter of the table's
    starts and one prefix sum; no per-lane search, no loop) and the
    fields of its run — bit offset, width, value — from
    ``rle.step_fields`` (one scatter of each field's steps and one
    prefix sum: fields by prefix sum, no gather through a run's
    index), and each stored value is decoded exactly once. A
    bit-packed value is read from the int32 staging ``words``
    themselves — the two aligned words it lies in, ``rle.read_packed``.
    A PLAIN fixed-width value is read from them too, with no gather:
    the host lays a chunk's PLAIN value sections end to end from a
    4-aligned byte, in dense order (``device_decode._plan_column``),
    and hands over where (``plain_at``: the region's word index and the
    dense lane of its first value, an int32 pair on the device), so
    ``rle.read_plain`` takes one contiguous window at the fixed stride
    and moves it by that lane. The window may reach past the buffer
    for lanes no row uses, so the buffer is padded once by the widest
    window (``dynamic_slice`` would clamp the start and shift every
    lane). Where a chunk's pages are dictionary pages followed by
    PLAIN ones — what writers produce — no page table rides at all
    (``npg == 0``, a static fact of the layout): a lane is PLAIN iff
    it is not before that first lane. Only BYTE_STREAM_SPLIT and
    string pages read bytes and expand the buffer to them, lazily; a
    layout with neither never does. Rows reach their values by ONE gather
    through ``j`` (row -> dense rank) at the end, and a column without
    definition levels (``ndl == 0``, a static fact of the layout)
    skips it: there every active row IS its own dense lane, and rows
    past ``n`` are zeroed by ``validity`` either way. Each lane runs
    under a ``jax.named_scope``
    (``decode_page_lookup``, ``decode_bits`` with ``/bytes``,
    ``/run_fields`` and ``/window`` inside it,
    ``decode_dict``, ``decode_plain``, ``decode_chars``,
    ``decode_delta``, ``decode_rows``) so a device profile ranks them
    (``tools trace <profile dir>``, docs/observability.md)."""
    from spark_rapids_tpu.io.device_decode import (PGE_BSS, PGE_DELTA,
                                                   PGE_DICT, PGE_DL_STR,
                                                   PGE_PLAIN_STR)
    from spark_rapids_tpu.ops import rle as R
    bytes_all = None

    def get_bytes():
        nonlocal bytes_all
        if bytes_all is None:
            with jax.named_scope("decode_bits/bytes"):
                bytes_all = R.bytes_of_words(words)
        return bytes_all

    # ent[3], ent[10]: elem_bytes and has_plain of a "dev" entry
    plain_pad = max((R.plain_window_words(cap, ent[3]) for ent in layout
                     if ent[0] == "dev" and ent[10]), default=0)
    words_plain = jnp.pad(words, (0, plain_pad)) if plain_pad else None

    def rows(dense, j):
        if j is None:
            return dense
        with jax.named_scope("decode_rows"):
            return dense[j]

    active = jnp.arange(cap) < n_arr
    pos = jnp.arange(cap, dtype=jnp.int64)
    outs: List[jax.Array] = []
    cur = 0
    for ent in layout:
        if ent[0] == "host":
            _tag, n_parts = ent
            outs.extend(extras[cur:cur + n_parts])
            cur += n_parts
            continue
        (_tag, kind, np_dt, elem_bytes, char_cap, npg, ndl, nvr,
         ndr, dict_shapes, has_plain, has_delta, has_bss,
         has_slen) = ent
        if npg:
            dense_start, plain_byte, pg_enc = extras[cur:cur + 3]
            cur += 3
        plain_at = None
        if has_plain:
            plain_at = extras[cur]
            cur += 1
        pg_first = None
        if has_delta:
            pg_first = extras[cur]
            cur += 1
        if ndl:
            # definition levels are per ROW: row i's level is lane i of
            # their stream; every other lookup below is per stored value
            dl = extras[cur:cur + 5]
            cur += 5
            dl_v = R.hybrid_lookup(words, pos, *dl)
            validity = (dl_v == 1) & active
            with jax.named_scope("decode_rows"):
                j = jnp.clip(R.dense_ranks(validity), 0, cap - 1)
        else:
            validity = active
            j = None
        vr = None
        if nvr:
            vr = extras[cur:cur + 5]
            cur += 5
        dr = None
        if ndr:
            dr = extras[cur:cur + 5]
            cur += 5
        slen = None
        if has_slen:
            slen = extras[cur]
            cur += 1
        dicts = [extras[cur + i] for i in range(len(dict_shapes))]
        cur += len(dict_shapes)

        if kind == "bool":
            v = rows(R.hybrid_lookup(words, pos, *vr), j)
            data = jnp.where(validity, v != 0, False)
            outs.extend([data, validity])
            continue
        didx = None
        if vr is not None and dict_shapes:
            didx = jnp.clip(R.hybrid_lookup(words, pos, *vr),
                            0, dict_shapes[0][0][0] - 1)
        # which lanes read the dictionary: by page where a page table
        # rides, else those before the PLAIN region's first lane, else all
        on_dict = None
        if npg:
            with jax.named_scope("decode_page_lookup"):
                pg = jnp.minimum(R.run_index(dense_start, cap), npg - 1)
                pg_start = dense_start[pg]
                local = pos - pg_start
                enc_pg = pg_enc[pg]
                on_dict = enc_pg == PGE_DICT
        elif has_plain:
            on_dict = pos < plain_at[1]

        def from_dict(table, other):
            got = table[didx]
            if on_dict is None:
                return got
            return jnp.where(on_dict if got.ndim == 1 else on_dict[:, None],
                             got, other)

        if kind == "str":
            if has_slen:
                # offset+bytes model (SURVEY.md §7 c): each stored
                # value's footprint counts exactly once — offsets are a
                # per-page segmented prefix-sum over the byte
                # footprints (PLAIN values add their 4-byte length
                # prefix), then one gather builds the char matrix
                with jax.named_scope("decode_chars"):
                    lp = jnp.where(enc_pg == PGE_PLAIN_STR, 4, 0) \
                        .astype(jnp.int64)
                    is_str = (enc_pg == PGE_PLAIN_STR) \
                        | (enc_pg == PGE_DL_STR)
                    contrib = jnp.where(
                        is_str, slen.astype(jnp.int64) + lp, 0)
                    rel = R.seg_excl_cumsum(
                        contrib, jnp.clip(pg_start, 0, cap - 1))
                    plens = slen.astype(jnp.int32)
                    pchars = R.gather_chars(
                        get_bytes(), plain_byte[pg] + rel + lp, plens,
                        char_cap)
            else:
                pchars = jnp.zeros((cap, char_cap), dtype=jnp.uint8)
                plens = jnp.zeros(cap, dtype=jnp.int32)
            if didx is not None:
                with jax.named_scope("decode_dict"):
                    chars = from_dict(dicts[0], pchars)
                    lengths = from_dict(dicts[1].astype(jnp.int32), plens)
            else:
                chars, lengths = pchars, plens
            chars = jnp.where(validity[:, None], rows(chars, j), 0)
            lengths = jnp.where(validity, rows(lengths, j), 0)
            outs.extend([chars, lengths, validity])
            continue
        if kind == "dec128":
            if has_plain:
                with jax.named_scope("decode_plain"):
                    p_hi, p_lo = R.read_plain(words_plain, plain_at, cap,
                                              elem_bytes, True)
            else:
                p_hi = p_lo = jnp.zeros(cap, dtype=jnp.int64)
            if didx is not None:
                with jax.named_scope("decode_dict"):
                    hi = from_dict(dicts[0], p_hi)
                    lo = from_dict(dicts[1], p_lo)
            else:
                hi, lo = p_hi, p_lo
            hi = jnp.where(validity, rows(hi, j), 0)
            lo = jnp.where(validity, rows(lo, j), 0)
            outs.extend([hi, lo, validity])
            continue
        # fixed-width scalar kinds: select in the int64 bit domain
        if has_plain:
            with jax.named_scope("decode_plain"):
                v = R.read_plain(words_plain, plain_at, cap, elem_bytes,
                                 kind == "dec64")
        else:
            v = jnp.zeros(cap, dtype=jnp.int64)
        if has_bss:
            # BYTE_STREAM_SPLIT: byte k of value i lives at
            # page_base + k*values_in_page + i
            with jax.named_scope("decode_plain"):
                stride = jnp.clip(dense_start[pg + 1] - pg_start,
                                  0, cap)
                b_v = R.read_bss(get_bytes(), plain_byte[pg], stride,
                                 local, elem_bytes)
                v = jnp.where(enc_pg == PGE_BSS, b_v, v)
        if has_delta:
            # DELTA_BINARY_PACKED: per-value deltas from the miniblock
            # run table, reconstructed by a per-page segmented
            # prefix-sum off the page's first_value
            d_raw = R.delta_lookup(words, pos, *dr)
            with jax.named_scope("decode_delta"):
                d_contrib = jnp.where(
                    (enc_pg == PGE_DELTA) & (pos > pg_start), d_raw, 0)
                c = jnp.cumsum(d_contrib)
                d_v = pg_first[pg] \
                    + (c - c[jnp.clip(pg_start, 0, cap - 1)])
                v = jnp.where(enc_pg == PGE_DELTA, d_v, v)
        if didx is not None:
            with jax.named_scope("decode_dict"):
                v = from_dict(dicts[0], v)
        v = rows(v, j)
        if kind == "f32":
            data = jax.lax.bitcast_convert_type(
                v.astype(jnp.int32), jnp.float32)
            data = jnp.where(validity, data, jnp.float32(0))
        elif kind == "f64":
            data = jax.lax.bitcast_convert_type(v, jnp.float64)
            data = jnp.where(validity, data, jnp.float64(0))
        else:  # int / dec64: reinterpret low bits into the storage
            data = v.astype(jnp.dtype(np_dt)) if np_dt != "int64" \
                else v
            if np_dt == "int64" and elem_bytes == 4 \
                    and kind != "dec64":
                data = v.astype(jnp.int32).astype(jnp.int64)
            data = jnp.where(validity, data, 0)
        outs.extend([data, validity])
    return active, tuple(outs)


def _build_encoded_decode(layout: Tuple, cap: int) -> Callable:
    """One XLA program: packed page words + plan tables -> per-column
    (data, validity) arrays at full capacity, plus the active mask.
    The page-encoding class array (pg_enc) selects the decode lane per
    page, so dict / PLAIN / DELTA / BYTE_STREAM_SPLIT / string pages
    can mix freely inside one chunk (dictionary overflow)."""

    def fn(words, n_arr, *extras):
        return _encoded_decode_body(layout, cap, words, n_arr, extras)

    return named_jit("srt_decode", fn)


def _chain_fn(layout, cap: int, nbytes: int):
    # the row count is a DEVICE SCALAR input, not a static shape: row
    # groups of any size share one compiled program per (layout, cap,
    # bucketed-words) key
    key = ("enc", layout, cap, nbytes)
    fn = _DECODE_CACHE.get(key)
    if fn is None:
        fn = _DECODE_CACHE.put(key, _build_encoded_decode(layout, cap))
    return fn


def _finish_encoded_upload(token):
    from spark_rapids_tpu.columnar import device as D
    _tag, schema, n, cap, nbytes, layout, spec, dev, metrics = token
    fn = _chain_fn(layout, cap, nbytes)
    _trace.first_dispatch(metrics, fn)
    active, outs = fn(dev[0], dev[1], *dev[2:])
    return D.DeviceBatch(schema, D.rebuild_columns(list(spec), outs),
                         active, n)

"""Device groupBy kernel: sort-based segmented aggregation, fully static
shapes (the cuDF groupBy the reference leans on, reimagined for XLA).

Strategy (one jitted program per (expr-structure, capacity)):
  1. Encode each key column into order-preserving sub-key words
     (floats as [is_nan, nan-zeroed value] — no 64-bit float bitcasts,
     which some TPU compile stacks can't lower — strings as packed
     big-endian uint64 words from the byte matrix).
  2. ``lexsort`` rows with the batch ``active`` mask as the primary key so
     live rows are contiguous at the front.
  3. Boundary flags where any sub-key (or active flag) changes between
     adjacent sorted rows.
  4. Aggregate with SCAN primitives — prefix sums and segmented
     associative scans — and read each segment's result at its END row.

Step 4 is the TPU-critical design point: `jax.ops.segment_*` lowers to
XLA scatters, which serialize on TPU (~200ms per op at 2M rows measured
on v5e); prefix scans and sorts are fast parallel primitives. So NOTHING
here scatters: per-segment results live at segment-end rows of the
sorted layout (``out_active`` marks exactly one row per real group), and
the aggregation output batch simply uses that scattered active mask —
the engine's mask-based batch model makes "one result row per group"
free. Compaction (an argsort) happens later at shrink/shuffle points.
"""

from __future__ import annotations

import threading
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.device import (
    AnyDeviceColumn, DeviceColumn, DeviceStringColumn)
from spark_rapids_tpu.sql import types as T

_U64_MAX = jnp.uint64(0xFFFFFFFFFFFFFFFF)
_SIGN64 = jnp.uint64(0x8000000000000000)


def rank_u64(col: DeviceColumn) -> jax.Array:
    """Order-preserving uint64 encoding of NON-FLOAT fixed-width data.
    Floats use :func:`rank_words` instead — their total-order encoding
    would need a 64-bit float bitcast, which some TPU compile stacks
    (v5e X64-rewrite) cannot lower; integer bitcasts lower fine."""
    data = col.data
    assert not jnp.issubdtype(data.dtype, jnp.floating), \
        "float ranks are multi-word; use rank_words"
    if data.dtype == jnp.bool_:
        return data.astype(jnp.uint64)
    return data.astype(jnp.int64).view(jnp.uint64) ^ _SIGN64


# spark.rapids.sql.hasNans: when the user asserts NaN-free data, float
# key encodings drop their is-NaN word — one fewer radix-sort pass per
# float key in every sort/group/join program (RapidsConf HAS_NANS role,
# re-purposed as a kernel hint on this NaN-exact engine). Set at session
# start; kernel_salt() feeds the compiled-program caches so a flip never
# reuses a stale trace.
_HAS_NANS = True


def set_has_nans(v: bool) -> None:
    global _HAS_NANS
    _HAS_NANS = bool(v)


def kernel_salt() -> tuple:
    """Session-level kernel flags that compiled-program cache keys must
    include (they change traced structure, not argument shapes)."""
    return (_HAS_NANS,)


_NAN_SCOPE = threading.local()


class nan_scope:
    """Pin has_nans for the current thread while a salted program is
    (possibly) traced: the value baked into the trace then always
    matches the salt its cache key was computed with, even if another
    session flips the module global concurrently."""

    def __init__(self, value: bool):
        self.value = bool(value)

    def __enter__(self):
        self.prev = getattr(_NAN_SCOPE, "value", None)
        _NAN_SCOPE.value = self.value
        return self

    def __exit__(self, *exc):
        _NAN_SCOPE.value = self.prev
        return False


def rank_words(col: DeviceColumn,
               has_nans: Optional[bool] = None) -> List[jax.Array]:
    """Order+equality words (most significant first) whose joint
    ascending lexicographic order is Spark's total order, using only
    native-dtype comparisons: floats become [is_nan, nan-zeroed value]
    (NaN greatest + all NaNs equal; IEEE compare folds -0.0 == 0.0;
    ``+0.0`` normalizes any -0.0 so equality words match bitwise).

    ``has_nans`` resolution: explicit param (build-time snapshot) >
    thread-local nan_scope (set by salted call sites) > module global.
    Inside cached/jitted programs one of the first two MUST be in
    effect — reading only the global at trace time could disagree with
    the salt the program was cached under if another session flips the
    flag concurrently."""
    if has_nans is None:
        has_nans = getattr(_NAN_SCOPE, "value", None)
        if has_nans is None:
            has_nans = _HAS_NANS
    data = col.data
    if jnp.issubdtype(data.dtype, jnp.floating):
        zero = jnp.zeros((), data.dtype)
        if not has_nans:
            return [data + zero]  # -0.0 still normalized
        nanf = jnp.isnan(data)
        return [nanf, jnp.where(nanf, zero, data) + zero]
    return [rank_u64(col)]


def limb_words(col) -> List[jax.Array]:
    """Order+equality words for a DECIMAL128 limb column: signed-128
    lexicographic order == (sign-flipped hi, unsigned lo)."""
    return [col.hi.view(jnp.uint64) ^ _SIGN64,
            col.lo.view(jnp.uint64)]


def value_words(col: AnyDeviceColumn,
                has_nans: Optional[bool] = None) -> List[jax.Array]:
    """Comparison words for ANY column type (strings included)."""
    from spark_rapids_tpu.columnar.device import (DeviceDecimal128Column,
                                                  DeviceStructColumn)
    if isinstance(col, DeviceStringColumn):
        return pack_string_words(col) + [col.lengths.astype(jnp.uint64)]
    if isinstance(col, DeviceDecimal128Column):
        return limb_words(col)
    if isinstance(col, DeviceStructColumn):
        # field-wise words (each prefixed by its validity) order structs
        # field-major, which is also exact equality
        words: List[jax.Array] = []
        for f in col.fields:
            words.append(f.validity)
            words.extend(value_words(f, has_nans))
        return words
    return rank_words(col, has_nans)


def pack_string_words(c: DeviceStringColumn) -> List[jax.Array]:
    """Big-endian packed uint64 words: numeric word order == byte
    lexicographic order, so word-wise compare/sort matches UTF-8 binary
    order (with the lengths column as tiebreak for zero padding)."""
    from spark_rapids_tpu.ops.lanes import chars_to_u64_words
    char_cap = c.chars.shape[1]
    chars = c.chars
    if char_cap % 8:
        n_words = (char_cap + 7) // 8
        chars = jnp.pad(chars, ((0, 0), (0, 8 * n_words - char_cap)))
    return chars_to_u64_words(chars)


def grouping_subkeys(col: AnyDeviceColumn,
                     has_nans: Optional[bool] = None) -> List[jax.Array]:
    """Sub-key arrays whose joint equality == Spark group-key equality.
    Validity is included so null forms its own group; invalid slots hold
    normalized zeros so their data words tie."""
    from spark_rapids_tpu.columnar.device import (DeviceDecimal128Column,
                                                  DeviceStructColumn)
    if isinstance(col, DeviceStringColumn):
        return [col.validity, col.lengths] + pack_string_words(col)
    if isinstance(col, DeviceDecimal128Column):
        return [col.validity] + limb_words(col)
    if isinstance(col, DeviceStructColumn):
        return [col.validity] + value_words(col, has_nans)
    return [col.validity] + rank_words(col, has_nans)


def _value_word_count(col: AnyDeviceColumn, has_nans: bool) -> int:
    """``len(value_words(col))`` from the column's static shapes."""
    from spark_rapids_tpu.columnar.device import (DeviceDecimal128Column,
                                                  DeviceStructColumn)
    if isinstance(col, DeviceStringColumn):
        return -(-col.chars.shape[1] // 8) + 1
    if isinstance(col, DeviceDecimal128Column):
        return 2
    if isinstance(col, DeviceStructColumn):
        return sum(1 + _value_word_count(f, has_nans) for f in col.fields)
    floating = jnp.issubdtype(col.data.dtype, jnp.floating)
    return 2 if floating and has_nans else 1


def key_word_count(key_cols: Sequence[AnyDeviceColumn],
                   has_nans: bool) -> int:
    """The group-key words a row sorts or gathers:
    ``len(grouping_subkeys(c))`` summed over the keys, from their static
    shapes alone (a string key: validity, length and ``char_cap/8``
    packed words)."""
    return sum(1 + _value_word_count(c, has_nans) for c in key_cols)


def word_sentinel(dtype, is_min: bool):
    """A value no real candidate beats: the loser for this word dtype."""
    if dtype == jnp.bool_:
        return jnp.array(is_min, dtype=jnp.bool_)
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf if is_min else -jnp.inf, dtype=dtype)
    if dtype == jnp.uint64:
        return _U64_MAX if is_min else jnp.uint64(0)
    info = jnp.iinfo(dtype)
    return jnp.array(info.max if is_min else info.min, dtype=dtype)


def seg_scan_best(seg_marker: jax.Array, words: Sequence[jax.Array],
                  valid: jax.Array, is_min: bool
                  ) -> Tuple[jax.Array, jax.Array]:
    """Segmented RUNNING arg-min/max over multi-word ranks: for each
    sorted row, the position of the best valid row from its segment's
    start up to itself (lexicographic over `words`, most-significant
    first). Returns (winner position, has-winner). One associative scan
    — no scatters. ``seg_marker`` is any per-row value constant within a
    segment and distinct across adjacent segments (e.g. the segment's
    start position)."""
    cap = seg_marker.shape[0]
    pos = jnp.arange(cap, dtype=jnp.int32)

    def combine(a, b):
        a_id, a_valid, a_p = a[0], a[1], a[2]
        b_id, b_valid, b_p = b[0], b[1], b[2]
        aw, bw = a[3:], b[3:]
        same = b_id == a_id
        a_live = a_valid & same
        better = jnp.zeros_like(a_valid)
        eq = jnp.ones_like(a_valid)
        for wa, wb in zip(aw, bw):
            c = (wa < wb) if is_min else (wa > wb)
            better = better | (eq & c)
            eq = eq & (wa == wb)
        take_a = a_live & ((~b_valid) | better)
        out = [b_id, a_live | b_valid, jnp.where(take_a, a_p, b_p)]
        out += [jnp.where(take_a, wa, wb) for wa, wb in zip(aw, bw)]
        return tuple(out)

    res = jax.lax.associative_scan(
        combine, tuple([seg_marker, valid, pos] + list(words)))
    return res[2], res[1]


class Segments:
    """Sorted-row-space segmentation. Aggregates read their per-segment
    result at the segment's END row; ``out_active`` marks those rows.
    ``payload`` holds the caller's arrays co-permuted by the SAME sort.
    ``start_of_row``/``end_of_row``/``seg_ids`` are computed lazily —
    each is a fusion-breaking scan this backend pays ~25-40ms for, so
    programs that never touch them never emit them."""

    def __init__(self, order, active_sorted, boundary, is_end,
                 capacity: int, payload: Tuple[jax.Array, ...] = ()):
        self.order = order                  # sorted pos -> original row
        self.active_sorted = active_sorted
        self.boundary = boundary            # first row of its segment
        self.is_end = is_end                # last row of its segment
        self.capacity = capacity
        self.out_active = is_end & active_sorted
        self.payload = payload              # co-sorted caller arrays
        self._start = None
        self._end = None
        self._seg_ids = None

    @property
    def start_of_row(self):
        """Own segment's first sorted position, per row."""
        if self._start is None:
            pos = jnp.arange(self.capacity, dtype=jnp.int32)
            self._start = jax.lax.cummax(
                jnp.where(self.boundary, pos, -1))
        return self._start

    @property
    def end_of_row(self):
        """Own segment's last sorted position (inclusive), per row."""
        if self._end is None:
            pos = jnp.arange(self.capacity, dtype=jnp.int32)
            self._end = jnp.flip(jax.lax.cummin(
                jnp.flip(jnp.where(self.is_end, pos, self.capacity))))
        return self._end

    @property
    def seg_ids(self):
        """Dense segment id per sorted row."""
        if self._seg_ids is None:
            self._seg_ids = jnp.cumsum(
                self.boundary.astype(jnp.int32)) - 1
        return self._seg_ids


def _boundaries_from_words(sorted_keys: Sequence[jax.Array],
                           active_s: jax.Array, cap: int):
    prev_differs = jnp.zeros(cap, dtype=bool)
    for k in sorted_keys:
        d = k[1:] != k[:-1]
        prev_differs = prev_differs.at[1:].set(prev_differs[1:] | d)
    prev_differs = prev_differs.at[1:].set(
        prev_differs[1:] | (active_s[1:] != active_s[:-1]))
    boundary = prev_differs.at[0].set(True)
    is_end = jnp.concatenate([boundary[1:], jnp.ones(1, dtype=bool)])
    return boundary, is_end


# Named scopes (jax.named_scope, metadata only): every HLO operation of
# an aggregate program says in its op_name which STEP it belongs to —
# groupby_sort (the segment sort + payload gather), groupby_reduce (the
# segmented scans) — so a device trace totals by step
# (tools trace <profile dir>; columnar.device._compact_body is
# "compact").
@jax.named_scope("groupby_sort")
def build_segments(key_cols: Sequence[AnyDeviceColumn],
                   active: jax.Array,
                   payload: Sequence[jax.Array] = (),
                   has_nans: Optional[bool] = None) -> Segments:
    cap = active.shape[0]
    subkeys: List[jax.Array] = []
    for c in key_cols:
        subkeys.extend(grouping_subkeys(c, has_nans))
    from spark_rapids_tpu.columnar.device import sort_with_payload
    # ONE multi-operand sort: ~active primary (live rows first), then the
    # sub-keys (row index appended by sort_with_payload = stable), with
    # the caller's payload co-permuted for free.
    sorted_keys_all, order, payload_sorted = sort_with_payload(
        [~active] + subkeys, payload)
    active_s = ~sorted_keys_all[0]
    sorted_keys = sorted_keys_all[1:]
    boundary, is_end = _boundaries_from_words(sorted_keys, active_s, cap)
    return Segments(order, active_s, boundary, is_end, cap,
                    tuple(payload_sorted))


_FNV64 = jnp.uint64(0xcbf29ce484222325)
_PRIME64 = jnp.uint64(0x00000100000001B3)
_MIX64 = jnp.uint64(0x9E3779B97F4A7C15)


def _hash_word_u64(w: jax.Array) -> jax.Array:
    """Deterministic u64 image of one equality word. Equal words MUST
    map equal; collisions only fragment groups (harmless for partial
    aggregates — see build_segments_hashed)."""
    if w.dtype == jnp.bool_:
        return w.astype(jnp.uint64)
    if w.dtype == jnp.uint64:
        return w
    if w.dtype == jnp.float32:
        from spark_rapids_tpu.ops.lanes import _as_u64_bits
        return _as_u64_bits(w)
    if w.dtype == jnp.float64:
        # no 64-bit float bitcast on this stack: build a value image
        # from integer conversions (saturating, deterministic; equal
        # values -> equal images)
        a = w.astype(jnp.int64)
        b = (w * jnp.float64(65536.0)).astype(jnp.int64)
        return (a.view(jnp.uint64) * _MIX64) ^ b.view(jnp.uint64)
    return w.astype(jnp.int64).view(jnp.uint64)


def hash_subkey_words(words: Sequence[jax.Array]) -> jax.Array:
    """FNV-style fold of equality words into one u64 (elementwise —
    fuses into neighbouring ops)."""
    h = jnp.full(words[0].shape, _FNV64, dtype=jnp.uint64)
    for w in words:
        h = (h ^ _hash_word_u64(w)) * _PRIME64
    h = h ^ (h >> jnp.uint64(29))
    h = h * _MIX64
    h = h ^ (h >> jnp.uint64(32))
    return h


@jax.named_scope("groupby_sort")
def build_segments_hashed(key_cols: Sequence[AnyDeviceColumn],
                          active: jax.Array,
                          payload: Sequence[jax.Array] = (),
                          has_nans: Optional[bool] = None,
                          sorted_keys_from_payload=None) -> Segments:
    """Hash-sorted segmentation: ONE radix pass (63-bit key hash with
    the inactive flag on top) instead of one pass per subkey word, then
    exact boundaries from the co-gathered REAL key words.

    Hash collisions between different keys can interleave their rows
    within a hash run, FRAGMENTING a group into several segments — but
    never merge two groups (boundaries compare the real words).
    Fragmented partial aggregates are correct by construction: the
    merge/final stage re-groups them. Use ONLY where duplicate group
    rows are acceptable (partial/merge modes); final/complete must use
    the exact :func:`build_segments`."""
    cap = active.shape[0]
    subkeys: List[jax.Array] = []
    for c in key_cols:
        subkeys.extend(grouping_subkeys(c, has_nans))
    if subkeys:
        h = hash_subkey_words(subkeys) >> jnp.uint64(1)
    else:  # global aggregate: one segment, sort only compacts live rows
        h = jnp.zeros(cap, dtype=jnp.uint64)
    word = jnp.where(active, h, jnp.uint64(0xFFFFFFFFFFFFFFFF))
    pos = jnp.arange(cap, dtype=jnp.int32)
    _sw, order = jax.lax.sort((word, pos), num_keys=1, is_stable=True)
    from spark_rapids_tpu.ops.lanes import fused_take
    if sorted_keys_from_payload is not None:
        # the key columns already ride the payload: recompute their
        # equality words AFTER the gather (elementwise, fuses) instead of
        # widening the lane matrix with a second copy of the keys
        gathered = fused_take(list(payload) + [active], order)
        payload_sorted = gathered[:-1]
        active_s = gathered[-1]
        sorted_keys = []
        for c in sorted_keys_from_payload(payload_sorted):
            sorted_keys.extend(grouping_subkeys(c, has_nans))
    else:
        gathered = fused_take(list(payload) + subkeys + [active], order)
        payload_sorted = gathered[:len(payload)]
        sorted_keys = gathered[len(payload):-1]
        active_s = gathered[-1]
    boundary, is_end = _boundaries_from_words(sorted_keys, active_s, cap)
    return Segments(order, active_s, boundary, is_end, cap,
                    tuple(payload_sorted))


def seg_running_sum(seg_marker: jax.Array, x: jax.Array) -> jax.Array:
    """Segmented inclusive running sum via one associative scan (resets
    at marker changes). Used for FLOATS, where the global-cumsum-
    difference trick suffers catastrophic cancellation contaminated by
    unrelated preceding segments."""
    def combine(a, b):
        a_id, a_v = a
        b_id, b_v = b
        same = b_id == a_id
        return (b_id, jnp.where(same, a_v + b_v, b_v))
    _ids, run = jax.lax.associative_scan(combine, (seg_marker, x))
    return run


def prefix_total(seg: Segments, x: jax.Array) -> jax.Array:
    """Per-row running total restarting at segment starts; at END rows
    this is the segment total (the scatter-free segment_sum)."""
    if jnp.issubdtype(x.dtype, jnp.floating):
        return seg_running_sum(seg.start_of_row, x)
    pp = jnp.cumsum(x)
    base = jnp.where(seg.start_of_row > 0,
                     jnp.take(pp, jnp.maximum(seg.start_of_row - 1, 0)),
                     jnp.zeros((), x.dtype))
    return pp - base


@jax.named_scope("groupby_reduce")
def seg_sum(seg: Segments, col_s: AnyDeviceColumn, out_type: T.DataType,
            null_when_empty: bool):
    """sum / sum_nonnull primitive. ``col_s`` is ALREADY in sorted row
    space (ride it through build_segments' payload)."""
    from spark_rapids_tpu.columnar.device import storage_jnp_dtype
    valid_s = col_s.validity & seg.active_sorted
    if T.is_limb_decimal(out_type):
        return _seg_sum_limb(seg, col_s, valid_s, out_type,
                             null_when_empty)
    acc_dt = storage_jnp_dtype(out_type)
    vals = jnp.where(valid_s, col_s.data.astype(acc_dt),
                     jnp.zeros((), acc_dt))
    run = prefix_total(seg, vals)
    if null_when_empty:
        has = prefix_total(seg, valid_s.astype(jnp.int64)) > 0
        validity = has & seg.out_active
    else:
        validity = seg.out_active
    return DeviceColumn(out_type, jnp.where(validity, run,
                                            jnp.zeros((), acc_dt)),
                        validity)


def _seg_sum_limb(seg: Segments, col_s: AnyDeviceColumn, valid_s,
                  out_type: T.DecimalType, null_when_empty: bool):
    """DECIMAL128 segment sum: scan four 32-bit parts (each part total
    fits int64 below 2^31 rows), recombine in 128-bit limbs, then apply
    the Spark Sum overflow rule (null past 10^precision; like the
    reference's DECIMAL128 sums this is exact while the true total stays
    within 128 bits)."""
    from spark_rapids_tpu.columnar.device import (DeviceColumn as DC,
                                                  DeviceDecimal128Column)
    from spark_rapids_tpu.ops import int128 as I
    if isinstance(col_s, DeviceDecimal128Column):
        hi, lo = col_s.hi, col_s.lo
    else:  # <=18-digit input accumulating into a wide buffer
        hi, lo = I.from_i64(jnp, col_s.data.astype(jnp.int64))
    z = jnp.int64(0)
    hi = jnp.where(valid_s, hi, z)
    lo = jnp.where(valid_s, lo, z)
    ulo = lo.view(jnp.uint64)
    m32 = jnp.uint64(0xFFFFFFFF)
    parts = [
        (ulo & m32).astype(jnp.int64),
        (ulo >> jnp.uint64(32)).astype(jnp.int64),
        (hi.view(jnp.uint64) & m32).astype(jnp.int64),
        hi >> jnp.int64(32),  # signed top part
    ]
    sums = [prefix_total(seg, p) for p in parts]
    # recombine: ((s3<<32 + s2) << 64) + s1<<32 + s0, exact mod 2^128
    rhi, rlo = I.from_i64(jnp, sums[0])
    h1, l1 = I.mul_i64(jnp, sums[1], jnp.full_like(sums[1], 1 << 32))
    rhi, rlo = I.add(jnp, rhi, rlo, h1, l1)
    rhi = rhi + sums[2] + (sums[3] << jnp.int64(32))
    ok = I.fits_precision(jnp, rhi, rlo, out_type.precision)
    if null_when_empty:
        has = prefix_total(seg, valid_s.astype(jnp.int64)) > 0
        validity = has & seg.out_active & ok
    else:
        validity = seg.out_active & ok
    rhi = jnp.where(validity, rhi, z)
    rlo = jnp.where(validity, rlo, z)
    return DeviceDecimal128Column(out_type, rhi, rlo, validity)


@jax.named_scope("groupby_reduce")
def seg_sums_batched(seg: Segments, entries, has_nans=None):
    """All of a program's sum/count-family aggregates in ONE pass: every
    slot contributes int64 lanes to a single ``(cap, P)`` matrix (one
    cumsum + one base gather) and float slots to a single f64 matrix
    (one segmented associative scan). Replaces per-slot seg_sum/seg_count
    chains — each separate cumsum/gather costs a flat ~25-40ms on this
    backend regardless of width, so lane-batching is a near-P-fold win.

    ``entries``: list of ``(col_s, kind, out_type)`` with ``kind`` in
    {"count", "sum", "sum_nonnull"}; ``col_s`` already in sorted row
    space. Returns one device column per entry (same semantics as
    seg_count / seg_sum)."""
    from spark_rapids_tpu.columnar.device import (
        DeviceColumn as DC, DeviceDecimal128Column, storage_jnp_dtype)
    from spark_rapids_tpu.ops import int128 as I
    if not entries:
        return []
    ilanes: List[jax.Array] = []
    flanes: List[jax.Array] = []
    specs: List[Tuple] = []
    m32 = jnp.uint64(0xFFFFFFFF)
    z64 = jnp.int64(0)
    lane_of: dict = {}  # (id(array), tag) -> existing lane index

    def _ilane(arr, tag, a) -> int:
        key = (id(arr), tag)
        li = lane_of.get(key)
        if li is None:
            li = len(ilanes)
            ilanes.append(a)
            lane_of[key] = li
        return li

    for col, kind, out_type in entries:
        valid = col.validity & seg.active_sorted
        if kind == "count":
            specs.append(("count",
                          _ilane(col.validity, "valid",
                                 valid.astype(jnp.int64))))
            continue
        nwe = kind == "sum"  # null_when_empty
        has_lane = None
        if nwe:
            has_lane = _ilane(col.validity, "valid",
                              valid.astype(jnp.int64))
        if T.is_limb_decimal(out_type):
            if isinstance(col, DeviceDecimal128Column):
                hi, lo = col.hi, col.lo
            else:
                hi, lo = I.from_i64(jnp, col.data.astype(jnp.int64))
            hi = jnp.where(valid, hi, z64)
            lo = jnp.where(valid, lo, z64)
            ulo = lo.view(jnp.uint64)
            l0 = _ilane(col, "dec0", (ulo & m32).astype(jnp.int64))
            l1 = _ilane(col, "dec1",
                        (ulo >> jnp.uint64(32)).astype(jnp.int64))
            # hi accumulates with int64 wraparound == mod-2^128 on the
            # high limb (carries from lo re-added at recombine)
            lh = _ilane(col, "dechi", hi)
            specs.append(("dec", (l0, l1, lh), has_lane, out_type))
        elif jnp.issubdtype(storage_jnp_dtype(out_type), jnp.floating):
            key = (id(col), "fval")
            fl = lane_of.get(key)
            if fl is None:
                fl = len(flanes)
                flanes.append(jnp.where(
                    valid, col.data.astype(jnp.float64),
                    jnp.float64(0.0)))
                lane_of[key] = fl
            specs.append(("float", fl, has_lane, out_type))
        else:
            specs.append(("int",
                          _ilane(col, "ival",
                                 jnp.where(valid,
                                           col.data.astype(jnp.int64),
                                           z64)),
                          has_lane, out_type))
    start = seg.start_of_row
    itot = None
    if ilanes:
        imat = (jnp.stack(ilanes, axis=1) if len(ilanes) > 1
                else ilanes[0][:, None])
        pp = jnp.cumsum(imat, axis=0)
        base = jnp.where((start > 0)[:, None],
                         jnp.take(pp, jnp.maximum(start - 1, 0), axis=0),
                         z64)
        itot = pp - base
    ftot = None
    if flanes:
        fmat = (jnp.stack(flanes, axis=1) if len(flanes) > 1
                else flanes[0][:, None])

        def combine(a, b):
            a_id, a_v = a
            b_id, b_v = b
            same = b_id == a_id
            return (b_id, jnp.where(same[:, None], a_v + b_v, b_v))
        _ids, ftot = jax.lax.associative_scan(combine, (start, fmat))
    out = []
    out_active = seg.out_active
    for spec in specs:
        if spec[0] == "count":
            run = itot[:, spec[1]]
            out.append(DC(T.LongT, jnp.where(out_active, run, z64),
                          out_active))
            continue
        kind, lane, has_lane, out_type = spec
        validity = out_active
        if has_lane is not None:
            validity = validity & (itot[:, has_lane] > 0)
        if kind == "dec":
            l0, l1, lh = lane
            s0, s1, shi = itot[:, l0], itot[:, l1], itot[:, lh]
            rhi, rlo = I.from_i64(jnp, s0)
            h1, l1 = I.mul_i64(jnp, s1, jnp.full_like(s1, 1 << 32))
            rhi, rlo = I.add(jnp, rhi, rlo, h1, l1)
            rhi = rhi + shi
            ok = I.fits_precision(jnp, rhi, rlo, out_type.precision)
            validity = validity & ok
            rhi = jnp.where(validity, rhi, z64)
            rlo = jnp.where(validity, rlo, z64)
            out.append(DeviceDecimal128Column(out_type, rhi, rlo, validity))
        elif kind == "float":
            run = ftot[:, lane]
            acc = storage_jnp_dtype(out_type)
            out.append(DC(out_type,
                          jnp.where(validity, run.astype(acc),
                                    jnp.zeros((), acc)), validity))
        else:
            run = itot[:, lane]
            acc = storage_jnp_dtype(out_type)
            out.append(DC(out_type,
                          jnp.where(validity, run.astype(acc),
                                    jnp.zeros((), acc)), validity))
    return out


@jax.named_scope("groupby_reduce")
def seg_count(seg: Segments, col_s: AnyDeviceColumn) -> DeviceColumn:
    valid_s = col_s.validity & seg.active_sorted
    run = prefix_total(seg, valid_s.astype(jnp.int64))
    validity = seg.out_active
    return DeviceColumn(T.LongT, jnp.where(validity, run, jnp.int64(0)),
                        validity)


def _winner_gather(seg: Segments, col_s: AnyDeviceColumn,
                   win_pos: jax.Array, won: jax.Array) -> AnyDeviceColumn:
    """Gather the winning SORTED position's row from the sorted column;
    `won` marks rows with a winner (others -> null)."""
    from spark_rapids_tpu.columnar.device import take_columns
    safe = jnp.clip(win_pos, 0, seg.capacity - 1)
    return take_columns([col_s], safe, valid_at=won)[0]


@jax.named_scope("groupby_reduce")
def seg_extreme(seg: Segments, col_s: AnyDeviceColumn, is_min: bool,
                has_nans: Optional[bool] = None) -> AnyDeviceColumn:
    """min/max by winning-row-position so values round-trip untouched."""
    valid_s = col_s.validity & seg.active_sorted
    words = value_words(col_s, has_nans)
    win, has = seg_scan_best(seg.start_of_row, words, valid_s, is_min)
    won = has & seg.out_active
    return _winner_gather(seg, col_s, win, won)


@jax.named_scope("groupby_reduce")
def seg_first_last(seg: Segments, col_s: AnyDeviceColumn, is_first: bool,
                   ignore_nulls: bool) -> AnyDeviceColumn:
    """first/last by original row order (Spark First/Last semantics).
    ignore_nulls=False ("_any" prims) takes the first/last *row* and
    keeps its null-ness."""
    eligible = seg.active_sorted
    if ignore_nulls:
        eligible = eligible & col_s.validity
    # rank = original row index (+1 so the uint encoding has no 0 tie)
    orig_rank = (seg.order.astype(jnp.int64) + 1).astype(jnp.uint64)
    win, has = seg_scan_best(seg.start_of_row, [orig_rank], eligible,
                             is_min=is_first)
    won = has & seg.out_active
    # _winner_gather keeps the winning row's own validity, which is what
    # ignore_nulls=False needs (null first-row -> null result)
    return _winner_gather(seg, col_s, win, won)

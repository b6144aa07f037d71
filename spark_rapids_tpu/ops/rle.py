"""Device-side Parquet page-decode kernels (XLA, static shapes).

The reference decodes Parquet pages on the GPU inside cuDF
(gpu_decode_page_data / rle_stream in cudf's parquet reader); these are
the TPU twins, built from gathers and elementwise bit math so XLA can
fuse them into ONE decode program per scan batch:

- ``hybrid_lookup``: positional decode of the RLE/bit-packed hybrid
  stream (dictionary indices, definition levels). The *run headers* are
  parsed on the host (they are a few bytes per run); the *payload* —
  every packed value — is extracted here, on device, from the raw page
  bytes. Every lookup is positional — lane i of ``arange(cap)`` wants
  the run (or page) that covers dense position i — so a lane's page is
  found by ``run_index`` (mark each entry's first lane once, take one
  prefix sum) and the fields of its run reach it by ``step_fields``
  (mark each field's step at the run's first lane, take one prefix
  sum): fields by prefix sum. No per-lane search, no loop, no gather
  through a run's index. The lane then either keeps the run's RLE
  value or bit-gathers from the packed words.
- ``read_packed``: a bit-packed value of width <= 32 at any bit offset
  lies inside two ALIGNED 32-bit words (phase <= 31, 31 + 32 <= 64
  bits), so a lane reads two elements of the int32 staging words
  themselves and shifts them together in unsigned 32-bit lanes; the
  staging buffer is not expanded to bytes for it (PERF.md §6, PR 33:
  on the chip a gather costs by the element gathered).
- ``read_plain``: PLAIN fixed-width values (INT32/INT64/FLOAT/DOUBLE
  little-endian; FIXED_LEN_BYTE_ARRAY decimals big-endian, 1..16
  bytes) with no gather. The host guarantees what makes that
  possible (``io/device_decode._plan_column``): a chunk's PLAIN value
  sections lie end to end in the staging buffer, 4-aligned at the
  start, in dense order, so value k is at byte ``base + k * W`` — a
  contiguous window at a fixed stride. One ``dynamic_slice`` takes the
  window, strided slices de-interleave it, a second ``dynamic_slice``
  moves the values to their first dense lane (PERF.md §6, PR 35).
- ``gather_char_words``: a string value's bytes lie end to end from an
  arbitrary byte, so a value of at most ``char_cap`` bytes lies in
  ``char_cap/4 + 1`` consecutive staging words: a row gathers that one
  window of words, shifts it into place by the value's phase and
  reinterprets the words as bytes, in place of one gathered byte a
  lane (PERF.md §6).

All functions are shape-polymorphic trace-time helpers. The bit-packed
readers (``read_packed``, ``read_packed64``, ``hybrid_lookup``,
``delta_lookup``) take the packed int32 staging ``words`` and int64
BIT offsets into them, ``read_plain`` the same ``words`` and the
region's word index, ``gather_char_words`` the same ``words`` and
int64 BYTE offsets; only ``read_bss`` takes the byte array, an int32
array of one byte per element (the form ``bytes_of_words`` produces
from the staging words), and int64 byte offsets. All return int64
values but ``gather_char_words``, whose uint8 character matrix is
masked to each value's length. Callers mask invalid lanes afterwards;
out-of-range offsets are clipped, never trapped.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def bytes_of_words(words: jax.Array) -> jax.Array:
    """int32 staging words -> int32 byte array (little-endian order)."""
    shifts = jnp.arange(4, dtype=jnp.int32) * 8
    return ((words[:, None] >> shifts) & 0xFF).reshape(-1)


def read_packed(words: jax.Array, bit_off: jax.Array,
                width: jax.Array) -> jax.Array:
    """Extract ``width``-bit little-endian values at arbitrary bit
    offsets into the int32 staging ``words`` (the Parquet bit-packed
    layout). width may vary per lane (dictionary index width differs
    across pages); width <= 32, and width == 0 reads 0 (an RLE run
    reads zero packed bits: ``hybrid_lookup`` relies on it).

    The value starts at phase ``s = bit_off & 31`` of word
    ``bit_off >> 5`` and ends inside the next one, so two aligned
    words a lane hold it: ``(w0 >> s) | (w1 << (32 - s))`` in unsigned
    32-bit (the staging words are int32 and may be negative: the
    shifts are logical). The word index goes down to int32, exact for
    staging buffers up to 8 GiB; an index outside the buffer is
    clipped, so a lane whose value starts before the buffer or runs
    past it reads garbage the caller masks."""
    nw = words.shape[0]
    i0 = (bit_off >> 5).astype(jnp.int32)
    s = (bit_off & 31).astype(jnp.uint32)
    with jax.named_scope("window"):
        w0 = jax.lax.bitcast_convert_type(
            words[jnp.clip(i0, 0, nw - 1)], jnp.uint32)
        w1 = jax.lax.bitcast_convert_type(
            words[jnp.clip(i0 + 1, 0, nw - 1)], jnp.uint32)
        # w1 << (32 - s) as two shifts: a shift by 32 (s == 0) is not
        # defined, 31 then 1 is, and leaves the 0 that phase wants
        v = (w0 >> s) | ((w1 << (31 - s)) << 1)
    w = width.astype(jnp.uint32)
    # width 32 is all ones: 1 << 32 does not fit the lane
    mask = jnp.where(w >= 32, jnp.uint32(0xFFFFFFFF),
                     (jnp.uint32(1) << w) - 1)
    return (v & mask).astype(jnp.int64)


def run_index(out_start: jax.Array, cap: int) -> jax.Array:
    """Lane i of ``arange(cap)`` -> index of the last entry of the
    ascending table ``out_start`` whose start is <= i (int32, clipped
    to the table): ``searchsorted(out_start, i, side="right") - 1``
    for every lane at once. Because the query is the lane number
    itself, the count of entries <= i is a prefix sum: scatter a one at
    each entry's start lane (starts >= cap, the padding sentinel among
    them, land in a spare lane that is cut off), cumsum, subtract 1.
    Duplicate starts (empty runs or pages) add up on one lane, so the
    LAST of them wins, as it does in the search."""
    lanes = jnp.clip(out_start, 0, cap).astype(jnp.int32)
    marks = jnp.zeros(cap + 1, dtype=jnp.int32).at[lanes].add(1)
    return jnp.clip(jnp.cumsum(marks[:cap]) - 1, 0,
                    out_start.shape[0] - 1)


def step_fields(out_start: jax.Array, cap: int, *fields: jax.Array
                ) -> tuple:
    """Each field (int32 or int64) of an ascending table, broadcast to
    the lanes its entries cover: lane i of ``arange(cap)`` gets
    ``field[r]`` for ``r = run_index(out_start, cap)[i]`` — without a
    gather. A field read through ``r`` is a step function of the lane,
    so scatter each entry's step (``field[r] - field[r - 1]``) at the
    entry's start lane and take one prefix sum: the steps up to a lane
    telescope to the field of the last entry that starts at or before
    it. Entry 0 carries ``field[0]`` and lands on lane 0, so lanes
    before the first start read entry 0, as the clipped index makes
    them; duplicate starts add up on one lane and the last of them
    wins; starts >= cap, the padding sentinel among them, fall into a
    spare lane that is cut off. Differences may wrap: addition is
    modular and the prefix sum undoes it exactly.

    All fields ride ONE scatter and ONE ``cumsum`` of int32 rows
    (lanes minor). An int64 field rides as its two 32-bit halves, each
    a step function of its own, rejoined per lane: exact for any
    int64, and the chip (which has no 64-bit lanes) sums two int32
    rows in under half the time of one int64 row (PERF.md §6, PR
    31)."""
    rows = []
    for f in fields:
        rows.append(f.astype(jnp.int32))
        if f.dtype == jnp.int64:
            rows.append((f >> 32).astype(jnp.int32))
    tab = jnp.stack(rows)
    steps = jnp.concatenate([tab[:, :1], tab[:, 1:] - tab[:, :-1]], axis=1)
    lanes = jnp.concatenate([
        jnp.zeros(1, dtype=jnp.int32),
        jnp.clip(out_start[1:], 0, cap).astype(jnp.int32)])
    buf = jnp.zeros((len(rows), cap + 1), jnp.int32).at[:, lanes].add(steps)
    got = iter(jnp.cumsum(buf[:, :cap], axis=1))
    outs = []
    for f in fields:
        lo = next(got)
        if f.dtype == jnp.int64:
            lo = (lo.astype(jnp.int64) & 0xFFFFFFFF) \
                | (next(got).astype(jnp.int64) << 32)
        outs.append(lo)
    return tuple(outs)


def _run_fields(pos: jax.Array, out_start: jax.Array,
                bit_start: jax.Array, width: jax.Array,
                value: jax.Array) -> tuple:
    """What a lane needs of its run: ``(bit_off, w, v)`` — the absolute
    bit offset of the lane's packed value (the run's payload start
    plus the lane's place in the run times the run's width), that
    width and the run's value. All reach the lanes by prefix sum
    (``step_fields``), none by a gather.
    ``bit_start[r] + (pos - out_start[r]) * w[r]`` is
    ``base[r] + pos * w[r]`` with ``base = bit_start - out_start * w``
    folded once on the table: two fields broadcast for three. The
    width rides as one int32 row (a Parquet bit width is at most 64),
    base and value as an int64's two each. Shared by the hybrid and
    the delta lookup, whose run tables have one shape."""
    with jax.named_scope("decode_bits/run_fields"):
        base, w, v = step_fields(
            out_start, pos.shape[0], bit_start - out_start * width,
            width.astype(jnp.int32), value)
        w = w.astype(jnp.int64)
        return base + pos * w, w, v


def hybrid_lookup(words: jax.Array, pos: jax.Array,
                  out_start: jax.Array, packed: jax.Array,
                  value: jax.Array, bit_start: jax.Array,
                  width: jax.Array) -> jax.Array:
    """Decode the RLE/bit-packed hybrid stream at every dense position:
    ``pos`` is ``arange(cap)`` (int64), the lanes of the stream.

    The run table (out_start ascending, padded with a huge sentinel;
    packed flag; RLE value; absolute payload bit offset; per-run bit
    width) comes from the host-side header parse; its fields reach the
    lanes by prefix sum (``step_fields``). The packed flag is folded
    into the other fields on the table: an RLE run reads zero packed
    bits (width 0 unpacks to 0) and keeps its value, a packed run
    keeps its width and no value, so a lane ORs the two with nothing
    to select. Positions beyond the last real run decode garbage —
    callers mask by validity/active."""
    bit_off, w, rle_value = _run_fields(
        pos, out_start, bit_start, jnp.where(packed, width, 0),
        jnp.where(packed, 0, value))
    with jax.named_scope("decode_bits"):
        return read_packed(words, bit_off, w) | rle_value


def read_packed64(words: jax.Array, bit_off: jax.Array,
                  width: jax.Array) -> jax.Array:
    """``read_packed`` for widths up to 64 (DELTA_BINARY_PACKED
    miniblocks store deltas at any width): the value is assembled from
    two <=32-bit reads so every intermediate fits an int64 without
    shift overflow. width may vary per lane; width == 0 reads 0."""
    w = width.astype(jnp.int64)
    lo = read_packed(words, bit_off, jnp.minimum(w, 32))
    hi = read_packed(words, bit_off + 32, jnp.maximum(w - 32, 0))
    return lo | (hi << 32)


def delta_lookup(words: jax.Array, pos: jax.Array,
                 out_start: jax.Array, packed: jax.Array,
                 value: jax.Array, bit_start: jax.Array,
                 width: jax.Array) -> jax.Array:
    """Per-lane DELTA_BINARY_PACKED delta: the run table is one entry
    per miniblock (out_start = dense lane of the miniblock's first
    delta, value = the block's min_delta, bit_start = absolute payload
    bit offset, width = miniblock bit width). Lane ``pos`` returns
    min_delta + unpacked[pos - out_start], for ``pos = arange(cap)``;
    positions outside any run (a page's first value, other-encoding
    pages) decode garbage — callers mask before the segmented
    cumsum."""
    bit_off, w, min_delta = _run_fields(pos, out_start, bit_start, width,
                                        value)
    with jax.named_scope("decode_bits"):
        raw = read_packed64(words, bit_off, w)
        return min_delta + raw


def read_bss(bytes_all: jax.Array, base: jax.Array, stride: jax.Array,
             local: jax.Array, nbytes: int) -> jax.Array:
    """BYTE_STREAM_SPLIT reinterpret: a page's value section holds
    ``stride`` (= values-in-page) copies of byte 0, then byte 1, ...;
    value ``local`` gathers byte j at base + j*stride + local and
    assembles little-endian into an int64 (zero-extended)."""
    nb = bytes_all.shape[0]
    k = jnp.arange(nbytes, dtype=jnp.int64)
    idx = base[:, None] + k[None, :] * stride[:, None] + local[:, None]
    win = bytes_all[jnp.clip(idx, 0, nb - 1)].astype(jnp.int64)
    return jnp.sum(win << (k * 8), axis=1)


def char_window_words(char_cap: int) -> int:
    """Words a string value of at most ``char_cap`` bytes lies in,
    counted from the word of its first byte: ``gather_char_words``
    gathers this many a row."""
    return char_cap // 4 + 1


def gather_char_words(words: jax.Array, starts: jax.Array,
                      lengths: jax.Array, char_cap: int) -> jax.Array:
    """Variable bytes -> (n, char_cap) uint8 matrix: row i holds the
    lengths[i] bytes at byte starts[i] of the int32 staging ``words``,
    zero-padded (the SURVEY offset+bytes string model's gather half;
    offsets come from a segmented prefix-sum over the lengths).

    A value of L <= char_cap bytes at byte b lies in the
    ``char_window_words(char_cap)`` words from ``b >> 2`` on, so a row
    gathers that window's words (a quarter of the elements of one
    gather a byte lane, and the buffer is never expanded to bytes),
    shifts each pair of neighbours together by the row's phase
    ``8 * (b & 3)`` in unsigned 32-bit lanes (the staging words are
    int32: an arithmetic shift would smear the sign of a byte >= 0x80
    into the next), and reinterprets the words as their bytes. On the
    chip the element-wise gather of the window, rows minor, is the
    fastest spelling: 63 ms for Q13's u8[393,216, 80] against 280 ms a
    byte lane; ``lax.gather`` of one contiguous slice a row takes
    530 ms and the transposed form 72 ms (PERF.md §6).

    The word index goes down to int32 once, exact for staging buffers
    up to 8 GiB. Each word of a window is clipped to the buffer on its
    own, so the buffer needs no padding: a value lies inside the
    buffer, and a word past its end feeds only the lanes past the
    value's length, which are masked (a window clamped as a whole, as
    ``dynamic_slice`` or a sliced gather clamps it, would shift every
    byte of its row). Rows whose start lies outside the buffer read
    garbage the caller masks."""
    n = starts.shape[0]
    nw = char_window_words(char_cap)
    w0 = (starts >> 2).astype(jnp.int32)
    s = ((starts & 3) * 8).astype(jnp.uint32)[:, None]
    win = jax.lax.bitcast_convert_type(words, jnp.uint32)[
        w0[:, None] + jnp.arange(nw, dtype=jnp.int32)]
    # win[:, k + 1] << (32 - s) as two shifts: 32 (phase 0) is not
    # defined, 24 then 8 is, and leaves the 0 that phase wants
    aligned = (win[:, :-1] >> s) | ((win[:, 1:] << (24 - s)) << 8)
    chars = jax.lax.bitcast_convert_type(aligned, jnp.uint8) \
        .reshape(n, char_cap)
    mask = jnp.arange(char_cap, dtype=jnp.int32) < lengths[:, None]
    return jnp.where(mask, chars, jnp.uint8(0))


def seg_excl_cumsum(contrib: jax.Array, seg_first_lane: jax.Array
                    ) -> jax.Array:
    """Exclusive prefix sum of ``contrib`` restarting at each segment:
    lane i gets sum(contrib[seg_first_lane[i]:i]). seg_first_lane is
    each lane's own segment-start lane index (clipped by the caller).
    This is the offsets-from-lengths half of the string decode: within
    a page, value i starts at the sum of the byte footprints before
    it."""
    c = jnp.cumsum(contrib)
    excl = c - contrib
    return excl - excl[seg_first_lane]


def _plain_groups(cap: int, nbytes: int) -> tuple:
    """``(g, gw, ng)``: the PLAIN region of ``nbytes``-wide values
    repeats every ``g = 4 / gcd(nbytes, 4)`` values, which are
    ``gw = nbytes / gcd(nbytes, 4)`` WHOLE words; ``ng`` such groups
    cover ``cap`` values."""
    d = math.gcd(nbytes, 4)
    g = 4 // d
    return g, nbytes // d, -(-cap // g)


def plain_window_words(cap: int, nbytes: int) -> int:
    """Words ``read_plain`` slices out of the staging buffer for
    ``cap`` values of ``nbytes``: what its caller pads the buffer by."""
    _g, gw, ng = _plain_groups(cap, nbytes)
    return ng * gw


def _bswap32(x: jax.Array) -> jax.Array:
    return ((x & 0xFF) << 24) | ((x & 0xFF00) << 8) \
        | ((x >> 8) & 0xFF00) | (x >> 24)


def _plain_parts(le32, nbytes: int, big_endian: bool) -> list:
    """A value's 32-bit parts, most significant first: the first int32
    and sign-extended from the value's top byte, the others uint32.
    ``le32(m)`` is the little-endian uint32 at byte ``m`` of the value
    (what it holds past the value's last byte is shifted out)."""
    def signed(x):
        return jax.lax.bitcast_convert_type(x, jnp.int32)

    if not big_endian:  # INT32 / INT64 / FLOAT / DOUBLE: whole words
        top = nbytes - 4
        return [signed(le32(top))] + [le32(m)
                                      for m in range(top - 4, -1, -4)]
    parts = [_bswap32(le32(m)) for m in range(nbytes % 4, nbytes, 4)]
    if nbytes % 4:  # the 1..3 top bytes: arithmetic shift extends them
        return [signed(_bswap32(le32(0))) >> (8 * (4 - nbytes % 4))] + parts
    return [signed(parts[0])] + parts[1:]


def _pair64(hi: jax.Array, lo: jax.Array) -> jax.Array:
    """int64 of a signed or unsigned high part and a uint32 low part."""
    return (hi.astype(jnp.int64) << 32) | lo.astype(jnp.int64)


def read_plain(words: jax.Array, at: jax.Array, cap: int, nbytes: int,
               big_endian: bool):
    """PLAIN fixed-width values at their dense lanes, with no gather:
    lane ``i >= d0`` of ``arange(cap)`` gets the ``nbytes``-wide value
    at byte ``4 * b0 + (i - d0) * nbytes`` of the int32 staging
    ``words``, where ``at = (b0, d0)`` is an int32 pair on the DEVICE
    (the region's word index and the dense lane of its first value
    differ by file; a static pair would compile a program a file).
    The host lays a chunk's PLAIN value sections end to end from a
    4-aligned byte (``io/device_decode._plan_column``), so the values
    lie at a fixed stride: ONE ``dynamic_slice`` takes the window's
    words, strided slices de-interleave them — ``g`` values are ``gw``
    whole words (``_plain_groups``), so inside a group every word
    index and every shift is static — and a second ``dynamic_slice``
    of the front-padded parts moves value ``k`` to lane ``d0 + k``.
    Lanes before ``d0`` read zero. All arithmetic runs in 32-bit
    lanes; the parts are widened to int64 once, at the end.

    ``big_endian=False`` reads PLAIN INT32/FLOAT (``nbytes`` 4,
    sign-extended) and INT64/DOUBLE (8); ``big_endian=True`` a
    FIXED_LEN_BYTE_ARRAY decimal, two's complement of 1..16 bytes.
    Returns int64 for ``nbytes <= 8`` and the ``(hi, lo)`` int64 limbs
    of transfer.py's dec128 layout above that.

    ``dynamic_slice`` CLAMPS a start that would run its window past
    the array, silently shifting every lane: the caller pads ``words``
    with ``plain_window_words(cap, nbytes)`` words, so that no ``b0``
    inside the buffer can. Lanes whose value would lie behind the
    buffer read that padding; callers mask them (``validity``)."""
    g, gw, ng = _plain_groups(cap, nbytes)
    b0, d0 = at[0], at[1]
    with jax.named_scope("window"):
        win = jax.lax.bitcast_convert_type(
            jax.lax.dynamic_slice(words, (b0,), (ng * gw,)), jnp.uint32)
        cols = [jax.lax.slice(win, (c,), (c + (ng - 1) * gw + 1,), (gw,))
                for c in range(gw)]

    def value(r: int) -> list:
        def le32(m: int) -> jax.Array:
            c, p = divmod(r * nbytes + m, 4)
            if not p:
                return cols[c]
            v = cols[c] >> (8 * p)
            # the next word is the next GROUP's where c is the last:
            # only bytes past this value's end would come from it
            return v | (cols[c + 1] << (32 - 8 * p)) if c + 1 < gw else v
        return _plain_parts(le32, nbytes, big_endian)

    def interleaved(vals: list) -> jax.Array:
        # value r of every group to lanes r, r + g, ...: each spread by
        # interior padding, then ORed (a stack on a minor axis of g and
        # a reshape runs as fast and compiles 3.5 times as long:
        # PERF.md §6, PR 35)
        out = None
        for r, v in enumerate(vals):
            v = jax.lax.bitcast_convert_type(v, jnp.int32)
            if g > 1:
                v = jax.lax.pad(v, jnp.int32(0), [(r, g - 1 - r, g - 1)])
            out = v if out is None else out | v
        return out[:cap]

    per_r = [value(r) for r in range(g)]
    with jax.named_scope("lanes"):
        # part k of the g values of a group, interleaved again; then
        # every part moved by d0 lanes at once
        parts = jnp.stack([interleaved([v[k] for v in per_r])
                           for k in range(len(per_r[0]))])
        parts = jax.lax.dynamic_slice(
            jnp.pad(parts, ((0, 0), (cap, 0))),
            (jnp.zeros((), d0.dtype), cap - d0), parts.shape)
    top = parts[0]
    rest = [jax.lax.bitcast_convert_type(p, jnp.uint32) for p in parts[1:]]
    if not rest:
        return top.astype(jnp.int64)
    if len(rest) == 1:
        return _pair64(top, rest[0])
    lo = _pair64(rest[-2], rest[-1])
    hi = top.astype(jnp.int64) if len(rest) == 2 else _pair64(top, rest[0])
    return hi, lo


def dense_ranks(validity: jax.Array) -> jax.Array:
    """Row -> index of its value in the null-stripped (dense) value
    stream: Parquet data pages store only non-null values, so row i's
    value is the rank-of-i-among-valid-rows'th entry (the reference
    calls this the value scatter step of page decode)."""
    return jnp.cumsum(validity.astype(jnp.int32)) - 1

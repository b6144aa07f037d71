"""Positional run/page lookup of the device decode (ops/rle.run_index,
ops/rle.step_fields): one scatter of the table's starts and one prefix
sum must give what a per-lane binary search gives, one scatter of a
field's steps and one prefix sum what a gather through that search
gives, and the decode program must hold no loop and no gather by run.
Then the bit-packed read (ops/rle.read_packed): two aligned staging
words a lane must give what Python integers over the bytes give, at
every width and phase, and the decode program must gather two elements
a lane a hybrid stream, not a byte window. Then the PLAIN read
(ops/rle.read_plain): one contiguous window of the staging words,
de-interleaved at the fixed stride and moved to its first dense lane,
must give what Python integers over the bytes give, at every width,
either byte order, any first lane and any place in the buffer, and the
decode program must gather nothing for it."""

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_tpu.io.device_decode import _SENTINEL
from spark_rapids_tpu.ops import rle as R


def _table(starts, pad_to):
    t = np.full(pad_to, _SENTINEL, dtype=np.int64)
    t[:len(starts)] = starts
    return t


def _searched(table, query):
    """The per-lane binary search the lookup replaced, clipped as the
    decode body clips it."""
    return np.clip(np.searchsorted(table, query, side="right") - 1,
                   0, len(table) - 1)


# (case, real starts, padded table length, cap)
_TABLES = [
    ("plain_runs", [0, 8, 16, 40, 41, 100], 8, 128),
    ("duplicate_starts", [0, 8, 8, 8, 24, 24, 60], 8, 64),
    ("starts_at_and_beyond_cap", [0, 10, 64, 70, 4000], 8, 64),
    ("first_start_above_zero", [5, 9, 30], 8, 48),
    ("one_real_entry", [0], 8, 32),
    ("one_real_entry_above_zero", [7], 8, 32),
    ("cap_not_a_power_of_two", [0, 3, 3, 50, 99, 100], 8, 100),
    ("no_padding", [0, 1, 2, 3, 4, 5, 6, 7], 8, 24),
    ("page_table_odd_length", [0, 20, 20, 45, 45, 45, 80, 90, 96], 9, 96),
    ("every_lane_a_run", list(range(64)), 64, 64),
    ("wide_table", sorted(np.random.default_rng(3)
                          .integers(0, 5000, 300).tolist()), 512, 4099),
]


@pytest.mark.parametrize("case,starts,pad_to,cap", _TABLES,
                         ids=[t[0] for t in _TABLES])
def test_run_index_matches_binary_search(case, starts, pad_to, cap):
    table = _table(starts, pad_to)
    got = np.asarray(R.run_index(jnp.asarray(table), cap))
    assert got.dtype == np.int32 and got.shape == (cap,)
    assert np.array_equal(got, _searched(table, np.arange(cap))), case


def _field(kind, n, rng):
    """A table column of ``n`` entries, padding entries included (no
    lane may read them, so they hold values like any other)."""
    if kind == "int64_wrapping":
        # magnitudes near 2^62 with sign changes: first differences
        # pass 2^63 and wrap, and the prefix sum has to undo it
        mag = (1 << 62) - rng.integers(0, 1 << 20, n)
        return np.where(rng.random(n) < 0.5, mag, -mag).astype(np.int64)
    if kind == "packed_flag":
        return (rng.random(n) < 0.5).astype(np.int32)
    if kind == "width":
        return rng.integers(0, 65, n).astype(np.int32)
    raise AssertionError(kind)


_FIELD_KINDS = {
    "int64_wrapping": ("int64_wrapping",),
    "packed_flag": ("packed_flag",),
    "width": ("width",),
    "five_at_once": ("int64_wrapping", "width", "packed_flag",
                     "int64_wrapping", "width"),
}


@pytest.mark.parametrize("kinds", sorted(_FIELD_KINDS))
@pytest.mark.parametrize("case,starts,pad_to,cap", _TABLES,
                         ids=[t[0] for t in _TABLES])
def test_step_fields_match_gather_through_search(case, starts, pad_to,
                                                 cap, kinds):
    table = _table(starts, pad_to)
    rng = np.random.default_rng(len(case) + len(kinds))
    fields = [_field(k, pad_to, rng) for k in _FIELD_KINDS[kinds]]
    got = R.step_fields(jnp.asarray(table), cap,
                        *[jnp.asarray(f) for f in fields])
    rid = _searched(table, np.arange(cap))
    assert len(got) == len(fields)
    for f, g in zip(fields, got):
        g = np.asarray(g)
        assert g.dtype == f.dtype and g.shape == (cap,)
        assert np.array_equal(g, f[rid]), (case, kinds)


@pytest.mark.parametrize("case,starts,pad_to,cap", _TABLES,
                         ids=[t[0] for t in _TABLES])
def test_run_fields_match_the_gathers_they_replaced(case, starts, pad_to,
                                                    cap):
    """Bit offset, width and value of every lane, garbage lanes
    (before the first start, past the last run) included: what
    ``bit_start[rid] + (pos - out_start[rid]) * width[rid]`` and the
    plain reads through ``rid`` gave, in wrapping int64."""
    table = _table(starts, pad_to)
    rng = np.random.default_rng(len(case))
    width = rng.integers(0, 65, pad_to).astype(np.int64)
    width[len(starts):] = 1
    bit_start = np.cumsum(rng.integers(0, 504 * 64, pad_to))
    value = _field("int64_wrapping", pad_to, rng)
    pos = np.arange(cap, dtype=np.int64)
    bit_off, w, v = R._run_fields(
        jnp.asarray(pos), jnp.asarray(table), jnp.asarray(bit_start),
        jnp.asarray(width), jnp.asarray(value))
    rid = _searched(table, pos)
    with np.errstate(over="ignore"):
        want = bit_start[rid] + (pos - table[rid]) * width[rid]
    assert np.asarray(bit_off).dtype == np.int64
    assert np.array_equal(np.asarray(bit_off), want), case
    assert np.asarray(w).dtype == np.int64
    assert np.array_equal(np.asarray(w), width[rid]), case
    assert np.array_equal(np.asarray(v), value[rid]), case


def _validity(case, cap, rng):
    v = rng.random(cap) < 0.7
    if case == "leading_nulls":
        v[:11] = False
    elif case == "trailing_nulls":
        v[-17:] = False
    elif case == "all_null_stretches":
        v[20:45] = False
        v[60:61] = False
        v[90:] = False
    elif case == "all_null":
        v[:] = False
    elif case == "no_nulls":
        v[:] = True
    return v


@pytest.mark.parametrize("case", ["leading_nulls", "trailing_nulls",
                                  "all_null_stretches", "all_null",
                                  "no_nulls", "scattered"])
def test_run_index_through_dense_ranks(case):
    """Rows reach their run through ``j`` (row -> dense rank): the
    dense-lane answer gathered by ``j`` equals searching ``j`` itself,
    null rows (which repeat a rank, or clip to 0) included."""
    cap = 120
    rng = np.random.default_rng(len(case))
    validity = _validity(case, cap, rng)
    for starts, pad_to in (([0, 8, 8, 30, 31, 77], 8),
                           ([4, 50, 200], 4),
                           ([0, 16, 16, 16, 48, 64, 64, 80, 119], 9)):
        table = _table(starts, pad_to)
        j = jnp.clip(R.dense_ranks(jnp.asarray(validity)), 0, cap - 1)
        got = np.asarray(R.run_index(jnp.asarray(table), cap)[j])
        assert np.array_equal(got, _searched(table, np.asarray(j))), \
            (case, starts)


# -- the bit-packed read: two aligned staging words a lane -------------------

def _staging(kind, nw, rng):
    """``nw`` int32 staging words; every kind but ``random`` has the
    top bit of every word set, so an arithmetic shift would show."""
    if kind == "random":
        u = rng.integers(0, 1 << 32, nw, dtype=np.uint64)
    elif kind == "top_bit_set":
        u = rng.integers(0, 1 << 32, nw, dtype=np.uint64) | 0x80000000
    elif kind == "all_ones":
        u = np.full(nw, 0xFFFFFFFF, dtype=np.uint64)
    else:
        raise AssertionError(kind)
    return u.astype(np.uint32).view(np.int32)


def _plain_read(words, bit_off, width):
    """The value Python integers give: the buffer's bytes as ONE
    little-endian integer, shifted and masked."""
    whole = int.from_bytes(words.tobytes(), "little")
    return [(whole >> int(o)) & ((1 << int(w)) - 1)
            for o, w in zip(bit_off, width)]


def _read(fn, words, bit_off, width):
    got = fn(jnp.asarray(words), jnp.asarray(bit_off, dtype=jnp.int64),
             jnp.asarray(width, dtype=jnp.int64))
    assert got.dtype == jnp.int64 and got.shape == (len(bit_off),)
    return np.asarray(got)


_NW = 12


@pytest.mark.parametrize("phase", range(32))
@pytest.mark.parametrize("width", range(33))
def test_read_packed_every_width_at_every_phase(width, phase):
    """Every word the value can start in, last word included where it
    still ends inside the buffer, over words with the top bit set."""
    rng = np.random.default_rng(width * 32 + phase)
    words = _staging("top_bit_set" if (width + phase) % 2 else "random",
                     _NW, rng)
    off = np.array([32 * k + phase for k in range(_NW)
                    if 32 * k + phase + width <= 32 * _NW], dtype=np.int64)
    assert len(off) >= _NW - 1
    w = np.full(len(off), width)
    got = _read(R.read_packed, words, off, w)
    assert got.tolist() == _plain_read(words, off, w), (width, phase)
    if width == 0:
        assert not got.any()


@pytest.mark.parametrize("kind", ["random", "top_bit_set", "all_ones"])
@pytest.mark.parametrize("width", range(1, 33))
def test_read_packed_value_ending_on_the_buffers_last_bit(width, kind):
    """The lane's second word does not exist: its index is clipped and
    whatever it reads must be masked away. Beside it the same width
    ending on the last bit of the word before."""
    words = _staging(kind, _NW, np.random.default_rng(width))
    off = np.array([32 * _NW - width, 32 * (_NW - 1) - width])
    w = np.full(2, width)
    got = _read(R.read_packed, words, off, w)
    assert got.tolist() == _plain_read(words, off, w), (width, kind)


@pytest.mark.parametrize("phase", [0, 31])
@pytest.mark.parametrize("kind", ["random", "top_bit_set", "all_ones"])
def test_read_packed_width_32_at_the_edges_of_a_word(kind, phase):
    """Width 32 is the mask that ``1 << 32`` cannot make; phase 0 the
    shift by 32 that is not defined; phase 31 a value with one bit in
    its first word."""
    words = _staging(kind, _NW, np.random.default_rng(phase))
    off = np.array([32 * k + phase for k in range(_NW - 1)])
    w = np.full(len(off), 32)
    got = _read(R.read_packed, words, off, w)
    assert got.tolist() == _plain_read(words, off, w)
    assert (got >= 0).all() and (got < (1 << 32)).all()


def test_read_packed_widths_mixed_across_lanes():
    rng = np.random.default_rng(5)
    words = _staging("top_bit_set", 256, rng)
    w = rng.integers(0, 33, 4000)
    off = rng.integers(0, 256 * 32 - 32, 4000)
    got = _read(R.read_packed, words, off, w)
    assert got.tolist() == _plain_read(words, off, w)


@pytest.mark.parametrize("width", range(33, 65))
def test_read_packed64_wide_widths_at_every_phase(width):
    rng = np.random.default_rng(width)
    words = _staging("top_bit_set", _NW, rng)
    off = np.array([32 * k + phase for phase in range(32)
                    for k in range(_NW)
                    if 32 * k + phase + width <= 32 * _NW])
    w = np.full(len(off), width)
    got = _read(R.read_packed64, words, off, w).astype(np.uint64)
    want = np.array(_plain_read(words, off, w), dtype=np.uint64)
    assert np.array_equal(got, want), width


@pytest.mark.parametrize("fn", ["read_packed", "read_packed64"])
@pytest.mark.parametrize("case,off", [
    ("negative", [-1, -31, -32, -33, -(1 << 40), -(1 << 62)]),
    ("past_the_end", [32 * _NW, 32 * _NW + 7, 1 << 36, 1 << 40,
                      (1 << 62) + 5, (1 << 63) - 1]),
    ("running_past_the_end", [32 * _NW - 1, 32 * _NW - 8,
                              32 * _NW - 31]),
])
def test_packed_reads_clip_offsets_outside_the_buffer(case, off, fn):
    """Garbage lanes (callers mask them) must come back, not trap: an
    index outside the buffer is clipped."""
    words = _staging("top_bit_set", _NW, np.random.default_rng(1))
    top = 32 if fn == "read_packed" else 64
    for width in (0, 1, 17, top):
        got = _read(getattr(R, fn), words, np.array(off, dtype=np.int64),
                    np.full(len(off), width))
        if width < 64:
            assert (got >= 0).all() and (got < (1 << width)).all(), \
                (case, width)


# -- the PLAIN read: one contiguous window at a fixed stride -----------------

_PLAIN_CAP = 163_840    # bucket_capacity(151_265)
_PLAIN_NW = 16_384      # staging words: 65,536 bytes
# (bytes a value, big-endian): PLAIN INT32/FLOAT and INT64/DOUBLE are
# little-endian, a FIXED_LEN_BYTE_ARRAY decimal is big-endian at 1..16
_PLAIN_SHAPES = [(4, False), (8, False)] + [(w, True) for w in range(1, 17)]


@functools.lru_cache(maxsize=None)
def _plain_reader(nbytes, big_endian):
    """One program a shape: the region's place and first lane are
    device values, as the decode body hands them over."""
    pad = R.plain_window_words(_PLAIN_CAP, nbytes)
    return jax.jit(lambda words, at: R.read_plain(
        jnp.pad(words, (0, pad)), at, _PLAIN_CAP, nbytes, big_endian))


def _plain_ints(got, lanes):
    """Python integers of lanes: an int64, or (hi, lo) limbs."""
    if isinstance(got, tuple):
        hi = np.asarray(got[0])[lanes].tolist()
        lo = np.asarray(got[1])[lanes].astype(np.uint64).tolist()
        return [(h << 64) | l for h, l in zip(hi, lo)]
    return np.asarray(got)[lanes].tolist()


def _plain_want(raw, byte0, nbytes, big_endian, count):
    order = "big" if big_endian else "little"
    return [int.from_bytes(raw[byte0 + k * nbytes:byte0 + (k + 1) * nbytes],
                           order, signed=True) for k in range(count)]


@pytest.mark.parametrize("place", ["first_in_buffer", "middle",
                                   "ends_on_last_byte"])
@pytest.mark.parametrize("d0", [0, 1, 19_999, 151_264])
@pytest.mark.parametrize("nbytes,big_endian", _PLAIN_SHAPES,
                         ids=[f"{w}{'be' if b else 'le'}"
                              for w, b in _PLAIN_SHAPES])
def test_read_plain_matches_python_integers(nbytes, big_endian, d0, place):
    """Lane i >= d0 holds the value at byte 4 * b0 + (i - d0) * W, as
    a signed Python integer reads it, for every lane a row can use;
    lanes before d0 hold zero. ``ends_on_last_byte`` is the clamp
    hazard: the window of ``cap`` values starts inside the buffer and
    runs far past it, and a clamped ``dynamic_slice`` would shift
    every lane."""
    rng = np.random.default_rng(nbytes * 7 + d0 % 5)
    words = _staging("top_bit_set" if (nbytes + d0) % 2 else "random",
                     _PLAIN_NW, rng)
    raw = words.tobytes()
    if place == "first_in_buffer":
        b0 = 0
    elif place == "middle":
        b0 = 5_003
    else:  # the region's last value ends on the buffer's last byte
        count = 4 * 37
        b0 = _PLAIN_NW - count * nbytes // 4
    count = min((len(raw) - 4 * b0) // nbytes, _PLAIN_CAP - d0, 9_000)
    assert count >= 4 * 37 and d0 + count <= _PLAIN_CAP
    got = _plain_reader(nbytes, big_endian)(
        jnp.asarray(words), jnp.asarray(np.array([b0, d0], np.int32)))
    for g in got if isinstance(got, tuple) else (got,):
        assert g.dtype == jnp.int64 and g.shape == (_PLAIN_CAP,)
    assert _plain_ints(got, slice(d0, d0 + count)) == _plain_want(
        raw, 4 * b0, nbytes, big_endian, count), (nbytes, d0, place)
    assert not any(_plain_ints(got, slice(0, d0)))


@pytest.mark.parametrize("nbytes,big_endian", _PLAIN_SHAPES,
                         ids=[f"{w}{'be' if b else 'le'}"
                              for w, b in _PLAIN_SHAPES])
def test_read_plain_sign_extension_and_limbs(nbytes, big_endian):
    """The extremes of every width: -1, the least and the greatest
    value, and a one in the lowest bit — sign extension from the top
    byte at every width under 8, the (hi, lo) limbs above it."""
    least = bytes([0x80]) + bytes(nbytes - 1)
    most = bytes([0x7F]) + bytes([0xFF] * (nbytes - 1))
    one = bytes(nbytes - 1) + bytes([1])
    vals = [bytes([0xFF] * nbytes), least, most, one] * 5
    if not big_endian:
        vals = [v[::-1] for v in vals]
    raw = b"".join(vals)
    raw += bytes(-len(raw) % 4)
    words = np.zeros(_PLAIN_NW, dtype=np.int32)
    words[:len(raw) // 4] = np.frombuffer(raw, dtype=np.int32)
    got = _plain_reader(nbytes, big_endian)(
        jnp.asarray(words), jnp.asarray(np.array([0, 3], np.int32)))
    bits = 8 * nbytes
    want = [-1, -(1 << (bits - 1)), (1 << (bits - 1)) - 1, 1] * 5
    assert _plain_ints(got, slice(3, 23)) == want, nbytes
    assert _plain_ints(got, slice(0, 3)) == [0, 0, 0]


def test_read_plain_one_program_serves_any_region_and_first_lane():
    """``at`` is a runtime value: files whose dictionaries overflow at
    different lanes, and whose regions lie elsewhere, share a program."""
    fn = _plain_reader(8, False)
    words = jnp.asarray(_staging("random", _PLAIN_NW,
                                 np.random.default_rng(8)))
    fn(words, jnp.asarray(np.array([0, 0], np.int32)))
    before = fn._cache_size()
    fn(words, jnp.asarray(np.array([4_001, 151_264], np.int32)))
    assert fn._cache_size() == before


# -- structural guard: no loop in the decode program -------------------------

_RUN_DTYPES = ("int64", "bool", "int64", "int64", "int64")

# the seven ("dev", ...) entries q1 gives at SF1 (cap 786,432; one decode
# a partition): all RLE_DICTIONARY, extendedprice overflowing to PLAIN
_Q1_CAP = 786_432
# (no page table rides: npg 0, every chunk is dictionary pages then PLAIN)
_Q1_LAYOUT = (
    ("dev", "dec64", "int64", 7, 0, 0, 0, 2048, 0,
     (((64,), "int64"),), False, False, False, False),
    ("dev", "dec64", "int64", 7, 0, 0, 0, 512, 0,
     (((262144,), "int64"),), True, False, False, False),
    ("dev", "dec64", "int64", 7, 0, 0, 0, 2048, 0,
     (((16,), "int64"),), False, False, False, False),
    ("dev", "dec64", "int64", 7, 0, 0, 0, 2048, 0,
     (((16,), "int64"),), False, False, False, False),
    ("dev", "str", "uint8", 0, 8, 0, 0, 2048, 0,
     (((4, 8), "uint8"), ((4,), "int32")), False, False, False, False),
    ("dev", "str", "uint8", 0, 8, 0, 0, 4096, 0,
     (((2, 8), "uint8"), ((2,), "int32")), False, False, False, False),
    ("dev", "int", "int32", 4, 0, 0, 0, 2048, 0,
     (((4096,), "int64"),), False, False, False, False),
)

_LAYOUTS = {
    "q1_sf1": (_Q1_LAYOUT, _Q1_CAP),
    "nullable_dict_and_plain": ((
        ("dev", "int", "int64", 8, 0, 0, 16, 8, 0,
         (((32,), "int64"),), True, False, False, False),), 1024),
    # PLAIN pages with a dictionary page between them (no writer does
    # it): the page table rides and says which lanes are PLAIN
    "plain_pages_not_consecutive": ((
        ("dev", "dec64", "int64", 7, 0, 8, 0, 8, 0,
         (((32,), "int64"),), True, False, False, False),), 1024),
    "plain_from_the_first_page": ((
        ("dev", "f64", "float64", 8, 0, 0, 8, 0, 0, (),
         True, False, False, False),), 1024),
    "string_with_lengths": ((
        ("dev", "str", "uint8", 0, 16, 8, 8, 8, 0,
         (((8, 16), "uint8"), ((8,), "int32")),
         False, False, False, True),), 1024),
    "delta_binary_packed": ((
        ("dev", "int", "int64", 8, 0, 8, 8, 0, 16, (),
         False, True, False, False),), 1024),
    "nullable_bool": ((
        ("dev", "bool", "bool", 1, 0, 0, 8, 8, 0, (),
         False, False, False, False),), 1024),
    "dec128_plain_and_dict": ((
        ("dev", "dec128", "int64", 16, 0, 0, 8, 8, 0,
         (((8,), "int64"), ((8,), "int64")),
         True, False, False, False),), 1024),
    "byte_stream_split_f64": ((
        ("dev", "f64", "float64", 8, 0, 8, 0, 0, 0, (),
         True, False, True, False),), 1024),
    "host_column_beside_a_device_one": ((
        ("host", 2),
        ("dev", "f32", "float32", 4, 0, 0, 8, 8, 0,
         (((16,), "int64"),), True, False, False, False)), 1024),
}


def _abstract_extras(layout, cap):
    """Shapes of the plan tables a layout's decode program takes, in the
    order ``prepare_encoded_upload`` stages them."""
    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))

    out = []
    for ent in layout:
        if ent[0] == "host":
            out.extend(arr((cap,), "int64") for _ in range(ent[1]))
            continue
        (_tag, _kind, _np_dt, _eb, _cc, npg, ndl, nvr, ndr, dict_shapes,
         has_plain, has_delta, _has_bss, has_slen) = ent
        if npg:
            out.extend([arr((npg + 1,), "int64"), arr((npg,), "int64"),
                        arr((npg,), "int32")])
        if has_plain:
            out.append(arr((2,), "int32"))
        if has_delta:
            out.append(arr((npg,), "int64"))
        for n_runs in (ndl, nvr, ndr):
            if n_runs:
                out.extend(arr((n_runs,), d) for d in _RUN_DTYPES)
        if has_slen:
            out.append(arr((cap,), "int32"))
        out.extend(arr(shape, dtype) for shape, dtype in dict_shapes)
    return out


def _eqns(jaxpr):
    """Every equation of a jaxpr, sub-jaxprs (pjit, cond branches, loop
    bodies, custom calls) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _primitives(jaxpr):
    """How often each primitive occurs in a jaxpr."""
    return collections.Counter(e.primitive.name for e in _eqns(jaxpr))


def test_guard_sees_the_search_it_guards_against():
    prims = _primitives(jax.make_jaxpr(
        lambda t, q: jnp.searchsorted(t, q, side="right"))(
            jnp.zeros(8, jnp.int64), jnp.zeros(16, jnp.int64)).jaxpr)
    assert prims.keys() & {"while", "scan"}


# ``gather`` primitives each layout's decode program holds now that no
# field of a run is read through the run's index and a packed value is
# read from two aligned staging words (two gathers of one element a
# hybrid stream, four a DELTA stream, where the byte window was one
# gather of five: q1's seven streams held 32), a PLAIN value is read
# with none (the byte windows were one to three a column) and a chunk
# of dictionary pages followed by PLAIN ones carries no page table (its
# ``dense_start[pg]`` / ``pg_enc[pg]`` / ``plain_byte[pg]`` went: q1's
# seven columns held 16). What is left beside the packed reads: the
# dictionary reads, the BSS and string byte windows, the page tables of
# the layouts that still need one and the row gather through ``j``.
_GATHERS = {
    "q1_sf1": 23,
    "nullable_dict_and_plain": 6,
    "plain_pages_not_consecutive": 5,
    "plain_from_the_first_page": 3,
    "string_with_lengths": 13,
    "delta_binary_packed": 11,
    "nullable_bool": 5,
    "dec128_plain_and_dict": 8,
    "byte_stream_split_f64": 5,
    "host_column_beside_a_device_one": 6,
}


def _decode_jaxpr(name):
    from spark_rapids_tpu.columnar.transfer import _build_encoded_decode
    layout, cap = _LAYOUTS[name]
    fn = _build_encoded_decode(layout, cap)
    words = jax.ShapeDtypeStruct((4096,), jnp.int32)
    n_arr = jax.ShapeDtypeStruct((), jnp.int64)
    return jax.make_jaxpr(fn)(words, n_arr,
                              *_abstract_extras(layout, cap)).jaxpr


@pytest.mark.parametrize("name", sorted(_LAYOUTS))
def test_decode_program_has_no_loop(name):
    prims = _primitives(_decode_jaxpr(name))
    assert "cumsum" in prims, sorted(prims)
    assert not prims.keys() & {"while", "scan"}, sorted(prims)


@pytest.mark.parametrize("name", sorted(_LAYOUTS))
def test_decode_program_gathers_no_field_of_a_run(name):
    """The static proof that run fields reach their lanes by prefix
    sum in every layout: the count of ``gather`` is pinned (a gather
    by ``rid`` that came back would raise it)."""
    count = _primitives(_decode_jaxpr(name))["gather"]
    assert count == _GATHERS[name], (name, count)


def _gathered_elements(jaxpr, scope):
    """Elements all ``gather`` equations under a named scope put out."""
    return sum(v.aval.size for eqn in _eqns(jaxpr)
               if eqn.primitive.name == "gather"
               and scope in str(eqn.source_info.name_stack)
               for v in eqn.outvars)


@pytest.mark.parametrize("name", sorted(_LAYOUTS))
def test_packed_read_gathers_two_elements_a_lane(name):
    """The static proof that a packed value is read from two aligned
    words, however the two are spelled: under ``decode_bits/window`` a
    hybrid stream (definition levels, dictionary indices, booleans)
    gathers 2 elements a lane and a DELTA stream 4 (two reads of up
    to 32 bits). A byte window that came back would gather 5 and 10."""
    layout, cap = _LAYOUTS[name]
    want = sum(2 * bool(ent[6]) + 2 * bool(ent[7]) + 4 * bool(ent[8])
               for ent in layout if ent[0] == "dev")
    got = _gathered_elements(_decode_jaxpr(name), "decode_bits/window")
    assert got == want * cap, (name, got / cap, want)


@pytest.mark.parametrize("name", sorted(_LAYOUTS))
def test_plain_read_gathers_nothing(name):
    """The static proof that a PLAIN value reaches its lane by
    contiguous copies in every layout: no element is gathered under
    ``decode_plain`` but BYTE_STREAM_SPLIT's, which shares the scope
    (its bytes a value, and two page-table fields)."""
    layout, cap = _LAYOUTS[name]
    want = sum((ent[3] + 2) * bool(ent[12])
               for ent in layout if ent[0] == "dev")
    got = _gathered_elements(_decode_jaxpr(name), "decode_plain")
    assert got == want * cap, (name, got / cap, want)


@pytest.mark.parametrize("name", sorted(_LAYOUTS))
def test_page_lookup_only_where_a_page_table_rides(name):
    """A column of dictionary pages followed by PLAIN ones (``npg``
    0) looks no page up: nothing runs under ``decode_page_lookup`` for
    it. A layout with a page table gathers its two fields a lane."""
    layout, cap = _LAYOUTS[name]
    jaxpr = _decode_jaxpr(name)
    paged = sum(bool(ent[5]) and ent[1] != "bool"
                for ent in layout if ent[0] == "dev")
    assert _gathered_elements(jaxpr, "decode_page_lookup") \
        == 2 * paged * cap, name
    if not paged:
        assert not any("decode_page_lookup"
                       in str(e.source_info.name_stack)
                       for e in _eqns(jaxpr)), name

"""Device Murmur3 (Spark Murmur3_x86_32, seed 42) — bit-compatible with the
host implementation in columnar/murmur3.py, which itself matches Spark's
HashExpression so device hash partitioning places rows exactly where CPU
Spark would (reference: GpuHashPartitioning.scala + cudf spark-murmur3 mode).

All arithmetic is uint32 with wraparound (XLA integer ops wrap, like Java).
Strings hash their UTF-8 bytes from the padded byte matrix: full 4-byte
little-endian words first, then trailing bytes one at a time as sign-extended
ints — a static loop over the (bucketed) char capacity, masked per row by
the actual byte length.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import numpy as np

from spark_rapids_tpu.sql import types as T

_C1 = np.uint32(0xCC9E2D51)
_C2 = np.uint32(0x1B873593)
_M5 = np.uint32(0xE6546B64)


def _rotl(x: jax.Array, r: int) -> jax.Array:
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def _mix_k1(k1: jax.Array) -> jax.Array:
    k1 = k1.astype(jnp.uint32) * _C1
    k1 = _rotl(k1, 15)
    return k1 * _C2


def _mix_h1(h1: jax.Array, k1: jax.Array) -> jax.Array:
    h1 = h1 ^ k1
    h1 = _rotl(h1, 13)
    return h1 * np.uint32(5) + _M5


def _fmix(h1: jax.Array, length: jax.Array) -> jax.Array:
    h1 = h1 ^ length.astype(jnp.uint32)
    h1 = h1 ^ (h1 >> np.uint32(16))
    h1 = h1 * np.uint32(0x85EBCA6B)
    h1 = h1 ^ (h1 >> np.uint32(13))
    h1 = h1 * np.uint32(0xC2B2AE35)
    h1 = h1 ^ (h1 >> np.uint32(16))
    return h1


def hash_int(values: jax.Array, seed: jax.Array) -> jax.Array:
    """hashInt: one 4-byte round + fmix(4). Returns int32."""
    k1 = _mix_k1(values.astype(jnp.int32).view(jnp.uint32))
    h1 = _mix_h1(seed.astype(jnp.int32).view(jnp.uint32), k1)
    return _fmix(h1, np.uint32(4)).view(jnp.int32)


def hash_long(values: jax.Array, seed: jax.Array) -> jax.Array:
    """hashLong: low int32 word then high, + fmix(8)."""
    v = values.astype(jnp.int64).view(jnp.uint64)
    low = (v & np.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    high = (v >> np.uint64(32)).astype(jnp.uint32)
    h1 = seed.astype(jnp.int32).view(jnp.uint32)
    h1 = _mix_h1(h1, _mix_k1(low))
    h1 = _mix_h1(h1, _mix_k1(high))
    return _fmix(h1, np.uint32(8)).view(jnp.int32)


def hash_float(values: jax.Array, seed: jax.Array) -> jax.Array:
    v = values.astype(jnp.float32)
    v = jnp.where(v == np.float32(0.0), np.float32(0.0), v)  # fold -0.0
    return hash_int(v.view(jnp.int32), seed)


def hash_double(values: jax.Array, seed: jax.Array) -> jax.Array:
    v = values.astype(jnp.float64)
    v = jnp.where(v == 0.0, 0.0, v)
    return hash_long(v.view(jnp.int64), seed)


def hash_bytes(chars: jax.Array, lengths: jax.Array,
               seed: jax.Array) -> jax.Array:
    """hashUnsafeBytes over a padded uint8[n, char_cap] matrix.

    Static unrolled loop over word slots; each row applies only the rounds
    its length covers. Trailing (< 4) bytes are sign-extended int8 rounds,
    matching Spark's byte-at-a-time tail handling.
    """
    n, char_cap = chars.shape
    lengths = lengths.astype(jnp.int32)
    aligned = lengths - (lengths % 4)
    h1 = seed.astype(jnp.int32).view(jnp.uint32)
    c32 = chars.astype(jnp.uint32)
    n_words = char_cap // 4
    for w in range(n_words):
        off = 4 * w
        word = (c32[:, off]
                | (c32[:, off + 1] << 8)
                | (c32[:, off + 2] << 16)
                | (c32[:, off + 3] << 24))
        mixed = _mix_h1(h1, _mix_k1(word))
        h1 = jnp.where(off + 4 <= aligned, mixed, h1)
    # tail: up to 3 bytes at offsets aligned+k; gather per row
    for k in range(3):
        off = jnp.minimum(aligned + k, char_cap - 1)
        b = jnp.take_along_axis(chars, off[:, None], axis=1)[:, 0]
        sb = b.astype(jnp.int8).astype(jnp.int32).view(jnp.uint32)
        mixed = _mix_h1(h1, _mix_k1(sb))
        h1 = jnp.where(aligned + k < lengths, mixed, h1)
    return _fmix(h1, lengths.astype(jnp.uint32)).view(jnp.int32)


def hash_device_column(col, seed: jax.Array) -> jax.Array:
    """Fold one device column into the running per-row hash (seed);
    null slots leave the hash unchanged (Spark HashExpression)."""
    from spark_rapids_tpu.columnar.device import DeviceStringColumn
    dt = col.dtype
    if isinstance(col, DeviceStringColumn):
        h = hash_bytes(col.chars, col.lengths, seed)
    elif isinstance(dt, T.BooleanType):
        h = hash_int(col.data.astype(jnp.int32), seed)
    elif isinstance(dt, (T.ByteType, T.ShortType, T.IntegerType, T.DateType)):
        h = hash_int(col.data.astype(jnp.int32), seed)
    elif isinstance(dt, (T.LongType, T.TimestampType)):
        h = hash_long(col.data.astype(jnp.int64), seed)
    elif isinstance(dt, T.FloatType):
        h = hash_float(col.data, seed)
    elif isinstance(dt, T.DoubleType):
        h = hash_double(col.data, seed)
    elif isinstance(dt, T.DecimalType) and dt.precision <= 18:
        h = hash_long(col.data.astype(jnp.int64), seed)
    else:
        from spark_rapids_tpu.columnar.device import DeviceStructColumn
        if isinstance(col, DeviceStructColumn):
            # fold fields left-to-right with the running hash as seed;
            # null STRUCT rows keep the incoming seed (twin of the host
            # _hash_column struct branch)
            h = seed
            for f in col.fields:
                h = hash_device_column(f, h)
            return jnp.where(col.validity, h, seed)
        raise TypeError(f"cannot hash {dt} on device")
    return jnp.where(col.validity, h, seed)


def murmur3_columns(cols, capacity: int, seed: int = 42) -> jax.Array:
    """Spark Murmur3Hash(cols, seed): fold columns left-to-right."""
    h = jnp.full(capacity, seed, dtype=jnp.int32)
    for c in cols:
        h = hash_device_column(c, h)
    return h


# ---------------------------------------------------------------------------
# XXH64 (Spark XxHash64, seed 42L) — device twin of columnar/xxhash64.py
# ---------------------------------------------------------------------------

_XP1 = jnp.uint64(0x9E3779B185EBCA87)
_XP2 = jnp.uint64(0xC2B2AE3D27D4EB4F)
_XP3 = jnp.uint64(0x165667B19E3779F9)
_XP4 = jnp.uint64(0x85EBCA77C2B2AE63)
_XP5 = jnp.uint64(0x27D4EB2F165667C5)


def _xrotl(x: jax.Array, r: int) -> jax.Array:
    return (x << jnp.uint64(r)) | (x >> jnp.uint64(64 - r))


def _xfmix(h: jax.Array) -> jax.Array:
    h = h ^ (h >> jnp.uint64(33))
    h = h * _XP2
    h = h ^ (h >> jnp.uint64(29))
    h = h * _XP3
    h = h ^ (h >> jnp.uint64(32))
    return h


def xx_hash_int(values: jax.Array, seed: jax.Array) -> jax.Array:
    v = values.astype(jnp.int32).view(jnp.uint32).astype(jnp.uint64)
    h = seed.astype(jnp.int64).view(jnp.uint64) + _XP5 + jnp.uint64(4)
    h = h ^ (v * _XP1)
    h = _xrotl(h, 23) * _XP2 + _XP3
    return _xfmix(h).view(jnp.int64)


def xx_hash_long(values: jax.Array, seed: jax.Array) -> jax.Array:
    v = values.astype(jnp.int64).view(jnp.uint64)
    h = seed.astype(jnp.int64).view(jnp.uint64) + _XP5 + jnp.uint64(8)
    h = h ^ (_xrotl(v * _XP2, 31) * _XP1)
    h = _xrotl(h, 27) * _XP1 + _XP4
    return _xfmix(h).view(jnp.int64)


def xx_hash_float(values: jax.Array, seed: jax.Array) -> jax.Array:
    v = values.astype(jnp.float32)
    v = jnp.where(v == jnp.float32(0.0), jnp.float32(0.0), v)
    return xx_hash_int(v.view(jnp.int32), seed)


def xx_hash_double(values: jax.Array, seed: jax.Array) -> jax.Array:
    v = values.astype(jnp.float64)
    v = jnp.where(v == 0.0, 0.0, v)
    return xx_hash_long(v.view(jnp.int64), seed)


def xx_hash_bytes(chars: jax.Array, lengths: jax.Array,
                  seed: jax.Array) -> jax.Array:
    """Full XXH64 over a padded uint8[n, char_cap] matrix: 32-byte
    stripes, then 8/4/1-byte tail rounds, each statically unrolled to
    the bucketed capacity and masked per row by the true byte length."""
    n, char_cap = chars.shape
    pad_cap = max(32, ((char_cap + 31) // 32) * 32)
    if pad_cap != char_cap:
        chars = jnp.pad(chars, ((0, 0), (0, pad_cap - char_cap)))
    L = lengths.astype(jnp.int64)
    Lu = L.astype(jnp.uint64)
    c64 = chars.astype(jnp.uint64)
    lanes = []  # 8-byte little-endian lanes, each uint64[n]
    for j in range(pad_cap // 8):
        lane = jnp.zeros(n, dtype=jnp.uint64)
        for k in range(8):
            lane = lane | (c64[:, 8 * j + k] << jnp.uint64(8 * k))
        lanes.append(lane)
    words = []  # 4-byte words for the one 4-byte tail round
    for j in range(pad_cap // 4):
        w = jnp.zeros(n, dtype=jnp.uint64)
        for k in range(4):
            w = w | (c64[:, 4 * j + k] << jnp.uint64(8 * k))
        words.append(w)
    seed_u = seed.astype(jnp.int64).view(jnp.uint64)
    acc = [seed_u + _XP1 + _XP2, seed_u + _XP2, seed_u,
           seed_u - _XP1]
    for s in range(pad_cap // 32):
        live = L >= 32 * (s + 1)
        for k in range(4):
            new_v = _xrotl(acc[k] + lanes[4 * s + k] * _XP2, 31) * _XP1
            acc[k] = jnp.where(live, new_v, acc[k])
    hbig = (_xrotl(acc[0], 1) + _xrotl(acc[1], 7) + _xrotl(acc[2], 12)
            + _xrotl(acc[3], 18))
    for v in acc:
        hbig = (hbig ^ (_xrotl(v * _XP2, 31) * _XP1)) * _XP1 + _XP4
    h = jnp.where(L >= 32, hbig, seed_u + _XP5)
    h = h + Lu
    lane_stack = jnp.stack(lanes, axis=1)
    tail = (L // 32) * 32
    for t in range(3):
        pos = tail + 8 * t
        idx = jnp.clip(pos // 8, 0, len(lanes) - 1)
        lane = jnp.take_along_axis(lane_stack, idx[:, None], axis=1)[:, 0]
        new_h = _xrotl(h ^ (_xrotl(lane * _XP2, 31) * _XP1), 27) \
            * _XP1 + _XP4
        h = jnp.where(pos + 8 <= L, new_h, h)
    word_stack = jnp.stack(words, axis=1)
    i8 = (L // 8) * 8
    has4 = i8 + 4 <= L
    widx = jnp.clip(i8 // 4, 0, len(words) - 1)
    w = jnp.take_along_axis(word_stack, widx[:, None], axis=1)[:, 0]
    h = jnp.where(has4, _xrotl(h ^ (w * _XP1), 23) * _XP2 + _XP3, h)
    i4 = i8 + jnp.where(has4, 4, 0)
    for b in range(3):
        pos = i4 + b
        bidx = jnp.clip(pos, 0, pad_cap - 1)
        byte = jnp.take_along_axis(c64, bidx[:, None], axis=1)[:, 0]
        h = jnp.where(pos < L,
                      _xrotl(h ^ (byte * _XP5), 11) * _XP1, h)
    return _xfmix(h).view(jnp.int64)


def xx_hash_device_column(col, seed: jax.Array) -> jax.Array:
    from spark_rapids_tpu.columnar.device import DeviceStringColumn
    dt = col.dtype
    if isinstance(col, DeviceStringColumn):
        h = xx_hash_bytes(col.chars, col.lengths, seed)
    elif isinstance(dt, T.BooleanType):
        h = xx_hash_int(col.data.astype(jnp.int32), seed)
    elif isinstance(dt, (T.ByteType, T.ShortType, T.IntegerType,
                         T.DateType)):
        h = xx_hash_int(col.data.astype(jnp.int32), seed)
    elif isinstance(dt, (T.LongType, T.TimestampType)):
        h = xx_hash_long(col.data.astype(jnp.int64), seed)
    elif isinstance(dt, T.FloatType):
        h = xx_hash_float(col.data, seed)
    elif isinstance(dt, T.DoubleType):
        h = xx_hash_double(col.data, seed)
    elif isinstance(dt, T.DecimalType) and dt.precision <= 18:
        h = xx_hash_long(col.data.astype(jnp.int64), seed)
    else:
        raise TypeError(f"cannot xxhash {dt} on device")
    return jnp.where(col.validity, h, seed)


def xxhash64_columns(cols, capacity: int, seed: int = 42) -> jax.Array:
    """Spark XxHash64(cols, seed): fold columns left-to-right."""
    h = jnp.full(capacity, seed, dtype=jnp.int64)
    for c in cols:
        h = xx_hash_device_column(c, h)
    return h


def traced_partition_ids(exprs, cols, active, lit_vals,
                         n_parts: int) -> jax.Array:
    """Inside a traced program: pmod(murmur3(keys, 42), n) per row — the
    single definition of Spark HashPartitioning placement, shared by the
    in-process exchange and the ICI shard_map exchange so the two paths
    can never diverge. ``lit_vals`` must be passed as traced inputs (the
    compile caches key on expression *structure*, not literal values)."""
    from spark_rapids_tpu.ops import exprs as X
    cap = active.shape[0]
    ctx = X.Ctx(cols, cap, tuple(exprs), lit_vals)
    key_cols = [X.dev_eval(e, ctx) for e in exprs]
    hv = murmur3_columns(key_cols, cap, 42)
    return jnp.mod(hv.astype(jnp.int64), n_parts).astype(jnp.int32)

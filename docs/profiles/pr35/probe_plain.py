"""PR 35's microbenchmark, run on the chip before any cell: how a PLAIN
fixed-width value reaches its dense lane. One stream of 786,432 lanes over a
2,097,152-word staging buffer at W = 4, 7, 8 and 13 bytes a value, the first
PLAIN lane d0 = 0 and 151,264; the host has laid the PLAIN value sections end
to end at one 4-aligned byte offset B0, so lane i >= d0 reads bytes
B0 + (i - d0) * W.

  byte_window    the parent's ops/rle._gather_window: W gathered bytes a lane
                 out of the 4x-expanded int32-per-byte array, shifted together
                 in int64
  word_gathers   the floor: the ceil((W + 3) / 4) aligned words a value lies
                 in, one single-element gather each, shifted together in
                 32-bit lanes
  every other    gather-free: one dynamic_slice of the window's words from
                 B0, de-interleaved at the fixed stride (a group of
                 4 / gcd(W, 4) values is W / gcd(W, 4) whole words, so inside
                 a group every word index and shift is static), then moved by
                 d0 lanes with a second dynamic_slice of a front-padded stack.
                 The spellings differ in how a group's words become arrays
                 (cols_*) and how the group's values are interleaved again
                 (ilv_*), or read bytes (bytes_*).

Committed as ops/rle.read_plain: cols_strided.ilv_pad (as fast as any at
every width, and its W = 7 program compiles in 16 s where ilv_stack's takes
57).

Every spelling is held to Python integers over the buffer's bytes on every
lane d0 <= i < n. Milliseconds, median of 9 calls after one warm call.
Run from the root of a checkout: chiprun -- python3 docs/profiles/pr35/probe_plain.py
Writes chiprun_out/pr35/probe_plain.json."""
import json
import math
import os
import statistics
import sys
import time

import jax

jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

SCALE = int(os.environ.get("PROBE_SCALE", "1"))
NW = 2_097_152 // SCALE
CAP = 786_432 // SCALE
D0S = (0, 151_264 // SCALE)
B0 = 4 * (12_345 // SCALE)
U32 = jnp.uint32


def u32(x):
    return lax.bitcast_convert_type(x, U32)


def i32(x):
    return lax.bitcast_convert_type(x, jnp.int32)


def bswap(x):
    return ((x & 0xFF) << 24) | ((x & 0xFF00) << 8) \
        | ((x >> 8) & 0xFF00) | (x >> 24)


# -- assembling a value's 32-bit parts from unaligned 32-bit reads ----------

def parts_of(le32, nbytes, big_endian):
    """The value's 32-bit parts, most significant first, the first
    sign-extended (int32), the others uint32. ``le32(m)`` is the
    little-endian 32-bit read at byte m of the value (bytes past the
    value's end may be anything)."""
    if not big_endian:  # PLAIN INT32/INT64/FLOAT/DOUBLE
        return [i32(le32(m)) if m == nbytes - 4 else le32(m)
                for m in range(nbytes - 4, -1, -4)]
    parts = []
    m = nbytes
    while m >= 4:  # whole parts from the value's end
        m -= 4
        parts.insert(0, bswap(le32(m)))
    if m:  # the 1..3 most significant bytes, sign-extended
        parts.insert(0, i32(bswap(le32(0))) >> (8 * (4 - m)))
    else:
        parts[0] = i32(parts[0])
    return parts


def widen(parts):
    """32-bit parts (most significant first) -> int64, or (hi, lo) int64
    limbs for more than two parts."""
    def pair(hi, lo):
        return (hi.astype(jnp.int64) << 32) | lo.astype(jnp.int64)

    if len(parts) == 1:
        return parts[0].astype(jnp.int64)
    if len(parts) == 2:
        return pair(*parts)
    lo = pair(u32(parts[-2]), parts[-1])
    hi = parts[0].astype(jnp.int64) if len(parts) == 3 \
        else pair(parts[0], parts[1])
    return hi, lo


def shift_lanes(parts, d0, cap):
    """Lane i gets value i - d0 (lanes before d0: zero)."""
    st = jnp.stack([i32(p) for p in parts])
    st = jnp.pad(st, ((0, 0), (cap, 0)))
    st = lax.dynamic_slice(st, (jnp.zeros((), d0.dtype), cap - d0),
                           (len(parts), cap))
    return [st[0]] + [u32(p) for p in st[1:]]


def shift_lanes_roll(parts, d0, cap):
    return [jnp.roll(p, d0) for p in parts]


# -- the group geometry ------------------------------------------------------

def geometry(nbytes, cap):
    g = 4 // math.gcd(nbytes, 4)          # values a group
    gw = nbytes // math.gcd(nbytes, 4)    # words a group
    ng = -(-cap // g)
    return g, gw, ng


def window(words, b0w, nwin):
    """nwin words from word b0w; the buffer is padded so the window
    never runs past it (dynamic_slice would clamp the start)."""
    return u32(lax.dynamic_slice(jnp.pad(words, (0, nwin)), (b0w,), (nwin,)))


def cols_strided(win, gw, ng):
    return [lax.slice(win, (c,), (c + (ng - 1) * gw + 1,), (gw,))
            for c in range(gw)]


def cols_reshape(win, gw, ng):
    m = win.reshape(ng, gw)
    return [m[:, c] for c in range(gw)]


def cols_transpose(win, gw, ng):
    m = win.reshape(ng, gw).T
    return [m[c] for c in range(gw)]


def ilv_stack(per_r, cap):
    if len(per_r) == 1:
        return per_r[0][:cap]
    return jnp.stack(per_r, axis=1).reshape(-1)[:cap]


def ilv_pad(per_r, cap):
    g = len(per_r)
    if g == 1:
        return per_r[0][:cap]
    out = None
    for r, p in enumerate(per_r):
        z = lax.pad(p, jnp.zeros((), p.dtype), [(r, g - 1 - r, g - 1)])
        out = z if out is None else out | z
    return out[:cap]


def grouped(cols_fn, ilv_fn, shift_fn=shift_lanes):
    def read(words, at, cap, nbytes, big_endian):
        g, gw, ng = geometry(nbytes, cap)
        cols = cols_fn(window(words, at[0], ng * gw), gw, ng)
        per_r = []
        for r in range(g):
            def le32(m, r=r):
                c, p = divmod(r * nbytes + m, 4)
                v = cols[c] >> (8 * p) if p else cols[c]
                if p and c + 1 < gw:
                    v = v | (cols[c + 1] << (32 - 8 * p))
                return v
            per_r.append(parts_of(le32, nbytes, big_endian))
        parts = [ilv_fn([pr[k] for pr in per_r], cap)
                 for k in range(len(per_r[0]))]
        return widen(shift_fn(parts, at[1], cap))
    return read


def bytes_reshape(words, at, cap, nbytes, big_endian):
    """The window's bytes as a (cap, W) matrix, a column a byte."""
    nwin = -(-cap * nbytes // 4)
    win = window(words, at[0], nwin)
    b = ((win[:, None] >> (jnp.arange(4, dtype=U32) * 8)) & 0xFF) \
        .reshape(-1)[:cap * nbytes].reshape(cap, nbytes)
    return _from_bytes(lambda m: b[:, m], at, cap, nbytes, big_endian)


def bytes_strided(words, at, cap, nbytes, big_endian):
    """Byte m of every value by a stride-W slice of the window's bytes."""
    nwin = -(-cap * nbytes // 4)
    win = window(words, at[0], nwin)
    b = ((win[:, None] >> (jnp.arange(4, dtype=U32) * 8)) & 0xFF).reshape(-1)
    return _from_bytes(
        lambda m: lax.slice(b, (m,), (m + (cap - 1) * nbytes + 1,),
                            (nbytes,)),
        at, cap, nbytes, big_endian)


def _from_bytes(byte, at, cap, nbytes, big_endian):
    def le32(m):
        v = byte(m)
        for k in range(1, 4):
            if m + k < nbytes:
                v = v | (byte(m + k) << (8 * k))
        return v
    return widen(shift_lanes(parts_of(le32, nbytes, big_endian),
                             at[1], cap))


def word_gathers(words, at, cap, nbytes, big_endian):
    """The floor: aligned words a lane by single-element gathers."""
    nw = words.shape[0]
    k = jnp.arange(cap, dtype=jnp.int32) - at[1]
    off = k * nbytes                      # bytes from B0, int32
    w0 = at[0] + (off >> 2)
    ph = (off & 3).astype(U32) * 8
    got = {}

    def word(j):
        if j not in got:
            got[j] = u32(words[jnp.clip(w0 + j, 0, nw - 1)])
        return got[j]

    def le32(m):
        if nbytes % 4 == 0:               # every value starts a word
            return word(m // 4)
        # byte off + m: word (m + p) >> 2 where p = off & 3 is per lane;
        # read the two words that can hold it and shift by the lane's phase
        j, s = divmod(m, 4)
        a, b, c = word(j), word(j + 1), (word(j + 2) if s else None)
        sh = ph + 8 * s                   # 0..48 bits into a:b:c
        if s == 0:
            return (a >> ph) | ((b << (31 - ph)) << 1)
        lo = jnp.where(sh >= 32, b, a)
        hi = jnp.where(sh >= 32, c, b)
        t = sh & 31
        return (lo >> t) | ((hi << (31 - t)) << 1)
    return widen(parts_of(le32, nbytes, big_endian))


def byte_window(words, at, cap, nbytes, big_endian):
    """The parent: bytes_of_words + _gather_window + read_le /
    read_be_signed / read_be_limbs, offsets per lane in int64."""
    shifts = jnp.arange(4, dtype=jnp.int32) * 8
    bytes_all = ((words[:, None] >> shifts) & 0xFF).reshape(-1)
    nb = bytes_all.shape[0]
    pos = jnp.arange(cap, dtype=jnp.int64)
    off = at[0].astype(jnp.int64) * 4 + (pos - at[1]) * nbytes

    def win(o, w):
        idx = o[:, None] + jnp.arange(w, dtype=jnp.int64)
        return bytes_all[jnp.clip(idx, 0, nb - 1)].astype(jnp.int64)

    def be(o, w):
        k = (w - 1 - jnp.arange(w, dtype=jnp.int64)) * 8
        v = jnp.sum(win(o, w) << k, axis=1)
        return v if w >= 8 else v - ((v >> (8 * w - 1)) << (8 * w))

    if not big_endian:
        v = jnp.sum(win(off, nbytes)
                    << (jnp.arange(nbytes, dtype=jnp.int64) * 8), axis=1)
        return v.astype(jnp.int32).astype(jnp.int64) if nbytes == 4 else v
    if nbytes <= 8:
        return be(off, nbytes)
    hi = be(off, nbytes - 8)
    k = (7 - jnp.arange(8, dtype=jnp.int64)) * 8
    return hi, jnp.sum(win(off + nbytes - 8, 8) << k, axis=1)


VARIANTS = [
    ("byte_window", byte_window),
    ("word_gathers", word_gathers),
    ("cols_strided.ilv_stack", grouped(cols_strided, ilv_stack)),
    ("cols_strided.ilv_pad", grouped(cols_strided, ilv_pad)),
    ("cols_reshape.ilv_stack", grouped(cols_reshape, ilv_stack)),
    ("cols_transpose.ilv_stack", grouped(cols_transpose, ilv_stack)),
    ("cols_transpose.ilv_pad", grouped(cols_transpose, ilv_pad)),
    ("cols_strided.ilv_stack.roll",
     grouped(cols_strided, ilv_stack, shift_lanes_roll)),
    ("bytes_reshape", bytes_reshape),
    ("bytes_strided", bytes_strided),
]
# (bytes a value, big-endian): INT32, decimal(15,2), INT64, decimal(30,x)
SHAPES = [(4, False), (7, True), (8, False), (13, True)]


def oracle(raw, nbytes, big_endian, d0, n):
    """Python integers over the bytes: lane i in [d0, n)."""
    order = "big" if big_endian else "little"
    out = []
    for i in range(d0, n):
        o = B0 + (i - d0) * nbytes
        out.append(int.from_bytes(raw[o:o + nbytes], order, signed=True))
    return out


def as_ints(got, lanes):
    if isinstance(got, tuple):
        hi = np.asarray(got[0])[lanes].tolist()
        lo = np.asarray(got[1])[lanes].astype(np.uint64).tolist()
        return [(h << 64) | l for h, l in zip(hi, lo)]
    return np.asarray(got)[lanes].tolist()


def main():
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, flush=True)
    only = set(sys.argv[1:])
    rng = np.random.default_rng(35)
    words_np = rng.integers(-(1 << 31), 1 << 31, NW).astype(np.int32)
    raw = words_np.tobytes()
    words = jax.device_put(words_np)
    results = []
    for nbytes, big_endian in SHAPES:
        for d0 in D0S:
            # lanes past n would read past the buffer: validity zeroes them
            n = min(CAP, d0 + (len(raw) - B0) // nbytes)
            want = oracle(raw, nbytes, big_endian, d0, n)
            lanes = slice(d0, n)
            at = jax.device_put(np.array([B0 // 4, d0], dtype=np.int32))
            for name, fn in VARIANTS:
                if only and name not in only:
                    continue
                jfn = jax.jit(lambda w, a, fn=fn: fn(w, a, CAP, nbytes,
                                                     big_endian))
                rec = {"bytes": nbytes, "big_endian": big_endian, "d0": d0,
                       "lanes": CAP, "lanes_compared": n - d0,
                       "variant": name}
                try:
                    t = time.perf_counter()
                    got = jax.block_until_ready(jfn(words, at))
                    rec["first_call_s"] = time.perf_counter() - t
                    times = []
                    for _ in range(9):
                        t = time.perf_counter()
                        jax.block_until_ready(jfn(words, at))
                        times.append((time.perf_counter() - t) * 1e3)
                    rec["ms_median"] = statistics.median(times)
                    rec["ms_min"] = min(times)
                    rec["ms_max"] = max(times)
                    have = as_ints(got, lanes)
                    rec["lanes_differ_from_python_integers"] = sum(
                        a != b for a, b in zip(have, want))
                except Exception as e:  # noqa: BLE001 - a spelling the compiler refuses is a finding
                    rec["error"] = repr(e)[:400]
                results.append(rec)
                print(json.dumps(rec), flush=True)
    out = os.path.join("chiprun_out", "pr35")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "probe_plain.json"), "w") as f:
        json.dump({"device": dev.device_kind, "platform": dev.platform,
                   "words": NW, "lanes": CAP, "region_byte_offset": B0,
                   "results": results}, f, indent=1)


if __name__ == "__main__":
    sys.exit(main())

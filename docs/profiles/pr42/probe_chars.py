"""The microbenchmark behind ops/rle.gather_char_words, run on the chip:
how a string value's staged bytes become its row of the u8[cap, char_cap]
character matrix. The values lie end to end in the int32 staging words from
an arbitrary byte (pyarrow's concatenated-bytes page), so a value of
L <= char_cap bytes at byte b lies in the char_cap/4 + 1 words from b >> 2.

Shapes: Q13's ``o_comment`` (375,000 values of 19-78 bytes in a 393,216-lane
bucket, char_cap 80) and Q18's ``c_name`` (150,000 values of 18 bytes in a
163,840-lane bucket, char_cap 24). Every byte is drawn from 0..255, so the
sign of an int32 word is set wherever its top byte is 0x80 or more.

  bytes_gather   the parent: ``bytes_of_words`` (one int32 a byte) and a
                 gather of one element a byte lane, int64 indices
  words_elem     (a) ``words[w0[:, None] + arange(nw)]``: one element a word
  words_slice    (b) ``lax.gather`` of one window of nw words a row
  *_t            (c) the same gathers computed transposed: [nw, cap], the
                 realignment by static row slices, [char_cap, cap], one
                 transpose at the end
  .stack / .bitcast / .repeat   how a row of realigned uint32 words becomes
                 its bytes where the rows stay minor: a stack on a minor axis
                 of 4 and a reshape, ``bitcast_convert_type`` to u8, or each
                 word broadcast to its four byte lanes and shifted

Every spelling is held bit for bit to numpy over the buffer's bytes on every
row, padding rows included. Milliseconds, median of 9 calls after one warm
call; ``gathered`` is the elements the gather addresses.
Run from the root of a checkout, on the chip: python3 docs/profiles/pr42/probe_chars.py
(``PROBE_SCALE=64 JAX_PLATFORMS=cpu`` rehearses it.)
Writes chiprun_out/pr42/probe_chars.json."""
import json
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
# (name, cap, rows, char_cap, min length, max length)
SHAPES = [("q13_o_comment", 393_216 // SCALE, 375_000 // SCALE, 80, 19, 78),
          ("q18_c_name", 163_840 // SCALE, 150_000 // SCALE, 24, 18, 18)]
U32 = jnp.uint32


def bytes_gather(words, starts, lens, char_cap):
    shifts = jnp.arange(4, dtype=jnp.int32) * 8
    ba = ((words[:, None] >> shifts) & 0xFF).reshape(-1)
    nb = ba.shape[0]
    idx = starts[:, None] + jnp.arange(char_cap, dtype=jnp.int64)
    mask = jnp.arange(char_cap, dtype=jnp.int32) < lens[:, None]
    g = ba[jnp.clip(idx, 0, nb - 1)]
    return jnp.where(mask, g, 0).astype(jnp.uint8)


def _prep(words, starts, char_cap):
    nw = char_cap // 4 + 1
    wp = lax.bitcast_convert_type(jnp.pad(words, (0, nw)), U32)
    w0 = (starts >> 2).astype(jnp.int32)
    s = ((starts & 3) * 8).astype(U32)
    return nw, wp, w0, s


def _realign(lo, hi, s):
    # logical shifts in uint32; (hi << (24 - s)) << 8 is hi << (32 - s),
    # and 0 at phase 0, where one shift by 32 is not defined
    return (lo >> s) | ((hi << (24 - s)) << 8)


def _gather_elem(wp, w0, nw):
    return wp[w0[:, None] + jnp.arange(nw, dtype=jnp.int32)]


def _gather_slice(wp, w0, nw):
    dn = lax.GatherDimensionNumbers(offset_dims=(1,),
                                    collapsed_slice_dims=(),
                                    start_index_map=(0,))
    return lax.gather(wp, w0[:, None], dn, slice_sizes=(nw,),
                      mode=lax.GatherScatterMode.CLIP)


def _unpack_stack(ow):
    cap = ow.shape[0]
    b = jnp.stack([(ow >> (8 * r)) & 0xFF for r in range(4)], axis=-1)
    return b.reshape(cap, -1)


def _unpack_bitcast(ow):
    cap = ow.shape[0]
    return lax.bitcast_convert_type(ow, jnp.uint8).reshape(cap, -1)


def _unpack_repeat(ow):
    cap, m = ow.shape
    rep = jnp.broadcast_to(ow[:, :, None], (cap, m, 4)).reshape(cap, 4 * m)
    sh = jnp.tile(jnp.arange(4, dtype=U32) * 8, m)
    return (rep >> sh) & 0xFF


def rows_minor(gather, unpack):
    def fn(words, starts, lens, char_cap):
        nw, wp, w0, s = _prep(words, starts, char_cap)
        g = gather(wp, w0, nw)
        ow = _realign(g[:, :-1], g[:, 1:], s[:, None])
        b = unpack(ow)
        mask = jnp.arange(char_cap, dtype=jnp.int32) < lens[:, None]
        return jnp.where(mask, b, 0).astype(jnp.uint8)
    return fn


def transposed(gather_t, out_dtype):
    def fn(words, starts, lens, char_cap):
        nw, wp, w0, s = _prep(words, starts, char_cap)
        cols = gather_t(wp, w0, nw)                       # [nw, cap]
        ow = _realign(cols[:-1], cols[1:], s[None, :])    # [char_cap/4, cap]
        b = jnp.stack([(ow >> (8 * r)) & 0xFF for r in range(4)], axis=1)
        b = b.reshape(char_cap, -1)                       # [char_cap, cap]
        mask = jnp.arange(char_cap, dtype=jnp.int32)[:, None] < lens[None, :]
        b = jnp.where(mask, b, 0).astype(out_dtype)
        return b.T.astype(jnp.uint8)
    return fn


def _elem_t(wp, w0, nw):
    return jnp.stack([wp[w0 + k] for k in range(nw)])


def _slice_t(wp, w0, nw):
    return _gather_slice(wp, w0, nw).T


VARIANTS = [
    ("bytes_gather", bytes_gather),
    ("words_elem.stack", rows_minor(_gather_elem, _unpack_stack)),
    ("words_elem.bitcast", rows_minor(_gather_elem, _unpack_bitcast)),
    ("words_elem.repeat", rows_minor(_gather_elem, _unpack_repeat)),
    ("words_slice.stack", rows_minor(_gather_slice, _unpack_stack)),
    ("words_slice.bitcast", rows_minor(_gather_slice, _unpack_bitcast)),
    ("words_elem_t.u8", transposed(_elem_t, jnp.uint8)),
    ("words_elem_t.i32", transposed(_elem_t, jnp.int32)),
    ("words_slice_t.u8", transposed(_slice_t, jnp.uint8)),
]


def make_inputs(cap, rows, char_cap, lo, hi, rng):
    lens = np.zeros(cap, dtype=np.int32)
    lens[:rows] = rng.integers(lo, hi + 1, rows)
    base = 4 * 1_000 + 1  # the page starts off a word boundary
    starts = base + np.concatenate([[0], np.cumsum(lens[:-1],
                                                   dtype=np.int64)])
    nbytes = int(starts[-1] + lens[-1])
    raw = np.zeros((nbytes + 3) // 4 * 4 + 64, dtype=np.uint8)
    raw[base:nbytes] = rng.integers(0, 256, nbytes - base)
    return raw, starts.astype(np.int64), lens


def oracle(raw, starts, lens, char_cap):
    out = np.zeros((starts.shape[0], char_cap), dtype=np.uint8)
    for k in range(char_cap):
        on = lens > k
        out[on, k] = raw[starts[on] + k]
    return out


def main():
    dev = jax.devices()[0]
    rng = np.random.default_rng(42)
    results = []
    for name, cap, rows, char_cap, lo, hi in SHAPES:
        raw, starts_np, lens_np = make_inputs(cap, rows, char_cap, lo, hi,
                                              rng)
        want = oracle(raw, starts_np, lens_np, char_cap)
        words = jnp.asarray(raw.view(np.int32))
        starts = jnp.asarray(starts_np)
        lens = jnp.asarray(lens_np)
        nw = char_cap // 4 + 1
        for vname, fn in VARIANTS:
            rec = {"shape": name, "cap": cap, "char_cap": char_cap,
                   "variant": vname,
                   "gathered": cap * (char_cap if vname == "bytes_gather"
                                      else nw)}
            try:
                jfn = jax.jit(lambda w, s, n, fn=fn, c=char_cap:
                              fn(w, s, n, c))
                t = time.perf_counter()
                got = jax.block_until_ready(jfn(words, starts, lens))
                rec["first_call_s"] = time.perf_counter() - t
                rec["equal"] = bool(np.array_equal(np.asarray(got), want))
                times = []
                for _ in range(9):
                    t = time.perf_counter()
                    jax.block_until_ready(jfn(words, starts, lens))
                    times.append((time.perf_counter() - t) * 1e3)
                rec["ms_median"] = statistics.median(times)
                rec["ms_min"] = min(times)
                rec["ms_max"] = max(times)
            except Exception as e:  # noqa: BLE001 - a spelling may not lower
                rec["error"] = repr(e)[:300]
            print(json.dumps(rec), flush=True)
            results.append(rec)
    out = os.path.join("chiprun_out", "pr42")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "probe_chars.json"), "w") as f:
        json.dump({"device": dev.device_kind, "platform": dev.platform,
                   "jax": jax.__version__, "scale": SCALE,
                   "results": results}, f, indent=1)
    return 0 if all(r.get("equal") for r in results) else 1


if __name__ == "__main__":
    sys.exit(main())

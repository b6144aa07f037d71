"""PR 36's microbenchmark, run on the chip before any cell: how a window's
running sum of a decimal carries 128 bits on a chip without 64-bit lanes, and
what a running max costs by the width of its rank words.

One sorted stream of 786,432 lanes in partitions of about 31 rows (query 51's
shape), values over the whole of their type. The source is an int64 column
(``decimal(17,2)`` summed into ``decimal(27,2)``: query 51's own case) or a
two-limb column (``decimal(27,2)`` into ``decimal(37,2)``).

  scan_pairs      one ``lax.associative_scan`` over (restart flag, hi, lo)
                  whose combine adds with carry: 64-bit adds and an unsigned
                  compare at every level of the scan
  digits          the source split into 32-bit digits (2 of an int64, 4 of two
                  limbs; the top one signed), each prefix-summed in int64 by
                  one global cumsum less the prefix at its partition's start,
                  recombined with carries once a lane
  digits_stacked  the same digits as the columns of one (lanes, digits)
                  matrix under one cumsum along the lanes and one gather of
                  rows (committed: ``exec/window._sum_limbs`` over
                  ``_prefix_in_part``)

  digits_cummax   the digits made non-negative (the signed top one biased by
                  2**31, taken off again by the row's place in its
                  partition), so that a digit's prefix at its partition's
                  start is a running maximum and not a gather

  max_assoc64     the parent's ``_seg_running_extreme``: one
                  ``lax.associative_scan`` over (partition, valid, position,
                  words), here with the two uint64 rank words of a two-limb
                  value. Compiled ahead of time for the chip it is 146 MB of
                  code at this size (112 MB with one uint64 word, the
                  parent's own case; the doubling scan 3 MB)
  max_assoc32     the same over four uint32 words
  max_doubling64  the doubling scan: a row takes the better of its own winner
                  and the one 2**k rows back, k = 0, 1, ... until 2**k covers
                  the longest partition (9 steps here), each step elementwise
                  (committed: ``exec/window._seg_running_extreme``)
  max_doubling32  the same over four uint32 words

Every spelling is held to Python integers on every lane. Milliseconds, median
of 9 calls after one warm call. Run from the root of a checkout:
chiprun -- python3 docs/profiles/pr36/probe_prefix128.py
(``PROBE_SCALE=64 JAX_PLATFORMS=cpu`` rehearses it.) The spellings run in
three child processes, one after the other (the parent stays off JAX), so
that one the TPU compiler dies on costs its group and not the call:
``digits_cummax`` over two limbs segfaults it ahead of time on a CPU, every
time, and is not run; the first call on the chip died in the compiler on
``digits`` over two limbs, which compiled and ran in the second. Writes chiprun_out/pr36/probe_prefix128.json."""
import json
import os
import statistics
import subprocess
import sys
import time

GROUPS = ("sums.int64", "sums.limbs", "max")
OUT = "chiprun_out/pr36/probe_prefix128.json"

if __name__ == "__main__" and len(sys.argv) == 1:
    # the parent: one child a group, their lines merged
    result = {"ms": {}, "groups": {}}
    for group in GROUPS:
        p = subprocess.run([sys.executable, __file__, group],
                           capture_output=True, text=True)
        sys.stdout.write(p.stdout)
        result["groups"][group] = p.returncode
        for line in p.stdout.splitlines():
            if line.startswith("{"):
                piece = json.loads(line)
                result["ms"].update(piece.pop("ms"))
                result.update(piece)
        if p.returncode:
            sys.stdout.write(f"{group}: exit code {p.returncode}\n"
                             + p.stderr[-1500:] + "\n")
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as f:
        json.dump(result, f, indent=1)
    sys.exit(0)

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

sys.path.insert(0, os.getcwd())
from spark_rapids_tpu.exec import window as W  # noqa: E402
from spark_rapids_tpu.ops import int128 as I  # noqa: E402

SCALE = int(os.environ.get("PROBE_SCALE", "1"))
CAP = 786_432 // SCALE
M32 = jnp.int64(0xFFFFFFFF)
SH = jnp.int64(32)


def digits_of(limbs):
    if len(limbs) == 2:
        hi, lo = limbs
        return [lo & M32, (lo >> SH) & M32, hi & M32, hi >> SH]
    return [limbs[0] & M32, limbs[0] >> SH]


def recombine(sums):
    """exec/window._sum_limbs' carry chain."""
    words, carry = [], jnp.zeros_like(sums[0])
    for total in sums:
        t = total + carry
        words.append(t & M32)
        carry = t >> SH
    while len(words) < 4:
        words.append(carry & M32)
        carry = carry >> SH
    return words[2] | (words[3] << SH), words[0] | (words[1] << SH)


def scan_pairs(start, start_of_row, *limbs):
    if len(limbs) == 1:
        hi, lo = I.from_i64(jnp, limbs[0])
    else:
        hi, lo = limbs

    def combine(a, b):
        af, ah, al = a
        bf, bh, bl = b
        sh, sl = I.add(jnp, ah, al, bh, bl)
        return af | bf, jnp.where(bf, bh, sh), jnp.where(bf, bl, sl)
    _, rhi, rlo = lax.associative_scan(combine, (start, hi, lo))
    return rhi, rlo


def digits(start, start_of_row, *limbs):
    return recombine([W._prefix_in_part(d, start_of_row)
                      for d in digits_of(limbs)])


def digits_stacked(start, start_of_row, *limbs):
    m = W._prefix_in_part(jnp.stack(digits_of(limbs), axis=1), start_of_row)
    return recombine([m[:, k] for k in range(m.shape[1])])


def digits_cummax(start, start_of_row, *limbs):
    ds = digits_of(limbs)
    ds[-1] = ds[-1] + jnp.int64(1 << 31)
    rows = (jnp.arange(start.shape[0], dtype=jnp.int32) - start_of_row
            + 1).astype(jnp.int64)
    sums = []
    for d in ds:
        incl = jnp.cumsum(d)
        sums.append(incl - lax.cummax(jnp.where(start, incl - d, 0)))
    sums[-1] = sums[-1] - (rows << jnp.int64(31))
    return recombine(sums)


def assoc_running_max(part_id, words, valid):
    """The parent's exec/window._seg_running_extreme (is_min False)."""
    pos = jnp.arange(part_id.shape[0], dtype=jnp.int32)

    def combine(a, b):
        a_id, a_valid, a_p = a[0], a[1], a[2]
        b_id, b_valid, b_p = b[0], b[1], b[2]
        aw, bw = a[3:], b[3:]
        a_live = a_valid & (b_id == a_id)
        better = jnp.zeros_like(a_valid)
        eq = jnp.ones_like(a_valid)
        for wa, wb in zip(aw, bw):
            better = better | (eq & (wa > wb))
            eq = eq & (wa == wb)
        take_a = a_live & ((~b_valid) | better)
        return tuple([b_id, a_live | b_valid, jnp.where(take_a, a_p, b_p)]
                     + [jnp.where(take_a, wa, wb) for wa, wb in zip(aw, bw)])
    res = lax.associative_scan(combine, tuple([part_id, valid, pos] + words))
    return res[2], res[1]


def words64(hi, lo):
    return [hi.view(jnp.uint64) ^ jnp.uint64(1 << 63), lo.view(jnp.uint64)]


def words32(hi, lo):
    uh, ul = words64(hi, lo)
    return [(uh >> jnp.uint64(32)).astype(jnp.uint32), uh.astype(jnp.uint32),
            (ul >> jnp.uint64(32)).astype(jnp.uint32), ul.astype(jnp.uint32)]


def max_assoc64(part_id, valid, hi, lo):
    return assoc_running_max(part_id, words64(hi, lo), valid)


def max_assoc32(part_id, valid, hi, lo):
    return assoc_running_max(part_id, words32(hi, lo), valid)


def max_doubling64(part_id, valid, hi, lo):
    return W._seg_running_extreme(part_id, words64(hi, lo), valid, False)


def max_doubling32(part_id, valid, hi, lo):
    return W._seg_running_extreme(part_id, words32(hi, lo), valid, False)


def timed(fn, args):
    out = jax.block_until_ready(fn(*args))
    ms = []
    for _ in range(9):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ms.append((time.perf_counter() - t0) * 1e3)
    return out, statistics.median(ms)


def main(group):
    rng = np.random.default_rng(36)
    start = rng.random(CAP) < 1 / 31
    start[0] = True
    pos = np.arange(CAP)
    start_of_row = np.maximum.accumulate(np.where(start, pos, -1)).astype(np.int32)
    part_id = (np.cumsum(start) - 1).astype(np.int32)
    # decimal(17,2) and decimal(27,2) values, both signs
    small = rng.integers(-10**17 + 1, 10**17, CAP)
    wide = [int(a) * 10**10 + int(b) for a, b in zip(
        small.tolist(), rng.integers(0, 10**10, CAP).tolist())]
    whi, wlo = I.from_pyints(wide)
    result = {"device": str(jax.devices()[0]), "lanes": CAP, "ms": {}}
    if group.startswith("sums"):
        src = group.split(".")[1]
        limbs, ints = {"int64": ([small], small.tolist()),
                       "limbs": ([whi, wlo], wide)}[src]
        want, total = [], 0
        for s, v in zip(start.tolist(), ints):
            total = v if s else total + v
            want.append(total)
        args = [jnp.asarray(start), jnp.asarray(start_of_row)] + [
            jnp.asarray(a) for a in limbs]
        fns = [scan_pairs, digits_stacked, digits]
        if src == "int64":
            fns.append(digits_cummax)
        for fn in fns:
            (rhi, rlo), ms = timed(jax.jit(fn), args)
            got = I.to_pyints(np.asarray(rhi), np.asarray(rlo)).tolist()
            assert got == want, (fn.__name__, src)
            result["ms"][f"{fn.__name__}.{src}"] = ms
            print(f"{fn.__name__:16s} {src:6s} {ms:9.3f} ms", flush=True)
            print(json.dumps(result), flush=True)
        return
    valid = rng.random(CAP) < 0.8
    want_pos, best, at = [], None, 0
    for i, (s, ok, v) in enumerate(zip(start.tolist(), valid.tolist(), wide)):
        if s:
            best = None
        if ok and (best is None or v > best):
            best, at = v, i
        want_pos.append(at if best is not None else -1)
    args = [jnp.asarray(part_id), jnp.asarray(valid), jnp.asarray(whi),
            jnp.asarray(wlo)]
    for fn in (max_doubling64, max_doubling32, max_assoc64, max_assoc32):
        (win, has), ms = timed(jax.jit(fn), args)
        got = np.where(np.asarray(has), np.asarray(win), -1).tolist()
        assert got == want_pos, fn.__name__
        result["ms"][fn.__name__] = ms
        print(f"{fn.__name__:16s} limbs  {ms:9.3f} ms", flush=True)
        print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main(sys.argv[1])

"""PR 34's microbenchmark, run on the chip before any cell: which shape of the
conditional semi/anti mask. One stream chunk of Q21's semi join at SF1
(1,572,864 left lanes of which 63% late, 6,291,456 build lanes of 6,001,215
lines, 1-7 lines an order, l_suppkey uniform over 10,000), through the
engine's own count program, then three spellings of the mask:

  loop      ops/join.py's committed program: fori_loop over the ranks 0..max_m-1
  unrolled  the same body, statically unrolled over the bucketed max_m (8)
  expand    the candidate pairs expanded into an output bucket as the inner
            join's gather does (li by one scatter + cummax), the condition over
            the gathered pair, any-reduced by a prefix sum read at the extents

Writes chiprun_out/pr34/probe_cond_mask.json."""
import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.getcwd())
from spark_rapids_tpu.columnar.device import (DeviceColumn, bucket_capacity,
                                              take_columns)
from spark_rapids_tpu.ops import exprs as X
from spark_rapids_tpu.ops import groupby as G
from spark_rapids_tpu.ops import join as J
from spark_rapids_tpu.sql import expressions as E
from spark_rapids_tpu.sql import types as T

SCALE = int(os.environ.get("PROBE_SCALE", "1"))
CAP_L, CAP_R, LINES = 2 * 786_432 // SCALE, 8 * 786_432 // SCALE, 6_001_215 // SCALE
rng = np.random.default_rng(34)
counts = rng.integers(1, 8, 1_600_000 // SCALE)
okey = np.repeat(np.arange(len(counts), dtype=np.int64) * 4 + 1, counts)[:LINES]
supp = rng.integers(1, 10_001, len(okey))
late = rng.random(len(okey)) < 0.632
pick = np.sort(rng.choice(len(okey), CAP_L, replace=False))


def col(a, cap):
    data = np.zeros(cap, dtype=np.int64)
    data[:len(a)] = a
    valid = np.zeros(cap, dtype=bool)
    valid[:len(a)] = True
    return DeviceColumn(T.LongT, jnp.asarray(data), jnp.asarray(valid)), valid


lk, l_valid = col(okey[pick], CAP_L)
ls, _ = col(supp[pick], CAP_L)
rk, r_valid = col(okey, CAP_R)
rs, _ = col(supp, CAP_R)
active_l = jnp.asarray(l_valid & np.pad(late[pick], (0, 0)))
active_r = jnp.asarray(r_valid)
cols_l, cols_r = [lk, ls], [rk, rs]
keys_l = (E.BoundReference(0, T.LongT, True),)
keys_r = (E.BoundReference(0, T.LongT, True),)
cond = E.Not(E.EqualTo(E.BoundReference(3, T.LongT, True),
                       E.BoundReference(1, T.LongT, True)))
lits = X.literal_values([cond])
salt = G.kernel_salt()


def timed(fn, *args, n=9):
    out = fn(*args)
    jax.block_until_ready(out)
    t = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        t.append((time.perf_counter() - t0) * 1e3)
    return out, statistics.median(t)


with G.nan_scope(salt[0]):
    count_fn = J._build_count_fn(keys_l, keys_r, "leftsemi", (False,))
    (total_pairs, _n, max_m, m, _off, base, order_r, _eo, _mr), probe_ms = \
        timed(count_fn, cols_l, active_l, [], cols_r, active_r, [], n=5)
    total, deepest = int(total_pairs), int(max_m)

    loop_fn = J._build_cond_mask_fn(cond, 2, (1,), 2, "leftsemi")
    loop_out, loop_ms = timed(loop_fn, cols_l, [rs], active_l, lits, m, base,
                              order_r, max_m)

    steps = 1 << max(0, deepest - 1).bit_length()

    def unrolled(cols_l, cols_r_used, active_l, lits, m, base, order_r):
        cap_r = order_r.shape[0]
        found = jnp.zeros(CAP_L, dtype=jnp.bool_)
        for j in range(steps):
            has = j < m
            ri = jnp.take(order_r, jnp.clip(base + j, 0, cap_r - 1)
                          .astype(jnp.int32))
            got = take_columns(cols_r_used, jnp.where(has, ri, 0), valid_at=has)
            p = X.dev_eval(cond, X.Ctx(list(cols_l) + [None] + got, CAP_L,
                                       (cond,), lits))
            found = found | (has & p.validity & X._as_bool(p))
        return active_l & found
    unrolled_out, unrolled_ms = timed(jax.jit(unrolled), cols_l, [rs],
                                      active_l, lits, m, base, order_r)

    out_cap = bucket_capacity(max(1, total))

    def expand(cols_l, cols_r_used, active_l, lits, m, base, order_r, total):
        cap_r = order_r.shape[0]
        offsets = jnp.cumsum(m) - m
        rows = jnp.arange(CAP_L, dtype=jnp.int32)
        at = jnp.where(m > 0, offsets, out_cap).astype(jnp.int32)
        li = jax.lax.cummax(jnp.zeros(out_cap, jnp.int32).at[at].max(
            rows, mode="drop"))
        s = jnp.arange(out_cap, dtype=jnp.int64)
        in_pairs = s < total
        k = s - jnp.take(offsets, li)
        ri = jnp.take(order_r, jnp.clip(jnp.take(base, li) + k, 0, cap_r - 1)
                      .astype(jnp.int32))
        left = take_columns([cols_l[1]], jnp.where(in_pairs, li, 0),
                            valid_at=in_pairs)
        right = take_columns(cols_r_used, jnp.where(in_pairs, ri, 0),
                             valid_at=in_pairs)
        p = X.dev_eval(cond, X.Ctx([None] + left + [None] + right, out_cap,
                                   (cond,), lits))
        passed = jnp.cumsum((in_pairs & p.validity & X._as_bool(p))
                            .astype(jnp.int32))
        last = jnp.clip(offsets + m - 1, 0, out_cap - 1).astype(jnp.int32)
        first = jnp.clip(offsets - 1, 0, out_cap - 1).astype(jnp.int32)
        found = (m > 0) & (jnp.take(passed, last) - jnp.where(
            offsets > 0, jnp.take(passed, first), 0) > 0)
        return active_l & found
    expand_out, expand_ms = timed(jax.jit(expand), cols_l, [rs], active_l,
                                  lits, m, base, order_r, total_pairs)

# the answer, from numpy: a late picked line with a line of another supplier
order_of = okey
first_supp = {}
lo = np.full(int(okey.max()) + 1, np.iinfo(np.int64).max)
hi = np.zeros(int(okey.max()) + 1, dtype=np.int64)
np.minimum.at(lo, okey, supp)
np.maximum.at(hi, okey, supp)
want = np.zeros(CAP_L, dtype=bool)
want[:len(pick)] = late[pick] & (lo[okey[pick]] != hi[okey[pick]])
result = {
    "device": str(jax.devices()[0].device_kind), "cap_l": CAP_L,
    "cap_r": CAP_R, "active_left": int(np.asarray(active_l).sum()),
    "candidate_pairs": total, "max_m": deepest, "unrolled_steps": steps,
    "expand_out_cap": out_cap,
    "ms": {"srt_join_probe": probe_ms, "loop": loop_ms,
           "unrolled": unrolled_ms, "expand": expand_ms},
    "lanes_differing_from_numpy": {
        "loop": int((np.asarray(loop_out) != want).sum()),
        "unrolled": int((np.asarray(unrolled_out) != want).sum()),
        "expand": int((np.asarray(expand_out) != want).sum())},
}
os.makedirs("chiprun_out/pr34", exist_ok=True)
with open("chiprun_out/pr34/probe_cond_mask.json", "w") as f:
    json.dump(result, f, indent=1)
print(json.dumps(result))

#!/usr/bin/env python3
"""A microbenchmark: what a sparse build side costs its join, probed
as it is and after its compaction to its rows' bucket, at the two sites
where the count is already on the host.

    python3 docs/profiles/pr39/probe_builds.py            # on the chip
    PROBE_SCALE=64 JAX_PLATFORMS=cpu python3 docs/profiles/pr39/probe_builds.py

The programs timed are the engine's own (``ops/join.py``), over int64 keys
in the cells' shapes at scale factor 1:

- ``q18_semi_join``: Q18's ``o_orderkey IN (... HAVING sum(l_quantity) >
  300)``, the program ``srt_join_mask``. The build side is the HAVING's
  output, ONE batch of ``bucket_capacity(1,500,000)`` = 1,572,864 lanes with
  82 orders active (the most of 59-82 that qualify); the stream is
  ``orders`` in its four pinned partitions of 374,000 / 376,000 rows
  (393,216 lanes each), probed one partition at a time.
- ``q21_supplier_join``: Q21's ``supplier ⋈ l1'``, the program
  ``srt_join_probe`` (inner), with supplier's 10,000 rows (10,240 lanes) as
  the stream; the build side is the anti join's output, ONE batch of
  4,194,304 lanes and four int64 columns with 150,827 rows active; and
  ``build_key_max_multiplicity`` (``srt_join_build``) over the same build.
- ``compaction``: four spellings of the same stable compaction of those two
  build sides to their rows' bucket, each checked equal to the first:
  ``sort_gather_all`` (``srt_shrink`` as it was: a stable sort of the
  lanes, a gather of every lane, then a slice), ``sort_gather_prefix``
  (``srt_shrink`` now: the gather of the first ``bucket`` entries
  only), ``cumsum_scatter`` (each active lane's rank by a prefix sum, its
  index scattered to that rank) and ``cumsum_search`` (the rank's first
  lane found by ``searchsorted`` over the prefix sum).

Writes ``chiprun_out/pr39/probe_builds.json`` and prints it; times are
milliseconds, the median of 5 after one warm-up, ``block_until_ready``
inside.
"""
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from spark_rapids_tpu.columnar.device import (DeviceBatch,  # noqa: E402
                                              DeviceColumn, bucket_capacity,
                                              flatten_batch, shrink_to_bucket)
from spark_rapids_tpu.ops import exprs as X  # noqa: E402
from spark_rapids_tpu.ops import join as J  # noqa: E402
from spark_rapids_tpu.ops.lanes import fused_take  # noqa: E402
from spark_rapids_tpu.sql import expressions as E  # noqa: E402
from spark_rapids_tpu.sql import types as T  # noqa: E402

SCALE = int(os.environ.get("PROBE_SCALE", "1"))
REPEATS = 5
ORDERS = 1500000 // SCALE
PARTITIONS = (374000 // SCALE, 374000 // SCALE, 376000 // SCALE,
              376000 // SCALE)
Q18_QUALIFYING = 82
SUPPLIERS = 10000 // SCALE
L1_LANES = 4194304 // SCALE
L1_ROWS = 150827 // SCALE


def orderkeys(n):
    """dbgen's sparse o_orderkey: 8 of every 32 keys."""
    order = np.arange(n, dtype=np.int64)
    return (order // 8) * 32 + order % 8 + 1


def batch(cols, active, counted=True):
    """int64 columns over ``len(active)`` lanes; the count rides with it
    when ``counted``, as after the AQE demotion's read."""
    active = np.asarray(active)
    dev_active = jnp.asarray(active)
    columns, fields = [], []
    for i, c in enumerate(cols):
        data = np.zeros(len(active), dtype=np.int64)
        data[:len(c)] = c
        columns.append(DeviceColumn(T.LongT, jnp.asarray(data), dev_active))
        fields.append(T.StructField(f"c{i}", T.LongT, True))
    return DeviceBatch(T.StructType(fields), columns, dev_active,
                       int(active.sum()) if counted else None)


def timed(fn):
    jax.block_until_ready(fn())
    out = []
    for _ in range(REPEATS):
        t = time.perf_counter()
        jax.block_until_ready(fn())
        out.append((time.perf_counter() - t) * 1e3)
    return statistics.median(out)


def _tail_zeroed(new_active, gathered):
    return tuple(jnp.where(new_active, g, jnp.zeros((), g.dtype))
                 for g in gathered)


def spellings(cap):
    """Four stable compactions of (active, *arrays) into ``cap`` lanes."""
    def sort_idx(active):
        pos = jnp.arange(active.shape[0], dtype=jnp.int32)
        return jax.lax.sort((~active, pos), num_keys=1, is_stable=True)[1]

    def sort_gather_all(active, *arrs):
        n = active.shape[0]
        idx = sort_idx(active)
        new_active = jnp.arange(n, dtype=jnp.int32) < jnp.sum(active)
        outs = _tail_zeroed(new_active, fused_take(list(arrs), idx))
        return new_active[:cap], tuple(a[:cap] for a in outs)

    def sort_gather_prefix(active, *arrs):
        idx = sort_idx(active)[:cap]
        new_active = jnp.arange(cap, dtype=jnp.int32) < jnp.sum(active)
        return new_active, _tail_zeroed(new_active,
                                        fused_take(list(arrs), idx))

    def cumsum_scatter(active, *arrs):
        n = active.shape[0]
        rank = jnp.cumsum(active.astype(jnp.int32)) - 1
        at = jnp.where(active, rank, cap)
        idx = jnp.zeros(cap, jnp.int32).at[at].set(
            jnp.arange(n, dtype=jnp.int32), mode="drop")
        new_active = jnp.arange(cap, dtype=jnp.int32) < rank[-1] + 1
        return new_active, _tail_zeroed(new_active,
                                        fused_take(list(arrs), idx))

    def cumsum_search(active, *arrs):
        n = active.shape[0]
        csum = jnp.cumsum(active.astype(jnp.int32))
        idx = jnp.clip(jnp.searchsorted(
            csum, jnp.arange(1, cap + 1, dtype=jnp.int32), side="left"),
            0, n - 1).astype(jnp.int32)
        new_active = jnp.arange(cap, dtype=jnp.int32) < csum[-1]
        return new_active, _tail_zeroed(new_active,
                                        fused_take(list(arrs), idx))

    return {f.__name__: jax.jit(f) for f in (
        sort_gather_all, sort_gather_prefix, cumsum_scatter, cumsum_search)}


def price_compaction(b):
    cap = bucket_capacity(b.row_count())
    flat, _spec = flatten_batch(b)
    fns = spellings(cap)
    first = None
    res = {"lanes": b.capacity, "rows": b.row_count(), "bucket": cap,
           "arrays": len(flat)}
    for name, fn in fns.items():
        out = jax.device_get(fn(b.active, *flat))
        if first is None:
            first = out
        else:
            assert all(np.array_equal(x, y) for x, y in zip(
                jax.tree_util.tree_leaves(out),
                jax.tree_util.tree_leaves(first))), name
        res[name + "_ms"] = timed(lambda fn=fn: fn(b.active, *flat))
    engine = shrink_to_bucket(b)
    assert np.array_equal(np.asarray(engine.active), first[0])
    res["engine_srt_shrink_ms"] = timed(
        lambda: [c.arrays() for c in shrink_to_bucket(b).columns])
    return res


def main():
    device = jax.devices()[0]
    rng = np.random.default_rng(39)
    key = (E.BoundReference(0, T.LongT, True),)
    lits = X.literal_values(list(key))
    result = {"device": {"platform": device.platform,
                         "kind": device.device_kind},
              "scale": SCALE, "unit": "ms", "repeats": REPEATS}

    # Q18: the semi join's mask, a stream partition at a time
    okeys = orderkeys(ORDERS)
    having_lanes = bucket_capacity(ORDERS)
    having_active = np.zeros(having_lanes, dtype=bool)
    having_active[rng.choice(ORDERS, Q18_QUALIFYING, replace=False)] = True
    sparse = batch([okeys], having_active)
    compact = shrink_to_bucket(sparse)
    mask = J._build_mask_fn(key, key, "leftsemi", (False,))
    parts, start = [], 0
    for n in PARTITIONS:
        lanes = bucket_capacity(n)
        parts.append(batch([okeys[start:start + n]], np.arange(lanes) < n))
        start += n

    def run_mask(stream, build):
        return mask(stream.columns, stream.active, lits, build.columns,
                    build.active, lits)
    for p in parts:
        assert np.array_equal(np.asarray(run_mask(p, sparse)),
                              np.asarray(run_mask(p, compact)))
    sparse_ms = [timed(lambda p=p: run_mask(p, sparse)) for p in parts]
    compact_ms = [timed(lambda p=p: run_mask(p, compact)) for p in parts]
    result["q18_semi_join"] = {
        "stream_partitions": len(parts),
        "stream_lanes": [p.capacity for p in parts],
        "build_rows": Q18_QUALIFYING,
        "sparse_build_lanes": sparse.capacity,
        "compact_build_lanes": compact.capacity,
        "lanes_sorted_sparse": sum(p.capacity + sparse.capacity
                                   for p in parts),
        "lanes_sorted_compact": sum(p.capacity + compact.capacity
                                    for p in parts),
        "mask_sparse_ms": sum(sparse_ms), "each_sparse_ms": sparse_ms,
        "mask_compact_ms": sum(compact_ms), "each_compact_ms": compact_ms,
        "compaction": price_compaction(sparse)}
    print("[probe] q18_semi_join: " + json.dumps(result["q18_semi_join"]),
          flush=True)

    # Q21: supplier probing what the anti join left
    l1_active = np.zeros(L1_LANES, dtype=bool)
    l1_active[rng.choice(L1_LANES, L1_ROWS, replace=False)] = True
    supp = rng.integers(1, SUPPLIERS + 1, L1_LANES)
    l1 = batch([supp, orderkeys(L1_LANES), supp * 3, supp * 7], l1_active)
    l1_compact = shrink_to_bucket(l1)
    suppliers = batch([np.arange(1, SUPPLIERS + 1, dtype=np.int64)],
                      np.arange(bucket_capacity(SUPPLIERS)) < SUPPLIERS)
    probe = J._build_count_fn(key, key, "inner", (False,))

    def run_probe(build):
        return probe(suppliers.columns, suppliers.active, lits,
                     build.columns, build.active, lits)
    assert int(run_probe(l1)[0]) == int(run_probe(l1_compact)[0])

    def mult(build):
        return J.build_key_max_multiplicity(build, list(key), (False,))()
    assert mult(l1) == mult(l1_compact)
    result["q21_supplier_join"] = {
        "stream_lanes": suppliers.capacity,
        "build_rows": L1_ROWS,
        "sparse_build_lanes": l1.capacity,
        "compact_build_lanes": l1_compact.capacity,
        "probe_sparse_ms": timed(lambda: run_probe(l1)),
        "probe_compact_ms": timed(lambda: run_probe(l1_compact)),
        "build_multiplicity_sparse_ms": timed(lambda: mult(l1)),
        "build_multiplicity_compact_ms": timed(lambda: mult(l1_compact)),
        "compaction": price_compaction(l1)}
    print("[probe] q21_supplier_join: "
          + json.dumps(result["q21_supplier_join"]), flush=True)

    os.makedirs("chiprun_out/pr39", exist_ok=True)
    with open("chiprun_out/pr39/probe_builds.json", "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))


if __name__ == "__main__":
    main()

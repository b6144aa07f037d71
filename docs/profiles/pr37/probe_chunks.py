#!/usr/bin/env python3
"""PR 37's microbenchmark: what a join's probe costs joined a chunk at a
time against joined whole, at Q21's four lane counts.

    chiprun -- python3 docs/profiles/pr37/probe_chunks.py
    PROBE_SCALE=64 JAX_PLATFORMS=cpu python3 docs/profiles/pr37/probe_chunks.py

The program timed is the engine's own: ``ops/join._build_count_fn`` (the
program ``srt_join_probe``: ``_key_plan`` and the offsets) over int64 keys in
Q21's shapes: ``lineitem`` at scale factor 1 (6,001,215 lines of 1,500,000
orders, 1 to 7 lines an order, sparse ``l_orderkey``), 63.2% of the lines late.
The semi join probes the late lines (``l1``) against every line (``l2``:
6,291,456 lanes), the anti join against the late lines (``l3``: 4,194,304
lanes). Chunked: four chunks of 1,048,576 lanes, as ``batchSizeRows`` cut
them before PR 37. Whole: one chunk of 4,194,304 lanes. Both give every
stream row the same match count, which the script checks before it times.

The third pair prices what the whole probe leaves behind: the anti join now
emits ONE 4,194,304-lane batch with few rows active where four chunks were
concatenated (and so compacted) before the next join built on them.
``probe_sparse_build`` is supplier's 16,384 lanes probing that batch as it
is, ``probe_compact_build`` the same after ``shrink_to_bucket``, whose own
time is ``shrink``.

Writes ``chiprun_out/pr37/probe_chunks.json`` and prints it; times are
milliseconds, the median of 5 after one warm-up, ``block_until_ready`` inside.
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
                                              shrink_to_bucket)
from spark_rapids_tpu.ops import exprs as X  # noqa: E402
from spark_rapids_tpu.ops import join as J  # noqa: E402
from spark_rapids_tpu.sql import expressions as E  # noqa: E402
from spark_rapids_tpu.sql import types as T  # noqa: E402

SCALE = int(os.environ.get("PROBE_SCALE", "1"))
LINES = 6001215 // SCALE
CHUNKS = 4
REPEATS = 5


def lineitem_keys(rng):
    """l_orderkey of every line, in file order, and which lines are late."""
    counts = rng.integers(1, 8, size=LINES // 4 + 8)
    counts = counts[:np.searchsorted(np.cumsum(counts), LINES) + 1]
    order = np.arange(len(counts), dtype=np.int64)
    sparse = (order // 8) * 32 + order % 8 + 1  # dbgen: 8 of every 32 keys
    keys = np.repeat(sparse, counts)[:LINES]
    return keys, rng.random(LINES) < 0.632


def batch(keys, cap, extra_cols=0):
    """A one-key batch of ``cap`` lanes, its rows a prefix (as
    ``concat_device`` leaves them)."""
    n = len(keys)
    data = np.zeros(cap, dtype=np.int64)
    data[:n] = keys
    active = jnp.asarray(np.arange(cap) < n)
    cols = [DeviceColumn(T.LongT, jnp.asarray(data), active)]
    fields = [T.StructField("k", T.LongT, True)]
    for i in range(extra_cols):
        cols.append(DeviceColumn(T.LongT, jnp.asarray(data + i), active))
        fields.append(T.StructField(f"c{i}", T.LongT, True))
    return DeviceBatch(T.StructType(fields), cols, active, n)


def timed(fn):
    jax.block_until_ready(fn())
    out = []
    for _ in range(REPEATS):
        t = time.perf_counter()
        jax.block_until_ready(fn())
        out.append((time.perf_counter() - t) * 1e3)
    return statistics.median(out)


def main():
    device = jax.devices()[0]
    rng = np.random.default_rng(37)
    keys, late = lineitem_keys(rng)
    key = (E.BoundReference(0, T.LongT, True),)
    lits = X.literal_values(list(key))
    probe = J._build_count_fn(key, key, "leftsemi", (False,))

    def run(stream, build):
        return probe(stream.columns, stream.active, lits,
                     build.columns, build.active, lits)

    stream_keys = keys[late]
    per = -(-len(stream_keys) // CHUNKS)
    parts = [stream_keys[i * per:(i + 1) * per] for i in range(CHUNKS)]
    chunk_cap = bucket_capacity(per)
    whole_cap = bucket_capacity(len(stream_keys))
    chunks = [batch(p, chunk_cap) for p in parts]
    whole = batch(stream_keys, whole_cap)
    result = {"device": {"platform": device.platform,
                         "kind": device.device_kind},
              "scale": SCALE, "stream_rows": len(stream_keys),
              "chunk_lanes": chunk_cap, "whole_lanes": whole_cap,
              "unit": "ms", "repeats": REPEATS, "joins": {}}
    for name, build_keys in (("semi_l2", keys), ("anti_l3", stream_keys)):
        build = batch(build_keys, bucket_capacity(len(build_keys)))
        # the same match count and first build row for every stream row,
        # and the same candidate pairs, either way
        by_chunk = [run(c, build) for c in chunks]
        one = run(whole, build)
        m = np.concatenate([np.asarray(o[3])[:len(p)]
                            for o, p in zip(by_chunk, parts)])
        assert (m == np.asarray(one[3])[:len(stream_keys)]).all(), name
        pairs = sum(int(o[0]) for o in by_chunk)
        assert pairs == int(one[0]), name
        t_chunks = [timed(lambda c=c: run(c, build)) for c in chunks]
        t_whole = timed(lambda: run(whole, build))
        result["joins"][name] = {
            "build_lanes": build.capacity, "candidate_pairs": pairs,
            "chunked_lanes_sorted": CHUNKS * (chunk_cap + build.capacity),
            "whole_lanes_sorted": whole_cap + build.capacity,
            "chunked_ms": sum(t_chunks), "each_chunk_ms": t_chunks,
            "whole_ms": t_whole,
            "us_per_lane_chunked": sum(t_chunks) * 1e3 / (
                CHUNKS * (chunk_cap + build.capacity)),
            "us_per_lane_whole": t_whole * 1e3 / (
                whole_cap + build.capacity)}
        print(f"[probe] {name}: " + json.dumps(result["joins"][name]),
              flush=True)

    # what the whole anti join leaves the next join to build on
    survivors = stream_keys[rng.random(len(stream_keys)) < 0.04]
    sparse_active = np.zeros(whole_cap, dtype=bool)
    sparse_active[rng.choice(len(stream_keys), len(survivors),
                             replace=False)] = True
    sparse = batch(stream_keys, whole_cap, extra_cols=3)
    sparse = DeviceBatch(sparse.schema, sparse.columns,
                         jnp.asarray(sparse_active), None)
    supplier = batch(np.arange(1, 10000 // SCALE + 1, dtype=np.int64),
                     bucket_capacity(10000 // SCALE))
    inner = J._build_count_fn(key, key, "inner", (False,))

    def run_inner(build):
        return inner(supplier.columns, supplier.active, lits,
                     build.columns, build.active, lits)
    compacted = shrink_to_bucket(sparse)
    result["after_the_anti_join"] = {
        "stream_lanes": supplier.capacity,
        "sparse_build_lanes": sparse.capacity,
        "sparse_build_rows": int(sparse_active.sum()),
        "compact_build_lanes": compacted.capacity,
        "probe_sparse_build_ms": timed(lambda: run_inner(sparse)),
        "probe_compact_build_ms": timed(lambda: run_inner(compacted)),
        "shrink_ms": timed(lambda: [
            c.arrays() for c in shrink_to_bucket(DeviceBatch(
                sparse.schema, sparse.columns, sparse.active,
                int(sparse_active.sum()))).columns])}
    print("[probe] after_the_anti_join: "
          + json.dumps(result["after_the_anti_join"]), flush=True)

    os.makedirs("chiprun_out/pr37", exist_ok=True)
    with open("chiprun_out/pr37/probe_chunks.json", "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))


if __name__ == "__main__":
    main()

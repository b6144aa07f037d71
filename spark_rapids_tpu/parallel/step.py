"""Fused multi-chip aggregate step: the framework's "training step".

The canonical distributed SQL pipeline — scan-local partial aggregation,
hash exchange, final aggregation (SURVEY.md §3.3/§3.4) — expressed as ONE
``shard_map`` program jitted over the mesh, so XLA schedules the ICI
collective together with the segment kernels.  This is what the driver's
``dryrun_multichip`` compiles, and the strongest perf shape the framework
has: zero host round-trips between the partial agg, the shuffle, and the
final agg.

Reference counterpart: GpuHashAggregateExec(partial) ->
GpuShuffleExchangeExec -> GpuHashAggregateExec(final), three operators
bridged by the UCX transport; here the whole pipeline is one XLA program.
"""

from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from spark_rapids_tpu.columnar.device import DeviceColumn
from spark_rapids_tpu.ops import groupby as G
from spark_rapids_tpu.ops import hashing
from spark_rapids_tpu.parallel.ici import all_to_all_rows
from spark_rapids_tpu.parallel.mesh import SHUFFLE_AXIS
from spark_rapids_tpu.sql import types as T

# bounded LRU like every other structural jit cache: mesh step programs
# count in cache_stats() (bench detail.jitCaches) instead of living in
# an invisible module dict
from spark_rapids_tpu.jit_cache import JitCache, named_jit

_STEP_CACHE = JitCache("meshStep")


def sum_count_step(mesh: Mesh) -> Callable:
    """groupBy(key).agg(sum(val), count(val)) over the mesh.

    Inputs (stacked, leading axis = chip): ``keys`` int64[n, cap],
    ``vals`` int64[n, cap], ``active`` bool[n, cap].  Output per chip:
    final (keys, sums, counts, out_active) for the key-groups that chip
    owns (murmur3(key) % n_dev).
    """
    from spark_rapids_tpu.parallel.mesh import mesh_key
    n_dev = mesh.shape[SHUFFLE_AXIS]
    key = (mesh_key(mesh), "sum_count", G.kernel_salt())

    def per_shard(keys, vals, active):
        keys, vals, active = keys[0], vals[0], active[0]
        cap = active.shape[0]
        kc = DeviceColumn(T.LongT, keys, active)
        vc = DeviceColumn(T.LongT, vals, active)
        # local partial aggregation (segment kernel)
        seg = G.build_segments([kc], active,
                               payload=(keys, vals, active))
        keys_s, vals_s, act_s = seg.payload
        vc_s = DeviceColumn(T.LongT, vals_s, act_s)
        psum = G.seg_sum(seg, vc_s, T.LongT, null_when_empty=True)
        pcnt = G.seg_count(seg, vc_s)
        # results live at segment-END rows (scatter-free layout)
        pact = seg.out_active
        pkeys = jnp.where(pact, keys_s, jnp.int64(0))
        # route partial rows by bit-exact Spark murmur3 of the key
        kcol = DeviceColumn(T.LongT, pkeys, pact)
        hv = hashing.murmur3_columns([kcol], cap, 42)
        dest = jnp.mod(hv.astype(jnp.int64), n_dev).astype(jnp.int32)
        recv, recv_act = all_to_all_rows(
            [pkeys, psum.data, psum.validity, pcnt.data], pact, dest, n_dev)
        rkeys = recv[0].reshape(n_dev * cap)
        rsum = recv[1].reshape(n_dev * cap)
        rsum_valid = recv[2].reshape(n_dev * cap)
        rcnt = recv[3].reshape(n_dev * cap)
        ract = recv_act.reshape(n_dev * cap)
        # final merge: segment-sum the partial buffers per key
        fkc = DeviceColumn(T.LongT, rkeys, ract)
        fseg = G.build_segments(
            [fkc], ract,
            payload=(rkeys, rsum, rsum_valid & ract, rcnt, ract))
        rkeys_s, rsum_s, rsumv_s, rcnt_s, ract_s = fseg.payload
        fsum = G.seg_sum(fseg, DeviceColumn(T.LongT, rsum_s, rsumv_s),
                         T.LongT, null_when_empty=True)
        fcnt = G.seg_sum(fseg, DeviceColumn(T.LongT, rcnt_s, ract_s),
                         T.LongT, null_when_empty=False)
        fact = fseg.out_active
        fkeys = jnp.where(fact, rkeys_s, jnp.int64(0))
        add = lambda a: a[None]
        return (add(fkeys), add(fsum.data), add(fcnt.data), add(fact))

    def build():
        sm = shard_map(per_shard, mesh=mesh,
                       in_specs=(P(SHUFFLE_AXIS), P(SHUFFLE_AXIS),
                                 P(SHUFFLE_AXIS)),
                       out_specs=(P(SHUFFLE_AXIS),) * 4)
        return named_jit("srt_mesh_agg_step", sm)

    # single-flight get_or_build (not raw get/put): two concurrent
    # queries racing the first mesh-step compile would otherwise both
    # trace+jit the program (docs/serving.md thread-safety audit)
    fn, _ = _STEP_CACHE.get_or_build(key, build)
    return fn

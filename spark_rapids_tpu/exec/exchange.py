"""TpuShuffleExchangeExec: device-side partitioning + exchange
(GpuShuffleExchangeExecBase.scala:148, GpuPartitioning.scala:50).

Hash partition ids are computed on device with the bit-exact Spark
murmur3 (ops/hashing.py), so rows land in exactly the partitions CPU
Spark would use. The "split" (``split_by_pid``) is one device program per
input batch: stable-sort rows by partition id, then slice each partition
out at its own power-of-two capacity (the contiguousSplit analogue,
GpuPartitioning.scala:50) — a single host sync for the counts, with row
counts attached so consumers never re-sync. In-process the exchange is a
materialized list per partition (Spark's shuffle files); the multi-chip
ICI all-to-all path replaces this transport while keeping the same
partition-id kernel.
"""

from __future__ import annotations

import os
import threading
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp

import numpy as np

from spark_rapids_tpu import metrics as M
from spark_rapids_tpu.columnar.device import (DeviceBatch, bucket_capacity,
                                              flatten_batch, rebuild_columns)
from spark_rapids_tpu.conf import TpuConf
from spark_rapids_tpu.exec.base import (DevicePartitionThunk, TpuExec,
                                        device_channel)
from spark_rapids_tpu.ops import exprs as X
from spark_rapids_tpu.sql import expressions as E
from spark_rapids_tpu.sql import physical as P
from spark_rapids_tpu.sql import types as T

from spark_rapids_tpu.jit_cache import JitCache, named_jit

_PID_CACHE = JitCache("exchangePid")
_SORT_CACHE = JitCache("exchangeSort")
_EXTRACT_CACHE = JitCache("exchangeExtract")
_RANGE_PID_CACHE = JitCache("rangeKeys")
_RANGE_RANK_CACHE = JitCache("rangeRank")


def hash_partition_ids(exprs: List[E.Expression], batch: DeviceBatch,
                       num_partitions: int) -> jax.Array:
    """pmod(murmur3(keys, 42), n) per row — Spark HashPartitioning."""
    key = (tuple(X.expr_key(e) for e in exprs), num_partitions)
    fn = _PID_CACHE.get(key)
    if fn is None:
        from spark_rapids_tpu.ops import hashing

        def _fn(cols, active, lit_vals):
            return hashing.traced_partition_ids(
                exprs, cols, active, lit_vals, num_partitions)
        fn = _PID_CACHE.put(key, named_jit("srt_exchange_pid", _fn))
    return fn(batch.columns, batch.active, X.literal_values(exprs))


def _round_robin_body(active: jax.Array, start: jax.Array,
                      n: int) -> jax.Array:
    rank = jnp.cumsum(active.astype(jnp.int32)) - 1
    return jnp.mod(rank + start, n).astype(jnp.int32)


# tpu-lint: disable=jit-direct(single fixed round-robin program — jax's own signature cache bounds it by capacity bucket)
_round_robin_pids = named_jit("srt_exchange_round_robin",
                              _round_robin_body, static_argnums=(2,))


def range_key_columns(order: List[E.Expression],
                      bound: List[E.Expression],
                      batch: DeviceBatch) -> List:
    """Per-batch evaluated order-key COLUMNS for range partitioning. Only
    the keys leave the batch — the global ranking below never
    concatenates full batches (the sampled-boundary memory discipline of
    GpuRangePartitioner, exact instead of sampled)."""
    from spark_rapids_tpu.columnar.device import make_column
    key = tuple(X.expr_key(e) for e in bound)
    fn = _RANGE_PID_CACHE.get(key)
    if fn is None:
        bound_t = tuple(bound)

        def _fn(cols, active, lit_vals):
            cap = active.shape[0]
            ctx = X.Ctx(cols, cap, bound_t, lit_vals)
            return tuple(X.dev_eval(e, ctx).arrays() for e in bound_t)
        fn = _RANGE_PID_CACHE.put(
            key, named_jit("srt_exchange_range_keys", _fn))
    arrs = fn(batch.columns, batch.active, X.literal_values(bound))
    return [make_column(e.data_type, a) for e, a in zip(bound, arrs)]


def global_range_pids(order: List[E.Expression],
                      keycols_per_batch: List[List],
                      actives: List[jax.Array], n: int) -> List[jax.Array]:
    """Equal-depth bucketing over the global sort-rank space; returns the
    per-batch partition-id arrays. String key columns are padded to a
    common char width first so every batch yields the same subkey shape
    (pack_string_words emits ceil(char_cap/8) words). Matches the CPU
    engine's _range_partition assignment bit-for-bit (same stable
    order)."""
    from spark_rapids_tpu.columnar.device import DeviceStringColumn
    from spark_rapids_tpu.ops import sort as S
    n_keys = len(keycols_per_batch[0])
    for ki in range(n_keys):
        cols = [kc[ki] for kc in keycols_per_batch]
        if isinstance(cols[0], DeviceStringColumn):
            cc = max(c.char_cap for c in cols)
            for bi, c in enumerate(cols):
                if c.char_cap < cc:
                    keycols_per_batch[bi][ki] = DeviceStringColumn(
                        c.dtype,
                        jnp.pad(c.chars, ((0, 0), (0, cc - c.char_cap))),
                        c.lengths, c.validity)
    # ONE jitted program for the whole global ranking (concat + LSD
    # sort + inverse permutation + bucketing): the previous eager form
    # paid a dispatch PER op — dozens per range exchange
    from spark_rapids_tpu.ops import groupby as G
    flags = tuple((o.ascending, o.nulls_first) for o in order)
    salt = G.kernel_salt()  # snapshot: key AND trace use this value
    has_nans = salt[0]
    key = (flags, n, salt)
    fn = _RANGE_RANK_CACHE.get(key)
    if fn is None:
        def _fn(keycols_pb, actives_t):
            from spark_rapids_tpu.columnar.device import sort_with_payload
            keysets = []
            for kc in keycols_pb:
                subkeys: List[jax.Array] = []
                for c, (asc, nf) in zip(kc, flags):
                    # has_nans pinned from the snapshotted salt so the
                    # trace can never disagree with its cache key
                    # (sort.py / window.py follow the same discipline)
                    subkeys.extend(S.order_subkeys(c, asc, nf, has_nans))
                keysets.append(tuple(subkeys))
            combined = [jnp.concatenate([ks[i] for ks in keysets])
                        for i in range(len(keysets[0]))]
            active = jnp.concatenate(actives_t)
            # most-significant first: live rows, then the order words
            # (the LSD helper replaces jnp.lexsort, whose many-operand
            # sorts hang the TPU compiler — see sort_with_payload)
            _k, perm, _p = sort_with_payload([~active] + combined, [])
            # rank of row p = its sorted position = inverse permutation
            # (a sort, not a scatter — scatters serialize on TPU)
            ranks = jnp.argsort(perm).astype(jnp.int64)
            total = jnp.maximum(jnp.sum(active), 1)
            pids = jnp.minimum((ranks * n) // total,
                               n - 1).astype(jnp.int32)
            outs: List[jax.Array] = []
            off = 0
            for a in actives_t:
                outs.append(pids[off:off + a.shape[0]])
                off += a.shape[0]
            return tuple(outs)
        fn = _RANGE_RANK_CACHE.put(
            key, named_jit("srt_exchange_range_rank", _fn))
    return list(fn(tuple(tuple(kc) for kc in keycols_per_batch),
                   tuple(actives)))


def split_by_pid(batch: DeviceBatch, pids: jax.Array, n: int
                 ) -> List[Optional[DeviceBatch]]:
    """contiguousSplit (GpuPartitioning.scala:50) as ONE device program:
    stable-sort rows by partition id (inactive rows sink), then slice each
    partition out at its own capacity bucket. One host sync (the counts)
    per input batch; row counts are attached so downstream consumers never
    re-sync."""
    flat, spec = flatten_batch(batch)
    shapes = tuple((a.shape, str(a.dtype)) for a in flat)
    skey = (shapes, n)
    sort_fn = _SORT_CACHE.get(skey)
    if sort_fn is None:
        def _sort(pids, active, *arrs):
            from spark_rapids_tpu.columnar.device import sort_with_payload
            key = jnp.where(active, pids, jnp.int32(n))
            (sorted_key,), _order, sorted_arrs = sort_with_payload(
                [key], arrs)
            # counts via binary search over the sorted keys (n+1 tiny
            # queries) — bincount is a scatter-add, slow on TPU
            edges = jnp.searchsorted(sorted_key,
                                     jnp.arange(n + 1, dtype=jnp.int32),
                                     side="left")
            counts = edges[1:] - edges[:-1]
            return counts, tuple(sorted_arrs)
        sort_fn = _SORT_CACHE.put(
            skey, named_jit("srt_exchange_split_sort", _sort))
    counts_d, sorted_flat = sort_fn(pids, batch.active, *flat)
    from spark_rapids_tpu import trace as TR
    with TR.device_sync("exchangeSplit"):
        counts = np.asarray(counts_d)
    offsets = np.concatenate([[0], np.cumsum(counts)])

    out: List[Optional[DeviceBatch]] = []
    for pid in range(n):
        cnt = int(counts[pid])
        if cnt == 0:
            out.append(None)
            continue
        cap = bucket_capacity(cnt)
        ekey = (shapes, cap)
        ext_fn = _EXTRACT_CACHE.get(ekey)
        if ext_fn is None:
            def _extract(off, cnt, *arrs, _cap=cap):
                new_active = jnp.arange(_cap) < cnt
                idx = jnp.clip(off + jnp.arange(_cap), 0,
                               arrs[0].shape[0] - 1)
                outs = []
                for a in arrs:
                    g = a[idx]
                    if a.ndim == 2:
                        g = jnp.where(new_active[:, None], g, 0)
                    else:
                        g = jnp.where(new_active, g,
                                      jnp.zeros((), dtype=g.dtype))
                    outs.append(g)
                return new_active, tuple(outs)
            ext_fn = _EXTRACT_CACHE.put(
                ekey, named_jit("srt_exchange_extract", _extract))
        new_active, outs = ext_fn(
            T.device_long(offsets[pid]), T.device_long(cnt), *sorted_flat)
        out.append(DeviceBatch(batch.schema, rebuild_columns(spec, outs),
                               new_active, cnt))
    return out


def realign_spilled_pids(handle, pids: jax.Array, act: jax.Array
                         ) -> Tuple[DeviceBatch, jax.Array]:
    """Re-promote a spillable handle whose per-slot ``pids`` were computed
    against the pre-spill layout. A spill round-trip compacts the batch
    (active rows become a prefix, original order kept), so the pids are
    remapped through the same compaction permutation. Shared by the range
    exchange and the out-of-core sort."""
    b = handle.get()
    if handle.ever_spilled or b.capacity != act.shape[0]:
        comp = jnp.argsort(~act, stable=True)
        pids = pids[comp][:b.capacity]
    return b, pids


class TpuBroadcastExchangeExec(TpuExec):
    """Device-resident reusable broadcast (GpuBroadcastExchangeExec
    .scala:280): the build side concatenates into HBM ONCE behind a
    lock; every consumer — all stream partitions, and several joins
    after the reuse pass deduplicates equal broadcast subtrees — shares
    the same device batch. ``broadcastBuilds`` pins build-once in
    tests."""

    def __init__(self, child: TpuExec, conf: TpuConf):
        super().__init__(conf)
        self.children = [child]
        self._lock = threading.Lock()
        self._built = None

    @property
    def child(self) -> TpuExec:
        return self.children[0]

    @property
    def output(self):
        return self.child.output

    def materialize_device(self):
        from spark_rapids_tpu.exec.join import assemble_build
        from spark_rapids_tpu.resource import get_semaphore
        # consumers touch the device with the broadcast batch: take the
        # permit BEFORE the build lock — a permit-holder blocked on the
        # lock while the lock-holder waits for a permit would deadlock
        # at concurrentGpuTasks=1 — and time the wait against the
        # broadcast's own registry (the per-task collect path was the
        # only one metered before)
        get_semaphore(self.conf).acquire_if_necessary(self.metrics)
        with self._lock:
            if self._built is None:
                self.metrics.create("broadcastBuilds", M.ESSENTIAL).add(1)
                try:
                    batches = [b for t in device_channel(self.child)
                               for b in t() if b._num_rows != 0]
                except BaseException:
                    # the build drain acquired a device permit on THIS
                    # thread; a fault mid-build (often during plan
                    # wiring, before any C2R finally exists) must not
                    # burn it for the process lifetime
                    from spark_rapids_tpu.resource import \
                        release_current_thread
                    release_current_thread()
                    raise
                self._built = assemble_build(batches, self.child.schema,
                                             self.metrics)
            return self._built

    def device_partitions(self) -> List[DevicePartitionThunk]:
        return [lambda: iter([self.materialize_device()])]

    def simple_string(self):
        return "TpuBroadcastExchange"


class TpuShuffleExchangeExec(TpuExec):
    def __init__(self, partitioning: P.Partitioning, child: TpuExec,
                 conf: TpuConf):
        super().__init__(conf)
        self.children = [child]
        self.partitioning = partitioning
        self._cache: Optional[List[List[DeviceBatch]]] = None
        self._lock = threading.Lock()
        # set by the rewrite for consumers that accept any partition
        # count (agg/sort/window) - enables AQE partition coalescing
        self.allow_aqe_coalesce = False
        # realized per-partition byte/row counts, captured at
        # _materialize (adaptive.ExchangeStats): the AQE pass reads
        # these to demote joins to broadcast, coalesce undersized
        # partitions, and split skewed ones (docs/adaptive.md)
        self.exchange_stats = None

    @property
    def child(self) -> TpuExec:
        return self.children[0]

    @property
    def output(self):
        return self.child.output

    def _task_threads(self) -> int:
        from spark_rapids_tpu.conf import TASK_PARALLELISM
        return int(self.conf.get(TASK_PARALLELISM))

    def _pull_split(self, thunks, split_one) -> List[List]:
        """Drain the child's partitions (concurrently when configured)
        and split each batch; results keep (input partition, batch)
        order so first/last semantics stay deterministic. ``split_one``
        must REGISTER whatever it retains (spillable handles) itself, so
        batches become demotable the moment they exist — not after the
        whole child is drained."""
        from spark_rapids_tpu.resource import get_semaphore
        n_threads = self._task_threads()
        sem = get_semaphore(self.conf)

        from spark_rapids_tpu import trace as TR
        scope = TR.scope_of(self.metrics)

        def pull(thunk):
            try:
                # bill the drain thread's permit wait to the EXCHANGE
                # (semaphoreWaitTime span + metric): the lazy acquire
                # inside the child's R2C books it against the upload,
                # hiding exchange-drain contention from the breakdown
                sem.acquire_if_necessary(self.metrics)
                with TR.attach(scope):
                    return [split_one(b) for b in thunk()]
            finally:
                # pool threads acquire the TpuSemaphore inside the child
                # pipeline (R2C upload) but never reach a root C2R —
                # release here or the permits leak and later tasks hang
                sem.release_if_necessary()

        if n_threads > 1 and len(thunks) > 1:
            from concurrent.futures import ThreadPoolExecutor
            # this thread may already hold a semaphore permit (acquired
            # while draining an earlier subtree); release it before
            # blocking on the pool or the pull threads can starve of
            # permits and deadlock (the throttle is re-acquired on the
            # next device touch)
            sem.release_if_necessary()
            with ThreadPoolExecutor(
                    min(n_threads, len(thunks)),
                    thread_name_prefix="srt-shuffle") as pool:
                return list(pool.map(pull, thunks))
        return [pull(t) for t in thunks]

    def _materialize(self) -> List[List]:
        # release any held permit BEFORE blocking on the lock: if every
        # task thread parked here while holding one, the materializer's
        # pull threads could never acquire and the job would hang
        from spark_rapids_tpu.resource import get_semaphore
        get_semaphore(self.conf).release_if_necessary()
        with self._lock:  # consumers race here under taskParallelism
            if self._cache is not None:
                return self._cache
            # graceful degradation (docs/robustness.md): demote the
            # failed chip, then re-execute the subtree on the surviving
            # mesh — single-chip/in-process once too few chips remain
            from spark_rapids_tpu import trace as TR
            from spark_rapids_tpu.retry import degrade_on_chip_failure
            with TR.span("exchangeMaterialize",
                         parts=self.partitioning.num_partitions):
                cache = degrade_on_chip_failure(self._materialize_inner,
                                                self.metrics)
            from spark_rapids_tpu.conf import SHUFFLE_MODE
            if str(self.conf.get(SHUFFLE_MODE)).lower() == "external":
                cache = self._external_roundtrip(cache)
            # the exchange-stat capture (docs/adaptive.md): exact
            # realized partition sizes on EVERY path (single-chip,
            # mesh, external) — the reference treats file-level stats
            # as a first guess and replans from map output sizes; these
            # counts are that signal. Recorded as node metrics too, so
            # the profile artifact carries them to `tools doctor`'s
            # skewedShuffle verdict
            from spark_rapids_tpu import adaptive as A
            self.exchange_stats = stats = A.capture_stats(cache)
            self.metrics.create("exchangeTotalBytes",
                                M.ESSENTIAL).add(stats.total_bytes)
            self.metrics.create("exchangeMaxPartitionBytes",
                                M.ESSENTIAL).add(stats.max_bytes)
            self.metrics.create("exchangeMedianPartitionBytes",
                                M.ESSENTIAL).add(stats.median_bytes)
            self._cache = cache
            return self._cache

    def _external_roundtrip(self, cache):
        """shuffle.mode=external: ship every partition through the SRTB
        cross-process leg (serialize -> shared-fs files -> deserialize ->
        re-upload). In one process this is a filesystem loopback — the
        DCN/host-staged transport skeleton
        (RapidsShuffleInternalManagerBase.scala:76 role)."""
        from spark_rapids_tpu.columnar.device import DeviceBatch
        from spark_rapids_tpu.conf import SHUFFLE_COMPRESSION_CODEC
        from spark_rapids_tpu.memory import SpillableBatch, get_device_store
        from spark_rapids_tpu.parallel import external_shuffle as XS
        codec = str(self.conf.get(SHUFFLE_COMPRESSION_CODEC))
        sdir = XS.new_shuffle_dir()
        store = get_device_store(self.conf)
        with self.metrics.timed("externalShuffleWriteTime"):
            host_parts = []
            for part in cache:
                hb = []
                for item in part:
                    b = item.get() if isinstance(item, SpillableBatch) \
                        else item
                    hb.append(b.to_host())
                    if isinstance(item, SpillableBatch):
                        item.close()
                host_parts.append(hb)
            XS.write_map_output(sdir, "0", host_parts, codec)
        out = []
        with self.metrics.timed("externalShuffleReadTime"):
            for pid in range(len(cache)):
                part = []
                for hb in XS.read_partition(sdir, pid):
                    part.append(self.register_spillable(
                        store, DeviceBatch.from_host(hb)))
                out.append(part)
        self.metrics.create("externalShuffleBytes", M.ESSENTIAL).add(
            sum(os.path.getsize(os.path.join(sdir, f))
                for f in os.listdir(sdir)))
        import shutil
        shutil.rmtree(sdir, ignore_errors=True)
        return out

    def _materialize_inner(self) -> List[List]:
        from spark_rapids_tpu.memory import SpillableBatch, get_device_store
        store = get_device_store(self.conf)
        p = self.partitioning
        n = p.num_partitions
        out: List[List] = [[] for _ in range(n)]
        try:
            return self._materialize_parts(p, n, store, out)
        except BaseException:
            # an aborted attempt (chip failure mid-drain, exhausted OOM)
            # must not strand its already-registered partitions in the
            # store: the degrade loop re-executes from scratch, and a
            # leaked handle would shrink the budget for the process
            # lifetime (close is idempotent)
            for part in out:
                for h in part:
                    if isinstance(h, SpillableBatch):
                        h.close()
            raise

    def _materialize_parts(self, p, n: int, store,
                           out: List[List]) -> List[List]:

        def keep(pid: int, part: DeviceBatch) -> None:
            """Retain a materialized partition as a spillable handle —
            the exchange holds the whole dataset across yields, so every
            held batch must be demotable (SpillableColumnarBatch role)."""
            out[pid].append(self.register_spillable(store, part))

        single_out = isinstance(p, P.SinglePartitioning) or (
            n == 1 and isinstance(p, (P.HashPartitioning,
                                      P.RangePartitioning,
                                      P.RoundRobinPartitioning))
            and not self._mesh_eligible())
        if single_out:
            # one output partition trivially satisfies any required
            # distribution: pass batches through with NO partition-id
            # program and NO count sync (the split exists only to route
            # rows between partitions)
            for per_part in self._pull_split(
                    device_channel(self.child),
                    lambda b: self.register_spillable(store, b)
                    if b._num_rows != 0 else None):
                for h in per_part:
                    if h is not None:
                        out[0].append(h)
        elif isinstance(p, P.HashPartitioning) and self._mesh_eligible() \
                and (mesh_out := self._materialize_mesh(p, n)) is not None:
            # mesh batches are sharded jax arrays pinned per chip; the
            # spill tiers (host numpy round-trip) would gather them
            # cross-device, so the ICI path manages residency itself —
            # the reference likewise exempts UCX bounce buffers from the
            # catalog (RapidsShuffleClient). A None mesh_out means the
            # mesh lost a degradation race after the eligibility gate;
            # the next branch takes the in-process path.
            out = mesh_out
        elif isinstance(p, P.HashPartitioning):
            bound = P.bind_list(p.exprs, self.child.output)

            def split_one(b):
                from spark_rapids_tpu import retry as R
                with self.metrics.timed(M.PARTITION_TIME):
                    # the contiguous-split staging is an allocation
                    # point: OOM spills the store down and re-runs the
                    # pid+sort-split program (pure over b — idempotent)
                    parts = R.with_retry(
                        lambda: split_by_pid(
                            b, hash_partition_ids(bound, b, n), n),
                        self.conf, self.metrics)
                # register IMMEDIATELY (store is thread-safe) so the
                # spill budget applies during the drain, not after
                return [self.register_spillable(store, part)
                        if part is not None else None for part in parts]
            for per_part in self._pull_split(device_channel(self.child),
                                             split_one):
                for handles in per_part:
                    for pid, h in enumerate(handles):
                        if h is not None:
                            out[pid].append(h)
        elif isinstance(p, P.RoundRobinPartitioning):
            start = 0
            for thunk in device_channel(self.child):
                for b in thunk():
                    # jitted (eager ops pay a dispatch per op)
                    pids = _round_robin_pids(b.active, jnp.int32(start),
                                             n)
                    from spark_rapids_tpu import retry as R
                    with self.metrics.timed(M.PARTITION_TIME):
                        parts = R.with_retry(
                            lambda: split_by_pid(b, pids, n),
                            self.conf, self.metrics)
                    for pid, part in enumerate(parts):
                        if part is not None:
                            keep(pid, part)
                    start += 1
        elif isinstance(p, P.RangePartitioning):
            self._materialize_range(p, n, store, keep)
        else:
            raise NotImplementedError(repr(p))
        return out

    def _materialize_range(self, p: P.RangePartitioning, n: int, store,
                           keep) -> None:
        """Two passes: (1) extract order-encoded KEYS per batch while the
        batches themselves become spillable, (2) rank keys globally and
        split each batch by its partition ids. Full batches are never
        concatenated — only the uint64 key columns are."""
        bound = P.bind_list([o.child for o in p.order], self.child.output)
        handles, keycols, actives = [], [], []
        for thunk in device_channel(self.child):
            for b in thunk():
                if b._num_rows == 0:  # skip only KNOWN-empty (no sync)
                    continue
                with self.metrics.timed(M.PARTITION_TIME):
                    keycols.append(range_key_columns(p.order, bound, b))
                actives.append(b.active)
                handles.append(self.register_spillable(store, b))
        if not handles:
            return
        from spark_rapids_tpu import retry as R
        try:
            with self.metrics.timed(M.PARTITION_TIME):
                pids_per_batch = R.with_retry(
                    lambda: global_range_pids(p.order, keycols, actives,
                                              n),
                    self.conf, self.metrics)
            for h, pids, act in zip(handles, pids_per_batch, actives):
                b, pids = realign_spilled_pids(h, pids, act)
                with self.metrics.timed(M.PARTITION_TIME):
                    parts = R.with_retry(
                        lambda b=b, pids=pids: split_by_pid(b, pids, n),
                        self.conf, self.metrics)
                h.close()
                for pid, part in enumerate(parts):
                    if part is not None:
                        keep(pid, part)
        except BaseException:
            # don't strand the staged input handles in the store when
            # the ranking/split aborts (close is idempotent; the split
            # outputs in `out` are closed by _materialize_inner)
            for h in handles:
                h.close()
            raise

    def _mesh_eligible(self) -> bool:
        # the HEALTHY mesh: demoted chips shrink it, and below 2
        # survivors the exchange falls back to the in-process transport
        # (the bottom of the degradation ladder, docs/robustness.md)
        from spark_rapids_tpu.parallel.mesh import healthy_mesh, mesh_size
        m = healthy_mesh()
        return m is not None and mesh_size(m) > 1

    def _materialize_mesh(self, p: P.HashPartitioning, n: int
                          ) -> Optional[List[List[DeviceBatch]]]:
        """ICI path: batches stay HBM-resident per chip and ride one
        all_to_all (SURVEY.md §2.3 TPU mapping note). Streams from the
        mesh-sharded scan arrive already committed per chip and KEEP
        their residency (slot = resident chip, concat runs on that
        chip, the stack assembles from the resident shards) — no host
        gather between scan and exchange. Single-device children fall
        back to the round-robin task->chip placement Spark's scheduler
        provides in the reference."""
        from spark_rapids_tpu import retry as R
        from spark_rapids_tpu.columnar.device import (batch_device,
                                                      concat_device)
        from spark_rapids_tpu.parallel.ici import mesh_exchange
        from spark_rapids_tpu.parallel.mesh import healthy_mesh, mesh_size
        mesh = healthy_mesh()
        if mesh is None or mesh_size(mesh) <= 1:
            # lost a degradation race: a concurrent thread demoted
            # chip(s) between the caller's _mesh_eligible gate and here,
            # shrinking the healthy mesh below 2 survivors. Signal the
            # caller to take the in-process path instead of crashing.
            return None
        n_dev = mesh_size(mesh)
        # dispatch-failure checkpoint per mesh chip BEFORE staging: an
        # injected (or detected) chip failure raises TpuChipFailure and
        # the degrade loop in _materialize re-plans on the survivors
        for d in mesh.devices.flat:
            R.chip_checkpoint(self.conf, d)
        bound = P.bind_list(p.exprs, self.child.output)
        # concurrent drain (taskParallelism): each per-chip stream's
        # host orchestration overlaps the other chips' device compute
        drained = self._pull_split(device_channel(self.child),
                                   lambda b: b)
        with_dev = [(ti, b, batch_device(b))
                    for ti, per_part in enumerate(drained)
                    for b in per_part if b.row_count()]
        slot_of = {d.id: i for i, d in enumerate(mesh.devices.flat)}
        resident = {d.id for _ti, _b, d in with_dev
                    if d is not None and d.id in slot_of}
        slots: List[List[DeviceBatch]] = [[] for _ in range(n_dev)]
        for ti, b, d in with_dev:
            if len(resident) >= 2 and d is not None and d.id in slot_of:
                slots[slot_of[d.id]].append(b)
            else:
                slots[ti % n_dev].append(b)
        schema = self.child.schema
        slot_batches = [
            concat_device(bs) if bs else DeviceBatch.empty(schema)
            for bs in slots]
        self.metrics.create("numIciExchanges", M.ESSENTIAL).add(1)
        # collective_section AFTER the drain: the child's own (possibly
        # mesh) stages completed above, so the mutex only serializes
        # this exchange's collective dispatch — holding it across the
        # drain could deadlock against a nested exchange on a pool
        # thread (docs/multichip.md "Served queries")
        from spark_rapids_tpu.parallel.mesh import collective_section

        # the mutex is taken PER ATTEMPT, inside the retried thunk, so
        # the OOM backoff sleeps between attempts run with it released
        # (other served queries' collectives proceed while this one
        # waits out memory pressure); the timed scope sits inside the
        # mutex so queue-wait never inflates partitionTime (the
        # slow-query triggers read that metric)
        def _locked_exchange():
            with collective_section(self.conf), \
                    self.metrics.timed(M.PARTITION_TIME):
                return mesh_exchange(slot_batches, bound, n, mesh,
                                     self.metrics)

        return R.with_retry(_locked_exchange, self.conf, self.metrics)

    def device_partitions(self) -> List[DevicePartitionThunk]:
        from spark_rapids_tpu.memory import SpillableBatch
        nparts = self.partitioning.num_partitions
        groups = [[i] for i in range(nparts)]
        if self._aqe_coalesce_eligible():
            groups = self._aqe_partition_groups(nparts)

        def make(pids: List[int]) -> DevicePartitionThunk:
            def run() -> Iterator[DeviceBatch]:
                mat = self._materialize()
                for pid in pids:
                    for item in mat[pid]:
                        yield (item.get()
                               if isinstance(item, SpillableBatch)
                               else item)
            return run
        return [make(g) for g in groups]

    def _aqe_coalesce_eligible(self) -> bool:
        from spark_rapids_tpu import adaptive as A
        return (self.allow_aqe_coalesce
                and A.adaptive_enabled(self.conf)
                and not getattr(self.partitioning, "user_specified", False)
                and self.partitioning.num_partitions > 1
                and not self._mesh_eligible())

    def _aqe_partition_groups(self, nparts: int) -> List[List[int]]:
        """Merge ADJACENT materialized partitions toward
        adaptive.targetPartitionBytes (GpuCustomShuffleReaderExec /
        Spark coalesced-partition-spec role; adjacency preserves
        range-partition ordering). Only consumers that accept any
        partition count opt in (allow_aqe_coalesce) — co-partitioned
        join inputs never do. Sizes come from the exchange-stat
        capture, so coalescing and skew detection agree on what a
        partition weighs."""
        from spark_rapids_tpu import adaptive as A
        self._materialize()
        stats = self.exchange_stats
        target = A.target_partition_bytes(self.conf)
        from spark_rapids_tpu.memory import get_budget_oracle
        oracle = get_budget_oracle(self.conf)
        if oracle.enabled:
            # budget-aware cap (docs/out_of_core.md): never coalesce
            # toward a concat the consumer could not materialize
            # within its budget share
            share = oracle.operator_share()
            if share < target:
                target = share
                self.metrics.create(M.BUDGET_PRESSURE_PEAK,
                                    M.ESSENTIAL).set_max(
                    int(A.target_partition_bytes(self.conf) * 100
                        // max(1, share)))
        groups = A.coalesce_groups(stats.partition_bytes, target)
        if len(groups) < nparts:
            self.metrics.create("aqeCoalescedPartitions",
                                M.ESSENTIAL).add(nparts - len(groups))
        return groups

    def simple_string(self):
        return f"TpuExchange {self.partitioning!r}"

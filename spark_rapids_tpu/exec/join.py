"""TpuShuffledHashJoinExec / TpuBroadcastHashJoinExec
(GpuShuffledHashJoinExec.scala / GpuBroadcastHashJoinExec.scala twins over
the count-then-gather kernel in ops/join.py).

Residual (non-equi) conditions run on the device for four join types:
inner and cross joins filter the joined pairs, left semi and left anti
joins (a decorrelated ``[NOT] EXISTS``) evaluate the condition over each
left row's candidate build rows inside the mask program
(``ops/join.py: srt_join_cond_mask``). The rewrite tags outer joins with
a residual back to the CPU by name (the reference compiles those to AST
filters inside cudf's join, a complexity this design doesn't need yet).
"""

from __future__ import annotations

import threading
from typing import Callable, Iterator, List, Optional

from spark_rapids_tpu import metrics as M
from spark_rapids_tpu.columnar.device import DeviceBatch, concat_device
from spark_rapids_tpu.conf import TpuConf
from spark_rapids_tpu.exec.base import (DevicePartitionThunk, TpuExec,
                                        device_channel)
from spark_rapids_tpu.ops import exprs as X
from spark_rapids_tpu.ops.join import MASK_JOINS, PAIR_JOINS, device_join
from spark_rapids_tpu.sql import expressions as E
from spark_rapids_tpu.sql import physical as P
from spark_rapids_tpu.sql import types as T


# join types whose residual condition the device evaluates
CONDITIONAL_JOINS = ("inner", "cross") + MASK_JOINS


def is_device_join(join_type: str, left_keys: List[E.Expression],
                   right_keys: List[E.Expression],
                   condition: Optional[E.Expression],
                   conf: TpuConf) -> Optional[str]:
    """Tagging helper: None when the join can run on device."""
    if join_type not in PAIR_JOINS + MASK_JOINS:
        return f"join type {join_type} is not supported on TPU"
    if condition is not None and join_type not in CONDITIONAL_JOINS:
        return (f"conditional {join_type} join runs on CPU (residual "
                "conditions run on the device for inner, cross, left "
                "semi and left anti joins; outer joins with one do not)")
    if condition is not None:
        r = X.is_device_expr(condition, conf)
        if r:
            return r
        if X.contains_ansi_cast(condition):
            return "ANSI casts in join conditions run on CPU" 
    for lk, rk in zip(left_keys, right_keys):
        for e in (lk, rk):
            dt = e.data_type
            if isinstance(dt, (T.ArrayType, T.MapType, T.StructType)):
                return "nested join keys are not supported on TPU"
            r = X.is_device_expr(e, conf)
            if r:
                return r
            if X.contains_ansi_cast(e):
                return "ANSI casts in join keys run on CPU" 
        if type(lk.data_type) is not type(rk.data_type):
            return (f"mismatched join key types {lk.data_type} vs "
                    f"{rk.data_type} run on CPU")
    return None


def stream_chunks(handles: List, goal: int) -> List[List]:
    """Consecutive stream handles grouped into the chunks one probe
    takes each: a chunk closes before the handle whose ``rows`` would
    take it past ``goal``, and a handle over the goal by itself is a
    chunk of its own. Order kept, every handle once; no handle at all is
    one empty chunk, since the join still runs once against its build
    side."""
    chunks: List[List] = [[]]
    rows = 0
    for h in handles:
        n = h.rows
        if chunks[-1] and rows + n > goal:
            chunks.append([])
            rows = 0
        chunks[-1].append(h)
        rows += n
    return chunks


class TpuShuffledHashJoinExec(TpuExec):
    def __init__(self, left_keys: List[E.Expression],
                 right_keys: List[E.Expression], join_type: str,
                 condition: Optional[E.Expression], left: TpuExec,
                 right: TpuExec, output: List[E.AttributeReference],
                 conf: TpuConf,
                 null_safe: Optional[List[bool]] = None):
        super().__init__(conf)
        self.children = [left, right]
        self.left_keys = left_keys
        self.right_keys = right_keys
        self.join_type = join_type
        self.condition = condition
        self._output = output
        self.null_safe = list(null_safe or [False] * len(left_keys))
        # per-chip copies of a shared build side (mesh-sharded streams);
        # values pin their source batch so id() keys can never alias.
        # Bounded LRU: each entry holds a full build-side copy in HBM,
        # so the cache must not retain one per (partition, chip) for
        # the exec's whole lifetime
        from collections import OrderedDict
        self._build_dev_cache: "OrderedDict" = OrderedDict()
        self._build_dev_cap = 8
        self._build_dev_lock = threading.Lock()

    def _align_build(self, lwhole: DeviceBatch, rwhole: DeviceBatch
                     ) -> DeviceBatch:
        """When the stream chunk is resident on a different chip than
        the build side (streams over the mesh-sharded scan), ship the
        build side to the stream's chip — the reference broadcasts its
        build to every executor; here chips are the executors. Copies
        are cached per (build batch, chip) for the exec's lifetime."""
        from spark_rapids_tpu.columnar.device import (batch_device,
                                                      batch_to_device)
        ld = batch_device(lwhole)
        if ld is None:
            return rwhole
        rd = batch_device(rwhole)
        if rd is not None and rd.id == ld.id:
            return rwhole
        with self._build_dev_lock:
            key = (id(rwhole), ld.id)
            hit = self._build_dev_cache.get(key)
            if hit is None:
                from spark_rapids_tpu import retry as R
                hit = (rwhole, R.with_retry(
                    lambda: batch_to_device(rwhole, ld),
                    self.conf, self.metrics))
                self._build_dev_cache[key] = hit
            self._build_dev_cache.move_to_end(key)
            while len(self._build_dev_cache) > self._build_dev_cap:
                self._build_dev_cache.popitem(last=False)
            return hit[1]

    @property
    def left(self) -> TpuExec:
        return self.children[0]

    @property
    def right(self) -> TpuExec:
        return self.children[1]

    @property
    def output(self):
        return self._output

    def _pair_attrs(self):
        return list(self.left.output) + list(self.right.output)

    def _join_one(self, lbatches: List[DeviceBatch],
                  rbatches: List[DeviceBatch],
                  fk_hint: bool = False) -> Iterator[DeviceBatch]:
        lschema = self.left.schema
        rschema = self.right.schema
        lwhole = (concat_device(lbatches) if len(lbatches) > 1 else
                  lbatches[0] if lbatches else DeviceBatch.empty(lschema))
        rwhole = (concat_device(rbatches) if len(rbatches) > 1 else
                  rbatches[0] if rbatches else DeviceBatch.empty(rschema))
        rwhole = self._align_build(lwhole, rwhole)
        lk = P.bind_list(self.left_keys, self.left.output)
        rk = P.bind_list(self.right_keys, self.right.output)
        if self.join_type in MASK_JOINS:
            out_schema = lschema
        else:
            out_schema = self._pair_schema()
        from spark_rapids_tpu import retry as R
        from spark_rapids_tpu import trace as TR
        cond = None if self.condition is None else E.bind_references(
            self.condition, self._pair_attrs())
        if cond is not None and self.join_type in MASK_JOINS:
            # the condition decides inside the mask program; a left row's
            # verdict reads no other left row, so an OOM may halve the
            # stream side
            self.metrics.create(M.JOIN_CONDITIONAL_COUNT,
                                M.ESSENTIAL).add(1)
            with self.metrics.timed(M.JOIN_TIME, chip=TR.chip_of(lwhole)), \
                    self.metrics.timed(M.JOIN_CONDITION_TIME):
                pieces = R.with_split_retry(
                    lwhole, lambda piece: device_join(
                        piece, rwhole, lk, rk, self.join_type, out_schema,
                        null_safe=self.null_safe, metrics=self.metrics,
                        condition=cond),
                    self.conf, self.metrics)
            yield from pieces
            return

        def attempt():
            out = device_join(lwhole, rwhole, lk, rk, self.join_type,
                              out_schema, null_safe=self.null_safe,
                              fk_hint=fk_hint, metrics=self.metrics)
            if cond is not None:
                out = X.run_filter(cond, out)
            return out

        with self.metrics.timed(M.JOIN_TIME, chip=TR.chip_of(lwhole)):
            out = R.with_retry(attempt, self.conf, self.metrics)
        self._book_output(out)
        # the exec's declared output may prune/reorder pair columns
        if self.join_type not in MASK_JOINS:
            out = self._project_output(out)
        yield out

    def _project_output(self, pair: DeviceBatch) -> DeviceBatch:
        attrs = self._pair_attrs()
        want = [a.expr_id for a in self._output]
        have = {a.expr_id: i for i, a in enumerate(attrs)}
        if want == [a.expr_id for a in attrs]:
            return pair
        cols = [pair.columns[have[w]] for w in want]
        return DeviceBatch(self.schema, cols, pair.active, pair._num_rows,
                           pair._num_rows_dev)

    # join types whose per-left-row results are independent of other left
    # rows — the stream (left) side may be processed in bounded chunks
    # against the whole build side (JoinGatherer.scala:55 chunked-gather
    # role). Right/full outer chunk too: each chunk joins as inner/
    # leftouter while a matched-right mask accumulates on device, and the
    # unmatched right rows emit once at the end.
    _LEFT_STREAM_TYPES = ("inner", "cross", "left", "leftouter",
                          "leftsemi", "leftanti")
    _CHUNKED_OUTER = {"right": "inner", "rightouter": "inner",
                      "full": "leftouter", "fullouter": "leftouter"}

    # join types Spark builds broadcast-right for
    _BROADCASTABLE = ("inner", "cross", "left", "leftouter", "leftsemi",
                      "leftanti")

    def _subplan_cache_key(self) -> Optional[tuple]:
        """``(cache, key)`` for this join's build side when the
        cross-query subplan cache (docs/caching.md) is enabled, else
        None. The key is the build subtree's structural signature —
        identical build sides across queries, sessions, and tenants
        share one device-resident table."""
        from spark_rapids_tpu.serve import result_cache as RC
        if not RC.subplan_cache_enabled(self.conf):
            return None
        key = RC.subplan_signature(self.right, self.conf)
        return (RC.get_subplan_cache(self.conf), key)

    def _subplan_cache_put(self, probe, captured, rwhole) -> None:
        """Publish a freshly built broadcast table for cross-query
        reuse; refused entries (no fingerprints, oversized) just skip."""
        if probe is None or captured is None:
            return
        from spark_rapids_tpu.memory import get_device_store
        cache, key = probe
        cache.put(key, captured, rwhole, get_device_store(self.conf))

    def _aqe_try_broadcast(self) -> Optional[List[DevicePartitionThunk]]:
        """AQE runtime replan (GpuOverrides.scala:3550
        GpuQueryStagePrepOverrides role; docs/adaptive.md): materialize
        the build-side exchange, and when its MEASURED bytes land under
        adaptive.autoBroadcastBytes, demote the shuffled hash join to a
        broadcast-style join - build side concat once and shared across
        stream partitions, and the stream side's co-partitioning
        exchange is bypassed entirely (the surviving subtree re-enters
        the static fusion pass)."""
        from spark_rapids_tpu import adaptive as A
        from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
        from spark_rapids_tpu.memory import SpillableBatch
        if not A.adaptive_enabled(self.conf):
            return None
        threshold = A.auto_broadcast_bytes(self.conf)
        if threshold < 0 or self.join_type not in self._BROADCASTABLE:
            return None
        rexch = self.right
        if not isinstance(rexch, TpuShuffleExchangeExec) \
                or rexch._mesh_eligible():
            return None
        mat = rexch._materialize()
        handles = [h for part in mat for h in part
                   if isinstance(h, SpillableBatch)]
        total = sum(h.sizeof() for h in handles)
        if total > threshold:
            # capacity-based bytes over-count mask-filtered batches
            # (filters only flip the active mask); refine with the
            # ACTIVE row fraction before giving up - this sync is the
            # AQE stat read (Spark reads map output sizes the same way).
            # Spilled handles keep their full size (capacity_hint None):
            # a build side that spilled is no broadcast candidate, and
            # probing it would re-promote batches just for a statistic.
            total = 0
            for h in handles:
                cap = h.capacity_hint
                frac = (h.rows / cap) if cap else 1.0
                total += int(h.sizeof() * frac)
                if total > threshold:
                    return None
        if total > threshold:
            return None
        from spark_rapids_tpu import trace as TR
        from spark_rapids_tpu.serve import result_cache as RC
        with TR.span("aqeReplan", action="broadcastDemotion",
                     buildBytes=total, thresholdBytes=threshold):
            self.metrics.create("aqeBroadcastFlip", M.ESSENTIAL).add(1)
            self.metrics.create(M.JOIN_DEMOTED_COUNT, M.ESSENTIAL).add(1)
            self.metrics.create("aqeReplans", M.ESSENTIAL).add(1)
            probe = self._subplan_cache_key()
            rwhole = probe[0].lookup(probe[1]) if probe is not None \
                else None
            if rwhole is not None:
                self.metrics.create("subplanCacheHits",
                                    M.ESSENTIAL).add(1)
            else:
                rbatches = [h.get() for h in handles]
                rwhole = (concat_device(rbatches) if len(rbatches) > 1
                          else rbatches[0] if rbatches else
                          DeviceBatch.empty(self.right.schema))
                # the build executed during the exchange's stat
                # materialization above, so the pre-EXECUTION capture
                # (session TLS; a superset of this subtree's inputs) is
                # the only fingerprint honest for this data
                self._subplan_cache_put(
                    probe, RC.current_execution_fingerprints(), rwhole)
            left_src = self.left
            if isinstance(left_src, TpuShuffleExchangeExec) \
                    and not getattr(left_src.partitioning,
                                    "user_specified", False) \
                    and not left_src._mesh_eligible():
                # the exchange existed only for this join's
                # co-partitioning
                left_src = self._replan_stream_side(left_src)
        return self._broadcast_stream_thunks(left_src, rwhole)

    def _replan_stream_side(self, exch) -> TpuExec:
        """Drop the stream side's now-useless co-partitioning exchange.
        The surviving subtree is cloned plan_cache.clone_plan-style
        (fresh metric registries, locks and containers; the original
        nodes keep whatever was already recorded against them) and
        re-enters apply_overrides' fusion pass — the removed exchange
        boundary can expose a Filter/Project chain the static pass had
        to stop at. The join's child pointer is rewired so profile and
        history walks see the subtree that actually executed."""
        from spark_rapids_tpu.overrides import refuse_replanned_subtree
        from spark_rapids_tpu.plan_cache import clone_plan
        new_left = refuse_replanned_subtree(clone_plan(exch.child),
                                            self.conf)
        self.children[0] = new_left
        return new_left

    def _aqe_try_skew_split(self
                            ) -> Optional[List[DevicePartitionThunk]]:
        """AQE skew mitigation (docs/adaptive.md): when the realized
        stream-side partition sizes show a partition above
        adaptive.skewFactor x the median, that partition's retained
        batches split into sub-partitions — each re-joined against the
        SAME build partition — so one hot key stops serializing the
        probe stage behind a single task and stops riding the OOM-retry
        storm. Valid only for join types whose per-left-row results are
        independent (_LEFT_STREAM_TYPES); key colocation within the
        original partition is irrelevant downstream because the planner
        always re-partitions before the next keyed operator."""
        from spark_rapids_tpu import adaptive as A
        from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
        if not A.adaptive_enabled(self.conf) \
                or self.join_type not in self._LEFT_STREAM_TYPES:
            return None
        factor = A.skew_factor(self.conf)
        if factor <= 0:
            return None
        lexch, rexch = self.left, self.right
        for e in (lexch, rexch):
            if not isinstance(e, TpuShuffleExchangeExec) \
                    or e._mesh_eligible():
                return None
        lexch._materialize()
        stats = lexch.exchange_stats
        if stats is None:
            return None
        plan = A.skew_splits(stats, factor)
        if not plan:
            return None
        from spark_rapids_tpu import trace as TR
        with TR.span("aqeReplan", action="skewSplit",
                     partitions=len(plan),
                     skewRatio=round(stats.skew_ratio, 2)):
            self.metrics.create("aqeSkewSplits", M.ESSENTIAL).add(
                len(plan))
            self.metrics.create("aqeReplans", M.ESSENTIAL).add(1)
            mat = lexch._materialize()
            rparts = device_channel(rexch)
            assert len(mat) == len(rparts), \
                "join children must be co-partitioned"
            thunks: List[DevicePartitionThunk] = []
            for pid, rt in enumerate(rparts):
                if pid not in plan:
                    thunks.append(self._partition_join_thunk(
                        self._items_thunk(mat[pid]), rt,
                        co_parts=len(rparts)))
                    continue
                for items in self._split_partition(mat[pid], plan[pid]):
                    thunks.append(self._partition_join_thunk(
                        self._items_thunk(items), rt,
                        co_parts=len(rparts)))
        return thunks

    def _items_thunk(self, items) -> DevicePartitionThunk:
        """A stream-partition thunk over already-materialized exchange
        items (mirrors TpuShuffleExchangeExec.device_partitions' pull:
        promote, never close — the exchange owns its handles)."""
        from spark_rapids_tpu.memory import SpillableBatch

        def run() -> Iterator[DeviceBatch]:
            for item in items:
                yield (item.get() if isinstance(item, SpillableBatch)
                       else item)
        return run

    def _split_partition(self, items: List, k: int) -> List[List]:
        """Split one skewed partition's retained items into up to ``k``
        sub-partitions: contiguous byte-balanced slices of the handle
        list, and — when the list is too short to slice — the largest
        batch goes through the exchange's sort-split program first
        (split_by_pid over round-robin pids, the existing machinery).
        Sub-batches register as the join's own spillables; the
        exchange's originals stay untouched for other consumers."""
        from spark_rapids_tpu import adaptive as A
        if len(items) < k:
            import jax.numpy as jnp

            from spark_rapids_tpu import retry as R
            from spark_rapids_tpu.exec.exchange import (_round_robin_pids,
                                                        split_by_pid)
            from spark_rapids_tpu.memory import (SpillableBatch,
                                                 get_device_store)
            store = get_device_store(self.conf)
            weights = [A._item_stats(it)[0] for it in items]
            big = max(range(len(items)), key=lambda i: weights[i])
            pieces = k - len(items) + 1
            item = items[big]
            b = item.get() if isinstance(item, SpillableBatch) else item
            pids = _round_robin_pids(b.active, jnp.int32(0), pieces)
            parts = R.with_retry(
                lambda: split_by_pid(b, pids, pieces),
                self.conf, self.metrics)
            subs = [self.register_spillable(store, p)
                    for p in parts if p is not None]
            items = items[:big] + subs + items[big + 1:]
        weights = [A._item_stats(it)[0] for it in items]
        return [[items[i] for i in g]
                for g in A.slice_groups(weights, k)]

    def _broadcast_stream_thunks(self, left_src: TpuExec,
                                 rwhole: DeviceBatch
                                 ) -> List[DevicePartitionThunk]:
        """Broadcast-style execution: the resident build side is shared
        by every stream partition, and each stream partition keeps the
        shuffled path's discipline — batches register as spillable and
        join a chunk at a time (``_join_stream``: skew safety). Shared
        by TpuBroadcastHashJoinExec and the AQE runtime flip."""
        self._book_build(rwhole)
        # one sizing probe for the WHOLE broadcast: unique build keys
        # (the dimension-table norm) certify every stream chunk for the
        # no-sync FK fast path (ops/join.py build_key_max_multiplicity).
        # The probe resolves lazily at the first joined chunk, so its
        # one flat fetch overlaps the stream side's scan/upload.
        fk_resolve = None
        if self.join_type in ("inner", "left", "leftouter") \
                and self.condition is None:
            from spark_rapids_tpu.ops.join import build_key_max_multiplicity
            rk = P.bind_list(self.right_keys, self.right.output)
            fk_resolve = build_key_max_multiplicity(
                rwhole, rk, self.null_safe)
        fk_state: dict = {}
        fk_lock = threading.Lock()

        def fk_hint() -> bool:
            if fk_resolve is None:
                return False
            with fk_lock:
                if "v" not in fk_state:
                    fk_state["v"] = fk_resolve() <= 1
                    if fk_state["v"]:
                        self.metrics.create("fkFastPathJoins",
                                            M.ESSENTIAL).add(1)
            return fk_state["v"]

        def make(lt: DevicePartitionThunk) -> DevicePartitionThunk:
            def run() -> Iterator[DeviceBatch]:
                from spark_rapids_tpu.memory import get_device_store
                store = get_device_store(self.conf)
                lhandles = [self.register_spillable(store, b)
                            for b in lt() if b._num_rows != 0]
                yield from self._join_stream(lhandles, rwhole, fk_hint)
            return run
        return [make(t) for t in device_channel(left_src)]

    def device_partitions(self) -> List[DevicePartitionThunk]:
        flipped = self._aqe_try_broadcast()
        if flipped is not None:
            return flipped
        skewed = self._aqe_try_skew_split()
        if skewed is not None:
            return skewed
        lparts = device_channel(self.left)
        rparts = device_channel(self.right)
        assert len(lparts) == len(rparts), \
            "join children must be co-partitioned"
        return [self._partition_join_thunk(lt, rt,
                                           co_parts=len(lparts))
                for lt, rt in zip(lparts, rparts)]

    def _partition_join_thunk(self, lt: DevicePartitionThunk,
                              rt: DevicePartitionThunk,
                              co_parts: int = 1
                              ) -> DevicePartitionThunk:
        def make(lt: DevicePartitionThunk, rt: DevicePartitionThunk
                 ) -> DevicePartitionThunk:
            def run() -> Iterator[DeviceBatch]:
                from spark_rapids_tpu.memory import (get_budget_oracle,
                                                     get_device_store)
                store = get_device_store(self.conf)
                # stream side drains into spillable handles first, so a
                # skewed partition never pins both sides at once
                lhandles = [self.register_spillable(store, b)
                            for b in lt() if b._num_rows != 0]
                rb = [b for b in rt() if b._num_rows != 0]
                # planned out-of-core gate (docs/out_of_core.md): when
                # the build side's estimated bytes exceed the budget
                # oracle's operator share, partition BOTH sides by the
                # murmur3 partition hash into spill-backed buckets
                # sized up front, instead of concatenating a build
                # table the retry protocol would then thrash over
                oracle = get_budget_oracle(self.conf)
                if rb and oracle.enabled and self._ooc_eligible():
                    n = oracle.plan_partitions(
                        sum(b.sizeof() for b in rb), self.metrics)
                    if n > 1:
                        rhandles = [self.register_spillable(store, b)
                                    for b in rb]
                        yield from self._ooc_join(
                            store, lhandles, rhandles,
                            n * max(1, co_parts), oracle, depth=0)
                        return
                yield from self._join_items(store, lhandles, rb)
            return run
        return make(lt, rt)

    def _ooc_eligible(self) -> bool:
        """The partitioned out-of-core join needs hashable equi-keys
        (cross joins have none — every row would land in one bucket)
        and row-splittable batches (array/map columns carry element
        pools the sort-split cannot ride)."""
        if not self.left_keys:
            return False
        for a in list(self.left.output) + list(self.right.output):
            if isinstance(a.data_type, (T.ArrayType, T.MapType)):
                return False
        return True

    def _ooc_split(self, store, handles: List, bound_keys,
                   modulus: int) -> List[List]:
        """Split every handle's batch into ``modulus`` spill-backed
        buckets by the exchange's bit-exact murmur3 partition hash of
        the join keys (equal keys land in the same bucket on both
        sides, so per-bucket joins concatenate to the full join).
        Input handles close as they are consumed; only one source
        batch is promoted at a time."""
        from spark_rapids_tpu import retry as R
        from spark_rapids_tpu.exec.exchange import (hash_partition_ids,
                                                    split_by_pid)
        buckets: List[List] = [[] for _ in range(modulus)]
        for h in handles:
            b = h.get()
            with self.metrics.timed(M.PARTITION_TIME):
                parts = R.with_retry(
                    lambda b=b: split_by_pid(
                        b, hash_partition_ids(bound_keys, b, modulus),
                        modulus),
                    self.conf, self.metrics)
            h.close()
            for pid, part in enumerate(parts):
                if part is not None:
                    buckets[pid].append(
                        self.register_spillable(store, part))
        return buckets

    def _ooc_join(self, store, lhandles: List, rhandles: List,
                  modulus: int, oracle, depth: int
                  ) -> Iterator[DeviceBatch]:
        """Planned partitioned hash join (docs/out_of_core.md): both
        sides split by pmod(murmur3, modulus) into spill-backed
        buckets, processed one bucket at a time through the ordinary
        chunked-gather machinery. A bucket whose realized build bytes
        still exceed the budget share — or whose build materialization
        OOMs before anything was emitted — re-partitions recursively
        at a DOUBLED modulus (pmod(h, 2N) refines pmod(h, N)), bounded
        by outOfCore.maxRecursion; past the bound the OOM-retry
        protocol is the backstop, as everywhere else."""
        from spark_rapids_tpu import retry as R
        from spark_rapids_tpu import trace as TR
        TR.instant("oocJoinPlan", modulus=modulus, depth=depth)
        lk = P.bind_list(self.left_keys, self.left.output)
        rk = P.bind_list(self.right_keys, self.right.output)
        lbuckets = self._ooc_split(store, lhandles, lk, modulus)
        rbuckets = self._ooc_split(store, rhandles, rk, modulus)
        share = oracle.operator_share()
        inj = R.get_fault_injector(self.conf)
        for pid in range(modulus):
            lhs, rhs = lbuckets[pid], rbuckets[pid]
            if not lhs and not rhs:
                continue
            rbytes = sum(h.sizeof() for h in rhs)
            if rbytes > share and depth < oracle.max_recursion:
                # the estimate says this bucket still overflows:
                # re-plan (escalate), don't materialize-and-thrash
                self.metrics.create(M.PLANNED_OOC_ESCALATIONS,
                                    M.ESSENTIAL).add(1)
                yield from self._ooc_join(store, lhs, rhs, modulus * 2,
                                          oracle, depth + 1)
                continue
            def mat(rhs=rhs) -> List[DeviceBatch]:
                bs = [h.get() for h in rhs]
                return [concat_device(bs)] if len(bs) > 1 else bs

            if depth >= oracle.max_recursion:
                # recursion exhausted: the OOM-retry protocol is the
                # backstop for this bucket, as everywhere else
                rwhole = R.with_retry(mat, self.conf, self.metrics,
                                      site="oocJoin")
            else:
                try:
                    # the bucket's ONE over-budget-risk point: promote
                    # + concat the build bucket. Nothing has been
                    # emitted for this bucket yet and both sides'
                    # handles are intact, so an OOM here can soundly
                    # re-plan at a doubled modulus instead of riding
                    # the spill-and-retry loop
                    if inj is not None:
                        inj.on_alloc("oocJoin")
                    rwhole = mat()
                except Exception as e:
                    if not R.is_oom_error(e):
                        raise
                    self.metrics.create(M.PLANNED_OOC_ESCALATIONS,
                                        M.ESSENTIAL).add(1)
                    yield from self._ooc_join(store, lhs, rhs,
                                              modulus * 2, oracle,
                                              depth + 1)
                    continue
            for h in rhs:
                h.close()
            yield from self._join_items(store, lhs, rwhole)

    def _join_items(self, store, lhandles: List,
                    rb: List[DeviceBatch]) -> Iterator[DeviceBatch]:
        """One co-partition's join: the stream side arrives as
        spillable handles, the build side as device batches (shared by
        the in-memory path and each out-of-core bucket)."""
        rwhole = (concat_device(rb) if len(rb) > 1 else
                  rb[0] if rb else
                  DeviceBatch.empty(self.right.schema))
        self._book_build(rwhole)
        yield from self._join_stream(lhandles, rwhole)

    def _join_stream(self, lhandles: List, rwhole: DeviceBatch,
                     fk_hint: Callable[[], bool] = lambda: False
                     ) -> Iterator[DeviceBatch]:
        """One stream partition's handles against one build side, a
        chunk at a time, for every join type. Every probe sorts its
        chunk's lanes together with the build side's (ops/join.py
        _key_plan), so a chunk smaller than the build side spends most
        of its probe sorting the build side again: a chunk may grow to
        ``batchSizeRows`` or to the build side's capacity (a shape: no
        sync), whichever is larger. A build side over ``batchSizeRows``
        is then never probed by less than itself, and its join sorts at
        most twice the lanes it would joined whole. A right/full outer
        join in several chunks joins each as inner/leftouter while the
        matched-right mask accumulates on the device, and emits the
        unmatched right rows once at the end."""
        chunks = stream_chunks(
            lhandles, max(self.conf.batch_size_rows, rwhole.capacity))
        chunk_type = (self._CHUNKED_OUTER.get(self.join_type)
                      if len(chunks) > 1 else None)
        matched_any = None
        if chunk_type is not None:
            lk = P.bind_list(self.left_keys, self.left.output)
            rk = P.bind_list(self.right_keys, self.right.output)
            pair_schema = self._pair_schema()
        for chunk in chunks:
            # left handles re-promoted only for their own chunk
            lb = [h.get() for h in chunk]
            for h in chunk:
                h.close()
            self.metrics.create(M.JOIN_STREAM_CHUNKS, M.ESSENTIAL).add(1)
            if chunk_type is None:
                yield from self._join_one(lb, [rwhole], fk_hint=fk_hint())
            else:
                out, matched = self._join_one_matched(
                    lb, rwhole, chunk_type, lk, rk, pair_schema)
                from spark_rapids_tpu.ops.join import or_masks
                matched_any = matched if matched_any is None \
                    else or_masks(matched_any, matched)
                yield out
        if chunk_type is not None:
            from spark_rapids_tpu.ops.join import \
                right_extras_batch
            left_fields = [
                T.StructField(a.name, a.data_type, a.nullable)
                for a in self.left.output]
            extras = right_extras_batch(
                rwhole, matched_any, left_fields, pair_schema)
            yield self._project_output(extras)

    def _book_build(self, rwhole: DeviceBatch) -> None:
        """joinBuildRows, once per build, from a count someone already
        read (a lazy one stays unread: no sync for a counter)."""
        if rwhole._num_rows is not None:
            self.metrics.create(M.JOIN_BUILD_ROWS, M.ESSENTIAL).add(
                rwhole._num_rows)

    def _book_output(self, out: DeviceBatch) -> None:
        """Known counts only: fetching one here would be a blocking
        roundtrip per joined batch purely for the metric."""
        if out._num_rows is not None:
            for key in (M.NUM_OUTPUT_ROWS, M.JOIN_OUTPUT_ROWS):
                self.metrics.create(key, M.ESSENTIAL).add(out._num_rows)

    def _pair_schema(self) -> T.StructType:
        return T.StructType(
            [T.StructField(a.name, a.data_type, a.nullable)
             for a in self._pair_attrs()])

    def _join_one_matched(self, lbatches: List[DeviceBatch],
                          rwhole: DeviceBatch, chunk_type: str, lk, rk,
                          out_schema: T.StructType):
        """One stream chunk of a chunked right/full outer: joins with the
        downgraded ``chunk_type`` and returns (projected batch,
        matched-right device mask). Bound keys and the pair schema are
        hoisted out of the chunk loop by the caller."""
        lwhole = (concat_device(lbatches) if len(lbatches) > 1
                  else lbatches[0])
        rwhole = self._align_build(lwhole, rwhole)
        from spark_rapids_tpu import retry as R
        from spark_rapids_tpu import trace as TR
        with self.metrics.timed(M.JOIN_TIME, chip=TR.chip_of(lwhole)):
            out, matched = R.with_retry(
                lambda: device_join(lwhole, rwhole, lk, rk, chunk_type,
                                    out_schema, collect_matched_r=True,
                                    null_safe=self.null_safe,
                                    metrics=self.metrics),
                self.conf, self.metrics)
        self._book_output(out)
        return self._project_output(out), matched

    def simple_string(self):
        return (f"TpuShuffledHashJoin {self.join_type} l={self.left_keys} "
                f"r={self.right_keys} cond={self.condition!r}")


class TpuBroadcastHashJoinExec(TpuShuffledHashJoinExec):
    """Build side (right) materialized once in HBM and shared across all
    stream partitions (GpuBroadcastHashJoinExec; the broadcast itself is
    the device residency — no per-partition re-upload)."""

    def device_partitions(self) -> List[DevicePartitionThunk]:
        from spark_rapids_tpu.serve import result_cache as RC
        probe = self._subplan_cache_key()
        captured = None
        if probe is not None:
            cached = probe[0].lookup(probe[1])
            if cached is not None:
                # cross-query build reuse (docs/caching.md): the build
                # subtree never executes — zero scan/decode/concat work
                self.metrics.create("subplanCacheHits",
                                    M.ESSENTIAL).add(1)
                return self._broadcast_stream_thunks(self.left, cached)
            # fingerprint the build inputs BEFORE the build reads them:
            # a file mutated mid-build mismatches at reuse time instead
            # of going stale
            captured = RC.capture_fingerprints(self.right)
        # skip only KNOWN-empty batches: a row_count() here costs a
        # blocking roundtrip per batch; concat_device syncs counts once
        # when it actually has to stitch
        rbatches: List[DeviceBatch] = []
        for t in device_channel(self.right):
            rbatches.extend(b for b in t() if b._num_rows != 0)
        # concat the build side ONCE (a TpuBroadcastExchangeExec child
        # already yields its single cached batch); every stream
        # partition shares it, chunked by _join_stream
        rwhole = (concat_device(rbatches) if len(rbatches) > 1 else
                  rbatches[0] if rbatches else
                  DeviceBatch.empty(self.right.schema))
        self._subplan_cache_put(probe, captured, rwhole)
        return self._broadcast_stream_thunks(self.left, rwhole)

    def simple_string(self):
        return (f"TpuBroadcastHashJoin {self.join_type} l={self.left_keys} "
                f"r={self.right_keys} cond={self.condition!r}")

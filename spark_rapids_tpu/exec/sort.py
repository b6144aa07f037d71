"""TpuSortExec / TpuTopNExec: device sort (GpuSortExec.scala:68 twin).

Per-partition sort matching the CPU engine's semantics. Partitions that
fit the batch-row goal are concatenated and sorted in one fused program.
Larger partitions take the OUT-OF-CORE path (GpuOutOfCoreSortIterator,
GpuSortExec.scala:231, re-imagined for the static-shape model): input
batches become spillable handles while only their order-encoded KEY
columns stay resident; global sort ranks split every batch into
rank-contiguous sub-ranges (the same exact-rank machinery as the range
exchange), and each sub-range — bounded by the batch-row goal — is then
concatenated, sorted, and emitted in order. The partition is never fully
resident in HBM; stable rank splitting keeps the result bit-identical to
the CPU engine's stable lexsort.

TpuTopNExec is the TakeOrderedAndProject analogue (GpuTopN,
limit.scala:123): sort then keep the first ``n`` rows via the active
mask — no data movement beyond the sort's own gather; per-batch TopN
bounds memory by construction, so it never needs the out-of-core path.
"""

from __future__ import annotations

from typing import Callable, Dict, Iterator, List, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_tpu import metrics as M
from spark_rapids_tpu.columnar.device import (DeviceBatch, bucket_capacity,
                                              concat_device, take_columns)
from spark_rapids_tpu.conf import TpuConf
from spark_rapids_tpu.exec.base import (DevicePartitionThunk, TpuExec,
                                        device_channel)
from spark_rapids_tpu.exec.exchange import range_key_columns
from spark_rapids_tpu.ops import exprs as X
from spark_rapids_tpu.ops import sort as S
from spark_rapids_tpu.sql import expressions as E
from spark_rapids_tpu.sql import physical as P

from spark_rapids_tpu.jit_cache import JitCache, named_jit, program_name

_SORT_FN_CACHE = JitCache("sort")


def is_device_sort(order: List[E.SortOrder], conf: TpuConf):
    """Tagging helper: None when every sort key can run on device."""
    from spark_rapids_tpu.sql import types as T
    for o in order:
        dt = o.child.data_type
        if isinstance(dt, (T.ArrayType, T.MapType, T.StructType)):
            return "nested sort keys are not supported on TPU"
        r = X.is_device_expr(o.child, conf)
        if r:
            return r
        if X.contains_ansi_cast(o.child):
            return "ANSI casts in sort keys run on CPU"
    return None


def sorted_batch(order: List[E.SortOrder], bound: List[E.Expression],
                 batch: DeviceBatch, limit: int = -1) -> DeviceBatch:
    """Sort one device batch by `order` (keys pre-bound); optionally keep
    only the first `limit` rows, emitted at the limit's own capacity
    bucket: the kept rows are a prefix, and what a collect fetches is
    the capacity (100 rows of query 51's filtered windows rode 786,432
    lanes, 67 MB, to the host: PERF.md §6, PR 36). One fused jitted
    program."""
    from spark_rapids_tpu.ops import groupby as G
    salt = G.kernel_salt()  # snapshot: key AND trace use this value
    key = (tuple(X.expr_key(e) for e in bound),
           tuple((o.ascending, o.nulls_first) for o in order),
           limit, salt)
    fn = _SORT_FN_CACHE.get(key)
    if fn is None:
        orders = list(order)
        bound_t = tuple(bound)
        has_nans = salt[0]

        def _fn(cols, active, lit_vals):
            from spark_rapids_tpu.columnar.device import (
                flatten_columns, rebuild_columns, sort_with_payload)
            cap = active.shape[0]
            ctx = X.Ctx(cols, cap, bound_t, lit_vals)
            key_cols = [X.dev_eval(e, ctx) for e in bound_t]
            # every column array rides the sort as payload (one
            # multi-operand lax.sort; sort+gather is far slower on TPU)
            subkeys: list = [~active]
            for c, o in zip(key_cols, orders):
                subkeys.extend(
                    S.order_subkeys(c, o.ascending, o.nulls_first,
                                    has_nans))
            flat, spec = flatten_columns(cols)
            _k, _order, sorted_flat = sort_with_payload(subkeys, flat)
            n = jnp.sum(active)
            if limit >= 0:
                n = jnp.minimum(n, limit)
                cap = min(cap, bucket_capacity(limit))
                sorted_flat = [a[:cap] for a in sorted_flat]
            new_active = jnp.arange(cap) < n
            from spark_rapids_tpu.columnar.device import mask_col
            out = [mask_col(c, new_active).arrays()
                   for c in rebuild_columns(spec, sorted_flat)]
            return out, new_active
        fn = _SORT_FN_CACHE.put(key, named_jit(
            "srt_topn" if limit >= 0 else "srt_sort", _fn))
    from spark_rapids_tpu import trace as TR
    TR.first_dispatch(None, fn)
    arrs, new_active = fn(batch.columns, batch.active,
                          X.literal_values(bound))
    from spark_rapids_tpu.columnar.device import make_column
    cols = [make_column(c.dtype, a) for c, a in zip(batch.columns, arrs)]
    return DeviceBatch(batch.schema, cols, new_active, None)


class TpuSortExec(TpuExec):
    def __init__(self, order: List[E.SortOrder], is_global: bool,
                 child: TpuExec, conf: TpuConf):
        super().__init__(conf)
        self.children = [child]
        self.order = order
        self.is_global = is_global

    @property
    def child(self) -> TpuExec:
        return self.children[0]

    @property
    def output(self):
        return self.child.output

    def _limit(self) -> int:
        return -1

    def device_partitions(self) -> List[DevicePartitionThunk]:
        bound = P.bind_list([o.child for o in self.order],
                            self.child.output)
        metrics = self.metrics
        limit = self._limit()
        goal = self.conf.batch_size_rows

        def make(thunk: DevicePartitionThunk) -> DevicePartitionThunk:
            def run() -> Iterator[DeviceBatch]:
                if limit >= 0:
                    # TopN: memory-bounded by construction (per-batch
                    # sort+limit, then one bounded merge). Skip only
                    # KNOWN-empty batches: a row_count() here would be a
                    # blocking roundtrip per input batch
                    batches = [b for b in thunk() if b._num_rows != 0]
                    if not batches:
                        return
                    whole = (batches[0] if len(batches) == 1
                             else concat_device(batches))
                    from spark_rapids_tpu import retry as R
                    from spark_rapids_tpu import trace as TR
                    with metrics.timed(M.SORT_TIME,
                                       chip=TR.chip_of(whole)):
                        out = R.with_retry(
                            lambda: sorted_batch(self.order, bound,
                                                 whole, limit),
                            self.conf, metrics)
                    metrics.create(M.NUM_OUTPUT_ROWS, M.ESSENTIAL).add(
                        out.row_count())
                    yield out
                    return
                from spark_rapids_tpu.memory import get_device_store
                store = get_device_store(self.conf)
                handles, keycols, actives = [], [], []
                for b in thunk():
                    if b._num_rows == 0:  # skip only KNOWN-empty
                        continue
                    with metrics.timed(M.SORT_TIME):
                        keycols.append(
                            range_key_columns(self.order, bound, b))
                    actives.append(b.active)
                    handles.append(self.register_spillable(store, b))
                if not handles:
                    return
                # len check FIRST: a single handle sorts in-core no
                # matter its size, and skipping h.rows avoids a count
                # sync (the common single-batch case post-aggregation)
                if len(handles) == 1 or \
                        sum(h.rows for h in handles) <= goal:
                    keycols.clear()
                    whole = concat_device([h.get() for h in handles])
                    for h in handles:
                        h.close()
                    from spark_rapids_tpu import retry as R
                    from spark_rapids_tpu import trace as TR
                    with metrics.timed(M.SORT_TIME,
                                       chip=TR.chip_of(whole)):
                        # retry-only: a sort is not row-splittable (the
                        # out-of-core rank-split path IS the split story)
                        out = R.with_retry(
                            lambda: sorted_batch(self.order, bound,
                                                 whole, -1),
                            self.conf, metrics)
                    if out._num_rows is not None:
                        # known counts only: fetching one here would be
                        # a blocking D2H roundtrip purely for the metric
                        metrics.create(M.NUM_OUTPUT_ROWS,
                                       M.ESSENTIAL).add(out._num_rows)
                    yield out
                    return
                yield from self._out_of_core(
                    store, handles, keycols, actives,
                    sum(h.rows for h in handles),  # cached after the gate
                    goal, bound, metrics)
            return run
        return [make(t) for t in device_channel(self.child)]

    def _out_of_core(self, store, handles, keycols, actives, total: int,
                     goal: int, bound, metrics) -> Iterator[DeviceBatch]:
        """Rank-split external sort: exact global ranks over the resident
        key columns assign each row to a rank-contiguous sub-range of at
        most ``goal`` rows; each sub-range is concatenated, sorted, and
        emitted in order (GpuSortExec.scala:231 role)."""
        from spark_rapids_tpu import retry as R
        from spark_rapids_tpu.exec.exchange import (global_range_pids,
                                                    realign_spilled_pids,
                                                    split_by_pid)
        n_sub = (total + goal - 1) // goal
        with metrics.timed(M.SORT_TIME):
            pids_per_batch = R.with_retry(
                lambda: global_range_pids(self.order, keycols, actives,
                                          n_sub),
                self.conf, metrics)
        keycols.clear()
        buckets: List[List] = [[] for _ in range(n_sub)]
        for h, pids, act in zip(handles, pids_per_batch, actives):
            b, pids = realign_spilled_pids(h, pids, act)
            with metrics.timed(M.SORT_TIME):
                parts = R.with_retry(
                    lambda b=b, pids=pids: split_by_pid(b, pids, n_sub),
                    self.conf, metrics)
            h.close()
            for pid, part in enumerate(parts):
                if part is not None:
                    buckets[pid].append(
                        self.register_spillable(store, part))
        for pid in range(n_sub):
            parts = [h.get() for h in buckets[pid]]
            if not parts:
                continue
            whole = parts[0] if len(parts) == 1 else concat_device(parts)
            for h in buckets[pid]:
                h.close()
            from spark_rapids_tpu import trace as TR
            with metrics.timed(M.SORT_TIME, chip=TR.chip_of(whole)):
                out = R.with_retry(
                    lambda w=whole: sorted_batch(self.order, bound, w,
                                                 -1),
                    self.conf, metrics)
            metrics.create(M.NUM_OUTPUT_ROWS, M.ESSENTIAL).add(
                out.row_count())
            yield out

    def simple_string(self):
        return f"TpuSort {self.order} global={self.is_global}"


class TpuTopNExec(TpuSortExec):
    """Sort + per-partition limit in one device program (GpuTopN)."""

    def __init__(self, n: int, order: List[E.SortOrder], child: TpuExec,
                 conf: TpuConf):
        super().__init__(order, False, child, conf)
        self.n = n

    def _limit(self) -> int:
        return self.n

    def simple_string(self):
        return f"TpuTopN n={self.n} {self.order}"

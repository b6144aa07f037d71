"""Basic device operators: project, filter, range, union, limit
(basicPhysicalOperators.scala:113,313,374,510 and limit.scala twins).

Projects/filters evaluate their whole bound expression list as ONE fused
jitted XLA program (ops/exprs.py); filters only flip the ``active`` mask —
no data movement until an explicit compaction point (shuffle/concat), which
is the static-shape discipline SURVEY.md section 7(a) calls for.
"""

from __future__ import annotations

from typing import Callable, Iterator, List

import jax.numpy as jnp

from spark_rapids_tpu import metrics as M
from spark_rapids_tpu.columnar.device import DeviceBatch, bucket_capacity
from spark_rapids_tpu.conf import TpuConf
from spark_rapids_tpu.exec.base import (DevicePartitionThunk, TpuExec,
                                        device_channel)
from spark_rapids_tpu.ops import exprs as X
from spark_rapids_tpu.sql import expressions as E
from spark_rapids_tpu.sql import physical as P
from spark_rapids_tpu.sql import types as T

import jax

from spark_rapids_tpu.jit_cache import JitCache, named_jit

# row counters are DEVICE int64 scalars created via T.device_long —
# a bare jnp.int64 would silently truncate to int32 without x64 and
# wrap past 2^31 rows; the explicit dtype= keeps the jitted sum wide
# tpu-lint: disable=jit-direct(single fixed row-counter program — one executable, bounded by construction)
_advance_rows = named_jit(
    "srt_advance_rows",
    lambda start, active: start + jnp.sum(active, dtype=jnp.int64))


class TpuProjectExec(TpuExec):
    def __init__(self, project_list: List[E.Expression], child: TpuExec,
                 conf: TpuConf):
        super().__init__(conf)
        self.children = [child]
        self.project_list = project_list

    @property
    def child(self) -> TpuExec:
        return self.children[0]

    @property
    def output(self):
        return [E.named_output(e) for e in self.project_list]

    def device_partitions(self) -> List[DevicePartitionThunk]:
        bound = P.bind_list(self.project_list, self.child.output)
        schema = self.schema
        metrics = self.metrics
        needs_part = X._needs_part_ctx(bound)

        def make(pid: int, thunk: DevicePartitionThunk
                 ) -> DevicePartitionThunk:
            def run() -> Iterator[DeviceBatch]:
                # row_start rides as a DEVICE scalar so counting rows
                # across batches never syncs to host
                row_start = T.device_long(0) if needs_part else None
                pid_d = T.device_long(pid) if needs_part else None
                for b in thunk():
                    with metrics.timed(M.OP_TIME):
                        if needs_part:
                            cols = X.run_project(
                                bound, b, part_ctx=(pid_d, row_start))
                            row_start = _advance_rows(row_start,
                                                      b.active)
                        else:
                            cols = X.run_project(bound, b)
                    metrics.create(M.DISPATCH_COUNT, M.ESSENTIAL).add(1)
                    from spark_rapids_tpu.parallel.mesh import \
                        record_chip_dispatch
                    record_chip_dispatch(metrics, b)
                    metrics.create(M.NUM_OUTPUT_BATCHES, M.ESSENTIAL).add(1)
                    yield b.with_columns(schema, cols)
            return run
        return [make(i, t)
                for i, t in enumerate(device_channel(self.child))]

    def simple_string(self):
        return f"TpuProject {self.project_list}"


class TpuFilterExec(TpuExec):
    def __init__(self, condition: E.Expression, child: TpuExec,
                 conf: TpuConf):
        super().__init__(conf)
        self.children = [child]
        self.condition = condition

    @property
    def child(self) -> TpuExec:
        return self.children[0]

    @property
    def output(self):
        return self.child.output

    def device_partitions(self) -> List[DevicePartitionThunk]:
        bound = E.bind_references(self.condition, self.child.output)
        metrics = self.metrics
        needs_part = X._needs_part_ctx([bound])

        def make(pid: int, thunk: DevicePartitionThunk
                 ) -> DevicePartitionThunk:
            def run() -> Iterator[DeviceBatch]:
                row_start = T.device_long(0) if needs_part else None
                pid_d = T.device_long(pid) if needs_part else None
                for b in thunk():
                    with metrics.timed(M.OP_TIME):
                        if needs_part:
                            out = X.run_filter(
                                bound, b, part_ctx=(pid_d, row_start),
                                metrics=metrics)
                            row_start = _advance_rows(row_start,
                                                      b.active)
                        else:
                            out = X.run_filter(bound, b, metrics=metrics)
                    metrics.create(M.DISPATCH_COUNT, M.ESSENTIAL).add(1)
                    from spark_rapids_tpu.parallel.mesh import \
                        record_chip_dispatch
                    record_chip_dispatch(metrics, b)
                    metrics.create(M.NUM_OUTPUT_BATCHES, M.ESSENTIAL).add(1)
                    yield out
            return run
        return [make(i, t)
                for i, t in enumerate(device_channel(self.child))]

    def simple_string(self):
        return f"TpuFilter {self.condition!r}"


def _range_chunk_body(start, off, step, n, cap):
    idx = jnp.arange(cap, dtype=jnp.int64)
    data = start + (off + idx) * step
    active = idx < n
    return jnp.where(active, data, jnp.int64(0)), active


def _limit_mask_body(active, remaining):
    rank = jnp.cumsum(active.astype(jnp.int32))
    return active & (rank <= remaining)


# tpu-lint: disable=jit-direct(two fixed helper programs — jax's own signature cache bounds them by capacity bucket)
_range_chunk = named_jit("srt_range_chunk", _range_chunk_body,
                         static_argnums=(4,))
# tpu-lint: disable=jit-direct(two fixed helper programs — jax's own signature cache bounds them by capacity bucket)
_limit_mask = named_jit("srt_limit_mask", _limit_mask_body)


class TpuRangeExec(TpuExec):
    """Device iota (GpuRangeExec basicPhysicalOperators.scala:374): values
    are generated directly in HBM, chunked to the batch-row goal."""

    def __init__(self, output, start: int, end: int, step: int,
                 num_partitions: int, conf: TpuConf):
        super().__init__(conf)
        self.children = []
        self._output = output
        self.start, self.end, self.step = start, end, step
        self.num_partitions = max(1, num_partitions)

    @property
    def output(self):
        return self._output

    def device_partitions(self) -> List[DevicePartitionThunk]:
        total = max(0, (self.end - self.start + self.step
                        - (1 if self.step > 0 else -1)) // self.step)
        per = (total + self.num_partitions - 1) // self.num_partitions \
            if total else 0
        goal = self.conf.batch_size_rows
        schema = self.schema

        def make(pidx: int) -> DevicePartitionThunk:
            def run() -> Iterator[DeviceBatch]:
                lo = pidx * per
                hi = min(total, lo + per)
                off = lo
                while off < hi:
                    n = min(goal, hi - off)
                    cap = bucket_capacity(n)
                    # ONE jitted program per capacity bucket (four
                    # eager ops would each pay a dispatch)
                    data, active = _range_chunk(
                        T.device_long(self.start), T.device_long(off),
                        T.device_long(self.step), T.device_long(n), cap)
                    from spark_rapids_tpu.columnar.device import DeviceColumn
                    col = DeviceColumn(T.LongT, data, active)
                    yield DeviceBatch(schema, [col], active, n)
                    off += n
            return run
        return [make(i) for i in range(self.num_partitions)]

    def simple_string(self):
        return f"TpuRange ({self.start}, {self.end}, step={self.step})"


class TpuUnionExec(TpuExec):
    def __init__(self, children: List[TpuExec], output, conf: TpuConf):
        super().__init__(conf)
        self.children = list(children)
        self._output = output

    @property
    def output(self):
        return self._output

    def device_partitions(self) -> List[DevicePartitionThunk]:
        out: List[DevicePartitionThunk] = []
        schema = self.schema

        def retag(thunk: DevicePartitionThunk) -> DevicePartitionThunk:
            def run() -> Iterator[DeviceBatch]:
                for b in thunk():
                    yield DeviceBatch(schema, b.columns, b.active,
                                      b._num_rows, b._num_rows_dev)
            return run
        for c in self.children:
            out.extend(retag(t) for t in device_channel(c))
        return out

    def simple_string(self):
        return "TpuUnion"


class TpuLocalLimitExec(TpuExec):
    """Limit on device batches (limit.scala:124): keeps the first n active
    rows by masking — cumulative count over the active mask, fixed shape."""

    def __init__(self, n: int, child: TpuExec, conf: TpuConf):
        super().__init__(conf)
        self.children = [child]
        self.n = n

    @property
    def child(self) -> TpuExec:
        return self.children[0]

    @property
    def output(self):
        return self.child.output

    def device_partitions(self) -> List[DevicePartitionThunk]:
        n = self.n

        def make(thunk: DevicePartitionThunk) -> DevicePartitionThunk:
            def run() -> Iterator[DeviceBatch]:
                remaining = n
                for b in thunk():
                    if remaining <= 0:
                        break
                    cnt = b.row_count()
                    if cnt <= remaining:
                        remaining -= cnt
                        yield b
                        continue
                    # jitted: the eager cumsum+and paid two dispatch
                    # handshakes per truncated batch
                    active = _limit_mask(b.active, jnp.int32(remaining))
                    yield DeviceBatch(b.schema, b.columns, active, remaining)
                    remaining = 0
            return run
        return [make(t) for t in device_channel(self.child)]

    def simple_string(self):
        return f"TpuLocalLimit {self.n}"


class TpuGlobalLimitExec(TpuLocalLimitExec):
    """Same mask-based limit over the single post-exchange partition
    (limit.scala:129)."""

    def simple_string(self):
        return f"TpuGlobalLimit {self.n}"


_EXPAND_CACHE = JitCache("expand")


def _expand_entry_key(e: E.Expression) -> tuple:
    """What one entry of a projection is to the expand program: a null
    (the key a set leaves out), a constant, or an expression. A literal
    (the grouping id) is a constant of the program, its value in the
    key: the sets' literals are the same in every query of a plan, and
    passing them as scalars would upload one a set and batch."""
    if isinstance(e, E.Literal):
        if e.value is None:
            return ("null",)
        return ("const", repr(e.value), repr(e.data_type))
    return ("expr", X.expr_key(e))


def _expand_exprs(projections) -> List[E.Expression]:
    """The entries the expand program evaluates, in one order for the
    program's literals and the dispatch's."""
    return [e for proj in projections for e in proj
            if _expand_entry_key(e)[0] == "expr"]


def _expand_slots(projections) -> List[List[object]]:
    """Where each entry's column comes from: None for a null, else the
    slot it is evaluated into. A constant, or an expression with no
    runtime literal, reads the same wherever it appears and shares one
    slot; an expression with a runtime literal has a slot of its own,
    since ``expr_key`` leaves literal values out."""
    slots = []
    for si, proj in enumerate(projections):
        row = []
        for j, e in enumerate(proj):
            key = _expand_entry_key(e)
            if key[0] == "null":
                row.append(None)
            elif key[0] == "expr" and any(X.collect_literals([e])):
                row.append((si, j))
            else:
                row.append(key)
        slots.append(row)
    return slots


def _build_expand(projections, out_cap: int) -> Callable:
    """ONE program for every grouping set of a batch. A batch whose rows
    fit a smaller capacity bucket is first compacted to ``out_cap`` lanes
    (``expand/compact``: the sets would repeat its empty lanes nine
    times). Each slot is then evaluated once (``expand/project``), a key
    a set leaves out is that column with every row nulled, and the sets
    are written one after another (``expand/write``): set ``s`` at lanes
    ``[s * out_cap, (s + 1) * out_cap)``, the rows' activity repeated."""
    from spark_rapids_tpu.columnar.device import (_compact_body,
                                                  flatten_columns, mask_col,
                                                  rebuild_columns)
    width = len(projections[0])
    exprs = _expand_exprs(projections)
    slots = _expand_slots(projections)

    def fn(cols, active, lit_vals):
        if out_cap < active.shape[0]:
            with jax.named_scope("expand/compact"):
                flat, spec = flatten_columns(cols)
                active, flat = _compact_body(active, flat, out_cap)
                cols = rebuild_columns(spec, flat)
        ctx = X.Ctx(cols, out_cap, exprs, lit_vals)
        ctx.active_hint = active
        with jax.named_scope("expand/project"):
            evaluated = {}
            for proj, row in zip(projections, slots):
                for e, slot in zip(proj, row):
                    if slot is not None and slot not in evaluated:
                        evaluated[slot] = mask_col(X.dev_eval(e, ctx),
                                                   active)
            template = [next((evaluated[row[j]] for row in slots
                              if row[j] is not None), None)
                        for j in range(width)]
            none = jnp.zeros_like(active)
            sets = []
            for proj, row in zip(projections, slots):
                out = []
                for j, (e, slot) in enumerate(zip(proj, row)):
                    if slot is not None:
                        out.append(evaluated[slot])
                    elif template[j] is not None:
                        out.append(mask_col(template[j], none))
                    else:
                        raise X.DeviceUnsupported(
                            f"expand entry {e!r} has no column to null")
                sets.append(flatten_columns(out))
        with jax.named_scope("expand/write"):
            spec = sets[0][1]
            flat = [jnp.concatenate([f[i] for f, _ in sets], axis=0)
                    for i in range(len(sets[0][0]))]
            return (rebuild_columns(spec, flat),
                    jnp.concatenate([active] * len(projections)))
    return named_jit("srt_expand", fn)


class TpuExpandExec(TpuExec):
    """Grouping-sets expansion (GpuExpandExec.scala twin): ONE program a
    batch writes every set's projection into one output batch of
    ``sets x bucket(rows)`` lanes (``_build_expand``). The batch's row
    count is read first (``deviceSync site=expandRows``, where its
    producer attached none): it sizes the output, so a join's sparse
    output is not expanded with its empty lanes. The host books
    ``expandTime`` around the enqueue (the count's wait is
    ``deviceSyncTime``'s) and ``expandRows``, sets x the rows."""

    def __init__(self, projections: List[List[E.Expression]],
                 output, child: TpuExec, conf: TpuConf):
        super().__init__(conf)
        self.children = [child]
        self.projections = projections
        self._output = output

    @property
    def child(self) -> TpuExec:
        return self.children[0]

    @property
    def output(self):
        return self._output

    def device_partitions(self) -> List[DevicePartitionThunk]:
        from spark_rapids_tpu import trace as TR
        bound = [P.bind_list(proj, self.child.output)
                 for proj in self.projections]
        key = tuple(tuple(_expand_entry_key(e) for e in proj)
                    for proj in bound)
        lit_exprs = _expand_exprs(bound)
        schema = self.schema
        metrics = self.metrics
        n_sets = len(bound)

        def make(thunk: DevicePartitionThunk) -> DevicePartitionThunk:
            def run() -> Iterator[DeviceBatch]:
                for b in thunk():
                    rows = b.row_count(site="expandRows")
                    if rows == 0:
                        continue
                    with metrics.timed(M.EXPAND_TIME):
                        out_cap = min(b.capacity, bucket_capacity(rows))
                        fn = _EXPAND_CACHE.get((key, out_cap))
                        if fn is None:
                            fn = _EXPAND_CACHE.put(
                                (key, out_cap), _build_expand(bound, out_cap))
                        TR.first_dispatch(metrics, fn)
                        cols, active = fn(b.columns, b.active,
                                          X.literal_values(lit_exprs))
                    metrics.create(M.EXPAND_ROWS, M.ESSENTIAL).add(
                        n_sets * rows)
                    metrics.create(M.DISPATCH_COUNT, M.ESSENTIAL).add(1)
                    yield DeviceBatch(schema, cols, active, n_sets * rows)
            return run
        return [make(t) for t in device_channel(self.child)]

    def simple_string(self):
        return f"TpuExpand [{len(self.projections)} sets]"

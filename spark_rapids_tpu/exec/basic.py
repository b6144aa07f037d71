"""Basic device operators: project, filter, range, union, limit
(basicPhysicalOperators.scala:113,313,374,510 and limit.scala twins).

Projects/filters evaluate their whole bound expression list as ONE fused
jitted XLA program (ops/exprs.py); filters only flip the ``active`` mask —
no data movement until an explicit compaction point (shuffle/concat), which
is the static-shape discipline SURVEY.md section 7(a) calls for.
"""

from __future__ import annotations

from typing import Iterator, List

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

from spark_rapids_tpu.jit_cache import named_jit

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


class TpuExpandExec(TpuExec):
    """Grouping-sets expansion (GpuExpandExec.scala twin): each input
    batch is projected once per grouping set and the results concat on
    device (one fused program per projection + the jitted concat)."""

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
        from spark_rapids_tpu.columnar.device import concat_device
        bound = [P.bind_list(proj, self.child.output)
                 for proj in self.projections]
        schema = self.schema
        metrics = self.metrics

        def make(thunk: DevicePartitionThunk) -> DevicePartitionThunk:
            def run() -> Iterator[DeviceBatch]:
                for b in thunk():
                    outs = []
                    for proj in bound:
                        with metrics.timed(M.OP_TIME):
                            cols = X.run_project(proj, b)
                        outs.append(b.with_columns(schema, cols))
                    if outs:
                        yield concat_device(outs)
            return run
        return [make(t) for t in device_channel(self.child)]

    def simple_string(self):
        return f"TpuExpand [{len(self.projections)} sets]"

"""TpuFusedStageExec: whole-stage fusion of linear Tpu*Exec chains.

The r05 bench showed the device wall on TPC-H q1 is NOT the math
(per-op times are milliseconds) but per-operator dispatch, intermediate
DeviceBatch materialization between ops, and serial batch-at-a-time
draining. The reference attacks this with GpuTieredProject + coalesced
per-batch execution (SURVEY §L5); Spark's own answer is whole-stage
codegen. The JAX-native equivalent implemented here: after the plan
rewrite, ``fuse_stages`` collapses every maximal linear chain of
per-batch, shape-preserving operators —

    TpuFilterExec -> TpuProjectExec -> [partial TpuHashAggregateExec]

(and filter/project chains feeding sort/TopN/join build sides) — into
ONE ``TpuFusedStageExec`` whose whole chain is traced into a single
jitted XLA program per (chain structure, capacity bucket), cached in a
bounded LRU like the aggregation programs. When the chain's source is a
fresh-buffer producer (the row-to-columnar upload or the device iota
range) the program additionally DONATES the input HBM buffers
(``jax.jit(..., donate_argnums=...)``), so each batch's input storage
is reused for the outputs instead of being held live across the op
boundary.

Draining is asynchronous: ``device_partitions`` keeps a configurable
window of ``spark.rapids.sql.stageFusion.maxInFlight`` batches in
flight — batch k+1 is dispatched while batch k computes, and the stage
only blocks at its sink (JAX's async dispatch does the device-side
overlap; the window bounds HBM held by outstanding batches).

Metrics: per-operator metrics still report under the SAME stage keys —
the fused node fans ``numOutputBatches``/``opTime`` updates back to its
constituent execs — plus the fusion-specific ``fusedOps``,
``dispatchCount`` and ``stageCompileTime`` counters (docs/fusion.md).
"""

from __future__ import annotations

from collections import deque
from typing import Iterator, List, Optional, Tuple

import jax

from spark_rapids_tpu import metrics as M
from spark_rapids_tpu.columnar.device import DeviceBatch
from spark_rapids_tpu.conf import (STAGE_FUSION_ENABLED,
                                   STAGE_FUSION_MAX_IN_FLIGHT, TpuConf)
from spark_rapids_tpu.exec.base import (DevicePartitionThunk, TpuExec,
                                        TpuRowToColumnarExec,
                                        device_channel)
from spark_rapids_tpu.exec.basic import (TpuFilterExec, TpuProjectExec,
                                         TpuRangeExec)
from spark_rapids_tpu.jit_cache import (JitCache, mirror_to_metrics,
                                        program_of)
from spark_rapids_tpu.ops import exprs as X
from spark_rapids_tpu.sql import expressions as E
from spark_rapids_tpu.sql import physical as P

_STAGE_CACHE = JitCache("fusedStage")


def _donation_supported() -> bool:
    """Buffer donation is a no-op (with a warning) on XLA:CPU; only
    dispatch donating programs where the runtime honors it."""
    return jax.default_backend() in ("tpu", "gpu")


def _source_owns_buffers(child: TpuExec) -> bool:
    """True when every batch the child yields is freshly allocated and
    consumed by nobody else, so its buffers may be donated: the upload
    transition materializes new HBM arrays per batch, the range source
    generates them. Anything else (exchanges, coalesce pass-throughs,
    broadcast) may hand out store-registered batches whose arrays must
    survive for spill/reuse."""
    return isinstance(child, (TpuRowToColumnarExec, TpuRangeExec))


def batch_donatable(batch: DeviceBatch) -> bool:
    """A batch may only be donated when no HBM buffer appears twice in
    its pytree: e.g. the range source's column validity IS the batch
    active array, and donating one buffer through two leaves is a
    runtime error (or silent aliasing) under PJRT."""
    leaves = jax.tree_util.tree_leaves((batch.columns, batch.active))
    seen = set()
    for a in leaves:
        i = id(a)
        if i in seen:
            return False
        seen.add(i)
    return True


def bind_chain_steps(ops: List[TpuExec]) -> Tuple:
    """Bound ``(kind, exprs)`` steps for a filter/project chain. Each
    op still holds its original child link, so binding is identical to
    what the unfused per-op device_partitions would have done."""
    steps = []
    for op in ops:
        if isinstance(op, TpuFilterExec):
            steps.append(("filter", (E.bind_references(
                op.condition, op.child.output),)))
        elif isinstance(op, TpuProjectExec):
            steps.append(("project", tuple(P.bind_list(
                op.project_list, op.child.output))))
        else:
            raise AssertionError(f"not a fusible chain op: {op!r}")
    return tuple(steps)


class TpuFusedStageExec(TpuExec):
    """One compiled program for a linear operator chain.

    ``ops`` is the chain bottom-up (closest-to-source first); the last
    entry may be a partial-mode TpuHashAggregateExec, in which case the
    agg absorbs the filter/project prelude into its own per-batch
    program (TpuHashAggregateExec.absorb_prelude) and this node
    delegates execution to it — either way the plan shows ONE fused
    node whose output is the chain top's output.
    """

    def __init__(self, ops: List[TpuExec], child: TpuExec, conf: TpuConf):
        from spark_rapids_tpu.exec.agg import TpuHashAggregateExec
        super().__init__(conf)
        self.children = [child]
        self.fused_ops = list(ops)
        self.sink_agg: Optional[TpuHashAggregateExec] = None
        if isinstance(ops[-1], TpuHashAggregateExec):
            self.sink_agg = ops[-1]
            self.sink_agg.absorb_prelude(ops[:-1], child)
        self.metrics.create(M.FUSED_OPS, M.ESSENTIAL).add(len(ops))

    @property
    def child(self) -> TpuExec:
        return self.children[0]

    @property
    def output(self):
        return self.fused_ops[-1].output

    def _fan_back(self, elapsed_ns: int) -> None:
        """Per-operator metrics keep their stage keys: each constituent
        exec gets an equal share of the fused program's wall (one
        program — per-op attribution inside it is not observable). The
        fused node itself does NOT book opTime, so the breakdown still
        sums to the real wall."""
        share = elapsed_ns // max(1, len(self.fused_ops))
        for op in self.fused_ops:
            op.metrics.create(M.OP_TIME).add(share)

    def _fan_back_batches(self) -> None:
        for op in self.fused_ops:
            op.metrics.create(M.NUM_OUTPUT_BATCHES, M.ESSENTIAL).add(1)

    def device_partitions(self) -> List[DevicePartitionThunk]:
        if self.sink_agg is not None:
            return self.sink_agg.device_partitions()
        steps = bind_chain_steps(self.fused_ops)
        may_donate = (_donation_supported()
                      and _source_owns_buffers(self.child))
        skey = X.stage_structural_key(steps)
        stage_lits = X.stage_literal_values(steps)  # constant per stage
        schema = self.schema
        has_filter = any(k == "filter" for k, _ in steps)
        window_n = max(1, int(self.conf.get(STAGE_FUSION_MAX_IN_FLIGHT)))
        metrics = self.metrics
        stage_label = "+".join(op.simple_string().split()[0]
                               for op in self.fused_ops)
        import itertools
        bseq = itertools.count()  # thread-safe-enough batch ids (GIL)

        def run_one(b: DeviceBatch) -> DeviceBatch:
            from spark_rapids_tpu import trace as TR
            # per-batch: a batch whose pytree repeats a buffer (range
            # validity aliasing active) must use the non-donating
            # program variant
            donate = may_donate and batch_donatable(b)
            # per-chip attribution BEFORE dispatch: a donating program
            # deletes b's buffers, after which batch_device(b) cannot
            # read their placement
            from spark_rapids_tpu.parallel.mesh import record_chip_dispatch
            record_chip_dispatch(metrics, b)
            chip = TR.chip_of(b)  # None (no device query) when untraced
            fn, was_miss = _STAGE_CACHE.get_or_build(
                (skey, donate), lambda: X.build_stage_fn(steps, donate))
            mirror_to_metrics(_STAGE_CACHE, metrics, was_miss)
            lits = stage_lits
            nrows = None if has_filter else b._num_rows
            nrows_dev = None if has_filter else b._num_rows_dev
            TR.first_dispatch(metrics, fn)
            # the ENQUEUE of the program (jax dispatch is asynchronous:
            # the device's own time is in the profiler's device planes,
            # under the program's name). The SAME measurement feeds the
            # metric channel, the trace span and the profiler
            # annotation — one set of numbers (docs/observability.md)
            with TR.span("TpuFusedStageExec.dispatch", metrics=metrics,
                         batch=next(bseq), chip=chip, stage=stage_label,
                         compile=bool(was_miss),
                         program=program_of(fn)) as sp:
                cols, active, err = fn(b.columns, b.active, lits)
            elapsed = sp.t1 - sp.t0
            # a miss's first call carries trace+XLA-compile on top of
            # the dispatch: book it as compile wall; otherwise the wall
            # is fanned back to the constituents ONLY (the fused node
            # booking it too would double-count the stage breakdown)
            if was_miss:
                metrics.create(M.STAGE_COMPILE_TIME, M.ESSENTIAL).add(
                    elapsed)
            else:
                self._fan_back(elapsed)
            metrics.create(M.DISPATCH_COUNT, M.ESSENTIAL).add(1)
            metrics.create(M.NUM_OUTPUT_BATCHES, M.ESSENTIAL).add(1)
            self._fan_back_batches()
            X._raise_if_errors(err)
            return DeviceBatch(schema, list(cols), active, nrows,
                               nrows_dev)

        def make(thunk: DevicePartitionThunk) -> DevicePartitionThunk:
            def run() -> Iterator[DeviceBatch]:
                from spark_rapids_tpu import retry as R
                # async pipeline: dispatch up to window_n batches ahead
                # of the consumer; jax's async dispatch overlaps batch
                # k+1's programs with batch k's device compute, the
                # deque bounds outstanding HBM
                window: deque = deque()
                for b in thunk():
                    # OOM protocol: spill+retry, then split the input
                    # in half by rows (halves yield in order, so the
                    # stream stays bit-identical). Real backend OOMs
                    # are only retried when inputs were NOT donated —
                    # a donating program may have consumed its buffers
                    for ob in R.with_split_retry(
                            b, run_one, self.conf, metrics,
                            translate_real=not may_donate):
                        window.append(ob)
                        if len(window) >= window_n:
                            yield window.popleft()
                while window:
                    yield window.popleft()
            return run
        return [make(t) for t in device_channel(self.child)]

    def simple_string(self):
        names = "+".join(op.simple_string().split()[0]
                         for op in self.fused_ops)
        return f"TpuFusedStage [{names}]"

    def tree_string(self, indent: int = 0) -> str:
        s = " " * indent + self.simple_string()
        for op in self.fused_ops:
            s += "\n" + " " * (indent + 2) + ": " + op.simple_string()
        for c in self.children:
            s += "\n" + c.tree_string(indent + 2)
        return s


# ---------------------------------------------------------------------------
# The fusion pass (runs at the end of apply_overrides)
# ---------------------------------------------------------------------------

def _fusible_chain_op(op) -> bool:
    """Per-batch, shape-preserving, one-program ops that may join a
    chain. Partition-context expressions (partition id / monotonic id)
    carry cross-batch device state the fused program does not thread,
    and ANSI casts need the per-op error channel in aggregate preludes
    — both fall back to the unfused per-op path."""
    if isinstance(op, TpuFilterExec):
        exprs = [op.condition]
    elif isinstance(op, TpuProjectExec):
        exprs = list(op.project_list)
    else:
        return False
    if X._needs_part_ctx(exprs):
        return False
    if any(X.contains_ansi_cast(e) for e in exprs):
        return False
    return True


def _collect_chain(top) -> Tuple[List, Optional[TpuExec]]:
    """Maximal fusible chain starting at ``top`` going DOWN the tree;
    returns (ops bottom-up, source) — never crosses anything that is
    not a fusible per-batch op (exchanges, transitions, coalesce,
    aggregates, ...), so a fused stage cannot span a shuffle or a
    CPU<->device boundary by construction."""
    chain: List = []
    cur = top
    while _fusible_chain_op(cur):
        chain.append(cur)
        cur = cur.children[0]
    chain.reverse()
    return chain, (cur if chain else None)


def _agg_absorbable(agg, conf) -> bool:
    from spark_rapids_tpu.exec.agg import TpuHashAggregateExec
    return (isinstance(agg, TpuHashAggregateExec)
            and agg.mode == "partial"
            and getattr(agg, "_prelude_ops", None) is None)


def fuse_stages(plan: P.PhysicalPlan, conf: TpuConf) -> P.PhysicalPlan:
    """Top-down rewrite: each node first claims the maximal chain
    hanging below it (so inner sub-chains are never fused separately),
    then recursion continues under the fused stage's source."""
    fused = _try_fuse(plan, conf)
    fused.children = [fuse_stages(c, conf) for c in fused.children]
    return fused


def _try_fuse(plan, conf):
    if _agg_absorbable(plan, conf):
        chain, source = _collect_chain(plan.children[0])
        if chain and not _agg_prelude_blocked(plan):
            return TpuFusedStageExec(chain + [plan], source, conf)
        return plan
    if isinstance(plan, (TpuFilterExec, TpuProjectExec)):
        chain, source = _collect_chain(plan)
        # fusing a single op would just re-wrap its one program
        if len(chain) >= 2:
            return TpuFusedStageExec(chain, source, conf)
    return plan


def _agg_prelude_blocked(agg) -> bool:
    """The aggregate program has no ANSI error channel; its tagger
    already rejects ANSI casts in agg inputs, so nothing extra to
    check today — kept as the single gate point for future agg-side
    restrictions."""
    return False

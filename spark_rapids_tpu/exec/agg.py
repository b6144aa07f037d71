"""TpuHashAggregateExec: device groupBy aggregation
(GpuHashAggregateExec / GpuHashAggregateIterator, aggregate.scala:247).

Modes mirror Spark/the CPU engine: 'partial' emits keys+buffer slots
per input batch (merged downstream after the exchange), 'final' merges
buffers, 'complete' does both. Each batch aggregation is ONE jitted XLA
program built from the sort+segment kernel in ops/groupby.py, with the
slot update/merge expressions traced inline (so e.g. Average's
Cast-to-double fuses into the same program).

The reference's concat+merge / sort-fallback staging (aggregate.scala
:224-245) is unnecessary here: the kernel IS sort-based, so repeated
partial-result batches simply concat (static-bucketed) and re-aggregate.
"""

from __future__ import annotations

from typing import Callable, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp

import numpy as np

from spark_rapids_tpu import metrics as M
from spark_rapids_tpu import trace as TR
from spark_rapids_tpu.columnar.device import (
    AnyDeviceColumn, DeviceBatch, DeviceColumn, concat_device, mask_col,
    shrink_to_bucket, slice_compacted_to_bucket, take_columns)
from spark_rapids_tpu.columnar.host import HostColumn
from spark_rapids_tpu.conf import TpuConf
from spark_rapids_tpu.exec.base import (DevicePartitionThunk, TpuExec,
                                        device_channel)
from spark_rapids_tpu.ops import exprs as X
from spark_rapids_tpu.ops import groupby as G
from spark_rapids_tpu.sql import expressions as E
from spark_rapids_tpu.sql import physical as P
from spark_rapids_tpu.sql import types as T


def apply_prim_device(prim: str, seg: G.Segments, col: AnyDeviceColumn,
                      out_type: T.DataType,
                      has_nans: Optional[bool] = None) -> AnyDeviceColumn:
    """Device twin of physical.apply_update_prim (same prim vocabulary)."""
    if prim == E.PRIM_COUNT:
        return G.seg_count(seg, col)
    if prim == E.PRIM_SUM:
        return G.seg_sum(seg, col, out_type, null_when_empty=True)
    if prim == E.PRIM_SUM_NONNULL:
        return G.seg_sum(seg, col, out_type, null_when_empty=False)
    if prim == E.PRIM_MIN:
        return G.seg_extreme(seg, col, is_min=True, has_nans=has_nans)
    if prim == E.PRIM_MAX:
        return G.seg_extreme(seg, col, is_min=False, has_nans=has_nans)
    if prim == E.PRIM_FIRST:
        return G.seg_first_last(seg, col, is_first=True, ignore_nulls=True)
    if prim == E.PRIM_LAST:
        return G.seg_first_last(seg, col, is_first=False, ignore_nulls=True)
    if prim == E.PRIM_FIRST_ANY:
        return G.seg_first_last(seg, col, is_first=True, ignore_nulls=False)
    if prim == E.PRIM_LAST_ANY:
        return G.seg_first_last(seg, col, is_first=False, ignore_nulls=False)
    raise X.DeviceUnsupported(f"aggregate primitive {prim}")


def dev_evaluate(func: E.AggregateFunction,
                 buffers: List[AnyDeviceColumn],
                 out_active: jax.Array) -> AnyDeviceColumn:
    """Device twin of AggregateFunction.evaluate over merged buffers."""
    if isinstance(func, (E.Sum, E.Min, E.Max, E.First, E.Last)):
        return buffers[0]
    if isinstance(func, E.Count):
        b = buffers[0]
        data = jnp.where(b.validity, b.data, jnp.int64(0))
        data = jnp.where(out_active, data, jnp.int64(0))
        return DeviceColumn(T.LongT, data, out_active)
    if isinstance(func, E.CentralMomentAgg):
        # device twin of CentralMomentAgg._finish: M2 = sumsq - sum^2/n
        n = jnp.where(buffers[0].validity, buffers[0].data, jnp.int64(0))
        s = buffers[1].data.astype(jnp.float64)
        sq = buffers[2].data.astype(jnp.float64)
        nf = n.astype(jnp.float64)
        m2 = jnp.maximum(
            sq - (s * s) / jnp.where(n > 0, nf, jnp.float64(1.0)), 0.0)
        div = nf - 1.0 if func.is_sample else nf
        out = m2 / div  # n==1 sample: 0/0 -> NaN (Spark semantics)
        if func.is_stddev:
            out = jnp.sqrt(out)
        validity = (n > 0) & out_active
        return DeviceColumn(T.DoubleT,
                            jnp.where(validity, out, jnp.float64(0.0)),
                            validity)
    if isinstance(func, E.Average):
        s, cnt = buffers[0], buffers[1]
        count = jnp.where(cnt.validity, cnt.data, jnp.int64(0))
        dec = func._child_decimal()
        if dec is not None:
            # HALF_UP(sum * 10^(s_res - s) / count) in 128-bit limbs —
            # the twin of the host Average.evaluate decimal path
            from spark_rapids_tpu.columnar.device import (
                DeviceDecimal128Column)
            from spark_rapids_tpu.ops import decimal_ops as DD
            from spark_rapids_tpu.ops import int128 as I
            res = func.data_type
            if isinstance(s, DeviceDecimal128Column):
                hi, lo = s.hi, s.lo
            else:
                hi, lo = I.from_i64(jnp, s.data.astype(jnp.int64))
            hi, lo, over = DD.rescale_up(jnp, hi, lo,
                                         max(res.scale - dec.scale, 0))
            nz = count > 0
            qh, ql = I.div_halfup(jnp, hi, lo,
                                  jnp.where(nz, count, jnp.int64(1)))
            validity = s.validity & nz & out_active & ~over \
                & I.fits_precision(jnp, qh, ql, res.precision)
            return X._limbs_to_devcol(qh, ql, validity, res)
        validity = (count > 0) & out_active
        data = s.data.astype(jnp.float64) / jnp.where(
            count > 0, count, jnp.int64(1)).astype(jnp.float64)
        data = jnp.where(validity, data, jnp.float64(0.0))
        return DeviceColumn(T.DoubleT, data, validity)
    raise X.DeviceUnsupported(
        f"aggregate {type(func).__name__} has no device evaluate")


def _float_agg_allowed(conf) -> bool:
    if conf is None:
        return False
    from spark_rapids_tpu.conf import ENABLE_FLOAT_AGG
    return bool(conf.get(ENABLE_FLOAT_AGG))


def is_device_agg(grouping: List[E.AttributeReference],
                  aggregates: List[E.Expression],
                  conf=None) -> Optional[str]:
    """Tagging helper: None if the whole aggregate can run on device."""
    from spark_rapids_tpu import device_caps as DC
    for g in grouping:
        dt = g.data_type
        if isinstance(dt, T.StructType):
            from spark_rapids_tpu import typesig as TS
            r = TS.common_tpu_struct.support(dt)
            if r:
                return f"grouping key: {r}"
            continue  # flat-field structs group on device (TimeWindow)
        if isinstance(dt, (T.ArrayType, T.MapType)):
            return "nested grouping keys are not supported on TPU"
    for e in aggregates:
        if isinstance(e, E.Alias) and isinstance(e.child,
                                                 E.AggregateExpression):
            func = e.child.func
            if e.child.is_distinct:
                return "DISTINCT aggregates are not supported"
            if not isinstance(func, (E.Sum, E.Count, E.Min, E.Max,
                                     E.Average, E.First, E.Last,
                                     E.CentralMomentAgg)):
                return (f"aggregate {type(func).__name__} has no device "
                        "implementation")
            if isinstance(func, E.Average) \
                    and func._child_decimal() is None \
                    and not DC.float_div_exact() \
                    and not _float_agg_allowed(conf):
                # decimal averages divide in exact integer limbs and
                # never hit the emulated-f64 concern
                # the final sum/count division is emulated on this backend;
                # same knob as ordering-variable float aggs (the reference's
                # spark.rapids.sql.variableFloatAgg.enabled semantics:
                # "results can differ from CPU")
                return ("device Average division is not bit-identical to "
                        "CPU on this backend (TPU f64 is emulated); set "
                        "spark.rapids.sql.variableFloatAgg.enabled=true "
                        "to allow")
            # decimal Average's adjusted result scale can never drop
            # below the child's (38 - (p - s) >= s for every p <= 38),
            # so its rescale is always an exact scale-UP — no gate needed
            for s in func.buffer_slots():
                r = X.is_device_expr(s[3], conf) if isinstance(
                    s[3], E.Expression) else None
                if r:
                    return r
                if isinstance(s[3], E.Expression) and \
                        X.contains_ansi_cast(s[3]):
                    return "ANSI casts in aggregate inputs run on CPU"
    return None


# Compiled aggregation programs cached on structure so re-planned queries
# (every collect() builds fresh exec instances) reuse XLA executables;
# bounded LRU so long-running sessions can't grow it without limit.
from spark_rapids_tpu.jit_cache import (JitCache, mirror_to_metrics,
                                        named_jit, program_name,
                                        program_of)

_AGG_FN_CACHE = JitCache("agg")
# (program cache key, input shapes) -> lanes x group-key words, kept
# while the program traces and booked as ``aggKeyWords`` at each dispatch
_AGG_KEY_WORDS: Dict[Tuple, int] = {}

# tpu-lint: disable=jit-direct(single fixed count-stack program — one executable, bounded by construction)
_stack_counts = named_jit("srt_agg_stack_counts",
                          lambda cs: jnp.stack(cs))


class TpuHashAggregateExec(TpuExec):
    def __init__(self, grouping: List[E.AttributeReference],
                 aggregates: List[E.Expression], mode: str, child: TpuExec,
                 slots: Dict[int, List[P.AggSlot]], conf: TpuConf):
        super().__init__(conf)
        self.children = [child]
        self.grouping = grouping
        self.aggregates = aggregates
        self.mode = mode
        self.slots = slots
        # stage fusion (exec/fused.py): filter/project prelude traced
        # INSIDE this exec's per-batch program — one dispatch per batch
        self._prelude_ops = None
        self._prelude_bind_out = None
        self._donate_input = False

    def absorb_prelude(self, prelude_ops, source) -> None:
        """Absorb a fusible filter/project chain: the chain's programs
        fuse into this aggregate's per-batch update program (the
        GpuTieredProject-into-aggregate shape). ``source`` becomes the
        direct child; agg expressions keep binding against the chain
        top's output (the attrs they were resolved to)."""
        assert self.mode == "partial", self.mode
        self._prelude_ops = list(prelude_ops)
        self._prelude_bind_out = prelude_ops[-1].output
        self.children = [source]
        # donate input buffers only when the source's batches are
        # freshly allocated and solely ours (see fused._source_owns)
        from spark_rapids_tpu.exec.fused import (_donation_supported,
                                                 _source_owns_buffers)
        self._donate_input = (_donation_supported()
                              and _source_owns_buffers(source))

    @property
    def child(self) -> TpuExec:
        return self.children[0]

    @property
    def output(self):
        if self.mode == "partial":
            out = list(self.grouping)
            for e in self.aggregates:
                if isinstance(e, E.Alias) and isinstance(
                        e.child, E.AggregateExpression):
                    out.extend(s.attr for s in self.slots[e.expr_id])
            return out
        return [E.named_output(e) for e in self.aggregates]

    # -- helpers -------------------------------------------------------

    def _agg_aliases(self):
        return [e for e in self.aggregates
                if isinstance(e, E.Alias)
                and isinstance(e.child, E.AggregateExpression)]

    def _bound_slot_sources(self, mode: str, child_out=None
                            ) -> Tuple[List[E.Expression],
                                       List[Tuple[str, T.DataType]]]:
        """Per-slot (bound source expr, (prim, out_type)) for `mode`."""
        if child_out is None:
            child_out = self.child.output
        srcs: List[E.Expression] = []
        prims: List[Tuple[str, T.DataType]] = []
        for alias in self._agg_aliases():
            for s in self.slots[alias.expr_id]:
                if mode in ("partial", "complete"):
                    prim, src = s.update_prim, s.update_expr
                else:  # final and the internal buffer-merge mode
                    prim, src = s.merge_prim, s.attr
                srcs.append(E.bind_references(src, child_out))
                prims.append((prim, s.dtype))
        return srcs, prims

    def _build_fn(self, mode: str, key_bound: List[E.Expression],
                  slot_srcs: List[E.Expression],
                  prims: List[Tuple[str, T.DataType]],
                  has_nans: bool, prelude_steps=None,
                  donate: bool = False, words_key=None) -> Callable:
        aliases = self._agg_aliases()
        slot_counts = [len(self.slots[a.expr_id]) for a in aliases]
        grouping = self.grouping
        aggregates = self.aggregates
        all_exprs = tuple(key_bound) + tuple(slot_srcs)

        # partial/merge outputs feed a re-grouping stage downstream, so
        # hash-fragmented groups are fine and the 1-pass hash sort
        # applies; final/complete emit user-facing rows and need the
        # exact multi-word sort (build_segments_hashed docstring)
        hashed = mode in ("partial", "merge", "merge_partial")
        _SUM_KINDS = {E.PRIM_COUNT: "count", E.PRIM_SUM: "sum",
                      E.PRIM_SUM_NONNULL: "sum_nonnull"}

        def fn(cols, active, lit_vals):
            from spark_rapids_tpu.columnar.device import (flatten_columns,
                                                          rebuild_columns)
            shapes = X.input_shapes(cols, active)
            if prelude_steps:
                # fused filter/project prelude: the chain's mask update
                # and projections trace INLINE ahead of the key/slot
                # evaluation — one XLA program for the whole stage
                prelude_lits, lit_vals = lit_vals
                cols, active, _errs = X.trace_stage_steps(
                    prelude_steps, cols, active, prelude_lits)
            cap = active.shape[0]
            ctx = X.Ctx(cols, cap, all_exprs, lit_vals)
            with jax.named_scope("agg_inputs"):
                key_cols = [X.dev_eval(e, ctx) for e in key_bound]
            # tpu-lint: disable=trace-purity(a static fact of the trace, lanes and key widths, kept for the host to book; no value flows into the program)
            _AGG_KEY_WORDS[words_key, shapes] = cap * G.key_word_count(
                key_cols, has_nans)
            # dedupe slot sources (sum(x) + avg(x) share x): each unique
            # expression is evaluated, sorted, and lane-packed ONCE
            uniq_srcs: List[E.Expression] = []
            uniq_of: Dict[tuple, int] = {}
            src_map: List[int] = []
            for e in slot_srcs:
                k = X.expr_key(e)
                if k not in uniq_of:
                    uniq_of[k] = len(uniq_srcs)
                    uniq_srcs.append(e)
                src_map.append(uniq_of[k])
            with jax.named_scope("agg_inputs"):
                slot_vals = [X.dev_eval(e, ctx) for e in uniq_srcs]
            # keys AND slot values ride the segment sort as payload (one
            # fused lane-matrix gather; sorting each array separately is
            # a flat ~25-40ms per op on this backend)
            flat, spec = flatten_columns(key_cols + slot_vals)
            if hashed:
                seg = G.build_segments_hashed(
                    key_cols, active, payload=flat, has_nans=has_nans,
                    sorted_keys_from_payload=lambda ps:
                        rebuild_columns(spec, ps)[:len(key_cols)])
            else:
                seg = G.build_segments(key_cols, active, payload=flat,
                                       has_nans=has_nans)
            sorted_cols = rebuild_columns(spec, seg.payload)
            keys_s = sorted_cols[:len(key_cols)]
            uniq_s = sorted_cols[len(key_cols):]
            vals_s = [uniq_s[j] for j in src_map]
            # sum/count-family slots batch into ONE cumsum/scan pass;
            # min/max/first/last keep their per-slot scans
            buffers: List[Optional[AnyDeviceColumn]] = [None] * len(prims)
            entries, entry_pos = [], []
            for i, ((p, dt), v) in enumerate(zip(prims, vals_s)):
                if p in _SUM_KINDS:
                    entries.append((v, _SUM_KINDS[p], dt))
                    entry_pos.append(i)
                else:
                    buffers[i] = apply_prim_device(p, seg, v, dt,
                                                   has_nans)
            for i, c in zip(entry_pos,
                            G.seg_sums_batched(seg, entries, has_nans)):
                buffers[i] = c
            # results live at segment-END rows of the sorted layout;
            # the keys are ALREADY in that layout — just mask them
            out_active = seg.out_active
            key_out = [mask_col(c, out_active) for c in keys_s] \
                if grouping else []

            if mode in ("partial", "merge", "merge_partial"):
                # merge: buffer-space -> buffer-space (the bounded
                # concat+merge staging of aggregate.scala:224-245).
                # Compact results to a prefix IN-PROGRAM and emit the
                # group count as a device scalar: downstream sizing then
                # needs one tiny (async-overlappable) fetch instead of a
                # blocking count sync per batch (a D2H read is a
                # device sync).
                from spark_rapids_tpu.columnar.device import _compact_body
                out_cols = list(key_out) + list(buffers)
                cnt = jnp.sum(out_active)
                flat2, spec2 = flatten_columns(out_cols)
                new_active, outs2 = _compact_body(out_active, flat2)
                return rebuild_columns(spec2, outs2), new_active, cnt

            # final / complete: evaluate results
            by_alias: Dict[int, List[AnyDeviceColumn]] = {}
            off = 0
            for a, n in zip(aliases, slot_counts):
                by_alias[a.expr_id] = buffers[off:off + n]
                off += n
            key_by_attr = {a.expr_id: kc for a, kc in
                           zip(grouping, key_out)}
            out_cols = []
            for e in aggregates:
                if isinstance(e, E.Alias) and isinstance(
                        e.child, E.AggregateExpression):
                    with jax.named_scope("agg_result"):
                        out_cols.append(dev_evaluate(
                            e.child.func, by_alias[e.expr_id],
                            out_active))
                elif isinstance(e, E.AttributeReference):
                    out_cols.append(key_by_attr[e.expr_id])
                elif isinstance(e, E.Alias) and isinstance(
                        e.child, E.AttributeReference):
                    out_cols.append(key_by_attr[e.child.expr_id])
                else:
                    raise X.DeviceUnsupported(f"agg result expr {e!r}")
            return out_cols, out_active
        return named_jit(program_name("agg", mode), fn,
                         donate_argnums=(0, 1) if donate else ())

    def _out_desc(self) -> Tuple:
        """Structural descriptor of the result-column layout (what the
        compiled program's output order depends on besides the exprs)."""
        aliases = self._agg_aliases()
        alias_ids = {a.expr_id: i for i, a in enumerate(aliases)}
        group_ids = {g.expr_id: i for i, g in enumerate(self.grouping)}
        desc = []
        for e in self.aggregates:
            if isinstance(e, E.Alias) and isinstance(e.child,
                                                     E.AggregateExpression):
                desc.append(("agg", alias_ids[e.expr_id],
                             type(e.child.func).__name__))
            elif isinstance(e, E.AttributeReference):
                desc.append(("key", group_ids[e.expr_id]))
            elif isinstance(e, E.Alias) and isinstance(e.child,
                                                       E.AttributeReference):
                desc.append(("key", group_ids[e.child.expr_id]))
            else:
                desc.append(("other", repr(e)))
        return tuple(desc)

    def _aggregate_batch(self, batch: DeviceBatch,
                         mode: Optional[str] = None):
        """Run one aggregation program. Returns ``(DeviceBatch, cnt)``:
        ``cnt`` is the device-scalar group count for partial/merge
        modes (compacted output) and None for final/complete."""
        mode = mode or self.mode
        prelude = (self._prelude_ops
                   if self._prelude_ops and mode == "partial" else None)
        if mode == "merge_partial":
            # merge-within-partial: inputs are in THIS exec's buffer
            # layout (self.output), not the child's raw rows
            bind_out = self.output
        elif prelude:
            # fused prelude: agg exprs reference the chain top's attrs,
            # which the prelude steps produce in-program
            bind_out = self._prelude_bind_out
        else:
            bind_out = self.child.output
        child_out = bind_out
        key_bound = [E.bind_references(g, child_out) for g in self.grouping]
        slot_srcs, prims = self._bound_slot_sources(mode, child_out)
        prelude_steps = None
        donate = False
        salt = G.kernel_salt()  # snapshot: key AND trace use this value
        struct = (mode, salt,
                  tuple(X.expr_key(e) for e in key_bound),
                  tuple(X.expr_key(e) for e in slot_srcs),
                  tuple(p for p, _ in prims),
                  tuple(repr(dt) for _, dt in prims),
                  tuple(len(self.slots[a.expr_id])
                        for a in self._agg_aliases()),
                  self._out_desc(),
                  X.stage_structural_key(prelude_steps)
                  if prelude_steps else None)
        if prelude:
            from spark_rapids_tpu.exec.fused import bind_chain_steps
            prelude_steps = bind_chain_steps(prelude)
            struct = struct[:-1] + (
                X.stage_structural_key(prelude_steps),)
        if prelude:
            from spark_rapids_tpu.exec.fused import batch_donatable
            # per-batch: aliased buffers (one array on two pytree
            # leaves) must not be donated twice
            donate = self._donate_input and batch_donatable(batch)
        lit_vals = X.literal_values(list(key_bound) + list(slot_srcs))
        if prelude_steps:
            lit_vals = (X.stage_literal_values(prelude_steps), lit_vals)
        cnt = None
        self.metrics.create(M.DISPATCH_COUNT, M.ESSENTIAL).add(1)
        from spark_rapids_tpu.parallel.mesh import record_chip_dispatch
        record_chip_dispatch(self.metrics, batch)
        chip = TR.chip_of(batch)  # None (no device query) when untraced
        import time as _time

        cache_key = struct + (donate,)
        fn, was_miss = _AGG_FN_CACHE.get_or_build(
            cache_key,
            lambda: self._build_fn(mode, key_bound, slot_srcs, prims,
                                   has_nans=salt[0],
                                   prelude_steps=prelude_steps,
                                   donate=donate, words_key=cache_key))
        mirror_to_metrics(_AGG_FN_CACHE, self.metrics, was_miss)
        TR.first_dispatch(self.metrics, fn)
        in_shapes = X.input_shapes(batch.columns, batch.active)
        with TR.annotation(
                "TpuHashAggregateExec.dispatch", TR.scope_of(self.metrics),
                attrs={"mode": mode, "program": program_of(fn)}):
            # the ENQUEUE interval (jax dispatch is asynchronous)
            t0 = _time.perf_counter_ns()
            outs = fn(batch.columns, batch.active, lit_vals)
        elapsed = _time.perf_counter_ns() - t0
        key_words = _AGG_KEY_WORDS.get((cache_key, in_shapes))
        if key_words:
            self.metrics.create(M.AGG_KEY_WORDS, M.ESSENTIAL).add(key_words)
        if mode in ("partial", "merge", "merge_partial"):
            out_cols, out_active, cnt = outs
        else:
            out_cols, out_active = outs
        qt = TR._ACTIVE
        if qt is not None:
            # the same measurement feeds computeAggTime/stageCompileTime
            # below — trace and metrics agree (docs/observability.md)
            TR.record(qt, "TpuHashAggregateExec.dispatch", t0,
                      t0 + elapsed, TR.scope_of(self.metrics),
                      chip=chip,
                      attrs={"mode": mode, "compile": bool(was_miss),
                             "program": program_of(fn)})
        if was_miss:
            # first call after a compile miss carries trace+XLA compile
            self.metrics.create(M.STAGE_COMPILE_TIME,
                                M.ESSENTIAL).add(elapsed)
        elif prelude:
            # per-operator metrics keep their stage keys
            # (docs/fusion.md): the ONE program's wall splits evenly
            # across prelude ops + the agg, so fused and unfused stage
            # breakdowns stay comparable without double counting
            share = elapsed // (len(prelude) + 1)
            for op in prelude:
                op.metrics.create(M.OP_TIME).add(share)
            self.metrics.create(M.AGG_TIME).add(
                elapsed - share * len(prelude))
        else:
            self.metrics.create(M.AGG_TIME).add(elapsed)
        if prelude:
            for op in prelude:
                op.metrics.create(M.NUM_OUTPUT_BATCHES,
                                  M.ESSENTIAL).add(1)
        if mode in ("merge", "merge_partial"):
            # buffer layout keeps the input's schema
            schema = T.StructType(
                [T.StructField(a.name, a.data_type, a.nullable)
                 for a in child_out])
        else:
            schema = self.schema
        return DeviceBatch(schema, list(out_cols), out_active,
                           None), cnt

    def _empty_global_result(self) -> DeviceBatch:
        cols: List[HostColumn] = []
        for e in self.aggregates:
            assert isinstance(e, E.Alias)
            func = e.child.func
            buffers = [HostColumn.nulls(1, s.dtype)
                       for s in self.slots[e.expr_id]]
            cols.append(func.evaluate(buffers))
        from spark_rapids_tpu.columnar.host import HostBatch
        return DeviceBatch.from_host(HostBatch(self.schema, cols, 1))

    def _merge_bounded(self, handles: List, store) -> DeviceBatch:
        """Out-of-core final staging: repeatedly concat+merge chunks of
        buffer batches whose total row count stays within
        ``batchSizeRows`` (aggregate.scala:224-245); inputs and
        intermediates live behind spillable handles so the partition
        never needs to fit in HBM at once."""
        limit = max(self.conf.batch_size_rows, 2)
        while len(handles) > 1:
            merged: List = []
            i = 0
            while i < len(handles):
                chunk = [handles[i]]
                rows = handles[i].rows  # cached; never touches the tiers
                i += 1
                # take at least 2 per chunk (guaranteed progress), more
                # while the concat stays within the row budget
                while i < len(handles) and (
                        len(chunk) < 2
                        or rows + handles[i].rows <= limit):
                    rows += handles[i].rows
                    chunk.append(handles[i])
                    i += 1
                if len(chunk) == 1:
                    merged.append(chunk[0])
                    continue
                whole = concat_device([h.get() for h in chunk])
                self.metrics.create(M.AGG_MERGE_COUNT, M.ESSENTIAL).add(1)
                from spark_rapids_tpu import retry as R
                out, cnt = R.with_retry(
                    lambda w=whole: self._aggregate_batch(w, mode="merge"),
                    self.conf, self.metrics)
                with TR.device_sync("aggMerge", self.metrics):
                    out._num_rows = int(cnt)  # sizes the bucket slice
                out = slice_compacted_to_bucket(out)
                for h in chunk:
                    h.close()
                merged.append(self.register_spillable(store, out))
            handles = merged
        final = handles[0].get()
        handles[0].close()
        return final

    def _ooc_eligible(self) -> bool:
        """The bucketed out-of-core aggregation needs hashable grouping
        keys and row-splittable batches (array/map columns carry
        element pools the sort-split cannot ride). Bucketing is by the
        murmur3 HASH of the grouping keys — never by range — so a run
        of equal keys can never straddle a bucket boundary and emit a
        group twice."""
        for g in self.grouping:
            if isinstance(g.data_type, (T.ArrayType, T.MapType,
                                        T.StructType)):
                return False
        for a in self.child.output:
            if isinstance(a.data_type, (T.ArrayType, T.MapType)):
                return False
        return True

    def _ooc_split(self, store, handles: List, bound_keys,
                   modulus: int) -> List[List]:
        """Split every handle's batch into ``modulus`` spill-backed
        buckets by the exchange's bit-exact murmur3 partition hash of
        the grouping keys (a group's rows all land in one bucket, so
        per-bucket aggregation unions to the full result). Input
        handles close as they are consumed; only one source batch is
        promoted at a time."""
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

    def _ooc_aggregate(self, store, handles: List, modulus: int,
                       oracle, depth: int) -> Iterator[DeviceBatch]:
        """Planned out-of-core aggregation (docs/out_of_core.md): the
        partition's buffer batches split by pmod(murmur3(grouping),
        modulus) into spill-backed buckets, each aggregated alone (the
        kernel is already sort-based, so this IS the sort fallback of
        aggregate.scala:224-245 with hash-bucketed staging). The
        modulus starts at plannedPartitions × co-partition count —
        rows here already satisfy pmod(h, P) == pid, so any modulus
        dividing P would put every row in one bucket. A bucket whose
        estimate still overflows — or whose complete-mode concat OOMs
        before anything was emitted — re-buckets recursively at a
        DOUBLED modulus, bounded by outOfCore.maxRecursion; past the
        bound the OOM-retry protocol is the backstop."""
        from spark_rapids_tpu import retry as R
        TR.instant("oocAggPlan", modulus=modulus, depth=depth)
        child_out = self.child.output
        bound = [E.bind_references(g, child_out) for g in self.grouping]
        buckets = self._ooc_split(store, handles, bound, modulus)
        share = oracle.operator_share()
        inj = R.get_fault_injector(self.conf)
        for pid in range(modulus):
            bh = buckets[pid]
            if not bh:
                continue
            if sum(h.sizeof() for h in bh) > share \
                    and depth < oracle.max_recursion:
                # the estimate says this bucket still overflows:
                # re-plan (escalate), don't materialize-and-thrash
                self.metrics.create(M.PLANNED_OOC_ESCALATIONS,
                                    M.ESSENTIAL).add(1)
                yield from self._ooc_aggregate(store, bh, modulus * 2,
                                               oracle, depth + 1)
                continue
            if self.mode == "final":
                # merge staging is itself spill-backed and row-bounded
                whole = self._merge_bounded(bh, store)
            else:  # complete consumes raw rows; concat is the one
                #    over-budget-risk point for this bucket
                def mat(hs=bh) -> DeviceBatch:
                    bs = [h.get() for h in hs]
                    return concat_device(bs) if len(bs) > 1 else bs[0]

                if depth >= oracle.max_recursion:
                    whole = R.with_retry(mat, self.conf, self.metrics,
                                         site="oocAgg")
                else:
                    try:
                        # nothing emitted for this bucket yet and its
                        # handles are intact, so an OOM here soundly
                        # re-plans at a doubled modulus instead of
                        # riding the spill-and-retry loop
                        if inj is not None:
                            inj.on_alloc("oocAgg")
                        whole = mat()
                    except Exception as e:
                        if not R.is_oom_error(e):
                            raise
                        self.metrics.create(M.PLANNED_OOC_ESCALATIONS,
                                            M.ESSENTIAL).add(1)
                        yield from self._ooc_aggregate(
                            store, bh, modulus * 2, oracle, depth + 1)
                        continue
                for h in bh:
                    h.close()
            out, _cnt = R.with_retry(
                lambda w=whole: self._aggregate_batch(w),
                self.conf, self.metrics)
            yield out

    def device_partitions(self) -> List[DevicePartitionThunk]:
        grouped = len(self.grouping) > 0

        def make(thunk: DevicePartitionThunk,
                 co_parts: int = 1) -> DevicePartitionThunk:
            def run() -> Iterator[DeviceBatch]:
                from spark_rapids_tpu.memory import get_device_store
                store = get_device_store(self.conf)
                if self.mode == "partial":
                    yield from self._run_partial(thunk, store)
                    return
                handles = [self.register_spillable(store, b)
                           for b in thunk() if b._num_rows != 0]
                if not handles:
                    if not grouped and self.mode in ("final", "complete"):
                        yield self._empty_global_result()
                    return
                # planned out-of-core gate (docs/out_of_core.md): when
                # the estimated working set exceeds the budget oracle's
                # operator share, bucket the partition by the murmur3
                # hash of the grouping keys and aggregate one
                # spill-backed bucket at a time instead of
                # concatenating a whole the retry protocol would thrash
                if grouped and self.mode in ("final", "complete") \
                        and self._ooc_eligible():
                    from spark_rapids_tpu.memory import get_budget_oracle
                    oracle = get_budget_oracle(self.conf)
                    if oracle.enabled:
                        n = oracle.plan_partitions(
                            sum(h.sizeof() for h in handles),
                            self.metrics)
                        if n > 1:
                            yield from self._ooc_aggregate(
                                store, handles,
                                n * max(1, co_parts), oracle, depth=0)
                            return
                if self.mode == "final":
                    whole = self._merge_bounded(handles, store)
                    if whole._num_rows is not None:
                        # one row a group: merged above, or the one
                        # compacted batch of a single partial
                        self.metrics.create(
                            M.AGG_GROUP_COUNT, M.ESSENTIAL).add(
                                whole._num_rows)
                else:  # complete consumes raw rows; concat directly
                    if len(handles) > 1:
                        self.metrics.create(M.AGG_MERGE_COUNT,
                                            M.ESSENTIAL).add(1)
                    whole = concat_device([h.get() for h in handles])
                    for h in handles:
                        h.close()
                # no shrink: results stay mask-scattered (caps here are
                # already small post-exchange; skipping saves a sync)
                from spark_rapids_tpu import retry as R
                out, _cnt = R.with_retry(
                    lambda: self._aggregate_batch(whole),
                    self.conf, self.metrics)
                if not grouped and self.mode in ("final", "complete") \
                        and out.row_count() == 0:
                    # inputs existed but every row was filtered/inactive:
                    # a global aggregate still returns its one row
                    yield self._empty_global_result()
                    return
                yield out
            return run
        thunks = device_channel(self.child)
        return [make(t, len(thunks)) for t in thunks]

    def _run_partial(self, thunk: DevicePartitionThunk, store
                     ) -> Iterator[DeviceBatch]:
        """Partial mode, sync-lean: each batch's program compacts its
        groups and emits the count as a device scalar whose host copy is
        started immediately (overlapping the next batch's work). After
        the drain, outputs are sliced to their buckets using the by-then
        arrived counts, and — when the reduced data is small — merged ON
        DEVICE into one batch per partition, so the exchange ships one
        small batch with zero extra syncs (the pre-shuffle reduction of
        aggregate.scala:224-245, restructured for a ~0.2-0.7s-per-D2H-
        roundtrip backend)."""
        from spark_rapids_tpu import retry as R
        from spark_rapids_tpu.columnar.device import _prefetch_host
        pending = []
        prefetched = True

        for b in thunk():
            # OOM protocol on the per-batch update program: spill+retry
            # first, then split the input in half by rows — partial
            # outputs from the halves merge downstream exactly like two
            # ordinary input batches, so results stay bit-identical
            for out, cnt in R.with_split_retry(
                    b, self._aggregate_batch, self.conf, self.metrics,
                    translate_real=not self._donate_input):
                # async host copy starts NOW: by drain time the scalar
                # is already local, so the drain costs pipeline-
                # completion, not + a flat ~0.2s roundtrip per fetch
                prefetched = _prefetch_host([cnt]) and prefetched
                pending.append((self.register_spillable(store, out), cnt))
        if not pending:
            return
        # This read is where the whole async upstream pipeline (upload
        # transfer, decode, filter/project, per-batch agg) actually
        # drains, so its wall time IS the device-side pipeline cost —
        # metered so the bench breakdown shows it (round-4 verdict: the
        # dominant term must not be invisible). Without async copies the
        # per-batch reads would pay one flat roundtrip EACH — stack them
        # into the single-fetch form instead.
        # timed_wall: with taskParallelism > 1, several pool threads
        # drain concurrently; interval-union keeps the metric <= query
        # wall so the bench stage breakdown sums sensibly
        with self.metrics.timed_wall("pipelineDrainTime"), \
                TR.device_sync("aggCounts", self.metrics):
            if prefetched:
                counts = [int(np.asarray(c)) for _h, c in pending]
            else:
                counts = np.asarray(
                    _stack_counts([c for _h, c in pending]))
        shrunk = []
        for (h, _c), cnt in zip(pending, counts):
            b = h.get()
            b._num_rows = int(cnt)
            h.close()
            b = slice_compacted_to_bucket(b)
            shrunk.append(self.register_spillable(store, b))
        total = sum(h.rows for h in shrunk)
        if len(shrunk) > 1 and total <= self.conf.batch_size_rows:
            whole = concat_device([h.get() for h in shrunk])
            for h in shrunk:
                h.close()
            out, _cnt = R.with_retry(
                lambda: self._aggregate_batch(whole,
                                              mode="merge_partial"),
                self.conf, self.metrics)
            # leave _num_rows lazy: the output is compacted at a small
            # concat capacity already, and fetching the count here would
            # cost one more roundtrip nothing downstream needs
            yield out
            return
        for h in shrunk:
            b = h.get()
            h.close()
            yield b

    def simple_string(self):
        return (f"TpuHashAggregate mode={self.mode} keys={self.grouping} "
                f"aggs={self.aggregates}")

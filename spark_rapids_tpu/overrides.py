"""TpuOverrides: the plan-rewrite engine (GpuOverrides.scala:3564 twin).

Pipeline mirrors the reference's wrap -> tag -> convert flow:

1. **wrap**: every CPU physical node is wrapped in an ``ExecMeta`` (the
   RapidsMeta tree, RapidsMeta.scala:70) carrying its matching
   ``ExecRule`` from the registry below.
2. **tag**: each meta collects ``willNotWorkOnTpu`` reasons — per-op conf
   keys (``spark.rapids.sql.exec.<Op>`` / ``...sql.expression.<Expr>``,
   auto-derived like ReplacementRule.confKey GpuOverrides.scala:147),
   TypeSig checks over the node's schema, expression-tree device support,
   and op-specific rules (e.g. range partitioning stays on CPU until the
   device sort lands).
3. **convert**: supported subtrees become ``Tpu*Exec`` nodes; transitions
   ``TpuRowToColumnarExec`` / ``TpuColumnarToRowExec`` are inserted at
   every CPU<->device boundary (GpuTransitionOverrides.scala:48), and the
   root is brought back to rows.

``RewriteReport`` records every fallback with its reason — the
``spark.rapids.sql.explain=NOT_ON_GPU`` output and the hook the
fallback-assertion tests use (ExecutionPlanCaptureCallback analogue).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Type

from spark_rapids_tpu import typesig as TS
from spark_rapids_tpu.conf import (ALLOW_DISABLE_ENTIRE_PLAN,
                                   ENABLE_FLOAT_AGG, INCOMPATIBLE_OPS,
                                   TEST_FORCE_DEVICE, TpuConf)
from spark_rapids_tpu.ops import exprs as X
from spark_rapids_tpu.sql import expressions as E
from spark_rapids_tpu.sql import physical as P
from spark_rapids_tpu.sql import types as T


# ---------------------------------------------------------------------------
# Expression rules (the `expressions` registry, GpuOverrides.scala:3136)
# ---------------------------------------------------------------------------

@dataclass
class ExprRule:
    name: str
    checks: TS.ExprChecks
    incompat: Optional[str] = None  # reason string when semantics differ

    @property
    def conf_key(self) -> str:
        return f"spark.rapids.sql.expression.{self.name}"


_EXPR_RULES: Dict[Type, ExprRule] = {}


def expr_rule(cls: Type, checks: Optional[TS.ExprChecks] = None,
              incompat: Optional[str] = None) -> None:
    _EXPR_RULES[cls] = ExprRule(
        cls.__name__, checks or TS.expr_checks(TS.common_tpu), incompat)


# default rules for every device-implemented expression; specific
# signatures/incompat flags override below
for _cls in X._HANDLERS:
    expr_rule(_cls)

expr_rule(E.Substring, incompat="byte-positioned substring is exact only "
          "for ASCII strings")
expr_rule(E.Upper, incompat="case conversion is ASCII-only")
expr_rule(E.Lower, incompat="case conversion is ASCII-only")
expr_rule(E.InitCap, incompat="case conversion is ASCII-only")
expr_rule(E.StringInstr, incompat="byte positions are exact only for "
          "ASCII strings")
expr_rule(E.StringLocate, incompat="byte positions are exact only for "
          "ASCII strings")
expr_rule(E.StringLPad, incompat="byte-counted padding is exact only "
          "for ASCII strings")
expr_rule(E.StringRPad, incompat="byte-counted padding is exact only "
          "for ASCII strings")
expr_rule(E.StringReverse, incompat="byte reversal is exact only for "
          "ASCII strings")
# array consumers/producers: the array side of their signature is nested
expr_rule(E.Size, checks=TS.expr_checks(TS.common_tpu,
                                        TS.common_tpu_nested))
expr_rule(E.ElementAt, checks=TS.expr_checks(TS.common_tpu,
                                             TS.common_tpu_nested))
expr_rule(E.GetArrayItem, checks=TS.expr_checks(TS.common_tpu,
                                                TS.common_tpu_nested))
expr_rule(E.ArrayContains, checks=TS.expr_checks(TS.common_tpu,
                                                 TS.common_tpu_nested))
expr_rule(E.CreateArray, checks=TS.expr_checks(TS.common_tpu_nested,
                                               TS.common_tpu))
expr_rule(E.CreateNamedStruct,
          checks=TS.expr_checks(TS.common_tpu_nested, TS.common_tpu))
expr_rule(E.GetStructField,
          checks=TS.expr_checks(TS.common_tpu, TS.common_tpu_nested))
expr_rule(E.TimeWindow,
          checks=TS.expr_checks(TS.common_tpu_nested, TS.common_tpu))

# leaves that are valid in any device expression tree without a handler
_LEAF_OK = (E.AttributeReference,)


def _expr_desc(e: E.Expression, limit: int = 64) -> str:
    """Short rendering of the offending expression SUBTREE for explain
    output (the reference's willNotWorkOnGpu messages carry the expr's
    toString); truncated so one pathological tree cannot flood the
    report."""
    try:
        s = repr(e)
    except Exception:
        s = type(e).__name__
    s = " ".join(s.split())
    return s if len(s) <= limit else s[:limit - 3] + "..."


def check_expr_tree(e: E.Expression, conf: TpuConf) -> Optional[str]:
    """willNotWorkOnTpu reason for an (unbound) expression tree, or
    None. Reasons NAME the offending subtree (`<expr ...>`), so a
    failure deep inside a projection is attributable without replaying
    the rewrite."""
    if isinstance(e, E.Alias):
        return check_expr_tree(e.child, conf)
    if isinstance(e, _LEAF_OK):
        return X.leaf_support(e)
    rule = _EXPR_RULES.get(type(e))
    if rule is None:
        return (f"expression {type(e).__name__} <{_expr_desc(e)}> "
                f"is not supported on TPU")
    r = X._limb_decimal_gate(e)
    if r:
        return r
    if not conf.is_op_enabled(rule.conf_key):
        return (f"expression {type(e).__name__} <{_expr_desc(e)}> has "
                f"been disabled ({rule.conf_key}=false)")
    if rule.incompat and not conf.get(INCOMPATIBLE_OPS):
        return (f"expression {type(e).__name__} <{_expr_desc(e)}> is "
                f"not 100% compatible: {rule.incompat}. Set "
                f"spark.rapids.sql.incompatibleOps.enabled=true to allow")
    if not conf.get(INCOMPATIBLE_OPS):
        r = X.platform_gate(e)
        if r:
            return f"expression {type(e).__name__} <{_expr_desc(e)}>: {r}"
    r = rule.checks.tag(e)
    if r:
        return f"expression {type(e).__name__} <{_expr_desc(e)}>: {r}"
    extra = X._EXTRA_CHECKS.get(type(e))
    if extra is not None:
        r = extra(e)
        if r:
            return f"expression {type(e).__name__} <{_expr_desc(e)}>: {r}"
    for i, c in enumerate(e.children):
        if i in X._ARRAY_ARG_OK.get(type(e), ()) and \
                isinstance(c, E.AttributeReference) and \
                isinstance(c.data_type, T.ArrayType):
            r = X._array_leaf_ok(c)
            if r:
                return f"expression {type(e).__name__}: {r}"
            continue
        r = check_expr_tree(c, conf)
        if r:
            return r
    return None


# ---------------------------------------------------------------------------
# Exec rules (the `commonExecs` registry, GpuOverrides.scala:3252)
# ---------------------------------------------------------------------------

@dataclass
class ExecRule:
    name: str
    desc: str
    checks: TS.ExecChecks
    tag_fn: Optional[Callable[["ExecMeta"], None]] = None
    convert_fn: Optional[Callable] = None  # (meta, device_children) -> plan
    # types the exec can CONSUME (child outputs); project/filter/generate
    # pass nested columns through, the heavy operators do not
    input_sig: Optional[TS.TypeSig] = None

    @property
    def conf_key(self) -> str:
        return f"spark.rapids.sql.exec.{self.name}"


_EXEC_RULES: Dict[Type, ExecRule] = {}


def exec_rule(cls: Type, desc: str,
              checks: Optional[TS.ExecChecks] = None,
              tag_fn=None, convert_fn=None, input_sig=None) -> None:
    _EXEC_RULES[cls] = ExecRule(cls.__name__.replace("Cpu", ""), desc,
                                checks or TS.ExecChecks(TS.common_tpu),
                                tag_fn, convert_fn, input_sig)


# CPU data sources that legitimately feed the device through a
# TpuRowToColumnarExec transition; they are not "fallbacks" (the reference
# likewise scans host-side relations via HostColumnarToGpu without
# reporting them NOT_ON_GPU)
_TRANSPARENT_CPU: tuple = ()


def register_transparent_cpu(*classes: Type) -> None:
    global _TRANSPARENT_CPU
    _TRANSPARENT_CPU = _TRANSPARENT_CPU + classes


class ExecMeta:
    """Wrapper over one CPU physical node (SparkPlanMeta RapidsMeta:543)."""

    def __init__(self, wrapped: P.PhysicalPlan, conf: TpuConf,
                 parent: Optional["ExecMeta"]):
        self.wrapped = wrapped
        self.conf = conf
        self.parent = parent
        self.rule = _EXEC_RULES.get(type(wrapped))
        self.children = [ExecMeta(c, conf, self) for c in wrapped.children]
        self.reasons: List[str] = []

    def will_not_work(self, reason: str) -> None:
        if reason not in self.reasons:
            self.reasons.append(reason)

    @property
    def can_replace(self) -> bool:
        return self.rule is not None and not self.reasons

    def tag(self) -> None:
        for c in self.children:
            c.tag()
        if isinstance(self.wrapped, _TRANSPARENT_CPU):
            return
        if self.rule is None:
            self.will_not_work(
                f"{type(self.wrapped).__name__} has no TPU replacement")
            return
        if not self.conf.is_op_enabled(self.rule.conf_key):
            self.will_not_work(
                f"the exec has been disabled ({self.rule.conf_key}=false)")
        r = self.rule.checks.tag(
            [f.data_type for f in self.wrapped.schema.fields])
        if r:
            self.will_not_work(r)
        # inputs must be representable too (transitions carry data)
        in_sig = self.rule.input_sig or TS.common_tpu
        for c in self.wrapped.children:
            r = in_sig.supports_all(
                [f.data_type for f in c.schema.fields])
            if r:
                self.will_not_work(f"input: {r}")
        if self.rule.tag_fn is not None:
            self.rule.tag_fn(self)

    def convert(self) -> P.PhysicalPlan:
        """Emit the mixed plan under this meta (convertIfNeeded)."""
        from spark_rapids_tpu.exec.base import (TpuColumnarToRowExec,
                                                TpuExec,
                                                TpuRowToColumnarExec)
        conf = self.conf
        converted = [c.convert() for c in self.children]
        if self.can_replace:
            device_children = []
            for plan in converted:
                if isinstance(plan, TpuExec):
                    device_children.append(plan)
                else:
                    device_children.append(TpuRowToColumnarExec(plan, conf))
            return self.rule.convert_fn(self, device_children)
        # stays on CPU: device children come back through C2R
        cpu_children = []
        for plan in converted:
            if isinstance(plan, TpuExec):
                cpu_children.append(TpuColumnarToRowExec(plan, conf))
            else:
                cpu_children.append(plan)
        if cpu_children:
            return self.wrapped.with_new_children(cpu_children)
        return self.wrapped

    # -- reporting -----------------------------------------------------

    def collect_fallbacks(self, out: List) -> None:
        # rule or no rule, a tagged node reports the same way (the two
        # branches used to duplicate this append verbatim)
        if self.reasons:
            out.append((type(self.wrapped).__name__, list(self.reasons)))
        for c in self.children:
            c.collect_fallbacks(out)


# -- op-specific tagging ----------------------------------------------------

def _tag_project(meta: ExecMeta) -> None:
    for e in meta.wrapped.project_list:
        r = check_expr_tree(e, meta.conf)
        if r:
            meta.will_not_work(r)


def _tag_filter(meta: ExecMeta) -> None:
    r = check_expr_tree(meta.wrapped.condition, meta.conf)
    if r:
        meta.will_not_work(r)


def _tag_exchange(meta: ExecMeta) -> None:
    # (struct PAYLOAD columns are vetted by the exchange's
    # common_tpu_struct signature, which recurses into fields)
    p = meta.wrapped.partitioning
    if isinstance(p, P.HashPartitioning):
        for e in p.exprs:
            dt = getattr(e, "data_type", None)
            if isinstance(dt, (T.ArrayType, T.MapType)):
                meta.will_not_work(
                    "nested hash partition keys run on CPU")
            elif isinstance(dt, T.StructType):
                from spark_rapids_tpu import typesig as TS
                r = TS.common_tpu_struct.support(dt)
                if r:
                    meta.will_not_work(f"hash partition key: {r}")
                elif any(isinstance(f.data_type, T.DecimalType)
                         and f.data_type.precision > 18
                         for f in dt.fields):
                    # the variable-length big-decimal byte hash has no
                    # device twin (same gate as top-level decimal128)
                    meta.will_not_work(
                        "decimal128 struct fields in hash partition "
                        "keys run on CPU")
            r = check_expr_tree(e, meta.conf)
            if r:
                meta.will_not_work(r)
            if X.contains_ansi_cast(e):
                meta.will_not_work(
                    "ANSI casts in partition keys run on CPU")
            dt = getattr(e, "data_type", None)
            if dt is not None and isinstance(dt, T.DecimalType) \
                    and dt.precision > 18:
                meta.will_not_work(
                    "decimal128 hash partitioning runs on CPU")
    elif isinstance(p, (P.SinglePartitioning, P.RoundRobinPartitioning)):
        pass
    elif isinstance(p, P.RangePartitioning):
        from spark_rapids_tpu.exec.sort import is_device_sort
        r = is_device_sort(p.order, meta.conf)
        if r:
            meta.will_not_work(f"range partitioning: {r}")
    else:
        meta.will_not_work(
            f"{type(p).__name__} is not supported on TPU yet")


def _tag_expand(meta: ExecMeta) -> None:
    for proj in meta.wrapped.projections:
        for e in proj:
            r = check_expr_tree(e, meta.conf)
            if r:
                meta.will_not_work(r)
                return


def _tag_sort(meta: ExecMeta) -> None:
    from spark_rapids_tpu.exec.sort import is_device_sort
    r = is_device_sort(meta.wrapped.order, meta.conf)
    if r:
        meta.will_not_work(r)


def _tag_window(meta: ExecMeta) -> None:
    from spark_rapids_tpu.exec.window import is_device_window
    w = meta.wrapped
    r = is_device_window(w.window_exprs, w.partition_spec, w.order_spec,
                         meta.conf)
    if r:
        meta.will_not_work(r)


def _tag_join(meta: ExecMeta) -> None:
    from spark_rapids_tpu.exec.join import is_device_join
    w = meta.wrapped
    r = is_device_join(w.join_type, w.left_keys, w.right_keys, w.condition,
                       meta.conf)
    if r:
        meta.will_not_work(r)


def _tag_aggregate(meta: ExecMeta) -> None:
    from spark_rapids_tpu.exec.agg import is_device_agg
    node = meta.wrapped
    r = is_device_agg(node.grouping, node.aggregates, meta.conf)
    if r:
        meta.will_not_work(r)
        return
    for g in node.grouping:
        # flat-field structs group on device (TimeWindow keys)
        rr = TS.common_tpu_struct.support(g.data_type)
        if rr:
            meta.will_not_work(f"grouping key {g.name}: {rr}")
    if not meta.conf.get(ENABLE_FLOAT_AGG):
        for e in node.aggregates:
            if isinstance(e, E.Alias) and isinstance(
                    e.child, E.AggregateExpression):
                func = e.child.func
                if isinstance(func, (E.Sum, E.Average)) and T.is_floating(
                        func.children[0].data_type):
                    meta.will_not_work(
                        "device float sum/average may differ from CPU due "
                        "to addition ordering "
                        "(spark.rapids.sql.variableFloatAgg.enabled=false)")
                if isinstance(func, E.CentralMomentAgg):
                    # stddev/variance sums floats (sum + sum-of-squares
                    # buffers) regardless of the input dtype
                    meta.will_not_work(
                        "device stddev/variance may differ from CPU due "
                        "to addition ordering "
                        "(spark.rapids.sql.variableFloatAgg.enabled=false)")


# -- converters -------------------------------------------------------------

def _coalesced(kid, conf):
    """Insert TpuCoalesceBatches over a device exchange so narrow
    per-batch operators see goal-sized batches instead of the exchange's
    per-input splits (GpuTransitionOverrides' coalesce-insertion role;
    ops that concat whole partitions anyway — agg/sort/join/window —
    skip it)."""
    from spark_rapids_tpu.exec.base import TpuCoalesceBatchesExec
    from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
    if isinstance(kid, TpuShuffleExchangeExec):
        return TpuCoalesceBatchesExec(kid, conf)
    return kid


def _conv_project(meta, kids):
    from spark_rapids_tpu.exec.basic import TpuProjectExec
    return TpuProjectExec(meta.wrapped.project_list,
                          _coalesced(kids[0], meta.conf), meta.conf)


def _conv_filter(meta, kids):
    from spark_rapids_tpu.exec.basic import TpuFilterExec
    return TpuFilterExec(meta.wrapped.condition,
                         _coalesced(kids[0], meta.conf), meta.conf)


def _conv_range(meta, kids):
    from spark_rapids_tpu.exec.basic import TpuRangeExec
    w = meta.wrapped
    return TpuRangeExec(w.output, w.start, w.end, w.step,
                        w.num_partitions, meta.conf)


def _conv_union(meta, kids):
    from spark_rapids_tpu.exec.basic import TpuUnionExec
    return TpuUnionExec(kids, meta.wrapped.output, meta.conf)


def _conv_local_limit(meta, kids):
    from spark_rapids_tpu.exec.basic import TpuLocalLimitExec
    from spark_rapids_tpu.exec.sort import TpuSortExec, TpuTopNExec
    kid = kids[0]
    # LocalLimit over Sort fuses into TopN (TakeOrderedAndProject /
    # GpuTopN, limit.scala:123)
    if type(kid) is TpuSortExec:
        return TpuTopNExec(meta.wrapped.n, kid.order, kid.child, meta.conf)
    return TpuLocalLimitExec(meta.wrapped.n, kids[0], meta.conf)


def _conv_global_limit(meta, kids):
    from spark_rapids_tpu.exec.basic import TpuGlobalLimitExec
    return TpuGlobalLimitExec(meta.wrapped.n, kids[0], meta.conf)


def _device_shuffle_partitions(conf, n: int) -> int:
    """Coalesced partition count for device hash/range exchanges: the
    planner's spark.sql.shuffle.partitions sizes CPU-core parallelism,
    but one chip runs every partition's programs serially — extra
    in-process partitions only add split programs and count syncs. Auto
    (0) = ICI mesh size when the mesh shuffle is active, else 1."""
    from spark_rapids_tpu.conf import DEVICE_SHUFFLE_PARTITIONS
    want = int(conf.get(DEVICE_SHUFFLE_PARTITIONS))
    if want <= 0:
        from spark_rapids_tpu.parallel.mesh import (get_active_mesh,
                                                    mesh_size)
        want = mesh_size() if get_active_mesh() is not None else 1
    return max(1, min(n, want))


def _conv_exchange(meta, kids):
    from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
    p = meta.wrapped.partitioning
    # user-explicit repartition(n, ...) keeps its count (planner marks
    # it user_specified); planner-inserted hash/range distribution
    # requirements are satisfied by ANY partition count, so those
    # coalesce to the device-friendly one
    if not getattr(p, "user_specified", False):
        if isinstance(p, P.HashPartitioning):
            n = _device_shuffle_partitions(meta.conf, p.num_partitions)
            if n != p.num_partitions:
                p = P.HashPartitioning(p.exprs, n)
        elif isinstance(p, P.RangePartitioning):
            n = _device_shuffle_partitions(meta.conf, p.num_partitions)
            if n != p.num_partitions:
                p = P.RangePartitioning(p.order, n)
    return TpuShuffleExchangeExec(p, kids[0], meta.conf)


def _allow_aqe_coalesce(kid):
    """Aggregate/sort/window consumers accept ANY partition count, so
    their exchange child may coalesce tiny partitions at runtime
    (GpuCustomShuffleReaderExec role); join inputs must stay
    co-partitioned and never opt in."""
    from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
    if isinstance(kid, TpuShuffleExchangeExec):
        kid.allow_aqe_coalesce = True
    return kid


def _conv_aggregate(meta, kids):
    from spark_rapids_tpu.exec.agg import TpuHashAggregateExec
    w = meta.wrapped
    return TpuHashAggregateExec(w.grouping, w.aggregates, w.mode,
                                _allow_aqe_coalesce(kids[0]),
                                w.slots, meta.conf)


def _conv_expand(meta, kids):
    from spark_rapids_tpu.exec.basic import TpuExpandExec
    w = meta.wrapped
    return TpuExpandExec(w.projections, w.output, kids[0], meta.conf)


def _conv_sort(meta, kids):
    from spark_rapids_tpu.exec.sort import TpuSortExec
    w = meta.wrapped
    return TpuSortExec(w.order, w.is_global,
                       _allow_aqe_coalesce(kids[0]), meta.conf)


def _conv_window(meta, kids):
    from spark_rapids_tpu.exec.window import TpuWindowExec
    w = meta.wrapped
    return TpuWindowExec(w.window_exprs, w.partition_spec, w.order_spec,
                         _allow_aqe_coalesce(kids[0]), meta.conf)


def _conv_shuffled_join(meta, kids):
    from spark_rapids_tpu.exec.join import TpuShuffledHashJoinExec
    w = meta.wrapped
    return TpuShuffledHashJoinExec(w.left_keys, w.right_keys, w.join_type,
                                   w.condition, kids[0], kids[1], w.output,
                                   meta.conf, null_safe=w.null_safe)


def _conv_broadcast_exchange(meta, kids):
    from spark_rapids_tpu.exec.exchange import TpuBroadcastExchangeExec
    return TpuBroadcastExchangeExec(kids[0], meta.conf)


def _conv_broadcast_join(meta, kids):
    from spark_rapids_tpu.exec.join import TpuBroadcastHashJoinExec
    w = meta.wrapped
    return TpuBroadcastHashJoinExec(w.left_keys, w.right_keys, w.join_type,
                                    w.condition, kids[0], kids[1], w.output,
                                    meta.conf, null_safe=w.null_safe)


def _tag_generate(meta: ExecMeta) -> None:
    from spark_rapids_tpu.exec.generate import is_device_generate
    r = is_device_generate(meta.wrapped.generator, meta.conf)
    if r:
        meta.will_not_work(r)


def _conv_generate(meta, kids):
    from spark_rapids_tpu.exec.generate import TpuGenerateExec
    w = meta.wrapped
    return TpuGenerateExec(w.generator, w.gen_output, kids[0], meta.conf)


exec_rule(P.CpuProjectExec, "projection onto device columns",
          checks=TS.ExecChecks(TS.common_tpu_nested),
          tag_fn=_tag_project, convert_fn=_conv_project,
          input_sig=TS.common_tpu_nested)
exec_rule(P.CpuFilterExec, "device predicate filter (mask update)",
          checks=TS.ExecChecks(TS.common_tpu_nested),
          tag_fn=_tag_filter, convert_fn=_conv_filter,
          input_sig=TS.common_tpu_nested)
exec_rule(P.CpuGenerateExec, "device explode over segmented arrays",
          checks=TS.ExecChecks(TS.common_tpu_nested),
          tag_fn=_tag_generate, convert_fn=_conv_generate,
          input_sig=TS.common_tpu_nested)
exec_rule(P.CpuRangeExec, "device iota range source",
          convert_fn=_conv_range)
exec_rule(P.CpuUnionExec, "union of device partitions",
          convert_fn=_conv_union)
exec_rule(P.CpuLocalLimitExec, "per-partition limit by mask",
          convert_fn=_conv_local_limit)
exec_rule(P.CpuGlobalLimitExec, "global limit by mask",
          convert_fn=_conv_global_limit)
exec_rule(P.CpuShuffleExchangeExec, "device-partitioned exchange",
          checks=TS.ExecChecks(TS.common_tpu_struct),
          input_sig=TS.common_tpu_struct,
          tag_fn=_tag_exchange, convert_fn=_conv_exchange)
exec_rule(P.CpuBroadcastExchangeExec,
          "device-resident reusable broadcast "
          "(GpuBroadcastExchangeExec.scala:280)",
          convert_fn=_conv_broadcast_exchange)
exec_rule(P.CpuHashAggregateExec, "sort-segmented device aggregation",
          checks=TS.ExecChecks(TS.common_tpu_struct),
          input_sig=TS.common_tpu_struct,
          tag_fn=_tag_aggregate, convert_fn=_conv_aggregate)
exec_rule(P.CpuExpandExec, "device grouping-sets expansion",
          tag_fn=_tag_expand, convert_fn=_conv_expand)
exec_rule(P.CpuSortExec, "device lexsort over encoded sort keys",
          checks=TS.ExecChecks(TS.common_tpu_struct),
          input_sig=TS.common_tpu_struct,
          tag_fn=_tag_sort, convert_fn=_conv_sort)
from spark_rapids_tpu.sql.window_exec import CpuWindowExec  # noqa: E402
exec_rule(CpuWindowExec, "segment-scan device window functions",
          tag_fn=_tag_window, convert_fn=_conv_window)
exec_rule(P.CpuShuffledHashJoinExec, "count-then-gather device equi-join",
          tag_fn=_tag_join, convert_fn=_conv_shuffled_join)
exec_rule(P.CpuBroadcastHashJoinExec,
          "device equi-join with HBM-resident build side",
          tag_fn=_tag_join, convert_fn=_conv_broadcast_join)
register_transparent_cpu(P.CpuLocalScanExec)

from spark_rapids_tpu.io.readers import CpuFileScanExec  # noqa: E402
from spark_rapids_tpu.io.cache import CpuCachedScanExec  # noqa: E402
register_transparent_cpu(CpuFileScanExec, CpuCachedScanExec)

from spark_rapids_tpu.exec import python_exec as PY  # noqa: E402


def _conv_arrow_eval(meta, kids):
    from spark_rapids_tpu.exec.python_exec import TpuArrowEvalPythonExec
    return TpuArrowEvalPythonExec(meta.wrapped, kids[0], meta.conf)


def _conv_map_in_pandas(meta, kids):
    from spark_rapids_tpu.exec.python_exec import TpuMapInPandasExec
    return TpuMapInPandasExec(meta.wrapped, kids[0], meta.conf)


exec_rule(PY.CpuArrowEvalPythonExec,
          "scalar pandas UDFs via the python worker pool; the "
          "surrounding plan stays on device "
          "(GpuArrowEvalPythonExec.scala:487)",
          convert_fn=_conv_arrow_eval)
exec_rule(PY.CpuMapInPandasExec,
          "mapInPandas via the python worker pool "
          "(GpuMapInPandasExec role)",
          convert_fn=_conv_map_in_pandas)


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

@dataclass
class RewriteReport:
    """Explain/fallback record for one query: the
    ``spark.rapids.sql.explain=NOT_ON_TPU|ALL`` output and the
    per-query explain section of the profile artifact
    (GpuOverrides explain / ExecutionPlanCaptureCallback roles)."""

    fallbacks: List = field(default_factory=list)  # (exec name, [reasons])
    device_ops: List[str] = field(default_factory=list)  # placed on TPU
    replaced_any: bool = False

    def format(self, mode: str = "NOT_ON_TPU") -> str:
        """NOT_ON_TPU: one line per fallback reason; ALL additionally
        lists every operator that WILL run on TPU (the reference's
        `*Exec <x> will run on GPU` / `!Exec <x> cannot run` shape)."""
        lines = []
        if mode == "ALL":
            for name in self.device_ops:
                lines.append(f"*Exec <{name}> will run on TPU")
        for name, reasons in self.fallbacks:
            for r in reasons:
                lines.append(f"!Exec <{name}> cannot run on TPU because {r}")
        return "\n".join(lines)

    @property
    def coverage(self) -> float:
        """Fraction of rated operators placed on device (transitions
        excluded from device_ops by construction)."""
        total = len(self.device_ops) + len(self.fallbacks)
        return (len(self.device_ops) / total) if total else 1.0

    def reason_counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for _name, reasons in self.fallbacks:
            for r in reasons:
                out[r] = out.get(r, 0) + 1
        return out

    def print_explain(self, conf: TpuConf) -> None:
        """Print the explain lines the configured mode asks for
        (NOT_ON_GPU honored as an alias). ``apply_overrides`` calls
        this once per rewrite; a plan-cache HIT replays it from the
        cached report so `sql.explain` output does not disappear when
        the rewrite itself was skipped (docs/serving.md)."""
        mode = conf.explain
        if mode == "NOT_ON_GPU":
            mode = "NOT_ON_TPU"
        if mode == "ALL" or (mode == "NOT_ON_TPU" and self.fallbacks):
            text = self.format(mode)
            if text:
                print(text)

    def summary(self) -> Dict:
        """JSON-ready aggregate (profile artifact + event log v2)."""
        return {
            "replacedAny": self.replaced_any,
            "deviceOps": list(self.device_ops),
            "coverage": round(self.coverage, 4),
            "fallbacks": [{"op": n, "reasons": list(rs)}
                          for n, rs in self.fallbacks],
            "reasonCounts": self.reason_counts(),
        }


def _record_device_ops(plan: P.PhysicalPlan, report: RewriteReport) -> None:
    """Fill report.device_ops from the FINAL plan (post-CBO/fusion):
    every Tpu* operator, fused-stage constituents included, transitions
    excluded (they are plumbing, not accelerated operators — the
    reference likewise does not rate them)."""
    from spark_rapids_tpu.exec.base import TpuExec, TpuRowToColumnarExec
    report.device_ops = []

    def walk(p) -> None:
        # TpuColumnarToRowExec is not a TpuExec, so download transitions
        # skip themselves here
        if isinstance(p, TpuExec) and not isinstance(
                p, TpuRowToColumnarExec):
            if getattr(p, "fused_ops", None):
                report.device_ops.extend(
                    op.simple_string().split()[0] for op in p.fused_ops)
            else:
                report.device_ops.append(p.simple_string().split()[0])
        for c in p.children:
            walk(c)

    walk(plan)


def apply_overrides(physical: P.PhysicalPlan, conf: TpuConf,
                    report: Optional[RewriteReport] = None
                    ) -> P.PhysicalPlan:
    """GpuOverrides.apply + GpuTransitionOverrides in one pass."""
    from spark_rapids_tpu.exec.base import TpuColumnarToRowExec, TpuExec
    meta = ExecMeta(physical, conf, None)
    meta.tag()
    if report is None:
        report = RewriteReport()
    meta.collect_fallbacks(report.fallbacks)
    if conf.get(TEST_FORCE_DEVICE) and report.fallbacks:
        raise AssertionError(
            "Part of the plan is not columnar (test.forceDevice):\n"
            + report.format())
    new_plan = meta.convert()
    if isinstance(new_plan, TpuExec):
        new_plan = TpuColumnarToRowExec(new_plan, conf)
        report.replaced_any = True
    else:
        report.replaced_any = _has_device_op(new_plan)
    from spark_rapids_tpu.conf import CBO_ENABLED
    if conf.get(CBO_ENABLED) and not conf.get(TEST_FORCE_DEVICE):
        new_plan = _revert_small_islands(new_plan, report)
        report.replaced_any = _has_device_op(new_plan)
    # whole-stage fusion LAST: it must see the final operator placement
    # (post-CBO), and a fused stage never crosses the boundaries the
    # passes above inserted (transitions, exchanges, coalesce)
    from spark_rapids_tpu.conf import STAGE_FUSION_ENABLED
    if conf.get(STAGE_FUSION_ENABLED):
        from spark_rapids_tpu.exec.fused import fuse_stages
        new_plan = fuse_stages(new_plan, conf)
    _record_device_ops(new_plan, report)
    # NOT_ON_GPU accepted as an alias: half the reference's docs/tests
    # spell it that way and the muscle memory is worth honoring
    report.print_explain(conf)
    return new_plan


def refuse_replanned_subtree(plan: P.PhysicalPlan,
                             conf: TpuConf) -> P.PhysicalPlan:
    """AQE's re-entry into the static fusion pass (docs/adaptive.md):
    a runtime replan that removes an exchange boundary (the broadcast
    demotion in exec/join.py) hands the surviving — already cloned —
    subtree back through fuse_stages under the same conf gate
    apply_overrides used, so the replanned plan gets the Filter/Project
    chains the boundary previously blocked. No-op with fusion off."""
    from spark_rapids_tpu.conf import STAGE_FUSION_ENABLED
    if conf.get(STAGE_FUSION_ENABLED):
        from spark_rapids_tpu.exec.fused import fuse_stages
        return fuse_stages(plan, conf)
    return plan


# -- cost model (CostBasedOptimizer.scala:52 CpuCostModel/GpuCostModel) ----
#
# Constants in seconds. The host<->HBM wire rate and the flat
# sync/dispatch latency per device island are UNMEASURED on the
# directly attached chip: both values date from an installation that
# no longer exists. The optimizer that reads them is off by default;
# recalibrating them is left to the PR that measures the wire. The CPU
# engine's numpy passes stream at memory bandwidth (~2GB/s) EXCEPT
# regex-class expressions, which run a python-level loop per row.
_WIRE_BYTES_PER_S = 150e6
_ISLAND_FLAT_S = 0.15
_DEFAULT_ROW_COUNT = 1 << 20  # reference optimizer's default-row-count role

_NS_ELEMENTWISE = 3.0      # one vectorized numpy pass per expression node
_NS_STRING_OP = 25.0       # object-array string kernels
_NS_REGEX = 2000.0         # python re loop per row (LIKE/regexp/split;
                           # measured 2-4us/row on the host engine)


def _expr_cost_ns(e) -> float:
    """Estimated CPU nanoseconds PER ROW to evaluate this expression
    tree with the host engine."""
    from spark_rapids_tpu.sql import expressions as E
    name = type(e).__name__
    if name in ("Like", "RLike", "RegExpExtract", "RegExpReplace",
                "StringSplit", "PythonUDF", "PandasUDF"):
        ns = _NS_REGEX
    elif isinstance(getattr(e, "data_type", None), T.StringType) \
            and e.children:
        ns = _NS_STRING_OP
    elif not e.children:
        ns = 0.0  # attribute/literal: no pass of its own
    else:
        ns = _NS_ELEMENTWISE
    return ns + sum(_expr_cost_ns(c) for c in e.children)


def _row_width_bytes(schema: T.StructType) -> int:
    w = 0
    for f in schema.fields:
        dt = f.data_type
        if isinstance(dt, (T.StringType, T.BinaryType)):
            w += 24
        elif T.is_limb_decimal(dt):
            w += 16
        else:
            try:
                w += T.numpy_dtype(dt).itemsize
            except Exception:
                w += 8
        w += 1  # validity
    return max(1, w)


def _estimate_rows(p: P.PhysicalPlan) -> int:
    """Row-count estimate for a CPU source subtree (the optimizer's
    stats stand-in; scans estimate from file bytes, local data is
    exact, everything else passes through its first child)."""
    from spark_rapids_tpu.io.readers import CpuFileScanExec
    if isinstance(p, P.CpuLocalScanExec):
        return sum(b.num_rows for b in p.batches) \
            if getattr(p, "batches", None) else _DEFAULT_ROW_COUNT
    if isinstance(p, CpuFileScanExec):
        # parquet row-group footers carry EXACT row counts (already
        # parsed into ScanUnit.stats for predicate pushdown)
        rows = 0
        exact = True
        for u in p._units:
            nr = None
            if u.stats:
                for st in u.stats.values():
                    nr = st[3]
                    break
            if nr is None:
                exact = False
                break
            rows += int(nr)
        if exact and rows:
            return rows
        total = sum(u.size_bytes for u in p._units)
        # non-parquet bytes are compressed ~2x relative to in-memory
        return max(1, int(total * 2) // _row_width_bytes(p.schema))
    if p.children:
        return _estimate_rows(p.children[0])
    return _DEFAULT_ROW_COUNT


def _revert_small_islands(plan: P.PhysicalPlan, report: RewriteReport
                          ) -> P.PhysicalPlan:
    """Cost-based optimizer (CostBasedOptimizer.scala:52 role): revert a
    CPU-sandwiched device island (a Project/Filter/Coalesce chain
    between an upload and a download) when the estimated CPU cost of its
    expressions is LESS than the transition cost of shipping the rows to
    HBM and back. Unlike the v0 pattern-match, this keeps a single
    regex-heavy operator on device for large inputs (the python re loop
    dwarfs the wire cost) and reverts multi-op chains over small data
    (the flat sync latency dominates)."""
    from spark_rapids_tpu.exec.base import (TpuColumnarToRowExec,
                                            TpuCoalesceBatchesExec,
                                            TpuRowToColumnarExec)
    from spark_rapids_tpu.exec.basic import TpuFilterExec, TpuProjectExec

    new_children = [_revert_small_islands(c, report)
                    for c in plan.children]
    if new_children != plan.children:
        plan = plan.with_new_children(new_children)
    if not isinstance(plan, TpuColumnarToRowExec):
        return plan
    island: List[P.PhysicalPlan] = []
    cur = plan.child
    while isinstance(cur, (TpuProjectExec, TpuFilterExec,
                           TpuCoalesceBatchesExec)):
        island.append(cur)
        cur = cur.children[0]
    if not isinstance(cur, TpuRowToColumnarExec):
        return plan
    compute = [n for n in island
               if not isinstance(n, TpuCoalesceBatchesExec)]
    cpu_src = cur.children[0]
    rows = _estimate_rows(cpu_src)
    cpu_ns_per_row = 0.0
    for n in compute:
        if isinstance(n, TpuProjectExec):
            cpu_ns_per_row += sum(_expr_cost_ns(e)
                                  for e in n.project_list)
        elif isinstance(n, TpuFilterExec):
            cpu_ns_per_row += _expr_cost_ns(n.condition)
    cpu_cost_s = rows * cpu_ns_per_row * 1e-9
    in_bytes = rows * _row_width_bytes(cpu_src.schema)
    out_bytes = rows * _row_width_bytes(plan.child.schema)
    transition_cost_s = (in_bytes + out_bytes) / _WIRE_BYTES_PER_S \
        + _ISLAND_FLAT_S
    if cpu_cost_s >= transition_cost_s:
        return plan  # the island repays its transitions
    cpu = cpu_src
    for n in reversed(island):
        if isinstance(n, TpuProjectExec):
            cpu = P.CpuProjectExec(n.project_list, cpu)
        elif isinstance(n, TpuFilterExec):
            cpu = P.CpuFilterExec(n.condition, cpu)
        # coalesce nodes have no CPU-side meaning: drop
    report.fallbacks.append((
        type(compute[0]).__name__ if compute else "TpuRowToColumnar",
        [f"the transition cost (~{transition_cost_s:.2f}s for ~{rows} "
         f"rows) outweighs the estimated device speedup "
         f"(~{cpu_cost_s:.2f}s of CPU work) "
         "(spark.rapids.sql.optimizer.enabled)"]))
    return cpu


def _has_device_op(plan: P.PhysicalPlan) -> bool:
    from spark_rapids_tpu.exec.base import TpuExec
    if isinstance(plan, TpuExec):
        return True
    return any(_has_device_op(c) for c in plan.children)

"""Logical plans (the Catalyst layer Spark provides in the reference).

Name resolution happens eagerly in the DataFrame API (resolve() below)
rather than in a separate analyzer phase; after construction every
expression in a plan refers to AttributeReferences with unique ids.
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence

from spark_rapids_tpu.sql import types as T
from spark_rapids_tpu.sql.expressions import (
    AggregateExpression, Alias, AttributeReference, Cast, Expression,
    Literal, SortOrder, UnresolvedAttribute, named_output)


class LogicalPlan:
    children: List["LogicalPlan"]

    @property
    def output(self) -> List[AttributeReference]:
        raise NotImplementedError

    @property
    def schema(self) -> T.StructType:
        return T.StructType([
            T.StructField(a.name, a.data_type, a.nullable)
            for a in self.output])

    def __repr__(self) -> str:
        return self._tree_string(0)

    def _tree_string(self, indent: int) -> str:
        s = " " * indent + self.simple_string()
        for c in self.children:
            s += "\n" + c._tree_string(indent + 2)
        return s

    def simple_string(self) -> str:
        return type(self).__name__


class UnresolvedColumnError(KeyError):
    """No input attribute has this name; ``column`` is the name."""

    def __init__(self, column: str, inputs: Sequence[AttributeReference]):
        super().__init__(f"cannot resolve '{column}' among "
                         f"{[a.name for a in inputs]}")
        self.column = column


def resolve(expr: Expression, inputs: Sequence[AttributeReference],
            case_sensitive: bool = False) -> Expression:
    """Replace UnresolvedAttribute with matching AttributeReference."""

    def norm(s: Optional[str]) -> Optional[str]:
        return s if case_sensitive or s is None else s.lower()

    def base_matches(parts: List[str], k: int) -> List[AttributeReference]:
        """Attributes matching the first k name parts: as a bare (dotted)
        column name, or as qualifier + column (Catalyst's order)."""
        nm = norm(".".join(parts[:k]))
        ms = [a for a in inputs if norm(a.name) == nm]
        if not ms and k >= 2:
            qual, col = norm(parts[0]), norm(".".join(parts[1:k]))
            ms = [a for a in inputs
                  if norm(a.name) == col and norm(a.qualifier) == qual]
        return ms

    def rule(e: Expression) -> Optional[Expression]:
        if isinstance(e, UnresolvedAttribute):
            parts = e.name.split(".")
            # longest base first: `a.s.y` prefers column a.s (or
            # qualifier a + column s) before treating y as a field
            for k in range(len(parts), 0, -1):
                ms = base_matches(parts, k)
                if len(ms) > 1:
                    raise KeyError(f"ambiguous column '{e.name}'")
                if not ms:
                    continue
                out: Expression = ms[0]
                ok = True
                for p in parts[k:]:  # remaining parts walk struct fields
                    dt = out.data_type
                    fld = next(
                        (f.name for f in dt.fields
                         if norm(f.name) == norm(p)), None) \
                        if isinstance(dt, T.StructType) else None
                    if fld is None:
                        ok = False
                        break
                    from spark_rapids_tpu.sql.expressions import \
                        GetStructField
                    out = GetStructField(out, name=fld)
                if ok:
                    return out
            raise UnresolvedColumnError(e.name, inputs)
        return None

    return expr.transform(rule)


class MapInPandas(LogicalPlan):
    """DataFrame.mapInPandas(func, schema) (sql/core MapInPandas)."""

    def __init__(self, fn, schema: T.StructType, child: LogicalPlan):
        self.children = [child]
        self.fn = fn
        self._schema = schema
        self._output = [AttributeReference(f.name, f.data_type, f.nullable)
                        for f in schema.fields]

    @property
    def child(self) -> LogicalPlan:
        return self.children[0]

    @property
    def output(self) -> List[AttributeReference]:
        return self._output

    def simple_string(self) -> str:
        return f"MapInPandas {getattr(self.fn, '__name__', '<fn>')}"


class SubqueryAlias(LogicalPlan):
    """Relation alias (Catalyst SubqueryAlias): same expr_ids, outputs
    re-qualified so ``alias.col`` references resolve. Physically
    transparent — the planner plans straight through it."""

    def __init__(self, alias: str, child: LogicalPlan):
        self.children = [child]
        self.alias = alias

    @property
    def child(self) -> LogicalPlan:
        return self.children[0]

    @property
    def output(self) -> List[AttributeReference]:
        return [a.with_qualifier(self.alias) for a in self.child.output]

    def simple_string(self) -> str:
        return f"SubqueryAlias {self.alias}"


class LocalRelation(LogicalPlan):
    """In-memory data; plays LocalTableScan / the test-side gen_df source."""

    def __init__(self, schema: T.StructType, batches: List,
                 num_partitions: int = 1):
        from spark_rapids_tpu.columnar.host import HostBatch
        self.children = []
        self._output = [AttributeReference(f.name, f.data_type, f.nullable)
                        for f in schema.fields]
        self._schema = schema
        self.batches: List[HostBatch] = batches
        self.num_partitions = num_partitions

    @property
    def output(self) -> List[AttributeReference]:
        return self._output

    def simple_string(self) -> str:
        n = sum(b.num_rows for b in self.batches)
        return f"LocalRelation [{n} rows, {len(self._output)} cols]"


class FileScan(LogicalPlan):
    """Parquet/CSV/ORC scan (GpuFileSourceScanExec's logical ancestor)."""

    def __init__(self, fmt: str, paths: List[str], schema: T.StructType,
                 options: Optional[dict] = None):
        self.children = []
        self.fmt = fmt
        self.paths = paths
        self._schema = schema
        self.options = options or {}
        self._output = [AttributeReference(f.name, f.data_type, f.nullable)
                        for f in schema.fields]

    @property
    def output(self) -> List[AttributeReference]:
        return self._output

    def simple_string(self) -> str:
        return f"FileScan {self.fmt} {self.paths}"


class Range(LogicalPlan):
    """spark.range(); GpuRangeExec analogue upstream."""

    def __init__(self, start: int, end: int, step: int = 1,
                 num_partitions: int = 1):
        self.children = []
        self.start, self.end, self.step = start, end, step
        self.num_partitions = num_partitions
        self._output = [AttributeReference("id", T.LongT, nullable=False)]

    @property
    def output(self) -> List[AttributeReference]:
        return self._output


class Project(LogicalPlan):
    def __init__(self, project_list: List[Expression], child: LogicalPlan):
        self.children = [child]
        self.project_list = project_list

    @property
    def child(self) -> LogicalPlan:
        return self.children[0]

    @property
    def output(self) -> List[AttributeReference]:
        return [named_output(e) for e in self.project_list]

    def simple_string(self) -> str:
        return f"Project {self.project_list}"


class Filter(LogicalPlan):
    def __init__(self, condition: Expression, child: LogicalPlan):
        self.children = [child]
        self.condition = condition

    @property
    def child(self) -> LogicalPlan:
        return self.children[0]

    @property
    def output(self) -> List[AttributeReference]:
        return self.child.output

    def simple_string(self) -> str:
        return f"Filter {self.condition!r}"


class Aggregate(LogicalPlan):
    """grouping expressions + result expressions (group attrs and
    Alias(AggregateExpression) items)."""

    def __init__(self, grouping: List[Expression],
                 aggregates: List[Expression], child: LogicalPlan):
        self.children = [child]
        self.grouping = grouping
        self.aggregates = aggregates

    @property
    def child(self) -> LogicalPlan:
        return self.children[0]

    @property
    def output(self) -> List[AttributeReference]:
        return [named_output(e) for e in self.aggregates]

    def simple_string(self) -> str:
        return f"Aggregate {self.grouping} {self.aggregates}"


class Join(LogicalPlan):
    def __init__(self, left: LogicalPlan, right: LogicalPlan,
                 join_type: str, condition: Optional[Expression]):
        self.children = [left, right]
        self.join_type = join_type  # inner/left/right/full/leftsemi/leftanti/cross
        self.condition = condition

    @property
    def left(self) -> LogicalPlan:
        return self.children[0]

    @property
    def right(self) -> LogicalPlan:
        return self.children[1]

    @property
    def output(self) -> List[AttributeReference]:
        jt = self.join_type
        if jt in ("leftsemi", "leftanti"):
            return self.left.output
        left_out = list(self.left.output)
        right_out = list(self.right.output)
        if jt in ("left", "full", "leftouter", "fullouter"):
            right_out = [AttributeReference(a.name, a.data_type, True,
                                            a.expr_id, a.qualifier)
                         for a in right_out]
        if jt in ("right", "full", "rightouter", "fullouter"):
            left_out = [AttributeReference(a.name, a.data_type, True,
                                           a.expr_id, a.qualifier)
                        for a in left_out]
        return left_out + right_out

    def simple_string(self) -> str:
        return f"Join {self.join_type} {self.condition!r}"


class Sort(LogicalPlan):
    def __init__(self, order: List[SortOrder], is_global: bool,
                 child: LogicalPlan):
        self.children = [child]
        self.order = order
        self.is_global = is_global

    @property
    def child(self) -> LogicalPlan:
        return self.children[0]

    @property
    def output(self) -> List[AttributeReference]:
        return self.child.output

    def simple_string(self) -> str:
        return f"Sort {self.order} global={self.is_global}"


class Limit(LogicalPlan):
    def __init__(self, n: int, child: LogicalPlan):
        self.children = [child]
        self.n = n

    @property
    def child(self) -> LogicalPlan:
        return self.children[0]

    @property
    def output(self) -> List[AttributeReference]:
        return self.child.output


class Union(LogicalPlan):
    def __init__(self, plans: List[LogicalPlan]):
        self.children = list(plans)
        first = plans[0].output
        self._output = [AttributeReference(a.name, a.data_type,
                                           any(p.output[i].nullable
                                               for p in plans))
                        for i, a in enumerate(first)]

    @property
    def output(self) -> List[AttributeReference]:
        return self._output


class Repartition(LogicalPlan):
    def __init__(self, num_partitions: int, shuffle: bool,
                 child: LogicalPlan, by: Optional[List[Expression]] = None):
        self.children = [child]
        self.num_partitions = num_partitions
        self.shuffle = shuffle
        self.by = by  # None = round robin

    @property
    def child(self) -> LogicalPlan:
        return self.children[0]

    @property
    def output(self) -> List[AttributeReference]:
        return self.child.output


class Generate(LogicalPlan):
    """Generator application: child rows x generator output
    (Spark Generate / GpuGenerateExec.scala:440 logical twin). Output =
    child output + the generator's attributes (pre-allocated so
    downstream references bind by expr_id)."""

    def __init__(self, generator: Expression,
                 gen_output: List[AttributeReference], child: LogicalPlan):
        self.children = [child]
        self.generator = generator
        self.gen_output = gen_output

    @property
    def child(self) -> LogicalPlan:
        return self.children[0]

    @property
    def output(self) -> List[AttributeReference]:
        return list(self.child.output) + list(self.gen_output)

    def simple_string(self) -> str:
        return f"Generate {self.generator!r}"


class Expand(LogicalPlan):
    """Grouping-sets expansion (GpuExpandExec's logical twin)."""

    def __init__(self, projections: List[List[Expression]],
                 output: List[AttributeReference], child: LogicalPlan):
        self.children = [child]
        self.projections = projections
        self._output = output

    @property
    def child(self) -> LogicalPlan:
        return self.children[0]

    @property
    def output(self) -> List[AttributeReference]:
        return self._output


class Window(LogicalPlan):
    def __init__(self, window_exprs: List[Expression],
                 partition_spec: List[Expression],
                 order_spec: List[SortOrder], child: LogicalPlan):
        self.children = [child]
        self.window_exprs = window_exprs  # Alias(WindowExpression) items
        self.partition_spec = partition_spec
        self.order_spec = order_spec

    @property
    def child(self) -> LogicalPlan:
        return self.children[0]

    @property
    def output(self) -> List[AttributeReference]:
        return self.child.output + [named_output(e)
                                    for e in self.window_exprs]


# ---------------------------------------------------------------------------
# Analysis rules over a resolved plan (session.plan_physical runs them on
# every statement, before scalar subqueries are materialized; the CPU
# engine and the device rewrite both plan what they return)
# ---------------------------------------------------------------------------

# attributes of plan nodes that hold expressions (or lists of them)
EXPR_ATTRS = ("project_list", "condition", "aggregates", "grouping",
              "order", "window_exprs", "partition_spec", "order_spec",
              "generator", "expressions")


def node_expressions(p: LogicalPlan) -> List[Expression]:
    out: List[Expression] = []
    for attr in EXPR_ATTRS:
        v = getattr(p, attr, None)
        for x in (v if isinstance(v, list) else [v]):
            if isinstance(x, Expression):
                out.append(x)
    return out


def split_conjuncts(e: Expression) -> List[Expression]:
    from spark_rapids_tpu.sql.expressions import And
    if isinstance(e, And):
        return split_conjuncts(e.left) + split_conjuncts(e.right)
    return [e]


def _conjunction(parts: List[Expression]) -> Optional[Expression]:
    from spark_rapids_tpu.sql.expressions import And
    out = None
    for c in parts:
        out = c if out is None else And(out, c)
    return out


def _ids(attrs) -> set:
    return {a.expr_id for a in attrs}


def _is_comma_join(p: LogicalPlan) -> bool:
    return isinstance(p, Join) and p.join_type == "cross" \
        and p.condition is None


def _has_in_subquery(e: Expression) -> bool:
    from spark_rapids_tpu.sql.expressions import InSubquery
    return bool(e.collect(lambda x: isinstance(x, InSubquery)))


def needs_rewrite(p: LogicalPlan) -> bool:
    """Whether ``rewrite_joins_and_subqueries`` has anything to do: the
    one walk a statement with neither form pays."""
    if isinstance(p, Filter) and _is_comma_join(p.child):
        return True
    if any(_has_in_subquery(e) for e in node_expressions(p)):
        return True
    return any(needs_rewrite(c) for c in p.children)


def rewrite_joins_and_subqueries(plan: LogicalPlan) -> LogicalPlan:
    """Two rules, copy-on-write (a DataFrame's plan is planned again by
    every action):

    - a ``WHERE`` over a comma list of relations (cross joins without a
      condition) becomes inner joins: conjuncts that read one relation
      are pushed to it, and the relations join in the text's order, each
      next one the first that a remaining conjunct connects to those
      joined so far, with those conjuncts as its condition; a relation
      nothing connects stays a cross join (Catalyst's ReorderJoin and
      PushPredicateThroughJoin). No cost decides anything.
    - an uncorrelated ``IN (subquery)`` that is a conjunct of a filter
      becomes a left semi join (RewritePredicateSubquery) on the side of
      the inner joins below that its value reads, so the joins above see
      the rows it keeps. ``NOT IN (subquery)`` needs Spark's null-aware
      anti join and an ``IN (subquery)`` anywhere else an existence
      join: both raise NotImplementedError by name.

    Returns ``plan`` itself when neither has anything to do."""
    import copy
    from spark_rapids_tpu.sql.expressions import InSubquery, Not
    p = plan
    new_children = [rewrite_joins_and_subqueries(c) for c in p.children]
    if new_children != p.children:
        p = copy.copy(p)
        p.children = new_children
    if isinstance(p, Filter):
        if _is_comma_join(p.child):
            p = _join_comma_list(p)
        if isinstance(p, Filter):
            p = _in_subqueries_to_semi_joins(p)
    for e in node_expressions(p):
        for x in e.collect(lambda x: isinstance(x, InSubquery)):
            neg = e.collect(lambda n: isinstance(n, Not)
                            and n.children[0] is x)
            raise NotImplementedError(
                "NOT IN (subquery) is not supported: it needs Spark's "
                "null-aware anti join" if neg else
                "IN (subquery) is supported only as a conjunct of WHERE "
                f"or HAVING, not inside {e!r}")
    return p


def _join_comma_list(f: Filter) -> LogicalPlan:
    rels: List[LogicalPlan] = []
    node = f.child
    while _is_comma_join(node):
        rels.append(node.right)
        node = node.left
    rels.append(node)
    rels.reverse()
    rel_ids = [_ids(r.output) for r in rels]
    pushed: List[List[Expression]] = [[] for _ in rels]
    rest: List[tuple] = []          # (conjunct, ids it reads)
    for c in split_conjuncts(f.condition):
        refs = _ids(c.references())
        home = [i for i, ids in enumerate(rel_ids) if refs & ids]
        if len(home) == 1 and refs <= rel_ids[home[0]]:
            pushed[home[0]].append(c)
        else:
            rest.append((c, refs))
    for i, conds in enumerate(pushed):
        if conds:
            rels[i] = rewrite_joins_and_subqueries(
                Filter(_conjunction(conds), rels[i]))
    joined, joined_ids = rels[0], set(rel_ids[0])
    left = list(range(1, len(rels)))
    while left:
        pick, conds = left[0], []
        for i in left:
            conds = [c for c, refs in rest
                     if refs <= joined_ids | rel_ids[i]
                     and refs & rel_ids[i] and refs & joined_ids]
            if conds:
                pick = i
                break
        left.remove(pick)
        rest = [(c, refs) for c, refs in rest
                if not any(c is d for d in conds)]
        joined = Join(joined, rels[pick], "inner" if conds else "cross",
                      _conjunction(conds))
        joined_ids |= rel_ids[pick]
    if [a.expr_id for a in joined.output] != \
            [a.expr_id for a in f.child.output]:
        joined = Project(list(f.child.output), joined)
    above = _conjunction([c for c, _refs in rest])
    return Filter(above, joined) if above is not None else joined


def _in_subqueries_to_semi_joins(f: Filter) -> LogicalPlan:
    from spark_rapids_tpu.sql.dataframe import _coerce_resolved
    from spark_rapids_tpu.sql.expressions import EqualTo, InSubquery
    child, rest = f.child, []
    for c in split_conjuncts(f.condition):
        if not isinstance(c, InSubquery) or _has_in_subquery(c.value):
            rest.append(c)
            continue
        sub = rewrite_joins_and_subqueries(c.plan)
        # the subquery may read a table the outer query reads too (Q18
        # reads lineitem twice): fresh ids keep the two sides apart
        if _ids(sub.output) & _plan_ids(child):
            sub = Project([Alias(a, a.name) for a in sub.output], sub)
        cond = _coerce_resolved(EqualTo(c.value, sub.output[0]))
        child = _semi_join_below(child, _ids(c.value.references()),
                                 sub, cond)
    if child is f.child:
        return f
    return Filter(_conjunction(rest), child) if rest else child


def _plan_ids(p: LogicalPlan) -> set:
    out = _ids(p.output)
    for c in p.children:
        out |= _plan_ids(c)
    return out


def _semi_join_below(p: LogicalPlan, refs: set, sub: LogicalPlan,
                     cond: Expression) -> LogicalPlan:
    """``p LEFT SEMI JOIN sub ON cond``, pushed through the filters and
    inner joins of ``p`` to the side that holds all of ``refs``."""
    import copy
    if refs:
        if isinstance(p, Join) and p.join_type in ("inner", "cross"):
            for i, side in enumerate(p.children):
                if refs <= _ids(side.output):
                    q = copy.copy(p)
                    q.children = list(p.children)
                    q.children[i] = _semi_join_below(side, refs, sub, cond)
                    return q
        elif isinstance(p, Filter) and not _has_in_subquery(p.condition):
            q = copy.copy(p)
            q.children = [_semi_join_below(p.child, refs, sub, cond)]
            return q
    return Join(p, sub, "leftsemi", cond)

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


def _has_subquery_predicate(e: Expression) -> bool:
    from spark_rapids_tpu.sql.expressions import Exists, InSubquery
    return bool(e.collect(lambda x: isinstance(x, (InSubquery, Exists))))


def outer_references(p: LogicalPlan) -> List[AttributeReference]:
    """The enclosing query's attributes that the expressions of ``p``
    read (one an ``expr_id``, in the order met). A nested subquery's own
    outer references belong to the query it stands in and are not walked
    into: its ``Exists`` node holds them as plain attributes."""
    from spark_rapids_tpu.sql.expressions import OuterReference
    out: List[AttributeReference] = []

    def walk(q: LogicalPlan) -> None:
        for e in node_expressions(q):
            for o in e.collect(lambda x: isinstance(x, OuterReference)):
                if o.attr.expr_id not in _ids(out):
                    out.append(o.attr)
        for c in q.children:
            walk(c)
    walk(p)
    return out


def needs_rewrite(p: LogicalPlan) -> bool:
    """Whether ``rewrite_joins_and_subqueries`` has anything to do: the
    one walk a statement with none of its forms pays."""
    if isinstance(p, Filter) and _is_comma_join(p.child):
        return True
    if any(_has_subquery_predicate(e) for e in node_expressions(p)):
        return True
    return any(needs_rewrite(c) for c in p.children)


def rewrite_joins_and_subqueries(plan: LogicalPlan) -> LogicalPlan:
    """Three rules, copy-on-write (a DataFrame's plan is planned again
    by every action):

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
      the rows it keeps.
    - a correlated ``[NOT] EXISTS (subquery)`` that is a conjunct of a
      filter is decorrelated (``_decorrelate``): the conjuncts of the
      subquery's WHERE that read outer columns become the condition of a
      left semi (``NOT EXISTS``: a plain left anti) join, its equalities
      the keys and the rest the residual, placed like the ``IN``'s; the
      filter's other conjuncts stay beneath that join.

    ``NOT IN (subquery)`` needs Spark's null-aware anti join and a
    subquery predicate anywhere but such a conjunct an existence join:
    both raise NotImplementedError by name.

    Returns ``plan`` itself when none has anything to do."""
    import copy
    from spark_rapids_tpu.sql.expressions import Exists, InSubquery, Not
    p = plan
    new_children = [rewrite_joins_and_subqueries(c) for c in p.children]
    if new_children != p.children:
        p = copy.copy(p)
        p.children = new_children
    if isinstance(p, Filter):
        if _is_comma_join(p.child):
            p = _join_comma_list(p)
        if isinstance(p, Filter):
            p = _subqueries_to_semi_joins(p)
    for e in node_expressions(p):
        for x in e.collect(lambda x: isinstance(x, (InSubquery, Exists))):
            if isinstance(x, Exists):
                raise NotImplementedError(
                    "EXISTS (subquery) is supported only as a conjunct "
                    f"of WHERE or HAVING, not inside {e!r}")
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
    above: List[Expression] = []
    for c in split_conjuncts(f.condition):
        refs = _ids(c.references())
        home = [i for i, ids in enumerate(rel_ids) if refs & ids]
        if len(home) == 1 and refs <= rel_ids[home[0]]:
            pushed[home[0]].append(c)
        elif _has_subquery_predicate(c):
            # never a join's condition: it stays a filter over the joins,
            # whence _subqueries_to_semi_joins places it
            above.append(c)
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
    above = _conjunction([c for c, _refs in rest] + above)
    return Filter(above, joined) if above is not None else joined


def _subqueries_to_semi_joins(f: Filter) -> LogicalPlan:
    """The filter's ``IN (subquery)`` and ``[NOT] EXISTS (subquery)``
    conjuncts as left semi and left anti joins over its child, in the
    text's order; the conjuncts without a subquery stay a filter beneath
    them (they read the left side alone, so the joins see fewer rows),
    one with a subquery in any other position a filter above, for
    ``rewrite_joins_and_subqueries`` to refuse by name."""
    from spark_rapids_tpu import metrics as M
    from spark_rapids_tpu import trace as TR
    from spark_rapids_tpu.sql.dataframe import _coerce_resolved
    from spark_rapids_tpu.sql.expressions import (EqualTo, Exists,
                                                  InSubquery, Not)
    plain, refused, joins = [], [], []
    for c in split_conjuncts(f.condition):
        x = c.children[0] if isinstance(c, Not) else c
        if isinstance(x, Exists) or (x is c and isinstance(x, InSubquery)
                                     and not _has_subquery_predicate(
                                         x.value)):
            joins.append(("leftanti" if x is not c else "leftsemi", x))
        elif _has_subquery_predicate(c):
            refused.append(c)
        else:
            plain.append(c)
    if not joins:
        return f
    child = Filter(_conjunction(plain), f.child) if plain else f.child
    for join_type, x in joins:
        if isinstance(x, InSubquery):
            sub = rewrite_joins_and_subqueries(x.plan)
            # the subquery may read a table the outer query reads too
            # (Q18 reads lineitem twice): fresh ids keep the sides apart
            if _ids(sub.output) & _plan_ids(child):
                sub = Project([Alias(a, a.name) for a in sub.output], sub)
            cond = _coerce_resolved(EqualTo(x.value, sub.output[0]))
            refs = _ids(x.value.references())
        else:
            with TR.span("plan", phase="decorrelate"):
                sub, cond = _decorrelate(x)
            M.query_registry().create(M.DECORRELATED_SUBQUERY_COUNT,
                                      M.ESSENTIAL).add(1)
            refs = _ids(x.references())
        child = _semi_join_below(child, refs, sub, cond, join_type)
    return Filter(_conjunction(refused), child) if refused else child


def _decorrelate(x) -> tuple:
    """``EXISTS (subquery)`` -> ``(build side, join condition)``. The
    conjuncts of the subquery's own WHERE that read outer columns are
    lifted into the condition (the planner takes its ``outer = inner``
    equalities as keys and the rest as the residual); the others stay a
    filter beneath the build side, which is cut to the columns the
    condition reads under fresh ids (Q21 reads lineitem three times).
    An outer column anywhere else, under OR or NOT, or with no equality
    to key the join on, is refused by name."""
    from spark_rapids_tpu.sql.expressions import (BinaryComparison, EqualTo,
                                                  Not, Or, OuterReference)

    def is_outer(e: Expression) -> bool:
        return isinstance(e, OuterReference)

    def refuse(what: str):
        raise NotImplementedError(
            f"correlated subquery: {what}; an outer column may be read "
            "only in an AND-conjunct of the WHERE of an [NOT] EXISTS "
            "(subquery), with at least one outer = inner equality")

    node = x.plan
    while isinstance(node, (Project, SubqueryAlias)):
        node = node.child     # an EXISTS reads no column of its select list
    lifted: List[Expression] = []
    build = node
    if isinstance(node, Filter):
        kept = []
        for c in split_conjuncts(node.condition):
            (lifted if c.collect(is_outer) else kept).append(c)
        build = Filter(_conjunction(kept), node.child) if kept \
            else node.child
    stray = outer_references(build)
    if stray:
        refuse(f"outer column {stray[0].name!r} is read beneath the "
               "subquery's WHERE (an aggregate, a HAVING, a join or a "
               "derived table)")
    if not lifted:
        raise NotImplementedError(
            "uncorrelated EXISTS (subquery) is not supported: its WHERE "
            "reads no column of the outer query")

    def inner_side(e: Expression) -> Expression:
        return e.transform(lambda n: Literal(None) if is_outer(n) else None)

    def keys_the_join(c: Expression) -> bool:
        if not isinstance(c, EqualTo):
            return False
        # (reads outer columns, reads inner columns) of each side
        sides = sorted((bool(k.collect(is_outer)),
                        bool(inner_side(k).references()))
                       for k in c.children)
        return sides == [(False, True), (True, False)]

    inner: List[AttributeReference] = []
    for c in lifted:
        # `<>` parses to Not(EqualTo): a NOT over one comparison is plain
        if c.collect(lambda e: e.collect(is_outer) and (
                isinstance(e, Or) or isinstance(e, Not) and not
                isinstance(e.children[0], BinaryComparison))):
            refuse(f"an outer column under OR or NOT in {c!r}")
        if _has_subquery_predicate(c):
            refuse(f"a nested subquery beside an outer column in {c!r}")
        for a in inner_side(c).references():
            if a.expr_id not in _ids(inner):
                inner.append(a)
    if not any(keys_the_join(c) for c in lifted):
        refuse(f"no outer = inner equality among {lifted!r} (a "
               "nested-loop semi join)")
    fresh = [Alias(a, a.name) for a in inner]
    to_fresh = {a.expr_id: f.to_attribute() for a, f in zip(inner, fresh)}

    def lift(e: Expression) -> Expression:
        # top-down, so that an OuterReference's own attribute is never
        # taken for the inner side's (a self-join shares expr_ids)
        if is_outer(e):
            return e.attr
        if isinstance(e, AttributeReference):
            return to_fresh[e.expr_id]
        kids = [lift(k) for k in e.children]
        if all(k is o for k, o in zip(kids, e.children)):
            return e
        return e.with_children(kids)

    cond = _conjunction([lift(c) for c in lifted])
    return Project(fresh, rewrite_joins_and_subqueries(build)), cond


def _plan_ids(p: LogicalPlan) -> set:
    out = _ids(p.output)
    for c in p.children:
        out |= _plan_ids(c)
    return out


def _accepts_semi_join(p: LogicalPlan, refs: set) -> bool:
    """Whether an inner join beneath ``p``'s filters has a side that
    holds all of ``refs``."""
    if isinstance(p, Filter) and not _has_subquery_predicate(p.condition):
        return _accepts_semi_join(p.child, refs)
    return isinstance(p, Join) and p.join_type in ("inner", "cross") \
        and any(refs <= _ids(side.output) for side in p.children)


def _semi_join_below(p: LogicalPlan, refs: set, sub: LogicalPlan,
                     cond: Expression, join_type: str) -> LogicalPlan:
    """``p LEFT SEMI`` (or ``ANTI``) ``JOIN sub ON cond``, pushed through
    the inner joins of ``p``, and the filters over them, to the side
    that holds all of ``refs`` (Catalyst's
    PushLeftSemiLeftAntiThroughJoin); over a filter with no such join
    beneath it the join stays above."""
    import copy
    if refs and _accepts_semi_join(p, refs):
        q = copy.copy(p)
        q.children = list(p.children)
        if isinstance(p, Filter):
            q.children[0] = _semi_join_below(p.child, refs, sub, cond,
                                             join_type)
            return q
        for i, side in enumerate(p.children):
            if refs <= _ids(side.output):
                q.children[i] = _semi_join_below(side, refs, sub, cond,
                                                 join_type)
                return q
    return Join(p, sub, join_type, cond)

"""SQL planner: typed AST lowering straight to physical plans.

Reference: presto-main sql/analyzer/* (StatementAnalyzer/ExpressionAnalyzer
name+type resolution) + sql/planner/* (RelationPlanner/QueryPlanner building
the PlanNode tree, then PlanOptimizers). Because our plan space is narrower,
the passes the reference runs separately are folded into one lowering:

  - predicate pushdown: WHERE conjuncts referencing one relation filter that
    relation's scan directly (reference: optimizations/PredicatePushDown);
  - column pruning: scans read only referenced columns (reference:
    PruneUnreferencedOutputs);
  - join-key extraction + join ordering: equality conjuncts become hash-join
    edges; a greedy left-deep tree keeps the largest relation as probe side
    and joins the smallest connected relation next (reference: AddExchanges'
    distribution choice + join reordering, heuristic here);
  - OR factoring: conjuncts common to every OR branch are hoisted so queries
    like TPC-H Q19 still get their join keys;
  - subquery decorrelation (reference: sql/planner/SubqueryPlanner +
    TransformCorrelated* rules):
      * uncorrelated scalar -> eager execution, result inlined as a literal
      * correlated scalar aggregate -> group-by over correlation keys joined
        back to the outer side (Q2/Q17/Q20)
      * [NOT] IN / equality-correlated [NOT] EXISTS -> semi/anti join
      * EXISTS with extra correlated predicates -> unique-id join +
        distinct + semi join (general fallback; Q21)

Divergence note: long-decimal (p>18) aggregate results are cast to DOUBLE
when consumed by further expressions (the reference does exact decimal(38)
arithmetic; our exactness boundary is the 2^53 mantissa — far above TPC-H
group sums at validated scales).
"""

from __future__ import annotations

import dataclasses
import datetime
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

from presto_tpu import types as T
from presto_tpu.exec import agg_states as AS
from presto_tpu.exec import plan as P
from presto_tpu.expr import ir
from presto_tpu.expr import functions as F
from presto_tpu.expr.fold import fold_constants
from presto_tpu.ops.sort import SortKey
from presto_tpu.sql import ast_nodes as N

AGG_FUNCTIONS = {"sum", "count", "avg", "min", "max", "any_value",
                 "bool_or", "bool_and",
                 "stddev", "stddev_samp", "stddev_pop",
                 "variance", "var_samp", "var_pop",
                 "approx_distinct",
                 "array_agg", "map_agg", "approx_percentile"}

# SQL-surface aliases -> agg_states layout names (reference:
# FunctionRegistry registers stddev as an alias of stddev_samp)
_AGG_CANON = {"stddev": "stddev_samp", "variance": "var_samp",
              "any_value": "any"}


def _canon_agg(name: str) -> str:
    return _AGG_CANON.get(name, name)


def _is_agg_name(name: str) -> bool:
    """Builtin aggregates plus plugin-registered ones (reference:
    FunctionRegistry resolution spanning builtins and plugins)."""
    return name in AGG_FUNCTIONS or AS.is_plugin_aggregate(name)


def _extract_unnests(item: N.Node):
    """Peel UNNEST relations off a FROM item: returns (base relation or
    None, [(UnnestRelation, column_aliases), ...])."""
    if isinstance(item, N.UnnestRelation):
        return None, [(item, ())]
    if isinstance(item, N.AliasedRelation) and isinstance(
        item.relation, N.UnnestRelation
    ):
        return None, [(item.relation, tuple(item.column_aliases))]
    if isinstance(item, N.JoinRelation) and item.join_type == "cross":
        rbase, runs = _extract_unnests(item.right)
        if rbase is None and runs:
            lbase, lruns = _extract_unnests(item.left)
            return lbase, lruns + runs
    return item, []

_EPOCH = datetime.date(1970, 1, 1)


class PlanningError(ValueError):
    pass


class UnresolvedColumnError(PlanningError):
    """A column name resolved in no scope. Distinguished from other planning
    failures so correlation detection (_is_correlated) keys on *this* error
    only — an uncorrelated subquery using an unsupported feature must surface
    its real error, not be misrouted into the correlated decorrelator."""

    def __init__(self, ident):
        super().__init__(f"column not found: {'.'.join(ident.parts)}")
        self.ident = ident


@dataclasses.dataclass(frozen=True)
class OuterRef(ir.RowExpression):
    """Planning-only placeholder for a correlated column (resolved in an
    enclosing scope). Never reaches the evaluator."""

    channel: int
    type: T.SqlType

    def __repr__(self):
        return f"outer#{self.channel}:{self.type}"


@dataclasses.dataclass(frozen=True)
class Field:
    name: Optional[str]
    type: T.SqlType
    qualifiers: frozenset = frozenset()


@dataclasses.dataclass
class RelationPlan:
    node: P.PhysicalNode
    fields: List[Field]

    @property
    def channels(self) -> int:
        return len(self.fields)


class Scope:
    def __init__(self, fields: List[Field], parent: Optional["Scope"] = None):
        self.fields = fields
        self.parent = parent

    def resolve(self, ident: N.Identifier) -> Tuple[int, int, Field]:
        """Returns (level, channel, field); level 0 = this scope."""
        matches = []
        for ch, f in enumerate(self.fields):
            if f.name != ident.name:
                continue
            if ident.qualifier and ident.qualifier not in f.qualifiers:
                continue
            matches.append((ch, f))
        if len(matches) > 1:
            raise PlanningError(f"ambiguous column: {'.'.join(ident.parts)}")
        if matches:
            return 0, matches[0][0], matches[0][1]
        if self.parent is not None:
            lvl, ch, f = self.parent.resolve(ident)
            return lvl + 1, ch, f
        raise UnresolvedColumnError(ident)

    def can_resolve(self, ident: N.Identifier) -> bool:
        try:
            self.resolve(ident)
            return True
        except PlanningError:
            return False


# --------------------------------------------------------------- utilities

def split_conjuncts(e: Optional[N.Node]) -> List[N.Node]:
    if e is None:
        return []
    if isinstance(e, N.BinaryOp) and e.op == "and":
        return split_conjuncts(e.left) + split_conjuncts(e.right)
    return [e]


def split_disjuncts(e: N.Node) -> List[N.Node]:
    if isinstance(e, N.BinaryOp) and e.op == "or":
        return split_disjuncts(e.left) + split_disjuncts(e.right)
    return [e]


def hoist_or_conjuncts(conjuncts: List[N.Node]) -> List[N.Node]:
    """Factor conjuncts common to all OR branches out of the OR (gives Q19
    its p_partkey = l_partkey join key)."""
    out: List[N.Node] = []
    for c in conjuncts:
        branches = split_disjuncts(c)
        if len(branches) < 2:
            out.append(c)
            continue
        branch_sets = [split_conjuncts(b) for b in branches]
        common = [x for x in branch_sets[0]
                  if all(x in bs for bs in branch_sets[1:])]
        if not common:
            out.append(c)
            continue
        out.extend(common)
        rests = []
        for bs in branch_sets:
            rest = [x for x in bs if x not in common]
            rests.append(_and_all(rest))
        residual = _or_all([r for r in rests if r is not None])
        if any(r is None for r in rests):
            residual = None  # one branch fully covered => OR is implied
        if residual is not None:
            out.append(residual)
    return out


def _and_all(items: List[N.Node]) -> Optional[N.Node]:
    if not items:
        return None
    e = items[0]
    for x in items[1:]:
        e = N.BinaryOp("and", e, x)
    return e


def _or_all(items: List[N.Node]) -> Optional[N.Node]:
    if not items:
        return None
    e = items[0]
    for x in items[1:]:
        e = N.BinaryOp("or", e, x)
    return e


def expr_refs(e: ir.RowExpression) -> Set[int]:
    out: Set[int] = set()

    def walk(x):
        if isinstance(x, ir.InputRef):
            out.add(x.channel)
        for c in x.children():
            walk(c)

    walk(e)
    return out


def has_outer_refs(e: ir.RowExpression) -> bool:
    if isinstance(e, OuterRef):
        return True
    return any(has_outer_refs(c) for c in e.children())


def remap_expr(e: ir.RowExpression, mapping: Callable[[int], int]):
    if isinstance(e, ir.InputRef):
        return ir.InputRef(mapping(e.channel), e.type)
    if isinstance(e, OuterRef):
        return e
    if isinstance(e, ir.Call):
        return ir.Call(e.name, tuple(remap_expr(a, mapping) for a in e.args),
                       e.type)
    if isinstance(e, ir.SpecialForm):
        return ir.SpecialForm(
            e.form, tuple(remap_expr(a, mapping) for a in e.args), e.type
        )
    return e


def outer_to_input(e: ir.RowExpression, offset_outer: int, offset_inner: int):
    """Rewrite a correlated predicate for a joined (outer ++ inner) layout."""
    if isinstance(e, OuterRef):
        return ir.InputRef(e.channel + offset_outer, e.type)
    if isinstance(e, ir.InputRef):
        return ir.InputRef(e.channel + offset_inner, e.type)
    if isinstance(e, ir.Call):
        return ir.Call(
            e.name,
            tuple(outer_to_input(a, offset_outer, offset_inner)
                  for a in e.args),
            e.type,
        )
    if isinstance(e, ir.SpecialForm):
        return ir.SpecialForm(
            e.form,
            tuple(outer_to_input(a, offset_outer, offset_inner)
                  for a in e.args),
            e.type,
        )
    return e


def _agg_capacity(node: P.PhysicalNode, catalogs) -> int:
    """Static group-capacity estimate for an aggregation input (reference:
    the pre-CBO source-size heuristics): distinct groups <= input rows,
    clamped to a sane ceiling. Avoids overflow-retry re-runs on
    high-cardinality keys (GROUP BY orderkey) while keeping small
    aggregations small."""
    from presto_tpu.dist.fragmenter import est_rows

    try:
        est = est_rows(node, catalogs)
    except Exception:  # noqa: BLE001 - estimation must never fail
        est = 1 << 16  # planning; unknown shapes get a default
    return max(4096, min(int(est), 1 << 22))


def find_windows(e: N.Node) -> List[N.FunctionCall]:
    """Windowed function calls (fn(...) OVER ...) in an expression, not
    crossing subquery boundaries."""
    out: List[N.FunctionCall] = []

    def walk(x):
        if isinstance(x, N.Query):
            return
        if isinstance(x, N.FunctionCall) and x.window is not None:
            out.append(x)
            return
        for f in (
            dataclasses.fields(x) if dataclasses.is_dataclass(x) else []
        ):
            v = getattr(x, f.name)
            if isinstance(v, N.Node):
                walk(v)
            elif isinstance(v, tuple):
                for item in v:
                    if isinstance(item, N.Node):
                        walk(item)

    walk(e)
    return out


def find_aggregates(e: N.Node) -> List[N.FunctionCall]:
    """Aggregate calls in an AST expression (not nested in another agg and
    not inside a subquery — those belong to the inner SELECT)."""
    out: List[N.FunctionCall] = []

    def walk(x):
        if isinstance(x, N.Query):
            return  # subquery boundary: its aggregates are its own
        if isinstance(x, N.FunctionCall) and x.window is not None:
            # windowed call: not an aggregate; its args/spec may still
            # contain real aggregates (rank() over (order by sum(x)))
            for a in x.args:
                walk(a)
            for p in x.window.partition_by:
                walk(p)
            for o in x.window.order_by:
                walk(o.expr)
            return
        if isinstance(x, N.FunctionCall) and (
            _is_agg_name(x.name) or x.is_star
        ):
            out.append(x)
            return
        for f in dataclasses.fields(x) if dataclasses.is_dataclass(x) else []:
            v = getattr(x, f.name)
            if isinstance(v, N.Node):
                walk(v)
            elif isinstance(v, tuple):
                for item in v:
                    if isinstance(item, N.Node):
                        walk(item)
                    elif (isinstance(item, tuple) and len(item) == 2
                          and isinstance(item[0], N.Node)):
                        walk(item[0])
                        walk(item[1])

    walk(e)
    return out


_BINOP_FN = {
    "+": "add", "-": "subtract", "*": "multiply", "/": "divide",
    "%": "modulus", "=": "eq", "<>": "ne", "<": "lt", "<=": "le",
    ">": "gt", ">=": "ge",
}


# ----------------------------------------------------------------- planner


class Planner:
    """One instance per statement (reference: LogicalPlanner +
    LocalExecutionPlanner collapsed; symbol allocation is implicit in
    channel layouts)."""

    def __init__(
        self,
        catalogs: Dict[str, object],
        default_catalog: str = "tpch",
        scalar_executor: Optional[Callable[[P.PhysicalNode], list]] = None,
        scalar_cache: Optional[Dict] = None,
        views: Optional[Dict] = None,
    ):
        self.catalogs = catalogs
        self.default_catalog = default_catalog
        self.scalar_executor = scalar_executor
        self.ctes: Dict[str, RelationPlan] = {}
        # (catalog, name) -> view SQL text, expanded at analysis like the
        # reference (Analyzer view expansion over sql/tree/CreateView)
        self.views: Dict = views if views is not None else {}
        self._expanding_views: set = set()
        # memoizes executed scalar subqueries per Query node so correlation
        # probes and repeated translation don't re-run them
        self.scalar_cache: Dict = scalar_cache if scalar_cache is not None \
            else {}
        # Call nodes the statement's expressions lost to constant
        # folding (expr/fold.py): the plan span's constants_folded
        self.constants_folded = 0

    # --------------------------------------------------------- statements
    def plan_statement(self, stmt: N.Node) -> P.Output:
        if isinstance(stmt, N.Explain):
            raise PlanningError("EXPLAIN is handled by the runner")
        if not isinstance(stmt, N.Query):
            raise PlanningError(f"unsupported statement: {type(stmt)}")
        rp, names = self.plan_query_named(stmt, None)
        return P.Output(rp.node, tuple(names))

    def plan_query_named(self, q: N.Query, outer: Optional[Scope]):
        rp = self.plan_query(q, outer)
        names = [f.name or f"_col{i}" for i, f in enumerate(rp.fields)]
        return rp, names

    def plan_query(self, q: N.Query, outer: Optional[Scope]) -> RelationPlan:
        saved = dict(self.ctes)
        try:
            for w in q.withs:
                sub = self.plan_query(w.query, outer)
                fields = sub.fields
                if w.column_names:
                    if len(w.column_names) != len(fields):
                        raise PlanningError(
                            f"WITH {w.name}: column alias count mismatch"
                        )
                    fields = [
                        Field(nm, f.type, frozenset({w.name}))
                        for nm, f in zip(w.column_names, fields)
                    ]
                else:
                    fields = [
                        Field(f.name, f.type, frozenset({w.name}))
                        for f in fields
                    ]
                self.ctes[w.name] = RelationPlan(sub.node, fields)
            body = q.body
            if isinstance(body, N.QuerySpec):
                rp = self.plan_query_spec(body, outer)
            elif isinstance(body, N.SetOp):
                rp = self.plan_set_op(body, outer)
            elif isinstance(body, N.Query):
                rp = self.plan_query(body, outer)
            else:
                raise PlanningError(f"unsupported query body: {type(body)}")
            if q.order_by:
                keys = self._order_keys(q.order_by, rp)
                if q.limit is not None and not q.offset:
                    rp = RelationPlan(P.TopN(rp.node, keys, q.limit),
                                      rp.fields)
                else:
                    rp = RelationPlan(P.Sort(rp.node, keys), rp.fields)
                    if q.limit is not None:
                        rp = RelationPlan(
                            P.Limit(rp.node, q.limit, q.offset), rp.fields
                        )
            elif q.limit is not None:
                rp = RelationPlan(P.Limit(rp.node, q.limit, q.offset),
                                  rp.fields)
            return rp
        finally:
            self.ctes = saved

    def plan_set_op(self, s: N.SetOp, outer: Optional[Scope]) -> RelationPlan:
        left = self._plan_term(s.left, outer)
        right = self._plan_term(s.right, outer)
        if left.channels != right.channels:
            raise PlanningError("set operation column count mismatch")
        if s.op in ("union_all", "union"):
            # coerce branches to common column types (reference: the
            # analyzer's setop type coercion)
            common = []
            for lf, rf in zip(left.fields, right.fields):
                ct = T.common_super_type(lf.type, rf.type)
                if ct is None:
                    raise PlanningError(
                        f"UNION column types incompatible: {lf.type} vs "
                        f"{rf.type}"
                    )
                common.append(ct)

            def coerce(rp: RelationPlan) -> RelationPlan:
                if all(f.type == c for f, c in zip(rp.fields, common)):
                    return rp
                exprs = tuple(
                    ir.InputRef(i, f.type) if f.type == c
                    else ir.cast(ir.InputRef(i, f.type), c)
                    for i, (f, c) in enumerate(zip(rp.fields, common))
                )
                return RelationPlan(
                    P.Project(rp.node, exprs),
                    [Field(f.name, c, f.qualifiers)
                     for f, c in zip(rp.fields, common)],
                )

            left = coerce(left)
            right = coerce(right)
            node = P.Union((left.node, right.node))
            rp = RelationPlan(node, left.fields)
            if s.op == "union":
                rp = RelationPlan(
                    P.Aggregation(
                        rp.node, tuple(range(rp.channels)), (),
                        capacity=_agg_capacity(rp.node, self.catalogs),
                    ),
                    rp.fields,
                )
            return rp
        raise PlanningError(f"unsupported set operation: {s.op}")

    def _plan_term(self, t: N.Node, outer):
        if isinstance(t, N.QuerySpec):
            return self.plan_query_spec(t, outer)
        if isinstance(t, N.Query):
            return self.plan_query(t, outer)
        if isinstance(t, N.SetOp):
            return self.plan_set_op(t, outer)
        raise PlanningError(f"unsupported set operand: {type(t)}")

    # ---------------------------------------------------------- relations
    def plan_relation(self, rel: N.Node, outer: Optional[Scope]):
        if isinstance(rel, N.Table):
            return self._plan_table(rel)
        if isinstance(rel, N.AliasedRelation):
            rp = self.plan_relation(rel.relation, outer)
            names = (
                list(rel.column_aliases)
                if rel.column_aliases
                else [f.name for f in rp.fields]
            )
            if len(names) != len(rp.fields):
                raise PlanningError("column alias count mismatch")
            fields = [
                Field(nm, f.type, frozenset({rel.alias}))
                for nm, f in zip(names, rp.fields)
            ]
            return RelationPlan(rp.node, fields)
        if isinstance(rel, N.SubqueryRelation):
            rp, names = self.plan_query_named(rel.query, outer)
            fields = [
                Field(nm, f.type, frozenset())
                for nm, f in zip(names, rp.fields)
            ]
            return RelationPlan(rp.node, fields)
        if isinstance(rel, N.JoinRelation):
            return self._plan_explicit_join(rel, outer)
        raise PlanningError(f"unsupported relation: {type(rel)}")

    def _plan_table(self, rel: N.Table) -> RelationPlan:
        parts = rel.parts
        name = parts[-1]
        if len(parts) == 1 and name in self.ctes:
            cte = self.ctes[name]
            return RelationPlan(cte.node, list(cte.fields))
        catalog = self.default_catalog
        if len(parts) >= 2 and parts[0] in self.catalogs:
            catalog = parts[0]
        view_sql = self.views.get((catalog, name))
        if view_sql is not None:
            return self._expand_view(catalog, name, view_sql)
        conn = self.catalogs.get(catalog)
        if conn is None:
            raise PlanningError(f"unknown catalog: {catalog}")
        try:
            schema = conn.table_schema(name)
        except KeyError:
            raise PlanningError(f"table not found: {name}")
        cols = tuple(schema.column_names())
        fields = [
            Field(c.name, c.type, frozenset({name}))
            for c in schema.columns
        ]
        return RelationPlan(P.TableScan(catalog, name, cols), fields)

    def _expand_view(self, catalog: str, name: str,
                     view_sql: str) -> RelationPlan:
        """Reference: StatementAnalyzer view expansion — the stored SQL
        re-analyzes against current metadata; cycles are an error. The
        view body must not see the referencing query's CTEs."""
        from presto_tpu.sql.parser import parse as _parse

        key = (catalog, name)
        if key in self._expanding_views:
            raise PlanningError(f"view cycle detected at {name!r}")
        self._expanding_views.add(key)
        saved_ctes = self.ctes
        self.ctes = {}
        try:
            q = _parse(view_sql)
            rp, names = self.plan_query_named(q, None)
        finally:
            self.ctes = saved_ctes
            self._expanding_views.discard(key)
        fields = [
            Field(nm, f.type, frozenset({name}))
            for nm, f in zip(names, rp.fields)
        ]
        return RelationPlan(rp.node, fields)

    def _plan_explicit_join(self, rel: N.JoinRelation, outer):
        left = self.plan_relation(rel.left, outer)
        right = self.plan_relation(rel.right, outer)
        if rel.join_type == "cross":
            return RelationPlan(
                P.CrossJoin(left.node, right.node), left.fields + right.fields
            )
        on = rel.on
        scope = Scope(left.fields + right.fields, outer)
        nleft = left.channels
        if isinstance(on, tuple) and on[0] == "using":
            return self._plan_using_join(rel, left, right, on[1])
        conjuncts = split_conjuncts(on)
        tr = ExprTranslator(self, scope)
        left_keys: List[int] = []
        right_keys: List[int] = []
        left_filters: List[ir.RowExpression] = []
        right_filters: List[ir.RowExpression] = []
        residual: List[ir.RowExpression] = []
        for c in conjuncts:
            e = tr.translate(c)
            refs = expr_refs(e)
            if (
                isinstance(e, ir.Call) and e.name == "eq"
                and isinstance(e.args[0], ir.InputRef)
                and isinstance(e.args[1], ir.InputRef)
            ):
                a, b = e.args[0].channel, e.args[1].channel
                if a < nleft <= b:
                    left_keys.append(a)
                    right_keys.append(b - nleft)
                    continue
                if b < nleft <= a:
                    left_keys.append(b)
                    right_keys.append(a - nleft)
                    continue
            if refs and max(refs) < nleft:
                left_filters.append(e)
                continue
            if refs and min(refs) >= nleft:
                right_filters.append(
                    remap_expr(e, lambda ch: ch - nleft)
                )
                continue
            residual.append(e)
        jt = rel.join_type
        # single-side ON filters: for outer joins they scope the *join*,
        # not the preserved side; pushing into the non-preserved side is
        # equivalent (reference: PredicatePushDown's outer join handling)
        if left_filters:
            if jt in ("inner", "right"):
                left = RelationPlan(
                    P.Filter(left.node, _and_ir(left_filters)), left.fields
                )
            else:
                raise PlanningError(
                    "ON predicate over the preserved side of an outer join "
                    "is not supported yet"
                )
        if right_filters:
            if jt in ("inner", "left"):
                right = RelationPlan(
                    P.Filter(right.node, _and_ir(right_filters)), right.fields
                )
            else:
                raise PlanningError(
                    "ON predicate over the preserved side of an outer join "
                    "is not supported yet"
                )
        if not left_keys:
            if jt != "inner":
                raise PlanningError("outer join requires equi-join keys")
            node: P.PhysicalNode = P.CrossJoin(left.node, right.node)
        else:
            node = P.HashJoin(
                left.node, right.node, tuple(left_keys), tuple(right_keys),
                join_type=jt,
            )
        rp = RelationPlan(node, left.fields + right.fields)
        if residual:
            if jt != "inner":
                raise PlanningError(
                    "non-equi ON predicates on outer joins are not "
                    "supported yet"
                )
            rp = RelationPlan(P.Filter(rp.node, _and_ir(residual)), rp.fields)
        return rp

    def _plan_using_join(self, rel, left, right, names) -> RelationPlan:
        """JOIN ... USING (c1, ...): equi-join on same-named columns;
        the output carries ONE copy of each using column (unqualified),
        coalescing the sides for FULL joins, then the remaining columns
        of both sides in order (reference: StatementAnalyzer USING
        output scope rules)."""
        jt = rel.join_type

        def chan(fields, name, side):
            hits = [
                i for i, f in enumerate(fields) if f.name == name
            ]
            if not hits:
                raise PlanningError(
                    f"USING column {name!r} not on the {side} side"
                )
            if len(hits) > 1:
                raise PlanningError(
                    f"USING column {name!r} is ambiguous on the "
                    f"{side} side"
                )
            return hits[0]

        left_keys = tuple(
            chan(left.fields, n, "left") for n in names
        )
        right_keys = tuple(
            chan(right.fields, n, "right") for n in names
        )
        node = P.HashJoin(
            left.node, right.node, left_keys, right_keys, join_type=jt,
        )
        nleft = left.channels
        joined = left.fields + right.fields
        exprs: List[ir.RowExpression] = []
        fields: List[Field] = []
        for n, lk, rk in zip(names, left_keys, right_keys):
            lt = left.fields[lk].type
            rt = right.fields[rk].type
            t = T.common_super_type(lt, rt) or lt
            lref = ir.InputRef(lk, lt)
            rref = ir.InputRef(nleft + rk, rt)
            if jt == "full":
                e = ir.coalesce(lref, rref)
            elif jt == "right":
                e = rref
            else:
                e = lref
            exprs.append(e)
            fields.append(Field(n, t, frozenset()))
        skip_l = set(left_keys)
        skip_r = {nleft + rk for rk in right_keys}
        for i, f in enumerate(joined):
            if i in skip_l or i in skip_r:
                continue
            exprs.append(ir.InputRef(i, f.type))
            fields.append(f)
        return RelationPlan(P.Project(node, tuple(exprs)), fields)

    # ------------------------------------------------------------ costing
    def estimate(self, node: P.PhysicalNode) -> float:
        """Crude cardinality estimate driving join order / side choice
        (reference: the stats calculators feeding AddExchanges; here simple
        selectivity constants)."""
        if isinstance(node, P.TableScan):
            return float(self.catalogs[node.catalog].row_count(node.table))
        if isinstance(node, P.Values):
            return float(len(node.rows))
        if isinstance(node, P.Filter):
            return max(self.estimate(node.source) * 0.33, 1.0)
        if isinstance(node, (P.Project, P.UniqueId, P.Output)):
            return self.estimate(node.children()[0])
        if isinstance(node, P.Aggregation):
            return max(self.estimate(node.source) / 8.0, 1.0)
        if isinstance(node, P.HashJoin):
            if node.join_type in ("semi", "anti"):
                return self.estimate(node.left)
            return max(self.estimate(node.left), self.estimate(node.right))
        if isinstance(node, P.CrossJoin):
            return self.estimate(node.left) * self.estimate(node.right)
        if isinstance(node, P.Union):
            return sum(self.estimate(s) for s in node.sources)
        if isinstance(node, (P.Sort, P.TopN, P.Limit)):
            return self.estimate(node.source)
        return 1000.0

    # ------------------------------------------------- FROM + WHERE engine
    def _plan_from_where(
        self,
        spec: N.QuerySpec,
        outer: Optional[Scope],
        collect_correlation: bool,
    ):
        """Plan FROM relations and WHERE; returns (RelationPlan, corr_eqs,
        residual_correlated) where corr_eqs are (outer_channel,
        local_channel) equality pairs when collect_correlation is set.

        UNNEST items are lateral: they are peeled off the FROM list
        here and applied AFTER the join tree, where their array
        expressions can see every base relation's columns. (WHERE
        conjuncts cannot reference UNNEST outputs in this version —
        filter in an enclosing query.)"""
        base_items: List[N.Node] = []
        unnests: List[Tuple[N.UnnestRelation, tuple]] = []
        for item in spec.from_:
            b, us = _extract_unnests(item)
            if b is not None:
                base_items.append(b)
            unnests.extend(us)
        if not base_items:
            rp = RelationPlan(P.Values((T.BIGINT,), ((0,),)),
                              [Field(None, T.BIGINT)])
            units = [rp]
        else:
            units = [self.plan_relation(r, outer) for r in base_items]

        offsets = []
        total = 0
        all_fields: List[Field] = []
        for u in units:
            offsets.append(total)
            total += u.channels
            all_fields.extend(u.fields)
        scope = Scope(all_fields, outer)
        tr = ExprTranslator(self, scope)

        conjuncts = hoist_or_conjuncts(split_conjuncts(spec.where))

        unit_filters: Dict[int, List[ir.RowExpression]] = {}
        edges: List[Tuple[int, int, int, int]] = []  # (ui, ci, uj, cj)
        post: List[ir.RowExpression] = []
        corr_eqs: List[Tuple[int, int]] = []  # (outer_ch, combined_ch)
        corr_residual: List[ir.RowExpression] = []
        subplans: List[Tuple[str, object]] = []  # deferred subquery preds

        def unit_of(ch: int) -> int:
            for i in range(len(units) - 1, -1, -1):
                if ch >= offsets[i]:
                    return i
            return 0

        for c in conjuncts:
            handled = self._try_subquery_conjunct(c, scope, subplans)
            if handled:
                continue
            e = tr.translate(c)
            if has_outer_refs(e):
                if not collect_correlation:
                    raise PlanningError(
                        "correlated reference outside a subquery"
                    )
                if (
                    isinstance(e, ir.Call) and e.name == "eq"
                    and isinstance(e.args[0], ir.InputRef)
                    and isinstance(e.args[1], OuterRef)
                ):
                    corr_eqs.append((e.args[1].channel, e.args[0].channel))
                elif (
                    isinstance(e, ir.Call) and e.name == "eq"
                    and isinstance(e.args[1], ir.InputRef)
                    and isinstance(e.args[0], OuterRef)
                ):
                    corr_eqs.append((e.args[0].channel, e.args[1].channel))
                else:
                    corr_residual.append(e)
                continue
            refs = expr_refs(e)
            ref_units = {unit_of(ch) for ch in refs}
            if (
                isinstance(e, ir.Call) and e.name == "eq"
                and isinstance(e.args[0], ir.InputRef)
                and isinstance(e.args[1], ir.InputRef)
                and len(ref_units) == 2
            ):
                ui = unit_of(e.args[0].channel)
                uj = unit_of(e.args[1].channel)
                edges.append((
                    ui, e.args[0].channel - offsets[ui],
                    uj, e.args[1].channel - offsets[uj],
                ))
                continue
            if len(ref_units) <= 1:
                u = next(iter(ref_units)) if ref_units else 0
                unit_filters.setdefault(u, []).append(
                    remap_expr(e, lambda ch, o=offsets[u]: ch - o)
                )
                continue
            post.append(e)

        for u, filters in unit_filters.items():
            units[u] = RelationPlan(
                P.Filter(units[u].node, _and_ir(filters)), units[u].fields
            )

        plan, layout = self._build_join_tree(units, edges)

        def final_ch(combined_ch: int) -> int:
            u = unit_of(combined_ch)
            return layout[u] + (combined_ch - offsets[u])

        post = [remap_expr(e, final_ch) for e in post]
        corr_eqs = [(o, final_ch(c)) for o, c in corr_eqs]
        corr_residual = [remap_expr(e, final_ch) for e in corr_residual]

        # deferred subquery predicates (IN / EXISTS / scalar comparisons)
        for kind, payload in subplans:
            plan, extra = self._apply_subquery_pred(
                plan, kind, payload, final_ch
            )
            post.extend(extra)

        if post:
            plan = RelationPlan(
                P.Filter(plan.node, _and_ir(post)), plan.fields
            )

        # lateral UNNEST expansion over the joined relation
        for un, cols in unnests:
            tr2 = ExprTranslator(self, Scope(plan.fields, outer))
            e = tr2.translate(un.expr)
            if not isinstance(e.type, T.ArrayType):
                raise PlanningError(
                    f"UNNEST requires an array-typed expression, got "
                    f"{e.type}"
                )
            ch = self._append_channel(plan, e)
            elem_t = e.type.element
            plan.node = P.Unnest(plan.node, ch, elem_t,
                                 un.with_ordinality)
            plan.fields = plan.fields + [
                Field(cols[0] if cols else None, elem_t)
            ]
            if un.with_ordinality:
                plan.fields = plan.fields + [
                    Field(cols[1] if len(cols) > 1 else "ordinality",
                          T.BIGINT)
                ]
        return plan, corr_eqs, corr_residual

    def _try_subquery_conjunct(self, c: N.Node, scope: Scope,
                               subplans: list) -> bool:
        if isinstance(c, N.Exists):
            subplans.append(("exists", (c.query, c.negated, scope)))
            return True
        if isinstance(c, N.UnaryOp) and c.op == "not" and isinstance(
                c.operand, N.Exists):
            subplans.append(
                ("exists", (c.operand.query, not c.operand.negated, scope))
            )
            return True
        if isinstance(c, N.InSubquery):
            subplans.append(("in", (c.value, c.query, c.negated, scope)))
            return True
        if isinstance(c, N.BinaryOp) and c.op in (
                "=", "<>", "<", "<=", ">", ">="):
            for side, other in ((c.left, c.right), (c.right, c.left)):
                if isinstance(side, N.ScalarSubquery):
                    if self._is_correlated(side.query, scope):
                        subplans.append(
                            ("scalar_corr", (other, c.op, side.query,
                                             side is c.left, scope))
                        )
                        return True
                    return False  # uncorrelated: inline via translator
        return False

    def _is_correlated(self, q: N.Query, scope: Scope) -> bool:
        """Correlated iff planning without an outer scope hits an unresolved
        column that DOES resolve in the outer scope. Any other planning
        failure is a genuine error in the subquery and propagates as-is
        (ADVICE r1: inferring correlation from arbitrary PlanningErrors sent
        unsupported-feature errors into the decorrelator's misleading
        'must be a single aggregate' path)."""
        try:
            self._plan_uncorrelated_probe(q)
            return False
        except UnresolvedColumnError as err:
            if scope is not None and scope.can_resolve(err.ident):
                return True
            raise

    def _plan_uncorrelated_probe(self, q: N.Query):
        # planning without an outer scope raises on correlated refs
        sub = Planner(self.catalogs, self.default_catalog,
                      self.scalar_executor,
                      scalar_cache=self.scalar_cache)
        sub.ctes = dict(self.ctes)
        return sub.plan_query(q, None)

    def _apply_subquery_pred(self, plan: RelationPlan, kind: str, payload,
                             final_ch):
        """Attach a subquery predicate to the built join tree. Channels are
        append-only so previously-translated expressions stay valid."""
        extra: List[ir.RowExpression] = []
        if kind == "in":
            value_ast, query, negated, _scope = payload
            scope = Scope(plan.fields)
            tr = ExprTranslator(self, scope)
            value = tr.translate(value_ast)
            if has_outer_refs(value):
                raise PlanningError("correlated IN value not supported")
            sub = self.plan_query(query, None)
            if sub.channels != 1:
                raise PlanningError("IN subquery must produce one column")
            probe_ch = self._append_channel(plan, value)
            plan = RelationPlan(
                P.HashJoin(plan.node, sub.node, (probe_ch,), (0,),
                           join_type="semi"),
                plan.fields + [Field(None, T.BOOLEAN)],
            )
            match = ir.InputRef(plan.channels - 1, T.BOOLEAN)
            extra.append(ir.not_(match) if negated else match)
            return plan, extra
        if kind == "exists":
            query, negated, _scope = payload
            outer_scope = Scope(plan.fields)
            spec = _query_to_spec(query)
            if spec.group_by or spec.having is not None or any(
                find_aggregates(i.expr)
                for i in spec.select
                if not isinstance(i.expr, N.Star)
            ):
                raise PlanningError(
                    "EXISTS over aggregated/grouped subqueries is not "
                    "supported yet"
                )
            inner, corr_eqs, corr_residual = self._plan_from_where(
                spec, outer_scope, collect_correlation=True
            )
            if not corr_eqs:
                raise PlanningError(
                    "uncorrelated EXISTS not supported yet"
                )
            if not corr_residual:
                outer_keys = tuple(o for o, _ in corr_eqs)
                inner_keys = tuple(i for _, i in corr_eqs)
                plan = RelationPlan(
                    P.HashJoin(plan.node, inner.node, outer_keys, inner_keys,
                               join_type="semi"),
                    plan.fields + [Field(None, T.BOOLEAN)],
                )
                match = ir.InputRef(plan.channels - 1, T.BOOLEAN)
                extra.append(ir.not_(match) if negated else match)
                return plan, extra
            # general fallback (Q21): unique-id join + distinct + semi
            with_id = RelationPlan(
                P.UniqueId(plan.node), plan.fields + [Field(None, T.BIGINT)]
            )
            id_ch = with_id.channels - 1
            n_outer = with_id.channels
            join = P.HashJoin(
                with_id.node, inner.node,
                tuple(o for o, _ in corr_eqs),
                tuple(i for _, i in corr_eqs),
                join_type="inner",
            )
            preds = [
                outer_to_input(e, 0, n_outer) for e in corr_residual
            ]
            filt = P.Filter(join, _and_ir(preds))
            dedup_src = P.Project(filt, (ir.InputRef(id_ch, T.BIGINT),))
            matched_ids = P.Aggregation(
                dedup_src, (0,), (),
                capacity=_agg_capacity(dedup_src, self.catalogs),
            )
            plan = RelationPlan(
                P.HashJoin(with_id.node, matched_ids, (id_ch,), (0,),
                           join_type="semi"),
                with_id.fields + [Field(None, T.BOOLEAN)],
            )
            match = ir.InputRef(plan.channels - 1, T.BOOLEAN)
            extra.append(ir.not_(match) if negated else match)
            return plan, extra
        if kind == "scalar_corr":
            other_ast, op, query, subquery_is_left, _scope = payload
            outer_scope = Scope(plan.fields)
            spec = _query_to_spec(query)
            if len(spec.select) != 1 or spec.group_by or (
                spec.having is not None
            ):
                raise PlanningError(
                    "correlated scalar subquery must be a single aggregate"
                )
            inner_aggs = find_aggregates(spec.select[0].expr)
            if not inner_aggs:
                raise PlanningError(
                    "correlated scalar subquery must be a single aggregate"
                )
            has_count = any(
                a.is_star or a.name == "count" for a in inner_aggs
            )
            is_count = has_count and spec.select[0].expr in inner_aggs
            if has_count and not is_count:
                raise PlanningError(
                    "correlated scalar subquery computing over count() "
                    "is only supported as a bare count"
                )
            inner, corr_eqs, corr_residual = self._plan_from_where(
                spec, outer_scope, collect_correlation=True
            )
            if corr_residual or not corr_eqs:
                raise PlanningError(
                    "correlated scalar subquery needs pure equality "
                    "correlation"
                )
            # aggregate over correlation keys (classic decorrelation)
            inner_scope = Scope(inner.fields)
            sub, _names = self._plan_aggregation_block(
                inner, inner_scope,
                group_irs=[
                    ir.InputRef(i, inner.fields[i].type)
                    for _, i in corr_eqs
                ],
                select_items=[N.SelectItem(spec.select[0].expr, "value")],
                having=None,
                include_keys=True,
            )
            n_keys = len(corr_eqs)
            base = plan.channels
            # LEFT join: outer rows with no group must survive — for count
            # aggregates SQL defines the subquery value as 0 there, and for
            # min/max/sum/avg the NULL comparison filters the row anyway
            plan = RelationPlan(
                P.HashJoin(
                    plan.node, sub.node,
                    tuple(o for o, _ in corr_eqs),
                    tuple(range(n_keys)),
                    join_type="left",
                ),
                plan.fields + sub.fields,
            )
            tr = ExprTranslator(self, Scope(plan.fields))
            other = tr.translate(other_ast)
            value_ref: ir.RowExpression = ir.InputRef(
                base + n_keys, sub.fields[n_keys].type
            )
            value_ref = _decimal_safe(value_ref)
            if is_count:
                value_ref = ir.coalesce(
                    value_ref, ir.Constant(0, value_ref.type)
                )
            a, b = ((value_ref, other) if subquery_is_left
                    else (other, value_ref))
            extra.append(ir.call(_BINOP_FN[op], a, b))
            return plan, extra
        raise PlanningError(f"unknown subquery kind: {kind}")

    def _append_channel(self, plan: RelationPlan,
                        expr: ir.RowExpression) -> int:
        """Append a computed channel via identity projection; mutates plan
        in place and returns the new channel index."""
        exprs = tuple(
            ir.InputRef(i, f.type) for i, f in enumerate(plan.fields)
        ) + (expr,)
        plan.node = P.Project(plan.node, exprs)
        plan.fields = plan.fields + [Field(None, expr.type)]
        return len(plan.fields) - 1

    def _unit_unique_channels(self, unit: RelationPlan) -> frozenset:
        """Channels of a relation plan that provably carry a unique
        column of the underlying scan (shared walker:
        P.scan_column_unique — the same judgment the executor's join
        sizing makes)."""
        return frozenset(
            ch for ch in range(len(unit.fields))
            if P.scan_column_unique(unit.node, ch, self.catalogs)
        )

    def _build_join_tree(self, units: List[RelationPlan], edges):
        """Greedy left-deep join tree: largest unit is the initial probe;
        repeatedly join the best connected unit as build side
        (reference: AddExchanges partitioned-vs-broadcast + join
        reordering, heuristic form).

        "Best" = SAFE joins first — build keys that include a provably
        unique column of the build unit guarantee <=1 match per probe
        row, so the join can never expand the probe — then smallest
        estimated BYTE footprint (exact generator/table row counts x
        static row width, the same stats the memory governor sizes
        buffers with — a narrow-but-long table no longer beats a
        wide-but-short one for the build side). Without the safety
        term, a small-but-non-unique build (TPC-H Q5's customer joined
        on c_nationkey: 25 distinct values) fans out catastrophically
        at scale even though it looks cheapest."""
        from presto_tpu.exec.executor import _row_bytes

        n = len(units)
        if n == 1:
            return units[0], {0: 0}
        est = [
            self.estimate(u.node) * _row_bytes([f.type for f in u.fields])
            for u in units
        ]
        uniq = [self._unit_unique_channels(u) for u in units]
        start = max(range(n), key=lambda i: est[i])
        placed = {start: 0}
        plan = units[start]
        remaining = set(range(n)) - {start}
        while remaining:

            def candidate_keys(u):
                probe_keys, build_keys = [], []
                for ui, ci, uj, cj in edges:
                    if ui in placed and uj == u:
                        probe_keys.append(placed[ui] + ci)
                        build_keys.append(cj)
                    elif uj in placed and ui == u:
                        probe_keys.append(placed[uj] + cj)
                        build_keys.append(ci)
                return probe_keys, build_keys

            connected = [
                u for u in remaining
                if any(
                    (ui in placed and uj == u) or (uj in placed and ui == u)
                    for ui, _, uj, _ in edges
                )
            ]
            if connected:
                u = min(
                    connected,
                    key=lambda i: (
                        not any(
                            k in uniq[i]
                            for k in candidate_keys(i)[1]
                        ),
                        est[i],
                    ),
                )
                probe_keys, build_keys = candidate_keys(u)
                node = P.HashJoin(
                    plan.node, units[u].node,
                    tuple(probe_keys), tuple(build_keys), join_type="inner",
                )
                placed[u] = plan.channels
                plan = RelationPlan(node, plan.fields + units[u].fields)
            else:
                u = min(remaining, key=lambda i: est[i])
                node = P.CrossJoin(plan.node, units[u].node)
                placed[u] = plan.channels
                plan = RelationPlan(node, plan.fields + units[u].fields)
            remaining.remove(u)
        return plan, placed

    # ------------------------------------------------------ spec planning
    def plan_query_spec(self, spec: N.QuerySpec,
                        outer: Optional[Scope]) -> RelationPlan:
        plan, corr_eqs, corr_residual = self._plan_from_where(
            spec, outer, collect_correlation=outer is not None
        )
        if corr_eqs or corr_residual:
            raise PlanningError(
                "correlated subquery in an unsupported position"
            )
        scope = Scope(plan.fields, outer)

        aggs: List[N.FunctionCall] = []
        for item in spec.select:
            if not isinstance(item.expr, N.Star):
                aggs.extend(find_aggregates(item.expr))
        if spec.having is not None:
            aggs.extend(find_aggregates(spec.having))
        for o in spec.order_by:
            aggs.extend(find_aggregates(o.expr))

        windows: List[N.FunctionCall] = []
        for item in spec.select:
            if not isinstance(item.expr, N.Star):
                windows.extend(find_windows(item.expr))
        for o in spec.order_by:
            windows.extend(find_windows(o.expr))
        if windows and (spec.group_by or aggs):
            raise PlanningError(
                "window functions over aggregations in the same query "
                "block are not supported yet; aggregate in a subquery"
            )

        if windows:
            plan, scope, win_subst = self._plan_windows(
                plan, scope, windows
            )
        else:
            win_subst = {}

        if spec.group_by or aggs:
            tr = ExprTranslator(self, scope)
            group_irs = []
            for g in spec.group_by:
                if isinstance(g, N.Literal) and g.kind == "long":
                    item = spec.select[
                        _ordinal(g.value, len(spec.select), "GROUP BY")
                    ]
                    group_irs.append(tr.translate(item.expr))
                else:
                    group_irs.append(tr.translate(g))
            (plan2, names) = self._plan_aggregation_block(
                plan, scope, group_irs, list(spec.select), spec.having,
                grouping_sets=spec.grouping_sets,
            )
            plan = plan2
        else:
            names = []
            exprs = []
            tr = ExprTranslator(self, scope, agg_subst=win_subst)
            out_fields = []
            for item in spec.select:
                if isinstance(item.expr, N.Star):
                    for ch, f in enumerate(plan.fields):
                        if item.expr.qualifier and (
                            item.expr.qualifier not in f.qualifiers
                        ):
                            continue
                        exprs.append(ir.InputRef(ch, f.type))
                        names.append(f.name)
                        out_fields.append(Field(f.name, f.type))
                    continue
                e = tr.translate(item.expr)
                nm = item.alias or (
                    item.expr.name if isinstance(item.expr, N.Identifier)
                    else None
                )
                exprs.append(e)
                names.append(nm)
                out_fields.append(Field(nm, e.type))
            plan = RelationPlan(P.Project(plan.node, tuple(exprs)),
                                out_fields)

        if spec.distinct:
            plan = RelationPlan(
                P.Aggregation(
                    plan.node, tuple(range(plan.channels)), (),
                    capacity=_agg_capacity(plan.node, self.catalogs),
                ),
                plan.fields,
            )

        # ORDER BY / LIMIT are query-level (plan_query) — the parser never
        # attaches them to a QuerySpec
        return plan

    @staticmethod
    def _check_frame(wspec):
        """Validate an explicit window frame (reference:
        sql/analyzer/WindowFrameAnalyzer rules): ROWS frames take any
        bound; RANGE frames only UNBOUNDED/CURRENT (value-range offsets
        need per-type arithmetic the kernels don't do)."""
        frame = wspec.frame
        if frame is None:
            return None
        unit, (sk, _sn), (ek, _en) = frame
        order = {"unbounded_preceding": 0, "preceding": 1, "current": 2,
                 "following": 3, "unbounded_following": 4}
        if sk == "unbounded_following" or ek == "unbounded_preceding":
            raise PlanningError("invalid window frame bounds")
        if order[sk] > order[ek]:
            raise PlanningError(
                "window frame start cannot follow its end"
            )
        if unit == "range" and (
            sk not in ("unbounded_preceding", "current")
            or ek not in ("current", "unbounded_following")
        ):
            raise PlanningError(
                "RANGE frames support only UNBOUNDED/CURRENT bounds"
            )
        return frame

    def _plan_windows(self, plan, scope, windows):
        """Plan windowed calls over the FROM/WHERE result: pre-project the
        partition/order/argument expressions, add one Window node per
        distinct OVER clause, and return a substitution map call->channel
        for the final projection (reference: QueryPlanner.window +
        WindowNode; execution is ops/window.py's segmented scans)."""
        from presto_tpu.ops import window as W
        from presto_tpu.ops.sort import SortKey

        tr = ExprTranslator(self, scope)
        pre_exprs: List[ir.RowExpression] = [
            ir.InputRef(i, f.type) for i, f in enumerate(plan.fields)
        ]

        def chan_for(ast_expr) -> int:
            # long-decimal window inputs compute in double (the module-
            # docstring long-decimal divergence; ops/window has no limb
            # arithmetic)
            e = _decimal_safe(tr.translate(ast_expr))
            if isinstance(e, ir.InputRef):
                return e.channel
            for i, existing in enumerate(pre_exprs):
                if existing == e:
                    return i
            pre_exprs.append(e)
            return len(pre_exprs) - 1

        # group calls by their OVER clause
        by_spec: Dict[object, List[N.FunctionCall]] = {}
        for call in windows:
            by_spec.setdefault(call.window, [])
            if call not in by_spec[call.window]:
                by_spec[call.window].append(call)

        specs = []
        for wspec, calls in by_spec.items():
            part_chs = tuple(chan_for(p) for p in wspec.partition_by)
            order_keys = tuple(
                SortKey(chan_for(o.expr), o.ascending, o.nulls_first)
                for o in wspec.order_by
            )
            frame = self._check_frame(wspec)
            fns = []
            for call in calls:
                fname = call.name
                arg_ch = None
                offset = 1

                def int_literal(node, what):
                    if not (isinstance(node, N.Literal)
                            and node.kind == "long"):
                        raise PlanningError(
                            f"{what} must be an integer literal"
                        )
                    return int(node.value)

                if fname in ("lag", "lead"):
                    if len(call.args) > 2:
                        raise PlanningError(
                            "lag/lead default argument not supported"
                        )
                    arg_ch = chan_for(call.args[0])
                    if len(call.args) == 2:
                        offset = int_literal(call.args[1],
                                             "lag/lead offset")
                elif fname in ("row_number", "rank", "dense_rank",
                               "percent_rank", "cume_dist"):
                    pass
                elif fname == "ntile":
                    if len(call.args) != 1:
                        raise PlanningError("ntile takes one argument")
                    offset = int_literal(call.args[0], "ntile buckets")
                    if offset < 1:
                        raise PlanningError("ntile buckets must be >= 1")
                elif fname == "nth_value":
                    if len(call.args) != 2:
                        raise PlanningError(
                            "nth_value takes two arguments"
                        )
                    arg_ch = chan_for(call.args[0])
                    offset = int_literal(call.args[1], "nth_value n")
                    if offset < 1:
                        raise PlanningError("nth_value n must be >= 1")
                elif fname in ("count",) and (call.is_star or
                                              not call.args):
                    fname = "count_star"
                elif fname in ("sum", "avg", "min", "max", "count",
                               "first_value", "last_value"):
                    arg_ch = chan_for(call.args[0])
                else:
                    raise PlanningError(
                        f"unsupported window function: {fname}"
                    )
                fns.append(W.WindowFunc(fname, arg_ch, offset,
                                        frame=frame))
            specs.append((part_chs, order_keys, tuple(fns), calls))

        node = plan.node
        if len(pre_exprs) > len(plan.fields) or any(
            not isinstance(e, ir.InputRef) or e.channel != i
            for i, e in enumerate(pre_exprs)
        ):
            node = P.Project(node, tuple(pre_exprs))
        pre_fields = list(plan.fields) + [
            Field(None, e.type) for e in pre_exprs[len(plan.fields):]
        ]

        win_subst: Dict[object, ir.RowExpression] = {}
        ch = len(pre_exprs)
        all_fields = list(pre_fields)
        for part_chs, order_keys, fns, calls in specs:
            node = P.Window(node, part_chs, order_keys, fns)
            for fn, call in zip(fns, calls):
                in_t = (
                    None if fn.arg_channel is None
                    else pre_fields[fn.arg_channel].type
                    if fn.arg_channel < len(pre_fields)
                    else pre_exprs[fn.arg_channel].type
                )
                out_t = W.result_type(fn, in_t)
                win_subst[call] = ir.InputRef(ch, out_t)
                all_fields.append(Field(None, out_t))
                ch += 1

        new_plan = RelationPlan(node, all_fields)
        new_scope = Scope(pre_fields, scope.parent)
        return new_plan, new_scope, win_subst

    def _plan_aggregation_block(
        self,
        plan: RelationPlan,
        scope: Scope,
        group_irs: List[ir.RowExpression],
        select_items: List[N.SelectItem],
        having: Optional[N.Node],
        include_keys: bool = False,
        grouping_sets=None,
    ):
        """GROUP BY block: pre-project group keys + agg args, aggregate,
        post-project select expressions with agg calls substituted
        (reference: QueryPlanner.planGroupingOperations + Aggregation
        symbol mapping)."""
        tr = ExprTranslator(self, scope)

        aggs: List[N.FunctionCall] = []
        for item in select_items:
            aggs.extend(find_aggregates(item.expr))
        if having is not None:
            aggs.extend(find_aggregates(having))
        # dedupe structurally
        uniq_aggs: List[N.FunctionCall] = []
        for a in aggs:
            if a not in uniq_aggs:
                uniq_aggs.append(a)

        distinct_aggs = [a for a in uniq_aggs if a.distinct]
        plain_aggs = [a for a in uniq_aggs if not a.distinct]

        # global collect aggregates (array_agg/map_agg/approx_percentile
        # with no GROUP BY) reuse the grouped machinery via a synthetic
        # constant key — the [cap, K] collect state needs the grouped
        # kernels. Divergence: over an EMPTY input this yields zero rows
        # where the reference yields one NULL row.
        if not group_irs and any(
            _canon_agg(a.name) in AS.COLLECT_FNS for a in uniq_aggs
        ):
            group_irs = [ir.Constant(0, T.BIGINT)]

        # pre-projection: group keys then agg arguments
        pre_exprs: List[ir.RowExpression] = list(group_irs)
        agg_arg_ch: List[Optional[int]] = []
        agg_arg_ir: List[Optional[ir.RowExpression]] = []
        agg_extra_ch: List[tuple] = []
        agg_extra_ir: List[tuple] = []
        agg_params: List[tuple] = []

        def _arg_channel(e: ir.RowExpression) -> int:
            idx = None
            if e in pre_exprs:
                i0 = pre_exprs.index(e)
                # under GROUPING SETS an aggregate argument must NOT
                # alias a group-key channel: GroupId nulls absent keys
                # per replica and would null the aggregate's input too
                if grouping_sets is None or i0 >= len(group_irs):
                    idx = i0
            if idx is None:
                pre_exprs.append(e)
                idx = len(pre_exprs) - 1
            return idx

        for a in uniq_aggs:
            if a.is_star or not a.args:
                agg_arg_ch.append(None)
                agg_arg_ir.append(None)
                agg_extra_ch.append(())
                agg_extra_ir.append(())
                agg_params.append(())
                continue
            cname = _canon_agg(a.name)
            e = _decimal_safe(tr.translate(a.args[0]))
            if cname in AS.VARIANCE_FNS and e.type != T.DOUBLE:
                e = ir.cast(e, T.DOUBLE)
            agg_arg_ch.append(_arg_channel(e))
            agg_arg_ir.append(e)
            extras_c: List[int] = []
            extras_e: List[ir.RowExpression] = []
            prms: tuple = ()
            if cname == "map_agg":
                if len(a.args) != 2:
                    raise PlanningError("map_agg takes (key, value)")
                e2 = _decimal_safe(tr.translate(a.args[1]))
                extras_c.append(_arg_channel(e2))
                extras_e.append(e2)
            elif cname == "approx_percentile":
                if len(a.args) != 2:
                    raise PlanningError(
                        "approx_percentile takes (value, fraction)"
                    )
                pe = tr.translate(a.args[1])
                if not isinstance(pe, ir.Constant) or pe.value is None:
                    raise PlanningError(
                        "approx_percentile fraction must be a constant"
                    )
                frac = pe.value
                if isinstance(pe.type, T.DecimalType):
                    frac = frac / (10 ** pe.type.scale)
                prms = (float(frac),)
            agg_extra_ch.append(tuple(extras_c))
            agg_extra_ir.append(tuple(extras_e))
            agg_params.append(prms)
        pre_fields = [Field(None, e.type) for e in pre_exprs]
        pre = RelationPlan(P.Project(plan.node, tuple(pre_exprs)),
                           pre_fields)

        nkeys = len(group_irs)
        # GROUPING SETS: expand through GroupId and aggregate over
        # (keys..., gid) — absent keys are NULLed per replica, and the
        # gid keeps visibly-equal groups of different sets apart
        # (reference: plan/GroupIdNode lowering)
        gid_extra = 0
        if grouping_sets is not None:
            if distinct_aggs:
                raise PlanningError(
                    "DISTINCT aggregates with GROUPING SETS are not "
                    "supported yet"
                )
            gid_extra = 1
        d_channels = sorted({
            ch for a, ch in zip(uniq_aggs, agg_arg_ch) if a.distinct
        })
        if distinct_aggs and not plain_aggs and len(d_channels) == 1:
            # two-level: dedupe (keys + the one arg), then aggregate over
            # the dedup — exchange-friendly, stays fully sharded
            dedup_channels = tuple(range(len(pre_exprs)))
            dedup = P.Aggregation(
                pre.node, dedup_channels, (),
                capacity=_agg_capacity(pre.node, self.catalogs),
            )
            specs = []
            for a, ch, ec, pr in zip(uniq_aggs, agg_arg_ch,
                                     agg_extra_ch, agg_params):
                fn = "count" if a.name == "count" else _canon_agg(a.name)
                specs.append(P.AggSpec(fn, ch, extra_channels=ec,
                                       params=pr))
            agg_node = P.Aggregation(
                dedup, tuple(range(nkeys)), tuple(specs),
                capacity=_agg_capacity(dedup, self.catalogs),
            )
        elif distinct_aggs:
            # general case — mixed DISTINCT/plain or several distinct
            # argument columns: MarkDistinct appends a first-occurrence
            # mark per (group keys, arg) set, and each distinct aggregate
            # reads its input through its mask (reference:
            # plan/MarkDistinctNode + AggregationNode mask symbols)
            mark_sets = tuple(
                tuple(range(nkeys)) + (c,) for c in d_channels
            )
            mark_of = {
                c: len(pre_exprs) + i for i, c in enumerate(d_channels)
            }
            md = P.MarkDistinct(pre.node, mark_sets)
            specs = []
            for a, ch, ec, pr in zip(uniq_aggs, agg_arg_ch,
                                     agg_extra_ch, agg_params):
                fn = _canon_agg(a.name)
                if a.is_star or (fn == "count" and ch is None):
                    specs.append(P.AggSpec("count_star", None))
                elif a.distinct:
                    specs.append(P.AggSpec(fn, ch, mask=mark_of[ch],
                                           extra_channels=ec, params=pr))
                else:
                    specs.append(P.AggSpec(fn, ch, extra_channels=ec,
                                           params=pr))
            agg_node = P.Aggregation(
                md, tuple(range(nkeys)), tuple(specs),
                capacity=_agg_capacity(pre.node, self.catalogs),
            )
        else:
            specs = []
            for a, ch, ec, pr in zip(uniq_aggs, agg_arg_ch,
                                     agg_extra_ch, agg_params):
                fn = _canon_agg(a.name)
                if a.is_star or (fn == "count" and ch is None):
                    specs.append(P.AggSpec("count_star", None))
                else:
                    specs.append(P.AggSpec(fn, ch, extra_channels=ec,
                                           params=pr))
            src_node = pre.node
            group_channels = tuple(range(nkeys))
            if grouping_sets is not None:
                masks = tuple(
                    tuple(i in s for i in range(nkeys))
                    for s in grouping_sets
                )
                src_node = P.GroupId(pre.node, tuple(range(nkeys)),
                                     masks)
                # gid channel appended after every pre-projection column
                group_channels = group_channels + (len(pre_exprs),)
            agg_node = P.Aggregation(
                src_node, group_channels, tuple(specs),
                capacity=_agg_capacity(src_node, self.catalogs),
            )

        # aggregate output fields: keys (then gid) then one per agg
        out_fields: List[Field] = []
        for i, g in enumerate(group_irs):
            nm = None
            out_fields.append(Field(nm, g.type))
        for _ in range(gid_extra):
            out_fields.append(Field(None, T.BIGINT))
        for a, e, ee in zip(uniq_aggs, agg_arg_ir, agg_extra_ir):
            if a.is_star or e is None:
                out_t = T.BIGINT
            elif a.distinct and a.name == "count":
                out_t = T.BIGINT
            else:
                out_t = AS.result_type(
                    _canon_agg(a.name), e.type,
                    tuple(x.type for x in ee),
                )
            out_fields.append(Field(None, out_t))
        agg_plan = RelationPlan(agg_node, out_fields)

        # substitution: agg AST -> channel; group ir -> channel
        subst: Dict[object, ir.RowExpression] = {}
        for i, a in enumerate(uniq_aggs):
            ref = ir.InputRef(
                nkeys + gid_extra + i,
                out_fields[nkeys + gid_extra + i].type,
            )
            subst[a] = ref
        group_map = {e: i for i, e in enumerate(group_irs)}

        agg_scope = Scope(agg_plan.fields)
        post_tr = ExprTranslator(
            self, scope, agg_subst=subst, group_subst=group_map,
            agg_fields=agg_plan.fields,
        )

        node = agg_plan.node
        if having is not None:
            h = post_tr.translate(having, root=False)
            node = P.Filter(node, h)

        exprs: List[ir.RowExpression] = []
        names: List[str] = []
        fields: List[Field] = []
        if include_keys:
            for i, g in enumerate(group_irs):
                exprs.append(ir.InputRef(i, g.type))
                names.append(None)
                fields.append(Field(None, g.type))
        for item in select_items:
            e = post_tr.translate(item.expr, root=True)
            nm = item.alias or (
                item.expr.name if isinstance(item.expr, N.Identifier)
                else None
            )
            exprs.append(e)
            names.append(nm)
            fields.append(Field(nm, e.type))
        out = RelationPlan(P.Project(node, tuple(exprs)), fields)
        return out, names

    def _order_keys(self, order_by, plan: RelationPlan):
        keys = []
        for o in order_by:
            ch = None
            if isinstance(o.expr, N.Identifier):
                for i, f in enumerate(plan.fields):
                    if f.name == o.expr.name:
                        ch = i
                        break
            elif isinstance(o.expr, N.Literal) and o.expr.kind == "long":
                ch = _ordinal(o.expr.value, len(plan.fields), "ORDER BY")
            if ch is None:
                raise PlanningError(
                    f"ORDER BY expression must reference an output column: "
                    f"{o.expr}"
                )
            keys.append(
                SortKey(ch, ascending=o.ascending, nulls_first=o.nulls_first)
            )
        return tuple(keys)

    # --------------------------------------------------- scalar subqueries
    def execute_scalar(self, q: N.Query) -> ir.Constant:
        """Eagerly run an uncorrelated scalar subquery and inline the value
        (reference: the engine keeps these as plan nodes; eager execution is
        our simplification — the value is a compile-time constant for every
        downstream jit)."""
        if self.scalar_executor is None:
            raise PlanningError(
                "scalar subqueries need an execution context"
            )
        if q in self.scalar_cache:
            return self.scalar_cache[q]
        sub = self._plan_uncorrelated_probe(q)
        if sub.channels != 1:
            raise PlanningError("scalar subquery must produce one column")
        rows = self.scalar_executor(sub.node)
        if len(rows) > 1:
            raise PlanningError("scalar subquery produced multiple rows")
        t = sub.fields[0].type
        value = rows[0][0] if rows else None
        if isinstance(t, T.DecimalType) and not t.is_short:
            if value is not None and abs(int(value)) < 2**62:
                out = ir.Constant(int(value), T.DecimalType(18, t.scale))
            else:
                out = ir.Constant(
                    None if value is None else float(value) / 10**t.scale,
                    T.DOUBLE,
                )
        else:
            out = ir.Constant(value, t)
        self.scalar_cache[q] = out
        return out


def _ordinal(value: int, n: int, where: str) -> int:
    if not 1 <= value <= n:
        raise PlanningError(
            f"{where} ordinal {value} out of range (1..{n})"
        )
    return value - 1


def _query_to_spec(q: N.Query) -> N.QuerySpec:
    if (
        q.withs or q.order_by or q.limit is not None
        or not isinstance(q.body, N.QuerySpec)
    ):
        raise PlanningError("unsupported subquery shape")
    return q.body


def _and_ir(exprs: List[ir.RowExpression]) -> ir.RowExpression:
    if len(exprs) == 1:
        return exprs[0]
    return ir.and_(*exprs)


def _decimal_safe(e: ir.RowExpression) -> ir.RowExpression:
    """Cast long-decimal refs to double before further arithmetic (module
    docstring divergence note)."""
    if isinstance(e.type, T.DecimalType) and not e.type.is_short:
        return ir.cast(e, T.DOUBLE)
    return e


# ------------------------------------------------------------- translator


class ExprTranslator:
    """AST expression -> typed ir.RowExpression over scope channels
    (reference: sql/relational/SqlToRowExpressionTranslator after
    ExpressionAnalyzer typing)."""

    def __init__(
        self,
        planner: Planner,
        scope: Scope,
        agg_subst: Optional[Dict] = None,
        group_subst: Optional[Dict] = None,
        agg_fields: Optional[List[Field]] = None,
    ):
        self.planner = planner
        self.scope = scope
        self.agg_subst = agg_subst or {}
        self.group_subst = group_subst or {}
        self.agg_fields = agg_fields
        # innermost-first stack of lambda parameter scopes
        # ({name: ir.ParamRef}); see _tr_higher_order
        self._lambda_scopes: List[dict] = []

    def translate(self, e: N.Node, root: bool = False) -> ir.RowExpression:
        """The ONE place a lowered expression leaves the translator:
        filters, projections, join conditions, aggregate arguments and
        HAVING all pass the constant fold here (expr/fold.py)."""
        out, folded = fold_constants(self._tr(e, root))
        self.planner.constants_folded += folded
        return out

    def _sub(self, e: N.Node) -> Optional[ir.RowExpression]:
        if self.agg_subst and e in self.agg_subst:
            ref = self.agg_subst[e]
            return ref
        return None

    def _tr(self, e: N.Node, root: bool = False) -> ir.RowExpression:
        sub = self._sub(e)
        if sub is not None:
            return sub if root else _decimal_safe(sub)
        if self.group_subst:
            # group expression appearing verbatim in select/having
            probe = self._group_probe(e)
            if probe is not None:
                return probe

        if isinstance(e, N.Identifier):
            if self._lambda_scopes and len(e.parts) == 1:
                # innermost frame ONLY: ParamRef indices are frame-
                # local, so an outer lambda's parameter inside a nested
                # lambda would silently alias the inner page's params —
                # raise (with the capture error below) instead
                ref = self._lambda_scopes[-1].get(e.parts[0])
                if ref is not None:
                    return ref
            if self._lambda_scopes:
                raise PlanningError(
                    f"lambda bodies cannot capture columns or outer "
                    f"lambda parameters ({'.'.join(e.parts)}); only "
                    f"this lambda's parameters and constants are "
                    f"allowed"
                )
            lvl, ch, f = self.scope.resolve(e)
            if lvl == 0:
                if self.group_subst is not None and self.agg_fields:
                    # inside an aggregation block a bare column must be a
                    # group key (checked via group_subst probe above)
                    raise PlanningError(
                        f"column {e.name!r} is neither grouped nor "
                        f"aggregated"
                    )
                return ir.InputRef(ch, f.type)
            return OuterRef(ch, f.type)
        if isinstance(e, N.Literal):
            return _literal(e)
        if isinstance(e, N.ArrayLiteral):
            items = [self._tr(i) for i in e.items]
            vals = []
            elem_t: T.SqlType = T.UNKNOWN
            for it in items:
                if not isinstance(it, ir.Constant):
                    raise PlanningError(
                        "ARRAY[...] elements must be constants"
                    )
                vals.append(it.value)
                if not isinstance(it.type, T.UnknownType):
                    ct = (T.common_super_type(elem_t, it.type)
                          if not isinstance(elem_t, T.UnknownType)
                          else it.type)
                    if ct is None:
                        raise PlanningError(
                            f"ARRAY[] elements have incompatible types: "
                            f"{elem_t} vs {it.type}"
                        )
                    elem_t = ct
            return ir.Constant(tuple(vals), T.ArrayType(elem_t))
        if isinstance(e, N.UnaryOp):
            if e.op == "not":
                return ir.not_(self._tr(e.operand))
            v = self._tr(e.operand)
            if e.op == "-":
                if isinstance(v, ir.Constant) and v.value is not None:
                    return ir.Constant(-v.value, v.type)
                return ir.call("negate", v)
            return v
        if isinstance(e, N.BinaryOp):
            if e.op == "and":
                return ir.and_(self._tr(e.left), self._tr(e.right))
            if e.op == "or":
                return ir.or_(self._tr(e.left), self._tr(e.right))
            if e.op == "||":
                return ir.call("concat", self._tr(e.left), self._tr(e.right))
            return ir.call(_BINOP_FN[e.op], self._tr(e.left),
                           self._tr(e.right))
        if isinstance(e, N.Between):
            b = ir.between(self._tr(e.value), self._tr(e.low),
                           self._tr(e.high))
            return ir.not_(b) if e.negated else b
        if isinstance(e, N.InList):
            x = ir.in_(self._tr(e.value), *[self._tr(i) for i in e.items])
            return ir.not_(x) if e.negated else x
        if isinstance(e, N.Like):
            args = [self._tr(e.value), self._tr(e.pattern)]
            if e.escape is not None:
                args.append(self._tr(e.escape))
            x = ir.call("like", *args)
            return ir.not_(x) if e.negated else x
        if isinstance(e, N.IsNull):
            x = ir.is_null(self._tr(e.value))
            return ir.not_(x) if e.negated else x
        if isinstance(e, N.Case):
            return self._tr_case(e)
        if isinstance(e, N.Cast):
            to = T.parse_type(e.type_name)
            if e.safe:
                return ir.Call("try_cast", (self._tr(e.value),), to)
            return ir.cast(self._tr(e.value), to)
        if isinstance(e, N.Extract):
            return ir.call(e.field.lower(), self._tr(e.value))
        if isinstance(e, N.FunctionCall):
            if _is_agg_name(e.name) or e.is_star:
                raise PlanningError(
                    f"aggregate {e.name} in invalid context"
                )
            # special forms spelled as function calls
            if e.name == "coalesce":
                return ir.coalesce(*[self._tr(a) for a in e.args])
            if e.name == "nullif":
                # `a` appears twice in the IR; XLA CSEs the identical
                # subgraphs under jit, so it is not evaluated twice on device
                a, b = (self._tr(x) for x in e.args)
                return ir.if_(
                    ir.call("eq", a, b), ir.Constant(None, a.type), a
                )
            if e.name == "if":
                args = [self._tr(a) for a in e.args]
                if len(args) == 2:
                    args.append(ir.Constant(None, args[1].type))
                return ir.if_(*args)
            if any(isinstance(a, N.Lambda) for a in e.args):
                return self._tr_higher_order(e)
            return ir.call(e.name, *[self._tr(a) for a in e.args])
        if isinstance(e, N.ScalarSubquery):
            return self.planner.execute_scalar(e.query)
        if isinstance(e, N.Parameter):
            raise PlanningError(
                f"parameter ?{e.index + 1} is not bound — run via "
                f"EXECUTE <name> USING <values>"
            )
        raise PlanningError(f"unsupported expression: {type(e).__name__}")

    def _tr_higher_order(self, e: N.FunctionCall) -> ir.RowExpression:
        """Higher-order function call: non-lambda args translate
        normally; lambda parameters bind to the collection's element
        type(s) (reference: ExpressionAnalyzer's lambda type
        inference against the function signature)."""
        first = self._tr(e.args[0])
        t0 = first.type
        if isinstance(t0, T.ArrayType):
            param_types = [t0.element]
        elif isinstance(t0, T.MapType):
            param_types = [t0.key, t0.value]
        else:
            raise PlanningError(
                f"{e.name}: first argument must be an array or map, "
                f"got {t0}"
            )
        out_args: List[ir.RowExpression] = [first]
        for pos, a in enumerate(e.args[1:], start=1):
            if not isinstance(a, N.Lambda):
                out_args.append(self._tr(a))
                continue
            if e.name == "reduce":
                # combine is (state, element) -> state; the optional
                # output lambda is state -> result; the state type
                # comes from the (already translated) initial value
                state_t = (out_args[1].type if len(out_args) > 1
                           else T.UNKNOWN)
                want = ([state_t, param_types[0]] if pos == 2
                        else [state_t])
            elif (e.name == "transform_values"
                    and len(a.params) == 1):
                want = [param_types[1]]  # v -> ... binds the value
            elif e.name == "zip_with":
                # (x, y) -> ... binds both arrays' element types
                t1 = out_args[1].type if len(out_args) > 1 else T.UNKNOWN
                want = [
                    param_types[0],
                    t1.element if isinstance(t1, T.ArrayType)
                    else T.UNKNOWN,
                ]
            else:
                want = (param_types if len(a.params) == len(param_types)
                        else param_types[: len(a.params)])
            if len(a.params) != len(want):
                raise PlanningError(
                    f"{e.name}: lambda takes {len(a.params)} "
                    f"parameters, expected {len(want)}"
                )
            frame = {
                p: ir.ParamRef(i, t)
                for i, (p, t) in enumerate(zip(a.params, want))
            }
            self._lambda_scopes.append(frame)
            try:
                body = self._tr(a.body)
            finally:
                self._lambda_scopes.pop()
            out_args.append(
                ir.Lambda(len(a.params), body, body.type)
            )
        return ir.call(e.name, *out_args)

    def _group_probe(self, e: N.Node) -> Optional[ir.RowExpression]:
        """If e translates (in the pre-agg scope) to a group expression,
        return the key channel ref."""
        folded = self.planner.constants_folded
        try:
            pre = ExprTranslator(self.planner, self.scope).translate(e)
        except PlanningError:
            return None
        finally:
            # looked at and thrown away: not the statement's folds
            self.planner.constants_folded = folded
        if pre in self.group_subst:
            ch = self.group_subst[pre]
            return ir.InputRef(ch, pre.type)
        return None

    def _tr_case(self, e: N.Case) -> ir.RowExpression:
        args: List[ir.RowExpression] = []
        for when, then in e.whens:
            if e.operand is not None:
                cond = ir.call("eq", self._tr(e.operand), self._tr(when))
            else:
                cond = self._tr(when)
            args.append(cond)
            args.append(self._tr(then))
        thens = args[1::2]
        if e.default is not None:
            default = self._tr(e.default)
        else:
            default = ir.Constant(None, thens[0].type)
        return ir.switch(*args, default)


def _literal(e: N.Literal) -> ir.Constant:
    if e.kind == "long":
        return ir.Constant(e.value, T.BIGINT)
    if e.kind == "double":
        return ir.Constant(float(e.value), T.DOUBLE)
    if e.kind == "decimal":
        text = str(e.value)
        if "." in text:
            intpart, frac = text.split(".")
        else:
            intpart, frac = text, ""
        scale = len(frac)
        digits = (intpart + frac).lstrip("0") or "0"
        precision = max(len(digits), scale, 1)
        unscaled = int(intpart + frac) if (intpart + frac) else 0
        return ir.Constant(unscaled, T.DecimalType(precision, scale))
    if e.kind == "string":
        return ir.Constant(e.value, T.VARCHAR)
    if e.kind == "boolean":
        return ir.Constant(bool(e.value), T.BOOLEAN)
    if e.kind == "null":
        return ir.Constant(None, T.UNKNOWN)
    if e.kind == "date":
        d = datetime.date.fromisoformat(e.value)
        return ir.Constant((d - _EPOCH).days, T.DATE)
    if e.kind == "timestamp":
        dt = datetime.datetime.fromisoformat(e.value)
        micros = int(
            (dt - datetime.datetime(1970, 1, 1)).total_seconds() * 1e6
        )
        return ir.Constant(micros, T.TIMESTAMP)
    if e.kind == "interval":
        amount, unit = e.value
        unit = unit.rstrip("s")
        if unit == "day":
            return ir.Constant(amount * 86_400_000_000, T.INTERVAL_DAY_TIME)
        if unit == "hour":
            return ir.Constant(amount * 3_600_000_000, T.INTERVAL_DAY_TIME)
        if unit == "minute":
            return ir.Constant(amount * 60_000_000, T.INTERVAL_DAY_TIME)
        if unit == "second":
            return ir.Constant(amount * 1_000_000, T.INTERVAL_DAY_TIME)
        if unit == "week":
            return ir.Constant(amount * 7 * 86_400_000_000,
                               T.INTERVAL_DAY_TIME)
        if unit == "month":
            return ir.Constant(amount, T.INTERVAL_YEAR_MONTH)
        if unit == "year":
            return ir.Constant(amount * 12, T.INTERVAL_YEAR_MONTH)
        raise PlanningError(f"unsupported interval unit: {unit}")
    raise PlanningError(f"unsupported literal kind: {e.kind}")

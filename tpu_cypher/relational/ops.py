"""Relational (physical) operator tree.

Re-design of the reference's lazy physical plan
(``okapi-relational/.../impl/operators/RelationalOperator.scala:48-514``):
each node computes ``header`` and ``table`` from its children; every
``table`` pull calls exactly one Table-SPI method. Mirrored ops: Start,
Alias, Add, Drop, Filter, Select, Distinct, Aggregate, OrderBy, Skip, Limit,
EmptyRecords, Join, TabularUnionAll, ReturnGraph, plus scan/swap helpers the
reference keeps inside its graph implementations."""

from __future__ import annotations

import math
import time

from dataclasses import dataclass, field as dc_field
from functools import cached_property
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..api import types as T
from ..api.table import Table
from ..ir import expr as E
from ..obs import trace as _obs_trace
from ..obs.metrics import REGISTRY as _OBS_REGISTRY
from . import procedures
from .header import RecordHeader


class RelationalError(Exception):
    pass


# how often an ungrouped count(*) got its number from the operator under it
# (``count``) and how often that operator had to build its rows after all
# (``rows``); ``AggregateOp`` is the one place that counts
COUNT_PUSHDOWN = _OBS_REGISTRY.counter(
    "tpu_cypher_count_pushdown_total",
    "ungrouped count(*) over a filter, an inner join, a DISTINCT, a tree "
    "of fused expands or an expand chain under constraints between two of "
    "its nodes (one a constraint): answered from the operator's count "
    "phase (count) or from its rows (rows)",
    labels=("op", "outcome"),
)
for _outcome in ("count", "rows"):  # a sound deployment reads 0 rows: exported
    COUNT_PUSHDOWN.inc(0, op="chain_constraint", outcome=_outcome)
    COUNT_PUSHDOWN.inc(0, op="tree", outcome=_outcome)


@dataclass
class RelationalRuntimeContext:
    """Reference ``RelationalRuntimeContext``: parameter map + graph resolver
    + backend table factory."""

    resolve_graph: Any  # Callable[[str], RelationalCypherGraph]
    parameters: Dict[str, Any] = dc_field(default_factory=dict)
    table_cls: type = None  # Table implementation class


class RelationalOperator:
    def __init__(self, *children: "RelationalOperator"):
        self.children = children
        self._header: Optional[RecordHeader] = None
        self._table: Optional[Table] = None

    # -- lazy header/table ------------------------------------------------

    @property
    def header(self) -> RecordHeader:
        if self._header is None:
            self._header = self._compute_header()
        return self._header

    @property
    def table(self) -> Table:
        if self._table is None:
            # every first pull is an operator span in the query's trace
            # tree (obs.trace); children pulled inside _compute_table nest
            # naturally. Memoized re-reads stay span-free — they do no
            # work. HOST wall time only: under JAX async dispatch this is
            # dispatch cost, never an added device sync.
            with _obs_trace.span(type(self).__name__, kind="operator"):
                t = self._compute_table()
            cols = set(t.physical_columns)
            need = set(self.header.columns)
            if need - cols:
                raise RelationalError(
                    f"{type(self).__name__}: header columns {sorted(need - cols)} "
                    f"missing from table columns {sorted(cols)}"
                )
            self._table = t
        return self._table

    def _compute_header(self) -> RecordHeader:
        return self.children[0].header

    def _compute_table(self) -> Table:
        raise NotImplementedError

    @property
    def context(self) -> RelationalRuntimeContext:
        return self.children[0].context

    @property
    def graph(self):
        return self.children[0].graph

    def pretty(self, indent: int = 0) -> str:
        pad = "  " * indent
        inner = self._show_inner()
        lines = [f"{pad}{type(self).__name__}{'(' + inner + ')' if inner else ''}"]
        for c in self.children:
            lines.append(c.pretty(indent + 1))
        return "\n".join(lines)

    def _show_inner(self) -> str:
        return ""


# ---------------------------------------------------------------------------
# Leaves
# ---------------------------------------------------------------------------


class StartOp(RelationalOperator):
    """Start from a table (unit table or driving table) bound to a graph."""

    def __init__(
        self,
        graph,
        ctx: RelationalRuntimeContext,
        table: Optional[Table] = None,
        header: Optional[RecordHeader] = None,
    ):
        super().__init__()
        self._graph = graph
        self._ctx = ctx
        self._start_table = table if table is not None else ctx.table_cls.unit()
        self._start_header = header if header is not None else RecordHeader()

    def _compute_header(self) -> RecordHeader:
        return self._start_header

    def _compute_table(self) -> Table:
        return self._start_table

    @property
    def context(self) -> RelationalRuntimeContext:
        return self._ctx

    @property
    def graph(self):
        return self._graph


class EmptyRecordsOp(RelationalOperator):
    def __init__(self, graph, ctx: RelationalRuntimeContext, header: RecordHeader):
        super().__init__()
        self._graph = graph
        self._ctx = ctx
        self._empty_header = header

    def _compute_header(self) -> RecordHeader:
        return self._empty_header

    def _compute_table(self) -> Table:
        return self._ctx.table_cls.empty(self._empty_header.columns)

    @property
    def context(self):
        return self._ctx

    @property
    def graph(self):
        return self._graph


class TableOp(RelationalOperator):
    """A precomputed (header, table) pair as an operator (scan results)."""

    def __init__(self, graph, ctx, header: RecordHeader, table: Table):
        super().__init__()
        self._graph = graph
        self._ctx = ctx
        self._h = header
        self._t = table

    def _compute_header(self):
        return self._h

    def _compute_table(self):
        return self._t

    @property
    def context(self):
        return self._ctx

    @property
    def graph(self):
        return self._graph


# ---------------------------------------------------------------------------
# Unary ops
# ---------------------------------------------------------------------------


class CacheOp(RelationalOperator):
    """Reference ``Cache`` (``RelationalOperator.scala:198``)."""

    def _compute_table(self) -> Table:
        return self.children[0].table.cache()


class AliasOp(RelationalOperator):
    """Bind aliases to existing columns — metadata only (reference ``Alias``)."""

    def __init__(self, in_op: RelationalOperator, aliases: Sequence[Tuple[E.Var, E.Var]]):
        super().__init__(in_op)
        self.aliases = list(aliases)  # (existing var, alias var)

    def _compute_header(self) -> RecordHeader:
        h = self.children[0].header
        for orig, alias in self.aliases:
            h = h.with_alias(alias, orig)
        return h

    def _compute_table(self) -> Table:
        return self.children[0].table

    def _show_inner(self) -> str:
        return ", ".join(f"{o.name} AS {a.name}" for o, a in self.aliases)


class PathBindOp(RelationalOperator):
    """Register a named-path binding in the header — metadata only; the path
    value is reassembled from member element columns at materialization."""

    def __init__(self, in_op: RelationalOperator, path_var: str, entities: Sequence[str]):
        super().__init__(in_op)
        self.path_var = path_var
        self.entities = tuple(entities)

    def _compute_header(self) -> RecordHeader:
        return self.children[0].header.with_path(self.path_var, self.entities)

    def _compute_table(self) -> Table:
        return self.children[0].table

    def _show_inner(self) -> str:
        return f"{self.path_var} = ({', '.join(self.entities)})"


class AddOp(RelationalOperator):
    """Project an expression into a (new or replaced) field column
    (reference ``Add``/``AddInto``, ``RelationalOperator.scala:219-249``)."""

    def __init__(self, in_op: RelationalOperator, expr: E.Expr, fld: str):
        super().__init__(in_op)
        self.expr = expr
        self.fld = fld

    @cached_property
    def _var(self) -> E.Var:
        return E.Var(self.fld).with_type(self.expr.cypher_type)

    def _compute_header(self) -> RecordHeader:
        h = self.children[0].header
        existing = [v for v in h.vars if v.name == self.fld]
        if existing:
            h = h.without(existing[0])
        return h.with_expr(self._var)

    def _compute_table(self) -> Table:
        in_op = self.children[0]
        col = self.header.column(self._var)
        return in_op.table.with_columns(
            [(self.expr, col)], in_op.header, self.context.parameters
        )

    def _show_inner(self) -> str:
        return f"{self.fld} := {self.expr.pretty_expr()}"


class RowIndexOp(RelationalOperator):
    """The input's rows with their number (0 .. n-1) in a new integer
    field: the key ``RelationalPlanner._plan_Optional`` joins on."""

    def __init__(self, in_op: RelationalOperator, fld: str):
        super().__init__(in_op)
        self.fld = fld

    @cached_property
    def var(self) -> E.Var:
        return E.Var(self.fld).with_type(T.CTInteger)

    def _compute_header(self) -> RecordHeader:
        return self.children[0].header.with_expr(self.var)

    def _compute_table(self) -> Table:
        return self.children[0].table.with_row_index(self.header.column(self.var))

    def _show_inner(self) -> str:
        return self.fld


class ProcedureCallOp(RelationalOperator):
    """A leading procedure call over the scan of every node of the graph:
    the procedure's value of each node in a new column, computed by the
    backend's own implementation (``table_cls.run_procedure``; the
    registry and the local one in ``procedures.py``)."""

    def __init__(self, scan: RelationalOperator, procedure: str,
                 args: Sequence[E.Expr], node_fld: str, value: E.Var):
        super().__init__(scan)
        self.procedure = procedure
        self.args = tuple(args)
        self.node_fld = node_fld
        self.value = value

    def _compute_header(self) -> RecordHeader:
        return self.children[0].header.with_expr(self.value)

    def _compute_table(self) -> Table:
        proc = procedures.lookup(self.procedure)
        params = self.context.parameters
        args = procedures.check_args(
            proc, [_static_value(a, params) for a in self.args]
        )
        scan = self.children[0]
        h = scan.header
        return self.context.table_cls.run_procedure(
            proc, self.graph, self.context, scan.table,
            h.column(h.id_expr(h.var(self.node_fld))),
            self.header.column(self.value), args,
        )

    def _show_inner(self) -> str:
        args = ", ".join(a.pretty_expr() for a in self.args)
        return f"{self.procedure}({args}): {self.node_fld}, {self.value.name}"


class DropOp(RelationalOperator):
    def __init__(self, in_op: RelationalOperator, exprs: Sequence[E.Expr]):
        super().__init__(in_op)
        self.exprs = list(exprs)

    def _compute_header(self) -> RecordHeader:
        h = self.children[0].header
        m = {e: c for e, c in ((e, h.get(e)) for e in h.expressions) if e not in self.exprs}
        return RecordHeader(m, h._paths)

    def _compute_table(self) -> Table:
        keep = self.header.columns
        return self.children[0].table.select(keep)


def _peel_cache(op: RelationalOperator) -> RelationalOperator:
    """``op`` without the CacheOps over it (a cache is the identity)."""
    while isinstance(op, CacheOp):
        op = op.children[0]
    return op


class ExistsFlagOp(DropOp):
    """The top of a planned ``ExistsSubQuery``
    (``RelationalPlanner._plan_ExistsSubQuery``): the DropOp it is, that
    also names the plan's parts — the outer rows (``outer``) and the
    pattern whose existence ``target_field`` reports per outer row
    (``pattern``) — so that a count over a filter on the flag can ask the
    pattern instead of building the join (``_chain_constraints``)."""

    def __init__(self, in_op: RelationalOperator, exprs, target_field: str):
        super().__init__(in_op, exprs)
        self.target_field = target_field

    @property
    def _join(self) -> RelationalOperator:
        with_target = _peel_cache(self.children[0])
        return _peel_cache(with_target.children[0])

    @property
    def outer(self) -> RelationalOperator:
        return _peel_cache(self._join.children[0])

    @property
    def pattern(self) -> RelationalOperator:
        op = self._join.children[1]  # flag := true over DISTINCT over SELECT
        for _ in range(3):
            op = _peel_cache(op).children[0]
        return _peel_cache(op)


class FilterOp(RelationalOperator):
    def __init__(self, in_op: RelationalOperator, predicate: E.Expr):
        super().__init__(in_op)
        self.predicate = predicate

    def _compute_table(self) -> Table:
        in_op = self.children[0]
        return in_op.table.filter(self.predicate, in_op.header, self.context.parameters)

    def row_count(self) -> Optional[int]:
        """Rows the filter keeps, without them (``Table.filter_count``: the
        mask's sum); None where the backend must build them to know. The
        input of the filter is built as ever."""
        in_op = self.children[0]
        return in_op.table.filter_count(
            self.predicate, in_op.header, self.context.parameters
        )

    def _show_inner(self) -> str:
        return self.predicate.pretty_expr()


def _is_node_var(header: RecordHeader, name: str) -> bool:
    try:
        v = header.var(name)
    except (KeyError, ValueError):
        return False
    m = v.cypher_type.material if v.cypher_type is not None else None
    return isinstance(m, T.CTNodeType) and not header.has_path(name)


def _node_pair_constraint(f: FilterOp):
    """``f`` read as one constraint between two node variables of its
    input, with the operator the next filter of the stack would sit on:
    ``("neq" | "eq", a, c)`` for ``a <> c`` / ``a = c``, ``("edge", source,
    target, types, negated)`` for a (negated) flag of an ``ExistsFlagOp``
    whose pattern is one directed relationship between two bound nodes of
    the very rows the flag is joined to. None: not such a filter."""
    p, child = f.predicate, f.children[0]
    if isinstance(p, (E.Neq, E.Equals)):
        a, c = p.lhs, p.rhs
        if (
            isinstance(a, E.Var) and isinstance(c, E.Var)
            and _is_node_var(child.header, a.name)
            and _is_node_var(child.header, c.name)
        ):
            kind = "neq" if isinstance(p, E.Neq) else "eq"
            return (kind, a.name, c.name), child
        return None
    negated = isinstance(p, E.Not)
    flag = p.expr if negated else p
    exists = _peel_cache(child)
    if (
        not isinstance(flag, E.Var)
        or not isinstance(exists, ExistsFlagOp)
        or exists.target_field != flag.name
        or exists._table is not None
    ):
        return None
    pattern = exists.pattern
    closing = getattr(pattern, "closing_edge", None)
    edge = closing() if closing is not None else None
    if edge is None or _peel_cache(pattern.children[0]) is not exists.outer:
        return None
    return ("edge", *edge, negated), exists.outer


def _chain_constraints(op: RelationalOperator):
    """The stack of filters from ``op`` down, each read as a constraint
    between two node variables (``_node_pair_constraint``), and the operator
    under the stack when it can count under such constraints
    (``chain_constraint_count``, the fused expand chain's); else None."""
    constraints = []
    while True:
        op = _peel_cache(op)
        if not isinstance(op, FilterOp):
            break
        got = None if op._table is not None else _node_pair_constraint(op)
        if got is None:
            return None
        constraints.append(got[0])
        op = got[1]
    if not constraints or not hasattr(op, "chain_constraint_count"):
        return None
    return constraints, op


class SelectOp(RelationalOperator):
    def __init__(self, in_op: RelationalOperator, fields: Sequence[str]):
        super().__init__(in_op)
        self.fields = list(fields)

    def _compute_header(self) -> RecordHeader:
        h = self.children[0].header
        vars_ = [h.var(f) for f in self.fields]
        return h.select(vars_)

    def _compute_table(self) -> Table:
        return self.children[0].table.select(self.header.columns)

    def _show_inner(self) -> str:
        return ", ".join(self.fields)


class DistinctOp(RelationalOperator):
    def __init__(self, in_op: RelationalOperator, fields: Sequence[str]):
        super().__init__(in_op)
        self.fields = list(fields)

    def distinct_columns(self) -> List[str]:
        h = self.header
        cols: List[str] = []
        for f in self.fields:
            v = h.var(f)
            m = v.cypher_type.material if v.cypher_type is not None else None
            if isinstance(m, (T.CTNodeType, T.CTRelationshipType)) and not h.has_path(f):
                # an element's id determines its labels/type/properties —
                # distinct on the id column alone (the reference relies on
                # the engines' optimizers for the same reduction)
                c = h.column(h.id_expr(v))
                if c not in cols:
                    cols.append(c)
                continue
            for e in h.expressions_for(v):
                c = h.column(e)
                if c not in cols:
                    cols.append(c)
        return cols

    def _compute_table(self) -> Table:
        cols = self.distinct_columns()
        t = self.children[0].table
        return t.distinct(cols) if cols else t.distinct()

    def _show_inner(self) -> str:
        return ", ".join(self.fields)


class AggregateOp(RelationalOperator):
    def __init__(
        self,
        in_op: RelationalOperator,
        group_fields: Sequence[str],
        aggregations: Sequence[Tuple[str, E.Agg]],
    ):
        super().__init__(in_op)
        self.group_fields = list(group_fields)
        self.aggregations = list(aggregations)

    def _compute_header(self) -> RecordHeader:
        in_h = self.children[0].header
        h = RecordHeader()
        for f in self.group_fields:
            v = in_h.var(f)
            for e in in_h.expressions_for(v):
                h = h.with_expr(e, in_h.column(e))
            if in_h.has_path(f):
                h = h.with_path(f, in_h.path_entities(f))
        for name, agg in self.aggregations:
            h = h.with_expr(E.Var(name).with_type(agg.cypher_type))
        return h

    def _compute_table(self) -> Table:
        in_op = self.children[0]
        in_h = in_op.header
        by: List[str] = []
        for f in self.group_fields:
            v = in_h.var(f)
            for e in in_h.expressions_for(v):
                c = in_h.column(e)
                if c not in by:
                    by.append(c)
        aggs = []
        for name, agg in self.aggregations:
            out_col = self.header.column(E.Var(name))
            aggs.append((out_col, agg))
        # plain count(*) and nothing else: only the NUMBER of input rows
        # is read, so the operator below is asked for that number first
        count_star_only = not by and all(
            getattr(agg, "expr", None) is None and not getattr(agg, "distinct", False)
            for _, agg in self.aggregations
        )
        if count_star_only:
            n = self._input_row_count()
            if n is not None:
                cols = {out_col: [n] for out_col, _ in aggs}
                # one host value per column to the device, no program. A
                # closed leaf, not a nested span: the lattice's rows_true /
                # rows_padded of this table stay on the operator, where
                # optimizer/feedback reads them
                sp = _obs_trace.current_span()
                t0 = time.perf_counter()
                table = self.context.table_cls.from_columns(cols)
                if sp is not None:
                    sp.add("build_table", "step", t0, time.perf_counter())
                return table
        return in_op.table.group(by, aggs, in_h, self.context.parameters)

    def _input_row_count(self) -> Optional[int]:
        """The number of input rows from the phase of the input operator
        that already knows it — a DISTINCT's first-occurrence count, a
        filter's mask, an inner join's count phase, a table a CSE-shared
        sibling built — or None: the rows are built and grouped. Every ask
        is counted in ``tpu_cypher_count_pushdown_total{op,outcome}`` and
        named on this operator's span (``count_from``)."""
        in_op = self.children[0]
        if isinstance(in_op, DistinctOp) and in_op._table is None:
            # count-over-distinct: WITH DISTINCT a, b ... RETURN count(*)
            # never materializes the deduped rows — the count is the
            # number of first-occurrence groups (the engines get the same
            # from their optimizers' aggregate pushdown)
            n = self._distinct_row_count(in_op)
            return self._note_pushdown("distinct", n)
        # projections keep the multiset: look through them
        inner = in_op
        while inner._table is None and isinstance(inner, (SelectOp, CacheOp, DropOp)):
            inner = inner.children[0]
        if inner._table is not None:
            _obs_trace.note("count_from", "table")
            return inner._table.size
        tree_count = getattr(inner, "tree_count", None)
        if tree_count is not None:
            # a tree of fused expands (a path, a star, OPTIONAL leaves):
            # multiplicities per node, no row of the pattern
            with _obs_trace.span(type(inner).__name__, kind="operator", count_only=True):
                n = tree_count()
            return self._note_pushdown("tree", n)
        if not isinstance(inner, (FilterOp, JoinOp)):
            return None
        if isinstance(inner, FilterOp):
            n = self._chain_constraint_count(inner)
            if n is not None:
                return n
            under = inner
            while isinstance(under, (FilterOp, SelectOp, CacheOp, DropOp)):
                under = under.children[0]
            if hasattr(under, "tree_count"):
                # a predicate over a tree of expands that is no mask of one
                # of its nodes (relationship uniqueness between two hops of
                # one type, ...): the tree count declines, the filter counts
                COUNT_PUSHDOWN.inc(op="tree", outcome="rows")
                _obs_trace.note("tree_decline", "predicate")
        op = "filter" if isinstance(inner, FilterOp) else "join"
        # the operator's span, as ``table`` would have opened it
        with _obs_trace.span(type(inner).__name__, kind="operator", count_only=True):
            n = inner.row_count()
            # no count phase for this kind of key: the pairs are built, but
            # still from the key columns alone
            rows = inner.key_join_size() if n is None and op == "join" else None
        self._note_pushdown(op, n)
        return n if n is not None else rows

    @staticmethod
    def _chain_constraint_count(top: "FilterOp") -> Optional[int]:
        """count(*) over filters that each relate two nodes of the expand
        chain under them (``a <> c``, ``a = c``, ``[NOT] (a)-[:T]->(c)``):
        the chain counts under the constraints without a row of its own
        (``CsrExpandOp.chain_constraint_count``). None — the filters are
        not all of that kind, or the chain declines (the pair further apart
        than two hops, ...) — and the generic path builds the rows; the
        constraints are counted either way, one each."""
        found = _chain_constraints(top)
        if found is None:
            return None
        constraints, chain = found
        n = chain.chain_constraint_count(constraints)
        COUNT_PUSHDOWN.inc(
            len(constraints), op="chain_constraint",
            outcome="rows" if n is None else "count",
        )
        if n is not None:
            _obs_trace.note("count_from", "chain_constraint")
        return n

    @staticmethod
    def _note_pushdown(op: str, n: Optional[int]) -> Optional[int]:
        outcome = "rows" if n is None else "count"
        COUNT_PUSHDOWN.inc(op=op, outcome=outcome)
        _obs_trace.note("count_from", op if n is not None else f"{op}:rows")
        return n

    @staticmethod
    def _distinct_row_count(in_op: "DistinctOp") -> Optional[int]:
        # deepest pushdown first: a fused expand chain can count its
        # DISTINCT endpoints without materializing ANY row set (the
        # backend op advertises `distinct_endpoints_count`). Column
        # projections keep the row multiset, so peel SelectOps as long
        # as the distinct fields survive them.
        inner = in_op.children[0]
        while (
            isinstance(inner, SelectOp)
            and set(in_op.fields) <= set(inner.fields)
        ) or isinstance(inner, CacheOp):
            inner = inner.children[0]
        fused = getattr(inner, "distinct_endpoints_count", None)
        if fused is not None:
            n = fused(in_op.fields)
            if n is not None:
                return n
        return in_op.children[0].table.distinct_count(in_op.distinct_columns())

    def _show_inner(self) -> str:
        return f"group={self.group_fields}"


class OrderByOp(RelationalOperator):
    def __init__(self, in_op: RelationalOperator, items: Sequence[Tuple[str, bool]]):
        super().__init__(in_op)
        self.items = list(items)  # (field, ascending)

    def sort_cols(self) -> List[Tuple[str, bool]]:
        """(physical column, ascending) sort keys — shared with LimitOp's
        top-k fusion so key resolution cannot diverge between paths."""
        h = self.header
        cols = []
        for f, asc in self.items:
            v = h.var(f)
            cols.append((h.column(h.id_expr(v)), asc))
        return cols

    def _compute_table(self) -> Table:
        return self.children[0].table.order_by(self.sort_cols())


class SkipOp(RelationalOperator):
    def __init__(self, in_op: RelationalOperator, expr: E.Expr):
        super().__init__(in_op)
        self.expr = expr

    def _count(self) -> int:
        v = _static_value(self.expr, self.context.parameters)
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            raise RelationalError(f"SKIP requires a non-negative integer, got {v!r}")
        return v

    def _compute_table(self) -> Table:
        return self.children[0].table.skip(self._count())


class LimitOp(RelationalOperator):
    def __init__(self, in_op: RelationalOperator, expr: E.Expr):
        super().__init__(in_op)
        self.expr = expr

    def _compute_table(self) -> Table:
        v = _static_value(self.expr, self.context.parameters)
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            raise RelationalError(f"LIMIT requires a non-negative integer, got {v!r}")
        # the limit goes into the gather: LIMIT k (with optional SKIP s)
        # directly over ORDER BY asks the backend for the first s+k sorted
        # rows (TpuTable sorts the keys and gathers the rows at the first
        # s+k entries of the permutation alone; the hook notes its path)
        node = _peel_cache(self.children[0])
        skip = 0
        ob = None
        if isinstance(node, SkipOp):
            try:
                skip = node._count()
                inner = _peel_cache(node.children[0])
                if isinstance(inner, OrderByOp):
                    ob = inner
            except RelationalError:
                ob = None
        elif isinstance(node, OrderByOp):
            ob = node
        # skip the fusion when the sorted table is already materialized
        # (a CSE-shared sibling computed it): slicing it is free
        if ob is not None and ob._table is None:
            in_t = ob.children[0].table
            hook = getattr(in_t, "order_by_limit", None)
            if hook is not None:
                t = hook(ob.sort_cols(), skip + v)
                if t is not None:
                    return t.skip(skip) if skip else t
        if ob is not None:  # the whole table sorted and gathered, then sliced
            _obs_trace.note_order_limit("full")
        return self.children[0].table.limit(v)


class UnwindOp(RelationalOperator):
    def __init__(self, in_op: RelationalOperator, list_expr: E.Expr, fld: str, fld_type):
        super().__init__(in_op)
        self.list_expr = list_expr
        self.fld = fld
        self.fld_type = fld_type

    @cached_property
    def _var(self):
        return E.Var(self.fld).with_type(self.fld_type)

    def _compute_header(self) -> RecordHeader:
        return self.children[0].header.with_expr(self._var)

    def _compute_table(self) -> Table:
        in_op = self.children[0]
        col = self.header.column(self._var)
        return in_op.table.explode(
            self.list_expr, col, in_op.header, self.context.parameters
        )


class SwapStartEndOp(RelationalOperator):
    """Produce the reversed orientation of a relationship scan (START<->END
    columns swapped) — used for undirected expands (reference plans undirected
    as a union of both orientations, ``RelationalPlanner.scala``)."""

    def __init__(self, in_op: RelationalOperator, rel_var: E.Var):
        super().__init__(in_op)
        self.rel_var = rel_var

    def _compute_table(self) -> Table:
        h = self.children[0].header
        start = next(
            e for e in h.expressions_for(self.rel_var) if isinstance(e, E.StartNode)
        )
        end = next(
            e for e in h.expressions_for(self.rel_var) if isinstance(e, E.EndNode)
        )
        sc, ec = h.column(start), h.column(end)
        return self.children[0].table.rename({sc: ec, ec: sc})


# ---------------------------------------------------------------------------
# Binary ops
# ---------------------------------------------------------------------------


class JoinOp(RelationalOperator):
    """Equi-join on expression pairs; colliding rhs columns are renamed before
    the join and deduplicated after (reference ``Join``
    ``RelationalOperator.scala:423-449`` + ``safeJoin`` renaming
    ``TableOps.scala:146``)."""

    _counter = 0

    def __init__(
        self,
        lhs: RelationalOperator,
        rhs: RelationalOperator,
        join_exprs: Sequence[Tuple[E.Expr, E.Expr]],
        kind: str = "inner",
    ):
        super().__init__(lhs, rhs)
        self.join_exprs = list(join_exprs)
        self.kind = kind
        self._plan: Optional[Tuple] = None

    def _analyze(self):
        if self._plan is not None:
            return self._plan
        lhs, rhs = self.children
        lh, rh = lhs.header, rhs.header
        l_cols = set(lh.columns)
        renames: Dict[str, str] = {}
        for c in rh.columns:
            if c in l_cols:
                JoinOp._counter += 1
                renames[c] = f"__rjoin_{JoinOp._counter}_{c}"
        # rhs exprs not in lhs keep their (possibly renamed) column
        new_map: Dict[E.Expr, str] = {}
        drop_cols: List[str] = []
        for c in rh.columns:
            target = renames.get(c, c)
            exprs = rh.exprs_for_column(c)
            keep_exprs = [e for e in exprs if e not in lh]
            if keep_exprs:
                for e in keep_exprs:
                    new_map[e] = target
            elif target != c:
                drop_cols.append(target)
        # all rhs columns that were renamed but only duplicate lhs data get dropped;
        # join key columns from rhs are also dropped post-join
        header = RecordHeader(
            {**{e: lh.column(e) for e in lh.expressions}, **new_map},
            {**lh.paths, **rh.paths},
        )
        self._plan = (renames, new_map, drop_cols, header)
        return self._plan

    def _compute_header(self) -> RecordHeader:
        return self._analyze()[3]

    def _compute_table(self) -> Table:
        lhs, rhs = self.children
        renames, new_map, drop_cols, header = self._analyze()
        rt = rhs.table.rename(renames) if renames else rhs.table
        if self.kind == "cross":
            joined = lhs.table.join(rt, "cross", [])
        else:
            pairs = []
            for le, re_ in self.join_exprs:
                lc = lhs.header.column(le)
                rc = rhs.header.column(re_)
                pairs.append((lc, renames.get(rc, rc)))
            joined = lhs.table.join(rt, self.kind, pairs)
        # remove join-duplicate columns
        join_key_cols = []
        for le, re_ in self.join_exprs:
            rc = rhs.header.column(re_)
            rc2 = renames.get(rc, rc)
            keeps = new_map.values()
            if rc2 not in keeps and rc2 not in drop_cols and rc2 not in lhs.header.columns:
                join_key_cols.append(rc2)
        to_drop = [c for c in set(drop_cols) | set(join_key_cols) if c in joined.physical_columns]
        if to_drop:
            joined = joined.drop(to_drop)
        return joined

    def _key_sides(self) -> Tuple[Table, Table, List[Tuple[str, str]]]:
        """Both sides cut to their key columns, the right side renamed as
        ``_compute_table`` renames it, and the column pairs to join on."""
        lhs, rhs = self.children
        renames = self._analyze()[0]
        l_cols: List[str] = []
        r_cols: List[str] = []
        for le, re_ in self.join_exprs:
            l_cols.append(lhs.header.column(le))
            r_cols.append(rhs.header.column(re_))
        rt = rhs.table.select(list(dict.fromkeys(r_cols)))
        rt = rt.rename({c: renames[c] for c in set(r_cols) if c in renames})
        pairs = [(lc, renames.get(rc, rc)) for lc, rc in zip(l_cols, r_cols)]
        lt = lhs.table.select(list(dict.fromkeys(l_cols)))
        return lt, rt, pairs

    def row_count(self) -> Optional[int]:
        """Rows of an inner equi-join from its count phase alone
        (``Table.join_count``): a sharded tier's first exchange, the
        one-device join's probe. Nothing of the pairs is built — not the
        materialize (on the mesh a second exchange of keys and row numbers
        of both sides), not their compaction, not a gather of any column.
        In ``snb-sf100-mesh4.analytic-mesh`` that tail was about a second a
        chip of a 2.12 s pass, run after the answer had left, inside the
        next request (PR 28's trace; taken out in PR 29). None for the other
        kinds, and where the backend cannot count before it has the pairs
        (composite, string, float or mixed keys): ``key_join_size``."""
        if self.kind != "inner" or not self.join_exprs:
            return None
        lt, rt, pairs = self._key_sides()
        return lt.join_count(rt, "inner", pairs)

    def key_join_size(self) -> Optional[int]:
        """Rows of an inner equi-join from a join of its key columns alone,
        where ``row_count`` has no answer; None for the other kinds.
        ``table`` gathers every column of both sides for every pair, and a
        ``count(*)`` over the join reads none of them (PR 28: ten columns a
        side, 3.2M pairs, 1.9 of a pass's 3.2 seconds on every chip)."""
        if self.kind != "inner" or not self.join_exprs:
            return None
        lt, rt, pairs = self._key_sides()
        return lt.join(rt, "inner", pairs).size

    def _show_inner(self) -> str:
        pairs = ", ".join(
            f"{l.pretty_expr()}={r.pretty_expr()}" for l, r in self.join_exprs
        )
        return f"{self.kind} on [{pairs}]"


class UnionAllOp(RelationalOperator):
    """Union by aligned header expressions (reference ``TabularUnionAll``)."""

    def __init__(self, lhs: RelationalOperator, rhs: RelationalOperator):
        super().__init__(lhs, rhs)

    def _compute_header(self) -> RecordHeader:
        return self.children[0].header

    def _compute_table(self) -> Table:
        lhs, rhs = self.children
        lh, rh = lhs.header, rhs.header
        # map each lhs column onto the rhs column carrying the same expression
        pairs: Dict[str, str] = {}
        for e in lh.expressions:
            if e not in rh:
                raise RelationalError(
                    f"UNION branches differ: missing {e.pretty_expr()} on rhs"
                )
            lc, rc = lh.column(e), rh.column(e)
            if pairs.setdefault(lc, rc) != rc:
                raise RelationalError(
                    f"UNION branches map column {lc} ambiguously"
                )
        if len(set(pairs.values())) != len(pairs):
            raise RelationalError(
                "UNION requires a distinct rhs column per lhs column"
            )
        rt = rhs.table.select(list(pairs.values()))
        rt = rt.rename({rc: lc for lc, rc in pairs.items() if rc != lc})
        cols = lh.columns
        return lhs.table.select(cols).union_all(rt.select(cols))


def _static_value(expr: E.Expr, params: Dict[str, Any]):
    """Constant-fold a variable-free SKIP/LIMIT expression (literals,
    parameters, and arithmetic over them — ``SKIP 1 + 1``; reference
    ``SkipLimitAcceptance``). Anything mentioning a variable stays an
    error, matching openCypher's static requirement."""
    if isinstance(expr, E.Lit):
        return expr.value
    if isinstance(expr, E.Param):
        return params.get(expr.name)
    if isinstance(expr, E.Neg):
        v = _static_value(expr.expr, params)
        return -v if v is not None else None
    if isinstance(expr, (E.Add, E.Subtract, E.Multiply, E.Divide, E.Modulo)):
        l = _static_value(expr.lhs, params)
        r = _static_value(expr.rhs, params)
        if l is None or r is None:
            return None
        if isinstance(expr, E.Add):
            return l + r
        if isinstance(expr, E.Subtract):
            return l - r
        if isinstance(expr, E.Multiply):
            return l * r
        both_int = isinstance(l, int) and isinstance(r, int)
        if isinstance(expr, E.Divide):
            if both_int:
                if r == 0:
                    raise RelationalError("/ by zero")
                q = abs(l) // abs(r)  # Cypher int division truncates to zero
                return q if (l >= 0) == (r >= 0) else -q
            return l / r
        if both_int:
            if r == 0:
                raise RelationalError("% by zero")
            m = abs(l) % abs(r)
            return m if l >= 0 else -m
        return math.fmod(l, r)
    raise RelationalError(
        f"Expected a literal or parameter, got {expr.pretty_expr()}"
    )

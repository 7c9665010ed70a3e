"""Graph algorithms called as procedures: ``CALL algo.bfs(...)``,
``CALL algo.wcc(...)`` (docs/cypher-surface.md, "Procedures").

The registry: a procedure's name, its typed arguments and its typed yields.
Every procedure yields ``node`` (a real node variable: one row per node of
the graph) and one value of that node. The call leads its query and takes
no input rows; its arguments are literals or parameters.

One implementation per backend, through the table class's
``run_procedure`` hook: the local oracle runs ``run_local`` here — plain
NumPy, a frontier loop for BFS and union-find for WCC — and the TPU backend
runs one device program per call (``backend/tpu/procedures.py``).

Semantics, as LDBC Graphalytics states them for an undirected graph, with
two departures written down:

* both procedures treat the relationship type as undirected;
* ``algo.bfs(source, type) YIELD node, depth``: the number of hops from
  ``source``; null where the source cannot reach the node (Graphalytics
  writes Long.MAX there);
* ``algo.wcc(type) YIELD node, component``: the smallest element id in the
  node's weakly connected component (Graphalytics allows any label that is
  the same inside a component).

``source`` is a node's element id (``id(n)``) or a node value; anything
else, or an id no node of the graph has, raises ``ProcedureError``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Tuple

import numpy as np

from ..api import types as T
from ..api.values import Node
from ..errors import TpuCypherError
from ..ir import expr as E
from ..ir.builder import UnsupportedFeatureError


class ProcedureError(TpuCypherError):
    """A procedure argument that is not what the procedure takes: a source
    that is not a node of the graph, a relationship type that is not a
    string. A client error; nothing is retried."""


@dataclass(frozen=True)
class Procedure:
    name: str
    args: Tuple[Tuple[str, str], ...]  # (name, kind): "node" or "string"
    value: Tuple[str, T.CypherType]  # the yield beside ``node``

    @property
    def yields(self) -> Tuple[Tuple[str, T.CypherType], ...]:
        return (("node", T.CTNodeType(())), self.value)


BFS = Procedure("algo.bfs", (("source", "node"), ("type", "string")),
                ("depth", T.CTInteger.nullable))
WCC = Procedure("algo.wcc", (("type", "string"),), ("component", T.CTInteger))
PROCEDURES: Dict[str, Procedure] = {p.name: p for p in (BFS, WCC)}


def lookup(name: str) -> Procedure:
    proc = PROCEDURES.get(name.lower())
    if proc is None:
        raise UnsupportedFeatureError(
            f"CALL {name}: unknown procedure (known: {', '.join(sorted(PROCEDURES))})"
        )
    return proc


def check_args(proc: Procedure, values: List[Any]) -> Dict[str, Any]:
    """The call's argument values by name, a node as its element id."""
    out: Dict[str, Any] = {}
    for (name, kind), v in zip(proc.args, values):
        if kind == "string":
            if not isinstance(v, str):
                raise ProcedureError(
                    f"{proc.name}: {name} must be a relationship type name, got {v!r}"
                )
        else:
            if isinstance(v, Node):
                v = v.id
            if not isinstance(v, int) or isinstance(v, bool):
                raise ProcedureError(
                    f"{proc.name}: {name} must be a node (or its id), got {v!r}"
                )
        out[name] = v
    return out


def source_position(proc: Procedure, sorted_ids: np.ndarray, source: int) -> int:
    """Where ``source`` stands among the graph's sorted node ids."""
    pos = int(np.searchsorted(sorted_ids, source))
    if pos >= len(sorted_ids) or int(sorted_ids[pos]) != source:
        raise ProcedureError(f"{proc.name}: source {source} is not a node of the graph")
    return pos


# ---------------------------------------------------------------------------
# the plain NumPy algorithms (the local oracle's implementation)
# ---------------------------------------------------------------------------


def bfs_depths(n: int, s: np.ndarray, d: np.ndarray, source: int) -> np.ndarray:
    """int64 per node position: hops from ``source`` over the edges
    ``s[i] - d[i]`` taken both ways, -1 where it cannot reach. A frontier a
    level: the neighbours of every frontier node at once."""
    a = np.concatenate([s, d]).astype(np.int64)
    b = np.concatenate([d, s]).astype(np.int64)
    order = np.argsort(a, kind="stable")
    a, b = a[order], b[order]
    row_ptr = np.searchsorted(a, np.arange(n + 1))
    depth = np.full(n, -1, np.int64)
    depth[source] = 0
    frontier = np.array([source], np.int64)
    level = 0
    while len(frontier):
        lo = row_ptr[frontier]
        counts = row_ptr[frontier + 1] - lo
        first = np.cumsum(counts) - counts  # where each node's lanes begin
        idx = np.arange(counts.sum()) + np.repeat(lo - first, counts)
        near = np.unique(b[idx])
        frontier = near[depth[near] < 0]
        level += 1
        depth[frontier] = level
    return depth


def wcc_roots(n: int, s: np.ndarray, d: np.ndarray) -> np.ndarray:
    """int64 per node position: the smallest position in its component,
    by union-find (the smaller root wins every union, paths halved)."""
    parent = list(range(n))

    def find(x: int) -> int:
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for u, v in zip(s.tolist(), d.tolist()):
        ru, rv = find(u), find(v)
        if ru != rv:
            parent[max(ru, rv)] = min(ru, rv)
    return np.array([find(x) for x in range(n)], np.int64)


def run_local(proc: Procedure, graph, ctx, table, id_col: str, out_col: str,
              args: Dict[str, Any]):
    """``proc`` over ``table`` (every node of ``graph`` once, its ids in
    ``id_col``) with its value in ``out_col``: the relationships of the type
    read from the graph's own scan, the algorithm in NumPy over the nodes'
    positions among the sorted ids."""
    ids = np.asarray(table.column_values(id_col), dtype=np.int64)
    sorted_ids = np.sort(ids)
    at = np.searchsorted(sorted_ids, ids)  # each row's position
    rel = graph.scan_operator(
        "__procedure_rel", T.CTRelationshipType(frozenset({args["type"]})), ctx
    )
    h, rt = rel.header, rel.table
    var = E.Var("__procedure_rel")
    s_ids = np.asarray(rt.column_values(h.column(E.StartNode(var))), dtype=np.int64)
    d_ids = np.asarray(rt.column_values(h.column(E.EndNode(var))), dtype=np.int64)
    s, d = np.searchsorted(sorted_ids, s_ids), np.searchsorted(sorted_ids, d_ids)
    n = len(ids)
    if proc is BFS:
        source = source_position(proc, sorted_ids, args["source"])
        depth = bfs_depths(n, s, d, source)[at]
        values = [int(x) if x >= 0 else None for x in depth]
    else:
        values = [int(x) for x in sorted_ids[wcc_roots(n, s, d)][at]]
    return type(table)({**table._cols, out_col: values}, table.size)

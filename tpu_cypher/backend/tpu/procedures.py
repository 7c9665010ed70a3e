"""The TPU backend's procedures (``relational/procedures.py``): one device
program per call over the CSR the count chains walk.

``algo.bfs`` runs ``jit_ops.bfs_levels`` and ``algo.wcc``
``jit_ops.wcc_labels``: each reaches its fixed point inside the program
(``lax.while_loop``, the convergence test on the device) over both
orientations of the type's CSR (``GraphIndex.csr``). The call reads its
counts back once, after the program has ended: no host sync per level or
per round. A call is the span ``procedure:<name>`` (kind ``kernel``,
attributes ``iterations``, ``edge_lanes`` — the lanes the steps went over:
for BFS the width of its push steps; for WCC the first lanes of every row
it links once, and the lanes of the rows outside the largest component
those links form once a round that reads them — and ``orientations``; for
WCC also ``rows_outside``, the rows it queued, and ``outside_rounds``) and
moves ``tpu_cypher_procedure_iterations_total`` /
``tpu_cypher_procedure_edge_lanes_total{procedure=}`` and, for WCC,
``tpu_cypher_procedure_rows_outside_total``.

A mesh session declines: a sharded form is not written, and the call raises
``UnsupportedFeatureError`` and counts ``mesh_declines{op="procedure"}``.
"""

from __future__ import annotations

import jax
import numpy as np

from ...ir.builder import UnsupportedFeatureError
from ...obs import trace as _obs_trace
from ...obs.metrics import REGISTRY as _REGISTRY
from ...parallel.mesh import current_mesh, mesh_size, note_decline
from ...relational import procedures as P
from ...runtime.faults import fault_point
from . import jit_ops as J
from .column import I64, Column
from .graph_index import GraphIndex

ITERATIONS = _REGISTRY.counter(
    "tpu_cypher_procedure_iterations_total",
    "steps the procedures' device programs ran to their fixed point: BFS "
    "levels (the last finds no new node), WCC hooking rounds over the sampled "
    "lanes and then over the rows outside their largest component (the last "
    "of each moves no root)",
    labels=("procedure",),
)
EDGE_LANES = _REGISTRY.counter(
    "tpu_cypher_procedure_edge_lanes_total",
    "edge lanes the procedures' steps went over: BFS the width of its push "
    "steps; WCC the sampled lanes at the head of every row once, and the "
    "lanes of the rows outside their largest component once a round",
    labels=("procedure",),
)
ROWS_OUTSIDE = _REGISTRY.counter(
    "tpu_cypher_procedure_rows_outside_total",
    "CSR rows, of either orientation, of the nodes outside the largest "
    "component WCC's sampled lanes form: the rows whose every lane it read",
    labels=("procedure",),
)
for _name in ("bfs", "wcc"):  # exported from the start
    ITERATIONS.inc(0, procedure=_name)
    EDGE_LANES.inc(0, procedure=_name)
ROWS_OUTSIDE.inc(0, procedure="wcc")


def _orientations(gi: GraphIndex, types_key, ctx):
    """Each CSR orientation of the type that holds an edge, as the programs
    take it: ``(row_ptr, col_idx)``."""
    return tuple(
        gi.csr(types_key, reverse, ctx)[:2]
        for reverse in (False, True)
        if gi.csr_lane_count(types_key, reverse, ctx)
    )


def run(proc: P.Procedure, graph, ctx, table, id_col: str, out_col: str, args):
    """``proc``'s value of every row of ``table`` (the scan of every node,
    its ids in ``id_col``) in the new column ``out_col``."""
    name = proc.name.split(".")[-1]
    with _obs_trace.span(f"procedure:{name}", kind="kernel") as sp:
        if current_mesh() is not None and mesh_size() > 1:
            note_decline("procedure", "sharded")
            raise UnsupportedFeatureError(
                f"CALL {proc.name}: a mesh session has no sharded form of it"
            )
        gi = GraphIndex.of(graph)
        dev_ids, host_ids = gi.node_ids(ctx)
        types_key = gi.types_key((args["type"],))
        col = table._cols[id_col]
        out = dict(table._cols)
        if proc is P.WCC and not len(host_ids):  # no node: nothing to label
            out[out_col] = Column(I64, col.data, col.valid, pad=col.pad)
            return type(table)(out, table.size)
        orients = _orientations(gi, types_key, ctx)
        if proc is P.BFS:
            source = P.source_position(proc, host_ids, args["source"])
            values, valid, steps, lanes = J.bfs_levels(
                orients, np.int32(source), dev_ids, col.data, col.valid,
                step=J.PUSH_LANES,
            )
            outside = None
        else:
            values, valid, steps, lanes, outside = J.wcc_labels(
                orients, dev_ids, col.data, col.valid, step=J.PUSH_LANES,
            )
        fault_point("procedure")  # the counts are read back
        with _obs_trace.sync("procedure"):
            steps, lanes, outside = jax.device_get((steps, lanes, outside))
        iterations, lanes = int(np.sum(steps)), int(lanes)
        sp.note("iterations", iterations)
        sp.note("edge_lanes", lanes)
        sp.note("orientations", len(orients))
        ITERATIONS.inc(iterations, procedure=name)
        EDGE_LANES.inc(lanes, procedure=name)
        if outside is not None:
            sp.note("rows_outside", int(outside))
            sp.note("outside_rounds", int(steps[1]))
            ROWS_OUTSIDE.inc(int(outside), procedure=name)
        out[out_col] = Column(I64, values, valid, pad=col.pad)
        return type(table)(out, table.size)

"""The TPU backend's procedures (``relational/procedures.py``): one device
program per call over the CSR the count chains walk.

``algo.bfs`` runs ``jit_ops.bfs_levels`` and ``algo.wcc``
``jit_ops.wcc_labels``: each reaches its fixed point inside the program
(``lax.while_loop``, the convergence test on the device) over both
orientations of the type's CSR (``GraphIndex.csr`` / ``csr_row_span``; WCC
also its lane rows, ``GraphIndex.csr_rows``). The call reads one number
back, how many steps ran, and only after the program has ended: no host sync
per level or per round. A call is the span ``procedure:<name>`` (kind
``kernel``, attributes ``iterations``, ``edge_lanes`` — the lanes the steps
went over: for WCC both orientations' lanes, a bucket's pad included, times
the rounds; for BFS the width of its push steps, read back with the step
count — and ``orientations``) and
moves ``tpu_cypher_procedure_iterations_total`` /
``tpu_cypher_procedure_edge_lanes_total{procedure=}``.

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
    "levels (the last finds no new node), WCC rounds (the last moves no label)",
    labels=("procedure",),
)
EDGE_LANES = _REGISTRY.counter(
    "tpu_cypher_procedure_edge_lanes_total",
    "edge lanes the procedures' steps went over: WCC both CSR orientations' "
    "lanes, a bucket's pad included, once a round; BFS the width of its push "
    "steps",
    labels=("procedure",),
)
for _name in ("bfs", "wcc"):  # exported from the start
    ITERATIONS.inc(0, procedure=_name)
    EDGE_LANES.inc(0, procedure=_name)


def _orientations(gi: GraphIndex, types_key, ctx, with_rows: bool):
    """Each CSR orientation of the type that holds an edge, as the programs
    take it."""
    out = []
    for reverse in (False, True):
        if not gi.csr_lane_count(types_key, reverse, ctx):
            continue
        rp, ci, _ = gi.csr(types_key, reverse, ctx)
        window, start = gi.csr_row_span(types_key, reverse, ctx).window
        rows = (gi.csr_rows(types_key, reverse, ctx),) if with_rows else ()
        out.append((rp, ci) + rows + (window, start))
    return tuple(out)


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
        if proc is P.BFS:
            source = P.source_position(proc, host_ids, args["source"])
            orients = _orientations(gi, types_key, ctx, with_rows=False)
            values, valid, steps, width = J.bfs_levels(
                orients, np.int32(source), dev_ids, col.data, col.valid,
                step=J.BFS_PUSH_LANES,
            )
        else:
            orients = _orientations(gi, types_key, ctx, with_rows=True)
            values, valid, steps = J.wcc_labels(
                orients, dev_ids, col.data, col.valid
            )
            width = None
        fault_point("procedure")  # the step count is read back
        with _obs_trace.sync("procedure"):
            iterations, width = jax.device_get((steps, width))
        iterations = int(iterations)
        if width is None:  # WCC reads every lane of both orientations a round
            lanes = sum(int(o[1].shape[0]) for o in orients) * iterations
        else:
            lanes = int(width)
        sp.note("iterations", iterations)
        sp.note("edge_lanes", lanes)
        sp.note("orientations", len(orients))
        ITERATIONS.inc(iterations, procedure=name)
        EDGE_LANES.inc(lanes, procedure=name)
        out[out_col] = Column(I64, values, valid, pad=col.pad)
        return type(table)(out, table.size)

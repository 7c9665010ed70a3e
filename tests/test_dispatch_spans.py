"""The ``dispatch`` leaves of the span tree (``obs.trace.dispatch`` round
each call of a jitted program from host code), over the benchmark's own
query shapes at a small size on the CPU: the seven of the ``snb-*`` mixes
and the five LSQB queries. A leaf is transparent to notes (every operator
span's ``rows_true`` / ``rows_padded`` / ``rows_pairs`` / ``sites`` /
``agg_form`` / ``order_limit`` / ``count_from`` is what it is without the
leaves), lies inside its parent, never overlaps or contains a ``sync``,
and is counted in ``tpu_cypher_program_dispatches_total``; a warm repeat
traces nothing (``tpu_cypher_jit_traces_total``), compiles nothing and
syncs as before, a fresh shape leaves ``retraced`` on the operator that
caused it; the calibration file's write is a ``persist`` step with two
counters; and the wire payload carries a rendered tree only where it
leaves the process."""

import asyncio
import importlib
import json
import os
import sys
import types

import pytest

from test_serve import _client, _http

from tpu_cypher.backend.tpu import bucketing
from tpu_cypher.obs import trace as OT
from tpu_cypher.obs.metrics import REGISTRY

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIPBENCH = os.path.join(ROOT, "chipbench")
SEED = 3_600_000_123

# shape -> (generator, loader): the cells' own files
SNB = ("gen_snb", "load_snb")
CHAIN = ("gen_lsqb", "load_lsqb")
TREE = ("gen_lsqb_full", "load_lsqb_full")
CASES = {
    "two_hop_count": SNB, "one_hop_count": SNB, "grouped_aggregate": SNB,
    "scan_filter": SNB, "order_by_limit": SNB, "distinct_values": SNB,
    "sort_probe_join": SNB,
    "lsqb_q6": CHAIN, "lsqb_q9": CHAIN,
    "lsqb_q1": TREE, "lsqb_q4": TREE, "lsqb_q7": TREE,
}
NOTES = ("rows_true", "rows_padded", "rows_pairs", "sites", "agg_form",
         "order_limit", "count_from")


def _flat(prefix):
    return sum(v for k, v in REGISTRY.flat().items() if k.startswith(prefix))


@pytest.fixture(scope="module")
def bench():
    """The benchmark's own modules, by the names they import each other,
    and one graph a generator."""
    from tpu_cypher import CypherSession
    from tpu_cypher.relational.session import PropertyGraph

    added = [CHIPBENCH, os.path.join(CHIPBENCH, "shapes"),
             os.path.join(CHIPBENCH, "readers")]
    sys.path[:0] = added
    bucketing.MODE.set("pow2")  # as the cells' configurations set it
    try:
        session = CypherSession.tpu()
        graphs = {}
        for gen, load in (SNB, CHAIN, TREE):
            arrays = importlib.import_module(gen).snb_arrays(600, 12_000, SEED)
            graphs[gen] = PropertyGraph(
                session, importlib.import_module(load).load(session, arrays))
        queries = {s: importlib.import_module(s).QUERY for s in CASES}
        yield types.SimpleNamespace(
            session=session, graphs=graphs, queries=queries,
            idle_by_kind=importlib.import_module("idle_by_kind"),
            idle_by_span=importlib.import_module("idle_by_span"),
            trace_reduce=importlib.import_module("trace_reduce"))
    finally:
        bucketing.MODE.reset()
        for p in added:
            sys.path.remove(p)


def _run(bench, shape):
    """One execution: the tree, the answer, and how far the counters moved."""
    before = {p: _flat(p) for p in (
        "tpu_cypher_program_dispatches_total", "tpu_cypher_host_syncs_total",
        "tpu_cypher_jit_traces_total", "tpu_cypher_xla_compiles_total")}
    result = bench.graphs[CASES[shape][0]].cypher(bench.queries[shape])
    rows = [dict(r) for r in result.records.collect()]
    trace = result.profile(execute=False).trace
    moved = {p.replace("tpu_cypher_", "").replace("_total", ""): _flat(p) - v
             for p, v in before.items()}
    return types.SimpleNamespace(trace=trace, rows=rows, **moved)


@pytest.fixture(scope="module")
def runs(bench):
    """shape -> (warm run with the leaves, warm run without them), made
    once: cold, warm, then the program wrapper stepped aside."""
    made = {}

    def of(shape):
        if shape not in made:
            _run(bench, shape)  # cold: compiles
            with_leaves = _run(bench, shape)
            call = OT.Program.__call__
            OT.Program.__call__ = lambda self, *a, **k: self.__wrapped__(*a, **k)
            try:
                without = _run(bench, shape)
            finally:
                OT.Program.__call__ = call
            made[shape] = (with_leaves, without)
        return made[shape]

    return of


def _noted(trace):
    """Every span but the leaves, preorder: name, kind and the attrs that
    feedback, analysis/shapes and the shape facts read."""
    return [(sp.name, sp.kind, {k: sp.attrs[k] for k in NOTES if k in sp.attrs})
            for sp in trace.spans() if sp.kind != "dispatch"]


@pytest.mark.parametrize("shape", CASES)
def test_a_dispatch_leaf_is_transparent_to_notes(shape, runs):
    with_leaves, without = runs(shape)
    assert with_leaves.rows == without.rows and with_leaves.rows
    assert _noted(with_leaves.trace) == _noted(without.trace)
    leaves = [sp for sp in with_leaves.trace.spans() if sp.kind == "dispatch"]
    assert leaves and not any(sp.attrs or sp.children for sp in leaves)
    assert not [sp for sp in without.trace.spans() if sp.kind == "dispatch"]
    # what feedback.observe folds in sits on operators, never on a leaf
    # or a step inside one (the lattice is on: pow2, as in the cells)
    padded = [sp for sp in with_leaves.trace.spans() if "rows_padded" in sp.attrs]
    assert all(sp.kind == "operator" for sp in padded)
    if shape != "grouped_aggregate" and shape != "order_by_limit":
        assert padded  # a count's one-row table is a rounding of its own


@pytest.mark.parametrize("shape", CASES)
def test_leaves_lie_inside_their_parent_and_apart_from_every_sync(shape, runs):
    with_leaves, _ = runs(shape)
    seen = 0
    for parent in with_leaves.trace.spans():
        leaves = [c for c in parent.children if c.kind == "dispatch"]
        syncs = [c for c in parent.children if c.kind == "sync"]
        for leaf in leaves:
            seen += 1
            assert leaf.name.startswith("jit_")
            assert parent.t0 <= leaf.t0 <= leaf.t1 <= parent.t1
            assert leaf.seconds == pytest.approx(leaf.t1 - leaf.t0)
            for sync in syncs:  # neither overlaps nor contains a read
                assert leaf.t1 <= sync.t0 or sync.t1 <= leaf.t0
        ends = sorted((c.t0, c.t1) for c in parent.children if c.t0 is not None)
        assert all(a[1] <= b[0] for a, b in zip(ends, ends[1:]))  # in order
    assert seen


@pytest.mark.parametrize("shape", CASES)
def test_the_counter_moves_by_the_leaves_and_a_warm_repeat_traces_nothing(
        shape, runs):
    with_leaves, without = runs(shape)
    leaves = [sp for sp in with_leaves.trace.spans() if sp.kind == "dispatch"]
    assert with_leaves.program_dispatches == len(leaves)
    assert without.program_dispatches == 0
    # no device sync added, no retrace and no compile on the warm path
    assert with_leaves.host_syncs == without.host_syncs
    assert with_leaves.jit_traces == 0 and without.jit_traces == 0
    assert with_leaves.xla_compiles == 0
    assert not [sp for sp in with_leaves.trace.spans() if "retraced" in sp.attrs]


def test_a_fresh_shape_counts_a_trace_and_names_it_on_the_operator(bench, runs):
    runs("order_by_limit")
    graph = bench.graphs["gen_snb"]
    query = bench.queries["order_by_limit"].replace("LIMIT 10", "LIMIT 7")
    assert query != bench.queries["order_by_limit"]
    before = (_flat("tpu_cypher_jit_traces_total"),
              _flat("tpu_cypher_jit_trace_seconds_total"))
    result = graph.cypher(query)
    assert len(result.records.collect()) == 7
    assert _flat("tpu_cypher_jit_traces_total") - before[0] >= 1
    assert _flat("tpu_cypher_jit_trace_seconds_total") > before[1]
    spans = result.profile(execute=False).trace.spans()
    (limit,) = [sp for sp in spans if sp.name == "LimitOp"]
    # k is a static argument of the gather: this operator retraced it, once
    # (the helpers traced inside it are part of it)
    assert limit.attrs["retraced"] == {"cols_take": 1}
    assert all(sp.kind == "operator" for sp in spans if "retraced" in sp.attrs)
    again = graph.cypher(query)
    again.records.collect()
    assert _flat("tpu_cypher_jit_traces_total") - before[0] == sum(
        n for sp in spans for n in sp.attrs.get("retraced", {}).values())


def test_a_program_called_while_another_is_traced_is_no_dispatch():
    import jax
    import jax.numpy as jnp

    from tpu_cypher.backend.tpu import jit_ops as J

    assert isinstance(J.mask_sum, OT.Program)
    assert J.mask_sum.program == "jit_mask_sum"
    assert callable(J.mask_sum.lower) and callable(J.mask_sum.clear_cache)
    assert OT.program(J.mask_sum) is J.mask_sum

    @jax.jit
    def outer(m):
        return J.mask_sum(m) + 1

    mask = jnp.arange(8) % 2 == 0
    J.mask_sum(mask)  # warm: the call under the span below traces nothing
    before = _flat("tpu_cypher_program_dispatches_total")
    assert int(outer(mask)) == 5
    assert _flat("tpu_cypher_program_dispatches_total") == before
    trace = OT.QueryTrace()
    with OT.activate(trace), OT.span("op", kind="operator") as op:
        assert int(J.mask_sum(mask)) == 4
        OT.note("seen", 1)  # lands on the operator, not on the leaf
    assert _flat("tpu_cypher_program_dispatches_total") == before + 1
    (leaf,) = op.children
    assert (leaf.name, leaf.kind, leaf.attrs) == ("jit_mask_sum", "dispatch", {})
    assert op.attrs == {"seen": 1}


def test_render_prints_the_leaves_of_a_span_as_one_line(runs):
    with_leaves, _ = runs("grouped_aggregate")
    text = OT.render(with_leaves.trace)
    leaves = [sp for sp in with_leaves.trace.spans() if sp.kind == "dispatch"]
    holders = [sp for sp in with_leaves.trace.spans()
               if any(c.kind == "dispatch" for c in sp.children)]
    assert "jit_" not in text
    assert text.count("`- dispatch x") == len(holders) < len(leaves)
    rendered = json.dumps(with_leaves.trace.to_dict())
    assert rendered.count('"kind": "dispatch"') == len(leaves)
    assert with_leaves.trace.to_dict()["schema_version"] == 2 == OT.SCHEMA_VERSION


@pytest.mark.parametrize("case", ["no path", "nothing to fold in", "written"])
def test_persist_span_and_counters_move_when_and_only_when_the_file_is_written(
        case, tmp_path, monkeypatch):
    from tpu_cypher.optimizer import feedback

    path = tmp_path / "optimizer_calibration.json"
    monkeypatch.setattr(feedback, "_persist_path",
                        lambda: None if case == "no path" else str(path))
    names = ("tpu_cypher_feedback_persist_seconds_total",
             "tpu_cypher_feedback_persist_bytes_total")
    assert all(n in REGISTRY.flat() for n in names)  # exported from the start
    before = [_flat(n) for n in names]
    trace = OT.QueryTrace()
    with OT.activate(trace):
        with OT.span("execute", kind="phase"):
            with OT.span("FilterOp", kind="operator"):
                if case != "nothing to fold in":  # a rounding of the lattice
                    OT.note_rows(700, 1024)
        with OT.span("feedback", kind="phase") as phase:
            # as session._observe_feedback calls it; a graph without
            # statistics calibrates under the fingerprint "default"
            feedback.observe(trace, object(), None)
    moved = [_flat(n) - b for n, b in zip(names, before)]
    if case != "written":
        assert not phase.children and moved == [0, 0] and not path.exists()
        return
    (sp,) = phase.children
    assert (sp.name, sp.kind) == ("persist", "step")
    stored = json.loads(path.read_text())
    assert "FilterOp" in json.dumps(stored)
    assert sp.attrs == {"bytes": path.stat().st_size,
                        "fingerprints": len(stored)}
    assert moved[1] == path.stat().st_size and 0 < moved[0] <= sp.seconds


def test_the_payload_carries_a_rendered_tree_only_where_it_leaves_the_process(
        bench):
    from tpu_cypher.serve import QueryServer, wire

    graph = bench.graphs["gen_snb"]
    query = bench.queries["order_by_limit"]
    # an engine worker's reply: no parent, the tree goes out rendered
    alone = wire.execute_payload(bench.session, graph, query)
    kinds = json.dumps(alone["profile"])
    assert alone["profile"]["schema_version"] == 2
    assert '"kind": "dispatch"' in kinds and '"LimitOp"' in kinds
    # the one-process server: the tree hangs under the request's own span
    # and nothing is rendered on the lane
    root = OT.QueryTrace("request", kind="serve")
    under = root.root.add("dispatch", "serve", root.root.t0)
    grafted = wire.execute_payload(bench.session, graph, query, parent=under)
    assert "profile" not in grafted and grafted["rows"] == alone["rows"]
    assert [c.name for c in under.children] == ["engine"]
    meta, stream = wire.open_stream(bench.session, graph, query, parent=under)
    assert "profile" not in meta and meta["total_rows"] == 10
    assert "profile" in wire.open_stream(bench.session, graph, query)[0]

    async def served():
        srv = QueryServer(bench.session, port=0, cache_bytes=0)
        srv.register_graph("g", graph)
        async with srv:
            msgs = await _client(srv.host, srv.port, [
                {"op": "submit", "id": "d1", "graph": "g", "query": query}])
            _, body = await _http(srv.host, srv.port, "/queries/d1")
            return msgs, json.loads(body), srv._records["d1"]["profile"]

    msgs, record, kept = asyncio.run(served())
    assert [m["type"] for m in msgs][-1] == "done"
    # the record keeps the tree itself; /queries/<id> renders the whole of it
    assert isinstance(kept, OT.QueryTrace)
    assert record["profile"] == kept.to_dict() == OT.recent()[-1]
    dispatch = next(c for c in record["profile"]["root"]["children"]
                    if c["name"] == "dispatch")
    engine = next(c for c in dispatch["children"] if c["name"] == "engine")
    assert '"kind": "dispatch"' in json.dumps(engine)
    assert '"jit_order_permutation"' in json.dumps(engine)


def test_the_cluster_front_end_still_grafts_the_workers_rendered_tree(bench):
    from tpu_cypher.serve import QueryServer, wire
    from tpu_cypher.serve.cluster import ClusterServer

    reply = wire.execute_payload(
        bench.session, bench.graphs["gen_snb"], bench.queries["scan_filter"])

    class WorkerBehindARouter:
        async def submit(self, **kw):
            return reply

    async def run():
        srv = ClusterServer(workers=1, port=0, batch_window_ms=0, cache_bytes=0)
        srv.register_graph("g", "CREATE (:P {id: 1})-[:K]->(:P {id: 2})")
        srv.router = WorkerBehindARouter()
        await QueryServer.start(srv)
        try:
            await _client(srv.host, srv.port, [
                {"op": "submit", "id": "c", "graph": "g",
                 "query": bench.queries["scan_filter"]}])
            _, body = await _http(srv.host, srv.port, "/queries/c")
        finally:
            await QueryServer.stop(srv)
        return json.loads(body)

    record = asyncio.run(run())
    dispatch = next(c for c in record["profile"]["root"]["children"]
                    if c["name"] == "dispatch")
    (route,) = dispatch["children"]
    assert route["children"] == [{**reply["profile"]["root"], "clock": "worker"}]
    assert '"kind": "dispatch"' in json.dumps(route["children"])


def test_idle_by_kind_partitions_what_idle_by_span_attributes(bench, monkeypatch):
    """The benchmark's new reader on one hand-made window: an operator with
    a dispatch leaf, a step and a sync inside it, the device busy over
    [100.1, 100.2] and [100.5, 100.9] of the slice [100, 101]."""
    shift = 90.0

    def span(name, kind, lo, hi, *children):
        return {"name": name, "kind": kind, "start_s": lo - 100.06,
                "seconds": hi - lo, "children": list(children)}

    log = [{"schema_version": 2, "start_perf_s": 100.06 - shift, "root": {
        **span("request", "serve", 100.06, 100.94,
               span("execute", "phase", 100.12, 100.70,
                    span("LimitOp", "operator", 100.13, 100.69,
                         span("jit_order_permutation", "dispatch", 100.18, 100.26),
                         span("depad", "step", 100.27, 100.31),
                         span("order", "sync", 100.33, 100.45)))),
        "attrs": {"id": "w-0-1"}}}]
    monkeypatch.setattr(OT, "recent", lambda: list(log))
    client = sys.modules["client"]
    requests = [client.Request(0, "a", 0, f"w-0-{k}", submitted=lo - shift,
                               finished=hi - shift)
                for k, (lo, hi) in enumerate(((99.05, 99.93), (100.05, 100.95)))]
    w = types.SimpleNamespace(
        trace=bench.trace_reduce.Trace(
            slice=(100.0, 101.0), busy=[[(100.1, 100.2), (100.5, 100.9)]],
            modules={}, requests=[("a", 100.05, 100.95)]),
        requests=requests, passes=2, counters={})
    by_kind = bench.idle_by_kind.table(w)["by_kind"]
    by_leaf = bench.idle_by_span.table(w)["by_leaf"]
    assert sum(by_kind.values()) == pytest.approx(sum(by_leaf.values()), abs=1e-9)
    assert sum(by_kind.values()) == pytest.approx(0.5, abs=1e-9)
    read = bench.idle_by_kind.read
    assert read(w, kinds=["dispatch"]) == pytest.approx(0.06)
    assert read(w, kinds=["step"]) == pytest.approx(0.04)
    assert read(w, kinds=["sync"]) == pytest.approx(0.12)
    assert read(w, kinds=["operator"]) == pytest.approx(0.01 + 0.02 + 0.05)
    assert by_kind["unattributed"] == pytest.approx(0.12)

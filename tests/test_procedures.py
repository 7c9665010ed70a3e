"""Procedures: ``CALL algo.bfs(source, type) YIELD node, depth`` and ``CALL
algo.wcc(type) YIELD node, component`` (``relational/procedures.py``).

The clause through the parser, the IR, the logical and relational planners,
with renames, ``WITH``, aggregation and ``ORDER BY`` after it; the TPU
session (one device program per call, its fixed point reached on the
device: ``jit_ops.bfs_levels`` / ``wcc_labels``) against the local oracle
(NumPy: a frontier loop, union-find) against a brute force written here (a
queue and a disjoint-set forest over Python lists), on seeded Kronecker
graphs and on graphs built for a case: disconnected, with vertices no edge
reaches, with parallel edges and self-loops, of one vertex, the source in a
small component, a hub; push steps far narrower than the graph, so that
rows run across them. The typed errors; a second source that compiles nothing;
the span and the two counters; host syncs that do not grow with the number
of levels."""

import collections

import jax
import numpy as np
import pytest

from tpu_cypher import CypherSession
from tpu_cypher.api import types as T
from tpu_cypher.backend.tpu import bucketing
from tpu_cypher.backend.tpu import jit_ops as J
from tpu_cypher.frontend.parser import parse as parse_cypher
from tpu_cypher.io.ldbc import graph_from_tables
from tpu_cypher.ir import blocks as B
from tpu_cypher.ir.builder import IRBuilderContext, UnsupportedFeatureError, build_ir
from tpu_cypher.logical import ops as L
from tpu_cypher.logical.planner import plan_logical
from tpu_cypher.obs.metrics import REGISTRY
from tpu_cypher.parallel.mesh import make_row_mesh, use_mesh
from tpu_cypher.relational.procedures import ProcedureError
from tpu_cypher.relational.session import PropertyGraph

ITERATIONS = "tpu_cypher_procedure_iterations_total{procedure=%s}"
EDGE_LANES = "tpu_cypher_procedure_edge_lanes_total{procedure=%s}"
ROWS_OUTSIDE = "tpu_cypher_procedure_rows_outside_total{procedure=%s}"
SYNCS = "tpu_cypher_host_syncs_total"
DECLINE = "tpu_cypher_mesh_declines_total{op=procedure,reason=sharded}"

BFS_ROWS = ("CALL algo.bfs($source, 'EDGE') YIELD node, depth "
            "RETURN node.id AS v, depth ORDER BY v")
WCC_ROWS = ("CALL algo.wcc('EDGE') YIELD node, component "
            "RETURN node.id AS v, component ORDER BY v")
BFS_SUMMARY = ("CALL algo.bfs($source, 'EDGE') YIELD node, depth "
               "RETURN depth, count(*) AS vertices, sum(node.id) AS id_sum ORDER BY depth")
WCC_SUMMARY = ("CALL algo.wcc('EDGE') YIELD node, component "
               "WITH component, count(*) AS size "
               "RETURN size, count(*) AS components, sum(component) AS id_sum ORDER BY size")


# -- graphs ----------------------------------------------------------------


def kronecker(scale, seed, keep_raw=False):
    """Graph500's Kronecker edges (initiator 0.57 / 0.19 / 0.19 / 0.05,
    labels permuted) over ids 1000 + label; cleaned as Graphalytics does —
    undirected, each edge once, no self-loop, no isolated vertex — unless
    ``keep_raw``, which keeps the drawn rows as they come, loops and
    parallel pairs included."""
    rng = np.random.default_rng(seed)
    n, m = 1 << scale, 16 << scale
    u = rng.random((scale, m))
    i = ((u >= 0.76) << np.arange(scale)[:, None]).sum(0)
    j = ((((u >= 0.57) & (u < 0.76)) | (u >= 0.95)) << np.arange(scale)[:, None]).sum(0)
    label = rng.permutation(n)
    s, d = label[i], label[j]
    if not keep_raw:
        lo, hi = np.minimum(s, d), np.maximum(s, d)
        keys = np.unique((lo * n + hi)[lo != hi])
        s, d = keys // n, keys % n
    ids = np.unique(np.concatenate([s, d])) + 1000
    return ids, s + 1000, d + 1000


def case_graph(case):
    """(vertex ids, edge sources, edge targets, the BFS source) of a case."""
    if case.startswith("kronecker"):
        scale = int(case.split("-")[1])
        ids, s, d = kronecker(scale, 3900 + scale)
        return ids, s, d, int(s[np.argmax(np.bincount(s))])
    if case == "disconnected":
        a = kronecker(8, 11)
        b = kronecker(7, 12)
        ids = np.concatenate([a[0], b[0] + 10_000])
        return ids, np.concatenate([a[1], b[1] + 10_000]), np.concatenate([a[2], b[2] + 10_000]), int(b[1][0] + 10_000)
    if case == "unreachable":
        ids, s, d = kronecker(8, 13)
        ids = np.concatenate([ids, np.arange(50_000, 50_040)])
        return ids, s, d, int(s[0])
    if case == "loops_and_parallel_edges":
        ids, s, d = kronecker(9, 14, keep_raw=True)
        assert (s == d).any() and len(np.unique(s * 4096 + d)) < len(s)
        return ids, s, d, int(d[0])
    if case == "one_vertex":
        return np.array([7]), np.zeros(0, np.int64), np.zeros(0, np.int64), 7
    if case == "source_in_a_small_component":
        ids, s, d = kronecker(8, 15)
        ids = np.concatenate([ids, [90_000, 90_001, 90_002, 90_003]])
        s = np.concatenate([s, [90_000, 90_001, 90_003]])
        d = np.concatenate([d, [90_001, 90_002, 90_000]])
        return ids, s, d, 90_002
    if case == "hub_and_path":
        # a hub of 3,000 leaves, half its edges stored from either end, and
        # a path off the last leaf
        leaves = np.arange(1, 3001)
        half = leaves % 2 == 0
        tail = np.arange(leaves[-1], leaves[-1] + 5)
        ids = np.concatenate([[0], leaves, tail[1:]])
        s = np.concatenate([np.where(half, 0, leaves), tail[:-1]])
        d = np.concatenate([np.where(half, leaves, 0), tail[1:]])
        return ids, s, d, 1
    if case == "late_lane_joins_the_giant":
        # 900_000's first two lanes in both orientations lead to 1 and 2, a
        # component of three the sampled lanes close; its third lane, and
        # its last in the hub's row, join the giant
        ids, s, d = kronecker(8, 16)
        hub = int(np.argmax(np.bincount(d)))
        ids = np.concatenate([[1, 2], ids, [900_000]])
        s = np.concatenate([s, [900_000, 900_000, 1, 2, 900_000, 1]])
        d = np.concatenate([d, [1, 2, 900_000, 900_000, hub, 2]])
        return ids, s, d, 900_000
    if case == "no_giant_component":
        # a perfect matching and five triangles: the largest sampled
        # component is the first triangle, every other row is outside
        pairs = np.arange(10, 410, 2)
        corners = 5_000 + 3 * np.arange(5)
        ids = np.concatenate([pairs, pairs + 1, corners, corners + 1, corners + 2])
        s = np.concatenate([pairs, corners, corners + 1, corners + 2])
        d = np.concatenate([pairs + 1, corners + 1, corners + 2, corners])
        return np.sort(ids), s, d, 5_000
    if case == "sampled_links_split_the_largest":
        # four pieces of ten (two anchors of small id, eight members, every
        # anchor-member pair stored both ways) chained by one edge each
        # from a member's third lane to a member's third lane: 40 vertices
        # the sampled lanes leave in pieces of 10, beside a path of 20 that
        # they close
        s, d = [], []
        for i in range(4):
            anchors = [10 + 2 * i, 11 + 2 * i]
            members = [1_000 + 100 * i + j for j in range(8)]
            for a in anchors:
                for b in [anchors[0] if a == anchors[1] else anchors[1]] + members:
                    s += [a, b]
                    d += [b, a]
            if i:
                s.append(1_000 + 100 * (i - 1) + 7)
                d.append(members[0])
        path_ids = np.arange(2_000, 2_020)
        s = np.concatenate([s, path_ids[:-1]])
        d = np.concatenate([d, path_ids[1:]])
        ids = np.unique(np.concatenate([s, d]))
        return ids, s, d, 2_000
    if case == "the_sampled_lanes_boundary":
        # beside a giant: a star of degree-1 rows; 4000's row holds a loop
        # and two parallel lanes to 4001 before its lane to 4010, whose
        # reverse row holds two smaller nodes first; rows of exactly two
        ids, s, d = kronecker(7, 17)
        extra = [(3001, 3000), (3002, 3000), (3003, 3000), (3004, 3000),
                 (4000, 4000), (4000, 4001), (4000, 4001), (4000, 4010),
                 (3990, 4010), (3991, 4010), (4001, 4000),
                 (5000, 5001), (5000, 5002), (5003, 5001)]
        s = np.concatenate([s, [a for a, _ in extra]])
        d = np.concatenate([d, [b for _, b in extra]])
        ids = np.unique(np.concatenate([ids, s, d]))
        return ids, s, d, 4000
    raise KeyError(case)


CASES = ["kronecker-8", "kronecker-10", "kronecker-12", "disconnected", "unreachable",
         "loops_and_parallel_edges", "one_vertex", "source_in_a_small_component",
         "hub_and_path"]
# where WCC's sampled lanes alone do not decide the answer
WCC_CASES = CASES + ["late_lane_joins_the_giant", "no_giant_component",
                     "sampled_links_split_the_largest", "the_sampled_lanes_boundary"]


def load(session, ids, s, d):
    return PropertyGraph(session, graph_from_tables(
        session,
        {"Vertex": (ids, {"id": (ids, T.CTInteger.nullable)})},
        {"EDGE": (s, d, {})},
    ))


def brute_bfs(ids, s, d, source):
    near = collections.defaultdict(list)
    for a, b in zip(s.tolist(), d.tolist()):
        near[a].append(b)
        near[b].append(a)
    depth = {source: 0}
    queue = collections.deque([source])
    while queue:
        v = queue.popleft()
        for w in near[v]:
            if w not in depth:
                depth[w] = depth[v] + 1
                queue.append(w)
    return [{"v": int(v), "depth": depth.get(int(v))} for v in sorted(ids.tolist())]


def brute_wcc(ids, s, d):
    parent = {int(v): int(v) for v in ids}

    def root(v):
        while parent[v] != v:
            v = parent[v]
        return v

    for a, b in zip(s.tolist(), d.tolist()):
        ra, rb = root(a), root(b)
        parent[max(ra, rb)] = min(ra, rb)
    return [{"v": v, "component": root(v)} for v in sorted(parent)]


def sampled_reads(ids, s, d, k=J.WCC_SAMPLED_LANES):
    """What WCC reads, by its definition: (the lanes at the head of every
    row, ``k`` at most, in both orientations; the rows, of either
    orientation, of the nodes outside the largest component those lanes
    form, the one of smallest position among equals; their lanes)."""
    n = len(ids)
    parent = list(range(n))

    def root(v):
        while parent[v] != v:
            v = parent[v]
        return v

    sampled, degrees = 0, []
    ps, pd = np.searchsorted(ids, s), np.searchsorted(ids, d)
    for a, b in ((ps, pd), (pd, ps)):
        b = b[np.lexsort((b, a))]  # the CSR's lanes: by row, then column
        deg = np.bincount(a, minlength=n)
        degrees.append(deg)
        first = np.cumsum(deg) - deg
        for r in range(k):
            rows_ = np.nonzero(deg > r)[0]
            sampled += len(rows_)
            for u, v in zip(rows_.tolist(), b[first[rows_] + r].tolist()):
                ru, rv = root(u), root(v)
                parent[max(ru, rv)] = min(ru, rv)
    roots = np.array([root(v) for v in range(n)])
    outside = roots != np.argmax(np.bincount(roots, minlength=n))
    return (sampled, sum(int((outside & (deg > 0)).sum()) for deg in degrees),
            sum(int(deg[outside].sum()) for deg in degrees))


def rows(graph, query, params=None):
    return [dict(r) for r in graph.cypher(query, params or {}).records.collect()]


@pytest.fixture(scope="module")
def sessions():
    return {"tpu": CypherSession.tpu(), "local": CypherSession.local()}


@pytest.fixture(scope="module")
def graphs(sessions):
    cache = {}

    def get(case, backend):
        if (case, backend) not in cache:
            ids, s, d, source = case_graph(case)
            cache[(case, backend)] = (load(sessions[backend], ids, s, d), ids, s, d, source)
        return cache[(case, backend)]

    return get


# -- parser, IR, planners --------------------------------------------------

PLANNED = {
    "yields": ("CALL algo.wcc('E') YIELD node, component RETURN node, component",
               ("node", "component")),
    "renamed": ("CALL algo.bfs(1, 'E') YIELD node AS v, depth AS d RETURN v.id AS id, d",
                ("id", "d")),
    "one_yield": ("CALL algo.bfs($s, 'E') YIELD depth RETURN depth", ("depth",)),
    "with_aggregate_order": (
        "CALL algo.wcc('E') YIELD node, component WITH component, count(*) AS size "
        "RETURN size, count(*) AS n ORDER BY size", ("size", "n")),
    "star": ("CALL algo.wcc('E') YIELD * RETURN component", ("component",)),
    "standalone": ("CALL algo.wcc('E')", ("node", "component")),
    "where_after": ("CALL algo.bfs(1, 'E') YIELD node, depth WITH node, depth WHERE depth > 1 "
                    "RETURN count(node) AS far", ("far",)),
}


@pytest.mark.parametrize("case", sorted(PLANNED))
def test_a_call_parses_builds_and_plans(case):
    query, returns = PLANNED[case]
    from tpu_cypher.api.schema import PropertyGraphSchema

    ir = build_ir(parse_cypher(query), IRBuilderContext(
        schema=PropertyGraphSchema.empty(), parameters={"s": 1}))
    call = ir.blocks[0]
    assert isinstance(call, B.ProcedureCallBlock)
    assert [y for y, _, _ in call.yields] == ["node", call.yields[1][0]]
    assert ir.returns == returns
    plan = plan_logical(ir)
    found = [op for op in plan.iter_nodes() if isinstance(op, L.ProcedureCall)]
    assert len(found) == 1 and isinstance(found[0].in_op, L.Start)
    assert {f for f, _ in found[0].fields} >= {f for _, f, _ in call.yields}


# -- the TPU session, the oracle and the brute force agree -----------------


@pytest.mark.parametrize("case", CASES)
def test_bfs_depths_agree(graphs, case):
    want = None
    for backend in ("local", "tpu"):
        graph, ids, s, d, source = graphs(case, backend)
        got = rows(graph, BFS_ROWS, {"source": source})
        want = want or brute_bfs(ids, s, d, source)
        assert got == want, backend


@pytest.mark.parametrize("case", WCC_CASES)
def test_wcc_components_agree(graphs, case):
    want = None
    for backend in ("local", "tpu"):
        graph, ids, s, d, _ = graphs(case, backend)
        want = want or brute_wcc(ids, s, d)
        assert rows(graph, WCC_ROWS) == want, backend


@pytest.mark.parametrize("case", ["kronecker-10", "disconnected", "unreachable"])
@pytest.mark.parametrize("query", ["bfs", "wcc"])
def test_the_cells_summaries_agree_on_the_device_rung_alone(sessions, graphs, case, query):
    text = BFS_SUMMARY if query == "bfs" else WCC_SUMMARY
    answers = {}
    for backend in ("local", "tpu"):
        graph, ids, s, d, source = graphs(case, backend)
        session = sessions[backend]
        session.record_fallbacks = True
        try:
            result = graph.cypher(text, {"source": source})
            answers[backend] = [dict(r) for r in result.records.collect()]
        finally:
            session.record_fallbacks = False
        if backend == "tpu":
            assert not result.fallbacks
            assert [e["rung"] for e in result.execution_log] == ["device"]
    assert answers["tpu"] == answers["local"]
    if query == "bfs":
        assert answers["tpu"][0]["depth"] == 0 and answers["tpu"][0]["vertices"] == 1
        reached = [r for r in answers["tpu"] if r["depth"] is not None]
        assert answers["tpu"][-1]["depth"] is None or len(reached) == len(answers["tpu"])
    else:
        assert sum(r["size"] * r["components"] for r in answers["tpu"]) == len(graphs(case, "tpu")[1])


# -- typed errors ----------------------------------------------------------

ERRORS = {
    "unknown_procedure": ("CALL db.labels() YIELD label RETURN label", {}, UnsupportedFeatureError),
    "correlated_after_match": ("MATCH (n) CALL algo.wcc('EDGE') YIELD component RETURN component", {},
                               UnsupportedFeatureError),
    "correlated_after_with": ("WITH 1 AS x CALL algo.wcc('EDGE') YIELD component RETURN x, component",
                              {}, UnsupportedFeatureError),
    "variable_argument": ("CALL algo.bfs(x, 'EDGE') YIELD depth RETURN depth", {}, Exception),
    "source_no_node": (BFS_ROWS, {"source": 123_456_789}, ProcedureError),
    "source_a_string": (BFS_ROWS, {"source": "1000"}, ProcedureError),
    "source_null": (BFS_ROWS, {"source": None}, ProcedureError),
    "type_not_a_string": ("CALL algo.wcc(7) YIELD component RETURN component", {}, ProcedureError),
    "unknown_yield": ("CALL algo.wcc('EDGE') YIELD label RETURN label", {}, Exception),
}


@pytest.mark.parametrize("backend", ["local", "tpu"])
@pytest.mark.parametrize("case", sorted(ERRORS))
def test_typed_errors(graphs, backend, case):
    query, params, error = ERRORS[case]
    graph = graphs("kronecker-8", backend)[0]
    with pytest.raises(error):
        rows(graph, query, params)


def test_a_mesh_session_declines_typed_and_counted(sessions):
    session = sessions["tpu"]
    with use_mesh(make_row_mesh(jax.devices()[:2])):
        ids, s, d, source = case_graph("kronecker-8")
        graph = load(session, ids, s, d)
        before = REGISTRY.flat().get(DECLINE, 0.0)
        with pytest.raises(UnsupportedFeatureError):
            rows(graph, BFS_ROWS, {"source": source})
        assert REGISTRY.flat()[DECLINE] == before + 1


@pytest.mark.parametrize("step", [4, 64])
@pytest.mark.parametrize("case", ["kronecker-10", "hub_and_path"])
def test_push_steps_narrower_than_a_row(sessions, monkeypatch, case, step):
    """The BFS pushes ``step`` lanes at a time: a hub's row, and a level's
    rows, run across many steps and both orientations, and each step
    starts inside a row where the last one stopped."""
    monkeypatch.setattr(J, "PUSH_LANES", step)
    ids, s, d, source = case_graph(case)
    graph = load(sessions["tpu"], ids, s, d)
    result = graph.cypher(BFS_ROWS, {"source": source})
    assert [dict(r) for r in result.records.collect()] == brute_bfs(ids, s, d, source)
    (span,) = _spans(result, "procedure:bfs")
    assert span["attrs"]["edge_lanes"] % step == 0
    assert span["attrs"]["edge_lanes"] >= 2 * len(s)


# -- compiles, spans, counters, host syncs ---------------------------------

REACH = ("CALL algo.bfs($source, 'EDGE') YIELD node, depth "
         "RETURN count(depth) AS reached, max(depth) AS levels")


def test_a_second_source_compiles_nothing(graphs):
    graph, ids, s, d, _ = graphs("kronecker-10", "tpu")
    first, second = int(s[0]), int(d[-1])
    assert rows(graph, REACH, {"source": first})[0]["reached"] > 1
    before = bucketing.compile_snapshot()
    cached = J.bfs_levels._cache_size()
    got = rows(graph, REACH, {"source": second})
    assert bucketing.compile_delta(before)["compiles"] == 0
    assert J.bfs_levels._cache_size() == cached
    want = [r for r in brute_bfs(ids, s, d, second) if r["depth"] is not None]
    assert got == [{"reached": len(want), "levels": max(r["depth"] for r in want)}]


def path_edges(length):
    """Vertices 0 .. length in a line, the edges stored from either end."""
    ids = np.arange(length + 1)
    flip = np.arange(length) % 2 == 0
    return ids, np.where(flip, ids[1:], ids[:-1]), np.where(flip, ids[:-1], ids[1:])


def path(session, length):
    return load(session, *path_edges(length))


def bit_reversed_path(session, length):
    """The path, the vertex at each place of it given the id of that place
    with its bits reversed: every other vertex is a local minimum at every
    scale, so each WCC hooking round halves the roots left, and the rounds
    grow with the length."""
    ids, s, d = path_edges(length)
    bits = max(int(length).bit_length(), 1)
    relabel = np.array([int(format(i, f"0{bits}b")[::-1], 2) for i in ids])
    return load(session, np.sort(relabel), relabel[s], relabel[d])


def _spans(result, name):
    out, stack = [], [result.profile().to_dict()["root"]]
    while stack:
        sp = stack.pop()
        if sp["name"] == name:
            out.append(sp)
        stack.extend(sp.get("children", ()))
    return out


def _wcc_reads(attrs, ids, s, d):
    """The span's ``edge_lanes``: the sampled lanes once and the lanes of
    the rows outside once a round that reads them, as counted here."""
    sampled, outside, lanes = sampled_reads(ids, s, d)
    assert attrs["rows_outside"] == outside
    assert (attrs["outside_rounds"] > 0) == (outside > 0)
    assert attrs["iterations"] > attrs["outside_rounds"]
    assert attrs["edge_lanes"] == sampled + lanes * attrs["outside_rounds"]
    return sampled, lanes


@pytest.mark.parametrize("procedure", ["bfs", "wcc"])
def test_the_span_and_both_counters(sessions, graphs, procedure):
    length = 12
    graph = path(sessions["tpu"], length)
    query = (REACH if procedure == "bfs" else WCC_SUMMARY)
    counters = (ITERATIONS, EDGE_LANES) + ((ROWS_OUTSIDE,) if procedure == "wcc" else ())
    flat = REGISTRY.flat()
    assert all(c % procedure in flat for c in counters)
    before = {c: flat[c % procedure] for c in counters}
    result = graph.cypher(query, {"source": 0})
    answer = [dict(r) for r in result.records.collect()]
    (span,) = _spans(result, f"procedure:{procedure}")
    attrs = span["attrs"]
    assert span["kind"] == "kernel" and attrs["orientations"] == 2
    if procedure == "bfs":
        assert answer == [{"reached": length + 1, "levels": length}]
        assert attrs["iterations"] == length + 1  # the last level finds none
    else:
        assert answer == [{"size": length + 1, "components": 1, "id_sum": 0}]
        assert 1 <= attrs["iterations"] <= length + 1
    flat = REGISTRY.flat()
    assert flat[ITERATIONS % procedure] - before[ITERATIONS] == attrs["iterations"]
    assert flat[EDGE_LANES % procedure] - before[EDGE_LANES] == attrs["edge_lanes"]
    if procedure == "bfs":  # push steps of one width, every edge from both ends
        assert attrs["edge_lanes"] >= 2 * length
        assert ROWS_OUTSIDE % procedure not in flat
        return
    assert flat[ROWS_OUTSIDE % procedure] - before[ROWS_OUTSIDE] == attrs["rows_outside"]
    # no row has more lanes than are sampled: every lane once, none outside
    assert _wcc_reads(attrs, *path_edges(length)) == (2 * length, 0)
    assert attrs["edge_lanes"] == 2 * length
    graph, ids, s, d, _ = graphs("kronecker-12", "tpu")
    (span,) = _spans(graph.cypher(WCC_SUMMARY), "procedure:wcc")
    attrs = span["attrs"]
    sampled, lanes = _wcc_reads(attrs, ids, s, d)
    k = J.WCC_SAMPLED_LANES
    assert sampled <= 2 * k * len(ids) and 0 < lanes < 0.001 * 2 * len(s)
    assert attrs["edge_lanes"] <= 2 * k * len(ids) + lanes * attrs["outside_rounds"]


@pytest.mark.parametrize("case", ["kronecker-10", "disconnected", "unreachable",
                                  "loops_and_parallel_edges", "one_vertex"] + WCC_CASES[len(CASES):])
def test_wcc_reads_the_sampled_lanes_and_the_rows_outside(graphs, case):
    graph, ids, s, d, _ = graphs(case, "tpu")
    (span,) = _spans(graph.cypher(WCC_SUMMARY), "procedure:wcc")
    _wcc_reads(span["attrs"], ids, s, d)


@pytest.mark.parametrize("step", [4, 64])
@pytest.mark.parametrize("case", ["no_giant_component", "sampled_links_split_the_largest",
                                  "the_sampled_lanes_boundary"])
def test_wcc_rows_outside_in_steps_narrower_than_a_row(sessions, monkeypatch, case, step):
    """The rows outside the largest sampled component are read ``step``
    lanes at a time: rows run across steps and both orientations."""
    monkeypatch.setattr(J, "PUSH_LANES", step)
    ids, s, d, _ = case_graph(case)
    graph = load(sessions["tpu"], ids, s, d)
    result = graph.cypher(WCC_ROWS)
    assert [dict(r) for r in result.records.collect()] == brute_wcc(ids, s, d)
    (span,) = _spans(result, "procedure:wcc")
    _wcc_reads(span["attrs"], ids, s, d)


def _syncs():
    return sum(v for k, v in REGISTRY.flat().items() if k.startswith(SYNCS))


@pytest.mark.parametrize("procedure", ["bfs", "wcc"])
def test_host_syncs_do_not_grow_with_the_levels(sessions, procedure):
    query = REACH if procedure == "bfs" else WCC_SUMMARY
    moved, steps = {}, {}
    for length in (5, 40):
        graph = (path if procedure == "bfs" else bit_reversed_path)(sessions["tpu"], length)
        rows(graph, query, {"source": 0})  # the graph's lazy indexes
        before = _syncs()
        result = graph.cypher(query, {"source": 0})
        result.records.collect()
        moved[length] = _syncs() - before
        steps[length] = _spans(result, f"procedure:{procedure}")[0]["attrs"]["iterations"]
    if procedure == "bfs":
        assert steps[5] > 5 and steps[40] > 40
    assert steps[40] > steps[5] + 1
    assert moved[5] == moved[40] > 0

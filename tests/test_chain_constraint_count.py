"""``count(*)`` over an expand chain under constraints between two of its
nodes two hops apart — ``a <> c``, ``a = c``, ``(a)-[:T]->(c)``, ``NOT
(a)-[:T]->(c)``, the closing edge in either direction, conjunctions — is
answered by the chain itself without a row of it
(``CsrExpandOp.chain_constraint_count``, asked by
``AggregateOp._chain_constraint_count``): the engine against a brute-force
enumeration on seeded random graphs of several labels, with parallel edges,
self-loops, asymmetric edges, neighbours of another label and an empty
label; a pair three hops apart, or a walk the chain has to keep free of
repeated relationships itself, declines and still answers right. Every
constraint is counted in ``tpu_cypher_count_pushdown_total{op=
"chain_constraint",outcome}``."""

import numpy as np
import pytest

from tpu_cypher import CypherSession
from tpu_cypher.api import types as T
from tpu_cypher.io.ldbc import graph_from_tables
from tpu_cypher.obs.metrics import REGISTRY
from tpu_cypher.relational.session import PropertyGraph

SERIES = 'tpu_cypher_count_pushdown_total{op=chain_constraint,outcome=%s}'
LANES = "tpu_cypher_chain_constraint_wedge_lanes_total"


def make_tables(seed, knows_loops=False):
    """Node ids per label and (source, target) rows per relationship type.
    KNOWS: persons to persons, some pairs twice, a few to a city (a
    neighbour of another label), never both directions of every pair;
    LIKES: persons to persons with self-loops and parallel rows;
    HAS_INTEREST: persons to tags; LIVES: persons to cities. Ghost has no
    node."""
    rng = np.random.default_rng(seed)
    person = np.arange(100, 100 + 14, dtype=np.int64)
    tag = np.arange(300, 300 + 5, dtype=np.int64)
    city = np.arange(500, 500 + 3, dtype=np.int64)

    def rows(src, dst, count, loops):
        s, d = rng.choice(src, count), rng.choice(dst, count)
        keep = np.ones(count, bool) if loops else s != d
        return s[keep], d[keep]

    ks, kd = rows(person, person, 46, knows_loops)
    ks = np.concatenate([ks, ks[:6], kd[6:16], rng.choice(person, 4)])
    kd = np.concatenate([kd, kd[:6], ks[6:16], rng.choice(city, 4)])
    ls, ld = rows(person, person, 30, True)
    ls = np.concatenate([ls, ls[:5], person[:3]])
    ld = np.concatenate([ld, ld[:5], person[:3]])
    nodes = {"Person": person, "Tag": tag, "City": city,
             "Ghost": np.zeros(0, np.int64)}
    rels = {
        "KNOWS": (ks, kd),
        "LIKES": (ls, ld),
        "HAS_INTEREST": rows(person, tag, 30, True),
        "LIVES": rows(person, city, 14, True),
    }
    return nodes, rels


def make_graph(session, nodes, rels):
    return PropertyGraph(session, graph_from_tables(
        session,
        {label: (ids, {"id": (ids, T.CTInteger.nullable)})
         for label, ids in nodes.items()},
        {t: (s, d, {}) for t, (s, d) in rels.items()},
    ))


def brute_force(nodes, rels, chain, constraints):
    """``chain``: node labels (None: any) and, between them, (type, forward)
    hops, in the order written; ``constraints``: ("neq" | "eq", i, j) or
    ("edge", i, j, type, negated) over positions of the chain. Every walk is
    enumerated; a walk repeats no relationship (they are one MATCH)."""
    label_of = {int(i): label for label, ids in nodes.items() for i in ids}
    edges = {t: list(zip(s.tolist(), d.tolist())) for t, (s, d) in rels.items()}
    pairs = {t: set(e) for t, e in edges.items()}
    labels, hops = chain[0::2], chain[1::2]

    def fits(node, k):
        return labels[k] is None or label_of[node] == labels[k]

    total = 0
    walks = [((n,), ()) for n in label_of if fits(n, 0)]
    for k, (rel_type, forward) in enumerate(hops):
        grown = []
        for path, used in walks:
            for e, (s, d) in enumerate(edges[rel_type]):
                here, there = (s, d) if forward else (d, s)
                if here == path[-1] and fits(there, k + 1) and (rel_type, e) not in used:
                    grown.append((path + (there,), used + ((rel_type, e),)))
        walks = grown
    for path, _ in walks:
        ok = True
        for kind, i, j, *rest in constraints:
            if kind == "neq":
                ok &= path[i] != path[j]
            elif kind == "eq":
                ok &= path[i] == path[j]
            else:
                rel_type, negated = rest
                ok &= ((path[i], path[j]) in pairs[rel_type]) != negated
        total += ok
    return total


TWO = ["Person", ("KNOWS", True), "Person", ("KNOWS", True), "Person"]
TWO_TEXT = "(a:Person)-[:KNOWS]->(b:Person)-[:KNOWS]->(c:Person)"
MIXED = ["Person", ("KNOWS", True), "Person", ("LIKES", True), "Person"]
MIXED_TEXT = "(a:Person)-[:KNOWS]->(b:Person)-[:LIKES]->(c:Person)"

# name: (pattern, its chain, WHERE, the constraints on chain positions)
CASES = {
    "neq": (TWO_TEXT, TWO, "a <> c", [("neq", 0, 2)]),
    "eq": (TWO_TEXT, TWO, "a = c", [("eq", 0, 2)]),
    "closed": (TWO_TEXT, TWO, "(a)-[:LIKES]->(c)",
               [("edge", 0, 2, "LIKES", False)]),
    "open": (TWO_TEXT, TWO, "NOT (a)-[:LIKES]->(c)",
             [("edge", 0, 2, "LIKES", True)]),
    "closed_back": (TWO_TEXT, TWO, "(c)-[:LIKES]->(a)",
                    [("edge", 2, 0, "LIKES", False)]),
    "open_back": (TWO_TEXT, TWO, "NOT (a)<-[:KNOWS]-(c)",
                  [("edge", 2, 0, "KNOWS", True)]),
    "neq_and_open": (TWO_TEXT, TWO, "a <> c AND NOT (a)-[:KNOWS]->(c)",
                     [("neq", 0, 2), ("edge", 0, 2, "KNOWS", True)]),
    "neq_and_closed": (TWO_TEXT, TWO, "a <> c AND (a)-[:LIKES]->(c)",
                       [("neq", 0, 2), ("edge", 0, 2, "LIKES", False)]),
    "eq_and_closed": (TWO_TEXT, TWO, "a = c AND (a)-[:LIKES]->(c)",
                      [("eq", 0, 2), ("edge", 0, 2, "LIKES", False)]),
    "eq_and_open": (TWO_TEXT, TWO, "a = c AND NOT (c)-[:LIKES]->(a)",
                    [("eq", 0, 2), ("edge", 2, 0, "LIKES", True)]),
    # parallel rows and self-loops on a hop of the wedge itself
    "mixed_neq_and_closed": (MIXED_TEXT, MIXED, "a <> c AND (a)-[:KNOWS]->(c)",
                             [("neq", 0, 2), ("edge", 0, 2, "KNOWS", False)]),
    "mixed_eq": (MIXED_TEXT, MIXED, "a = c", [("eq", 0, 2)]),
    # the middle node under no label: a city may stand there
    "any_middle": ("(a:Person)-[:KNOWS]->(b)-[:KNOWS]->(c:Person)",
                   ["Person", ("KNOWS", True), None, ("KNOWS", True), "Person"],
                   "a <> c", [("neq", 0, 2)]),
    "any_end": ("(a:Person)-[:KNOWS]->(b:Person)-[:KNOWS]->(c)",
                ["Person", ("KNOWS", True), "Person", ("KNOWS", True), None],
                "NOT (a)-[:KNOWS]->(c)", [("edge", 0, 2, "KNOWS", True)]),
    "written_backwards": (
        "(a:Person)<-[:KNOWS]-(b:Person)<-[:KNOWS]-(c:Person)",
        ["Person", ("KNOWS", False), "Person", ("KNOWS", False), "Person"],
        "a <> c AND NOT (a)-[:KNOWS]->(c)",
        [("neq", 0, 2), ("edge", 0, 2, "KNOWS", True)]),
    "lsqb_q6": (TWO_TEXT + "-[:HAS_INTEREST]->(t:Tag)",
                TWO + [("HAS_INTEREST", True), "Tag"], "a <> c",
                [("neq", 0, 2)]),
    "lsqb_q9": (TWO_TEXT + "-[:HAS_INTEREST]->(t:Tag)",
                TWO + [("HAS_INTEREST", True), "Tag"],
                "a <> c AND NOT (a)-[:KNOWS]->(c)",
                [("neq", 0, 2), ("edge", 0, 2, "KNOWS", True)]),
    "middle_of_four_hops": (
        "(t:Tag)<-[:HAS_INTEREST]-" + TWO_TEXT + "-[:LIVES]->(y:City)",
        ["Tag", ("HAS_INTEREST", False)] + TWO + [("LIVES", True), "City"],
        "a <> c AND NOT (a)-[:KNOWS]->(c)",
        [("neq", 1, 3), ("edge", 1, 3, "KNOWS", True)]),
    "empty_label": ("(a:Ghost)-[:KNOWS]->(b)-[:KNOWS]->(c)",
                    ["Ghost", ("KNOWS", True), None, ("KNOWS", True), None],
                    "a <> c", [("neq", 0, 2)]),
}


def _series(outcome):
    return REGISTRY.flat().get(SERIES % outcome, 0.0)


@pytest.fixture(scope="module", params=[11, 12])
def world(request):
    nodes, rels = make_tables(request.param)
    return nodes, rels, make_graph(CypherSession.tpu(), nodes, rels)


@pytest.mark.parametrize("case", list(CASES))
def test_constrained_chain_count_matches_brute_force(world, case):
    nodes, rels, graph = world
    pattern, chain, where, constraints = CASES[case]
    want = brute_force(nodes, rels, chain, constraints)
    counted, rows, lanes = _series("count"), _series("rows"), REGISTRY.flat().get(LANES, 0.0)
    result = graph.cypher(f"MATCH {pattern} WHERE {where} RETURN count(*) AS n")
    assert [dict(r) for r in result.records.collect()] == [{"n": want}]
    if case == "empty_label" and _series("count") == counted:
        return  # the planner may answer an empty scan before any chain
    assert _series("count") - counted == len(constraints)
    assert _series("rows") == rows
    spans = [s for s in result.profile().trace.spans() if s.name == "chain_constraint"]
    assert len(spans) == 1 and spans[0].kind == "kernel"
    assert spans[0].attrs["constraints"] == len(constraints)
    # under a = c the closing edge is a's own loop: no closing program runs
    closing = {c[0] for c in constraints} in ({"edge"}, {"edge", "neq"})
    assert (spans[0].attrs.get("form") == "bits") == closing
    assert (REGISTRY.flat().get(LANES, 0.0) > lanes) == closing
    if closing:
        assert spans[0].attrs["wedge_lanes"] > 0


def _csr(n, src, dst, lanes):
    """(row_ptr, col_idx, row per lane) of the lanes (src, dst), sorted by
    (src, dst) and padded to ``lanes`` as ``GraphIndex.csr`` pads them."""
    order = np.lexsort((dst, src))
    src, dst = src[order], dst[order]
    rp = np.concatenate([[0], np.cumsum(np.bincount(src, minlength=n))])
    ci = np.full(lanes, -1, np.int32)
    ci[: len(dst)] = dst
    rows = np.full(lanes, n - 1, np.int32)
    rows[: len(src)] = src
    return rp.astype(np.int32), ci, rows


# name: (nodes, the most parallel lanes of a first-hop pair, of a second-hop
# pair, of a closing pair, closing pairs, share of the nodes the middle may be)
CLOSING_CASES = {
    "simple": (64, 1, 1, 1, 150, None),
    "parallel_first_hop": (64, 2, 1, 1, 150, None),
    "parallel_second_hop": (64, 1, 5, 1, 150, None),
    "parallel_both_hops": (64, 5, 7, 1, 150, None),
    "middle_mask": (64, 3, 1, 1, 150, 0.6),
    "parallel_closing_lanes": (64, 1, 2, 4, 150, 0.8),
    "side_not_a_multiple_of_32": (77, 2, 2, 2, 200, 0.7),
    "no_closing_lane": (40, 1, 3, 1, 0, None),
}


@pytest.mark.parametrize("chunk", [8, 256])
@pytest.mark.parametrize("case", list(CLOSING_CASES))
def test_wedge_close_sum_matches_the_matrix_product(case, chunk):
    """``jit_ops.wedge_close_sum`` over ``bit_adjacency``'s planes against
    ``m1 @ m2`` in NumPy read at the closing pairs, each pair once: parallel
    lanes on either hop each count, whatever the chunks cut."""
    import jax.numpy as jnp

    from tpu_cypher.backend.tpu import jit_ops as J

    n, most1, most2, most_c, closing_pairs, mid_share = CLOSING_CASES[case]
    rng = np.random.default_rng(len(case))

    def lanes(pairs, most):
        key = rng.choice(n * n, pairs, replace=False)
        reps = rng.integers(1, most + 1, pairs)
        reps[: min(1, pairs)] = most  # some pair has the most
        return np.repeat(key // n, reps), np.repeat(key % n, reps)

    (s1, d1), (s2, d2), (sc, dc) = (
        lanes(300, most1), lanes(300, most2), lanes(closing_pairs, most_c)
    )
    m1, m2 = np.zeros((n, n), np.int64), np.zeros((n, n), np.int64)
    np.add.at(m1, (s1, d1), 1)
    np.add.at(m2, (s2, d2), 1)
    closes = np.zeros((n, n), bool)
    closes[sc, dc] = True
    mid = None if mid_share is None else rng.random(n) < mid_share
    # weights of more than 32 bits: each is gathered as two words
    left, right = rng.integers(0, 1 << 34, n), rng.integers(0, 1 << 12, n)
    wedges = (m1 if mid is None else m1 * mid[None, :]) @ m2
    want = int((left[:, None] * right[None, :] * wedges * closes).sum())

    # every node has a row and a bit: whole tiles of 8 rows, of 128 words
    rank = jnp.arange(n, dtype=jnp.int32)
    size, words = -(-n // 8) * 8, 128
    nodes = jnp.pad(rank, (0, size - n))

    def planes(src, dst, most):
        rp, ci, rows = _csr(n, src, dst, 2048)
        assert int(J.csr_longest_run(rp, ci, rows)) == most
        return J.bit_adjacency(rp, ci, rows, rank, rank, size=size, words=words,
                               planes=most.bit_length())

    b1 = planes(s1, d1, most1)
    b2t = planes(d2, s2, most2)  # the second hop by its far end
    # digit j of a pair's lanes is bit (row, col) of plane j
    digits = sum(
        ((np.asarray(p)[:n, :, None] >> np.arange(32)) & 1).reshape(n, -1)[:, :n]
        .astype(np.int64) << j
        for j, p in enumerate(b2t)
    )
    assert (digits == m2.T).all()
    rp_c, ci_c, rows_c = _csr(n, sc, dc, 1024)
    ra_c, kc_c = J.closing_pair_rows(rp_c, ci_c, rows_c, rank, rank)
    # each closing pair once, on its first lane
    assert int((np.asarray(ra_c) >= 0).sum()) == closing_pairs
    got = J.wedge_close_sum(
        b1, nodes, b2t, nodes, None if mid is None else jnp.asarray(mid),
        rp_c, ra_c, kc_c, jnp.asarray(left), jnp.asarray(right), chunk=chunk,
    )
    assert (len(b1), len(b2t)) == (most1.bit_length(), most2.bit_length())
    assert int(got) == want
    if not closing_pairs:
        none = np.zeros(0, np.int32)
        assert int(J.wedge_close_sum(
            b1, nodes, b2t, nodes, None, rp_c, none, none,
            jnp.asarray(left), jnp.asarray(right), chunk=chunk,
        )) == 0


def test_parallel_lanes_take_planes_not_another_form(world):
    """LIKES has pairs with several rows: the closing program takes them as
    further planes of the same bit rows (``planes`` on the span)."""
    nodes, rels, graph = world
    pattern, chain, where, constraints = CASES["mixed_neq_and_closed"]
    result = graph.cypher(f"MATCH {pattern} WHERE {where} RETURN count(*) AS n")
    assert [dict(r) for r in result.records.collect()] == [
        {"n": brute_force(nodes, rels, chain, constraints)}
    ]
    (span,) = [s for s in result.profile().trace.spans() if s.name == "chain_constraint"]
    pairs = {}
    for row in zip(*rels["LIKES"]):
        pairs[row] = pairs.get(row, 0) + 1
    most = max(pairs.values())
    assert most > 1 and span.attrs["form"] == "bits"
    knows = {}
    for row in zip(*rels["KNOWS"]):
        knows[row] = knows.get(row, 0) + 1
    assert span.attrs["planes"] == (
        f"{max(knows.values()).bit_length()}x{most.bit_length()}"
    )
    # graphs of one deployment share the closing programs: the bit rows'
    # shapes follow a lattice of their own, not each graph's node count
    from tpu_cypher.backend.tpu.graph_index import GraphIndex

    shapes = {p.shape for planes in GraphIndex.of(graph._graph)._wedge_adj.values()
              for p in planes}
    assert shapes == {(GraphIndex.WEDGE_ROWS, 128)}


DECLINED = {
    # the pair three hops apart
    "three_hops_apart": (
        "(a:Person)-[:KNOWS]->(b:Person)-[:KNOWS]->(c:Person)-[:LIKES]->(d:Person)",
        TWO + [("LIKES", True), "Person"], "a <> d", [("neq", 0, 3)], False),
    # self-loops under a type the wedge walks twice: the chain itself has to
    # keep a walk from taking one loop twice
    "loops_walked_twice": (TWO_TEXT, TWO, "a <> c", [("neq", 0, 2)], True),
    "loops_walked_twice_eq": (TWO_TEXT, TWO, "a = c", [("eq", 0, 2)], True),
}


@pytest.mark.parametrize("case", list(DECLINED))
def test_what_the_chain_declines_still_answers_right(case):
    pattern, chain, where, constraints, loops = DECLINED[case]
    nodes, rels = make_tables(13, knows_loops=loops)
    graph = make_graph(CypherSession.tpu(), nodes, rels)
    want = brute_force(nodes, rels, chain, constraints)
    counted, rows = _series("count"), _series("rows")
    result = graph.cypher(f"MATCH {pattern} WHERE {where} RETURN count(*) AS n")
    assert [dict(r) for r in result.records.collect()] == [{"n": want}]
    assert _series("count") == counted
    assert _series("rows") - rows == len(constraints)


def test_rows_are_what_they_were_and_neq_runs_on_the_device(world):
    """A query that returns the rows builds them as ever, and the
    comparison of two node variables is the comparison of their id columns,
    on the device (no host island)."""
    from tpu_cypher.backend.tpu.table import FALLBACK_COUNTER

    nodes, rels, graph = world
    before = dict(FALLBACK_COUNTER.snapshot())
    got = graph.cypher(
        f"MATCH {TWO_TEXT} WHERE a <> c RETURN a.id AS a, c.id AS c"
    ).records.collect()
    assert len(got) == brute_force(nodes, rels, TWO, [("neq", 0, 2)])
    assert all(r["a"] != r["c"] for r in got)
    same = graph.cypher(
        f"MATCH {TWO_TEXT} WHERE a = c RETURN a.id AS a, c.id AS c"
    ).records.collect()
    assert len(same) == brute_force(nodes, rels, TWO, [("eq", 0, 2)])
    # two relationship variables of two MATCH clauses (no uniqueness
    # between them): equal where they bind one KNOWS row
    one_row = graph.cypher(
        "MATCH (a:Person)-[r1:KNOWS]->(b) MATCH (c:Person)-[r2:KNOWS]->(b) "
        "WHERE r1 = r2 RETURN a.id AS a, c.id AS c"
    ).records.collect()
    assert len(one_row) == len(rels["KNOWS"][0])
    assert all(r["a"] == r["c"] for r in one_row)
    islands = {k: v for k, v in FALLBACK_COUNTER.snapshot().items()
               if k.startswith("island") and v != before.get(k, 0)}
    assert islands == {}


def test_served_and_sessions_agree():
    """Through ``QueryServer`` the same plan shape takes the same path."""
    import asyncio
    import json

    from tpu_cypher.serve import QueryServer

    nodes, rels = make_tables(14)
    session = CypherSession.tpu()
    graph = make_graph(session, nodes, rels)
    pattern, chain, where, constraints = CASES["lsqb_q9"]
    want = brute_force(nodes, rels, chain, constraints)
    query = f"MATCH {pattern} WHERE {where} RETURN count(*) AS n"

    async def ask():
        server = QueryServer(session, port=0, cache_bytes=0)
        server.register_graph("g", graph)
        async with server:
            reader, writer = await asyncio.open_connection(server.host, server.port)
            writer.write((json.dumps({"op": "submit", "id": "q", "graph": "g",
                                      "query": query, "parameters": {}}) + "\n").encode())
            await writer.drain()
            rows = []
            while True:
                m = json.loads(await asyncio.wait_for(reader.readline(), 120))
                if m["type"] == "rows":
                    rows.extend(m["rows"])
                elif m["type"] in ("done", "error", "cancelled"):
                    writer.close()
                    return rows, m

    counted, rows_before = _series("count"), _series("rows")
    rows, done = asyncio.run(ask())
    assert done["type"] == "done" and done["rungs"] == ["device"]
    assert rows == [{"n": want}]
    assert _series("count") - counted == 2 and _series("rows") == rows_before

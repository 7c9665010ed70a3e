"""Fused var-length expand coverage:
undirected steps ride a both-orientation CSR with direction-agnostic
walked-edge masks, zero-length lower bounds prepend the identity frontier,
and target-solved plans (unlabeled source, labeled target) no longer crash
(pre-existing logical-planner hole: the walk reached the connection from
its target and the cascade assumed the source was bound)."""

import numpy as np
import pytest

from tpu_cypher import CypherSession


def _create(seed, n, e):
    r = np.random.default_rng(seed)
    src = r.integers(0, n, e)
    dst = r.integers(0, n, e)
    parts = [f"(n{i}:{'N' if i % 2 else 'M'})" for i in range(n)]
    parts += [f"(n{s})-[:K]->(n{d})" for s, d in zip(src, dst)]
    parts += [f"(n{i})-[:K]->(n{i})" for i in range(0, n, 7)]  # self-loops
    return "CREATE " + ", ".join(parts)


QUERIES = [
    # undirected walks (fused via the both-orientation CSR)
    "MATCH (a)-[:K*1..3]-(b) RETURN count(*) AS c",
    "MATCH (a:N)-[:K*1..2]-(b:M) RETURN count(*) AS c",
    "MATCH (a)-[:K*2..3]-(b) RETURN a, b ORDER BY id(a), id(b) LIMIT 5",
    # zero-length lower bounds (identity frontier)
    "MATCH (a)-[:K*0..2]->(b) RETURN count(*) AS c",
    "MATCH (a:N)-[:K*0..1]-(b:N) RETURN count(*) AS c",
    "MATCH (a)-[:K*0..0]->(b:M) RETURN count(*) AS c",
    # target-solved plans (the planner brings the source in via cartesian)
    "MATCH (a)-[:K*1..2]->(b:M) RETURN count(*) AS c",
    "MATCH (a)-[:K*1..2]->(b:M) RETURN a, b ORDER BY id(a), id(b) LIMIT 4",
    "MATCH (a)-[:K*0..2]->(b:M) RETURN count(*) AS c",
]


@pytest.fixture(scope="module", params=[(1, 14, 30), (2, 20, 60), (3, 9, 18)])
def graphs(request):
    create = _create(*request.param)
    return (
        CypherSession.local().create_graph_from_create_query(create),
        CypherSession.tpu().create_graph_from_create_query(create),
    )


@pytest.mark.parametrize("query", QUERIES)
def test_var_expand_differential(graphs, query):
    g_local, g_tpu = graphs
    lv = [dict(r) for r in g_local.cypher(query).records.collect()]
    tv = [dict(r) for r in g_tpu.cypher(query).records.collect()]
    assert tv == lv, f"{query}: {tv[:3]} vs {lv[:3]}"


def test_undirected_and_zero_length_use_fused_plan():
    g = CypherSession.tpu().create_graph_from_create_query(_create(1, 14, 30))
    for q in (
        "MATCH (a)-[:K*1..3]-(b) RETURN count(*) AS c",
        "MATCH (a)-[:K*0..2]->(b) RETURN count(*) AS c",
    ):
        assert "CsrVarExpandOp" in g.cypher(q).plans, q


def test_undirected_rel_uniqueness_across_directions():
    """One relationship must not be walked twice even in opposite
    directions: a single edge admits exactly two undirected 1-walks and
    zero 2-walks."""
    gl = CypherSession.local().create_graph_from_create_query(
        "CREATE (x:N)-[:K]->(y:N)"
    )
    gt = CypherSession.tpu().create_graph_from_create_query(
        "CREATE (x:N)-[:K]->(y:N)"
    )
    for q, want in (
        ("MATCH (a)-[:K*1..1]-(b) RETURN count(*) AS c", 2),
        ("MATCH (a)-[:K*2..2]-(b) RETURN count(*) AS c", 0),
    ):
        lv = [dict(r) for r in gl.cypher(q).records.collect()]
        tv = [dict(r) for r in gt.cypher(q).records.collect()]
        assert lv == tv == [{"c": want}]

"""Adversarial + larger-cardinality differential tests for the TPU backend.

The differential surface once was 10 queries over 7
elements. This suite runs outer-join-heavy shapes, OPTIONAL MATCH chains,
var-length, CONSTRUCT, and adversarial values (null / NaN / -0.0 / mixed
int-float / empty strings / huge ids near the 2**53 float cliff) over a
randomized few-thousand-element graph, always comparing against the local
oracle."""

import math

import numpy as np
import pytest

from tpu_cypher import CypherSession
from tpu_cypher.api.mapping import NodeMappingBuilder, RelationshipMappingBuilder
from tpu_cypher.relational.graphs import ElementTable
from tpu_cypher.testing.bag import Bag

N = 400  # nodes
E = 1200  # edges


def _adversarial_values(rng, n):
    """Mixed numeric column with nulls, NaN, -0.0, huge ints, tiny floats."""
    pool = [
        None,
        float("nan"),
        -0.0,
        0.0,
        0,
        1,
        1.0,
        -1,
        2**53 + 1,
        2**53 + 2,
        -(2**53) - 1,
        0.5,
        -3.25,
        1e300,
        -1e300,
    ]
    return [pool[rng.integers(0, len(pool))] for _ in range(n)]


def _string_values(rng, n):
    pool = [None, "", "a", "A", "aa", "Z", "嗨", "null", "NaN", " b ", "'q'"]
    return [pool[rng.integers(0, len(pool))] for _ in range(n)]


def _build(session, ids, src, dst, nums, strs, since):
    t = session.table_cls
    nodes = t.from_columns(
        {"id": ids.tolist(), "num": nums, "s": strs}
    )
    nm = (
        NodeMappingBuilder.on("id")
        .with_implied_label("N")
        .with_property_keys("num", "s")
        .build()
    )
    rel_ids = (np.arange(len(src), dtype=np.int64) + int(ids.max()) + 1).tolist()
    rels = t.from_columns(
        {
            "rid": rel_ids,
            "a": ids[src].tolist(),
            "b": ids[dst].tolist(),
            "since": since,
        }
    )
    rm = (
        RelationshipMappingBuilder.on("rid")
        .from_("a")
        .to("b")
        .with_relationship_type("R")
        .with_property_key("since")
        .build()
    )
    return session.read_from(ElementTable(nm, nodes), ElementTable(rm, rels))


@pytest.fixture(scope="module")
def graphs():
    rng = np.random.default_rng(20260729)
    ids = np.arange(N, dtype=np.int64) * 9 + 5
    src = rng.integers(0, N, E)
    dst = rng.integers(0, N, E)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    nums = _adversarial_values(rng, N)
    strs = _string_values(rng, N)
    since = [
        None if rng.random() < 0.1 else int(rng.integers(2000, 2026))
        for _ in range(len(src))
    ]
    args = (ids, src, dst, nums, strs, since)
    return (
        _build(CypherSession.local(), *args),
        _build(CypherSession.tpu(), *args),
    )


QUERIES = [
    # outer-join-heavy / OPTIONAL MATCH chains
    "MATCH (a:N) OPTIONAL MATCH (a)-[r:R]->(b) OPTIONAL MATCH (b)-[q:R]->(c) "
    "RETURN count(a) AS ca, count(b) AS cb, count(c) AS cc",
    "MATCH (a:N) WHERE a.num IS NULL OPTIONAL MATCH (a)-[:R]->(b) "
    "RETURN count(*) AS rows, count(b.num) AS bn",
    "MATCH (a:N) OPTIONAL MATCH (a)-[:R]->(b) WHERE b.num > 0 "
    "RETURN count(b) AS c",
    # null / NaN / -0.0 semantics through filters, distinct, group, order
    "MATCH (a:N) WHERE a.num = 0 RETURN count(*) AS zeros",
    "MATCH (a:N) RETURN DISTINCT a.num AS v ORDER BY v LIMIT 12",
    "MATCH (a:N) RETURN a.num AS v, count(*) AS c ORDER BY c DESC, v LIMIT 8",
    "MATCH (a:N) WHERE a.num > 0.4 AND a.num < 2 RETURN count(*) AS c",
    "MATCH (a:N) RETURN sum(a.num) IS NULL AS has_sum",
    "MATCH (a:N) WHERE a.num <> a.num RETURN count(*) AS nan_ne",  # NaN<>NaN null!
    "MATCH (a:N) RETURN min(a.num) AS lo, max(a.num) AS hi",
    # huge ids near the float cliff joining exactly
    "MATCH (a:N) WHERE a.num = 9007199254740993 RETURN count(*) AS big",
    "MATCH (a:N), (b:N) WHERE a.num = b.num AND id(a) < id(b) "
    "RETURN count(*) AS pairs",
    # string adversaries through vocab machinery
    "MATCH (a:N) WHERE a.s = '' RETURN count(*) AS empties",
    "MATCH (a:N) WHERE a.s STARTS WITH 'a' RETURN count(*) AS c",
    "MATCH (a:N) RETURN a.s AS s, count(*) AS c ORDER BY c DESC, s LIMIT 6",
    "MATCH (a:N) WHERE a.s CONTAINS 'a' RETURN count(DISTINCT a.s) AS d",
    "MATCH (a:N) RETURN toUpper(a.s) AS u, count(*) AS c ORDER BY c DESC, u LIMIT 5",
    # var-length at cardinality
    "MATCH (a:N)-[:R*1..2]->(b) RETURN count(*) AS walks",
    "MATCH (a:N)-[rs:R*2..2]->(b) WHERE a.num > 0 RETURN count(*) AS c",
    # rel property nulls through fused expand
    "MATCH (a:N)-[r:R]->(b) WHERE r.since IS NULL RETURN count(*) AS c",
    "MATCH (a:N)-[r:R]->(b) RETURN r.since AS y, count(*) AS c ORDER BY c DESC, y LIMIT 5",
    # aggregates over adversarial values
    "MATCH (a:N) RETURN stDev(a.num) IS NULL AS sd_null",
    "MATCH (a:N) WHERE a.num >= 0 AND a.num <= 10 "
    "RETURN percentileDisc(a.num, 0.5) AS med, collect(DISTINCT a.num) AS xs",
    # union + distinct across vocabs
    "MATCH (a:N) RETURN a.s AS x UNION MATCH (a:N) RETURN toUpper(a.s) AS x",
    # fused count chains (SpMV path) incl. labels and backwards hops
    "MATCH (a:N)-[:R]->(b)-[:R]->(c) RETURN count(*) AS c2",
    "MATCH (a:N)-[:R]->(b)-[:R]->(c)-[:R]->(d) RETURN count(*) AS c3",
    "MATCH (a)<-[:R]-(b)<-[:R]-(c) RETURN count(*) AS back",
    # fused distinct-endpoints counts
    "MATCH (a:N)-[:R]->(b)-[:R]->(c) WITH DISTINCT a, c RETURN count(*) AS p",
    "MATCH (a:N)-[:R]->(b)-[:R]->(c) WITH DISTINCT c RETURN count(*) AS t",
    # packed top-k with ties, nulls, DESC, SKIP
    "MATCH (a:N) RETURN a.s AS s, id(a) AS i ORDER BY s DESC, i SKIP 5 LIMIT 9",
    "MATCH (a:N)-[r:R]->(b) RETURN r.since AS y, id(r) AS i ORDER BY y, i LIMIT 11",
    # exists() as value / in aggregates at cardinality
    "MATCH (a:N) RETURN exists((a)-[:R]->()) AS e, count(*) AS c ORDER BY e",
    "MATCH (a:N) RETURN sum(CASE WHEN exists((a)<-[:R]-()) THEN 1 ELSE 0 END) AS s",
    # identical UNION ALL branches (CSE shares + caches the stem)
    "MATCH (a:N) WHERE a.num > 0 RETURN count(*) AS c "
    "UNION ALL MATCH (a:N) WHERE a.num > 0 RETURN count(*) AS c",
]


@pytest.mark.parametrize("query", QUERIES)
def test_stress_differential(graphs, query):
    g_local, g_tpu = graphs
    expected = g_local.cypher(query).records.to_bag()
    got = g_tpu.cypher(query).records.to_bag()
    assert got == expected, f"\nquery: {query}\ntpu: {got!r}\nlocal: {expected!r}"


def test_construct_through_tpu_backend(graphs):
    _, g_tpu = graphs
    r = g_tpu.cypher(
        "MATCH (a:N)-[r:R]->(b) WHERE r.since >= 2020 "
        "CONSTRUCT NEW (:Hit {y: r.since}) RETURN GRAPH"
    )
    out = r.graph.cypher("MATCH (h:Hit) RETURN count(*) AS c").records.collect()
    g_local = graphs[0]
    want = g_local.cypher(
        "MATCH (a:N)-[r:R]->(b) WHERE r.since >= 2020 RETURN count(*) AS c"
    ).records.collect()
    assert out[0]["c"] == want[0]["c"]


def test_shared_subplan_computes_once(graphs):
    """The planner memoizes shared logical subtrees onto ONE operator object
    and RelationalOperator.table memoizes per object — the architectural
    replacement for the reference's InsertCachingOperators + Table.cache
    (RelationalOptimizer.scala:41; round-1 'cache() is not a cache')."""
    _, g_tpu = graphs
    r = g_tpu.cypher(
        "MATCH (a:N)-[:R]->(b) WITH a, b MATCH (b)-[:R]->(c) "
        "RETURN count(*) AS c"
    )
    plan = r.relational_plan
    visits = {}

    def walk(op):
        visits[id(op)] = visits.get(id(op), 0) + 1
        if visits[id(op)] > 1:
            return  # shared subtree: one object, multiple parents
        for ch in op.children:
            walk(ch)

    walk(plan)
    # the planner memo makes the shared MATCH subtree ONE object with
    # multiple parents
    assert any(v > 1 for v in visits.values())
    import tpu_cypher.relational.ops as R

    calls = {"n": 0}
    orig = R.RelationalOperator.table.fget

    def counting(self):
        if self._table is None:
            calls["n"] += 1
        return orig(self)

    R.RelationalOperator.table = property(counting)
    try:
        r2 = g_tpu.cypher(
            "MATCH (a:N)-[:R]->(b) WITH a, b MATCH (b)-[:R]->(c) "
            "RETURN count(*) AS c"
        )
        r2.records.collect()
        first = calls["n"]
        r2.records.collect()  # second pull: memoized, no recompute
        assert calls["n"] == first
    finally:
        R.RelationalOperator.table = property(orig)

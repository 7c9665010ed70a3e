"""Fused ExpandInto close counts: count(*) over a cycle/triangle pattern
runs as one chain program with a binary-search edge probe instead of
materializing the k-hop row set (``CsrExpandIntoOp._chain_close_count``,
BASELINE config #3's workload). Every case is differential vs the oracle."""

import numpy as np
import pytest

from tpu_cypher import CypherSession
from tpu_cypher.backend.tpu import jit_ops as J

TRIANGLE = "MATCH (a)-[:K]->(b)-[:K]->(c)-[:K]->(a) RETURN count(*) AS t"


def _pair(create):
    return (
        CypherSession.local().create_graph_from_create_query(create),
        CypherSession.tpu().create_graph_from_create_query(create),
    )


def _random_create(seed, n, e, labels=("N",)):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    parts = [f"(n{i}:{labels[i % len(labels)]})" for i in range(n)]
    parts += [f"(n{s})-[:K]->(n{d})" for s, d in zip(src, dst)]
    return "CREATE " + ", ".join(parts)


QUERIES = [
    TRIANGLE,
    # labeled intermediate/far nodes (label masks inside the chain walk)
    "MATCH (a:N)-[:K]->(b:N)-[:K]->(c:N)-[:K]->(a) RETURN count(*) AS t",
    # 2-cycle close: single-hop chain under the into op
    "MATCH (a)-[:K]->(b)-[:K]->(a) RETURN count(*) AS t",
    # undirected close: both probe orientations, loops dropped once
    "MATCH (a)-[:K]->(b)-[:K]->(c)-[:K]-(a) RETURN count(*) AS t",
    # backwards hop inside the chain
    "MATCH (a)<-[:K]-(b)-[:K]->(c)-[:K]->(a) RETURN count(*) AS t",
    # 4-cycle: longer chain before the close
    "MATCH (a)-[:K]->(b)-[:K]->(c)-[:K]->(d)-[:K]->(a) RETURN count(*) AS t",
]


@pytest.mark.parametrize("query", QUERIES)
def test_close_count_differential(query):
    g_local, g_tpu = _pair(_random_create(7, 30, 150, labels=("N", "M")))
    lv = [dict(r) for r in g_local.cypher(query).records.collect()]
    tv = [dict(r) for r in g_tpu.cypher(query).records.collect()]
    assert tv == lv, f"{query}: {tv} vs {lv}"


def test_close_count_self_loops_and_cycles():
    # self-loops can only close onto themselves; openCypher rel-isomorphism
    # (pairwise-distinct relationships per MATCH) makes most of these 0 —
    # both backends must agree exactly
    for create, expected in (
        ("CREATE (x:N)-[:K]->(x)", 0),
        ("CREATE (x:N)-[:K]->(y:N), (y)-[:K]->(x), (x)-[:K]->(x)", 3),
    ):
        g_local, g_tpu = _pair(create)
        lv = [dict(r) for r in g_local.cypher(TRIANGLE).records.collect()]
        tv = [dict(r) for r in g_tpu.cypher(TRIANGLE).records.collect()]
        assert tv == lv == [{"t": expected}]


@pytest.mark.parametrize("seed,loopy", [(3, True), (11, False)])
def test_close_count_uses_fused_program(monkeypatch, seed, loopy):
    """The triangle count(*) must go through a fused close-count program
    (no chain materialization) WHETHER OR NOT the graph has self-loops:
    loop-free graphs drop the uniqueness filters by proof and run
    into_close_count; loopy graphs enforce the filters in-kernel via
    into_close_count_unique. The materializing into_probe must not run."""
    calls = {"close": 0, "unique": 0, "probe": 0}
    orig_close = J.into_close_count
    orig_unique = J.into_close_count_unique
    orig_probe = J.into_probe

    def spy_close(*a, **k):
        calls["close"] += 1
        return orig_close(*a, **k)

    def spy_unique(*a, **k):
        calls["unique"] += 1
        return orig_unique(*a, **k)

    def spy_probe(*a, **k):
        calls["probe"] += 1
        return orig_probe(*a, **k)

    monkeypatch.setattr(J, "into_close_count", spy_close)
    monkeypatch.setattr(J, "into_close_count_unique", spy_unique)
    monkeypatch.setattr(J, "into_probe", spy_probe)
    create = _random_create(seed, 20, 80)
    if not loopy:
        create = _random_create_loop_free(seed, 20, 80)
    g_local = CypherSession.local().create_graph_from_create_query(create)
    g_tpu = CypherSession.tpu().create_graph_from_create_query(create)
    expected = [dict(r) for r in g_local.cypher(TRIANGLE).records.collect()]
    got = [dict(r) for r in g_tpu.cypher(TRIANGLE).records.collect()]
    assert got == expected
    # loop-free graphs drop the filters by PROOF (plain kernel); loopy
    # graphs must route through the in-kernel enforcement variant
    assert (calls["close"], calls["unique"]) == ((0, 1) if loopy else (1, 0))
    assert calls["probe"] == 0


def _random_create_loop_free(seed, n, e):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, e)
    dst = (src + 1 + rng.integers(0, n - 1, e)) % n
    parts = [f"(n{i}:N)" for i in range(n)]
    parts += [f"(n{s})-[:K]->(n{d})" for s, d in zip(src, dst)]
    return "CREATE " + ", ".join(parts)


def test_close_count_materializes_when_columns_needed():
    """RETURN of actual columns keeps the materializing path (and stays
    correct)."""
    q = "MATCH (a:N)-[:K]->(b)-[:K]->(c)-[:K]->(a) RETURN count(DISTINCT a) AS t"
    g_local, g_tpu = _pair(_random_create(9, 25, 120))
    lv = [dict(r) for r in g_local.cypher(q).records.collect()]
    tv = [dict(r) for r in g_tpu.cypher(q).records.collect()]
    assert tv == lv

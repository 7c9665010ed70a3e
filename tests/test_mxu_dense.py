"""MXU dense tier (SURVEY §2.2⚙ / tpu-first design): 2-hop close counts and
DISTINCT-endpoint counts as blocked bf16 ``A @ A`` on the systolic array —
the count becomes a matmul chain, which is where a TPU's FLOPs live. On CPU
the tier is off by default (dense N^3 does not win there); these tests
FORCE it (``TPU_CYPHER_MXU_DENSE=force``) to pin exactness differentially:
bf16 entries are small exact integers, accumulation is f32 with f64/int64
reductions, so the counts must be bit-equal to the oracle."""

import numpy as np
import pytest

from tpu_cypher import CypherSession
from tpu_cypher.backend.tpu import jit_ops as J

TRIANGLE = "MATCH (a)-[:K]->(b)-[:K]->(c)-[:K]->(a) RETURN count(*) AS t"


@pytest.fixture(autouse=True)
def _force_mxu(monkeypatch):
    monkeypatch.setenv("TPU_CYPHER_MXU_DENSE", "force")


def _random_create(seed, n, e, labels=("N",), loops=False):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    if not loops:
        keep = src != dst
        src, dst = src[keep], dst[keep]
    parts = [f"(n{i}:{labels[i % len(labels)]} {{v: {i}}})" for i in range(n)]
    parts += [f"(n{s})-[:K]->(n{d})" for s, d in zip(src, dst)]
    return "CREATE " + ", ".join(parts)


QUERIES = [
    TRIANGLE,
    # labeled middle/far nodes: masks fold into the matmul operands
    "MATCH (a:N)-[:K]->(b:M)-[:K]->(c:N)-[:K]->(a) RETURN count(*) AS t",
    # backwards hop: the reversed dense adjacency
    "MATCH (a)<-[:K]-(b)-[:K]->(c)-[:K]->(a) RETURN count(*) AS t",
    # 2-cycle close (1-hop chain under the into op stays on the walk path;
    # this guards against misrouting)
    "MATCH (a)-[:K]->(b)-[:K]->(a) RETURN count(*) AS t",
    # restricted frontier with multiplicity through a prior expansion
    "MATCH (s {v: 1})-[:K]->(a) WITH a "
    "MATCH (a)-[:K]->(b)-[:K]->(c), (a)-[:K]->(c) RETURN count(*) AS t",
    # DISTINCT endpoints over the dense boolean product
    "MATCH (a)-[:K]->(b)-[:K]->(c) WITH DISTINCT a, c RETURN count(*) AS t",
    "MATCH (a:N)-[:K]->(b:M)-[:K]->(c) WITH DISTINCT a, c RETURN count(*) AS t",
]


@pytest.mark.parametrize("query", QUERIES)
@pytest.mark.parametrize("seed", [7, 19])
def test_mxu_dense_differential(query, seed):
    create = _random_create(seed, 30, 140, labels=("N", "M"))
    gl = CypherSession.local().create_graph_from_create_query(create)
    gt = CypherSession.tpu().create_graph_from_create_query(create)
    lv = [dict(r) for r in gl.cypher(query).records.collect()]
    tv = [dict(r) for r in gt.cypher(query).records.collect()]
    assert tv == lv, f"{query}: {tv} vs {lv}"


def test_mxu_dense_parallel_edges_and_multiplicity():
    """bf16 multiplicity entries: parallel edges contribute their exact
    counts through the matmul."""
    create = (
        "CREATE (a:N {v: 0})-[:K]->(b:N {v: 1}), (a)-[:K]->(b), "
        "(b)-[:K]->(c:N {v: 2}), (c)-[:K]->(a), (c)-[:K]->(a)"
    )
    gl = CypherSession.local().create_graph_from_create_query(create)
    gt = CypherSession.tpu().create_graph_from_create_query(create)
    lv = [dict(r) for r in gl.cypher(TRIANGLE).records.collect()]
    tv = [dict(r) for r in gt.cypher(TRIANGLE).records.collect()]
    assert tv == lv  # 2 (a->b) * 1 (b->c) * 2 (c->a) rotations

    q = "MATCH (a)-[:K]->(b)-[:K]->(c) WITH DISTINCT a, c RETURN count(*) AS t"
    lv = [dict(r) for r in gl.cypher(q).records.collect()]
    tv = [dict(r) for r in gt.cypher(q).records.collect()]
    assert tv == lv


def test_mxu_kernels_route(monkeypatch):
    """The triangle count must go through mxu_close_count when forced (and
    NOT through the walk kernel)."""
    calls = {"mxu": 0, "walk": 0}
    orig_mxu = J.mxu_close_count
    orig_walk = J.into_close_count

    def spy_mxu(*a, **k):
        calls["mxu"] += 1
        return orig_mxu(*a, **k)

    def spy_walk(*a, **k):
        calls["walk"] += 1
        return orig_walk(*a, **k)

    monkeypatch.setattr(J, "mxu_close_count", spy_mxu)
    monkeypatch.setattr(J, "into_close_count", spy_walk)
    g = CypherSession.tpu().create_graph_from_create_query(
        _random_create(3, 25, 100)
    )
    g.cypher(TRIANGLE).records.collect()
    assert calls["mxu"] == 1
    assert calls["walk"] == 0


def test_mxu_disabled_on_cpu_by_default(monkeypatch):
    monkeypatch.setenv("TPU_CYPHER_MXU_DENSE", "auto")
    calls = {"mxu": 0}
    orig = J.mxu_close_count

    def spy(*a, **k):
        calls["mxu"] += 1
        return orig(*a, **k)

    monkeypatch.setattr(J, "mxu_close_count", spy)
    g = CypherSession.tpu().create_graph_from_create_query(
        _random_create(4, 20, 60)
    )
    g.cypher(TRIANGLE).records.collect()
    assert calls["mxu"] == 0


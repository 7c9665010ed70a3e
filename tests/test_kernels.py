"""Device kernel tests: fused kernels vs the engine/oracle, plus the sharded
multi-device path on the virtual CPU mesh."""

import numpy as np
import pytest

import jax

from tpu_cypher.backend.tpu.kernels import (
    CsrGraph,
    triangle_count,
    two_hop_count,
    two_hop_expand,
    walk_counts,
)
from tpu_cypher.parallel.mesh import (
    make_mesh,
    pad_edges,
    shard_edge_arrays,
    sharded_training_step,
    sharded_two_hop_count,
    sharded_walk_step,
)


def ring_graph(n):
    """0 -> 1 -> 2 -> ... -> n-1 -> 0"""
    ids = np.arange(n, dtype=np.int64) * 7 + 3  # non-contiguous ids
    src = ids
    dst = np.roll(ids, -1)
    return CsrGraph.build(ids, src, dst)


def random_graph(n, e, seed=0):
    rng = np.random.default_rng(seed)
    ids = np.arange(n, dtype=np.int64)
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    return CsrGraph.build(ids, ids[src], ids[dst]), ids[src], ids[dst]


def brute_two_hop(src, dst):
    out_edges = {}
    for s, d in zip(src, dst):
        out_edges.setdefault(s, []).append(d)
    count = 0
    pairs = set()
    for s, d in zip(src, dst):
        for c in out_edges.get(d, []):
            count += 1
            pairs.add((s, c))
    return count, len(pairs)


def brute_triangles(src, dst):
    # Cypher semantics: every (r1, r2, r3) relationship triple is a match
    from collections import Counter

    edge_mult = Counter(zip(src.tolist(), dst.tolist()))
    out_edges = {}
    for s, d in zip(src.tolist(), dst.tolist()):
        out_edges.setdefault(s, []).append(d)
    n = 0
    for s, d in zip(src, dst):
        for c in out_edges.get(d, []):
            n += edge_mult.get((c, s), 0)
    return n


def test_csr_build():
    g = ring_graph(5)
    assert g.num_nodes == 5 and g.num_edges == 5
    assert np.asarray(g.degrees).tolist() == [1, 1, 1, 1, 1]


def test_two_hop_count_ring():
    g = ring_graph(10)
    assert int(two_hop_count(g.row_ptr, g.col_idx)) == 10


def test_two_hop_vs_bruteforce():
    g, src, dst = random_graph(50, 300)
    # CSR dedups nothing; multi-edges allowed
    total = int(two_hop_count(g.row_ptr, g.col_idx))
    expected_count, expected_distinct = brute_two_hop(
        np.asarray(g.src_idx), np.asarray(g.col_idx)
    )
    assert total == expected_count
    a, c, distinct = two_hop_expand(g.row_ptr, g.col_idx, g.src_idx, total)
    assert len(np.asarray(a)) == total
    assert int(distinct) == expected_distinct


def test_triangles_vs_bruteforce():
    g, _, _ = random_graph(30, 200, seed=1)
    total = int(two_hop_count(g.row_ptr, g.col_idx))
    got = int(triangle_count(g.row_ptr, g.col_idx, g.src_idx, total))
    expected = brute_triangles(np.asarray(g.src_idx), np.asarray(g.col_idx))
    assert got == expected


def test_walk_counts_ring():
    g = ring_graph(6)
    start = np.zeros(6, np.int64)
    start[0] = 1
    per_hop = np.asarray(walk_counts(g.src_idx, g.col_idx, start, 4, g.num_nodes))
    # on a ring, exactly one walk per hop
    assert per_hop.sum(axis=1).tolist() == [1, 1, 1, 1]
    assert per_hop[3].tolist() == [0, 0, 0, 0, 1, 0]


def test_two_hop_matches_engine():
    """Fused kernel count == full engine result on the same graph."""
    from tpu_cypher import CypherSession

    s = CypherSession.local()
    g = s.create_graph_from_create_query(
        "CREATE (a:P {i:1})-[:R]->(b:P {i:2})-[:R]->(c:P {i:3}), (a)-[:R]->(c), (c)-[:R]->(a)"
    )
    engine = g.cypher("MATCH (x)-[:R]->(y)-[:R]->(z) RETURN count(*) AS c").records.collect()
    src = np.array([1, 2, 1, 3], np.int64)
    dst = np.array([2, 3, 3, 1], np.int64)
    csr = CsrGraph.build(np.array([1, 2, 3], np.int64), src, dst)
    assert engine[0]["c"] == int(two_hop_count(csr.row_ptr, csr.col_idx))


# -- sharded (8 virtual devices) --------------------------------------------


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) >= 8, "conftest must force 8 host devices"
    return make_mesh(jax.devices()[:8])


def test_sharded_two_hop_count(mesh):
    g, _, _ = random_graph(40, 256, seed=2)
    expected = int(two_hop_count(g.row_ptr, g.col_idx))
    src, col, _ = pad_edges(np.asarray(g.src_idx), np.asarray(g.col_idx), 8)
    deg = np.asarray(g.degrees)
    src_d, col_d = shard_edge_arrays(mesh, src, col)
    got = int(sharded_two_hop_count(mesh, deg, col_d))
    assert got == expected


def test_sharded_walk_step(mesh):
    g = ring_graph(8)
    src, col, _ = pad_edges(np.asarray(g.src_idx), np.asarray(g.col_idx), 8)
    src_d, col_d = shard_edge_arrays(mesh, src, col)
    step = sharded_walk_step(mesh, g.num_nodes)
    p = np.zeros(8, np.int64)
    p[0] = 1
    p1 = np.asarray(step(p, src_d, col_d))
    assert p1.tolist() == [0, 1, 0, 0, 0, 0, 0, 0]


def test_sharded_training_step(mesh):
    g, _, _ = random_graph(32, 128, seed=3)
    expected_two_hop = int(two_hop_count(g.row_ptr, g.col_idx))
    src, col, _ = pad_edges(np.asarray(g.src_idx), np.asarray(g.col_idx), 8)
    src_d, col_d = shard_edge_arrays(mesh, src, col)
    step = sharded_training_step(mesh, g.num_nodes, hops=3)
    p0 = np.ones(g.num_nodes, np.int64)
    deg = np.asarray(g.degrees).astype(np.int64)
    p_final, hop_counts, two_hop = step(p0, deg, src_d, col_d)
    assert int(two_hop) == expected_two_hop
    # hop 1 count with all-ones start = number of edges
    assert int(np.asarray(hop_counts)[0]) == g.num_edges


def test_frontier_degree_sum_matches_numpy():
    """The frontier degree-sum program equals the NumPy gather+sum, incl.
    masked slots and empty input."""
    import numpy as np
    import jax.numpy as jnp

    from tpu_cypher.backend.tpu.jit_ops import frontier_degree_sum

    rng = np.random.default_rng(5)
    for n_nodes, n_frontier in [(1, 1), (7, 3), (1000, 3333), (4096, 1024)]:
        deg = rng.integers(0, 100, n_nodes).astype(np.int32)
        rp = jnp.asarray(np.concatenate([[0], np.cumsum(deg)]).astype(np.int32))
        pos = jnp.asarray(rng.integers(0, n_nodes, n_frontier).astype(np.int64))
        present = jnp.asarray(rng.random(n_frontier) < 0.8)
        want = int(
            np.where(np.asarray(present), deg[np.asarray(pos)], 0).sum()
        )
        assert int(frontier_degree_sum(rp, pos, present)) == want
    rp = jnp.asarray(np.array([0, 5, 12], np.int32))
    assert (
        int(frontier_degree_sum(rp, jnp.zeros(0, jnp.int64), jnp.zeros(0, bool)))
        == 0
    )


def test_distinct_endpoints_count_fused_matches_oracle(monkeypatch):
    """count(DISTINCT chain endpoints) runs through the fused no-materialize
    path and matches the oracle across directions, labels, and field subsets."""
    import numpy as np

    from tpu_cypher import CypherSession
    from tpu_cypher.backend.tpu import jit_ops

    calls = {"n": 0}
    orig = jit_ops.distinct_pairs_count_final

    def spy(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    monkeypatch.setattr(jit_ops, "distinct_pairs_count_final", spy)

    rng = np.random.default_rng(11)
    n, e = 30, 120
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    parts = [
        f"(n{i}:P {{i:{i}}})" if i % 3 else f"(n{i}:P:Q {{i:{i}}})"
        for i in range(n)
    ]
    parts += [f"(n{s})-[:K]->(n{d})" for s, d in zip(src, dst)]
    create = "CREATE " + ", ".join(parts)

    fused_queries = [
        "MATCH (a:P)-[:K]->(b)-[:K]->(c) WITH DISTINCT a, c RETURN count(*) AS x",
        "MATCH (a:P)-[:K]->(b)-[:K]->(c) WITH DISTINCT c RETURN count(*) AS x",
        "MATCH (a:P)-[:K]->(b)-[:K]->(c) WITH DISTINCT a RETURN count(*) AS x",
        "MATCH (a)<-[:K]-(b)<-[:K]-(c:Q) WITH DISTINCT a, c RETURN count(*) AS x",
    ]
    # not fused, must stay correct: the star shape (two expands sharing
    # frontier b), and a 3-hop chain whose NON-adjacent relationship-
    # uniqueness predicate (r0 <> r2 can be violated via a 2-cycle, not
    # just a self-loop) cannot be dropped, so the filter stays planned
    unfused_queries = [
        "MATCH (a)-[:K]->(b:Q)-[:K]->(c) WITH DISTINCT a, c RETURN count(*) AS x",
        "MATCH (a)-[:K]->(b)-[:K]->(c)-[:K]->(d:P) WITH DISTINCT a, d RETURN count(*) AS x",
    ]
    gl = CypherSession.local().create_graph_from_create_query(create)
    gt = CypherSession.tpu().create_graph_from_create_query(create)
    for q in fused_queries + unfused_queries:
        want = gl.cypher(q).records.collect()
        got = gt.cypher(q).records.collect()
        assert got == want, f"{q}: {got} != {want}"
    assert calls["n"] >= len(fused_queries), "fused distinct-endpoints path not used"


def test_fused_var_length_expand_matches_oracle(monkeypatch):
    """Var-length MATCH through the fused CSR frontier loop is differential-
    equal to the oracle (edge-distinctness, bounds, labels, cycles, parallel
    edges) and genuinely routes through CsrVarExpandOp."""
    import numpy as np

    from tpu_cypher import CypherSession
    from tpu_cypher.backend.tpu import jit_ops

    calls = {"n": 0}
    orig = jit_ops.varlen_hop

    def spy(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    monkeypatch.setattr(jit_ops, "varlen_hop", spy)

    rng = np.random.default_rng(3)
    n, e = 14, 40
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    parts = [f"(n{i}:V {{i:{i}}})" if i % 2 else f"(n{i}:V:W {{i:{i}}})" for i in range(n)]
    # includes self-loops, cycles, and duplicated (parallel) edges
    parts += [f"(n{s})-[:E]->(n{d})" for s, d in zip(src, dst)]
    parts += ["(n0)-[:E]->(n1)", "(n0)-[:E]->(n1)", "(n1)-[:E]->(n0)", "(n2)-[:E]->(n2)"]
    create = "CREATE " + ", ".join(parts)

    fused_queries = [
        "MATCH (x:V)-[:E*1..3]->(y) RETURN count(*) AS c",
        "MATCH (x:V)-[:E*2..2]->(y:W) RETURN count(*) AS c",
        "MATCH (x:W)-[:E*1..2]->(y) RETURN x.i, y.i, count(*) AS c ORDER BY x.i, y.i",
        "MATCH (x:V)-[:E*2..4]->(y) WITH DISTINCT x, y RETURN count(*) AS c",
    ]
    # rel list required / zero lower bound / undirected: classic cascade
    classic_queries = [
        "MATCH (x:V)-[r:E*1..2]->(y) RETURN x.i, size(r) AS s, count(*) AS c ORDER BY x.i, s",
        "MATCH (x:V)-[:E*0..2]->(y) RETURN count(*) AS c",
        "MATCH (x:V)-[:E*1..2]-(y) RETURN count(*) AS c",
    ]
    gl = CypherSession.local().create_graph_from_create_query(create)
    gt = CypherSession.tpu().create_graph_from_create_query(create)
    for q in fused_queries + classic_queries:
        want = gl.cypher(q).records.collect()
        got = gt.cypher(q).records.collect()
        assert got == want, f"{q}: {got} != {want}"
    assert calls["n"] >= len(fused_queries), "var-length queries bypassed the fused loop"


def test_order_by_limit_topk_matches_oracle(monkeypatch):
    """ORDER BY ... [SKIP s] LIMIT k through the packed top-k path is
    row-identical to the oracle's stable full sort (ties break by original
    row order), and genuinely routes through order_topk."""
    import numpy as np

    from tpu_cypher import CypherSession
    from tpu_cypher.backend.tpu import jit_ops

    calls = {"n": 0}
    orig = jit_ops.order_topk

    def spy(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    monkeypatch.setattr(jit_ops, "order_topk", spy)
    # the top-k is chosen from this many rows up; forty rows take it here
    monkeypatch.setattr(jit_ops, "ORDER_TOPK_MIN_ROWS", 0)

    rng = np.random.default_rng(9)
    parts = []
    for i in range(40):
        v = int(rng.integers(0, 8))  # many ties
        s = ["'x'", "'y'", "'z'", "null"][int(rng.integers(0, 4))]
        nullv = "null" if rng.random() < 0.2 else v
        parts.append(f"(:N {{v: {nullv}, s: {s}, i: {i}}})")
    create = "CREATE " + ", ".join(parts)

    fused = [
        "MATCH (n:N) RETURN n.v AS v, n.i AS i ORDER BY v LIMIT 7",
        "MATCH (n:N) RETURN n.v AS v, n.i AS i ORDER BY v DESC LIMIT 5",
        "MATCH (n:N) RETURN n.s AS s, n.v AS v, n.i AS i ORDER BY s, v DESC LIMIT 9",
        "MATCH (n:N) RETURN n.v AS v, n.i AS i ORDER BY v SKIP 4 LIMIT 6",
        "MATCH (n:N) RETURN n.i AS i ORDER BY n.v LIMIT 100",
    ]
    gl = CypherSession.local().create_graph_from_create_query(create)
    gt = CypherSession.tpu().create_graph_from_create_query(create)
    for q in fused:
        want = [dict(r) for r in gl.cypher(q).records.collect()]
        got = [dict(r) for r in gt.cypher(q).records.collect()]
        assert got == want, f"{q}: {got[:4]}... != {want[:4]}..."
    assert calls["n"] >= len(fused), "ORDER BY LIMIT bypassed the top-k path"


def test_fused_optional_expand_matches_oracle(monkeypatch):
    """OPTIONAL MATCH of a single unlabeled directed expand runs the fused
    left-outer CSR program; results differential-equal to the oracle,
    including all-unmatched, duplicated frontiers, and null propagation."""
    import numpy as np

    from tpu_cypher import CypherSession
    from tpu_cypher.backend.tpu import jit_ops

    calls = {"n": 0}
    orig = jit_ops.optional_expand_materialize

    def spy(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    monkeypatch.setattr(jit_ops, "optional_expand_materialize", spy)

    rng = np.random.default_rng(17)
    n, e = 25, 50
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    parts = [f"(n{i}:V {{i:{i}}})" for i in range(n)]
    parts += [f"(n{s})-[:E {{w:{int(w)}}}]->(n{d})" for s, d, w in
              zip(src, dst, rng.integers(0, 5, e))]
    create = "CREATE " + ", ".join(parts)

    fused = [
        "MATCH (x:V) OPTIONAL MATCH (x)-[r:E]->(y) RETURN x.i, y.i, r.w ORDER BY x.i, y.i, r.w",
        "MATCH (x:V) OPTIONAL MATCH (x)-[r:E]->(y) RETURN count(*) AS rows, count(y) AS m, sum(r.w) AS s",
        "MATCH (x:V) OPTIONAL MATCH (x)-[:E]->(y) RETURN x.i, count(y) AS c ORDER BY x.i",
        # backward: bound var is the edge TARGET
        "MATCH (x:V) OPTIONAL MATCH (y)-[r:E]->(x) RETURN x.i, y.i, r.w ORDER BY x.i, y.i, r.w",
        # zero relationships of the requested type: all rows null-padded
        "MATCH (x:V) OPTIONAL MATCH (x)-[r:NOPE]->(y) RETURN x.i, r.w, y.i ORDER BY x.i",
    ]
    classic = [
        # WHERE (on the base match or inside OPTIONAL), far labels, and
        # undirected patterns keep the classic outer join
        "MATCH (x:V) WHERE x.i > 20 OPTIONAL MATCH (x)-[:E]->(y) RETURN x.i, count(y) AS c ORDER BY x.i",
        "MATCH (x:V) OPTIONAL MATCH (x)-[r:E]->(y) WHERE y.i > 10 RETURN count(y) AS c",
        "MATCH (x:V) OPTIONAL MATCH (x)-[:E]-(y) RETURN count(y) AS c",
    ]
    gl = CypherSession.local().create_graph_from_create_query(create)
    gt = CypherSession.tpu().create_graph_from_create_query(create)
    for q in fused + classic:
        want = gl.cypher(q).records.to_bag()
        got = gt.cypher(q).records.to_bag()
        assert got == want, f"{q}: {got} != {want}"
    assert calls["n"] >= len(fused), "optional expands bypassed the fused path"


def test_plan_cache_reuses_plans_and_rebinds_params():
    """Repeated query text on the same graph reuses the planned operator
    tree (no re-parse/re-plan); parameter VALUES rebind per execution, and
    catalog-touching queries stay uncached."""
    from tpu_cypher import CypherSession

    session_graph = CypherSession.local().create_graph_from_create_query(
        "CREATE (:V {i:1}), (:V {i:2}), (:V {i:3})"
    )
    sess = session_graph.session
    q = "MATCH (n:V) WHERE n.i < $p RETURN count(*) AS c"
    r1 = session_graph.cypher(q, parameters={"p": 2})
    assert [dict(r) for r in r1.records.collect()] == [{"c": 1}]
    r2 = session_graph.cypher(q, parameters={"p": 10})
    assert [dict(r) for r in r2.records.collect()] == [{"c": 3}]
    # the cache holds a TABLE-FREE clone; every execution (including the
    # first) keeps its own plan instance
    entry = next(
        v for k, v in sess._plan_cache.items() if k[0] == q and k[2] == (("p", "int"),)
    )
    assert entry[2] is not r1.relational_plan
    assert r2.relational_plan is not r1.relational_plan
    assert entry[2]._table is None, "cached plan pinned a materialized table"
    # param TYPE change produces a separate entry (no wrongly-typed replay)
    r3 = session_graph.cypher(q, parameters={"p": 2.5})
    assert [dict(r) for r in r3.records.collect()] == [{"c": 2}]
    # a different graph with the same text must not collide
    g2 = sess.create_graph_from_create_query("CREATE (:V {i:1})")
    assert [dict(r) for r in g2.cypher(q, parameters={"p": 10}).records.collect()] == [
        {"c": 1}
    ]
    # lazy results handed out earlier must KEEP their own bindings after
    # later cache hits (each hit executes a per-call plan clone)
    r_old = session_graph.cypher(q, parameters={"p": 2})
    session_graph.cypher(q, parameters={"p": 10}).records.collect()
    assert [dict(r) for r in r_old.records.collect()] == [{"c": 1}]
    # catalog-flavored text is never cached
    before = len(sess._plan_cache)
    try:
        session_graph.cypher("MATCH (n:V) RETURN count(*) AS c // CATALOG")
    except Exception:
        pass
    assert len(sess._plan_cache) == before


def test_cse_shares_identical_union_branches():
    """Structurally identical subplans merge into ONE shared operator whose
    table computes once, wrapped in a shared CacheOp (the reference's
    InsertCachingOperators analog, RelationalOptimizer.scala:41-90)."""
    from tpu_cypher import CypherSession
    from tpu_cypher.relational.ops import CacheOp, UnionAllOp

    g = CypherSession.local().create_graph_from_create_query(
        "CREATE (:V {i:1}), (:V {i:2})"
    )
    q = (
        "MATCH (a:V) WHERE a.i > 0 RETURN a.i AS x "
        "UNION ALL MATCH (a:V) WHERE a.i > 0 RETURN a.i AS x"
    )
    res = g.cypher(q)
    rows = [dict(r) for r in res.records.collect()]
    assert sorted(r["x"] for r in rows) == [1, 1, 2, 2]
    op = res.relational_plan
    while op.children and not isinstance(op, UnionAllOp):
        op = op.children[0]
    assert isinstance(op, UnionAllOp)
    left, right = op.children
    assert left is right, "identical UNION branches were not merged"
    assert isinstance(left, CacheOp), "shared subtree not wrapped in CacheOp"


def test_cse_never_merges_nondeterministic_branches():
    """Two syntactic rand() occurrences are independent evaluations — CSE
    must not collapse them (UNION would then wrongly dedup to one row)."""
    from tpu_cypher import CypherSession
    from tpu_cypher.relational.ops import UnionAllOp

    g = CypherSession.local().create_graph_from_create_query("CREATE (:V)")
    q = "MATCH (a:V) RETURN rand() AS x UNION ALL MATCH (a:V) RETURN rand() AS x"
    res = g.cypher(q)
    rows = [dict(r)["x"] for r in res.records.collect()]
    assert len(rows) == 2 and all(0 <= v < 1 for v in rows)
    op = res.relational_plan
    while op.children and not isinstance(op, UnionAllOp):
        op = op.children[0]
    assert op.children[0] is not op.children[1], "rand() branches merged"


def test_cse_does_not_merge_different_branches():
    from tpu_cypher import CypherSession
    from tpu_cypher.relational.ops import UnionAllOp

    g = CypherSession.local().create_graph_from_create_query(
        "CREATE (:V {i:1}), (:V {i:2})"
    )
    q = (
        "MATCH (a:V) WHERE a.i > 0 RETURN a.i AS x "
        "UNION ALL MATCH (a:V) WHERE a.i > 1 RETURN a.i AS x"
    )
    res = g.cypher(q)
    rows = sorted(dict(r)["x"] for r in res.records.collect())
    assert rows == [1, 2, 2]
    op = res.relational_plan
    while op.children and not isinstance(op, UnionAllOp):
        op = op.children[0]
    assert op.children[0] is not op.children[1]


def test_var_length_after_other_expands_matches_oracle():
    """A fixed or var-length hop FEEDING a var-length hop must survive
    pruning (regression: the var-length classic shadow's static select list
    broke when upstream fused expands pruned pass-through columns)."""
    from tpu_cypher import CypherSession

    create = (
        "CREATE (a:P {i:0})-[:E]->(b:P {i:1})-[:E]->(c:P {i:2}),"
        "(a)-[:E]->(c), (c)-[:E]->(a)"
    )
    queries = [
        "MATCH (a:P)-[r:E]->(b)-[:E*1..2]->(d) RETURN count(*) AS k",
        "MATCH (a)-[:E*1..2]->(b)-[:E*1..2]->(d) RETURN count(*) AS k",
        "MATCH (a:P)-[:E]->(b)-[:E*1..2]->(d) RETURN a.i, count(*) AS k ORDER BY a.i",
    ]
    gl = CypherSession.local().create_graph_from_create_query(create)
    gt = CypherSession.tpu().create_graph_from_create_query(create)
    for q in queries:
        want = gl.cypher(q).records.collect()
        got = gt.cypher(q).records.collect()
        assert got == want, f"{q}: {got} != {want}"


def test_jitted_eval_param_type_not_conflated():
    """1 == True == 1.0 in Python, but the jitted-eval cache must not replay
    a program traced for one param type when called with another."""
    from tpu_cypher import CypherSession

    g = CypherSession.tpu().create_graph_from_create_query("CREATE (:V {i:1})")
    q = "MATCH (n:V) RETURN $p AS y"
    for p in (True, 1, 1.0, True):
        got = g.cypher(q, parameters={"p": p}).records.collect()
        assert got[0]["y"] == p and type(got[0]["y"]) is type(p), (p, got)


def test_branching_pattern_counts_match_oracle():
    """Branching MATCH patterns stack CsrExpandOps whose frontier is NOT the
    child's far node; the fused count chain must NOT compose them (regression
    for a real miscount found in review: 1 vs 5)."""
    from tpu_cypher import CypherSession

    create = "CREATE (a:V)-[:E]->(b:V), (a)-[:E]->(c:V), (b)-[:E]->(c)"
    queries = [
        "MATCH (x:V)-[:E]->(y), (x)-[:E]->(z) RETURN count(*) AS c",
        "MATCH (x)-[:E]->(y), (z)-[:E]->(x) RETURN count(*) AS c",
        "MATCH (x)-[:E]->(y)-[:E]->(z), (y)-[:E]->(w) RETURN count(*) AS c",
    ]
    gl = CypherSession.local().create_graph_from_create_query(create)
    gt = CypherSession.tpu().create_graph_from_create_query(create)
    for q in queries:
        want = gl.cypher(q).records.collect()
        got = gt.cypher(q).records.collect()
        assert got == want, f"{q}: {got} != {want}"


def test_count_only_2hop_uses_fused_chain(monkeypatch):
    """2-hop count through the engine is exact (differential vs oracle) AND
    genuinely routes through the single-program fused count chain."""
    from tpu_cypher import CypherSession
    from tpu_cypher.backend.tpu import jit_ops

    calls = {"n": 0}
    orig = jit_ops.path_count_chain

    def spy(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    monkeypatch.setattr(jit_ops, "path_count_chain", spy)

    create = (
        "CREATE (a:V {i:0})-[:E]->(b:V {i:1})-[:E]->(c:V {i:2}),"
        "(a)-[:E]->(c), (c)-[:E]->(a)"
    )
    q = "MATCH (x:V)-[:E]->(y)-[:E]->(z) RETURN count(*) AS c"
    want = CypherSession.local().create_graph_from_create_query(create).cypher(q).records.collect()
    got = CypherSession.tpu().create_graph_from_create_query(create).cypher(q).records.collect()
    assert got == want
    assert calls["n"] >= 1, "count query bypassed the fused count chain"


def test_count_chain_failure_falls_back_to_classic(monkeypatch):
    """If the fused count chain raises, the classic shadow cascade must
    still answer correctly — including with PRUNED fused inputs (the shadow
    shares the pruned child op, so its headers must recompute post-prune)."""
    from tpu_cypher import CypherSession
    from tpu_cypher.backend.tpu import jit_ops
    from tpu_cypher.backend.tpu.graph_index import GraphIndexError

    def boom(*a, **kw):
        raise GraphIndexError("forced chain failure")

    monkeypatch.setattr(jit_ops, "path_count_chain", boom)

    create = (
        "CREATE (a:V {i:0})-[:E]->(b:V {i:1})-[:E]->(c:V {i:2}),"
        "(a)-[:E]->(c), (c)-[:E]->(a)"
    )
    q = "MATCH (x:V)-[:E]->(y)-[:E]->(z) RETURN count(*) AS c"
    want = CypherSession.local().create_graph_from_create_query(create).cypher(q).records.collect()
    got = CypherSession.tpu().create_graph_from_create_query(create).cypher(q).records.collect()
    assert got == want


def test_count_only_1hop_uses_degree_sum_path(monkeypatch):
    """Single-hop unrestricted count routes through the O(frontier)
    degree-sum, not the edge dot."""
    from tpu_cypher import CypherSession
    from tpu_cypher.backend.tpu import jit_ops

    calls = {"n": 0}
    orig = jit_ops.frontier_degree_sum

    def spy(rp, pos, present):
        calls["n"] += 1
        return orig(rp, pos, present)

    monkeypatch.setattr(jit_ops, "frontier_degree_sum", spy)

    create = "CREATE (a:V)-[:E]->(b:V)-[:E]->(c:V), (a)-[:E]->(c)"
    q = "MATCH (x:V)-[:E]->(y) RETURN count(*) AS c"
    want = CypherSession.local().create_graph_from_create_query(create).cypher(q).records.collect()
    got = CypherSession.tpu().create_graph_from_create_query(create).cypher(q).records.collect()
    assert got == want
    assert calls["n"] >= 1, "1-hop count bypassed the degree-sum path"

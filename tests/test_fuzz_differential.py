"""Seeded random-query differential fuzz: oracle vs TPU backend.

A small grammar over the supported surface (filters, projections,
aggregation, ORDER BY/SKIP/LIMIT, DISTINCT, expands, var-length, OPTIONAL
MATCH, exists) generates queries against a random property graph with
adversarial values; every query must produce identical bags on both
backends. Seeded, so failures are reproducible and fixed seeds become
permanent regressions."""

import numpy as np
import pytest

from tpu_cypher import CypherSession
from tpu_cypher.api.mapping import NodeMappingBuilder, RelationshipMappingBuilder
from tpu_cypher.relational.graphs import ElementTable

N, E = 120, 360

_NUM_POOL = [None, 0, 1, -1, 2, 7, 1.5, -0.5, 0.0, float("nan"), 3, 10]
_STR_POOL = [None, "", "a", "b", "ab", "B", "zz"]


def _graph_args(seed):
    rng = np.random.default_rng(seed)
    ids = np.arange(N, dtype=np.int64) * 11 + 3
    src = rng.integers(0, N, E)
    dst = rng.integers(0, N, E)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    nums = [_NUM_POOL[rng.integers(0, len(_NUM_POOL))] for _ in range(N)]
    strs = [_STR_POOL[rng.integers(0, len(_STR_POOL))] for _ in range(N)]
    ws = [None if rng.random() < 0.15 else int(rng.integers(0, 9)) for _ in range(len(src))]
    return ids, src, dst, nums, strs, ws


def _build(session, ids, src, dst, nums, strs, ws):
    t = session.table_cls
    nm = (
        NodeMappingBuilder.on("id")
        .with_implied_label("N")
        .with_property_keys("num", "s")
        .build()
    )
    nodes = t.from_columns({"id": ids.tolist(), "num": nums, "s": strs})
    rm = (
        RelationshipMappingBuilder.on("rid")
        .from_("a")
        .to("b")
        .with_relationship_type("R")
        .with_property_key("w")
        .build()
    )
    rels = t.from_columns(
        {
            "rid": (np.arange(len(src), dtype=np.int64) + int(ids.max()) + 1).tolist(),
            "a": ids[src].tolist(),
            "b": ids[dst].tolist(),
            "w": ws,
        }
    )
    return session.read_from(ElementTable(nm, nodes), ElementTable(rm, rels))


def _gen_query(rng) -> str:
    def pred(var, prop, is_node=True):
        opts = [
            f"{var}.{prop} > {rng.integers(-2, 8)}",
            f"{var}.{prop} < {rng.integers(-2, 8)}",
            f"{var}.{prop} = {rng.integers(-1, 4)}",
            f"{var}.{prop} IS NOT NULL",
            f"{var}.{prop} IS NULL",
        ]
        if is_node:  # string property + pattern predicates are node-only
            opts += [
                f"{var}.s STARTS WITH 'a'",
                f"{var}.s = ''",
                f"exists(({var})-[:R]->())",
            ]
        return rng.choice(opts)

    shape = rng.integers(0, 6)
    if shape == 0:  # filtered scan + aggregation
        p = pred("n", "num")
        agg = rng.choice(
            ["count(*) AS c", "count(n.num) AS c", "min(n.num) AS c",
             "max(n.s) AS c", "avg(n.num) AS c", "collect(DISTINCT n.s) AS c"]
        )
        return f"MATCH (n:N) WHERE {p} RETURN {agg}"
    if shape == 1:  # projection + order + slice
        p = pred("n", "num")
        asc = rng.choice(["", " DESC"])
        lim = rng.integers(1, 15)
        sk = rng.integers(0, 5)
        return (
            f"MATCH (n:N) WHERE {p} "
            f"RETURN n.num AS v, n.s AS s, id(n) AS i ORDER BY v{asc}, s, i SKIP {sk} LIMIT {lim}"
        )
    if shape == 2:  # expand + rel filter + group
        p = pred("r", "w", is_node=False)
        return (
            f"MATCH (x:N)-[r:R]->(y) WHERE {p} "
            f"RETURN y.s AS k, count(*) AS c, sum(r.w) AS s ORDER BY c DESC, k LIMIT 10"
        )
    if shape == 3:  # chains / counts / distinct
        q = rng.choice(
            [
                "MATCH (a:N)-[:R]->(b)-[:R]->(c) RETURN count(*) AS c",
                "MATCH (a:N)-[:R]->(b)-[:R]->(c) WITH DISTINCT a, c RETURN count(*) AS c",
                "MATCH (a:N)-[:R]->(b)-[:R]->(c)-[:R]->(d) RETURN count(*) AS c",
                "MATCH (a:N)<-[:R]-(b) RETURN count(*) AS c",
                "MATCH (a:N)-[:R]-(b) RETURN count(*) AS c",
            ]
        )
        return q
    if shape == 4:  # var-length
        lo = rng.integers(1, 3)
        hi = lo + rng.integers(0, 2)
        p = pred("a", "num")
        return (
            f"MATCH (a:N)-[:R*{lo}..{hi}]->(b) WHERE {p} RETURN count(*) AS c"
        )
    # OPTIONAL MATCH
    p = pred("a", "num")
    return (
        f"MATCH (a:N) WHERE {p} OPTIONAL MATCH (a)-[r:R]->(b) "
        f"RETURN count(a) AS ca, count(b) AS cb, sum(r.w) AS s"
    )


def _graph_args_adversarial(seed):
    """Self-loops KEPT, plus duplicated (parallel) edges and fork-heavy
    hubs — the graph class where relationship-uniqueness semantics bite
    (round-3 regression: fork patterns overcounted on these shapes)."""
    rng = np.random.default_rng(seed)
    ids = np.arange(N, dtype=np.int64) * 7 + 5
    src = rng.integers(0, N, E)
    dst = rng.integers(0, N, E)
    # hub bias: route one edge in five into a handful of shared endpoints
    hub = rng.integers(0, 5, E)
    dst = np.where(rng.random(E) < 0.2, hub, dst)
    # parallel edges: duplicate a slice verbatim; self-loops: pin a few
    src = np.concatenate([src, src[:30], np.arange(10)])
    dst = np.concatenate([dst, dst[:30], np.arange(10)])
    n_e = len(src)
    nums = [_NUM_POOL[rng.integers(0, len(_NUM_POOL))] for _ in range(N)]
    strs = [_STR_POOL[rng.integers(0, len(_STR_POOL))] for _ in range(N)]
    ws = [None if rng.random() < 0.15 else int(rng.integers(0, 9)) for _ in range(n_e)]
    return ids, src, dst, nums, strs, ws


def _gen_uniqueness_query(rng) -> str:
    """Shapes whose results differ between homomorphic and isomorphic
    relationship matching: forks, cycles, closes, distinct-through-fork."""
    return str(
        rng.choice(
            [
                "MATCH (a)-[r1:R]->(b)<-[r2:R]-(c) RETURN count(*) AS c",
                "MATCH (a)<-[r1:R]-(b)-[r2:R]->(c) RETURN count(*) AS c",
                "MATCH (a:N)-[:R]->(b)-[:R]->(c) RETURN count(*) AS c",
                "MATCH (a)-[:R]->(b)-[:R]->(a) RETURN count(*) AS c",
                "MATCH (a)-[:R]->(b)-[:R]->(c)-[:R]->(a) RETURN count(*) AS c",
                "MATCH (x)-[r1:R]->(y), (x)-[r2:R]->(y) RETURN count(*) AS c",
                "MATCH (a)-[r1:R]->(b)<-[r2:R]-(c) WITH DISTINCT a, c "
                "RETURN count(*) AS c",
                "MATCH (a)-[r1:R]->(b)<-[r2:R]-(c) "
                "RETURN id(r1) < id(r2) AS o, count(*) AS c ORDER BY o",
                "MATCH (a:N)-[:R*1..2]->(b) RETURN count(*) AS c",
            ]
        )
    )


@pytest.fixture(scope="module")
def fuzz_graphs():
    args = _graph_args(20260730)
    return _build(CypherSession.local(), *args), _build(CypherSession.tpu(), *args)


@pytest.fixture(scope="module")
def fuzz_graphs_adversarial():
    args = _graph_args_adversarial(20260731)
    return _build(CypherSession.local(), *args), _build(CypherSession.tpu(), *args)


@pytest.mark.parametrize("qseed", range(8))
def test_fuzz_differential(fuzz_graphs, qseed):
    gl, gt = fuzz_graphs
    rng = np.random.default_rng(1000 + qseed)
    for _ in range(8):
        q = str(_gen_query(rng))
        want = gl.cypher(q).records.to_bag()
        got = gt.cypher(q).records.to_bag()
        assert got == want, f"\nquery: {q}\ntpu: {got!r}\nlocal: {want!r}"


@pytest.mark.parametrize("qseed", range(4))
def test_fuzz_differential_adversarial(fuzz_graphs_adversarial, qseed):
    gl, gt = fuzz_graphs_adversarial
    rng = np.random.default_rng(3000 + qseed)
    for _ in range(6):
        q = _gen_uniqueness_query(rng) if rng.random() < 0.7 else str(
            _gen_query(rng)
        )
        want = gl.cypher(q).records.to_bag()
        got = gt.cypher(q).records.to_bag()
        assert got == want, f"\nquery: {q}\ntpu: {got!r}\nlocal: {want!r}"


# ---------------------------------------------------------------------------
# Temporal fuzz: zoned datetime / date properties + accessor, comparison,
# ordering, aggregation, and duration-arithmetic shapes (round-5 de-bias:
# the generator covers the temporal-zoned family)
# ---------------------------------------------------------------------------


def _temporal_graph(seed):
    import datetime as dt

    rng = np.random.default_rng(seed)
    tz = dt.timezone(dt.timedelta(hours=2))
    ids = np.arange(N, dtype=np.int64) * 3 + 1

    def zdt():
        if rng.random() < 0.12:
            return None
        return dt.datetime(
            int(rng.integers(1999, 2026)), int(rng.integers(1, 13)),
            int(rng.integers(1, 29)), int(rng.integers(0, 24)),
            int(rng.integers(0, 60)), int(rng.integers(0, 60)),
            int(rng.integers(0, 1_000_000)), tzinfo=tz,
        )

    def d():
        if rng.random() < 0.12:
            return None
        return dt.date(
            int(rng.integers(1999, 2026)), int(rng.integers(1, 13)),
            int(rng.integers(1, 29)),
        )

    ts = [zdt() for _ in range(N)]
    ds = [d() for _ in range(N)]
    return ids, ts, ds


def _build_temporal(session, ids, ts, ds):
    nm = (
        NodeMappingBuilder.on("id")
        .with_implied_label("N")
        .with_property_keys("ts", "d")
        .build()
    )
    nodes = session.table_cls.from_columns(
        {"id": ids.tolist(), "ts": ts, "d": ds}
    )
    return session.read_from(ElementTable(nm, nodes))


def _gen_temporal_query(rng) -> str:
    dur = f"P{rng.integers(0, 25)}M{rng.integers(-50, 50)}DT{rng.integers(0, 30)}H"
    cmp_dt = f"datetime('20{rng.integers(10, 25)}-0{rng.integers(1, 9)}-15T12:00+02:00')"
    acc = rng.choice(["year", "month", "day", "hour", "epochSeconds"])
    shapes = [
        f"MATCH (n:N) WHERE n.ts > {cmp_dt} RETURN count(*) AS c",
        f"MATCH (n:N) WHERE n.ts.{acc} % 2 = 0 RETURN count(*) AS c",
        f"MATCH (n:N) RETURN max(n.ts).{acc} AS x, min(n.d) AS mn",
        f"MATCH (n:N) RETURN n.ts AS t ORDER BY t SKIP 2 LIMIT 7",
        f"MATCH (n:N) WHERE n.d IS NOT NULL "
        f"RETURN (n.d + duration('P{rng.integers(0, 30)}M{rng.integers(-40, 40)}D')).day AS x "
        f"ORDER BY x LIMIT 9",
        f"MATCH (n:N) WHERE n.ts IS NOT NULL "
        f"RETURN (n.ts + duration('{dur}')).{acc} AS x ORDER BY x LIMIT 9",
        f"MATCH (n:N) WHERE n.ts IS NOT NULL "
        f"RETURN (n.ts - duration('{dur}')).offset AS o LIMIT 3",
        "MATCH (n:N) RETURN count(DISTINCT n.ts) AS c, count(DISTINCT n.d) AS cd",
        f"MATCH (n:N) WHERE n.d < date('2015-0{rng.integers(1, 9)}-01') "
        "RETURN collect(n.d.year) AS ys",
    ]
    return str(rng.choice(shapes))


@pytest.fixture(scope="module")
def fuzz_graphs_temporal():
    args = _temporal_graph(20260801)
    return (
        _build_temporal(CypherSession.local(), *args),
        _build_temporal(CypherSession.tpu(), *args),
    )


@pytest.mark.parametrize("qseed", range(5))
def test_fuzz_differential_temporal(fuzz_graphs_temporal, qseed):
    gl, gt = fuzz_graphs_temporal
    rng = np.random.default_rng(5000 + qseed)
    for _ in range(6):
        q = _gen_temporal_query(rng)
        want = gl.cypher(q).records.to_bag()
        got = gt.cypher(q).records.to_bag()
        assert got == want, f"\nquery: {q}\ntpu: {got!r}\nlocal: {want!r}"

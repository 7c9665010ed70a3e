"""TPU backend tests: differential against the local oracle.

The analog of the reference's backend test strategy: the same behavioral
queries run on both backends and must produce equal Bags. (On CI this runs on
the virtual CPU mesh; the same code path runs on a real TPU chip.)"""

import pytest

from tpu_cypher import CypherSession
from tpu_cypher.backend.tpu.column import Column
from tpu_cypher.backend.tpu.table import TpuTable
from tpu_cypher.testing.bag import Bag

CREATE = (
    "CREATE (a:Person {name:'Alice', age:23, score: 1.5})-[:KNOWS {since:2019}]->"
    "(b:Person {name:'Bob', age:42}),"
    "(b)-[:KNOWS {since:2020}]->(c:Person {name:'Carol', age:55, score: 2.5}),"
    "(a)-[:KNOWS {since:2021}]->(c),"
    "(a)-[:READS]->(k:Book {title:'Graphs'}),"
    "(c)-[:READS]->(k),"
    "(c)-[:KNOWS]->(a)"
)

QUERIES = [
    "MATCH (n) RETURN count(*) AS n",
    "MATCH (a:Person) RETURN a.name, a.age",
    "MATCH (a:Person)-[:KNOWS]->(b) RETURN a.name, b.name",
    "MATCH (a)-[:KNOWS]->(b)-[:KNOWS]->(c) RETURN a.name, c.name",
    "MATCH (a)-[:KNOWS]->(b)-[:KNOWS]->(c)-[:KNOWS]->(a) RETURN a.name",
    "MATCH (a:Person) WHERE a.age > 26 RETURN a.name",
    "MATCH (a:Person) WHERE a.age > 26 AND a.score IS NOT NULL RETURN a.name",
    "MATCH (a:Person)-[k:KNOWS]->(b) WHERE k.since >= 2020 RETURN a.name, k.since",
    "MATCH (a:Person) OPTIONAL MATCH (a)-[:READS]->(x) RETURN a.name, x.title",
    "MATCH (a:Person) RETURN a.age + 1 AS inc, a.age * 2 AS dbl, a.age % 10 AS m",
    "MATCH (a:Person) RETURN DISTINCT a.age > 30 AS old",
    "MATCH (a:Person) RETURN a.name ORDER BY a.age DESC LIMIT 2",
    "MATCH (a:Person) RETURN a.name AS name ORDER BY name SKIP 1",
    "MATCH (a:Person) RETURN a.score ORDER BY a.score",
    "MATCH (a:Person)-[r:KNOWS*1..2]->(b) RETURN a.name, b.name, size(r) AS hops",
    "MATCH (a:Person) WHERE (a)-[:READS]->() RETURN a.name",
    "MATCH (b:Person {name:'Bob'})-[:KNOWS]-(x) RETURN x.name",
    "MATCH (a:Person) RETURN count(*) AS n, sum(a.age) AS s, avg(a.age) AS m",
    "MATCH (a:Person) RETURN a.age AS age, count(*) AS c ORDER BY age",
    "MATCH (a:Person)-[:KNOWS]->(b) WITH b, count(a) AS fans WHERE fans > 1 RETURN b.name, fans",
    "UNWIND [3,1,2] AS x RETURN x ORDER BY x",
    "MATCH (p:Person) RETURN p.name AS n UNION ALL MATCH (b:Book) RETURN b.title AS n",
    "MATCH (p:Person) RETURN CASE WHEN p.age < 30 THEN 'young' ELSE 'old' END AS bucket",
    "MATCH (p:Person) RETURN coalesce(p.score, 0.0) AS s",
    "MATCH (p:Person) WHERE p.age IN [23, 55] RETURN p.name",
    "MATCH (p:Person) WHERE p.name STARTS WITH 'A' RETURN p",
    "MATCH (p) RETURN labels(p) AS l, count(*) AS c",
    # device aggregation path (segment ops): grouped + global, every agg kind
    "MATCH (a:Person)-[k:KNOWS]->(b) RETURN b.name, count(*) AS c, min(k.since) AS lo, max(k.since) AS hi",
    "MATCH (a:Person) RETURN min(a.name) AS first, max(a.name) AS last",
    "MATCH (a:Person) RETURN min(a.score) AS lo, max(a.score) AS hi, sum(a.score) AS s, avg(a.score) AS m",
    "MATCH (a:Person) OPTIONAL MATCH (a)-[:READS]->(x) RETURN a.name, count(x) AS reads",
    "MATCH (b:Book) WHERE b.title = 'nope' RETURN count(*) AS c, sum(1) AS s, min(1) AS lo",
    "MATCH (a:Person) RETURN a.age > 30 AS old, count(*) AS c, avg(a.age) AS m",
    "MATCH (a:Person) RETURN a.score AS key, count(*) AS c",
    "MATCH (a:Person)-[k:KNOWS]->() RETURN a.name, sum(k.since) AS total, max(k.since) AS last",
    "MATCH (a:Person) RETURN count(a.score) AS with_score, count(*) AS all_rows",
    "MATCH (p:Person) RETURN min(p.age > 30) AS b",
    # fused-CSR expand shapes: backwards, label-filtered far end, untyped,
    # undirected chains, incoming, rel-property reads through the fused op
    "MATCH (a:Person)-[r:KNOWS]->(b:Person {name:'Carol'}) RETURN a.name, r.since",
    "MATCH (a)-[r]-(b) RETURN count(*) AS c",
    "MATCH (k:Book)<-[:READS]-(p) RETURN p.name",
    "MATCH (a)-[x]->(b)-[y]->(c) WHERE a.name = 'Alice' RETURN b.name, c.name",
    "MATCH (a:Person)-[k1:KNOWS]-(b)-[k2:KNOWS]-(c) RETURN count(*) AS z",
    "MATCH (a)-[:KNOWS]->(b), (b)-[:KNOWS]->(c), (a)-[:KNOWS]->(c) RETURN a.name, b.name, c.name",
    # keyless outer join (uncorrelated OPTIONAL MATCH) + distinct-on-element
    "MATCH (b:Book) OPTIONAL MATCH (p:Person {name:'Nobody'}) RETURN b.title, p.name",
    "MATCH (b:Book) OPTIONAL MATCH (p:Person) RETURN b.title, count(p) AS n",
    "MATCH (a:Person)-[:KNOWS]->(b)-[:KNOWS]->(c) WITH DISTINCT a, c RETURN count(*) AS pairs",
    "MATCH (a:Person) OPTIONAL MATCH (x:Nope) WITH DISTINCT a RETURN count(a) AS n",
    # device aggregate surface: stdev/percentiles/collect/DISTINCT aggs,
    # grouped and global, empty groups, string percentileDisc
    "MATCH (a:Person) RETURN stDev(a.age) AS sd, stDevP(a.age) AS sdp",
    "MATCH (a:Person)-[k:KNOWS]->(b) RETURN b.name, stDev(k.since) AS sd ORDER BY b.name",
    "MATCH (a:Person) RETURN percentileCont(a.age, 0.5) AS m, percentileDisc(a.age, 0.5) AS d",
    "MATCH (a:Person) RETURN percentileCont(a.age, 0.0) AS lo, percentileCont(a.age, 1.0) AS hi",
    "MATCH (a:Person)-[k:KNOWS]->(b) RETURN b.name, percentileDisc(k.since, 0.75) AS p ORDER BY b.name",
    "MATCH (a:Person) RETURN percentileDisc(a.name, 0.5) AS mid",
    "MATCH (a:Person) RETURN collect(a.age) AS ages",
    "MATCH (a:Person)-[:KNOWS]->(b) RETURN a.name, collect(b.name) AS friends ORDER BY a.name",
    "MATCH (a:Person) RETURN count(DISTINCT a.age > 30) AS d",
    "MATCH (a:Person)-[:KNOWS]->(b) RETURN sum(DISTINCT b.age) AS s, avg(DISTINCT b.age) AS m",
    "MATCH (a:Person)-[:KNOWS]->(b) RETURN a.name, collect(DISTINCT b.name) AS ns ORDER BY a.name",
    "MATCH (a:Person) RETURN min(DISTINCT a.name) AS lo, max(DISTINCT a.age) AS hi",
    "MATCH (x:Nope) RETURN stDev(x.v) AS sd, percentileCont(x.v, 0.5) AS p, collect(x.v) AS c",
    "MATCH (a:Person) RETURN a.score AS s, collect(a.name) AS names ORDER BY s",
]


@pytest.fixture(scope="module")
def graphs():
    local = CypherSession.local()
    tpu = CypherSession.tpu()
    return (
        local.create_graph_from_create_query(CREATE),
        tpu.create_graph_from_create_query(CREATE),
    )


@pytest.mark.parametrize("query", QUERIES)
def test_differential(graphs, query):
    g_local, g_tpu = graphs
    expected = g_local.cypher(query).records.to_bag()
    got = g_tpu.cypher(query).records.to_bag()
    assert got == expected, f"\nquery: {query}\ntpu: {got!r}\nlocal: {expected!r}"


# -- fused CSR expand path ---------------------------------------------------


def test_expand_lowered_to_fused_csr_op(graphs):
    # the thesis of the backend: MATCH expands execute as fused CSR kernels,
    # not scan+2-join cascades
    _, g_tpu = graphs
    r = g_tpu.cypher("MATCH (a)-[:KNOWS]->(b)-[:KNOWS]->(c) RETURN count(*) AS c")
    assert "CsrExpandOp" in r.plans
    t = g_tpu.cypher(
        "MATCH (a)-[:KNOWS]->(b)-[:KNOWS]->(c)-[:KNOWS]->(a) RETURN count(*) AS t"
    )
    assert "CsrExpandIntoOp" in t.plans


def test_fused_expand_does_not_pull_classic_shadow(graphs):
    # the classic join cascade is attached as a same-header shadow plan; on
    # the happy path its table must never be computed
    _, g_tpu = graphs
    from tpu_cypher.relational.ops import JoinOp

    r = g_tpu.cypher("MATCH (a:Person)-[:KNOWS]->(b:Person) RETURN count(*) AS c")
    root = r.relational_plan

    def find(op, cls):
        out = [op] if isinstance(op, cls) else []
        for c in op.children:
            out.extend(find(c, cls))
        return out

    from tpu_cypher.backend.tpu.expand_op import CsrExpandOp

    fused = find(root, CsrExpandOp)
    assert fused, r.plans
    assert r.records.collect()  # pull the plan
    for f in fused:
        shadow = f.children[1]
        assert isinstance(shadow, JoinOp)
        assert shadow._table is None, "classic shadow was computed on happy path"


def test_fused_expand_falls_back_to_classic(graphs, monkeypatch):
    # when the graph cannot be CSR-indexed the shadow plan must take over
    # transparently with identical results
    g_local, g_tpu = graphs
    from tpu_cypher.backend.tpu import expand_op as eo
    from tpu_cypher.backend.tpu.graph_index import GraphIndexError

    def boom(self):
        raise GraphIndexError("forced")

    monkeypatch.setattr(eo.CsrExpandOp, "_fused_table", boom)
    monkeypatch.setattr(eo.CsrExpandIntoOp, "_fused_table", boom)
    try:
        q = "MATCH (a)-[:KNOWS]->(b)-[:KNOWS]->(c)-[:KNOWS]->(a) RETURN a.name, c.name"
        assert g_tpu.cypher(q).records.to_bag() == g_local.cypher(q).records.to_bag()
    finally:
        monkeypatch.undo()


# -- unit-level TpuTable checks ---------------------------------------------


def test_column_roundtrip():
    for vals in (
        [1, 2, None, 4],
        [1.5, None],
        [True, False, None],
        ["b", "a", None, "b"],
        [[1, 2], None, [3]],
    ):
        assert Column.from_values(vals).to_values() == vals


def test_device_join_inner():
    a = TpuTable.from_columns({"k": [1, 2, 2, 3], "x": [10, 20, 21, 30]})
    b = TpuTable.from_columns({"j": [2, 2, 3, 5], "y": ["a", "b", "c", "d"]})
    out = a.join(b, "inner", [("k", "j")])
    rows = sorted((r["k"], r["x"], r["y"]) for r in out.rows())
    assert rows == [(2, 20, "a"), (2, 20, "b"), (2, 21, "a"), (2, 21, "b"), (3, 30, "c")]


def test_device_join_null_keys_never_match():
    a = TpuTable.from_columns({"k": [1, None]})
    b = TpuTable.from_columns({"j": [1, None]})
    out = a.join(b, "inner", [("k", "j")])
    assert out.size == 1


def test_left_outer_join():
    a = TpuTable.from_columns({"k": [1, 2]})
    b = TpuTable.from_columns({"j": [2], "y": [9]})
    out = a.join(b, "left_outer", [("k", "j")])
    rows = sorted(((r["k"], r["y"]) for r in out.rows()), key=str)
    assert (2, 9) in rows and (1, None) in rows


def test_multi_key_join():
    a = TpuTable.from_columns({"k1": [1, 1], "k2": [5, 6]})
    b = TpuTable.from_columns({"j1": [1, 1], "j2": [5, 7], "y": ["x", "z"]})
    out = a.join(b, "inner", [("k1", "j1"), ("k2", "j2")])
    assert [(r["k2"], r["y"]) for r in out.rows()] == [(5, "x")]


def test_distinct_and_order():
    t = TpuTable.from_columns({"x": [3.0, 1.0, None, 3.0, float("nan")]})
    d = t.distinct(["x"])
    assert d.size == 4  # 3.0, 1.0, null, NaN
    o = t.order_by([("x", True)])
    vals = [r["x"] for r in o.rows()]
    assert vals[0] == 1.0 and vals[1] == 3.0 and vals[2] == 3.0
    import math

    assert math.isnan(vals[3]) and vals[4] is None


def test_group_runs_on_device_not_fallback(monkeypatch):
    # count/sum/avg/min/max without DISTINCT must use segment ops, never
    # the local-oracle fallback
    tpu = CypherSession.tpu()
    g = tpu.create_graph_from_create_query(CREATE)
    from tpu_cypher.backend.tpu.table import TpuTable

    def boom(self):
        raise AssertionError("device aggregation fell back to the local oracle")

    monkeypatch.setattr(TpuTable, "_to_local", boom)
    try:
        r = g.cypher(
            "MATCH (a:Person)-[k:KNOWS]->(b) "
            "RETURN b.name, count(*) AS c, sum(k.since) AS s, avg(k.since) AS m, "
            "min(k.since) AS lo, max(k.since) AS hi"
        ).records
        rows = {m["b.name"]: m for m in r.collect()}
    finally:
        monkeypatch.undo()
    assert rows["Carol"]["c"] == 2
    assert rows["Carol"]["s"] == 2020 + 2021
    assert rows["Carol"]["m"] == (2020 + 2021) / 2
    assert rows["Bob"]["lo"] == rows["Bob"]["hi"] == 2019


def test_full_aggregate_surface_on_device(monkeypatch):
    # collect / stdev / stdevp / percentiles / DISTINCT variants now run as
    # segment ops + segment-sorted gathers — no whole-table oracle fallback
    tpu = CypherSession.tpu()
    g = tpu.create_graph_from_create_query(CREATE)

    def boom(self, _reason="x"):
        raise AssertionError(f"aggregation fell back to the local oracle: {_reason}")

    monkeypatch.setattr(TpuTable, "_to_local", boom)
    try:
        r = g.cypher(
            "MATCH (a:Person) RETURN collect(a.age) AS ages, "
            "count(DISTINCT a.age) AS d, stDev(a.age) AS sd, "
            "stDevP(a.age) AS sdp, percentileCont(a.age, 0.5) AS med, "
            "percentileDisc(a.age, 0.5) AS dmed, sum(DISTINCT a.age) AS sd2, "
            "collect(DISTINCT a.name) AS names"
        ).records.collect()
    finally:
        monkeypatch.undo()
    row = r[0]
    assert sorted(row["ages"]) == [23, 42, 55]
    assert row["d"] == 3
    # ages [23,42,55]: mean 40, sq dev 289+4+225=518
    assert abs(row["sd"] - (518 / 2) ** 0.5) < 1e-9
    assert abs(row["sdp"] - (518 / 3) ** 0.5) < 1e-9
    assert row["med"] == 42.0
    assert row["dmed"] == 42
    assert row["sd2"] == 120
    assert sorted(row["names"]) == ["Alice", "Bob", "Carol"]


def test_right_and_full_outer_on_device(monkeypatch):
    # right/full outer joins must run on device — no oracle fallback
    def boom(self):
        raise AssertionError("outer join fell back to the local oracle")

    monkeypatch.setattr(TpuTable, "_to_local", boom)
    try:
        a = TpuTable.from_columns({"k": [1, 2, 2], "x": [10, 20, 21]})
        b = TpuTable.from_columns({"j": [2, 3], "y": ["b", "c"]})
        r = a.join(b, "right_outer", [("k", "j")])
        rows = sorted(((r_["x"], r_["y"]) for r_ in r.rows()), key=str)
        assert rows == [(20, "b"), (21, "b"), (None, "c")]
        f = a.join(b, "full_outer", [("k", "j")])
        rows = sorted(((r_["k"], r_["j"]) for r_ in f.rows()), key=str)
        assert rows == [(1, None), (2, 2), (2, 2), (None, 3)]
    finally:
        monkeypatch.undo()


def test_string_key_join_on_device(monkeypatch):
    # dictionary-coded string keys join via unified vocab — no fallback
    def boom(self):
        raise AssertionError("string-key join fell back to the local oracle")

    monkeypatch.setattr(TpuTable, "_to_local", boom)
    try:
        a = TpuTable.from_columns({"k": ["x", "y", None, "z"]})
        b = TpuTable.from_columns({"j": ["y", "z", "w", None], "v": [1, 2, 3, 4]})
        out = a.join(b, "inner", [("k", "j")])
        rows = sorted((r["k"], r["v"]) for r in out.rows())
        assert rows == [("y", 1), ("z", 2)]
    finally:
        monkeypatch.undo()


def test_nan_keys_never_join_either_backend():
    # joins implement `=` predicates (replaceCartesianWithValueJoin):
    # Cypher NaN = NaN is false, so NaN keys must not match — on both backends
    from tpu_cypher.backend.local.table import LocalTable

    nan = float("nan")
    for cls in (TpuTable, LocalTable):
        a = cls.from_columns({"k": [nan, 1.0]})
        b = cls.from_columns({"j": [nan, 1.0]})
        out = a.join(b, "inner", [("k", "j")])
        assert out.size == 1, cls.__name__


def test_mixed_int_float_join_keys_exact():
    # ints above 2**53 must not collapse when joined against floats
    # (graph-tagged ids live at 2**54+); equality is exact, not via-f64
    from tpu_cypher.backend.local.table import LocalTable

    big = 2**53 + 1
    for cls in (TpuTable, LocalTable):
        a = cls.from_columns({"k": [big, 7, 10]})
        b = cls.from_columns({"j": [float(2**53), 7.0, 7.5, 10.0]})
        out = a.join(b, "inner", [("k", "j")])
        rows = sorted((r["k"], r["j"]) for r in out.rows())
        assert rows == [(7, 7.0), (10, 10.0)], cls.__name__


def test_mixed_kind_secondary_join_key_fractional_never_matches():
    # secondary-key post-filter: a fractional/NaN float must not match int 0
    from tpu_cypher.backend.local.table import LocalTable

    for cls in (TpuTable, LocalTable):
        a = cls.from_columns({"k": [1, 1, 1], "x": [0, 0, 2]})
        b = cls.from_columns({"j": [1, 1, 1], "y": [0.5, float("nan"), 2.0]})
        out = a.join(b, "inner", [("k", "j"), ("x", "y")])
        rows = sorted((r["x"], r["y"]) for r in out.rows())
        assert rows == [(2, 2.0)], cls.__name__


def test_skip_limit_slice_not_gather():
    t = TpuTable.from_columns({"x": list(range(10))})
    s = t.skip(3).limit(4)
    assert [r["x"] for r in s.rows()] == [3, 4, 5, 6]
    assert t.skip(99).size == 0
    assert t.limit(0).size == 0


def test_column_type_obj_cached():
    t = TpuTable.from_columns({"x": [[1, 2], [3]]})
    t1 = t.column_type("x")
    col = t._cols["x"]
    assert col._obj_type is not None
    assert t.column_type("x") is t1 or t.column_type("x") == t1


def test_float_sum_empty_group_is_integer_zero():
    # oracle: Cypher sum over no values = integer 0 even for float inputs
    tpu = CypherSession.tpu()
    local = CypherSession.local()
    q = "MATCH (a:Person) OPTIONAL MATCH (a)-[:NOPE]->(x) RETURN a.name, sum(x.score) AS s"
    gt = tpu.create_graph_from_create_query(CREATE)
    gl = local.create_graph_from_create_query(CREATE)
    t = gt.cypher(q).records.to_bag()
    l = gl.cypher(q).records.to_bag()
    assert t == l
    row = next(iter(gt.cypher(q).records.collect()))
    assert row["s"] == 0 and not isinstance(row["s"], float)

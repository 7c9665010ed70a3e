"""An ungrouped ``count(*)`` asks the operator under it for its number of
rows (``AggregateOp._input_row_count``): a filter answers from its keep mask
(``Table.filter_count``), an inner equi-join from its count phase
(``Table.join_count``: a sharded tier's first exchange, the one-device
probe), a DISTINCT from its first-occurrence count. The number has to be the
rows path's, the programs that build rows must not be dispatched, a query
that returns rows must dispatch what it always did, and every ask is counted
in ``tpu_cypher_count_pushdown_total{op,outcome}``."""

import contextlib

import jax
import pytest

from tpu_cypher import CypherSession
from tpu_cypher.backend.tpu import bucketing
from tpu_cypher.backend.tpu import jit_ops as J
from tpu_cypher.backend.tpu.table import TpuTable
from tpu_cypher.obs.metrics import REGISTRY
from tpu_cypher.parallel import shuffle as SH
from tpu_cypher.parallel.mesh import make_row_mesh, use_mesh
from tpu_cypher.relational import ops as O
from tpu_cypher.utils.config import BROADCAST_LIMIT

PUSHDOWN = "tpu_cypher_count_pushdown_total"

# 40 persons: k unique, v in 0..4, w in 0..2, s a string with every fourth
# null, f a float, d a date; ingested under bucketing the table is padded
# to 64 lanes
CREATE = "CREATE " + ", ".join(
    "(:P {k: %d, v: %d, w: %d, f: %d.5, d: date('2020-01-%02d')%s})"
    % (i, i % 5, i % 3, i % 4, 1 + i % 7, ", s: 's%d'" % (i % 3) if i % 4 else "")
    for i in range(40)
)


@contextlib.contextmanager
def _bucket(mode):
    bucketing.MODE.set(mode)
    try:
        yield
    finally:
        bucketing.MODE.reset()


def _rows(graph, query):
    return [dict(r) for r in graph.cypher(query).records.collect()]


def _find(op, cls):
    if isinstance(op, cls):
        return op
    for child in op.children:
        got = _find(child, cls)
        if got is not None:
            return got
    return None


def _pushdowns():
    return {
        k[len(PUSHDOWN):]: v
        for k, v in REGISTRY.flat().items()
        if k.startswith(PUSHDOWN)
    }


def _moved(before):
    return {
        k: v - before.get(k, 0.0)
        for k, v in _pushdowns().items()
        if v != before.get(k, 0.0)
    }


@pytest.fixture(scope="module")
def oracle():
    return CypherSession.local().create_graph_from_create_query(CREATE)


# ---------------------------------------------------------------------------
# count(*) over a filter
# ---------------------------------------------------------------------------

PREDICATES = {
    "int": "a.v < 3",
    "string": "a.s = 's1'",
    "null_bearing": "a.s <> 's0'",
    "is_null": "a.s IS NULL",
    "is_not_null": "a.s IS NOT NULL",
    "empty": "a.k > 1000",
    "all": "a.k >= 0",
    "two_terms": "a.v < 3 AND a.s IS NULL",
}


@pytest.mark.parametrize("bucket", ["off", "pow2"])
@pytest.mark.parametrize("pred", list(PREDICATES))
def test_filter_count_equals_the_rows_paths_size(oracle, bucket, pred):
    where = PREDICATES[pred]
    query = f"MATCH (a:P) WHERE {where} RETURN count(*) AS n"
    want = _rows(oracle, query)
    with _bucket(bucket):
        graph = CypherSession.tpu().create_graph_from_create_query(CREATE)
        before = _pushdowns()
        result = graph.cypher(query)
        assert [dict(r) for r in result.records.collect()] == want
        assert _moved(before) == {"{op=filter,outcome=count}": 1.0}
        # the same number from the table itself, beside the rows it stands for
        flt = _find(result.relational_plan, O.FilterOp)
        assert flt._table is None  # the filtered rows were never built
        src = flt.children[0]
        table = src.table
        assert (table._phys > table._nrows) == (bucket != "off")
        n = table.filter_count(flt.predicate, src.header, {})
        kept = table.filter(flt.predicate, src.header, {})
        assert n == kept.size == want[0]["n"]
        assert n == len(_rows(graph, f"MATCH (a:P) WHERE {where} RETURN a.k"))


@pytest.mark.parametrize("bucket", ["off", "pow2"])
def test_filter_count_over_a_filters_padded_output(oracle, bucket):
    """The inner filter's rows are built (bucket-padded: pad lanes hold a
    duplicate of a real row, on which IS NULL may well be true); only the
    top filter answers from its mask, and no pad row is counted."""
    query = (
        "MATCH (a:P) WHERE a.v < 3 WITH a WHERE a.s IS NULL "
        "RETURN count(*) AS n"
    )
    with _bucket(bucket):
        graph = CypherSession.tpu().create_graph_from_create_query(CREATE)
        result = graph.cypher(query)
        assert [dict(r) for r in result.records.collect()] == _rows(oracle, query)
        top = _find(result.relational_plan, O.FilterOp)
        inner = _find(top.children[0], O.FilterOp)
        if inner is not None:  # two filters: the lower one was built
            assert top._table is None and inner._table is not None
            if bucket != "off":
                assert inner._table._phys > inner._table._nrows


@pytest.mark.parametrize("bucket", ["off", "pow2"])
def test_filter_count_hands_back_what_leaves_the_device(oracle, monkeypatch, bucket):
    """A predicate with no device form (here: an evaluator that refuses
    all): ``filter_count`` declines, the rows path — the local oracle's
    filter — answers, and the ask is counted as ``rows``."""
    from tpu_cypher.backend.tpu import table as TT

    class Refusing(TT.TpuEvaluator):
        def eval(self, expr):
            raise TT.TpuUnsupportedExpr("refused")

    query = "MATCH (a:P) WHERE a.v < 3 RETURN count(*) AS n"
    with _bucket(bucket):
        graph = CypherSession.tpu().create_graph_from_create_query(CREATE)
        monkeypatch.setattr(TT, "TpuEvaluator", Refusing)
        before, flat = _pushdowns(), REGISTRY.flat()
        result = graph.cypher(query)
        assert [dict(r) for r in result.records.collect()] == [{"n": 24}]
        assert _moved(before) == {"{op=filter,outcome=rows}": 1.0}
        key = "tpu_cypher_fallbacks_total{reason=filter:expr}"
        assert REGISTRY.flat()[key] - flat.get(key, 0.0) == 1.0
        flt = _find(result.relational_plan, O.FilterOp)
        src = flt.children[0]
        assert src.table.filter_count(flt.predicate, src.header, {}) is None


def test_local_backend_builds_the_rows_and_says_so(oracle):
    before = _pushdowns()
    assert _rows(oracle, "MATCH (a:P) WHERE a.v < 3 RETURN count(*) AS n") == [
        {"n": 24}
    ]
    assert _moved(before) == {"{op=filter,outcome=rows}": 1.0}


@pytest.mark.parametrize(
    "query",
    [
        "MATCH (a:P) WHERE a.v < 3 RETURN count(a.s) AS n",
        "MATCH (a:P) WHERE a.v < 3 RETURN count(DISTINCT a.w) AS n",
        "MATCH (a:P) WHERE a.v < 3 RETURN a.w AS w, count(*) AS n ORDER BY w",
        "MATCH (a:P) WHERE a.v < 3 RETURN count(*) AS n, max(a.k) AS m",
    ],
    ids=["count_expr", "count_distinct", "grouped", "beside_max"],
)
def test_other_aggregates_do_not_take_the_path(oracle, query):
    want = _rows(oracle, query)
    graph = CypherSession.tpu().create_graph_from_create_query(CREATE)
    before = _pushdowns()
    assert _rows(graph, query) == want
    assert _moved(before) == {}


# ---------------------------------------------------------------------------
# count(*) over an inner join
# ---------------------------------------------------------------------------

JOIN = (
    "MATCH (a:P) WHERE a.k < 25 WITH a MATCH (b:P) WHERE b.{r} = a.{l} "
    "RETURN count(*) AS c"
)
JOIN_ROWS = (
    "MATCH (a:P) WHERE a.k < 25 WITH a MATCH (b:P) WHERE b.{r} = a.{l} "
    "RETURN a.k AS a, b.k AS b"
)


@contextlib.contextmanager
def _deployment(where):
    """One device, or a mesh of four virtual devices with the build side
    inside (broadcast) or outside (shuffle) the broadcast window."""
    if where == "one_device":
        yield
        return
    BROADCAST_LIMIT.set(4096 if where == "broadcast" else 8)
    try:
        with use_mesh(make_row_mesh(jax.devices()[:4])):
            yield
    finally:
        BROADCAST_LIMIT.reset()


def _tier_moves(before):
    after = REGISTRY.flat()
    return {
        k: v - before.get(k, 0.0)
        for k, v in after.items()
        if k.startswith("tpu_cypher_mesh_join_total") and v != before.get(k, 0.0)
    }


@pytest.mark.parametrize("bucket", ["off", "pow2"])
@pytest.mark.parametrize("where", ["one_device", "broadcast", "shuffle"])
@pytest.mark.parametrize(
    "l,r", [("v", "v"), ("k", "v"), ("v", "k")], ids=["many_many", "one_many", "many_one"]
)
def test_join_count_equals_the_number_of_joined_rows(oracle, bucket, where, l, r):
    query = JOIN.format(l=l, r=r)
    want = _rows(oracle, query)
    assert want[0]["c"] > 0
    with _bucket(bucket), _deployment(where):
        graph = CypherSession.tpu().create_graph_from_create_query(CREATE)
        before, tiers = _pushdowns(), REGISTRY.flat()
        assert _rows(graph, query) == want
        # the join, and the filter under it whose rows the join needs
        assert _moved(before) == {"{op=join,outcome=count}": 1.0}
        if where != "one_device":
            assert _tier_moves(tiers) == {
                "tpu_cypher_mesh_join_total{tier=%s}" % where: 1.0
            }
        assert len(_rows(graph, JOIN_ROWS.format(l=l, r=r))) == want[0]["c"]


@pytest.mark.parametrize("where", ["one_device", "broadcast", "shuffle"])
def test_join_count_with_null_keys_and_an_empty_side(oracle, where):
    """``w2`` is null on two rows of three: null keys match nothing, and an
    empty probe side counts 0."""
    create = CREATE.replace("w: 0,", "w: 0, w2: 7,")
    local = CypherSession.local().create_graph_from_create_query(create)
    with _bucket("pow2"), _deployment(where):
        graph = CypherSession.tpu().create_graph_from_create_query(create)
        for query in (
            "MATCH (a:P) WHERE a.k < 25 WITH a MATCH (b:P) WHERE b.w2 = a.w2 "
            "RETURN count(*) AS c",
            "MATCH (a:P) WHERE a.k > 1000 WITH a MATCH (b:P) WHERE b.v = a.v "
            "RETURN count(*) AS c",
        ):
            want = _rows(local, query)
            before = _pushdowns()
            assert _rows(graph, query) == want
            assert _moved(before) == {"{op=join,outcome=count}": 1.0}


def test_join_count_when_a_bucket_of_the_exchange_overflows(oracle):
    """Every key equal: all rows of both sides go to one shard's bucket, the
    exchange declines (counted), the one-device probe counts — still without
    the pairs."""
    create = "CREATE " + ", ".join("(:P {k: %d, z: 7})" % i for i in range(600))
    query = (
        "MATCH (a:P) WHERE a.k < 300 WITH a MATCH (b:P) WHERE b.z = a.z "
        "RETURN count(*) AS c"
    )
    with _bucket("pow2"), _deployment("shuffle"):
        graph = CypherSession.tpu().create_graph_from_create_query(create)
        before, flat = _pushdowns(), REGISTRY.flat()
        assert _rows(graph, query) == [{"c": 300 * 600}]
        after = REGISTRY.flat()
        key = "tpu_cypher_mesh_declines_total{op=join,reason=overflow}"
        assert after[key] - flat.get(key, 0.0) == 1.0
        assert _tier_moves(flat) == {}
        assert _moved(before) == {"{op=join,outcome=count}": 1.0}


TABLE_L = {
    "i": [1, 2, 2, None, 5, 7],
    "j": [1, 1, 2, 2, 3, 3],
    "s": ["a", "b", "b", None, "c", "d"],
    "f": [1.0, 2.0, 2.0, None, 5.5, 7.0],
    "b": [True, False, True, None, True, False],
}
TABLE_R = {
    "ri": [2, 2, 5, None, 9],
    "rj": [1, 2, 3, 3, 3],
    "rs": ["b", "b", "c", None, "z"],
    "rf": [2.0, 2.0, 5.5, None, 9.0],
    "rb": [True, True, False, None, True],
}


@pytest.mark.parametrize("bucket", ["off", "pow2"])
@pytest.mark.parametrize(
    "kind,cols,answers",
    [
        ("inner", [("i", "ri")], True),
        ("inner", [("b", "rb")], True),
        ("inner", [("i", "ri"), ("j", "rj")], False),  # composite
        ("inner", [("s", "rs")], False),  # string
        ("inner", [("f", "rf")], False),  # float
        ("inner", [("i", "rf")], False),  # mixed
        ("inner", [("i", "rs")], False),  # kinds that never match
        ("left_outer", [("i", "ri")], False),
        ("right_outer", [("i", "ri")], False),
        ("full_outer", [("i", "ri")], False),
        ("cross", [], False),
    ],
    ids=[
        "int", "bool", "composite", "string", "float", "mixed", "cross_kind",
        "left_outer", "right_outer", "full_outer", "cross",
    ],
)
def test_join_count_answers_only_where_the_probe_is_exact(bucket, kind, cols, answers):
    with _bucket(bucket):
        lt, rt = TpuTable.from_columns(TABLE_L), TpuTable.from_columns(TABLE_R)
        n = lt.join_count(rt, kind, cols)
        rows = lt.join(rt, kind, cols).size
        assert (n == rows) if answers else (n is None)


@pytest.mark.parametrize(
    "query,asked",
    [
        # string and float keys: no count phase, the pairs are built from the
        # key columns alone (``JoinOp.key_join_size``)
        (JOIN.format(l="s", r="s"), "{op=join,outcome=rows}"),
        (JOIN.format(l="f", r="f"), "{op=join,outcome=rows}"),
        # a left outer join: the whole table is built and grouped
        (
            "MATCH (a:P) OPTIONAL MATCH (b:P) WHERE b.v = a.k RETURN count(*) AS c",
            "{op=join,outcome=rows}",
        ),
        # two key terms plan as a filter over a one-key join: the join's rows
        # are built (the filter needs them), the filter on top counts its mask
        (
            "MATCH (a:P), (b:P) WHERE b.v = a.v AND b.w = a.w RETURN count(*) AS c",
            "{op=filter,outcome=count}",
        ),
        (JOIN.format(l="d", r="d"), "{op=join,outcome=count}"),
    ],
    ids=["string", "float", "optional", "filter_over_join", "date"],
)
@pytest.mark.parametrize("where", ["one_device", "broadcast"])
def test_joins_without_a_count_phase_still_answer_right(oracle, where, query, asked):
    want = _rows(oracle, query)
    with _bucket("pow2"), _deployment(where):
        graph = CypherSession.tpu().create_graph_from_create_query(CREATE)
        before = _pushdowns()
        result = graph.cypher(query)
        assert [dict(r) for r in result.records.collect()] == want
        assert _moved(before) == {asked: 1.0}
        if asked == "{op=join,outcome=rows}":
            assert _find(result.relational_plan, O.JoinOp).row_count() is None


def test_a_table_already_built_gives_its_size(oracle):
    """A CSE-shared sibling has built the filter's rows: their number is
    read off the table, nothing is asked and nothing counted."""
    graph = CypherSession.tpu().create_graph_from_create_query(CREATE)
    result = graph.cypher("MATCH (a:P) WHERE a.v < 3 RETURN count(*) AS n")
    result.records.collect()
    agg = _find(result.relational_plan, O.AggregateOp)
    flt = _find(agg, O.FilterOp)
    flt.table  # build the rows, as a sibling would
    agg._table = None
    before = _pushdowns()
    with spied() as calls:
        assert agg._input_row_count() == 24
    assert _moved(before) == {} and not any(calls.values())


def test_count_over_distinct_is_counted(oracle):
    query = "MATCH (a:P) WITH DISTINCT a.v AS b RETURN count(*) AS c"
    graph = CypherSession.tpu().create_graph_from_create_query(CREATE)
    before = _pushdowns()
    assert _rows(graph, query) == [{"c": 5}]
    assert _moved(before) == {"{op=distinct,outcome=count}": 1.0}
    before = _pushdowns()
    assert _rows(oracle, query) == [{"c": 5}]
    assert _moved(before) == {"{op=distinct,outcome=rows}": 1.0}


# ---------------------------------------------------------------------------
# the programs that build rows: none for a count, all of them for rows
# ---------------------------------------------------------------------------

SPIED = ("mask_nonzero", "cols_take_counted", "cols_take", "tree_take")


@contextlib.contextmanager
def spied():
    """Dispatches of the compaction and gather programs, and builds of the
    sharded joins' materialize programs, while the block runs."""
    calls = {name: 0 for name in SPIED + ("materialize",)}
    saved = {name: getattr(J, name) for name in SPIED}
    saved_mat = (SH._materialize_fn, SH._bcast_materialize_fn)

    def counting(name, fn):
        def wrapper(*a, **k):
            calls[name] += 1
            return fn(*a, **k)

        return wrapper

    for name, fn in saved.items():
        setattr(J, name, counting(name, fn))
    SH._materialize_fn = counting("materialize", saved_mat[0])
    SH._bcast_materialize_fn = counting("materialize", saved_mat[1])
    try:
        yield calls
    finally:
        for name, fn in saved.items():
            setattr(J, name, fn)
        SH._materialize_fn, SH._bcast_materialize_fn = saved_mat


SCAN_FILTER = "MATCH (a:P) WHERE a.v < 3 RETURN count(*) AS n"
SCAN_FILTER_ROWS = "MATCH (a:P) WHERE a.v < 3 RETURN a"
VALUE_JOIN = JOIN.format(l="v", r="v")
VALUE_JOIN_ROWS = (
    "MATCH (a:P) WHERE a.k < 25 WITH a MATCH (b:P) WHERE b.v = a.v RETURN a, b"
)

# what the rows paths dispatched at the parent of PR 29 (the same spy on its
# tree), bucketing pow2: one compaction and one counted gather for a filter;
# for the join the left filter's two, the pairs' gathers of both sides, and
# on the mesh the materialize, its compaction and the pairs' tree_take
ROWS_DISPATCHES = {
    ("filter", "one_device"): {"mask_nonzero": 1, "cols_take_counted": 1, "cols_take": 0, "tree_take": 0, "materialize": 0},
    ("filter", "shuffle"): {"mask_nonzero": 1, "cols_take_counted": 1, "cols_take": 0, "tree_take": 0, "materialize": 0},
    ("join", "one_device"): {"mask_nonzero": 1, "cols_take_counted": 3, "cols_take": 0, "tree_take": 0, "materialize": 0},
    ("join", "broadcast"): {"mask_nonzero": 2, "cols_take_counted": 3, "cols_take": 0, "tree_take": 1, "materialize": 1},
    ("join", "shuffle"): {"mask_nonzero": 2, "cols_take_counted": 3, "cols_take": 0, "tree_take": 1, "materialize": 1},
}


@pytest.mark.parametrize("where", ["one_device", "shuffle"])
def test_scan_filter_dispatches_no_compaction_and_no_gather(oracle, where):
    with _bucket("pow2"), _deployment(where):
        graph = CypherSession.tpu().create_graph_from_create_query(CREATE)
        with spied() as calls:
            assert _rows(graph, SCAN_FILTER) == [{"n": 24}]
        assert calls == dict.fromkeys(calls, 0)
        with spied() as calls:
            assert len(_rows(graph, SCAN_FILTER_ROWS)) == 24
        assert calls == ROWS_DISPATCHES["filter", where]


@pytest.mark.parametrize("where", ["one_device", "broadcast", "shuffle"])
def test_value_join_count_dispatches_only_its_left_filter(oracle, where):
    want = _rows(oracle, VALUE_JOIN)
    with _bucket("pow2"), _deployment(where):
        graph = CypherSession.tpu().create_graph_from_create_query(CREATE)
        with spied() as calls:
            assert _rows(graph, VALUE_JOIN) == want
        # the join's left side is a filter whose rows the join needs: its
        # one compaction and one gather, and nothing of the join's own
        assert calls == {
            "mask_nonzero": 1, "cols_take_counted": 1, "cols_take": 0,
            "tree_take": 0, "materialize": 0,
        }
        with spied() as calls:
            assert len(_rows(graph, VALUE_JOIN_ROWS)) == want[0]["c"]
        assert calls == ROWS_DISPATCHES["join", where]

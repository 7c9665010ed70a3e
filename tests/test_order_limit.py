"""``ORDER BY ... [SKIP s] LIMIT k``: the limit goes into the gather.

``TpuTable.order_by_limit(items, k)`` computes the permutation ``order_by``
would and gathers the rows at its first ``k`` entries alone, the prefix cut
inside the gather's program; from ``jit_ops.ORDER_TOPK_MIN_ROWS`` rows up,
integral keys that pack into 62 bits take one ``lax.top_k`` to the same
indices. Every case holds the hook's rows to ``order_by(items).limit(k)`` on
the same table and to the local oracle's, row for row and ties included, and
``tpu_cypher_order_limit_total{path=}`` to the path expected: ``topk``,
``sort_prefix``, or ``full`` where a ``LimitOp`` over an ``OrderByOp`` still
had the whole table sorted and gathered."""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpu_cypher import CypherSession
from tpu_cypher.api.mapping import NodeMappingBuilder
from tpu_cypher.api.values import Duration
from tpu_cypher.backend.local.table import LocalTable
from tpu_cypher.backend.tpu import jit_ops
from tpu_cypher.backend.tpu.table import TpuTable
from tpu_cypher.obs import trace as obs_trace
from tpu_cypher.parallel.mesh import make_row_mesh, use_mesh
from tpu_cypher.relational.graphs import ElementTable

PATHS = ("topk", "sort_prefix", "full")
N = 97  # no multiple of a lattice step or of four shards


def _paths():
    return {p: obs_trace.ORDER_LIMIT.value(path=p) for p in PATHS}


def _moved(before):
    return {p: int(v - before[p]) for p, v in _paths().items() if v != before[p]}


def _norm(v):
    return "NaN" if isinstance(v, float) and math.isnan(v) else v


def _rows(table):
    return [{c: _norm(v) for c, v in r.items()} for r in table.rows()]


def _columns(case):
    """The columns of one table-level case, ``i`` the original row number."""
    rng = np.random.default_rng(33)
    i = list(range(N))
    if case == "packs":  # small ranges with nulls: under 62 bits together
        return {"i": i,
                "v": [None if rng.random() < 0.2 else int(x) for x in rng.integers(0, 8, N)],
                "s": [["x", "y", "z", None][int(x)] for x in rng.integers(0, 4, N)]}
    if case == "wide":  # LDBC-layout ids, millisecond dates: over 62 bits
        days = rng.integers(0, 12, N)  # a dozen birthdays: ties fall to the id
        return {"i": i,
                "v": (315532800000 + days * 86400000).tolist(),
                "s": ((rng.integers(0, 16, N) << 41) | rng.permutation(N)).tolist()}
    if case == "f64":
        pool = [1.5, -2.25, 0.0, float("nan"), None, float("inf"), 1e300]
        return {"i": i, "v": [pool[int(x)] for x in rng.integers(0, len(pool), N)],
                "s": rng.integers(0, 4, N).tolist()}
    if case == "dur":
        return {"i": i,
                "v": [None if x == 0 else Duration(months=int(x) % 3, days=int(x) % 5, seconds=int(x))
                      for x in rng.integers(0, 9, N)],
                "s": rng.integers(0, 4, N).tolist()}
    if case == "ties":  # two values: the stable sort keeps the rows' own order
        return {"i": i, "v": rng.integers(0, 2, N).tolist(), "s": [7] * N}
    raise ValueError(case)


@pytest.mark.parametrize(
    "case,items,k",
    [
        ("packs", [("v", True)], 7),
        ("packs", [("s", True), ("v", False)], 9),
        ("wide", [("v", False), ("s", True)], 10),
        ("f64", [("v", True), ("s", False)], 40),
        ("f64", [("v", False)], 40),
        ("dur", [("v", True), ("s", True)], 12),
        ("dur", [("v", False)], 12),
        ("packs", [("s", False), ("v", True), ("i", False)], 30),  # mixed ASC/DESC
        ("ties", [("v", True), ("s", False)], 25),
        ("ties", [("v", False)], 25),
        ("wide", [("v", True)], N),        # k == n: order_by itself
        ("wide", [("v", True)], 5 * N),    # k > n
        ("packs", [("v", True)], 1),
    ],
)
def test_prefix_is_the_sorted_tables_first_rows(case, items, k):
    _hook_agrees(_columns(case), items, k, "sort_prefix")


def _hook_agrees(cols, items, k, path):
    t = TpuTable.from_columns(cols)
    before = _paths()
    got = t.order_by_limit(items, k)
    assert _moved(before) == {path: 1}
    assert got.size == min(k, N)
    want = _rows(t.order_by(items).limit(k))
    assert _rows(got) == want
    assert want == _rows(LocalTable.from_columns(cols).order_by(items).limit(k))


@pytest.mark.parametrize(
    "case,items,k,path",
    [
        ("packs", [("v", True)], 7, "topk"),
        ("packs", [("s", True), ("v", False)], 9, "topk"),
        ("packs", [("s", False), ("v", True), ("i", False)], 30, "topk"),
        ("ties", [("v", False), ("s", True)], 25, "topk"),
        ("packs", [("v", True)], 5 * N, "topk"),          # k > n
        ("wide", [("v", False), ("s", True)], 10, "sort_prefix"),  # over 62 bits
        ("f64", [("v", True), ("s", False)], 40, "sort_prefix"),   # no integral key
        ("dur", [("v", False)], 12, "sort_prefix"),
    ],
)
def test_large_tables_take_the_top_k_where_the_keys_pack(monkeypatch, case, items, k, path):
    """From ``ORDER_TOPK_MIN_ROWS`` rows up (here: any), integral keys whose
    measured ranges pack take the top-k; the rest keep the sort's prefix."""
    monkeypatch.setattr(jit_ops, "ORDER_TOPK_MIN_ROWS", 0)
    _hook_agrees(_columns(case), items, k, path)


def test_top_k_compiles_once_for_a_binade_of_limits_and_any_range(monkeypatch):
    """``k`` rounds up to a power of two and the ranges are traced: LIMIT 5
    to 8 over keys of any measured range are one program."""
    monkeypatch.setattr(jit_ops, "ORDER_TOPK_MIN_ROWS", 0)
    cols = _columns("packs")
    items = [("v", True), ("i", False)]
    TpuTable.from_columns(cols).order_by_limit(items, 8)
    programs = jit_ops.order_topk._cache_size()
    for k, shift in ((5, 3), (6, 1000), (7, -50)):
        shifted = dict(cols, v=[None if x is None else x * 3 + shift for x in cols["v"]])
        _hook_agrees(shifted, items, k, "topk")
    assert jit_ops.order_topk._cache_size() == programs


def test_null_rows_are_ties_whatever_lies_under_them():
    """The payload under ``valid=False`` is arbitrary (an expression's, an
    outer join's): null rows tie and the next item orders them, in the full
    sort and in its prefix alike."""
    rng = np.random.default_rng(5)
    valid = rng.random(N) >= 0.4
    s = rng.integers(0, 50, N)
    t = TpuTable.from_columns({"i": list(range(N)), "v": [1] * N, "s": s.tolist()})
    v = t._cols["v"]
    v = type(v)(v.kind, jnp.asarray(rng.integers(0, 1 << 40, N)), jnp.asarray(valid))
    t = TpuTable({**t._cols, "v": v}, N)
    items = [("v", False), ("s", True)]
    got = _rows(t.order_by_limit(items, 30))
    assert got == _rows(t.order_by(items).limit(30))
    nulls = [r for r in got if r["v"] is None]
    assert len(nulls) == 30  # DESC: nulls first
    assert [r["s"] for r in nulls] == sorted(r["s"] for r in nulls)


@pytest.mark.parametrize(
    "why,table,items,k",
    [
        ("k == 0", lambda: TpuTable.from_columns({"v": [3, 1, 2]}), [("v", True)], 0),
        ("empty table", lambda: TpuTable.empty(["v"]), [("v", True)], 10),
        ("no items", lambda: TpuTable.from_columns({"v": [3, 1, 2]}), [], 2),
        ("OBJ key", lambda: TpuTable.from_columns({"v": [[2], [1], None]}), [("v", True)], 2),
    ],
)
def test_hook_declines_what_order_by_does_not_sort_on_the_device(why, table, items, k):
    before = _paths()
    assert table().order_by_limit(items, k) is None, why
    assert _moved(before) == {}  # LimitOp counts the full path, not the hook


def test_padded_table_orders_its_logical_rows():
    """A bucketed compaction's output carries invalid pad rows: the hook
    sorts the logical rows and no pad row reaches the first ``k``."""
    cols = _columns("wide")
    t = TpuTable.from_columns(cols)
    count = 70
    idx = jnp.minimum(jnp.arange(128, dtype=jnp.int64), count - 1)
    padded = t._take_counted(idx, count)
    assert any(c.pad for c in padded._cols.values())
    items = [("v", False), ("s", True)]
    got = _rows(padded.order_by_limit(items, 80))  # k > the logical rows
    assert len(got) == count
    assert got == _rows(padded.order_by(items).limit(80))
    local = LocalTable({c: v[:count] for c, v in cols.items()}, count)
    assert got == _rows(local.order_by(items).limit(80))


# ---------------------------------------------------------------------------
# through the engine: LimitOp over OrderByOp
# ---------------------------------------------------------------------------

PERSONS = 700


def _persons(session):
    """LDBC's layout: ids of joining period << 41 | serial, birthdays in
    milliseconds (45 + 40 bits, two null bits and the row index: no pack)."""
    rng = np.random.default_rng(33)
    table = session.table_cls.from_columns({
        "nid": list(range(PERSONS)),
        "id": ((rng.integers(0, 16, PERSONS) << 41) | rng.permutation(PERSONS)).tolist(),
        "birthday": (315532800000 + rng.integers(0, 60, PERSONS) * 86400000).tolist(),
        "score": [None if x < 0.1 else float("nan") if x < 0.2 else round(x, 1)
                  for x in rng.random(PERSONS)],
        "tags": [[int(x)] for x in rng.integers(0, 5, PERSONS)],
    })
    mapping = (
        NodeMappingBuilder.on("nid").with_implied_label("Person")
        .with_property_key("id").with_property_key("birthday")
        .with_property_key("score").with_property_key("tags").build()
    )
    return session.read_from(ElementTable(mapping, table))


@pytest.fixture(scope="module")
def persons():
    return _persons(CypherSession.local()), _persons(CypherSession.tpu())


def _records(graph, query):
    return [{c: _norm(v) for c, v in dict(r).items()}
            for r in graph.cypher(query).records.collect()]


THE_CELLS = ("MATCH (a:Person) RETURN a.id AS id, a.birthday AS b "
             "ORDER BY b DESC, id ASC LIMIT 10")


@pytest.mark.parametrize(
    "query,path",
    [
        (THE_CELLS, "sort_prefix"),
        ("MATCH (a:Person) RETURN a.id AS id, a.score AS s ORDER BY s DESC, id LIMIT 150", "sort_prefix"),
        ("MATCH (a:Person) RETURN a.id AS id, a.birthday AS b ORDER BY b, id DESC SKIP 25 LIMIT 10", "sort_prefix"),
        ("MATCH (a:Person) RETURN a.id AS id ORDER BY a.birthday, id SKIP 695 LIMIT 10", "sort_prefix"),
        ("MATCH (a:Person) RETURN a.id AS id ORDER BY id LIMIT 5000", "sort_prefix"),
        ("MATCH (a:Person) RETURN a.id AS id ORDER BY id LIMIT 0", "full"),
        ("MATCH (a:Person) WHERE a.id < 0 RETURN a.id AS id ORDER BY id LIMIT 3", "full"),
        ("MATCH (a:Person) RETURN a.id AS id, a.tags AS t ORDER BY t, id LIMIT 4", "full"),
    ],
    ids=["the-cells", "f64-nan-null", "skip-limit", "skip-past-the-end", "k-over-n",
         "k-zero", "empty", "obj-key"],
)
def test_limit_over_order_by_counts_its_path(persons, query, path):
    g_local, g_tpu = persons
    want = _records(g_local, query)
    before = _paths()
    result = g_tpu.cypher(query)
    got = [{c: _norm(v) for c, v in dict(r).items()} for r in result.records.collect()]
    assert got == want
    assert _moved(before) == {path: 1}
    noted = [s.attrs["order_limit"] for s in result._trace.spans() if "order_limit" in s.attrs]
    assert noted == [path]


def test_the_engine_takes_the_top_k_on_a_large_table(persons, monkeypatch):
    g_local, g_tpu = persons
    monkeypatch.setattr(jit_ops, "ORDER_TOPK_MIN_ROWS", PERSONS)
    query = "MATCH (a:Person) RETURN a.id AS id ORDER BY a.birthday DESC SKIP 2 LIMIT 6"
    want = _records(g_local, query)
    before = _paths()
    assert _records(g_tpu, query) == want
    assert _moved(before) == {"topk": 1}


def test_wide_keys_gather_no_more_than_the_limit(persons, monkeypatch):
    """The cells' query: no gather of the Person rows is longer than ``k``
    (the parent gathered all of them in sorted order, then kept ten)."""
    _, g_tpu = persons
    lengths = []
    orig = jit_ops.cols_take

    def spy(cols, idx, first=None):
        lengths.append(int(idx.shape[0]) if first is None else first)
        return orig(cols, idx, first=first)

    monkeypatch.setattr(jit_ops, "cols_take", spy)
    before = _paths()
    assert len(_records(g_tpu, THE_CELLS)) == 10
    assert lengths and max(lengths) <= 10, lengths
    assert _moved(before) == {"sort_prefix": 1}


def test_the_local_backend_has_no_prefix_form(persons):
    g_local, _ = persons
    before = _paths()
    assert len(_records(g_local, THE_CELLS)) == 10
    assert _moved(before) == {"full": 1}


def test_mesh_session_on_four_devices():
    """``CypherSession.tpu(mesh=4)`` on four virtual CPU devices: GSPMD
    partitions the same sort; the prefix's index vector is ``k`` long."""
    mesh = make_row_mesh(jax.devices()[:4])
    queries = (THE_CELLS, THE_CELLS.replace("LIMIT 10", "SKIP 3 LIMIT 9"))
    g_local = _persons(CypherSession.local())
    want = [_records(g_local, query) for query in queries]
    with use_mesh(mesh):
        g_tpu = _persons(CypherSession.tpu())
        col = g_tpu._graph.scans[0].table._cols["id"]
        assert len(col.data.sharding.device_set) == 4
        before = _paths()
        assert [_records(g_tpu, query) for query in queries] == want
        assert _moved(before) == {"sort_prefix": 2}


def test_both_series_are_exported_from_the_start():
    from tpu_cypher.obs.metrics import REGISTRY

    text = REGISTRY.prometheus_text()
    for path in PATHS:
        assert f'tpu_cypher_order_limit_total{{path="{path}"}}' in text

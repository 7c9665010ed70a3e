"""A segment reduction over a small, static number of groups is a dense
compare-and-reduce, not a scatter (``jit_ops.segment_reduce``): every group
compares its id against every row and reduces what matches. The form is a
function of ``k``, the dtype and the op alone (``segment_reduce_form``); a
float sum keeps the scatter at every ``k``, because its order of addition is
part of its bits.

Both forms are held to ``jax.ops.segment_*`` bit for bit, the payloads of
empty groups included; the whole aggregate programs (null and NaN masking,
intness tracking) to themselves under the other form; the lowered text to
holding a scatter exactly where the form says so; and the engine to the
local oracle, with ``tpu_cypher_segment_reduce_total{form=}`` counting each
aggregator by the form it took."""

import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_cypher import CypherSession
from tpu_cypher.api.mapping import NodeMappingBuilder
from tpu_cypher.backend.tpu import bucketing
from tpu_cypher.backend.tpu import jit_ops as J
from tpu_cypher.backend.tpu.pallas import dispatch
from tpu_cypher.obs import trace as obs_trace
from tpu_cypher.obs.metrics import REGISTRY
from tpu_cypher.relational.graphs import ElementTable

K_MAX = J.SEGMENT_DENSE_MAX_GROUPS
KS = (1, 5, K_MAX, K_MAX + 1)
OPS = ("sum", "min", "max")
SCATTER = {
    "sum": jax.ops.segment_sum,
    "min": jax.ops.segment_min,
    "max": jax.ops.segment_max,
}
N = 257  # rows of a case: odd, so no tiling divides it


@pytest.fixture
def force(monkeypatch):
    """Take one form at every ``k`` (a float sum stays the scatter): the
    form is read while tracing, so the callers here trace anew."""
    def _force(form):
        monkeypatch.setattr(
            J, "SEGMENT_DENSE_MAX_GROUPS", (1 << 40) if form == "dense" else -1
        )
    return _force


def _rng(*case):
    return np.random.default_rng(zlib.crc32(repr(case).encode()))


def _bits(a):
    a = np.asarray(a)
    return a.dtype, a.shape, a.view(np.uint8).tobytes()


def _values(dtype, rng, n):
    if dtype == "i64":  # sums wrap past 2^63 the same way in both forms
        near = (1 << 62) - rng.integers(0, 1 << 20, n)
        return np.where(rng.random(n) < 0.5, near, -near).astype(np.int64)
    if dtype == "i8":  # BOOL as the programs compare it, and ``all_int``
        return rng.integers(0, 2, n).astype(np.int8)
    if dtype == "i32":  # dictionary codes
        return rng.integers(0, 1 << 20, n).astype(np.int32)
    special = np.array([0.0, -0.0, np.inf, -np.inf, 1.5, -2.25, 1e300, 5e-324])
    return rng.choice(special, n)


def _case(case, dtype, op, k, rng):
    """(values, seg) as a caller hands them over: nulls already replaced by
    the op's sentinel."""
    n = 0 if case == "n0" else N
    vals = _values(dtype, rng, n)
    seg = rng.integers(0, k, n).astype(np.int64)
    if case == "empty_group" and k > 1:
        seg[seg == k - 1] = 0
    if case == "null_group":  # every row of group 0 is null
        sentinel = np.asarray(J._reduce_identity(vals.dtype, op))
        vals = np.where(seg == 0, sentinel, vals).astype(vals.dtype)
    if case == "outside":  # below, above, and past 32 bits
        seg[::7], seg[1::7], seg[2::7] = -1, k, (1 << 32) + (k - 1)
    return vals, seg


@pytest.mark.parametrize("case", ["empty_group", "null_group", "n0", "outside"])
@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("dtype", ["i64", "i8", "i32", "f64"])
@pytest.mark.parametrize("op", OPS)
def test_both_forms_equal_the_segment_op_bit_for_bit(force, op, dtype, k, case):
    rng = _rng(op, dtype, k, case)
    vals, seg = _case(case, dtype, op, k, rng)
    want = _bits(SCATTER[op](jnp.asarray(vals), jnp.asarray(seg), num_segments=k))
    for form in ("dense", "scatter"):
        force(form)
        got = jax.jit(lambda v, s: J.segment_reduce(v, s, k, op))(vals, seg)
        assert _bits(got) == want, form


FORM_TABLE = [
    # k, dtype, op, form
    (1, jnp.int64, "sum", "dense"),
    (5, jnp.int64, "sum", "dense"),
    (5, jnp.int64, "min", "dense"),
    (5, jnp.int8, "min", "dense"),
    (5, jnp.int32, "max", "dense"),
    (5, jnp.float64, "min", "dense"),
    (5, jnp.float64, "max", "dense"),
    (K_MAX, jnp.int64, "sum", "dense"),
    (K_MAX, jnp.float64, "max", "dense"),
    (K_MAX + 1, jnp.int64, "sum", "scatter"),
    (K_MAX + 1, jnp.int64, "min", "scatter"),
    (K_MAX + 1, jnp.float64, "max", "scatter"),
    # a float sum is a scatter at every k: its order of addition is bits
    (1, jnp.float64, "sum", "scatter"),
    (5, jnp.float64, "sum", "scatter"),
    (5, jnp.float32, "sum", "scatter"),
]


@pytest.mark.parametrize("k,dtype,op,form", FORM_TABLE)
def test_form_is_a_function_of_k_dtype_and_op(k, dtype, op, form):
    assert J.segment_reduce_form(k, dtype, op) == form


AGG_FORM_TABLE = [
    ("count", jnp.float64, 5, "dense"),
    ("count", jnp.float64, K_MAX + 1, "scatter"),
    ("sum", jnp.int64, 5, "dense"),
    ("avg", jnp.int64, 5, "dense"),
    ("sum", jnp.float64, 5, "scatter"),
    ("avg", jnp.float64, 5, "scatter"),
    ("stdev", jnp.int64, 5, "scatter"),
    ("stdevp", jnp.float64, 5, "scatter"),
    ("min", jnp.float64, 5, "dense"),
    ("max", jnp.bool_, 5, "dense"),
    ("max", jnp.int64, K_MAX + 1, "scatter"),
]


@pytest.mark.parametrize("name,dtype,k,form", AGG_FORM_TABLE)
def test_an_aggregator_is_counted_by_its_own_reduction(name, dtype, k, form):
    assert J.segment_aggregate_form(name, dtype, k) == form


# ---------------------------------------------------------------------------
# the aggregate programs whole: the dense form against today's scatters
# ---------------------------------------------------------------------------

AGGREGATES = [
    (name, kind)
    for kind, names in (
        (J.I64, ("count", "sum", "avg", "stdev", "stdevp", "min", "max")),
        (J.F64, ("count", "sum", "avg", "stdev", "min", "max")),
        (J.BOOL, ("count", "min", "max")),
        (J.STR, ("count", "min", "max")),
    )
    for name in names
]


def _column(kind, rng, n):
    """(data, valid, int_flag): nulls, and for floats NaN, signed zeros,
    infinities and rows that Cypher holds to be integers."""
    valid = rng.random(n) < 0.8
    if kind == J.F64:
        special = np.array([np.nan, 0.0, -0.0, np.inf, -np.inf, 3.0, -7.0, 2.5])
        data = rng.choice(special, n)
        iflag = (data == np.round(data)) & (rng.random(n) < 0.7)
        return data, valid, iflag
    if kind == J.BOOL:
        return rng.random(n) < 0.5, valid, None
    if kind == J.STR:
        return rng.integers(0, 40, n).astype(np.int32), valid, None
    return _values("i64", rng, n), valid, None


@pytest.mark.parametrize("masked", [True, False], ids=["nulls", "no_nulls"])
@pytest.mark.parametrize("k", (1, 5, K_MAX))
@pytest.mark.parametrize("name,kind", AGGREGATES)
def test_aggregate_programs_keep_their_bits(force, name, kind, k, masked):
    """``segment_aggregate`` under the dense form returns what it returns
    under the scatters it was written with — validity, int_flag and the
    payloads under them included; one group is left empty, one all null."""
    rng = _rng(name, kind, k, masked)
    data, valid, iflag = _column(kind, rng, N)
    seg = rng.integers(0, k, N).astype(np.int64)
    if k > 2:
        seg[seg == k - 1] = 0
        valid = valid & (seg != 1)
    if not masked:
        valid = None
    out = {}
    for form in ("dense", "scatter"):
        force(form)
        prog = jax.jit(partial(
            J.segment_aggregate.__wrapped__, name=name, kind=kind, k=k
        ))
        out[form] = [
            None if x is None else _bits(x)
            for x in prog(data, valid, iflag, seg)
        ]
    assert out["dense"] == out["scatter"]


SCATTER_OP = '"stablehlo.scatter"('


def _lowered(name, dtype, k):
    data = jax.ShapeDtypeStruct((4096,), dtype)
    seg = jax.ShapeDtypeStruct((4096,), jnp.int64)
    kind = J.F64 if dtype == jnp.float64 else J.I64
    return J.segment_aggregate.lower(
        data, None, None, seg, name=name, kind=kind, k=k
    ).as_text()


@pytest.mark.parametrize("name", ["min", "sum", "count", "avg"])
def test_small_k_lowers_without_a_scatter(name):
    assert SCATTER_OP not in _lowered(name, jnp.int64, 5)
    assert SCATTER_OP not in _lowered(name, jnp.int64, K_MAX)


@pytest.mark.parametrize("name", ["min", "sum"])
def test_past_the_constant_the_scatter_is_there(name):
    assert SCATTER_OP in _lowered(name, jnp.int64, K_MAX + 1)


def test_a_float_sum_lowers_to_the_scatter_it_had():
    """One scatter, the sum's own: the count beside it is dense."""
    text = _lowered("sum", jnp.float64, 5)
    assert text.count(SCATTER_OP) == 1
    assert SCATTER_OP not in _lowered("min", jnp.float64, 5)


# ---------------------------------------------------------------------------
# the engine: the cell's query, the counter, no compile on the second run
# ---------------------------------------------------------------------------

# chipbench/shapes/grouped_aggregate.py's text
CELL_QUERY = (
    "MATCH (a:Person) RETURN a.browserUsed AS browser, count(a.id) AS n, "
    "min(a.birthday) AS lo, max(a.birthday) AS hi, sum(a.id) AS s "
    "ORDER BY browser"
)
BY_ID = (
    "MATCH (a:Person) RETURN a.id AS id, min(a.birthday) AS lo, "
    "sum(a.birthday) AS s ORDER BY id"
)
BROWSERS = ("Chrome", "Firefox", "Internet Explorer", "Opera", "Safari")
PERSONS = K_MAX + 6  # a grouping by id has more groups than the constant


def _persons(session):
    rng = np.random.default_rng(31)
    ids = (np.arange(PERSONS, dtype=np.int64) * 3 + (7 << 41)).tolist()
    table = session.table_cls.from_columns({
        "nid": ids,
        "id": ids,
        "browserUsed": [BROWSERS[i] for i in rng.integers(0, 5, PERSONS)],
        "birthday": rng.integers(3 * 10**11, 9 * 10**11, PERSONS).tolist(),
    })
    mapping = (
        NodeMappingBuilder.on("nid").with_implied_label("Person")
        .with_property_key("id").with_property_key("browserUsed")
        .with_property_key("birthday").build()
    )
    return session.read_from(ElementTable(mapping, table))


def _forms():
    return {
        form: obs_trace.SEGMENT_REDUCE.value(form=form)
        for form in ("dense", "scatter")
    }


def _moved(before):
    return {form: v - before[form] for form, v in _forms().items()}


def _rows(graph, query):
    return [dict(r) for r in graph.cypher(query).records.collect()]


@pytest.fixture(scope="module")
def persons():
    return _persons(CypherSession.local()), _persons(CypherSession.tpu())


@pytest.mark.parametrize("pallas,dense", [("interpret", 3), ("off", 4)])
def test_the_cells_query_takes_the_dense_form(persons, pallas, dense):
    """Four aggregators over five groups: with the kernel tier on, as on the
    chip, ``count(a.id)`` is the Pallas kernel's and three are counted;
    without it all four are. No scatter either way, the oracle's rows, and a
    second run compiles nothing."""
    oracle, graph = persons
    dispatch.MODE.set(pallas)
    try:
        before = _forms()
        rows = _rows(graph, CELL_QUERY)
        assert _moved(before) == {"dense": dense, "scatter": 0}
        assert rows == _rows(oracle, CELL_QUERY)
        assert len(rows) == 5 and sum(r["n"] for r in rows) == PERSONS
        compiles = bucketing.compile_snapshot()["compiles"]
        assert _rows(graph, CELL_QUERY) == rows
        assert bucketing.compile_snapshot()["compiles"] == compiles
    finally:
        dispatch.MODE.reset()


def test_a_grouping_past_the_constant_takes_the_scatter(persons):
    oracle, graph = persons
    before = _forms()
    rows = _rows(graph, BY_ID)
    assert _moved(before) == {"dense": 0, "scatter": 2}
    assert rows == _rows(oracle, BY_ID)
    assert len(rows) == PERSONS


def test_count_star_and_the_span_note_the_form(persons):
    """``count(*)`` goes through the same jitted count; the operator's span
    carries ``agg_form``."""
    oracle, graph = persons
    query = (
        "MATCH (a:Person) RETURN a.browserUsed AS b, count(*) AS n, "
        "avg(a.birthday) AS m ORDER BY b"
    )
    before = _forms()
    result = graph.cypher(query)
    rows = [dict(r) for r in result.records.collect()]
    assert _moved(before) == {"dense": 2, "scatter": 0}
    assert rows == _rows(oracle, query)

    noted = [
        (s.name, s.attrs["agg_form"])
        for s in result._trace.spans() if "agg_form" in s.attrs
    ]
    assert noted == [("AggregateOp", {"dense": 2})]


def test_both_series_are_exported_from_the_start():
    text = REGISTRY.prometheus_text()
    for form in ("dense", "scatter"):
        assert f'tpu_cypher_segment_reduce_total{{form="{form}"}}' in text


def test_a_finished_query_leaves_no_device_array_to_the_cyclic_collector(persons):
    """What a request built on the device goes when the request's last
    reference goes — not when the cyclic collector next happens to run: a
    closure that calls itself (``_clone_plan``'s walk, the evaluator's
    dependency walks) is a cycle, and it kept every operator's table. The
    device's memory peak moved by 3% with the collector's timing (PR 31)."""
    import gc

    _, graph = persons
    queries = (
        CELL_QUERY,
        "MATCH (a:Person) RETURN a.id AS id ORDER BY a.birthday DESC, id LIMIT 10",
        "MATCH (a:Person) WHERE a.birthday < 500000000000 RETURN count(*) AS c",
    )
    for q in queries:  # warm: compiles and caches are not garbage
        _rows(graph, q)
    gc.collect()
    gc.disable()
    gc.set_debug(gc.DEBUG_SAVEALL)
    try:
        for q in queries:
            _rows(graph, q)
        gc.collect()
        held = [o for o in gc.garbage if isinstance(o, jax.Array)]
    finally:
        gc.set_debug(0)
        gc.garbage.clear()
        gc.enable()
    assert held == []

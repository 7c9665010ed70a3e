"""Sharded ENGINE execution on the virtual 8-device CPU mesh.

Round 1 sharded only a standalone demo kernel; these tests
run real Cypher queries through ``CypherSession.tpu()`` while a row mesh is
active, so TpuTable columns and the CSR edge arrays carry
``NamedSharding(mesh, P('rows'))`` and XLA GSPMD inserts the collectives
(the reference gets the same property from Spark/Flink partitioned tables,
``SparkTable.scala:178``). Every query is differential against the local
oracle."""

import numpy as np
import pytest

from tpu_cypher import CypherSession
from tpu_cypher.api.mapping import NodeMappingBuilder, RelationshipMappingBuilder
from tpu_cypher.backend.tpu.table import TpuTable
from tpu_cypher.parallel.mesh import ROW_AXIS, current_mesh, make_row_mesh, shard_rows, use_mesh
from tpu_cypher.relational.graphs import ElementTable
from tpu_cypher.testing.bag import Bag

N_NODES = 64  # divisible by the 8-device mesh
N_EDGES = 256

# deliberately NOT divisible by 8: ingest pads columns and CSR arrays to a
# shard multiple (sharding must not silently no-op on real-world
# cardinalities)
N_NODES_ODD = 61
N_EDGES_ODD = 243


def _edges(seed=3, n=N_NODES, e=N_EDGES):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, e * 2)
    dst = rng.integers(0, n, e * 2)
    keep = src != dst
    return src[keep][:e], dst[keep][:e]


def _build(session, ids, src, dst, ages):
    node_t = session.table_cls.from_columns(
        {"id": ids.tolist(), "age": ages}
    )
    node_m = (
        NodeMappingBuilder.on("id")
        .with_implied_label("Person")
        .with_property_key("age")
        .build()
    )
    rel_ids = np.arange(len(src), dtype=np.int64) + int(ids.max()) + 1
    rel_t = session.table_cls.from_columns(
        {"rid": rel_ids.tolist(), "s": ids[src].tolist(), "t": ids[dst].tolist()}
    )
    rel_m = (
        RelationshipMappingBuilder.on("rid")
        .from_("s")
        .to("t")
        .with_relationship_type("KNOWS")
        .build()
    )
    return session.read_from(ElementTable(node_m, node_t), ElementTable(rel_m, rel_t))


QUERIES = [
    # fused CSR expand (2-hop) under sharding: a whole frontier (degrees,
    # then the sharded gather-and-sum), and a filtered one (the sharded scan)
    "MATCH (a:Person)-[:KNOWS]->(b)-[:KNOWS]->(c) RETURN count(*) AS c",
    "MATCH (a:Person) WHERE a.age > 40 WITH a MATCH (a)-[:KNOWS]->(b)-[:KNOWS]->(c) RETURN count(*) AS c",
    # filter + projection over sharded scan columns
    "MATCH (a:Person) WHERE a.age > 40 RETURN count(*) AS n, sum(a.age) AS s",
    # sort-probe join path (value join) + distinct
    "MATCH (a:Person)-[:KNOWS]->(b) WITH DISTINCT a, b RETURN count(*) AS pairs",
    # grouped segment aggregation
    "MATCH (a:Person)-[:KNOWS]->(b) RETURN b.age AS k, count(*) AS c, avg(a.age) AS m ORDER BY k LIMIT 5",
    # var-length expand (unrolled joins) under sharding
    "MATCH (a:Person)-[:KNOWS*1..2]->(b) RETURN count(*) AS walks",
    # optional match (left outer join)
    "MATCH (a:Person) OPTIONAL MATCH (a)-[:KNOWS]->(b) RETURN count(b) AS c",
    # order by + skip/limit on device
    "MATCH (a:Person) RETURN a.age ORDER BY a.age DESC SKIP 3 LIMIT 4",
]


def _meshed_pair(n, e):
    import jax

    mesh = make_row_mesh(jax.devices()[:8])
    ids = np.arange(n, dtype=np.int64) * 7 + 3
    ages = (np.arange(n) * 13 % 60 + 20).tolist()
    src, dst = _edges(n=n, e=e)

    local = CypherSession.local()
    g_local = _build(local, ids, src, dst, ages)
    with use_mesh(mesh):
        tpu = CypherSession.tpu()
        g_tpu = _build(tpu, ids, src, dst, ages)
    return mesh, g_local, g_tpu


@pytest.fixture(scope="module")
def meshed():
    return _meshed_pair(N_NODES, N_EDGES)


@pytest.fixture(scope="module")
def meshed_odd():
    return _meshed_pair(N_NODES_ODD, N_EDGES_ODD)


@pytest.mark.parametrize("query", QUERIES)
def test_differential_on_mesh(meshed, query):
    mesh, g_local, g_tpu = meshed
    expected = g_local.cypher(query).records.to_bag()
    with use_mesh(mesh):
        got = g_tpu.cypher(query).records.to_bag()
    assert got == expected, f"\nquery: {query}\ntpu: {got!r}\nlocal: {expected!r}"


@pytest.mark.parametrize("query", QUERIES)
def test_differential_on_mesh_nondivisible(meshed_odd, query):
    """Same engine queries, cardinalities that do NOT divide the mesh:
    ingest pads to shard multiples and every result must still equal the
    oracle (pad rows are invalid everywhere)."""
    mesh, g_local, g_tpu = meshed_odd
    expected = g_local.cypher(query).records.to_bag()
    with use_mesh(mesh):
        got = g_tpu.cypher(query).records.to_bag()
    assert got == expected, f"\nquery: {query}\ntpu: {got!r}\nlocal: {expected!r}"


@pytest.mark.parametrize(
    "query,forms",
    [(QUERIES[0], {"degree": 1, "reduce": 1, "scan": 0}),
     (QUERIES[1], {"degree": 1, "reduce": 0, "scan": 1})],
    ids=["whole", "filtered"],
)
def test_count_chain_forms_on_mesh(meshed_odd, query, forms):
    """The mesh chain shares the one-chip chain's algebra: the same forms
    for the same frontier, over the shard_map programs (``expand_shards``
    on the operator's span says they ran)."""
    mesh, _, g_tpu = meshed_odd
    with use_mesh(mesh):
        result = g_tpu.cypher(query)
        result.records.collect()
    noted = [s.attrs for s in result._trace.spans() if "chain_hops" in s.attrs]
    assert len(noted) == 1 and noted[0]["chain_hops"] == forms
    assert noted[0]["expand_shards"] == 8


def test_nondivisible_columns_padded_and_sharded(meshed_odd):
    mesh, _, g_tpu = meshed_odd
    scans = g_tpu._graph.scans
    col = scans[0].table._cols["id"]
    assert col.pad == (-N_NODES_ODD) % 8
    assert len(col) == N_NODES_ODD + col.pad
    assert col.logical_len == N_NODES_ODD
    assert tuple(col.data.sharding.spec) == (ROW_AXIS,), col.data.sharding
    # pad rows are invalid; metadata stays non-nullable
    assert col.pad_synth and col.valid is not None
    assert scans[0].table.column_type("id").is_nullable is False


def test_nondivisible_csr_padded_and_sharded(meshed_odd):
    mesh, g_local, g_tpu = meshed_odd
    with use_mesh(mesh):
        got = g_tpu.cypher(
            "MATCH (a:Person)-[:KNOWS]->(b)-[:KNOWS]->(c) RETURN count(*) AS c"
        ).records.collect()
    expected = g_local.cypher(
        "MATCH (a:Person)-[:KNOWS]->(b)-[:KNOWS]->(c) RETURN count(*) AS c"
    ).records.collect()
    assert [dict(r) for r in got] == [dict(r) for r in expected]
    gi = g_tpu._graph._tpu_graph_index
    (row_ptr, col_idx, edge_orig) = next(iter(gi._csr.values()))
    assert int(col_idx.shape[0]) % 8 == 0 and int(col_idx.shape[0]) >= N_EDGES_ODD
    assert tuple(col_idx.sharding.spec) == (ROW_AXIS,)
    assert tuple(edge_orig.sharding.spec) == (ROW_AXIS,)


def test_mesh_engine_large_nondivisible():
    """~1M-row multichip correctness at a size where resharding costs are
    real: 2-hop count + DISTINCT endpoints on a
    999,983-edge CSR over the 8-device mesh, vs host-numpy ground truth.
    Slow-ish (~tens of seconds on the CPU mesh) by design."""
    import jax

    n, e = 100_003, 999_983  # both prime — nothing divides the mesh
    rng = np.random.default_rng(11)
    ids = np.arange(n, dtype=np.int64) * 3 + 5
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    mesh = make_row_mesh(jax.devices()[:8])
    with use_mesh(mesh):
        tpu = CypherSession.tpu()
        g = _build(tpu, ids, src, dst, (np.arange(n) % 60 + 20).tolist())
        got = g.cypher(
            "MATCH (a:Person)-[:KNOWS]->(b)-[:KNOWS]->(c) RETURN count(*) AS c"
        ).records.collect()
        got_d = g.cypher(
            "MATCH (a:Person)-[:KNOWS]->(b)-[:KNOWS]->(c) "
            "WITH DISTINCT a, c RETURN count(*) AS pairs"
        ).records.collect()
    outdeg = np.bincount(src, minlength=n)
    expected = int(outdeg[dst].sum())
    assert got[0]["c"] == expected
    # host ground truth for DISTINCT (a, c): expand per edge via CSR
    order = np.argsort(src, kind="stable")
    s_sorted, d_sorted = src[order], dst[order]
    row_ptr = np.searchsorted(s_sorted, np.arange(n + 1))
    # second hop: for each edge (a, b), all successors of b
    reps = outdeg[dst]
    a_rep = np.repeat(src, reps)
    starts = row_ptr[dst]
    flat = np.repeat(starts - np.concatenate([[0], np.cumsum(reps)[:-1]]), reps) + np.arange(reps.sum())
    c_rep = d_sorted[flat]
    distinct_pairs = len(np.unique(a_rep.astype(np.int64) * n + c_rep))
    assert got_d[0]["pairs"] == distinct_pairs
    # the big CSR actually sharded (padded to a multiple of 8)
    gi = g._graph._tpu_graph_index
    (_, col_idx, _) = next(iter(gi._csr.values()))
    assert int(col_idx.shape[0]) % 8 == 0
    assert tuple(col_idx.sharding.spec) == (ROW_AXIS,)


def test_base_columns_actually_sharded(meshed):
    mesh, _, g_tpu = meshed
    # the node scan's id column was ingested under the mesh: it must carry a
    # row NamedSharding, not a single-device placement
    scans = g_tpu._graph.scans
    col = scans[0].table._cols["id"]
    spec = col.data.sharding.spec
    assert tuple(spec) == (ROW_AXIS,), f"not row-sharded: {col.data.sharding}"


def test_csr_edge_arrays_sharded(meshed):
    mesh, g_local, g_tpu = meshed
    with use_mesh(mesh):
        # run a 2-hop to force CSR construction under the mesh
        g_tpu.cypher(
            "MATCH (a:Person)-[:KNOWS]->(b)-[:KNOWS]->(c) RETURN count(*) AS c"
        ).records.collect()
    gi = g_tpu._graph._tpu_graph_index
    (row_ptr, col_idx, edge_orig) = next(iter(gi._csr.values()))
    assert tuple(col_idx.sharding.spec) == (ROW_AXIS,)
    assert tuple(edge_orig.sharding.spec) == (ROW_AXIS,)


_COLLECTIVES = (
    "all-reduce",
    "all-gather",
    "all-to-all",
    "collective-permute",
    "reduce-scatter",
)


def test_sharded_programs_emit_xla_collectives(meshed):
    """The sharded engine's distribution story is GSPMD inserting ICI
    collectives into the compiled programs (SURVEY §2.3's replacement for
    the engines' shuffle exchange) — assert they are really in the HLO, not
    just implied by the sharding annotations."""
    import jax.numpy as jnp

    import tpu_cypher.backend.tpu.jit_ops as J

    mesh, _, _ = meshed
    n, e = N_NODES, N_EDGES
    rng = np.random.default_rng(0)
    src = np.sort(rng.integers(0, n, e))
    dst = rng.integers(0, n, e)
    rp = jnp.asarray(np.searchsorted(src, np.arange(n + 1)).astype(np.int32))
    with use_mesh(mesh):
        ci = shard_rows(jnp.asarray(dst.astype(np.int32)))
        ids = shard_rows(jnp.asarray(np.arange(n, dtype=np.int64)))
        rd = shard_rows(jnp.asarray(rng.integers(0, 50, e).astype(np.int64)))
    dev_ids = jnp.asarray(np.arange(n, dtype=np.int64))
    # fused count chain over a sharded CSR + sharded frontier ids (a
    # partial label on the far node, or the hop is row_ptr differences and
    # reads no edge)
    hops = ((rp, ci, None, None, None, jnp.asarray(np.arange(n) % 2 == 0)),)
    txt = (
        J.path_count_chain.lower(dev_ids, ids, None, hops, num_nodes=n)
        .compile()
        .as_text()
    )
    assert any(k in txt for k in _COLLECTIVES), "no collective in count chain HLO"
    # sort-probe join build phase over a sharded key column
    txt2 = (
        J.join_build.lower(rd, (), is_f64=False, is_bool=False)
        .compile()
        .as_text()
    )
    assert any(k in txt2 for k in _COLLECTIVES), "no collective in join HLO"


@pytest.mark.parametrize("spans", [True, False], ids=["windows", "whole_row_ptr"])
def test_sharded_scan_gathers_once_and_equals_one_device(spans):
    """A masked three-hop chain (three ``scan`` hops) over typed CSRs whose
    edge lanes are sharded over eight devices: per shard ONE gather of its
    prefix sums, at the window's row pointers clipped into the shard, the
    ``psum`` over the window's sums alone — against the one-device program
    and a dense NumPy product, with the index's windows and without."""
    import jax
    import jax.numpy as jnp

    import test_spmv_row_span as RS
    import tpu_cypher.backend.tpu.jit_ops as J

    mesh = make_row_mesh(jax.devices()[:8])
    w = np.ones(RS.NODES, dtype=np.int64)
    one_device, sharded = [], []
    for a in reversed(RS.typed_world()):
        hop, dense = RS.hop_of(a, "fwd", True, spans=spans)
        w = dense @ (RS.EVEN * w)
        one_device.insert(0, hop)
        with use_mesh(mesh):
            sharded.insert(0, (hop[0], shard_rows(hop[1])) + hop[2:])
    assert J.chain_forms([True] * 3, False) == ("scan",) * 3
    dev_ids = jnp.asarray(np.arange(RS.NODES, dtype=np.int64))
    picked = np.array([9, 9, 12, 25, 29])
    want = int(w[picked].sum())
    assert want > 0
    alone = J.path_count_chain(
        dev_ids, jnp.asarray(picked), None, tuple(one_device), num_nodes=RS.NODES)
    with use_mesh(mesh):
        on_mesh = J.path_count_chain_on_mesh(mesh, mesh.axis_names[0])(
            dev_ids, jnp.asarray(picked), None, tuple(sharded), num_nodes=RS.NODES)
    assert int(alone) == int(on_mesh) == want


# ---------------------------------------------------------------------------
# ISSUE 13 tiers: per-shard partial aggregates, hash-repartition DISTINCT,
# and the sharded WCOJ count — each proven to RUN (its counter advances)
# and to match the single-device / oracle result bit-identically.
# ---------------------------------------------------------------------------

from tpu_cypher.obs.metrics import REGISTRY as _OBS
from tpu_cypher.utils.config import WCOJ_MODE


def _counter(name):
    return _OBS.counter(name).value()


def test_sharded_agg_tier_runs_and_matches(meshed_odd):
    """Grouped INTEGER aggregates under the mesh run as per-shard
    ``segment_*`` partials tree-combined with psum/pmin/pmax
    (``tpu_cypher_mesh_agg_total`` advances) and stay bit-identical to the
    local oracle — count, sum, min, max, and the int-sum/int-count avg."""
    mesh, g_local, g_tpu = meshed_odd
    q = (
        "MATCH (a:Person)-[:KNOWS]->(b) RETURN b.age AS k, count(*) AS c, "
        "sum(a.age) AS s, min(a.age) AS lo, max(a.age) AS hi, "
        "avg(a.age) AS m ORDER BY k LIMIT 7"
    )
    expected = g_local.cypher(q).records.to_bag()
    before = _counter("tpu_cypher_mesh_agg_total")
    with use_mesh(mesh):
        got = g_tpu.cypher(q).records.to_bag()
    assert got == expected, f"\ntpu: {got!r}\nlocal: {expected!r}"
    assert _counter("tpu_cypher_mesh_agg_total") > before


def _declines():
    return {
        k: v for k, v in _OBS.flat().items()
        if k.startswith("tpu_cypher_mesh_declines_total")
    }


def _valued_persons(session, n):
    """``n`` persons: ``uid`` unique, ``age`` one of 60, ``v`` near 2^62
    with every ninth null, ``ok`` a BOOL."""
    i = np.arange(n, dtype=np.int64)
    v = ((1 << 62) - i * 977) * np.where(i % 2, 1, -1)
    table = session.table_cls.from_columns({
        "nid": (i * 7 + 3).tolist(),
        "uid": (i * 11 + (5 << 41)).tolist(),
        "age": (i * 13 % 60 + 20).tolist(),
        "v": [None if j % 9 == 4 else int(x) for j, x in enumerate(v)],
        "ok": (i % 3 == 0).tolist(),
    })
    mapping = (
        NodeMappingBuilder.on("nid").with_implied_label("Person")
        .with_property_key("uid").with_property_key("age")
        .with_property_key("v").with_property_key("ok").build()
    )
    return session.read_from(ElementTable(mapping, table))


@pytest.fixture(scope="module")
def valued():
    """The same persons on one device and row-sharded over eight; more of
    them than ``SEGMENT_DENSE_MAX_GROUPS``, and not a multiple of eight."""
    import jax

    from tpu_cypher.backend.tpu import jit_ops as J

    n = J.SEGMENT_DENSE_MAX_GROUPS + 203
    mesh = make_row_mesh(jax.devices()[:8])
    single = _valued_persons(CypherSession.tpu(), n)
    with use_mesh(mesh):
        sharded = _valued_persons(CypherSession.tpu(), n)
    return mesh, single, sharded


@pytest.mark.parametrize(
    "key,form", [("a.age", "dense"), ("a.uid", "scatter")],
    ids=["under_the_constant", "past_the_constant"],
)
def test_sharded_aggregates_equal_single_device_bit_for_bit(valued, key, form):
    """On both sides of ``SEGMENT_DENSE_MAX_GROUPS`` the per-shard partials
    (dense compare-and-reduce under it, the scatter past it) combine to
    the single-device columns, value for value; six aggregators ride the
    sharded tier, each counted by its form, and nothing is handed back."""
    from tpu_cypher.obs import trace as obs_trace

    mesh, single, sharded = valued
    q = (
        f"MATCH (a:Person) RETURN {key} AS k, count(a.v) AS c, sum(a.v) AS s, "
        "min(a.v) AS lo, max(a.v) AS hi, avg(a.age) AS m, max(a.ok) AS any "
        "ORDER BY k"
    )
    want = [dict(r) for r in single.cypher(q).records.collect()]
    aggs = _counter("tpu_cypher_mesh_agg_total")
    declines = _declines()
    forms = {
        f: obs_trace.SEGMENT_REDUCE.value(form=f) for f in ("dense", "scatter")
    }
    with use_mesh(mesh):
        got = [dict(r) for r in sharded.cypher(q).records.collect()]
    assert got == want
    assert [type(r["m"]) for r in got] == [type(r["m"]) for r in want]
    assert len(got) == (60 if form == "dense" else len(want)) > 0
    assert _counter("tpu_cypher_mesh_agg_total") == aggs + 6
    assert _declines() == declines
    for f, before in forms.items():
        assert obs_trace.SEGMENT_REDUCE.value(form=f) == before + (
            6 if f == form else 0
        )


def test_sharded_distinct_count_tier():
    """Table-level DISTINCT count under the mesh hash-repartitions the
    packed equivalence keys across shards (``tpu_cypher_mesh_distinct_total``
    advances) and matches the single-device packed-sort answer."""
    import jax

    from tpu_cypher.backend.tpu.column import Column

    rng = np.random.default_rng(5)
    vals = rng.integers(0, 97, 1001).astype(np.int64)
    t = TpuTable({"x": Column.from_numpy(vals)})
    single = t.distinct_count(["x"])
    assert single == len(np.unique(vals))
    before = _counter("tpu_cypher_mesh_distinct_total")
    with use_mesh(make_row_mesh(jax.devices()[:8])):
        t8 = TpuTable({"x": Column.from_numpy(vals)})
        sharded = t8.distinct_count(["x"])
    assert sharded == single
    assert _counter("tpu_cypher_mesh_distinct_total") > before


def test_sharded_wcoj_triangle(meshed_odd):
    """The WCOJ count tier under the mesh leapfrog-intersects each shard's
    LOCAL slice of the sorted adjacency and psum-combines the counts
    (``tpu_cypher_mesh_wcoj_total`` advances); the triangle count stays
    bit-identical to the local oracle."""
    mesh, g_local, g_tpu = meshed_odd
    q = (
        "MATCH (a:Person)-[:KNOWS]->(b)-[:KNOWS]->(c)-[:KNOWS]->(a) "
        "RETURN count(*) AS t"
    )
    expected = g_local.cypher(q).records.to_bag()
    before = _counter("tpu_cypher_mesh_wcoj_total")
    WCOJ_MODE.set("force")
    try:
        with use_mesh(mesh):
            got = g_tpu.cypher(q).records.to_bag()
    finally:
        WCOJ_MODE.reset()
    assert got == expected, f"\ntpu: {got!r}\nlocal: {expected!r}"
    assert _counter("tpu_cypher_mesh_wcoj_total") > before


def test_mesh_gates_disable_tiers(meshed_odd):
    """``TPU_CYPHER_MESH_AGG=off`` / ``TPU_CYPHER_MESH_WCOJ=off`` keep the
    global single-program paths — correct answers, counters frozen."""
    from tpu_cypher.utils.config import MESH_AGG, MESH_WCOJ

    mesh, g_local, g_tpu = meshed_odd
    q = (
        "MATCH (a:Person)-[:KNOWS]->(b) RETURN b.age AS k, count(*) AS c "
        "ORDER BY k LIMIT 5"
    )
    tq = (
        "MATCH (a:Person)-[:KNOWS]->(b)-[:KNOWS]->(c)-[:KNOWS]->(a) "
        "RETURN count(*) AS t"
    )
    MESH_AGG.set("off")
    MESH_WCOJ.set("off")
    WCOJ_MODE.set("force")
    a0 = _counter("tpu_cypher_mesh_agg_total")
    w0 = _counter("tpu_cypher_mesh_wcoj_total")
    try:
        with use_mesh(mesh):
            assert g_tpu.cypher(q).records.to_bag() == g_local.cypher(q).records.to_bag()
            assert g_tpu.cypher(tq).records.to_bag() == g_local.cypher(tq).records.to_bag()
    finally:
        MESH_AGG.reset()
        MESH_WCOJ.reset()
        WCOJ_MODE.reset()
    assert _counter("tpu_cypher_mesh_agg_total") == a0
    assert _counter("tpu_cypher_mesh_wcoj_total") == w0


def test_per_shard_bucket_lattice():
    """Under a mesh the bucket lattice rounds the PER-SHARD extent: the
    global padded size is ``lattice(ceil(n / nsh)) * nsh`` — always
    shard-divisible, and the per-shard shape is a plain lattice point, so
    changing the shard count never mints new local shapes (the
    compile-cache-stability invariant)."""
    import jax

    from tpu_cypher.backend.tpu import bucketing

    sizes = (1, 5, 31, 100, 1000, 12345)
    with bucketing.force_mode("pow2"):
        plain = {n: bucketing.round_size(n) for n in range(1, 5000)}
        lattice_points = set(plain.values())
        for nsh in (4, 8):
            mesh = make_row_mesh(jax.devices()[:nsh])
            with use_mesh(mesh):
                for n in sizes:
                    out = bucketing.round_size(n)
                    local_true = -(-n // nsh)
                    assert out % nsh == 0
                    assert out == plain[local_true] * nsh
                    assert out // nsh in lattice_points


def test_per_shard_admission_budget(meshed):
    """``bucketing.admit`` under a mesh judges each shard's 1/nsh slice of
    the padded bytes against its 1/nsh slice of the whole-mesh budget: the
    rejection names the per-shard scope while the typed exception keeps the
    GLOBAL estimate/budget for the ladder's telemetry."""
    from tpu_cypher.backend.tpu import bucketing
    from tpu_cypher.errors import AdmissionRejected
    from tpu_cypher.utils.config import MEM_BUDGET

    mesh, _, _ = meshed
    MEM_BUDGET.set(8 * 1024 * 1024)
    rows, bpr = 2 * 1024 * 1024, 16  # ~32 MiB padded: over budget anywhere
    try:
        with bucketing.force_mode("pow2"):
            with pytest.raises(AdmissionRejected) as e1:
                bucketing.admit(rows, bpr, "test-site")
            assert "per shard" not in str(e1.value)
            with use_mesh(mesh):
                with pytest.raises(AdmissionRejected) as e2:
                    bucketing.admit(rows, bpr, "test-site")
                bucketing.admit(64, 16, "test-site")  # small: admitted
            assert "per shard (x8)" in str(e2.value)
            assert e2.value.budget_bytes == 8 * 1024 * 1024
            assert e2.value.estimated_bytes > e2.value.budget_bytes
    finally:
        MEM_BUDGET.reset()


def test_mesh_context_restores():
    assert current_mesh() is None
    import jax

    mesh = make_row_mesh(jax.devices()[:8])
    with use_mesh(mesh):
        assert current_mesh() is mesh
        import jax.numpy as jnp

        x = shard_rows(jnp.arange(16, dtype=jnp.int64))
        assert tuple(x.sharding.spec) == (ROW_AXIS,)
        y = shard_rows(jnp.arange(17, dtype=jnp.int64))  # not divisible: as-is
        assert getattr(y.sharding, "spec", None) != (ROW_AXIS,)
    assert current_mesh() is None

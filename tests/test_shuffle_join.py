"""Explicit hash-repartition join on the mesh (SURVEY §2.3 distributed
join): each device buckets its keys by value, ONE
all_to_all per side meets equal keys on one shard, and the join runs
locally per shard — the deliberate analog of the engines' shuffled hash
join (``SparkTable.scala:178``). Differential vs host ground truth and vs
the whole-engine pipeline under the 8-device CPU mesh."""

from collections import Counter

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpu_cypher import CypherSession
from tpu_cypher.api.mapping import NodeMappingBuilder, RelationshipMappingBuilder
from tpu_cypher.parallel import shuffle as SH
from tpu_cypher.parallel.mesh import make_row_mesh, use_mesh
from tpu_cypher.relational.graphs import ElementTable


def _ground_truth(lk, lv, rk, rv):
    rmap = {}
    for j, (k, v) in enumerate(zip(rk, rv)):
        if v:
            rmap.setdefault(int(k), []).append(j)
    want = Counter()
    for i, (k, v) in enumerate(zip(lk, lv)):
        if v:
            for j in rmap.get(int(k), []):
                want[(i, j)] += 1
    return want


def _live_pairs(got):
    """A sharded join's (left rows, right rows) as lists: the ``total``
    live pairs that lead the arrays, without the lattice's pad lanes."""
    assert got is not None
    l_rows, r_rows, total = got
    assert len(l_rows) == len(r_rows) >= total
    return np.asarray(l_rows)[:total].tolist(), np.asarray(r_rows)[:total].tolist()


@pytest.mark.parametrize(
    "seed,n_l,n_r,lo,hi",
    [
        (0, 1003, 777, 0, 500),      # non-divisible sizes, duplicates
        (1, 64, 64, 0, 8),           # heavy duplication, small key space
        (2, 500, 3, 0, 1000),        # tiny build side
        (3, 257, 999, 10_000, 10_050),  # dense collisions, offset ids
    ],
)
def test_hash_repartition_join_matches_ground_truth(seed, n_l, n_r, lo, hi):
    rng = np.random.default_rng(seed)
    lk = rng.integers(lo, hi, n_l)
    rk = rng.integers(lo, hi, n_r)
    lv = rng.random(n_l) > 0.15
    rv = rng.random(n_r) > 0.15
    with use_mesh(make_row_mesh()):
        got = SH.hash_repartition_join(
            jnp.asarray(lk), jnp.asarray(lv), jnp.asarray(rk), jnp.asarray(rv)
        )
    got_c = Counter(zip(*_live_pairs(got)))
    assert got_c == _ground_truth(lk, lv, rk, rv)


def test_negative_keys_join_correctly():
    """Property-value joins can carry negative int64 keys; the even-key
    namespace keeps them first-class (the round-4 review caught a sentinel
    scheme that silently dropped them)."""
    lk = np.array([-5, -5, 0, 3, -(2**61)], dtype=np.int64)
    rk = np.array([-5, 3, -1, 0, -(2**61)], dtype=np.int64)
    with use_mesh(make_row_mesh()):
        got = SH.hash_repartition_join(
            jnp.asarray(lk), None, jnp.asarray(rk), None
        )
    got_c = Counter(zip(*_live_pairs(got)))
    assert got_c == _ground_truth(lk, [True] * 5, rk, [True] * 5)


@pytest.mark.parametrize("stride", [2, 4, 8, 7])
def test_strided_keys_use_all_shards(stride):
    """Bucket assignment mixes the key (splitmix64) before the modulo:
    strided id namespaces (multiples of the mesh size included) must spread
    over every shard instead of concentrating and tripping the capacity
    fallback (round-4 review findings: first the doubled-key collapse, then
    the general stride class)."""
    n = 4096
    keys = np.arange(n, dtype=np.int64) * stride
    with use_mesh(make_row_mesh()):
        got = SH.hash_repartition_join(
            jnp.asarray(keys), None, jnp.asarray(keys), None
        )
    l_rows, r_rows = (np.asarray(a) for a in _live_pairs(got))
    assert len(l_rows) == n
    assert (l_rows == r_rows).all()


def test_oversized_keys_fall_back_to_none():
    lk = jnp.asarray(np.array([1 << 62], dtype=np.int64))
    with use_mesh(make_row_mesh()):
        assert SH.hash_repartition_join(lk, None, lk, None) is None


def test_skew_overflow_falls_back_to_none():
    """One hot key routes every row to one bucket: the static capacity
    overflows and the helper reports None (caller keeps the global join)."""
    n = 4096
    lk = jnp.zeros(n, jnp.int64)  # all rows hash to shard 0
    rk = jnp.zeros(n, jnp.int64)
    with use_mesh(make_row_mesh()):
        got = SH.hash_repartition_join(lk, None, rk, None)
    assert got is None


@pytest.mark.parametrize("bucket", ["off", "pow2"])
@pytest.mark.parametrize(
    "q",
    [
        "MATCH (a:P)-[:K]->(b:P) "
        "RETURN b.age AS g, count(*) AS c ORDER BY g, c",
        # an outer shape: the tiers' pairs come tail-padded on the bucket
        # lattice and are cut to their true count before the unmatched rows
        "MATCH (a:P) OPTIONAL MATCH (a)-[:K]->(b:P) "
        "RETURN a.age AS g, count(b) AS c ORDER BY g, c",
    ],
    ids=["inner", "optional"],
)
def test_engine_join_on_mesh_uses_shuffle(monkeypatch, bucket, q):
    """An engine query whose plan genuinely JOINS (dangling edge endpoints
    make the CSR index bail, so Expand runs as the classic scan+join
    cascade) routes the mesh join through hash_repartition_join and
    matches the oracle."""
    calls = {"n": 0}
    orig = SH.hash_repartition_join_count
    orig_b = SH.broadcast_join_count

    def spy(*a, **k):
        out = orig(*a, **k)
        if out is not None:
            calls["n"] += 1
        return out

    def spy_b(*a, **k):
        out = orig_b(*a, **k)
        if out is not None:
            calls["n"] += 1
        return out

    monkeypatch.setattr(SH, "hash_repartition_join_count", spy)
    monkeypatch.setattr(SH, "broadcast_join_count", spy_b)

    rng = np.random.default_rng(5)
    n, e = 120, 400
    ids = np.arange(n, dtype=np.int64) * 7 + 3
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    ages = (np.arange(n) % 9).tolist()
    s_ids = ids[src].tolist()
    t_ids = ids[dst].tolist()
    # dangling endpoints: ids outside the node set force the classic
    # scan+join expand cascade (the CSR index requires closed topology)
    s_ids[0] = 999_999
    t_ids[1] = 999_998

    def build(session):
        nt = session.table_cls.from_columns({"id": ids.tolist(), "age": ages})
        nm = (
            NodeMappingBuilder.on("id")
            .with_implied_label("P")
            .with_property_key("age")
            .build()
        )
        rt = session.table_cls.from_columns(
            {
                "rid": (np.arange(e, dtype=np.int64) + 100_000).tolist(),
                "s": s_ids,
                "t": t_ids,
            }
        )
        rm = (
            RelationshipMappingBuilder.on("rid")
            .from_("s")
            .to("t")
            .with_relationship_type("K")
            .build()
        )
        return session.read_from(ElementTable(nm, nt), ElementTable(rm, rt))

    from tpu_cypher.backend.tpu import bucketing

    g_local = build(CypherSession.local())
    want = [dict(r) for r in g_local.cypher(q).records.collect()]
    bucketing.MODE.set(bucket)
    try:
        with use_mesh(make_row_mesh()):
            g_tpu = build(CypherSession.tpu())
            got = [dict(r) for r in g_tpu.cypher(q).records.collect()]
    finally:
        bucketing.MODE.reset()
    assert got == want
    assert calls["n"] >= 1, "mesh join did not route through a deliberate tier"


# ---------------------------------------------------------------------------
# Broadcast tier: small build side replicated, probe local, NO collective
# (SURVEY §2.3 "broadcast small relations")
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "seed,n_l,n_r,lo,hi",
    [
        (3, 1003, 50, 0, 40),     # small build side, duplicates both sides
        (4, 64, 1, 0, 4),         # single-row build
        (5, 513, 100, -50, 50),   # negative keys
    ],
)
def test_broadcast_join_differential(seed, n_l, n_r, lo, hi):
    rng = np.random.default_rng(seed)
    lk = rng.integers(lo, hi, n_l).astype(np.int64)
    rk = rng.integers(lo, hi, n_r).astype(np.int64)
    lv = rng.random(n_l) < 0.9
    rv = rng.random(n_r) < 0.9
    want = _ground_truth(lk, lv, rk, rv)
    with use_mesh(make_row_mesh()):
        got = SH.broadcast_join(
            jnp.asarray(lk), jnp.asarray(lv), jnp.asarray(rk), jnp.asarray(rv)
        )
    have = Counter(zip(*_live_pairs(got)))
    assert have == want


def test_broadcast_join_declines_large_build():
    with use_mesh(make_row_mesh()):
        n = SH._broadcast_limit() + 1
        got = SH.broadcast_join(
            jnp.arange(64, dtype=jnp.int64), None,
            jnp.arange(n, dtype=jnp.int64), None,
        )
    assert got is None  # falls through to the hash shuffle


def test_broadcast_join_hlo_has_no_collective():
    """The point of the tier: the compiled join program contains NO
    all_to_all / all-gather style collective (the build side is already
    replicated; probes are purely local)."""
    with use_mesh(make_row_mesh()) as mesh:
        axis = mesh.axis_names[0]
        from jax.sharding import NamedSharding, PartitionSpec as P

        lk = jax.device_put(
            jnp.arange(64, dtype=jnp.int64) * 2, NamedSharding(mesh, P(axis))
        )
        rk = jax.device_put(
            jnp.arange(16, dtype=jnp.int64) * 2, NamedSharding(mesh, P(None))
        )
        txt = SH._bcast_count_fn(mesh, axis).lower(lk, rk).compile().as_text()
    assert "all-to-all" not in txt
    # the count reduction gathers nsh scalars at the end; the JOIN itself
    # must not move row data — no all_to_all anywhere is the contract


def test_optional_match_rides_mesh_join(monkeypatch):
    """OPTIONAL MATCH (left outer) joins now ride the deliberate mesh
    tiers: match pairs from broadcast/shuffle, unmatched-row padding on
    top."""
    calls = {"bcast": 0, "shuffle": 0}
    orig_b, orig_s = SH.broadcast_join_count, SH.hash_repartition_join_count

    def spy_b(*a, **k):
        out = orig_b(*a, **k)
        if out is not None:
            calls["bcast"] += 1
        return out

    def spy_s(*a, **k):
        out = orig_s(*a, **k)
        if out is not None:
            calls["shuffle"] += 1
        return out

    monkeypatch.setattr(SH, "broadcast_join_count", spy_b)
    monkeypatch.setattr(SH, "hash_repartition_join_count", spy_s)

    create = (
        "CREATE (a:P {v: 1})-[:K]->(:Q {w: 10}), (:P {v: 2}), "
        "(c:P {v: 3})-[:K]->(:Q {w: 30})"
    )
    # the predicate keeps the optional side off the fused left-outer
    # expand (which takes one hop under far labels the index proves)
    q = (
        "MATCH (p:P) OPTIONAL MATCH (p)-[:K]->(x:Q) WHERE x.w > 0 "
        "RETURN p.v AS v, x.w AS w ORDER BY v"
    )
    want = [
        dict(r)
        for r in CypherSession.local()
        .create_graph_from_create_query(create)
        .cypher(q)
        .records.collect()
    ]
    with use_mesh(make_row_mesh()):
        gt = CypherSession.tpu().create_graph_from_create_query(create)
        got = [dict(r) for r in gt.cypher(q).records.collect()]
    assert got == want
    assert calls["bcast"] + calls["shuffle"] >= 1


def test_composite_key_join_rides_mesh(monkeypatch):
    """Multi-column join keys pack into ONE mixed key for the mesh tiers;
    every key column is post-verified (hash-collision screen)."""
    calls = {"n": 0}
    orig_b = SH.broadcast_join_count

    def spy_b(*a, **k):
        out = orig_b(*a, **k)
        if out is not None:
            calls["n"] += 1
        return out

    monkeypatch.setattr(SH, "broadcast_join_count", spy_b)
    create = (
        "CREATE (:L {a: 1, b: 1, s: 'x'}), (:L {a: 1, b: 2, s: 'y'}), "
        "(:L {a: 2, b: 1, s: 'z'}), (:R {a: 1, b: 1, t: 'p'}), "
        "(:R {a: 1, b: 2, t: 'q'}), (:R {a: 2, b: 2, t: 'r'})"
    )
    q = (
        "MATCH (l:L), (r:R) WHERE l.a = r.a AND l.b = r.b "
        "RETURN l.s AS s, r.t AS t ORDER BY s"
    )
    want = [
        dict(r)
        for r in CypherSession.local()
        .create_graph_from_create_query(create)
        .cypher(q)
        .records.collect()
    ]
    with use_mesh(make_row_mesh()):
        gt = CypherSession.tpu().create_graph_from_create_query(create)
        got = [dict(r) for r in gt.cypher(q).records.collect()]
    assert got == want

"""Worst-case-optimal multiway join (ISSUE 10).

Five guarantees under test:

* DIFFERENTIAL — cyclic patterns (triangle, 2-cycle, diamond, 4-clique,
  reversed orientations, self-loops, empty adjacency) answered through
  ``MultiwayIntersectOp`` under ``TPU_CYPHER_WCOJ=force`` are bit-identical
  to the forced binary plan (``=off``) and to the local host oracle, on
  loopy and loop-free graphs, both bucket modes, kernels on and off; and
  ``jit_ops.range_count`` (the sorted-range search step) matches a NumPy
  ``searchsorted`` reference at the contract level.
* ELIGIBILITY — ``auto`` mode applies the EmptyHeaded-style rule: routes
  to WCOJ only when the degree-stats blowup estimate clears
  ``TPU_CYPHER_WCOJ_MIN_ROWS``; small graphs keep the binary plan.
* FAULTS — the operator's ``expand`` site drives the degrade-and-retry
  ladder: typed failures in ``execution_log``, results oracle-identical,
  ``:*`` lands on the host oracle (the site is passed at every device
  rung), the unsupported multi-close materialize degrades to the classic
  shadow plan.
* GUARDS — the
  ``TPU_CYPHER_WCOJ*`` knobs live in the config registry, the engine lint
  reports zero findings on the new modules, and warm cyclic queries with
  kernels on compile ZERO new XLA programs.
* SORTED CSR — every CSR row's neighbor column is nondecreasing
  (``GraphIndex.csr_sorted``), the edge keys are globally sorted, and a
  build that violates the contract raises instead of mis-searching.
"""

import os

import numpy as np
import pytest

import jax.numpy as jnp

from tpu_cypher import CypherSession
from tpu_cypher import errors as ERR
from tpu_cypher.backend.tpu import bucketing
from tpu_cypher.backend.tpu import graph_index as GI
from tpu_cypher.backend.tpu.graph_index import GraphIndex, GraphIndexError
from tpu_cypher.backend.tpu import jit_ops as J
from tpu_cypher.backend.tpu.pallas import dispatch
from tpu_cypher.backend.tpu import wcoj as W
from tpu_cypher.runtime import faults, guard
from tpu_cypher.utils.config import FACTORIZE, REGISTRY, WCOJ_MIN_ROWS, WCOJ_MODE


@pytest.fixture(autouse=True)
def _clean():
    """Every test leaves WCOJ routing, kernel mode, bucketing, and fault
    specs as it found them."""
    yield
    WCOJ_MODE.reset()
    WCOJ_MIN_ROWS.reset()
    FACTORIZE.reset()
    dispatch.MODE.reset()
    dispatch.reset()
    bucketing.MODE.reset()
    faults.set_spec(None)


def _tiers():
    return dict(W.WCOJ_TIER_COUNTS)


TRIANGLE = "MATCH (a)-[:K]->(b)-[:K]->(c)-[:K]->(a) RETURN count(*) AS t"

CYCLIC_CORPUS = [
    TRIANGLE,
    "MATCH (a:N)-[:K]->(b:N)-[:K]->(c:N)-[:K]->(a) RETURN count(*) AS t",
    "MATCH (a)-[:K]->(b)-[:K]->(a) RETURN count(*) AS t",
    "MATCH (a)<-[:K]-(b)-[:K]->(c)-[:K]->(a) RETURN count(*) AS t",
    "MATCH (a)-[:K]->(b)-[:K]->(c)-[:K]->(d)-[:K]->(a) RETURN count(*) AS t",
    "MATCH (a)-[:K]->(b)-[:K]->(c)-[:K]->(a), (a)-[:K]->(d), "
    "(b)-[:K]->(d), (c)-[:K]->(d) RETURN count(*) AS t",
    "MATCH (a)-[:K]->(b)-[:K]->(c)-[:K]->(a) "
    "RETURN id(a) AS ia, id(c) AS ic ORDER BY ia, ic",
    "MATCH (a:N)-[:K]->(b)-[:K]->(c)-[:K]->(a) "
    "RETURN a.v AS av, c.v AS cv ORDER BY av, cv",
]


def _loopy_create(seed=7, n=30, e=150):
    rng = np.random.default_rng(seed)
    src, dst = rng.integers(0, n, e), rng.integers(0, n, e)
    parts = [f"(n{i}:{'N' if i % 3 else 'N:M'} {{v: {i % 9}}})" for i in range(n)]
    parts += [f"(n{s})-[:K]->(n{d})" for s, d in zip(src, dst)]
    return "CREATE " + ", ".join(parts)


def _loop_free_create(seed=13, n=40, e=220):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, e)
    dst = (src + 1 + rng.integers(0, n - 1, e)) % n
    parts = [f"(n{i}:N)" for i in range(n)]
    parts += [f"(n{s})-[:K]->(n{d})" for s, d in zip(src, dst)]
    return "CREATE " + ", ".join(parts)


# ---------------------------------------------------------------------------
# contract differential: jit_ops.range_count vs NumPy searchsorted
# ---------------------------------------------------------------------------

RANGE_SHAPES = [
    ("single_key", 1, 4, 1.0),
    ("dense", 700, 900, 0.85),
    ("all_invalid", 64, 200, 0.0),
    ("dup_heavy", 512, 1500, 0.6),
    ("non_pow2", 333, 1025, 0.5),
]


def _range_count_np(keys, q, qvalid):
    lo = np.searchsorted(keys, q, side="left")
    hi = np.searchsorted(keys, q, side="right")
    counts = np.where(qvalid, hi - lo, 0)
    return lo, counts, counts.sum()


@pytest.mark.parametrize("name,nk,nq,density", RANGE_SHAPES)
def test_range_count_differential(name, nk, nq, density):
    rng = np.random.default_rng(abs(hash(name)) % 2**31)
    lo = 0 if name != "dup_heavy" else 5  # duplicates: narrow key space
    keys = np.sort(rng.integers(lo, max(nk, 8), nk).astype(np.int64))
    q = rng.integers(0, max(nk, 8) + 2, nq).astype(np.int64)
    qvalid = rng.random(nq) < density
    want = _range_count_np(keys, q, qvalid)
    got = J.range_count(jnp.asarray(keys), jnp.asarray(q), jnp.asarray(qvalid))
    for w, g, nm in zip(want, got, ("lo", "counts", "total")):
        assert (np.asarray(w) == np.asarray(g)).all(), (name, nm)


def test_range_count_sentinel_padded_keys():
    """Keys arrive device-padded with the ``1 << 62`` sentinel (the
    ``GraphIndex.edge_keys`` contract): sentinels sort past every real
    query, so they never enter a counted range."""
    real = np.sort(np.random.default_rng(3).integers(0, 50, 37).astype(np.int64))
    padded = np.concatenate([real, np.full(7, 1 << 62, np.int64)])
    q = np.arange(-2, 55, dtype=np.int64)
    qvalid = np.ones(q.shape[0], bool)
    got = J.range_count(jnp.asarray(padded), jnp.asarray(q), jnp.asarray(qvalid))
    base = _range_count_np(real, q, qvalid)
    for w, g in zip(base, got):
        assert (np.asarray(w) == np.asarray(g)).all()


# ---------------------------------------------------------------------------
# engine differential: WCOJ vs forced-binary vs host oracle
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def loopy_oracle():
    g = CypherSession.local().create_graph_from_create_query(_loopy_create())
    return {q: g.cypher(q).records.collect() for q in CYCLIC_CORPUS}


@pytest.mark.parametrize("bucket_mode", ["pow2", "off"])
def test_engine_differential_wcoj_vs_binary_vs_oracle(
    loopy_oracle, bucket_mode
):
    bucketing.MODE.set(bucket_mode)
    create = _loopy_create()
    WCOJ_MODE.set("off")
    g_bin = CypherSession.tpu().create_graph_from_create_query(create)
    before = _tiers()
    binary = {q: g_bin.cypher(q).records.collect() for q in CYCLIC_CORPUS}
    assert _tiers() == before, "=off must never route to the multiway op"

    WCOJ_MODE.set("force")
    g_wcoj = CypherSession.tpu().create_graph_from_create_query(create)
    before = _tiers()
    for q in CYCLIC_CORPUS:
        got = [dict(r) for r in g_wcoj.cypher(q).records.collect()]
        assert got == [dict(r) for r in loopy_oracle[q]], f"oracle diverged: {q}"
        assert got == [dict(r) for r in binary[q]], f"binary diverged: {q}"
    after = _tiers()
    assert sum(after.values()) > sum(before.values()), (
        "force mode never reached the multiway op"
    )


def test_engine_differential_with_kernels_on(loopy_oracle):
    dispatch.MODE.set("interpret")
    bucketing.MODE.set("pow2")
    WCOJ_MODE.set("force")
    g = CypherSession.tpu().create_graph_from_create_query(_loopy_create())
    for q in CYCLIC_CORPUS:
        got = [dict(r) for r in g.cypher(q).records.collect()]
        assert got == [dict(r) for r in loopy_oracle[q]], q


def test_count_tier_on_loop_free_graph():
    """A loop-free graph lets the planner DROP the uniqueness filters by
    proof, so a pure count(*) triangle rides the count tier: no output
    materialize, no acyclic intermediate."""
    WCOJ_MODE.set("force")
    create = _loop_free_create()
    g_loc = CypherSession.local().create_graph_from_create_query(create)
    g_tpu = CypherSession.tpu().create_graph_from_create_query(create)
    want = g_loc.cypher(TRIANGLE).records.to_bag()
    before = _tiers()
    got = g_tpu.cypher(TRIANGLE).records.to_bag()
    after = _tiers()
    assert got == want
    assert after["count"] == before["count"] + 1
    assert after["materialize"] == before["materialize"]
    assert after["shadow"] == before["shadow"]


CORNER_GRAPHS = [
    ("CREATE (x:N)-[:K]->(x)", 0),
    ("CREATE (x:N)-[:K]->(y:N), (y)-[:K]->(x), (x)-[:K]->(x)", 3),
    ("CREATE (x:N), (y:N)", 0),  # empty adjacency
]


@pytest.mark.parametrize("create,expected", CORNER_GRAPHS)
def test_corner_graphs(create, expected):
    WCOJ_MODE.set("force")
    g_loc = CypherSession.local().create_graph_from_create_query(create)
    g_tpu = CypherSession.tpu().create_graph_from_create_query(create)
    want = g_loc.cypher(TRIANGLE).records.to_bag()
    got = g_tpu.cypher(TRIANGLE).records.to_bag()
    assert got == want
    rows = [dict(r) for r in g_tpu.cypher(TRIANGLE).records.collect()]
    assert rows == [{"t": expected}]


def test_multi_close_materialize_degrades_to_shadow(loopy_oracle):
    """With factorized execution pinned OFF, the multi-close materialize
    keeps its historical contract: a 4-clique on a LOOPY graph carries
    uniqueness pairs, forcing the materializing tier — whose flat form
    supports exactly one close constraint. The fused op must answer
    through its classic shadow plan, correctly."""
    WCOJ_MODE.set("force")
    FACTORIZE.set("off")
    clique = CYCLIC_CORPUS[5]
    g = CypherSession.tpu().create_graph_from_create_query(_loopy_create())
    before = _tiers()
    got = [dict(r) for r in g.cypher(clique).records.collect()]
    after = _tiers()
    assert got == [dict(r) for r in loopy_oracle[clique]]
    assert after["shadow"] > before["shadow"]


def test_multi_close_materialize_measured_by_default(loopy_oracle):
    """The factorized tier (backend/tpu/factorized.py) lifts the
    single-close restriction: by default the same 4-clique answers
    through a MEASURED materialize tier — run-decode over the per-close
    intersection counts — instead of falling back to the shadow plan."""
    WCOJ_MODE.set("force")
    clique = CYCLIC_CORPUS[5]
    g = CypherSession.tpu().create_graph_from_create_query(_loopy_create())
    before = _tiers()
    got = [dict(r) for r in g.cypher(clique).records.collect()]
    after = _tiers()
    assert got == [dict(r) for r in loopy_oracle[clique]]
    assert after["shadow"] == before["shadow"]
    measured = ("materialize", "factorized")
    assert sum(after[t] for t in measured) > sum(before[t] for t in measured)


# ---------------------------------------------------------------------------
# eligibility: the EmptyHeaded-style auto rule
# ---------------------------------------------------------------------------


def test_auto_mode_keeps_binary_plan_on_small_graphs():
    """Default threshold: a 30-node graph's blowup estimate stays under
    TPU_CYPHER_WCOJ_MIN_ROWS, so auto mode keeps today's binary plan."""
    g = CypherSession.tpu().create_graph_from_create_query(_loopy_create())
    before = _tiers()
    g.cypher(TRIANGLE).records.to_bag()
    assert _tiers() == before


def test_auto_mode_routes_past_threshold():
    WCOJ_MIN_ROWS.set(1)  # any nonempty graph clears the bar
    g = CypherSession.tpu().create_graph_from_create_query(_loopy_create())
    g_loc = CypherSession.local().create_graph_from_create_query(_loopy_create())
    before = _tiers()
    got = g.cypher(TRIANGLE).records.to_bag()
    after = _tiers()
    assert sum(after.values()) > sum(before.values())
    assert got == g_loc.cypher(TRIANGLE).records.to_bag()
    # the loopy graph keeps uniqueness enforcement, so the op lands on
    # the materializing tier (the count tier needs enforced_pairs gone)
    assert after["materialize"] > before["materialize"]
    assert after["count"] == before["count"]


@pytest.mark.parametrize(
    "dense,dense_max,tier",
    [
        # the dense MXU tier in reach: the count lands on the shadow tier,
        # and the shadow child is the PRUNED fused expand-into, so it
        # costs what ``off`` mode costs
        ("force", None, "shadow"),
        # not in reach — off (the CPU's default), or on with its node cap
        # pinned under the graph's 40 nodes: WCOJ's own count tier answers
        ("auto", None, "count"),
        ("force", 39, "count"),
    ],
)
def test_auto_mode_hands_pure_count_to_the_dense_tier_in_reach(
    monkeypatch, dense, dense_max, tier
):
    """Pure counts hand back to the classic plan in auto mode exactly when
    its dense MXU counting tier will take them (``_mxu_dense_mode`` under
    ``optimizer.cost.mxu_dense_node_cap()``, the gate ``dense_adj`` asks)."""
    monkeypatch.setenv("TPU_CYPHER_MXU_DENSE", dense)
    if dense_max is not None:
        monkeypatch.setenv("TPU_CYPHER_MXU_DENSE_MAX", str(dense_max))
    WCOJ_MIN_ROWS.set(1)
    create = _loop_free_create()
    g = CypherSession.tpu().create_graph_from_create_query(create)
    g_loc = CypherSession.local().create_graph_from_create_query(create)
    before = _tiers()
    got = g.cypher(TRIANGLE).records.to_bag()
    after = _tiers()
    assert got == g_loc.cypher(TRIANGLE).records.to_bag()
    moved = {k: after[k] - before[k] for k in after if after[k] != before[k]}
    assert moved == {tier: 1}


# ---------------------------------------------------------------------------
# fault injection at the operator's expand site: the full ladder
# ---------------------------------------------------------------------------

KIND_TO_ERROR = {
    "oom": ERR.DeviceOOM,
    "compile": ERR.CompileFailure,
    "lost": ERR.DeviceLost,
}


@pytest.fixture(scope="module")
def fault_graphs():
    create = _loopy_create(seed=11, n=12, e=50)
    return (
        CypherSession.tpu().create_graph_from_create_query(create),
        CypherSession.local().create_graph_from_create_query(create),
    )


@pytest.mark.parametrize("kind", sorted(KIND_TO_ERROR))
@pytest.mark.parametrize("depth", ["1", "*"])
def test_wcoj_expand_fault_matrix(fault_graphs, kind, depth):
    g_tpu, g_loc = fault_graphs
    want = g_loc.cypher(TRIANGLE).records.to_bag()

    WCOJ_MODE.set("force")
    dispatch.MODE.set("interpret")
    bucketing.MODE.set("pow2")
    faults.set_spec(f"{kind}@expand:{depth}")
    r = g_tpu.cypher(TRIANGLE)
    got = r.records.to_bag()
    faults.set_spec(None)

    assert got == want, f"expand/{kind}:{depth} diverged"
    log = r.execution_log
    assert log and log[-1]["ok"] is True
    failed = [e for e in log if not e["ok"]]
    assert failed, f"injected fault never fired: {log}"
    for e in failed:
        assert e["error"] == KIND_TO_ERROR[kind].__name__, log
    if depth == "*":
        # the operator passes its expand site at every device rung, so
        # only the host oracle escapes a persistent fault
        assert log[-1]["rung"] == guard.RUNG_HOST, log
    else:
        assert log[-1]["rung"] not in (guard.RUNG_DEVICE, guard.RUNG_HOST), log


# ---------------------------------------------------------------------------
# guards: registry, config knobs, engine lint, compile flatness
# ---------------------------------------------------------------------------


def test_wcoj_knobs_in_config_registry():
    assert "TPU_CYPHER_WCOJ" in REGISTRY
    assert "TPU_CYPHER_WCOJ_MIN_ROWS" in REGISTRY
    assert REGISTRY["TPU_CYPHER_WCOJ"].get() == "auto"
    assert REGISTRY["TPU_CYPHER_WCOJ_MIN_ROWS"].get() == 4096


def test_engine_lint_clean_on_wcoj_modules():
    from tpu_cypher import analysis

    root = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tpu_cypher",
        "backend",
        "tpu",
    )
    targets = [os.path.join(root, "wcoj.py")]
    # parse the whole backend so interprocedural rules keep their
    # substrate; report only on the new modules (--changed-only semantics)
    report = analysis.run_paths([root], restrict_to=targets)
    assert report.clean, report.render_text()


def test_wcoj_keeps_compile_stats_flat():
    """Acceptance: ZERO warm recompiles — a repeated cyclic query with the
    kernel tier on must reuse every compiled program."""
    WCOJ_MODE.set("force")
    dispatch.MODE.set("interpret")
    bucketing.MODE.set("pow2")
    g = CypherSession.tpu().create_graph_from_create_query(_loopy_create())
    g.cypher(TRIANGLE).records.to_bag()  # cold: compiles the lattice
    before = bucketing.compile_snapshot()
    g.cypher(TRIANGLE).records.to_bag()
    assert bucketing.compile_delta(before)["compiles"] == 0


# ---------------------------------------------------------------------------
# sorted-CSR regression (the correctness substrate of every binary search)
# ---------------------------------------------------------------------------


def test_csr_sorted_contract():
    assert GraphIndex.csr_sorted is True
    rng = np.random.default_rng(5)
    a = rng.integers(0, 9, 64)
    b = rng.integers(0, 9, 64)
    row_ptr, order, a_sorted = GraphIndex._sorted_csr(a, b, 9)
    b_sorted = b[order]
    for r in range(9):
        row = b_sorted[row_ptr[r]:row_ptr[r + 1]]
        assert (np.diff(row) >= 0).all(), f"row {r} not neighbor-sorted"
    # the flattened (a*N + b) keys — what edge_keys serves — are globally
    # nondecreasing, which is exactly what makes close ranges contiguous
    keys = a_sorted.astype(np.int64) * 9 + b_sorted.astype(np.int64)
    assert (np.diff(keys) >= 0).all()


def test_csr_build_violation_raises(monkeypatch):
    monkeypatch.setattr(
        GI.np, "lexsort", lambda keys: np.arange(len(keys[0]))
    )
    a = np.array([1, 1, 0])
    b = np.array([5, 3, 2])
    with pytest.raises(GraphIndexError, match="sorted-by-neighbor"):
        GraphIndex._sorted_csr(a, b, 6)


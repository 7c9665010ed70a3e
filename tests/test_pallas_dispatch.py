"""The Pallas kernel tier behind the dispatch layer.

Four guarantees under test:

* DIFFERENTIAL — the kernel under ``interpret`` is bit-identical to the
  jnp formulation it replaces across the fuzz-corpus shapes (empty
  frontier, all-masked lanes, single-bucket, max-bucket), both at the
  kernel contract level and end-to-end through the engine;
  ``TPU_CYPHER_PALLAS=off`` restores the pre-kernel execution path; and
  the jnp formulations that are the ONE path for expand and join-probe
  equal a NumPy reference on the same shapes.
* FAULTS — the ``kernel_agg`` site drives the degrade-and-retry ladder
  exactly like the relational sites: results stay oracle-identical, every
  failed attempt lands typed in ``execution_log``.
* GUARDS — every ``pl.pallas_call`` in ``backend/tpu`` lives inside a
  dispatch-registered impl (no raw calls bypassing eligibility), and
  repeated bucketed queries with kernels enabled compile ZERO new XLA
  programs once warm.
* NO HIDDEN FALLBACK — an interpreted failure re-raises as it is; a
  compiled-path refusal raises typed ``CompileFailure`` (never the jnp
  answer); a device fault mid-kernel surfaces as what it is.
"""

import os

import numpy as np
import pytest

import jax.numpy as jnp

from tpu_cypher import CypherSession
from tpu_cypher import errors as ERR
from tpu_cypher.backend.tpu import bucketing
from tpu_cypher.backend.tpu import jit_ops as J
from tpu_cypher.backend.tpu.pallas import aggregate as PA, dispatch
from tpu_cypher.utils.config import PALLAS_MAX_GROUPS
from tpu_cypher.runtime import faults, guard


@pytest.fixture(autouse=True)
def _clean_dispatch():
    """Every test leaves mode, launch counters, and fault specs as it
    found them — the no-cross-test-poisoning contract, enforced."""
    yield
    dispatch.MODE.reset()
    dispatch.reset()
    bucketing.MODE.reset()
    faults.set_spec(None)


@pytest.fixture
def interpret_mode():
    dispatch.MODE.set("interpret")
    yield


def _counts():
    return dispatch.use_counts()


# ---------------------------------------------------------------------------
# kernel-contract differentials across the corpus shapes
# ---------------------------------------------------------------------------

# the fuzz-corpus shape classes: (rows-ish, mask density); "empty",
# "all-masked", "single-bucket" (fits the 32-floor), "max-bucket" (pad
# tail much larger than the true count)
SHAPES = [
    ("empty", 0, 0.0),
    ("all_masked", 300, 0.0),
    ("single_bucket", 9, 0.9),
    ("dense", 1000, 0.85),
    ("max_bucket", 1025, 0.5),
]


@pytest.mark.parametrize("shape_name,n,density", SHAPES)
def test_expand_materialize_counted_vs_numpy(shape_name, n, density):
    rng = np.random.default_rng(hash(shape_name) % 2**31)
    n_nodes = max(n // 2, 4)
    deg = rng.integers(0, 6, n_nodes).astype(np.int64)
    rp_np = np.concatenate([[0], np.cumsum(deg)]).astype(np.int32)
    n_edges = int(deg.sum())
    ci_np = rng.integers(0, n_nodes, max(n_edges, 1)).astype(np.int32)[:n_edges]
    eo_np = rng.integers(0, 10**9, n_edges)
    pos_np = rng.integers(0, n_nodes, n)
    present_np = rng.random(n) < density
    rp, ci, eo = jnp.asarray(rp_np), jnp.asarray(ci_np), jnp.asarray(eo_np)
    pos, present = jnp.asarray(pos_np), jnp.asarray(present_np)
    dd, t_dev = J.expand_degrees_total(rp, pos, present)
    total = int(t_dev)
    # the NumPy repeat cascade: one lane per (frontier row, adjacent edge)
    fdeg = np.where(present_np, deg[pos_np], 0)
    assert total == int(fdeg.sum())
    row_np = np.repeat(np.arange(n), fdeg)
    edge_np = (
        np.repeat(rp_np[pos_np], fdeg)
        + np.arange(total)
        - np.repeat(np.cumsum(fdeg) - fdeg, fdeg)
    )
    # size 0 only pairs with total 0 (the engine's round_size(0) == 0 —
    # a nonzero pad-only materialize is outside the contract)
    sizes = (
        {total, bucketing.round_up_pow2(total, 32), total * 2 + 32}
        if total
        else {0}
    )
    for size in sizes:
        row, nbr, orig, live = J.expand_materialize_counted(
            rp, ci, eo, pos, dd, t_dev, size=size
        )
        live = np.asarray(live)
        assert live.sum() == total and live[:total].all(), (shape_name, size)
        assert (np.asarray(row)[:total] == row_np).all(), (shape_name, size)
        assert (np.asarray(nbr)[:total] == ci_np[edge_np]).all()
        assert (np.asarray(orig)[:total] == eo_np[edge_np]).all()
        # pad lanes are sanitized, never a raw out-of-bounds gather
        for arr in (row, nbr, orig):
            assert (np.asarray(arr)[total:] == 0).all(), (shape_name, size)


@pytest.mark.parametrize("shape_name,n,density", SHAPES)
def test_join_probe_bucketed_vs_numpy(shape_name, n, density):
    rng = np.random.default_rng(hash(shape_name) % 2**31 + 1)
    tag = 7 << 54  # graph-tagged ids: keys live far past int32
    nr = max(n // 3, 1)
    rd_np = rng.integers(0, max(nr // 2, 1), nr) + tag
    rvalid_np = rng.random(nr) < density
    ld_np = rng.integers(0, max(nr, 1), n) + tag
    lvalid_np = rng.random(n) < max(density, 0.5)
    rd, rvalid = jnp.asarray(rd_np), jnp.asarray(rvalid_np)
    ld, lvalid = jnp.asarray(ld_np), jnp.asarray(lvalid_np)
    rd_s, r_order, nvalid_dev = J.join_build(
        rd, (rvalid,), is_f64=False, is_bool=False
    )
    nvalid = int(nvalid_dev)
    assert nvalid == int(rvalid_np.sum())
    cap = min(bucketing.round_up_pow2(nvalid, 32), nr)
    r_idx, lo, counts, total_dev = J.join_probe_bucketed(
        rd_s, r_order, ld, (lvalid,), nvalid_dev,
        nvalid_cap=cap, is_f64=False, is_bool=False,
    )
    # per probe row: how many VALID build rows carry its key
    build_keys = rd_np[rvalid_np]
    want = np.where(
        lvalid_np, (ld_np[:, None] == build_keys[None, :]).sum(axis=1), 0
    )
    assert (np.asarray(counts) == want).all(), shape_name
    total = int(total_dev)
    assert total == int(want.sum())
    if total:
        # the shared materialize emits exactly the matching pairs
        size = bucketing.round_up_pow2(total, 32)
        left, right, _ = J.join_materialize_counted(
            r_idx, lo, counts, total_dev, size=size
        )
        left, right = np.asarray(left)[:total], np.asarray(right)[:total]
        assert (ld_np[left] == rd_np[right]).all(), shape_name
        assert rvalid_np[right].all() and lvalid_np[left].all()
        assert len({(a, b) for a, b in zip(left, right)}) == total


AGG_CASES = [
    ("count", "i64"), ("sum", "i64"), ("min", "i64"), ("max", "i64"),
    ("min", "f64"), ("max", "f64"), ("min", "bool"), ("max", "bool"),
]


@pytest.mark.parametrize("name,kind", AGG_CASES)
@pytest.mark.parametrize("shape_name,n,density", SHAPES)
def test_aggregate_kernel_differential(
    interpret_mode, name, kind, shape_name, n, density
):
    rng = np.random.default_rng(abs(hash((name, kind, shape_name))) % 2**31)
    k = max(min(n // 4, PALLAS_MAX_GROUPS.get()), 1)
    if kind == "i64":
        data = jnp.asarray(rng.integers(-(10**12), 10**12, n))
    elif kind == "f64":
        data = jnp.asarray(
            np.where(rng.random(n) < 0.15, np.nan, rng.normal(0, 10, n))
        )
    else:
        data = jnp.asarray(rng.random(n) < 0.5)
    valid = jnp.asarray(rng.random(n) < density)
    seg = jnp.asarray(rng.integers(0, k, n))
    want = J.segment_aggregate(data, valid, None, seg, name=name, kind=kind, k=k)
    got = PA.segment_aggregate(data, valid, None, seg, name=name, kind=kind, k=k)
    for w, g in zip(want, got):
        if w is None:
            assert g is None
            continue
        w, g = np.asarray(w), np.asarray(g)
        if w.dtype.kind == "f":
            assert ((w == g) | (np.isnan(w) & np.isnan(g))).all(), (
                name, kind, shape_name, w, g,
            )
        else:
            assert (w == g).all(), (name, kind, shape_name, w, g)
    # the kernel takes 32-bit planes only: count over anything, min/max
    # over BOOL. 64-bit values decline by eligibility — Mosaic never sees
    # them — and the drop-in still answers identically
    if name == "count" or kind == "bool":
        assert _counts()["segment_agg"]["pallas"] > 0
    else:
        assert _counts()["segment_agg"] == {"pallas": 0, "fallback": 1}


def test_aggregate_kernel_declines_over_group_cap(interpret_mode):
    n, k = 2000, PALLAS_MAX_GROUPS.get() + 1
    rng = np.random.default_rng(5)
    data = jnp.asarray(rng.integers(0, 100, n))
    seg = jnp.asarray(rng.integers(0, k, n))
    want = J.segment_aggregate(data, None, None, seg, name="count", kind="i64", k=k)
    got = PA.segment_aggregate(data, None, None, seg, name="count", kind="i64", k=k)
    assert (np.asarray(want[0]) == np.asarray(got[0])).all()
    assert _counts()["segment_agg"]["pallas"] == 0


# ---------------------------------------------------------------------------
# end-to-end: engine results identical with kernels on / off, and =off
# restores the pre-kernel path exactly
# ---------------------------------------------------------------------------


def _create_query(n=29, e=70, seed=11):
    rng = np.random.default_rng(seed)
    parts = []
    for i in range(n):
        props = [f"id:{i}"]
        if i % 4:
            props.append(f"age:{int(rng.integers(18, 70))}")
        parts.append(f"(n{i}:{'P' if i % 5 else 'P:Q'} {{{', '.join(props)}}})")
    src, dst = rng.integers(0, n, e), rng.integers(0, n, e)
    for s, d in zip(src, dst):
        if s != d:
            parts.append(f"(n{s})-[:K {{w:{int(rng.integers(1, 9))}}}]->(n{d})")
    return "CREATE " + ", ".join(parts)


ENGINE_CORPUS = [
    "MATCH (a:P)-[:K]->(b) RETURN count(*) AS c",
    "MATCH (a:P)-[r:K]->(b:P) RETURN a.id, b.id, r.w",
    "MATCH (a:P)-[:K]->(b:P)-[:K]->(c:P) RETURN count(*) AS c",
    "MATCH (a:P) WITH a.age AS g MATCH (b:P) WHERE b.age = g "
    "RETURN count(*) AS c",
    "MATCH (a:P)-[r:K]->(b) RETURN b.id AS t, count(*) AS c, "
    "min(r.w) AS lo, max(r.w) AS hi, sum(r.w) AS s ORDER BY t",
    "MATCH (a:P) OPTIONAL MATCH (a)-[:K]->(b) RETURN a.id, b.id",
    "MATCH (a:P) RETURN a.age AS g, count(*) AS c ORDER BY g",
    "MATCH (a:P)-[:K]->(b) RETURN b.id AS t, count(a.age) AS c ORDER BY t",
]


def test_engine_differential_kernels_on_vs_off():
    create = _create_query()
    dispatch.MODE.set("off")
    bucketing.MODE.set("pow2")
    g_off = CypherSession.tpu().create_graph_from_create_query(create)
    want = {q: g_off.cypher(q).records.to_bag() for q in ENGINE_CORPUS}
    assert all(v["pallas"] == 0 for v in _counts().values()), (
        "=off must never launch a kernel"
    )
    dispatch.MODE.set("interpret")
    g_on = CypherSession.tpu().create_graph_from_create_query(create)
    for q in ENGINE_CORPUS:
        got = g_on.cypher(q).records.to_bag()
        assert got == want[q], f"kernels diverged on: {q}"
    used = {k: v["pallas"] for k, v in _counts().items() if v["pallas"]}
    assert set(used) == {"segment_agg"}, used


def test_mode_off_never_reaches_pallas_fn():
    dispatch.MODE.set("off")
    dispatch.register("_probe_test_kernel", "kernel_agg", impls=())
    calls = {"pallas": 0}

    def pallas_fn(interpret):
        calls["pallas"] += 1
        return 1

    out = dispatch.launch("_probe_test_kernel", pallas_fn, lambda: 2)
    assert out == 2 and calls["pallas"] == 0


# ---------------------------------------------------------------------------
# fault injection at the kernel sites: the full ladder
# ---------------------------------------------------------------------------

# site -> query that reaches the kernel; which rung finally answers under
# ``:*`` (the aggregate kernel runs at every device rung, so only the host
# oracle escapes the fault)
KERNEL_SITE_QUERIES = {
    "kernel_agg": (
        "MATCH (a:P)-[:K]->(b:P) RETURN b.ref AS t, count(a.id) AS c, "
        "min(b.id) AS m",
        guard.RUNG_HOST,
    ),
}

KIND_TO_ERROR = {
    "oom": ERR.DeviceOOM,
    "compile": ERR.CompileFailure,
    "lost": ERR.DeviceLost,
}

FAULT_CREATE = (
    "CREATE "
    + ", ".join(f"(n{i}:P {{id:{i}, ref:{(i * 3) % 10}}})" for i in range(10))
    + ", "
    + ", ".join(f"(n{i})-[:K]->(n{(i * 7 + 3) % 10})" for i in range(10))
)


@pytest.fixture(scope="module")
def fault_graphs():
    return (
        CypherSession.tpu().create_graph_from_create_query(FAULT_CREATE),
        CypherSession.local().create_graph_from_create_query(FAULT_CREATE),
    )


@pytest.mark.parametrize("site", sorted(KERNEL_SITE_QUERIES))
@pytest.mark.parametrize("kind", sorted(KIND_TO_ERROR))
@pytest.mark.parametrize("depth", ["1", "*"])
def test_kernel_fault_matrix(fault_graphs, site, kind, depth):
    g_tpu, g_loc = fault_graphs
    query, star_rung = KERNEL_SITE_QUERIES[site]
    want = g_loc.cypher(query).records.to_bag()

    dispatch.MODE.set("interpret")
    bucketing.MODE.set("pow2")
    faults.set_spec(f"{kind}@{site}:{depth}")
    r = g_tpu.cypher(query)
    got = r.records.to_bag()
    faults.set_spec(None)

    assert got == want, f"{site}/{kind}:{depth} diverged: {got} vs {want}"
    log = r.execution_log
    assert log and log[-1]["ok"] is True
    failed = [e for e in log if not e["ok"]]
    assert failed, f"injected fault at {site} never fired: {log}"
    for e in failed:
        assert e["error"] == KIND_TO_ERROR[kind].__name__, log
    if depth == "*":
        assert log[-1]["rung"] == star_rung, log
    else:
        assert log[-1]["rung"] not in (guard.RUNG_DEVICE, guard.RUNG_HOST), log


# ---------------------------------------------------------------------------
# no hidden fallback: a selected kernel runs or raises
# ---------------------------------------------------------------------------


def test_interpret_failure_reraises_and_kernel_stays_live():
    """An interpreted failure is a real bug: it re-raises as it is, is
    never answered by the fallback, and leaves the kernel live."""
    dispatch.register("_raising_test_kernel", "kernel_agg", impls=())

    def boom(interpret):
        raise RuntimeError("synthetic interpret-mode failure")

    dispatch.MODE.set("interpret")
    with pytest.raises(RuntimeError):
        dispatch.launch("_raising_test_kernel", boom, lambda: "fallback")
    out = dispatch.launch(
        "_raising_test_kernel", lambda interpret: "pallas", lambda: "fallback"
    )
    assert out == "pallas"


def test_compiled_refusal_raises_compile_failure(monkeypatch):
    """On a TPU backend a lowering refusal is NEVER swallowed into the jnp
    formulation: it raises typed ``CompileFailure`` on every call."""
    dispatch.register("_refused_test_kernel", "kernel_agg", impls=())
    monkeypatch.setattr(dispatch, "_backend_is_tpu", lambda: True)
    calls = {"pallas": 0, "fallback": 0}

    def refused(interpret):
        assert interpret is False
        calls["pallas"] += 1
        raise ValueError("Cannot do int indexing on TPU")

    def fallback():
        calls["fallback"] += 1
        return "fb"

    for _ in range(2):
        with pytest.raises(ERR.CompileFailure) as info:
            dispatch.launch("_refused_test_kernel", refused, fallback)
        assert info.value.site == "kernel_agg"
        assert isinstance(info.value.cause, ValueError)
    assert calls == {"pallas": 2, "fallback": 0}
    assert _counts()["_refused_test_kernel"] == {"pallas": 0, "fallback": 0}


def test_device_fault_inside_kernel_surfaces_typed(monkeypatch):
    """An OOM raised DURING a compiled kernel run must re-raise typed as
    what it is (the ladder handles it), not as a lowering refusal."""
    import jax

    dispatch.register("_oom_test_kernel", "kernel_agg", impls=())
    monkeypatch.setattr(dispatch, "_backend_is_tpu", lambda: True)

    def oom(interpret):
        raise jax.errors.JaxRuntimeError(
            "RESOURCE_EXHAUSTED: out of memory allocating 1 bytes"
        )

    with pytest.raises(ERR.DeviceOOM):
        dispatch.launch("_oom_test_kernel", oom, lambda: 0)


def test_auto_mode_off_tpu_answers_from_jnp(monkeypatch):
    """``auto`` off a TPU backend never reaches the kernel (and never the
    interpreter); on a TPU backend it compiles — ``interpret=False``."""
    dispatch.register("_auto_test_kernel", "kernel_agg", impls=())
    seen = []

    def pallas_fn(interpret):
        seen.append(interpret)
        return "pallas"

    assert dispatch.launch("_auto_test_kernel", pallas_fn, lambda: "fb") == "fb"
    assert seen == []
    monkeypatch.setattr(dispatch, "_backend_is_tpu", lambda: True)
    assert dispatch.launch("_auto_test_kernel", pallas_fn, lambda: "fb") == "pallas"
    assert seen == [False]


# ---------------------------------------------------------------------------
# AST guard: no pallas_call outside registered dispatch impls
# ---------------------------------------------------------------------------


def test_every_pallas_call_goes_through_dispatch():
    """Raw ``pl.pallas_call`` only inside dispatch-registered impls under
    ``backend/tpu/pallas/`` — enforced by the ``obs-emission`` rule of
    ``tpu_cypher.analysis`` (ISSUE 5), which statically collects the
    ``dispatch.register(.., impls=(..))`` allowlist. The runtime registry
    must agree with the static one (same impls), so registration cannot
    drift from what the rule checks."""
    from tpu_cypher import analysis
    from tpu_cypher.analysis.project import ProjectContext

    root = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tpu_cypher",
        "backend",
        "tpu",
    )
    report = analysis.run_paths([root], rules=["obs-emission"])
    assert report.clean, (
        "raw pl.pallas_call outside a dispatch-registered impl — every "
        "kernel must launch through backend.tpu.pallas.dispatch.launch "
        f"(eligibility/fallback/fault sites):\n{report.render_text()}"
    )
    # static allowlist == runtime registry: the rule checks what actually
    # registers
    runtime_impls = set()
    for spec in dispatch.registry().values():
        runtime_impls.update(spec.impls)
    ctxs = []
    for dirpath, _dirs, files in os.walk(os.path.join(root, "pallas")):
        for fname in sorted(files):
            if fname.endswith(".py"):
                p = os.path.join(dirpath, fname)
                ctxs.append(
                    analysis.FileContext(p, os.path.relpath(p), open(p).read())
                )
    static_impls = ProjectContext(ctxs).dispatch_impls
    assert runtime_impls == static_impls, (
        f"runtime registry {sorted(runtime_impls)} != statically "
        f"registered impls {sorted(static_impls)}"
    )


# ---------------------------------------------------------------------------
# no-recompile guard: warm bucketed queries with kernels ON compile nothing
# ---------------------------------------------------------------------------


def test_kernels_keep_compile_stats_flat():
    bucketing.MODE.set("pow2")
    session = CypherSession.tpu()

    def build(n):
        parts = [f"(n{i}:P {{id:{i}, ref:{(i * 3) % 7}}})" for i in range(n)]
        parts += [
            f"(n{i})-[:K]->(n{(i * 5 + 2) % n})" for i in range(n)
        ]
        return session.create_graph_from_create_query(
            "CREATE " + ", ".join(parts)
        )

    # the grouped count rides the kernel; its group factorization runs at
    # EXACT sizes by design (seed behavior — "out of the bucketing
    # contract"), kernel tier or not, so a fresh size costs the kernel
    # path exactly what it costs the scatter path
    queries = [
        "MATCH (a:P)-[:K]->(b:P) RETURN a.id AS a, b.id AS b",
        "MATCH (x:P), (y:P) WHERE x.ref = y.id RETURN count(*) AS c",
        "MATCH (a:P) RETURN a.ref AS r, count(a.id) AS c",
    ]

    def run(g):
        before = bucketing.compile_snapshot()
        for q in queries:
            g.cypher(q).records.collect()
        return bucketing.compile_delta(before)["compiles"]

    # baseline: the pre-kernel path's own warm-delta for a fresh
    # bucket-sharing size (the delivery path compiles two tiny exact-size
    # slices per size — seed behavior, kernel-independent)
    dispatch.MODE.set("off")
    run(build(40))
    baseline = run(build(44))

    dispatch.MODE.set("interpret")
    g1 = build(46)
    run(g1)  # cold: compiles the bucket-lattice programs incl. kernels
    assert _counts()["segment_agg"]["pallas"] > 0
    assert run(g1) == 0, "same graph re-run must compile nothing"
    # fresh size in the same buckets: the kernel tier must add ZERO
    # compiles over the pre-kernel path's own delta
    assert run(build(50)) == baseline, (
        "kernels broke warm-path compile_stats flatness"
    )

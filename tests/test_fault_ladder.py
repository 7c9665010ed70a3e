"""Fault-tolerant execution: the typed-error taxonomy, memory admission,
and the degrade-and-retry ladder, proven by deterministic fault injection
(docs/robustness.md).

The matrix injects every fault kind at every named site at two ladder
depths (``:1`` — the first degraded rung absorbs it; ``:*`` — every device
rung fails and the host oracle answers) and asserts:

* results stay bag-identical to the local oracle,
* every attempt lands in ``result.execution_log`` with its typed error,
* no RAW (untyped) error ever escapes ``CypherResult`` — with the ladder
  disabled the caller sees a ``tpu_cypher.errors`` class, never an
  ``InjectedFault``/``JaxRuntimeError``.
"""

import os
import threading

import pytest

from tpu_cypher import CypherSession
from tpu_cypher import errors as ERR
from tpu_cypher.backend.tpu import bucketing
from tpu_cypher.backend.tpu.table import FALLBACK_COUNTER
from tpu_cypher.parallel.mesh import make_row_mesh, use_mesh
from tpu_cypher.runtime import faults, guard

CREATE = (
    "CREATE "
    + ", ".join(f"(n{i}:P {{id:{i}, ref:{(i * 3) % 10}}})" for i in range(10))
    + ", "
    + ", ".join(f"(n{i})-[:K]->(n{(i * 7 + 3) % 10})" for i in range(10))
)

# site -> (query exercising it, needs active row mesh)
SITE_QUERIES = {
    "filter": ("MATCH (n:P) WHERE n.id > 3 RETURN n.id AS i", False),
    "compact": ("MATCH (n:P) WHERE n.id > 3 RETURN n.id AS i", False),
    "join": (
        "MATCH (x:P), (y:P) WHERE x.ref = y.id RETURN x.id AS a, y.id AS b",
        False,
    ),
    "expand": ("MATCH (a:P)-[:K]->(b:P) RETURN a.id AS a, b.id AS b", False),
    "var_expand": ("MATCH (a:P)-[:K*1..2]->(b:P) RETURN count(*) AS c", False),
    "shuffle": (
        "MATCH (x:P), (y:P) WHERE x.ref = y.id RETURN count(*) AS c",
        True,
    ),
    # the PR-5 host-sync lint pass put the aggregation-path count syncs
    # behind their own site (table.distinct_count/_segment_agg/percentile)
    "agg": ("MATCH (n:P) RETURN n.ref AS r, sum(n.id) AS s", False),
}

KIND_TO_ERROR = {
    "oom": ERR.DeviceOOM,
    "compile": ERR.CompileFailure,
    "lost": ERR.DeviceLost,
}


@pytest.fixture(scope="module")
def graphs():
    s_tpu = CypherSession.tpu()
    s_loc = CypherSession.local()
    return (
        s_tpu.create_graph_from_create_query(CREATE),
        s_loc.create_graph_from_create_query(CREATE),
    )


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.set_spec(None)
    yield
    faults.set_spec(None)


def _run(g, query):
    r = g.cypher(query)
    bag = r.records.to_bag()
    return r, bag


# ---------------------------------------------------------------------------
# the matrix: every site x every kind x two ladder depths
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("site", sorted(SITE_QUERIES))
@pytest.mark.parametrize("kind", sorted(KIND_TO_ERROR))
@pytest.mark.parametrize("depth", ["1", "*"])
def test_fault_matrix(graphs, site, kind, depth):
    g_tpu, g_loc = graphs
    query, needs_mesh = SITE_QUERIES[site]
    want = g_loc.cypher(query).records.to_bag()

    faults.set_spec(f"{kind}@{site}:{depth}")
    if needs_mesh:
        with use_mesh(make_row_mesh()):
            r, got = _run(g_tpu, query)
    else:
        r, got = _run(g_tpu, query)
    faults.set_spec(None)

    assert got == want, f"{site}/{kind}:{depth} diverged: {got} vs {want}"
    log = r.execution_log
    assert log, "execution_log must record every attempt"
    assert log[-1]["ok"] is True
    failed = [e for e in log if not e["ok"]]
    assert failed, f"injected fault at {site} never fired: {log}"
    for e in failed:
        assert e["error"] == KIND_TO_ERROR[kind].__name__, log
    if depth == "*":
        # every device rung fails: the host oracle must have answered
        assert log[-1]["rung"] == guard.RUNG_HOST, log
    else:
        # one-shot fault: the FIRST degraded rung absorbs it
        assert log[-1]["rung"] != guard.RUNG_DEVICE
        assert log[-1]["rung"] != guard.RUNG_HOST, log


def test_bucket_exact_rung_used_when_bucketing_on(graphs):
    g_tpu, g_loc = graphs
    query, _ = SITE_QUERIES["expand"]
    want = g_loc.cypher(query).records.to_bag()
    bucketing.MODE.set("pow2")
    try:
        faults.set_spec("oom@expand:1")
        r, got = _run(g_tpu, query)
    finally:
        bucketing.MODE.reset()
        faults.set_spec(None)
    assert got == want
    assert [e["rung"] for e in r.execution_log] == [
        guard.RUNG_DEVICE,
        guard.RUNG_BUCKET_EXACT,
    ]


def test_no_raw_error_escapes_with_ladder_off(graphs):
    g_tpu, _ = graphs
    query, _ = SITE_QUERIES["join"]
    guard.LADDER_MODE.set("off")
    try:
        for kind, err_cls in KIND_TO_ERROR.items():
            faults.set_spec(f"{kind}@join:*")
            r = g_tpu.cypher(query)
            with pytest.raises(ERR.TpuCypherError) as ei:
                r.records
            assert isinstance(ei.value, err_cls), ei.value
            assert not isinstance(ei.value, faults.InjectedFault)
            faults.set_spec(None)
    finally:
        guard.LADDER_MODE.reset()
        faults.set_spec(None)


def test_clean_path_logs_single_device_rung(graphs):
    g_tpu, g_loc = graphs
    query, _ = SITE_QUERIES["expand"]
    r, got = _run(g_tpu, query)
    assert got == g_loc.cypher(query).records.to_bag()
    assert [e["rung"] for e in r.execution_log] == [guard.RUNG_DEVICE]
    assert r.execution_log[0]["ok"] is True
    assert r.compile_stats is not None


# ---------------------------------------------------------------------------
# the write-path matrix: commit fault sites x every kind (ISSUE 17). No
# ladder here — a write either commits atomically or fails typed with
# nothing durable; ``compact`` failures defer instead of failing the
# already-committed write. Pure write statements (no read prefix) keep the
# storage-tier ``compact`` site distinct from the device-tier one.
# ---------------------------------------------------------------------------


from tpu_cypher.storage import mutable_graph_from_create_query
from tpu_cypher.utils.config import COMPACT_DELTA_MAX


@pytest.mark.parametrize("kind", sorted(KIND_TO_ERROR))
@pytest.mark.parametrize("site", ["wal_append", "delta_apply"])
def test_write_fault_matrix_commit_atomic(tmp_path, site, kind):
    s = CypherSession.tpu()
    wal_path = str(tmp_path / f"{site}-{kind}.wal")
    pg = mutable_graph_from_create_query(
        s, "CREATE (:W {k: 0})", wal_path=wal_path
    )
    size = os.path.getsize(wal_path)
    version = pg._graph._version

    faults.set_spec(f"{kind}@{site}:1")
    with pytest.raises(ERR.TpuCypherError) as ei:
        s.cypher("CREATE (:W {k: 1})", graph=pg)
    faults.set_spec(None)

    # typed, never raw — same discipline as the read ladder
    assert isinstance(ei.value, KIND_TO_ERROR[kind]), ei.value
    assert not isinstance(ei.value, faults.InjectedFault)
    # atomic: nothing durable, nothing visible (delta_apply rolls the WAL
    # back to the pre-append offset; wal_append never reached it)
    assert os.path.getsize(wal_path) == size
    assert pg._graph._version == version
    # the fault was transient: the same statement retried commits, and a
    # cold rebuild from the WAL agrees (the failed attempt never replays)
    s.cypher("CREATE (:W {k: 1})", graph=pg)
    rebuilt = mutable_graph_from_create_query(
        s, "CREATE (:W {k: 0})", wal_path=wal_path
    )
    for g in (pg, rebuilt):
        got = s.cypher(
            "MATCH (n:W) RETURN count(*) AS c", graph=g
        ).records.collect()
        assert got == [{"c": 2}], (site, kind, got)


@pytest.mark.parametrize("kind", sorted(KIND_TO_ERROR))
def test_write_fault_compact_defers(kind):
    s = CypherSession.tpu()
    pg = mutable_graph_from_create_query(s, "CREATE (:W {k: 0})")
    COMPACT_DELTA_MAX.set(1)
    try:
        faults.set_spec(f"{kind}@compact:1")
        r = s.cypher("CREATE (:W {k: 1})", graph=pg)  # must NOT raise
        faults.set_spec(None)
        assert r.write_stats["nodes_created"] == 1
        m = pg._graph
        assert m.deferred_compactions == 1
        before = m.compactions
        s.cypher("CREATE (:W {k: 2})", graph=pg)
        assert m.compactions > before  # deferral retried next commit
    finally:
        COMPACT_DELTA_MAX.reset()
        faults.set_spec(None)


# ---------------------------------------------------------------------------
# memory admission
# ---------------------------------------------------------------------------


def test_admission_rejects_with_ladder_off(graphs):
    g_tpu, _ = graphs
    query, _ = SITE_QUERIES["expand"]
    guard.LADDER_MODE.set("off")
    bucketing.MEM_BUDGET.set(64)  # far under any real materialize
    try:
        r = g_tpu.cypher(query)
        with pytest.raises(ERR.AdmissionRejected) as ei:
            r.records
        assert ei.value.budget_bytes == 64
        assert ei.value.estimated_bytes > 64
        assert ei.value.site in ("expand", "join", "var_expand")
    finally:
        bucketing.MEM_BUDGET.reset()
        guard.LADDER_MODE.reset()


def test_admission_downgrades_to_host(graphs):
    g_tpu, g_loc = graphs
    query, _ = SITE_QUERIES["expand"]
    want = g_loc.cypher(query).records.to_bag()
    bucketing.MEM_BUDGET.set(64)
    try:
        r, got = _run(g_tpu, query)
    finally:
        bucketing.MEM_BUDGET.reset()
    assert got == want
    assert r.execution_log[-1]["rung"] == guard.RUNG_HOST
    assert any(
        e.get("error") == "AdmissionRejected" for e in r.execution_log
    ), r.execution_log


def test_admission_estimate_uses_bucket_lattice():
    bucketing.MODE.set("pow2")
    try:
        # 1000 rows round up to 1024 on the pow2 lattice
        assert bucketing.estimate_materialize_bytes(1000, 10) == 10240
    finally:
        bucketing.MODE.reset()
    assert bucketing.estimate_materialize_bytes(1000, 10) == 10000


def test_session_budget_option_sets_admission():
    prev = bucketing.MEM_BUDGET._override
    try:
        CypherSession.tpu(memory_budget_bytes=12345)
        assert bucketing.memory_budget_bytes() == 12345
    finally:
        bucketing.MEM_BUDGET._override = prev


# ---------------------------------------------------------------------------
# deadline
# ---------------------------------------------------------------------------


def test_query_deadline_raises_typed_timeout():
    s = CypherSession.tpu(query_deadline_seconds=1e-9)
    g = s.create_graph_from_create_query(CREATE)
    r = g.cypher(SITE_QUERIES["expand"][0])
    with pytest.raises(ERR.QueryTimeout):
        r.records
    # terminal: the ladder must NOT have retried past the first rung
    assert len(r.execution_log) == 1
    assert r.execution_log[0]["error"] == "QueryTimeout"


def test_injected_timeout_is_terminal(graphs):
    g_tpu, _ = graphs
    faults.set_spec("timeout@expand:*")
    r = g_tpu.cypher(SITE_QUERIES["expand"][0])
    with pytest.raises(ERR.QueryTimeout):
        r.records
    faults.set_spec(None)
    assert len(r.execution_log) == 1


# ---------------------------------------------------------------------------
# taxonomy / spec grammar units
# ---------------------------------------------------------------------------


def test_classify_raw_markers():
    # the raw class the INSTALLED runtime raises (what a chip fault is)
    from jax.errors import JaxRuntimeError

    oom = ERR.classify(JaxRuntimeError("RESOURCE_EXHAUSTED: out of memory"))
    assert isinstance(oom, ERR.DeviceOOM)
    lost = ERR.classify(JaxRuntimeError("UNAVAILABLE: device lost"))
    assert isinstance(lost, ERR.DeviceLost)
    comp = ERR.classify(JaxRuntimeError("INTERNAL: error while compiling"))
    assert isinstance(comp, ERR.CompileFailure)
    # unknown raw device error still classifies (generic DeviceError)
    other = ERR.classify(JaxRuntimeError("something odd"))
    assert isinstance(other, ERR.DeviceError)
    # non-device exceptions pass through unclassified
    assert ERR.classify(ValueError("RESOURCE_EXHAUSTED-looking text")) is None
    assert ERR.classify(KeyError("x")) is None


def test_fault_spec_grammar():
    spec = faults.parse_spec("oom@join:2, compile@expand:1-3 ,lost@compact:*")
    assert spec["join"] == [("oom", 2, 2)]
    assert spec["expand"] == [("compile", 1, 3)]
    assert spec["compact"][0][0] == "lost" and spec["compact"][0][2] > 10**9
    for bad in ("oom", "oom@", "zap@join:1", "oom@join:0", "oom@join:5-2"):
        with pytest.raises(faults.FaultSpecError):
            faults.parse_spec(bad)


# ---------------------------------------------------------------------------
# context-local fallback counter (satellite)
# ---------------------------------------------------------------------------


def test_fallback_scopes_are_context_local():
    agg_before = sum(FALLBACK_COUNTER.snapshot().values())
    seen_in_main = {}
    barrier = threading.Barrier(2)
    done = threading.Event()

    def other_thread():
        barrier.wait()
        FALLBACK_COUNTER.record("thread:other")
        done.set()

    t = threading.Thread(target=other_thread)
    with FALLBACK_COUNTER.scope() as events:
        t.start()
        barrier.wait()
        done.wait()
        FALLBACK_COUNTER.record("main:own")
        seen_in_main = dict(events)
    t.join()
    # the main scope saw only its own context's events...
    assert seen_in_main == {"main:own": 1}
    # ...while the aggregate saw both (the TCK corpus gate reads this)
    agg_after = FALLBACK_COUNTER.snapshot()
    assert sum(agg_after.values()) == agg_before + 2


def test_per_result_fallbacks_isolated_across_threads():
    results = {}

    def run(name):
        s = CypherSession.tpu()
        s.record_fallbacks = True
        g = s.create_graph_from_create_query(
            "CREATE (:Q {l: [1, 2]})-[:K]->(:Q {l: [3]})"
        )
        r = g.cypher("MATCH (n:Q) WHERE n.l[0] = 1 RETURN count(*) AS c")
        r.records.collect()
        results[name] = r.fallbacks

    threads = [threading.Thread(target=run, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # both queries recorded their own host islands; with the old
    # module-global snapshot diff, concurrent queries could double-count
    # or zero out each other's deltas
    for name, fb in results.items():
        assert fb, f"thread {name} lost its fallback events: {results}"
        assert sum(fb.values()) <= 4, f"cross-pollution: {results}"


# ---------------------------------------------------------------------------
# error discipline guard (satellite): no broad handler in backend/tpu may
# swallow a device fault silently
# ---------------------------------------------------------------------------


def test_no_silent_broad_excepts_in_tpu_backend():
    """Every ``except Exception``/bare ``except`` under
    ``tpu_cypher/backend/tpu/`` must either re-raise (a typed
    ``tpu_cypher.errors`` class or a narrower engine error), route device
    faults through ``errors.reraise_if_device``, or be explicitly
    annotated ``fault-ok`` on the except line. Enforced by the
    ``exception-hygiene`` rule of ``tpu_cypher.analysis`` (ISSUE 5), which
    generalizes the walker that used to live here to the WHOLE engine —
    this invocation keeps the original backend/tpu scope as a focused
    tier-1 gate; test_analysis covers the engine-wide run."""
    from tpu_cypher import analysis

    root = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tpu_cypher",
        "backend",
        "tpu",
    )
    report = analysis.run_paths([root], rules=["exception-hygiene"])
    assert report.clean, (
        "broad except handlers that neither re-raise nor carry a "
        "'fault-ok' annotation — route device faults through "
        "tpu_cypher.errors.reraise_if_device or annotate why the handler "
        f"is host-side-only:\n{report.render_text()}"
    )

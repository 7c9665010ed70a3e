"""Query observability subsystem (ISSUE 4): trace spans, unified metrics
registry, PROFILE surface.

Five guarantees under test:

* SPAN TREE — ``result.profile()`` returns one span per pipeline phase
  (parse -> ir -> logical -> ... -> execute -> collect) with relational
  operators nested under execute, per-operator self times that sum to the
  subtree total, bucket pad ratios, fault-site sync points, and the
  failing operator's span id in ``execution_log``; all with ZERO added
  device syncs and a flat warm-path compile count.
* ISOLATION — traces and metric scopes are context-local: interleaved and
  concurrent queries never cross-pollute each other's trees.
* REGISTRY — counters/gauges/histograms with labeled series, the
  cardinality cap, idempotent re-registration, and all four legacy
  counters (compile, fallback, pallas-use, fault-site) served through it
  with their legacy read paths green.
* EXPORT — deterministic Prometheus text (golden), schema-versioned
  JSON-lines events on ``TPU_CYPHER_METRICS_FILE``.
* LINT GUARD — the fault-site and kernel-dispatch chokepoints emit through
  ``obs``, and no module-global stray counter dicts exist anywhere in the
  engine. Checked by the ``obs-emission`` rule of ``tpu_cypher.analysis``
  (ISSUE 5) — this file just invokes the framework; the old ad-hoc AST
  walkers live on as the rule implementation.
"""

import json
import os
import threading

import pytest

from tpu_cypher import CypherSession
from tpu_cypher.backend.tpu import bucketing
from tpu_cypher.obs import metrics as OM
from tpu_cypher.obs import trace as OT
from tpu_cypher.runtime import faults, guard

HERE = os.path.dirname(os.path.abspath(__file__))
PKG = os.path.join(HERE, "..", "tpu_cypher")

THREE_HOP = (
    "MATCH (a:P)-[:K]->(b:P)-[:K]->(c:P)-[:K]->(d:P) "
    "RETURN count(*) AS c"
)


@pytest.fixture(autouse=True)
def _clean():
    yield
    faults.set_spec(None)
    bucketing.MODE.reset()
    OM.METRICS_FILE.reset()


def _chain_graph(session, n=12):
    parts = [f"(n{i}:P {{id:{i}}})" for i in range(n)]
    parts += [f"(n{i})-[:K]->(n{i + 1})" for i in range(n - 1)]
    parts += [f"(n{i})-[:K]->(n{(i + 3) % n})" for i in range(n)]
    return session.create_graph_from_create_query("CREATE " + ", ".join(parts))


# ---------------------------------------------------------------------------
# span tree shape
# ---------------------------------------------------------------------------


def test_profile_span_tree_per_phase():
    s = CypherSession.tpu()
    g = _chain_graph(s)
    r = g.cypher(THREE_HOP)
    r.records.collect()
    prof = r.profile()
    phases = [sp.name for sp in prof.trace.root.children]
    for want in ("parse", "ir", "logical", "logical_opt", "relational",
                 "prune", "cse", "execute", "collect"):
        assert want in phases, (want, phases)
    # relational operators nest under execute, as operator-kind spans
    execute = next(sp for sp in prof.trace.root.children if sp.name == "execute")
    assert execute.attrs.get("rung") == guard.RUNG_DEVICE
    ops = [sp for sp in prof.trace.spans() if sp.kind == "operator"]
    assert ops, "no operator spans recorded"
    # the rendered tree and the JSON form agree on the span census
    rendered = prof.render()
    assert "execute" in rendered and "ms" in rendered
    d = prof.to_dict()
    assert d["schema_version"] == OT.SCHEMA_VERSION
    assert json.loads(prof.to_json())["root"]["name"] == "query"


def test_operator_self_times_sum_to_total():
    """Acceptance: per-operator wall times sum (within tolerance) to the
    query's execute time on a 3-hop query — the self/total decomposition
    is exact by construction, so the tolerance only absorbs float error."""
    s = CypherSession.tpu()
    g = _chain_graph(s)
    r = g.cypher(THREE_HOP)
    r.records.collect()
    prof = r.profile()
    execute = next(sp for sp in prof.trace.root.children if sp.name == "execute")

    def subtree_self_sum(sp):
        return sp.self_seconds + sum(subtree_self_sum(c) for c in sp.children)

    total = execute.seconds
    assert total > 0
    assert abs(subtree_self_sum(execute) - total) <= max(1e-3, 0.02 * total)
    # and the root total is exactly the sum of its phases
    assert abs(
        prof.total_seconds - sum(prof.phase_seconds().values())
    ) < 1e-9


def test_profile_zero_added_syncs_and_flat_warm_compiles():
    """Acceptance: instrumentation adds no device syncs and no warm-path
    recompiles — the warm re-run of a profiled query compiles nothing."""
    s = CypherSession.tpu()
    g = _chain_graph(s)
    r1 = g.cypher(THREE_HOP)
    r1.records.collect()
    r1.profile()  # profiling the cold run must not poison the warm one
    before = bucketing.compile_snapshot()
    r2 = g.cypher(THREE_HOP)
    r2.records.collect()
    prof2 = r2.profile()
    assert bucketing.compile_delta(before)["compiles"] == 0
    assert r2.compile_stats["compiles"] == 0
    assert prof2.total_seconds > 0


def test_plan_cache_hit_trace_is_marked():
    s = CypherSession.tpu()
    g = _chain_graph(s)
    q = "MATCH (a:P) WHERE a.id > 3 RETURN count(*) AS c"
    g.cypher(q).records.collect()
    r = g.cypher(q)
    r.records.collect()
    prof = r.profile()
    assert prof.trace.root.attrs.get("plan_cache") == "hit"
    phases = [sp.name for sp in prof.trace.root.children]
    assert "parse" not in phases  # planning was skipped, the trace says so
    assert "execute" in phases


def test_bucket_pad_rows_recorded_on_spans():
    bucketing.MODE.set("pow2")
    s = CypherSession.tpu()
    g = _chain_graph(s, n=40)
    r = g.cypher("MATCH (a:P)-[:K]->(b:P) RETURN count(*) AS c")
    r.records.collect()
    padded = [
        sp for sp in r.profile().trace.spans()
        if sp.attrs.get("rows_padded", 0) > 0
    ]
    assert padded, "no span recorded bucket-lattice pad counts"
    for sp in padded:
        assert sp.attrs["rows_padded"] >= sp.attrs["rows_true"]


def test_fault_site_sync_points_on_spans():
    s = CypherSession.tpu()
    g = _chain_graph(s)
    r = g.cypher(THREE_HOP)
    r.records.collect()
    sites = {}
    for sp in r.profile().trace.spans():
        for k, v in sp.attrs.get("sites", {}).items():
            sites[k] = sites.get(k, 0) + v
    assert sites, "no fault-site sync points stamped on any span"


# ---------------------------------------------------------------------------
# the tree's clock (schema 2): start and end on every span, the device-trace
# annotations, the sync and build leaves, the log of finished trees
# ---------------------------------------------------------------------------


def _assert_nested_in_time(node, parent=None):
    """Every span's [start_s, start_s + seconds] lies inside its parent's,
    and siblings (one thread) do not overlap. 2 us of slack: to_dict
    rounds to the microsecond."""
    eps = 2e-6
    lo, hi = node["start_s"], node["start_s"] + node["seconds"]
    if parent is not None:
        plo, phi = parent["start_s"], parent["start_s"] + parent["seconds"]
        assert plo - eps <= lo and hi <= phi + eps, (node["name"], parent["name"])
    at = lo - eps
    for child in node.get("children", ()):
        assert child["start_s"] >= at - eps, (child["name"], node["name"])
        at = child["start_s"] + child["seconds"]
        _assert_nested_in_time(child, node)


def test_every_span_has_its_start_inside_its_parent():
    s = CypherSession.tpu()
    g = _chain_graph(s)
    r = g.cypher(THREE_HOP)
    r.records.collect()
    d = r.profile().to_dict()
    assert d["schema_version"] == 2 == OT.SCHEMA_VERSION
    assert d["start_perf_s"] > 0 and d["start_unix_ns"] > 10**18
    spans = []

    def walk(n):
        spans.append(n)
        for c in n.get("children", ()):
            walk(c)

    walk(d["root"])
    assert len(spans) > 10 and all("start_s" in n for n in spans)
    assert d["root"]["start_s"] == 0.0
    # an unclosed root (a lazy result) renders with its children's extent
    assert d["root"]["seconds"] >= d["total_seconds"] - 1e-5
    _assert_nested_in_time(d["root"])
    json.dumps(d)


def test_plan_cache_is_a_phase_on_hit_and_on_miss():
    s = CypherSession.tpu()
    g = _chain_graph(s)
    q = "MATCH (a:P) WHERE a.id > 4 RETURN count(*) AS c"
    miss = g.cypher(q)
    miss.records.collect()
    hit = g.cypher(q)
    hit.records.collect()
    planning = {"parse", "ir", "logical", "logical_opt", "relational",
                "prune", "cse"}
    names = [sp.name for sp in miss.profile().trace.root.children]
    assert names[0] == "plan_cache" and planning <= set(names)
    assert miss.profile().trace.root.attrs["plan_cache"] == "miss"
    names = [sp.name for sp in hit.profile().trace.root.children]
    assert names[0] == "plan_cache" and not planning & set(names)
    assert hit.profile().trace.root.children[0].kind == "phase"
    # the phase lands in the stage histogram, where plan_s reads it
    assert OM.STAGE_SECONDS.summary(stage="plan_cache")["count"] >= 2


def test_spans_annotate_any_capture_without_the_profile_dir(monkeypatch):
    """A span opens its TraceAnnotation whenever a trace is active —
    TPU_CYPHER_PROFILE_DIR unset — and the untraced path opens nothing."""
    from tpu_cypher.utils.config import PROFILE_DIR

    assert not PROFILE_DIR.get()
    opened = []

    class Stub:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            opened.append("exit")

    monkeypatch.setattr(OT, "_ANNOTATION", Stub)
    with OT.span("nobody", kind="operator") as sp:
        assert sp is OT.NULL_SPAN
    assert opened == []
    with OT.activate(OT.QueryTrace("query")):
        with OT.span("CsrExpandOp", kind="operator"):
            with OT.sync("expand"):
                pass
    assert opened == ["tpu_cypher:operator:CsrExpandOp",
                      "tpu_cypher:sync:expand", "exit", "exit"]


def test_sync_spans_wrap_the_reads_and_count_the_same_when_warm():
    s = CypherSession.tpu()
    g = _chain_graph(s)
    q = "MATCH (a:P)-[:K]->(b:P) RETURN a.id AS a, count(b) AS n ORDER BY a"
    g.cypher(q).records.collect()  # cold: builds, compiles

    def syncs():
        return sum(v for _, v in OT.HOST_SYNCS.items())

    moved = []
    for _ in range(2):
        before = syncs()
        r = g.cypher(q)
        r.records.collect()
        moved.append(syncs() - before)
    assert moved[0] == moved[1] > 0
    leaves = [sp for sp in r.profile().trace.spans() if sp.kind == "sync"]
    assert len(leaves) == moved[1]  # one span a read, one count a span
    assert not any(sp.children for sp in leaves)
    assert {sp.name for sp in leaves} <= {
        "expand", "agg", "compact", "to_host", "order", "distinct"}
    assert OT.HOST_SYNCS.value(site="to_host") > 0


def test_index_builds_are_spans_and_seconds_of_the_first_expand_only():
    from tpu_cypher.backend.tpu.graph_index import INDEX_BUILD_SECONDS

    def built():
        return {lbl["index"]: v for lbl, v in INDEX_BUILD_SECONDS.items()}

    s = CypherSession.tpu()
    g = _chain_graph(s)
    before = built()
    q = "MATCH (a:P)-[:K]->(b:P) RETURN count(*) AS c"
    first = g.cypher(q)
    first.records.collect()
    after_first = built()
    second = g.cypher(q)
    second.records.collect()
    assert after_first["csr"] > before.get("csr", 0.0)
    assert after_first["rel_scan"] > before.get("rel_scan", 0.0)
    assert built() == after_first  # a warm index never builds again
    builds = [sp for sp in first.profile().trace.spans() if sp.kind == "build"]
    assert {"index:csr", "index:rel_scan", "index:node_scan"} <= {
        sp.name for sp in builds}
    csr = next(sp for sp in builds if sp.name == "index:csr")
    assert csr.attrs["orientation"] == "forward" and csr.attrs["rows"] == 23
    assert not [sp for sp in second.profile().trace.spans()
                if sp.kind == "build"]


def test_a_load_from_the_persistent_cache_counts_seconds_and_no_compile():
    load = OM.REGISTRY.get("tpu_cypher_persistent_cache_load_seconds_total")
    before = (load.value(), bucketing.compile_snapshot())
    bucketing._on_event("/jax/compilation_cache/cache_hits")
    bucketing._on_event_duration("/jax/core/compile/backend_compile_duration", 0.25)
    assert load.value() == pytest.approx(before[0] + 0.25)
    after = bucketing.compile_snapshot()
    assert after["compiles"] == before[1]["compiles"]
    assert after["compile_seconds"] == before[1]["compile_seconds"]
    bucketing._on_event_duration("/jax/core/compile/backend_compile_duration", 0.5)
    assert bucketing.compile_snapshot()["compiles"] == after["compiles"] + 1
    assert load.value() == pytest.approx(before[0] + 0.25)


def test_recent_is_bounded_ordered_and_plain_data(monkeypatch):
    import collections

    monkeypatch.setattr(OT, "_RECENT", collections.deque(maxlen=3))
    for k in range(5):
        tr = OT.QueryTrace("request", kind="serve", id=f"r{k}")
        tr.root.add("queue_wait", "serve", tr.root.t0, tr.root.t0 + 2e-6)
        assert OT.finish(tr) is tr and tr.root.t1 is not None  # closed
    log = OT.recent()
    assert [t["root"]["attrs"]["id"] for t in log] == ["r2", "r3", "r4"]
    assert log[-1] == tr.to_dict()  # rendered when read, not when kept
    assert OT.RECENT_CAPACITY == 4096
    assert json.loads(json.dumps(log)) == log  # numbers and strings only
    child = log[0]["root"]["children"][0]
    assert child["kind"] == "serve" and child["seconds"] == 2e-6


# ---------------------------------------------------------------------------
# execution_log attribution
# ---------------------------------------------------------------------------


def test_execution_log_gains_duration_and_span_id():
    faults.set_spec("oom@expand:1")
    s = CypherSession.tpu()
    g = _chain_graph(s)
    r = g.cypher("MATCH (a:P)-[:K]->(b:P) RETURN count(*) AS c")
    r.records.collect()
    log = r.execution_log
    assert len(log) >= 2, log
    failed = log[0]
    assert failed["ok"] is False
    assert failed["duration_ms"] >= 0
    assert "span_id" in failed, failed
    # the span id resolves to an errored span in the trace
    by_id = {sp.span_id: sp for sp in r.profile(execute=False).trace.spans()}
    assert by_id[failed["span_id"]].status == "error"
    ok = log[-1]
    assert ok["ok"] is True and "duration_ms" in ok and "span_id" not in ok


# ---------------------------------------------------------------------------
# context-local isolation
# ---------------------------------------------------------------------------


def test_interleaved_lazy_results_do_not_cross_pollute():
    s = CypherSession.tpu()
    g = _chain_graph(s)
    r1 = g.cypher(THREE_HOP)
    r2 = g.cypher("MATCH (a:P) WHERE a.id >= 5 RETURN count(*) AS c")
    # pull in reverse creation order: r2's execution must land on r2's
    # trace even though r1's trace was created first
    r2.records.collect()
    r1.records.collect()
    names1 = {sp.name for sp in r1.profile(execute=False).trace.spans()}
    names2 = {sp.name for sp in r2.profile(execute=False).trace.spans()}
    assert "CsrExpandOp" in names1
    assert "CsrExpandOp" not in names2
    assert sum(1 for sp in r1.profile(execute=False).trace.spans()
               if sp.name == "execute") == 1
    assert sum(1 for sp in r2.profile(execute=False).trace.spans()
               if sp.name == "execute") == 1


def test_concurrent_queries_have_isolated_traces():
    s1, s2 = CypherSession.tpu(), CypherSession.tpu()
    g1, g2 = _chain_graph(s1), _chain_graph(s2, n=8)
    out = {}

    def run(key, g, q):
        r = g.cypher(q)
        r.records.collect()
        out[key] = r.profile(execute=False)

    t1 = threading.Thread(target=run, args=("a", g1, THREE_HOP))
    t2 = threading.Thread(
        target=run, args=("b", g2, "MATCH (a:P) RETURN count(*) AS c")
    )
    t1.start(); t2.start(); t1.join(); t2.join()
    spans_a = {sp.name for sp in out["a"].trace.spans()}
    spans_b = {sp.name for sp in out["b"].trace.spans()}
    assert "CsrExpandOp" in spans_a
    assert "CsrExpandOp" not in spans_b
    for prof in out.values():
        assert [c.name for c in prof.trace.root.children].count("execute") == 1


def test_metric_scopes_are_context_local_and_nested():
    reg = OM.MetricsRegistry()
    c = reg.counter("t_events_total", labels=("reason",))
    with reg.scope() as outer:
        c.inc(reason="x")
        with reg.scope() as inner:
            c.inc(reason="y")
            # a foreign thread's increments must not land in our scopes
            t = threading.Thread(target=lambda: c.inc(reason="thread"))
            t.start(); t.join()
        c.inc(reason="x")
    assert outer.label_counts("t_events_total", "reason") == {"x": 2.0, "y": 1.0}
    assert inner.label_counts("t_events_total", "reason") == {"y": 1.0}
    # the global aggregate saw everything, including the thread
    assert int(c.value(reason="thread")) == 1


# ---------------------------------------------------------------------------
# asyncio isolation (the serving layer's concurrency model: interleaved
# coroutines + fresh-context worker threads, tpu_cypher/serve/)
# ---------------------------------------------------------------------------


def test_asyncio_tasks_do_not_share_request_deadlines():
    """Each asyncio task snapshots the context at creation: a request
    deadline opened in one coroutine must be invisible to interleaved
    neighbors, and concurrent scopes keep their own values."""
    import asyncio

    async def scoped(seconds, settle):
        with guard.request_deadline(seconds):
            await asyncio.sleep(settle)  # others interleave while open
            return guard.request_deadline_s()

    async def unscoped():
        await asyncio.sleep(0.005)
        return guard.request_deadline_s()

    async def main():
        return await asyncio.gather(
            scoped(5.0, 0.02), unscoped(), scoped(0.5, 0.01)
        )

    a, none, c = asyncio.run(main())
    assert a == 5.0 and none is None and c == 0.5


def test_asyncio_tasks_have_private_fault_schedules():
    """Two chaos-scoped coroutines with the SAME ``:1`` spec must EACH see
    their own first-invocation window fire (private occurrence counters),
    while an interleaved clean query stays on the device rung."""
    import asyncio

    s = CypherSession.tpu()
    g = _chain_graph(s)
    q = "MATCH (a:P)-[:K]->(b:P) RETURN count(*) AS c"

    async def run_query(spec):
        with faults.scoped_spec(spec):
            await asyncio.sleep(0.01)  # interleave while the scope is open
            r = g.cypher(q)
            r.records.collect()
            return [e["rung"] for e in r.execution_log]

    async def main():
        return await asyncio.gather(
            run_query("oom@expand:1"), run_query(None),
            run_query("oom@expand:1"),
        )

    chaotic1, clean, chaotic2 = asyncio.run(main())
    assert chaotic1[0] == guard.RUNG_DEVICE
    assert len(chaotic1) > 1  # the injected fault degraded the ladder
    # a SHARED counter would put the second scope's first invocation at
    # n=2, outside its :1 window — private counters fire both
    assert chaotic2 == chaotic1
    assert clean == [guard.RUNG_DEVICE]


def test_asyncio_tasks_have_isolated_metric_scopes():
    import asyncio

    reg = OM.MetricsRegistry()
    c = reg.counter("t_async_events_total", labels=("who",))

    async def worker(who, n):
        with reg.scope() as sc:
            for _ in range(n):
                c.inc(who=who)
                await asyncio.sleep(0)  # yield between increments
            return dict(sc.label_counts("t_async_events_total", "who"))

    async def main():
        return await asyncio.gather(worker("a", 3), worker("b", 5))

    a, b = asyncio.run(main())
    assert a == {"a": 3.0}
    assert b == {"b": 5.0}


def test_asyncio_fallback_scopes_do_not_leak():
    import asyncio

    from tpu_cypher.backend.tpu.table import FALLBACK_COUNTER

    async def worker(record):
        with FALLBACK_COUNTER.scope() as events:
            await asyncio.sleep(0.005)
            if record:
                FALLBACK_COUNTER.record("t-async-leak-probe")
            await asyncio.sleep(0.005)
            return dict(events)

    async def main():
        return await asyncio.gather(worker(True), worker(False))

    recorded, silent = asyncio.run(main())
    assert recorded.get("t-async-leak-probe") == 1
    assert "t-async-leak-probe" not in silent


def test_asyncio_fresh_context_execution_isolates_span_trees():
    """The serving layer's execution primitive (``SessionPool.run``: a
    worker thread inside a FRESH contextvars.Context) keeps concurrent
    queries' span trees disjoint — driven from one event loop, as the
    server drives it."""
    import asyncio

    from tpu_cypher.serve import SessionPool

    s = CypherSession.tpu()
    g = _chain_graph(s)
    pool = SessionPool(s, workers=4)

    def exec_one(q):
        r = g.cypher(q)
        r.records.collect()
        return r

    async def main():
        return await asyncio.gather(
            *[pool.run(lambda q=q: exec_one(q))
              for q in (THREE_HOP, "MATCH (a:P) RETURN count(*) AS c") * 2]
        )

    try:
        results = asyncio.run(main())
    finally:
        pool.close()
    for r in results:
        tree = r.profile(execute=False).trace
        assert [ch.name for ch in tree.root.children].count("execute") == 1
    hop_names = {sp.name for sp in results[0].profile(execute=False).trace.spans()}
    cnt_names = {sp.name for sp in results[1].profile(execute=False).trace.spans()}
    assert "CsrExpandOp" in hop_names
    assert "CsrExpandOp" not in cnt_names


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------


def test_label_cardinality_cap_collapses_to_overflow():
    reg = OM.MetricsRegistry()
    c = reg.counter("t_wild_total", labels=("q",))
    for i in range(OM.LABEL_CARDINALITY_CAP + 50):
        c.inc(q=f"query-{i}")
    series = c.items()
    assert len(series) == OM.LABEL_CARDINALITY_CAP + 1
    overflow = c.value(q=OM.OVERFLOW_LABEL)
    assert int(overflow) == 50  # everything past the cap collapsed
    total = sum(v for _, v in series)
    assert int(total) == OM.LABEL_CARDINALITY_CAP + 50


def test_registry_reregistration_is_idempotent_and_typed():
    reg = OM.MetricsRegistry()
    a = reg.counter("t_same_total", labels=("k",))
    assert reg.counter("t_same_total", labels=("k",)) is a
    with pytest.raises(OM.MetricError):
        reg.gauge("t_same_total", labels=("k",))
    with pytest.raises(OM.MetricError):
        reg.counter("t_same_total", labels=("other",))
    with pytest.raises(OM.MetricError):
        a.inc(wrong_label=1)


def test_histogram_summary_p50_p95_max():
    reg = OM.MetricsRegistry()
    h = reg.histogram("t_lat_seconds", labels=("stage",))
    for v in range(1, 101):
        h.observe(float(v), stage="parse")
    s = h.summary(stage="parse")
    assert s["count"] == 100 and s["max"] == 100.0 and s["min"] == 1.0
    assert 45.0 <= s["p50"] <= 55.0
    assert 90.0 <= s["p95"] <= 100.0
    # untouched series reads as zeros, not KeyError
    assert h.summary(stage="never")["count"] == 0


def test_legacy_counters_served_by_registry():
    """All four legacy counters answer from the unified registry while the
    legacy read paths stay green."""
    from tpu_cypher.backend.tpu.pallas import dispatch
    from tpu_cypher.backend.tpu.table import FALLBACK_COUNTER

    # 1. compile counter
    snap = bucketing.compile_snapshot()
    assert snap["compiles"] == int(
        OM.REGISTRY.get("tpu_cypher_xla_compiles_total").value()
    )
    # 2. fallback counter
    FALLBACK_COUNTER.record("test:obs")
    assert FALLBACK_COUNTER.snapshot().get("test:obs", 0) >= 1
    assert OM.REGISTRY.get("tpu_cypher_fallbacks_total").value(
        reason="test:obs"
    ) >= 1
    # 3. pallas use counters (zeros pre-seeded per registered kernel)
    uc = dispatch.use_counts()
    assert set(uc) >= set(dispatch.registry())
    for v in uc.values():
        assert set(v) == {"pallas", "fallback"}
    # 4. fault-site hits
    faults.reset_counters()
    faults.fault_point("join")
    assert faults.counters() == {"join": 1}
    assert int(
        OM.REGISTRY.get("tpu_cypher_fault_site_hits_total").value(site="join")
    ) == 1
    faults.reset_counters()


# ---------------------------------------------------------------------------
# export formats
# ---------------------------------------------------------------------------


def test_prometheus_text_golden():
    reg = OM.MetricsRegistry()
    c = reg.counter("t_requests_total", "requests served", labels=("verb",))
    c.inc(3, verb="get")
    c.inc(verb='po"st')
    g = reg.gauge("t_depth", "queue depth")
    g.set(2.5)
    h = reg.histogram("t_secs", "latency", labels=("stage",))
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v, stage="s")
    assert reg.prometheus_text() == (
        "# HELP t_depth queue depth\n"
        "# TYPE t_depth gauge\n"
        "t_depth 2.5\n"
        "# HELP t_requests_total requests served\n"
        "# TYPE t_requests_total counter\n"
        "t_requests_total{verb=\"get\"} 3\n"
        "t_requests_total{verb=\"po\\\"st\"} 1\n"
        "# HELP t_secs latency\n"
        "# TYPE t_secs summary\n"
        "t_secs{quantile=\"0.5\",stage=\"s\"} 2\n"
        "t_secs{quantile=\"0.95\",stage=\"s\"} 3\n"
        "t_secs_sum{stage=\"s\"} 10\n"
        "t_secs_count{stage=\"s\"} 4\n"
    )


def test_session_metrics_text_covers_the_engine():
    s = CypherSession.tpu()
    g = _chain_graph(s)
    g.cypher(THREE_HOP).records.collect()
    text = s.metrics_text()
    for name in (
        "tpu_cypher_xla_compiles_total",
        "tpu_cypher_fault_site_hits_total",
        "tpu_cypher_ladder_activations_total",
        "tpu_cypher_stage_seconds",
        "tpu_cypher_pallas_launch_total",
        "tpu_cypher_mxu_tier_total",
        "tpu_cypher_fallbacks_total",
    ):
        assert f"# TYPE {name}" in text, name


def test_jsonl_sink_writes_schema_versioned_events(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    OM.METRICS_FILE.set(path)
    try:
        s = CypherSession.tpu()
        g = _chain_graph(s)
        g.cypher(THREE_HOP).records.collect()
    finally:
        OM.METRICS_FILE.reset()
    lines = [json.loads(l) for l in open(path) if l.strip()]
    assert lines, "no JSON-lines events written"
    ev = lines[-1]
    assert ev["v"] == OM.EVENT_SCHEMA_VERSION
    assert ev["event"] == "query" and ev["ok"] is True
    assert "execute" in ev["phases"]
    assert ev["execution_log"][-1]["ok"] is True
    assert ev["compile_stats"] is not None
    assert isinstance(ev["metrics"], dict)


# ---------------------------------------------------------------------------
# lint guards: everything emits through obs — the ``obs-emission`` rule of
# tpu_cypher.analysis (the ad-hoc AST walkers that used to live here are
# now the rule's implementation; lint time and test time enforce the SAME
# predicate)
# ---------------------------------------------------------------------------


def test_ast_guard_no_stray_module_global_counters():
    """No module-global ``NAME = {"k": 0, ...}`` counter dicts anywhere in
    the engine — the pattern the four pre-obs counters used. Counters
    belong to the registry."""
    from tpu_cypher import analysis

    report = analysis.check_engine(rules=["obs-emission"])
    assert report.clean, report.render_text()


def test_ast_guard_fault_sites_emit_through_obs():
    """``fault_point`` must count every site invocation through a registry
    counter (FAULT_SITE_HITS) — the obs-emission chokepoint check over
    runtime/faults.py."""
    from tpu_cypher import analysis

    report = analysis.run_paths(
        [os.path.join(PKG, "runtime", "faults.py")], rules=["obs-emission"]
    )
    assert report.clean, report.render_text()


def test_ast_guard_kernel_dispatch_emits_through_obs():
    """Every ``pl.pallas_call`` reaches the engine through a registered
    dispatch impl (guarded in test_pallas_dispatch) and dispatch's use
    counter is the obs registry, with ``launch`` opening a kernel span —
    together: no kernel launch escapes obs."""
    from tpu_cypher import analysis

    report = analysis.run_paths(
        [os.path.join(PKG, "backend", "tpu", "pallas", "dispatch.py")],
        rules=["obs-emission"],
    )
    assert report.clean, report.render_text()

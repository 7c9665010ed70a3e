"""The multi-tenant query server (``tpu_cypher/serve/``): admission
scheduling, micro-batching, isolation, and the observability surfaces.

Layers of coverage:

* **scheduler/batcher units** — pure asyncio, no engine: cost ordering,
  tenant fairness, quotas, queued-deadline expiry, coalescing semantics.
* **server end-to-end over real sockets** — submit/stream/cancel on the
  JSON protocol, per-query results byte-identical to serial execution,
  same-bucket bursts sharing one dispatch, chaos queries degrading
  without contaminating clean neighbors.
* **result cache** — zero-dispatch hits byte-identical to the original
  execution, fingerprint invalidation, LRU byte budget, and the
  chaos/deadline exclusions.
* **cursor streaming** — pull-based pages under the credit window,
  early close, backpressure isolation, and a subprocess pin that a
  >1M-row result streams under a fixed host-memory ceiling.
* **HTTP goldens** — ``GET /metrics`` byte-identical to the in-process
  ``session.metrics_text()``; ``GET /queries/<id>`` serving the span
  tree JSON; ``GET /cache`` + POST-only ``/cache/flush`` (405 on GET).
"""

import asyncio
import json
import os
import subprocess
import sys

import pytest

from tpu_cypher.errors import QueryTimeout
from tpu_cypher.relational.session import CypherSession
from tpu_cypher.serve import (
    AdmissionScheduler,
    BatchWindow,
    QueryServer,
    ResultCache,
    batch_key,
    estimate_cost_bytes,
)

# ---------------------------------------------------------------------------
# shared engine fixtures
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def session():
    return CypherSession.tpu()


@pytest.fixture(scope="module")
def graph(session):
    n = 16
    parts = [f"(n{i}:P {{id: {i}}})" for i in range(n)]
    parts += [f"(n{i})-[:K]->(n{(i + 1) % n})" for i in range(n)]
    parts += [f"(n{i})-[:K]->(n{(i + 5) % n})" for i in range(n)]
    return session.create_graph_from_create_query("CREATE " + ", ".join(parts))


COUNT_Q = "MATCH (a:P) RETURN count(a) AS n"
HOP_Q = "MATCH (a:P)-[:K]->(b:P) RETURN count(b) AS n"
ROWS_Q = "MATCH (a:P {id: 3})-[:K]->(b:P) RETURN b.id AS id ORDER BY id"


async def _client(host, port, lines, want=None):
    """Drive the JSON protocol: send every line, read until each submit
    reaches a terminal message. Returns the full message list."""
    reader, writer = await asyncio.open_connection(host, port)
    for line in lines:
        writer.write((json.dumps(line) + "\n").encode())
    await writer.drain()
    if want is None:
        want = sum(1 for l in lines if l.get("op") == "submit")
    out, done = [], 0
    while done < want:
        raw = await asyncio.wait_for(reader.readline(), 30)
        if not raw:
            break
        msg = json.loads(raw)
        out.append(msg)
        if msg.get("type") in ("done", "error", "cancelled"):
            done += 1
    writer.close()
    return out


async def _http(host, port, path, method="GET"):
    reader, writer = await asyncio.open_connection(host, port)
    writer.write(f"{method} {path} HTTP/1.1\r\nHost: t\r\n\r\n".encode())
    await writer.drain()
    data = await reader.read()
    writer.close()
    head, _, body = data.partition(b"\r\n\r\n")
    return head.decode().split("\r\n")[0], body


def _terminals(msgs, typ="done"):
    return {m["id"]: m for m in msgs if m["type"] == typ}


def _rows_of(msgs, qid):
    rows = []
    for m in msgs:
        if m["type"] == "rows" and m["id"] == qid:
            rows.extend(m["rows"])
    return rows


# ---------------------------------------------------------------------------
# scheduler units (no engine)
# ---------------------------------------------------------------------------


def test_scheduler_cost_ordering():
    """With one slot, waiters are granted cheapest-padded-cost first."""

    async def run():
        s = AdmissionScheduler(max_concurrent=1)
        await s.acquire(10, "t")  # occupy the slot
        order = []

        async def waiter(name, cost):
            await s.acquire(cost, "t")
            order.append(name)
            s.release("t")

        tasks = [
            asyncio.ensure_future(waiter("big", 4096)),
            asyncio.ensure_future(waiter("small", 64)),
            asyncio.ensure_future(waiter("mid", 512)),
        ]
        await asyncio.sleep(0.01)  # all queued
        s.release("t")
        await asyncio.gather(*tasks)
        return order

    assert asyncio.run(run()) == ["small", "mid", "big"]


def test_scheduler_tenant_fairness():
    """The next slot goes to the tenant with the fewest in flight, even
    when the hog's queries are cheaper."""

    async def run():
        s = AdmissionScheduler(max_concurrent=2)
        await s.acquire(10, "hog")
        await s.acquire(10, "hog")
        order = []

        async def waiter(name, tenant, cost):
            await s.acquire(cost, tenant)
            order.append(name)

        tasks = [
            asyncio.ensure_future(waiter("hog3", "hog", 1)),
            asyncio.ensure_future(waiter("guest", "guest", 1000)),
        ]
        await asyncio.sleep(0.01)
        s.release("hog")
        await asyncio.sleep(0.01)
        s.release("hog")
        await asyncio.gather(*tasks)
        return order

    assert asyncio.run(run()) == ["guest", "hog3"]


def test_scheduler_tenant_quota():
    """A quota caps one tenant's in-flight count outright: its extra
    queries wait even while slots sit free."""

    async def run():
        s = AdmissionScheduler(max_concurrent=4, tenant_quota=1)
        await s.acquire(1, "t1")
        task = asyncio.ensure_future(s.acquire(1, "t1"))
        await asyncio.sleep(0.01)
        assert not task.done() and s.running == 1  # slot free, still queued
        await s.acquire(1, "t2")  # another tenant sails through
        s.release("t1")
        await asyncio.wait_for(task, 1)
        return s.running

    assert asyncio.run(run()) == 2


def test_scheduler_queued_deadline_times_out_typed():
    async def run():
        s = AdmissionScheduler(max_concurrent=1)
        await s.acquire(1, "t")
        loop = asyncio.get_running_loop()
        with pytest.raises(QueryTimeout):
            await s.acquire(1, "t", deadline_at=loop.time() + 0.02)
        # the expired waiter left no ghost entry; a release still pumps
        s.release("t")
        await s.acquire(1, "t")
        return s.queued

    assert asyncio.run(run()) == 0


def test_scheduler_expired_deadline_rejected_before_slot():
    async def run():
        s = AdmissionScheduler(max_concurrent=1)
        with pytest.raises(QueryTimeout):
            await s.acquire(1, "t", deadline_at=0.0)
        return s.running

    assert asyncio.run(run()) == 0


def test_estimate_cost_bytes_orders_by_shape(graph):
    """More pattern fan-out -> strictly larger padded estimate; estimates
    ride the bucket lattice (so they are stable within a bucket)."""
    c1 = estimate_cost_bytes(graph, COUNT_Q)
    c2 = estimate_cost_bytes(graph, HOP_Q)
    c3 = estimate_cost_bytes(graph, "MATCH (a)-[:K]->()-[:K]->()-[:K]->(d) RETURN d")
    assert 0 < c1 < c2 < c3


# ---------------------------------------------------------------------------
# batcher units
# ---------------------------------------------------------------------------


def test_batch_key_none_for_uncacheable(session, graph):
    # catalog-interacting statements never batch (no plan-cache key)
    assert batch_key(session, "CREATE GRAPH g { RETURN 1 }", graph, {}) is None
    # table-valued parameters never batch either
    assert batch_key(session, COUNT_Q, graph, {"rows": [{"a": 1}]}) is None


def test_batch_key_separates_param_values(session, graph):
    q = "MATCH (a:P {id: $i}) RETURN a.id AS id"
    k1 = batch_key(session, q, graph, {"i": 1})
    k2 = batch_key(session, q, graph, {"i": 2})
    k1b = batch_key(session, q, graph, {"i": 1})
    assert k1 is not None and k1 == k1b and k1 != k2


def test_batch_window_coalesces_until_sealed():
    async def run():
        w = BatchWindow(window_ms=50)
        b, lead = w.lead_or_join("k", "q1")
        assert lead
        b2, lead2 = w.lead_or_join("k", "q2")
        assert b2 is b and not lead2
        w.close(b)
        # post-seal arrivals start a NEW batch
        b3, lead3 = w.lead_or_join("k", "q3")
        assert lead3 and b3 is not b
        w.publish(b, result="r")
        assert b.result == "r" and b.done.is_set()
        return b.size

    assert asyncio.run(run()) == 2


def test_batch_window_zero_disables_coalescing():
    async def run():
        w = BatchWindow(window_ms=0)
        b1, l1 = w.lead_or_join("k", "q1")
        b2, l2 = w.lead_or_join("k", "q2")
        return l1 and l2 and b1 is not b2

    assert asyncio.run(run()) is True


# ---------------------------------------------------------------------------
# server end-to-end (real sockets)
# ---------------------------------------------------------------------------


def _serve(session, graph, **kw):
    """Context helper: a started server with the module graph mounted."""
    srv = QueryServer(session, port=0, **kw)
    srv.register_graph("g", graph)
    return srv


def test_server_submit_stream_done(session, graph):
    async def run():
        async with _serve(session, graph) as srv:
            msgs = await _client(srv.host, srv.port, [
                {"op": "submit", "id": "a", "graph": "g", "query": ROWS_Q},
            ])
        return msgs

    msgs = asyncio.run(run())
    assert msgs[0] == {"type": "accepted", "id": "a"}
    done = _terminals(msgs)["a"]
    assert done["rungs"] == ["device"] and done["degraded"] is False
    assert _rows_of(msgs, "a") == [{"id": 4}, {"id": 8}]


def test_server_results_identical_to_serial(session, graph):
    """Every served row page must reproduce serial in-process execution
    byte-for-byte (JSON wire form vs the same encoding applied locally)."""
    from tpu_cypher.serve.server import _encode_rows

    queries = [COUNT_Q, HOP_Q, ROWS_Q,
               "MATCH (a:P) RETURN a.id AS id ORDER BY id LIMIT 5"]

    async def run():
        async with _serve(session, graph) as srv:
            return await _client(srv.host, srv.port, [
                {"op": "submit", "id": f"q{i}", "graph": "g", "query": q}
                for i, q in enumerate(queries)
            ])

    msgs = asyncio.run(run())
    for i, q in enumerate(queries):
        records = graph.cypher(q).records
        want = _encode_rows(records.collect(), records.columns)
        got = _rows_of(msgs, f"q{i}")
        assert json.dumps(got, sort_keys=True) == json.dumps(want, sort_keys=True), q


def test_server_burst_shares_one_dispatch(session, graph):
    """Same-plan same-params burst inside the window -> ONE dispatch,
    every client tagged with the batch size and the leader's id."""
    from tpu_cypher.serve.batching import DISPATCHES

    async def run():
        async with _serve(session, graph, batch_window_ms=50) as srv:
            before = sum(int(v) for _, v in DISPATCHES.items())
            msgs = await _client(srv.host, srv.port, [
                {"op": "submit", "id": f"b{i}", "graph": "g", "query": HOP_Q}
                for i in range(4)
            ])
            after = sum(int(v) for _, v in DISPATCHES.items())
        return msgs, after - before

    msgs, dispatches = asyncio.run(run())
    dones = _terminals(msgs)
    assert len(dones) == 4
    assert {d["batched"] for d in dones.values()} == {4}
    assert len({d["batch_leader"] for d in dones.values()}) == 1
    assert dispatches == 1
    # all four clients got identical rows
    pages = [json.dumps(_rows_of(msgs, f"b{i}")) for i in range(4)]
    assert len(set(pages)) == 1


def test_server_chaos_scoped_per_client(session, graph):
    """A chaos-mode query degrades down the ladder; an interleaved clean
    query of the SAME shape stays on the device rung — fault schedules are
    context-local to the client that asked for them."""

    async def run():
        async with _serve(session, graph, batch_window_ms=10) as srv:
            return await _client(srv.host, srv.port, [
                {"op": "submit", "id": "chaos", "graph": "g", "query": HOP_Q,
                 "faults": "oom@expand:*"},
                {"op": "submit", "id": "clean", "graph": "g", "query": HOP_Q},
            ])

    msgs = asyncio.run(run())
    dones = _terminals(msgs)
    assert dones["chaos"]["degraded"] is True
    assert dones["chaos"]["rungs"][0] == "device"
    assert dones["chaos"]["rungs"][-1] == "host-oracle"
    assert dones["clean"]["rungs"] == ["device"]
    # degraded or not, both clients got the same rows
    assert _rows_of(msgs, "chaos") == _rows_of(msgs, "clean")


def test_server_expired_deadline_is_typed_error(session, graph):
    async def run():
        async with _serve(session, graph) as srv:
            return await _client(srv.host, srv.port, [
                {"op": "submit", "id": "t", "graph": "g", "query": COUNT_Q,
                 "deadline_s": 1e-6},
            ])

    msgs = asyncio.run(run())
    err = _terminals(msgs, "error")["t"]
    assert err["error"] == "QueryTimeout"


def test_server_cancel_queued_query(session, graph):
    """Cancel while queued: the query never dispatches; the client gets a
    terminal 'cancelled' message."""

    async def run():
        async with _serve(session, graph, max_concurrent=1) as srv:
            # hold the server's only slot so the victim must queue
            await srv.scheduler.acquire(1, "holder")
            reader, writer = await asyncio.open_connection(srv.host, srv.port)

            async def send(obj):
                writer.write((json.dumps(obj) + "\n").encode())
                await writer.drain()

            async def recv():
                return json.loads(await asyncio.wait_for(reader.readline(), 30))

            await send({"op": "submit", "id": "victim", "graph": "g",
                        "query": COUNT_Q})
            assert (await recv())["type"] == "accepted"
            await asyncio.sleep(0.05)  # window elapses; victim queues
            await send({"op": "cancel", "id": "victim"})
            terminal = None
            while terminal is None:
                m = await recv()
                if m.get("type") in ("done", "error", "cancelled"):
                    terminal = m
            srv.scheduler.release("holder")
            # the scheduler is healthy afterwards: a fresh query completes
            await send({"op": "submit", "id": "after", "graph": "g",
                        "query": COUNT_Q})
            after = None
            while after is None:
                m = await recv()
                if m.get("type") in ("done", "error", "cancelled"):
                    after = m
            writer.close()
        return terminal, after

    terminal, after = asyncio.run(run())
    assert terminal == {"type": "cancelled", "id": "victim"}
    assert after["type"] == "done" and after["id"] == "after"


def test_server_protocol_errors(session, graph):
    async def run():
        async with _serve(session, graph) as srv:
            return await _client(srv.host, srv.port, [
                {"op": "submit", "id": "g1", "graph": "nope", "query": "RETURN 1"},
                {"op": "submit", "id": "g2", "graph": "g", "query": "MATCH ("},
                {"op": "nonsense", "id": "g3"},
            ], want=3)

    msgs = asyncio.run(run())
    errs = _terminals(msgs, "error")
    assert errs["g1"]["error"] == "UnknownGraph"
    assert errs["g2"]["error"]  # typed planner error, surfaced not swallowed
    assert errs["g3"]["error"] == "ProtocolError"


# ---------------------------------------------------------------------------
# HTTP observability surface
# ---------------------------------------------------------------------------


def test_http_metrics_golden_matches_in_process(session, graph):
    """GET /metrics must serve ``session.metrics_text()`` VERBATIM — the
    scrape surface and the in-process surface cannot drift."""

    async def run():
        async with _serve(session, graph) as srv:
            # run a query first so the body is non-trivial
            await _client(srv.host, srv.port, [
                {"op": "submit", "id": "m", "graph": "g", "query": COUNT_Q},
            ])
            status, body = await _http(srv.host, srv.port, "/metrics")
            golden = session.metrics_text()
        return status, body, golden

    status, body, golden = asyncio.run(run())
    assert status.endswith("200 OK")
    assert body.decode() == golden
    assert "tpu_cypher_serve_queries_total" in golden


def test_http_query_record_serves_profile(session, graph):
    async def run():
        async with _serve(session, graph) as srv:
            await _client(srv.host, srv.port, [
                {"op": "submit", "id": "p1", "graph": "g", "query": HOP_Q},
            ])
            ok = await _http(srv.host, srv.port, "/queries/p1")
            missing = await _http(srv.host, srv.port, "/queries/zzz")
        return ok, missing

    (status, body), (mstatus, _) = asyncio.run(run())
    assert status.endswith("200 OK")
    rec = json.loads(body)
    assert rec["status"] == "done" and rec["rungs"] == ["device"]
    assert rec["batched"] == 1 and rec["tenant"] == "default"
    # the span tree rode along (a plan-cache hit skips the planning
    # phases, so only the execution-side spans are guaranteed)
    names = json.dumps(rec["profile"])
    for phase in ("execute", "collect"):
        assert phase in names
    assert mstatus.endswith("404 Not Found")


# ---------------------------------------------------------------------------
# one span tree per served request (obs/trace.py): the serving stages as
# spans of kind "serve", the engine's tree under ``dispatch``
# ---------------------------------------------------------------------------


def _flat(node, out=None):
    out = [] if out is None else out
    out.append(node)
    for c in node.get("children", ()):
        _flat(c, out)
    return out


def _assert_nested(node, parent=None, eps=2e-6):
    lo, hi = node["start_s"], node["start_s"] + node["seconds"]
    if parent is not None:
        plo, phi = parent["start_s"], parent["start_s"] + parent["seconds"]
        assert plo - eps <= lo and hi <= phi + eps, (node["name"], parent["name"])
    for c in node.get("children", ()):
        _assert_nested(c, node)


def test_served_request_is_one_tree_from_request_to_the_sync_leaves(
        session, graph):
    from tpu_cypher.obs import trace as OT

    async def run():
        async with _serve(session, graph, cache_bytes=0) as srv:
            for qid in ("t0", "t1"):  # the second is a plan-cache hit
                await _client(srv.host, srv.port, [
                    {"op": "submit", "id": qid, "graph": "g", "query": HOP_Q,
                     "tenant": "acme"},
                ])
            _, body = await _http(srv.host, srv.port, "/queries/t1")
        return json.loads(body)

    rec = asyncio.run(run())
    prof = rec["profile"]
    assert prof == OT.recent()[-1]  # /queries/<id> renders the kept tree
    assert prof["schema_version"] == 2
    root = prof["root"]
    assert (root["name"], root["kind"]) == ("request", "serve")
    assert root["attrs"] == {"id": "t1", "graph": "g", "tenant": "acme"}
    assert [c["name"] for c in root["children"]] == [
        "cache", "batch_window", "queue_wait", "dispatch", "serialize",
        "demux"]
    assert all(c["kind"] == "serve" for c in root["children"])
    dispatch = root["children"][3]
    assert [(c["name"], c["kind"]) for c in dispatch["children"]] == [
        ("route", "serve"), ("engine", "query"), ("route", "serve")]
    assert [c["attrs"]["hop"] for c in dispatch["children"][::2]] == [
        "out", "back"]
    engine = dispatch["children"][1]
    assert engine["attrs"]["plan_cache"] == "hit"
    assert [c["name"] for c in engine["children"]] == [
        "plan_cache", "execute", "feedback", "collect", "encode"]
    assert all(c["kind"] == "phase" for c in engine["children"])
    spans = _flat(root)
    assert all("start_s" in sp for sp in spans)
    assert [sp for sp in spans if sp["kind"] == "sync"]  # down to the reads
    assert [sp for sp in spans if sp["kind"] == "operator"]
    _assert_nested(root)
    assert rec["status"] == "done" and rec["rungs"] == ["device"]


def test_stages_are_the_sums_of_the_serve_spans(session, graph):
    """One measurement, two views: ``QueryServer.stages[k]`` is the sum of
    the ``serve`` spans named ``k`` over every request's tree."""

    async def run():
        async with _serve(session, graph, cache_bytes=0) as srv:
            ids = [f"s{i}" for i in range(4)]
            for qid, q in zip(ids, (COUNT_Q, HOP_Q, ROWS_Q, HOP_Q)):
                await _client(srv.host, srv.port, [
                    {"op": "submit", "id": qid, "graph": "g", "query": q},
                ])
            await _stream_client(srv.host, srv.port, {
                "op": "submit", "id": "s4", "graph": "g", "query": ROWS_Q,
                "stream": True,
            })
            return dict(srv.stages), [
                srv._records[q]["profile"].to_dict() for q in ids + ["s4"]]

    stages, profiles = asyncio.run(run())
    sums = {}
    for prof in profiles:
        for sp in _flat(prof["root"])[1:]:
            if sp["kind"] == "serve":
                sums[sp["name"]] = sums.get(sp["name"], 0.0) + sp["seconds"]
    assert set(stages) == set(sums) == {
        "cache", "batch_window", "queue_wait", "dispatch", "route",
        "serialize", "demux"}
    for name, seconds in stages.items():
        # spans render rounded to the microsecond
        assert seconds == pytest.approx(sums[name], abs=2e-5), name
    assert stages["route"] < stages["dispatch"]


@pytest.mark.parametrize("path", ["error", "cancelled", "cached"])
def test_every_terminal_path_closes_its_tree(session, graph, path):
    from tpu_cypher.obs import trace as OT

    async def run():
        kw = {"max_concurrent": 1} if path == "cancelled" else {}
        async with _serve(session, graph, **kw) as srv:
            if path == "error":
                await _client(srv.host, srv.port, [
                    {"op": "submit", "id": "x", "graph": "g",
                     "query": "MATCH (a:P RETURN a"},
                ])
            elif path == "cached":
                for qid in ("warm", "x"):
                    await _client(srv.host, srv.port, [
                        {"op": "submit", "id": qid, "graph": "g",
                         "query": ROWS_Q},
                    ])
            else:
                await srv.scheduler.acquire(1, "holder")
                reader, writer = await asyncio.open_connection(
                    srv.host, srv.port)
                for msg in ({"op": "submit", "id": "x", "graph": "g",
                             "query": COUNT_Q},):
                    writer.write((json.dumps(msg) + "\n").encode())
                await writer.drain()
                await asyncio.sleep(0.05)  # the window elapses; x queues
                writer.write((json.dumps({"op": "cancel", "id": "x"})
                              + "\n").encode())
                await writer.drain()
                while json.loads(await asyncio.wait_for(
                        reader.readline(), 30)).get("type") != "cancelled":
                    pass
                srv.scheduler.release("holder")
                writer.close()
            _, body = await _http(srv.host, srv.port, "/queries/x")
        return json.loads(body)

    rec = asyncio.run(run())
    root = rec["profile"]["root"]
    assert rec["profile"] == OT.recent()[-1]
    assert root["name"] == "request" and root["attrs"]["id"] == "x"
    assert root["seconds"] > 0  # closed in _terminal, whatever the path
    names = [c["name"] for c in root["children"]]
    if path == "error":
        assert rec["status"] == "error" and root["status"] == "error"
        assert names[:3] == ["cache", "batch_window", "queue_wait"]
    elif path == "cancelled":
        assert rec["status"] == "cancelled" and root["status"] == "cancelled"
        assert "dispatch" not in names  # it never reached the engine
    else:
        assert rec["cached"] is True and root["status"] == "ok"
        assert names == ["cache", "serialize", "demux"]
        assert root["children"][0]["attrs"] == {"hit": True}
    _assert_nested(root)


def test_batch_follower_hangs_the_shared_execution_under_its_wait(
        session, graph):
    async def run():
        async with _serve(session, graph, batch_window_ms=50,
                          cache_bytes=0) as srv:
            msgs = await _client(srv.host, srv.port, [
                {"op": "submit", "id": f"f{i}", "graph": "g", "query": HOP_Q}
                for i in range(3)
            ])
            return msgs, {q: srv._records[q]["profile"].to_dict()
                          for q in ("f0", "f1", "f2")}

    msgs, recs = asyncio.run(run())
    leader = _terminals(msgs)["f0"]["batch_leader"]
    follower = next(q for q in recs if q != leader)
    names = [c["name"] for c in recs[leader]["root"]["children"]]
    assert "batch_window" in names and "dispatch" in names
    froot = recs[follower]["root"]
    wait = next(c for c in froot["children"] if c["name"] == "batch_wait")
    assert wait["attrs"] == {"leader": leader}
    shared = wait["children"][0]
    assert shared["name"] == "dispatch"
    assert "engine" in [c["name"] for c in shared["children"]]
    assert "dispatch" not in [c["name"] for c in froot["children"]]
    _assert_nested(froot)


def test_streamed_pages_keep_a_bounded_number_of_spans(session, graph,
                                                       monkeypatch):
    from tpu_cypher.serve import server as SRV

    monkeypatch.setattr(SRV, "PAGE_ROWS", 1)
    monkeypatch.setattr(SRV, "_PAGE_SPANS_MAX", 3)
    q = "MATCH (a:P) RETURN a.id AS id ORDER BY id"  # 16 one-row pages

    async def run():
        async with _serve(session, graph) as srv:
            msgs = await _stream_client(srv.host, srv.port, {
                "op": "submit", "id": "pg", "graph": "g", "query": q,
                "stream": True,
            })
            return (msgs, srv._records["pg"]["profile"].to_dict(),
                    dict(srv.stages))

    msgs, prof, stages = asyncio.run(run())
    assert _terminals(msgs)["pg"]["rows"] == 16
    root = prof["root"]
    ser = [c for c in root["children"] if c["name"] == "serialize"]
    assert len(ser) == 3 and ser[-1]["attrs"]["pages"] == 14
    assert sum(c["seconds"] for c in ser) == pytest.approx(
        stages["serialize"], abs=2e-5)
    engine = next(c for c in _flat(root) if c["name"] == "engine")
    (encode,) = [c for c in engine["children"] if c["name"] == "encode"]
    assert encode["attrs"]["pages"] == 16  # one span, however many pages


def test_cluster_records_keep_a_profile_with_the_workers_tree_under_route():
    """Cluster mode: the engine ran in another process on another clock,
    so its rendered tree hangs under the front end's ``route`` span with
    its own offsets, marked as such."""
    from tpu_cypher.serve.cluster import ClusterServer

    worker_tree = {"name": "query", "kind": "query", "start_s": 0.0,
                   "seconds": 0.002, "children": [
                       {"name": "execute", "kind": "phase",
                        "start_s": 0.0001, "seconds": 0.0015}]}

    class StubRouter:
        async def submit(self, **kw):
            return {"rows": [{"n": 2}], "columns": ["n"], "seconds": 0.002,
                    "execution_log": [{"rung": "device", "ok": True}],
                    "rungs": ["device"], "degraded": False,
                    "compile_stats": {},
                    "profile": {"schema_version": 2, "root": worker_tree}}

    async def run():
        srv = ClusterServer(workers=1, port=0, batch_window_ms=0,
                            cache_bytes=0)
        srv.register_graph("g", "CREATE (:P {id: 1})-[:K]->(:P {id: 2})")
        srv.router = StubRouter()
        await QueryServer.start(srv)
        try:
            msgs = await _client(srv.host, srv.port, [
                {"op": "submit", "id": "c", "graph": "g", "query": COUNT_Q},
            ])
            _, body = await _http(srv.host, srv.port, "/queries/c")
        finally:
            await QueryServer.stop(srv)
        return msgs, json.loads(body), dict(srv.stages)

    msgs, rec, stages = asyncio.run(run())
    assert _rows_of(msgs, "c") == [{"n": 2}]
    dispatch = next(c for c in rec["profile"]["root"]["children"]
                    if c["name"] == "dispatch")
    (route,) = dispatch["children"]
    assert route["name"] == "route" and route["kind"] == "serve"
    assert route["children"] == [{**worker_tree, "clock": "worker"}]
    assert stages["route"] == pytest.approx(route["seconds"], abs=2e-6)


def test_http_healthz_and_404(session, graph):
    async def run():
        async with _serve(session, graph) as srv:
            h = await _http(srv.host, srv.port, "/healthz")
            nf = await _http(srv.host, srv.port, "/bogus")
        return h, nf

    (hs, hb), (ns, _) = asyncio.run(run())
    assert hs.endswith("200 OK")
    health = json.loads(hb)
    assert health["ok"] is True and health["graphs"] == ["g"]
    assert ns.endswith("404 Not Found")


# ---------------------------------------------------------------------------
# result cache
# ---------------------------------------------------------------------------


def test_result_cache_lru_byte_budget_eviction():
    """Unit: the byte budget LRU-evicts, oversized and degraded payloads
    never store, and a fingerprint mismatch is a miss that drops the
    stale entry."""
    p = {"rows": [{"id": 1}], "degraded": False}
    one = len(json.dumps(p))
    cache = ResultCache(max_bytes=2 * one)
    assert cache.store("k1", "fp", p) and cache.store("k2", "fp", p)
    assert cache.lookup("k1", "fp") is not None  # freshen k1
    assert cache.store("k3", "fp", p)  # evicts k2 (LRU), not k1
    assert cache.lookup("k2", "fp") is None
    assert cache.lookup("k1", "fp") is not None
    assert cache.lookup("k3", "fp") is not None
    # fingerprint mismatch: miss AND the stale entry is gone
    assert cache.lookup("k1", "other-fp") is None
    assert cache.lookup("k1", "fp") is None
    # exclusions: oversized, degraded, uncacheable (None key), non-JSON
    assert not cache.store("big", "fp", {"rows": [{"id": i} for i in range(99)]})
    assert not cache.store("deg", "fp", {"rows": [], "degraded": True})
    assert not cache.store(None, "fp", p)
    assert not cache.store("obj", "fp", {"rows": object()})
    stats = cache.stats()
    assert stats["entries"] == 1 and stats["bytes"] == one
    assert cache.flush() == 1 and cache.stats()["bytes"] == 0


def test_result_cache_disabled_by_zero_budget():
    cache = ResultCache(max_bytes=0)
    assert not cache.enabled
    assert not cache.store("k", "fp", {"rows": []})
    assert cache.lookup("k", "fp") is None


def test_graph_fingerprint_tracks_statistics(session, graph):
    """Graphs with different data fingerprint differently; the same graph
    fingerprints stably."""
    from tpu_cypher.serve.result_cache import graph_fingerprint

    g2 = session.create_graph_from_create_query("CREATE (a:P {id: 0})")
    fp = graph_fingerprint(session, graph)
    assert fp == graph_fingerprint(session, graph)
    assert fp != graph_fingerprint(session, g2)


def test_cache_hit_byte_identical_and_zero_dispatch(session, graph):
    """The tentpole property: a repeat read is served with ZERO device
    dispatch (the batcher's dispatch counter does not move), in well
    under a millisecond, with zero compile movement, and its row pages
    are byte-identical to the original execution's."""
    from tpu_cypher.serve.batching import DISPATCHES

    async def run():
        async with _serve(session, graph) as srv:
            m1 = await _client(srv.host, srv.port, [
                {"op": "submit", "id": "c1", "graph": "g", "query": ROWS_Q},
            ])
            before = sum(int(v) for _, v in DISPATCHES.items())
            m2 = await _client(srv.host, srv.port, [
                {"op": "submit", "id": "c2", "graph": "g", "query": ROWS_Q},
            ])
            after = sum(int(v) for _, v in DISPATCHES.items())
            _, rec = await _http(srv.host, srv.port, "/queries/c2")
        return m1, m2, after - before, json.loads(rec)

    m1, m2, dispatches, rec = asyncio.run(run())
    d1, d2 = _terminals(m1)["c1"], _terminals(m2)["c2"]
    assert d1["cached"] is False and d2["cached"] is True
    assert dispatches == 0  # the hit never reached the batcher
    assert d2["seconds"] < 0.001  # served from host memory, sub-ms
    assert rec["cached"] is True and rec["compile_stats"] == {}
    # the hit's profile is a synthesized single-span cache trace
    assert rec["profile"]["root"]["children"][0]["name"] == "cache"
    # row pages byte-identical to the original execution's
    pages1 = json.dumps(_rows_of(m1, "c1"), sort_keys=True)
    pages2 = json.dumps(_rows_of(m2, "c2"), sort_keys=True)
    assert pages1 == pages2


def test_cache_excludes_chaos_and_deadline_queries(session, graph):
    """Chaos-injected and deadline-carrying queries neither hit nor
    populate: their state is client-scoped (and degraded payloads are
    refused at store time regardless)."""

    async def run():
        async with _serve(session, graph) as srv:
            await _client(srv.host, srv.port, [
                {"op": "submit", "id": "f1", "graph": "g", "query": HOP_Q,
                 "faults": "oom@expand:*"},
                {"op": "submit", "id": "d1", "graph": "g", "query": HOP_Q,
                 "deadline_s": 30.0},
            ])
            entries = srv.cache.stats()["entries"]
            # a later clean repeat of the same text is a genuine miss
            m = await _client(srv.host, srv.port, [
                {"op": "submit", "id": "f2", "graph": "g", "query": HOP_Q},
            ])
        return entries, m

    entries, m = asyncio.run(run())
    assert entries == 0
    assert _terminals(m)["f2"]["cached"] is False


def test_cache_fingerprint_mismatch_invalidates(session, graph):
    """A lookup under a changed statistics fingerprint is a miss that
    evicts the stale entry — the graph-change invalidation path (a
    re-registered graph object also changes the batch key itself; the
    fingerprint guards in-place drift)."""

    async def run():
        async with _serve(session, graph) as srv:
            await _client(srv.host, srv.port, [
                {"op": "submit", "id": "i1", "graph": "g", "query": COUNT_Q},
            ])
            assert srv.cache.stats()["entries"] == 1
            srv._fingerprints["g"] = "stats-changed"
            m = await _client(srv.host, srv.port, [
                {"op": "submit", "id": "i2", "graph": "g", "query": COUNT_Q},
            ])
            entries = srv.cache.stats()["entries"]
        return m, entries

    m, entries = asyncio.run(run())
    assert _terminals(m)["i2"]["cached"] is False
    assert entries == 1  # re-populated under the new fingerprint


def test_cache_batched_burst_populates_then_hits(session, graph):
    """A coalesced burst executes once AND populates; a straggler after
    the window is a pure cache hit tagged ``cached`` — not another batch."""
    from tpu_cypher.serve.batching import DISPATCHES

    async def run():
        async with _serve(session, graph, batch_window_ms=50) as srv:
            burst = await _client(srv.host, srv.port, [
                {"op": "submit", "id": f"s{i}", "graph": "g", "query": COUNT_Q}
                for i in range(3)
            ])
            before = sum(int(v) for _, v in DISPATCHES.items())
            late = await _client(srv.host, srv.port, [
                {"op": "submit", "id": "late", "graph": "g", "query": COUNT_Q},
            ])
            after = sum(int(v) for _, v in DISPATCHES.items())
        return burst, late, after - before

    burst, late, dispatches = asyncio.run(run())
    dones = _terminals(burst)
    assert {d["batched"] for d in dones.values()} == {3}
    assert all(d["cached"] is False for d in dones.values())
    d = _terminals(late)["late"]
    assert d["cached"] is True and dispatches == 0
    assert _rows_of(late, "late") == _rows_of(burst, "s0")


def test_http_cache_stats_and_flush(session, graph):
    async def run():
        async with _serve(session, graph) as srv:
            await _client(srv.host, srv.port, [
                {"op": "submit", "id": "h1", "graph": "g", "query": COUNT_Q},
                {"op": "submit", "id": "h2", "graph": "g", "query": COUNT_Q},
            ])
            _, stats = await _http(srv.host, srv.port, "/cache")
            _, flushed = await _http(
                srv.host, srv.port, "/cache/flush", method="POST"
            )
            _, stats2 = await _http(srv.host, srv.port, "/cache")
        return json.loads(stats), json.loads(flushed), json.loads(stats2)

    stats, flushed, stats2 = asyncio.run(run())
    assert stats["entries"] == 1 and stats["bytes"] > 0
    assert stats["max_bytes"] > 0
    assert flushed == {"flushed": 1}
    assert stats2["entries"] == 0 and stats2["bytes"] == 0


def test_cache_flush_requires_post(session, graph):
    """GET /cache/flush is 405 and must NOT drop the cache — a crawler or
    monitoring probe sweeping GET routes can't flush state. POST to any
    other route is 405 too."""

    async def run():
        async with _serve(session, graph) as srv:
            await _client(srv.host, srv.port, [
                {"op": "submit", "id": "h1", "graph": "g", "query": COUNT_Q},
            ])
            get_status, get_body = await _http(
                srv.host, srv.port, "/cache/flush"
            )
            _, stats = await _http(srv.host, srv.port, "/cache")
            post_other, _ = await _http(
                srv.host, srv.port, "/metrics", method="POST"
            )
        return get_status, json.loads(get_body), json.loads(stats), post_other

    get_status, get_body, stats, post_other = asyncio.run(run())
    assert get_status.startswith("HTTP/1.1 405")
    assert "POST" in get_body["error"]
    assert stats["entries"] == 1  # the GET dropped nothing
    assert post_other.startswith("HTTP/1.1 405")


# ---------------------------------------------------------------------------
# cursor streaming
# ---------------------------------------------------------------------------

# 16^3 = 4096 rows -> 16 pages of PAGE_ROWS=256: enough to exercise the
# credit window without being slow
CROSS_Q = "MATCH (a:P), (b:P), (c:P) RETURN a.id AS x, b.id AS y, c.id AS z"


async def _stream_client(host, port, submit, close_after=None):
    """Drive one streaming query: ack every page (``next``), optionally
    closing the cursor after ``close_after`` pages. Returns all messages."""
    reader, writer = await asyncio.open_connection(host, port)

    async def send(obj):
        writer.write((json.dumps(obj) + "\n").encode())
        await writer.drain()

    await send(submit)
    msgs, pages = [], 0
    while True:
        msg = json.loads(await asyncio.wait_for(reader.readline(), 30))
        msgs.append(msg)
        if msg.get("type") == "rows":
            pages += 1
            if close_after is not None and pages >= close_after:
                await send({"op": "close", "id": submit["id"]})
                close_after = None
            else:
                await send({"op": "next", "id": submit["id"]})
        if msg.get("type") in ("done", "error", "cancelled"):
            break
    writer.close()
    return msgs


def test_stream_rows_match_eager_and_zero_row_parity(session, graph):
    """Streamed pages reassemble to exactly the eager path's rows; a
    zero-row stream still sends one empty rows frame (protocol parity)."""

    async def run():
        async with _serve(session, graph) as srv:
            s = await _stream_client(srv.host, srv.port, {
                "op": "submit", "id": "st", "graph": "g", "query": ROWS_Q,
                "stream": True,
            })
            e = await _client(srv.host, srv.port, [
                {"op": "submit", "id": "ea", "graph": "g", "query": ROWS_Q},
            ])
            z = await _stream_client(srv.host, srv.port, {
                "op": "submit", "id": "zz", "graph": "g",
                "query": "MATCH (a:P {id: 99}) RETURN a.id AS id",
                "stream": True,
            })
        return s, e, z

    s, e, z = asyncio.run(run())
    d = _terminals(s)["st"]
    assert d["streamed"] is True and d["cached"] is False
    assert d["rows"] == d["total_rows"] == 2
    assert json.dumps(_rows_of(s, "st")) == json.dumps(_rows_of(e, "ea"))
    zd = _terminals(z)["zz"]
    assert zd["rows"] == 0
    assert [m["rows"] for m in z if m["type"] == "rows"] == [[]]


def test_stream_close_ends_delivery_early(session, graph):
    """``close`` after the first page: delivery stops, the query
    terminates ``done`` with only the rows sent so far."""

    async def run():
        async with _serve(session, graph) as srv:
            return await _stream_client(srv.host, srv.port, {
                "op": "submit", "id": "cl", "graph": "g", "query": CROSS_Q,
                "stream": True,
            }, close_after=1)

    msgs = asyncio.run(run())
    d = _terminals(msgs)["cl"]
    assert d["total_rows"] == 4096
    assert 0 < d["rows"] < 4096  # ended early, not exhausted


def test_stream_backpressure_parks_only_its_cursor(session, graph, monkeypatch):
    """A consumer that never grants credit parks its cursor after exactly
    ``window`` pages — while the event loop keeps serving other clients.
    ``close`` then releases it."""
    import tpu_cypher.serve.server as SRV

    monkeypatch.setenv("TPU_CYPHER_SERVE_STREAM_WINDOW", "2")

    async def run():
        async with _serve(session, graph) as srv:
            reader, writer = await asyncio.open_connection(srv.host, srv.port)

            async def send(obj):
                writer.write((json.dumps(obj) + "\n").encode())
                await writer.drain()

            async def recv():
                return json.loads(await asyncio.wait_for(reader.readline(), 30))

            before = SRV.BACKPRESSURE_WAITS.value()
            await send({"op": "submit", "id": "bp", "graph": "g",
                        "query": CROSS_Q, "stream": True})
            assert (await recv())["type"] == "accepted"
            pages = [await recv(), await recv()]  # the full window, no acks
            assert all(m["type"] == "rows" for m in pages)
            # the cursor must now be parked awaiting credit
            for _ in range(100):
                if SRV.BACKPRESSURE_WAITS.value() > before:
                    break
                await asyncio.sleep(0.01)
            waits = SRV.BACKPRESSURE_WAITS.value() - before
            # ... and the loop still serves other clients meanwhile
            other = await _client(srv.host, srv.port, [
                {"op": "submit", "id": "ok", "graph": "g", "query": COUNT_Q},
            ])
            await send({"op": "close", "id": "bp"})
            tail = []
            while True:
                m = await recv()
                tail.append(m)
                if m.get("type") in ("done", "error", "cancelled"):
                    break
            writer.close()
        return waits, other, pages + tail

    waits, other, msgs = asyncio.run(run())
    assert waits >= 1
    assert _terminals(other)["ok"]["type"] == "done"
    d = _terminals(msgs)["bp"]
    # exactly the window's worth of pages went out before the park
    assert sum(1 for m in msgs if m.get("type") == "rows") == 2
    assert d["rows"] == 512 and d["total_rows"] == 4096


# the subprocess pin: a >1M-row result (108^3 = 1,259,712 rows) streamed
# to a deliberately slow consumer must stay under a fixed host-memory
# ceiling. Runs in its own process because the high-water mark is
# process-lifetime; measured via /proc/self/status VmHWM, NOT ru_maxrss —
# on Linux a forked child's ru_maxrss starts at the PARENT's resident
# size, so under a multi-GB pytest parent it reports the suite's
# footprint instead of the stream's.
_RSS_CEILING_MB = 768
_RSS_SCRIPT = r"""
import asyncio, json, resource, sys


def peak_rss_mb():
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmHWM:"):
                    return int(line.split()[1]) // 1024
    except OSError:
        pass  # non-Linux: fall back, accepting the fork-inherited baseline
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss // 1024

from tpu_cypher.relational.session import CypherSession
from tpu_cypher.serve import QueryServer

N = 108  # N**3 = 1,259,712 rows

async def main():
    session = CypherSession.tpu()
    parts = [f"(n{i}:P {{id: {i}}})" for i in range(N)]
    graph = session.create_graph_from_create_query("CREATE " + ", ".join(parts))
    server = QueryServer(session, port=0)
    server.register_graph("g", graph)
    total, done = 0, None
    async with server:
        reader, writer = await asyncio.open_connection(server.host, server.port)
        sub = {"op": "submit", "id": "big", "graph": "g", "stream": True,
               "query": "MATCH (a:P), (b:P), (c:P) "
                        "RETURN a.id AS x, b.id AS y, c.id AS z"}
        writer.write((json.dumps(sub) + "\n").encode())
        await writer.drain()
        pages = 0
        while True:
            msg = json.loads(await asyncio.wait_for(reader.readline(), 120))
            t = msg.get("type")
            if t == "rows":
                total += len(msg["rows"])
                pages += 1
                if pages % 512 == 0:
                    await asyncio.sleep(0.005)  # a deliberately slow consumer
                writer.write((json.dumps({"op": "next", "id": "big"}) + "\n")
                             .encode())
                await writer.drain()
            elif t == "done":
                done = msg
                break
            elif t != "accepted":
                print(json.dumps({"error": msg}), flush=True)
                sys.exit(1)
        writer.close()
    print(json.dumps({"rows": total, "total_rows": done["total_rows"],
                      "streamed": done["streamed"],
                      "peak_rss_mb": peak_rss_mb()}))

asyncio.run(main())
"""


def test_stream_million_rows_under_fixed_rss_ceiling():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # conftest forces an 8-virtual-device XLA host platform for mesh
    # tests; the serving ceiling is a one-device measurement
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _RSS_SCRIPT],
        capture_output=True, text=True, timeout=540, env=env,
    )
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["rows"] == out["total_rows"] == 108 ** 3
    assert out["streamed"] is True
    assert out["peak_rss_mb"] < _RSS_CEILING_MB, out

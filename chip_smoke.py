#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that tpu-cypher still starts on the chip.

Drives the system's main path once, through the entry points a user calls,
and checks every answer against a plain NumPy reference computed here from
the generated arrays (independent of ``tpu_cypher``):

    python chip_smoke.py [--scale N] [--seed S] [--chips 4] [--only PHASES]

One chip (the default, what the driver runs), two phases, each a child
process that is gone before the next starts — the parent never imports JAX,
because a chip belongs to one process at a time:

* ``serve``   — ``CypherSession.tpu()`` -> ``QueryServer`` on an ephemeral
  port -> a client over the newline-JSON wire protocol, every query sent
  twice (cold, then warm). Deployment: the shipped generator
  ``tpu_cypher.io.ldbc`` at ``--scale`` (100 = 1M persons, ~45M KNOWS),
  loaded through the normal ingest, plus a small WAL-backed mutable graph
  (``TPU_CYPHER_WAL_SYNC=fsync``) that takes one acknowledged write, read
  back live and again after a WAL replay into a fresh store.
* ``cluster`` — ``ClusterServer(workers=1)``: the router front end stays off
  the device, its one worker holds the chip (its READY line says so), and a
  few queries answer correctly through the router.

``--chips 4`` (the builder's run) runs only ``mesh`` (one process,
``CypherSession.tpu(mesh=4)``: four-way sharded columns and CSR, balanced
per-device bytes, its queries sent through ``QueryServer`` and the wire, every
sharded tier taken and counted, none declined) and ``cluster4`` (four workers, one
chip each, assigned by the supervisor). ``--only`` keeps a subset of the
mode's phases (four-chip time costs four times as much).

It fails — exit code not 0, no result line — when JAX finds no accelerator
(before loading anything), when any phase fails, and where ``tpu_cypher`` is
not beside it. ``--rehearse-cpu`` is the tier-1 rehearsal: it pins the
children to the CPU at a tiny scale, names the CPU on every line, and its
last line can only ever say ``"platform": "cpu"``.

The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
Everything printed before it (sizes, device bytes, seconds per query, cache
hits) is set-up information, under no metric's name.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import subprocess
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "smoke")

_PREFIX = ""  # set once the platform is known; names it on every line


def say(msg: str) -> None:
    print(f"{_PREFIX}{msg}", flush=True)


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# parent: runs the phases as children, never touches JAX
# ---------------------------------------------------------------------------


def parent(args) -> int:
    global _PREFIX
    os.makedirs(OUT_DIR, exist_ok=True)
    env = dict(os.environ)
    env.setdefault("JAX_ENABLE_X64", "1")
    # the serving deployment's settings: durable commits, the pow2 shape
    # lattice (compiled programs then do not depend on the seed's counts)
    env["TPU_CYPHER_WAL_SYNC"] = "fsync"
    env.setdefault("TPU_CYPHER_BUCKET", "pow2")
    if args.rehearse_cpu:
        _PREFIX = "[cpu rehearsal] "
        env["JAX_PLATFORMS"] = "cpu"
        if args.chips > 1:
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={args.chips}"
            ).strip()
    phases = ["serve", "cluster"] if args.chips == 1 else ["mesh", "cluster4"]
    if args.only:
        phases = [p for p in phases if p in args.only.split(",")]
    t0 = time.monotonic()
    device = None
    for phase in phases:
        report_path = os.path.join(OUT_DIR, f"{phase}.json")
        if os.path.exists(report_path):
            os.remove(report_path)
        cmd = [
            sys.executable, os.path.abspath(__file__), "--phase", phase,
            "--scale", str(args.scale), "--seed", str(args.seed),
            "--chips", str(args.chips),
        ]
        if args.rehearse_cpu:
            cmd.append("--rehearse-cpu")
        child = subprocess.Popen(cmd, env=env, cwd=HERE)
        try:
            rc = child.wait()
        finally:
            if child.poll() is None:
                child.kill()
                child.wait()
        if rc != 0:
            say(f"phase {phase} FAILED (exit code {rc})")
            return rc if 0 < rc < 126 else 1
        with open(report_path) as f:
            report = json.load(f)
        if report.get("ok") is not True:
            say(f"phase {phase} FAILED: {report}")
            return 1
        if device is None:
            device = report["device"]
        elif report["device"]["platform"] != device["platform"]:
            say(f"phase {phase} ran on {report['device']}, not {device}")
            return 1
    if device is None:
        say("no phase ran")
        return 1
    say(f"all phases passed: {phases}; total wall seconds "
        f"{time.monotonic() - t0:.1f}")
    last = {"ok": True, "device": device}
    if args.rehearse_cpu:
        last["rehearsal"] = True
    print(json.dumps(last), flush=True)
    return 0


# ---------------------------------------------------------------------------
# children: one phase each
# ---------------------------------------------------------------------------


def claim_device(args):
    """First thing a child does: see what JAX found. No accelerator (and no
    rehearsal) ends the run here, before anything is loaded."""
    global _PREFIX
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.rehearse_cpu:
        sys.stderr.write(
            f"chip_smoke: JAX found no accelerator (platform "
            f"{dev.platform!r}); this is a chip check and has no CPU "
            "fallback\n"
        )
        sys.exit(3)
    if args.rehearse_cpu:
        check(dev.platform == "cpu", "a rehearsal runs on the CPU only")
        _PREFIX = "[cpu rehearsal] "
    else:
        _PREFIX = f"[{dev.platform}] "
    device = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }
    say(f"device: {device}")
    return device


def write_report(phase: str, report: dict) -> None:
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, f"{phase}.json"), "w") as f:
        json.dump(report, f)


# -- the deployment and its NumPy references --------------------------------


class Reference:
    """Plain NumPy answers over the generated arrays. Person ``i`` has id
    ``7*i+1``; everything below works on the compact index ``i``."""

    def __init__(self, arrays):
        self.ids = arrays["ids"]
        self.birthday = arrays["birthday"]
        self.n = n = len(self.ids)
        self.s = (arrays["src"] - 1) // 7
        self.d = (arrays["dst"] - 1) // 7
        self.e = len(self.s)
        self.outdeg = np.bincount(self.s, minlength=n).astype(np.int64)
        # edges sorted by (source, target): the CSR and the close-probe keys
        self.keys = np.sort(self.s * n + self.d)
        self.rp = np.concatenate([[0], np.cumsum(self.outdeg)])
        self.ci = self.keys % n

    def windows(self, budget_rows: int):
        """Two anchor windows [lo, hi) of compact positions, from the middle
        of the id range (away from the Zipf hubs at the low ids): the
        first sized so its 2-hop walk count stays within ``budget_rows``,
        the second so its <=3-hop walk count does."""
        w1 = self.outdeg.astype(np.float64)
        w2 = np.bincount(self.s, weights=w1[self.d], minlength=self.n)
        w3 = np.bincount(self.s, weights=w2[self.d], minlength=self.n)
        start = self.n // 2
        out = []
        for est in (w1 + w2, w1 + w2 + w3):
            k = int(np.searchsorted(np.cumsum(est[start:]), budget_rows))
            out.append((start, start + max(1, min(k, self.n - start))))
        return out

    def expand(self, rows):
        """(repeat index, neighbor) of one hop from compact nodes ``rows``."""
        deg = self.outdeg[rows]
        rep = np.repeat(np.arange(len(rows)), deg)
        off = np.arange(int(deg.sum())) - np.repeat(np.cumsum(deg) - deg, deg)
        return rep, self.ci[self.rp[rows][rep] + off]

    def edge_mult(self, a, b):
        """Number of parallel edges a->b per lane."""
        q = a * self.n + b
        return (
            np.searchsorted(self.keys, q, side="right")
            - np.searchsorted(self.keys, q, side="left")
        )


def big_graph_queries(ref: Reference, win2, win3):
    """(name, query, parameters, reference rows) per query shape. ``win2``
    anchors the materializing 1- and 2-hop shapes, ``win3`` the var-length
    one."""
    bday = ref.birthday
    cut = 9_000

    def id_range(w):
        return {"lo": int(ref.ids[w[0]]), "hi": int(ref.ids[w[1] - 1]) + 1}

    win, win_v = id_range(win2), id_range(win3)
    anchors = np.arange(*win2)
    r1, b = ref.expand(anchors)  # a -> b
    a1 = anchors[r1]
    r2, c = ref.expand(b)  # a -> b -> c
    a2 = a1[r2]
    triangles = int(ref.edge_mult(c, a2).sum())
    pairs = len(np.unique(a2 * ref.n + c))

    v0 = np.arange(*win3)
    q1, vb = ref.expand(v0)
    q2, vc = ref.expand(vb)
    _, vd = ref.expand(vc)
    # walks of length 3 repeat an edge only as a->b->a->b (no self loops)
    walks = len(vb) + len(vc) + len(vd) - int((vc == v0[q1][q2]).sum())

    # a star round b: the anchor's edge, and two OPTIONAL leaves off b
    indeg = np.bincount(ref.d, minlength=ref.n).astype(np.int64)
    star = int((np.maximum(ref.outdeg[b], 1) * np.maximum(indeg[b], 1)).sum())

    by_bday = np.bincount(bday, minlength=18_000)
    join_rows = int(by_bday[bday[anchors]].sum())

    grp = bday % 7
    groups = [
        {
            "d": int(g),
            "n": int((grp == g).sum()),
            "lo": int(bday[grp == g].min()),
            "hi": int(bday[grp == g].max()),
            "s": int(bday[grp == g].sum()),
        }
        for g in range(7)
        if (grp == g).any()
    ]
    top = np.lexsort((ref.ids, -bday))[:10]
    edge_order = np.lexsort((ref.ids[b], ref.ids[a1]))

    anchor = "MATCH (a:Person) WHERE a.id >= $lo AND a.id < $hi WITH a "
    return [
        ("scan_filter",
         "MATCH (a:Person) WHERE a.birthday < $cut RETURN count(*) AS n",
         {"cut": cut}, [{"n": int((bday < cut).sum())}]),
        ("two_hop_count",  # the whole-graph fused count chain
         "MATCH (a:Person)-[:KNOWS]->(b:Person)-[:KNOWS]->(c:Person) "
         "RETURN count(*) AS c",
         {}, [{"c": int(ref.outdeg[ref.d].sum())}]),
        ("grouped_aggregate",
         "MATCH (a:Person) RETURN a.birthday % 7 AS d, count(a.id) AS n, "
         "min(a.birthday) AS lo, max(a.birthday) AS hi, "
         "sum(a.birthday) AS s ORDER BY d",
         {}, groups),
        ("order_by_limit",
         "MATCH (a:Person) RETURN a.id AS id, a.birthday AS b "
         "ORDER BY b DESC, id ASC LIMIT 10",
         {}, [{"id": int(ref.ids[i]), "b": int(bday[i])} for i in top]),
        ("property_projection",
         "MATCH (a:Person) WHERE a.id >= $lo AND a.id < $hi "
         "RETURN a.id AS id, a.birthday AS b ORDER BY id",
         win, [{"id": int(ref.ids[i]), "b": int(bday[i])} for i in anchors]),
        ("expand_materialize",
         anchor + "MATCH (a)-[:KNOWS]->(b:Person) "
         "RETURN a.id AS a, b.id AS b, b.birthday AS bb ORDER BY a, b",
         win,
         [{"a": int(ref.ids[a1[i]]), "b": int(ref.ids[b[i]]),
           "bb": int(bday[b[i]])} for i in edge_order]),
        ("sort_probe_join",  # a value join: window persons x all persons
         anchor + "MATCH (b:Person) WHERE b.birthday = a.birthday "
         "RETURN count(*) AS c",
         win, [{"c": join_rows}]),
        ("distinct_two_hop",
         anchor + "MATCH (a)-[:KNOWS]->(b:Person)-[:KNOWS]->(c:Person) "
         "WITH DISTINCT a, c RETURN count(*) AS pairs",
         win, [{"pairs": pairs}]),
        ("triangle_close",  # expand-into / WCOJ
         anchor + "MATCH (a)-[:KNOWS]->(b:Person)-[:KNOWS]->(c:Person)"
         "-[:KNOWS]->(a) RETURN count(*) AS t",
         win, [{"t": triangles}]),
        ("var_length",
         anchor + "MATCH (a)-[:KNOWS*1..3]->(b:Person) "
         "RETURN count(*) AS walks",
         win_v, [{"walks": walks}]),
        ("star_optional",  # the tree count: no row of the star is built
         anchor + "MATCH (a)-[:KNOWS]->(b:Person) "
         "OPTIONAL MATCH (b)-[:KNOWS]->(c:Person) "
         "OPTIONAL MATCH (b)<-[:KNOWS]-(d:Person) RETURN count(*) AS n",
         win, [{"n": star}]),
    ]


SMALL_NAMES = ["Alice", "Bob", "Carol", "Dave", "Alina", "Eve", "Frank", "Ali"]


def small_create_query() -> str:
    parts = [
        f"(n{i}:Person {{id: {i}, name: '{name}'}})"
        for i, name in enumerate(SMALL_NAMES)
    ]
    parts += [
        f"(n{i})-[:KNOWS]->(n{(i + k) % len(SMALL_NAMES)})"
        for i in range(len(SMALL_NAMES)) for k in (1, 3)
    ]
    return "CREATE " + ", ".join(parts)


SMALL_QUERIES = [
    ("string_starts_with",
     "MATCH (p:Person) WHERE p.name STARTS WITH 'Al' "
     "RETURN p.name AS name ORDER BY name"),
    ("string_contains",
     "MATCH (p:Person) WHERE p.name CONTAINS 'a' RETURN count(*) AS c"),
    ("small_two_hop",
     "MATCH (a:Person)-[:KNOWS]->(b:Person)-[:KNOWS]->(c:Person) "
     "RETURN a.name AS a, c.name AS c ORDER BY a, c"),
]


def run_rows(graph, query, parameters=None):
    """One in-process query: (rows in the wire's row form, the result)."""
    from tpu_cypher.serve import wire

    result = graph.cypher(query, parameters or {})
    recs = result.records
    return wire.encode_rows(recs.collect(), list(recs.columns)), result


def oracle_rows(graph, query):
    """The ``backend/local`` oracle (Python rows; the small graph only)."""
    return run_rows(graph, query)[0]


# -- the wire client ---------------------------------------------------------


async def submit(host, port, qid, graph, query, parameters=None):
    """One query over the newline-JSON protocol: (rows, terminal message,
    client wall seconds)."""
    reader, writer = await asyncio.open_connection(host, port, limit=1 << 26)
    t0 = time.perf_counter()
    try:
        msg = {"op": "submit", "id": qid, "graph": graph, "query": query,
               "parameters": parameters or {}, "tenant": "smoke"}
        writer.write((json.dumps(msg) + "\n").encode())
        await writer.drain()
        rows = []
        while True:
            line = await reader.readline()
            check(line, f"{qid}: server closed the connection")
            m = json.loads(line)
            if m["type"] == "rows":
                rows.extend(m["rows"])
            elif m["type"] in ("done", "error"):
                return rows, m, time.perf_counter() - t0
    finally:
        writer.close()
        await writer.wait_closed()


async def http_get(host, port, path):
    reader, writer = await asyncio.open_connection(host, port, limit=1 << 26)
    try:
        writer.write(f"GET {path} HTTP/1.1\r\nHost: x\r\n\r\n".encode())
        await writer.drain()
        data = await reader.read()
    finally:
        writer.close()
        await writer.wait_closed()
    head, _, body = data.partition(b"\r\n\r\n")
    check(b" 200 " in head.split(b"\r\n", 1)[0], f"GET {path}: {head[:80]!r}")
    return body.decode()


async def run_query(host, port, name, graph, query, params, want):
    """Send one query twice (cold, then warm); hold both to the reference,
    to the first rung, and the warm one to zero compiles."""
    out = {}
    for temp in ("cold", "warm"):
        qid = f"{name}-{temp}"
        rows, done, wall = await submit(host, port, qid, graph, query, params)
        check(done["type"] == "done", f"{qid}: {done}")
        check(rows == want,
              f"{qid}: answer differs from the reference: got "
              f"{str(rows)[:300]} want {str(want)[:300]}")
        rec = json.loads(await http_get(host, port, f"/queries/{qid}"))
        log = rec["execution_log"]
        check(
            len(log) == 1 and log[0]["ok"] and log[0]["rung"] == "device",
            f"{qid}: not answered by the first rung alone: {log}",
        )
        check(not rec.get("fallbacks"),
              f"{qid}: host-oracle fallback {rec['fallbacks']}")
        check("pallas-interpret" not in json.dumps(rec["profile"]),
              f"{qid}: a Pallas kernel ran in the interpreter")
        out[temp] = {"seconds": round(wall, 3), **rec["compile_stats"]}
    check(out["warm"]["compiles"] == 0,
          f"{name}: warm send compiled {out['warm']['compiles']} programs")
    say(f"query {name}: rows={len(want)} equal to reference; "
        f"cold {out['cold']['seconds']}s ({out['cold']['compiles']} compiles, "
        f"{out['cold']['compile_seconds']}s compiling, persistent cache "
        f"{out['cold']['persistent_cache_hits']} hits/"
        f"{out['cold']['persistent_cache_misses']} misses); "
        f"warm {out['warm']['seconds']}s (0 compiles); first rung only; "
        f"fallbacks none")
    return out


def metric_series(text: str, name: str):
    """{label string: value} of one series in Prometheus text."""
    out = {}
    for line in text.splitlines():
        if line.startswith(name) and not line.startswith("#"):
            key, _, val = line.rpartition(" ")
            out[key[len(name):]] = float(val)
    return out


# -- phase: serve ------------------------------------------------------------


def load_snb(args, session):
    """The generated graph through the normal ingest; prints its size, load
    seconds and the device bytes it holds."""
    import jax

    from tpu_cypher import native
    from tpu_cypher.backend.tpu.graph_index import GraphIndex
    from tpu_cypher.io import ldbc
    from tpu_cypher.relational.session import PropertyGraph

    had_so = os.path.exists(
        os.path.join(os.path.dirname(native.__file__), "_native.so")
    )
    say("native library (edge-list parser, CSR builder): "
        + ("unavailable (no g++)" if native.get_lib() is None
           else "loaded" if had_so else "built here from csr_builder.cpp")
        + "; GraphIndex sorts its CSR with NumPy either way")
    t0 = time.perf_counter()
    arrays = ldbc.snb_arrays(args.scale, args.seed)
    t_gen = time.perf_counter() - t0
    t0 = time.perf_counter()
    scan_graph = ldbc.graph_from_snb_arrays(session, arrays)
    graph = PropertyGraph(session, scan_graph)
    gi = GraphIndex.of(scan_graph)
    ctx = session._runtime_context({})
    gi.node_ids(ctx)
    csr = gi.csr(("KNOWS",), False, ctx)
    jax.block_until_ready(csr)
    t_load = time.perf_counter() - t0
    stats = jax.devices()[0].memory_stats() or {}
    say(f"deployment: generate_snb scale={args.scale} seed={args.seed}: "
        f"persons={len(arrays['ids'])} edges={len(arrays['src'])}; "
        f"generate {t_gen:.1f}s, load+index {t_load:.1f}s; "
        f"device bytes_in_use after load={stats.get('bytes_in_use')}")
    return arrays, scan_graph, graph, gi, csr


async def serve_phase(args, device):
    from tpu_cypher import CypherSession
    from tpu_cypher.serve import QueryServer
    from tpu_cypher.storage import mutable_graph_from_create_query

    t_phase = time.perf_counter()
    session = CypherSession.tpu()
    session.record_fallbacks = True
    arrays, _, graph, _, _ = load_snb(args, session)

    t0 = time.perf_counter()
    ref = Reference(arrays)
    win2, win3 = ref.windows(budget_rows=1 << 19)
    queries = big_graph_queries(ref, win2, win3)
    say(f"references: NumPy, {time.perf_counter() - t0:.1f}s; anchor windows "
        f"of {win2[1] - win2[0]} and {win3[1] - win3[0]} persons")

    wal_path = os.path.join(OUT_DIR, "wal", "small.wal")
    if os.path.exists(wal_path):
        os.remove(wal_path)  # a run starts from an empty log
    create = small_create_query()
    small = mutable_graph_from_create_query(
        session, create, name="small", wal_path=wal_path
    )
    oracle = CypherSession.local().create_graph_from_create_query(create)

    # the result cache is off: the warm send must reach the device again
    server = QueryServer(session, port=0, cache_bytes=0)
    server.register_graph("snb", graph)
    server.register_graph("small", small)
    async with server:
        host, port = server.host, server.port
        say(f"QueryServer on {host}:{port}")
        for name, query, params, want in queries:
            await run_query(host, port, name, "snb", query, params, want)
        for name, query in SMALL_QUERIES:
            await run_query(
                host, port, name, "small", query, {},
                oracle_rows(oracle, query),
            )

        # one write: acknowledged, read back live, then after a WAL replay
        read_back = (
            "MATCH (p:Person) WHERE p.name = 'Zed' RETURN p.id AS id"
        )
        _, done, wall = await submit(
            host, port, "write", "small",
            "CREATE (:Person {id: 999, name: 'Zed'})",
        )
        check(done["type"] == "done", f"write not acknowledged: {done}")
        rows, done, _ = await submit(host, port, "read-back", "small", read_back)
        check(done["type"] == "done" and rows == [{"id": 999}],
              f"acknowledged write not read back: {rows} {done}")
        say(f"write: CREATE acknowledged in {wall:.3f}s "
            f"(TPU_CYPHER_WAL_SYNC=fsync, {wal_path}); read back live")

        metrics = await http_get(host, port, "/metrics")
    replayed = mutable_graph_from_create_query(
        session, create, name="small", wal_path=wal_path
    )
    rows = oracle_rows(replayed, read_back)
    check(rows == [{"id": 999}], f"write lost by the WAL replay: {rows}")
    say(f"write: read back after attach_wal replay into a fresh store "
        f"({replayed._graph.replayed_batches} batch replayed)")

    launches = metric_series(metrics, "tpu_cypher_pallas_launch_total")
    say(f"/metrics answered; tpu_cypher_pallas_launch_total: {launches}")
    on_kernel = launches.get('{kernel="segment_agg",tier="pallas"}', 0)
    if args.rehearse_cpu:
        check(on_kernel == 0, "auto mode reached a Pallas kernel off the TPU")
    else:
        # grouped count(a.id) over 7 groups is eligible; the 64-bit
        # min/max/sum beside it decline by eligibility (tier=fallback)
        check(on_kernel > 0, "segment_agg never launched as tier=pallas")
    hits = metric_series(metrics, "tpu_cypher_persistent_cache_hits_total")
    misses = metric_series(metrics, "tpu_cypher_persistent_cache_misses_total")
    from tpu_cypher.backend.tpu import bucketing

    say(f"compile cache {bucketing.persistent_cache_dir()}: "
        f"{sum(hits.values()):.0f} hits, {sum(misses.values()):.0f} misses; "
        f"phase wall seconds {time.perf_counter() - t_phase:.1f}")
    return {"ok": True, "device": device}


# -- phase: cluster / cluster4 ----------------------------------------------


async def cluster_phase(args, n_workers: int):
    """Router + workers. This process is the front end: it must end the
    phase without ever having initialised a JAX backend."""
    global _PREFIX
    from jax._src import xla_bridge

    from tpu_cypher import CypherSession
    from tpu_cypher.serve.cluster import ClusterServer

    _PREFIX = "[cpu rehearsal] " if args.rehearse_cpu else "[front end] "
    say("TPU_* environment the workers inherit: "
        f"{ {k: v for k, v in os.environ.items() if k.startswith('TPU_')} }")
    create = small_create_query()
    oracle = CypherSession.local().create_graph_from_create_query(create)
    server = ClusterServer(workers=n_workers, port=0, cache_bytes=0)
    server.register_graph("small", create)
    server.warmup([q for _, q in SMALL_QUERIES], "small")
    t0 = time.perf_counter()
    await server.start()  # typed WorkerLost if a worker cannot have its chip
    try:
        held = {w.worker_id: w.device for w in server.supervisor.workers}
        say(f"{n_workers} worker(s) READY in {time.perf_counter() - t0:.1f}s; "
            f"devices held: {held}")
        want = "cpu" if args.rehearse_cpu else "tpu"
        for wid, dev in held.items():
            check(dev.get("platform") == want,
                  f"worker {wid} holds {dev}, not a {want} device")
        if n_workers > 1:
            chips = [dev.get("chip") for dev in held.values()]
            check(len(set(chips)) == n_workers and None not in chips,
                  f"workers were not given distinct chips: {held}")
            if not args.rehearse_cpu:
                check(all(dev["count"] == 1 for dev in held.values()),
                      f"a worker sees more than its own chip: {held}")
        for rep in range(max(n_workers, 1) * 2):  # spread over the workers
            for name, query in SMALL_QUERIES:
                rows, done, wall = await submit(
                    server.host, server.port, f"{name}-{rep}", "small", query
                )
                check(done["type"] == "done", f"{name}: {done}")
                check(rows == oracle_rows(oracle, query),
                      f"{name}: answer through the router differs: {rows}")
        say(f"{len(SMALL_QUERIES)} queries x {max(n_workers, 1) * 2} answered "
            "correctly through the router")
    finally:
        await server.stop()
    check(not xla_bridge.backends_are_initialized(),
          "the router front end initialised a JAX backend")
    say("front end holds no device (no JAX backend initialised)")
    first = next(iter(held.values()))
    return {
        "ok": True,
        "device": {"platform": first["platform"], "kind": first["kind"],
                   "count": sum(d["count"] for d in held.values())},
    }


# -- phase: mesh -------------------------------------------------------------


async def mesh_phase(args, device):
    import jax

    from tpu_cypher import CypherSession
    from tpu_cypher.obs.metrics import REGISTRY

    n = args.chips
    check(device["count"] == n, f"need {n} devices, JAX reports {device}")
    session = CypherSession.tpu(mesh=n)
    session.record_fallbacks = True
    arrays, scan_graph, graph, gi, csr = load_snb(args, session)

    for scan in scan_graph.scans:
        for cname, col in scan.table._cols.items():
            check(len(col.data.sharding.device_set) == n,
                  f"column {cname} is not sharded over {n} devices: "
                  f"{col.data.sharding}")
    for arr, what in zip(csr[1:], ("col_idx", "edge_orig")):
        check(len(arr.sharding.device_set) == n,
              f"CSR {what} is not sharded over {n} devices: {arr.sharding}")
    in_use = [
        (d.memory_stats() or {}).get("bytes_in_use") for d in jax.devices()
    ]
    say(f"table columns and CSR arrays sharded {n} ways; bytes_in_use per "
        f"device: {in_use}")
    if not args.rehearse_cpu:  # the CPU client reports no memory stats
        check(min(in_use) > 0 and max(in_use) <= 2 * min(in_use),
              f"device bytes are not balanced within 2x: {in_use}")

    ref = Reference(arrays)
    win2, win3 = ref.windows(budget_rows=1 << 19)
    # count / aggregate / join from the one-chip list, plus the DISTINCT
    # shape that hash-repartitions over the mesh (parallel/shuffle.py)
    wanted = ("two_hop_count", "grouped_aggregate", "sort_probe_join")
    queries = [
        q for q in big_graph_queries(ref, win2, win3) if q[0] in wanted
    ] + [(
        "distinct_values",
        "MATCH (a:Person) WITH DISTINCT a.birthday AS b RETURN count(*) AS c",
        {}, [{"c": len(set(arrays["birthday"].tolist()))}],
    )]
    # through QueryServer and the wire, as the one-chip serve phase sends
    # its queries: worker lanes, admission and the record of each request
    # meet the process-global mesh here
    from tpu_cypher.serve import QueryServer

    server = QueryServer(session, port=0, cache_bytes=0)
    server.register_graph("snb", graph)
    before = REGISTRY.flat()
    async with server:
        say(f"QueryServer on {server.host}:{server.port}, mesh of {n}")
        for name, query, params, want in queries:
            await run_query(
                server.host, server.port, name, "snb", query, params, want
            )
    after = REGISTRY.flat()
    moved = {
        k: after[k] - before.get(k, 0) for k in after
        if k.startswith("tpu_cypher_mesh_") and after[k] != before.get(k, 0)
    }
    say(f"sharded tiers: counters moved {moved}")
    for series, what in (
        ("tpu_cypher_mesh_expand_total",
         "the count chain took the global path, not the sharded SpMV"),
        ("tpu_cypher_mesh_agg_total",
         "grouped aggregation took the global path, not parallel/agg.py"),
        ("tpu_cypher_mesh_distinct_total",
         "DISTINCT took the global path, not parallel/shuffle.py"),
        ("tpu_cypher_mesh_join_total",
         "the value join took the global sort-probe join, not a tier of "
         "parallel/shuffle.py"),
    ):
        check(any(k.startswith(series) for k in moved), what)
    declined = {
        k: v for k, v in moved.items()
        if k.startswith("tpu_cypher_mesh_declines_total")
    }
    check(not declined,
          f"a sharded tier handed back to the global path: {declined}")
    return {"ok": True, "device": device}


# ---------------------------------------------------------------------------


def child(args) -> int:
    if args.phase in ("cluster", "cluster4"):
        # the front end: claims no device, and proves it at the end
        report = asyncio.run(
            cluster_phase(args, 1 if args.phase == "cluster" else args.chips)
        )
    else:
        device = claim_device(args)
        phase = serve_phase if args.phase == "serve" else mesh_phase
        report = asyncio.run(phase(args, device))
    write_report(args.phase, report)
    say(f"phase {args.phase} passed")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", type=float, default=100.0,
                    help="generate_snb scale: 10k persons x 45 KNOWS each "
                    "per unit (default 100 = 1M persons, ~45M edges)")
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tier-1 rehearsal: pin the children to the CPU")
    ap.add_argument("--only", default="",
                    help="comma-separated subset of the phases (debugging)")
    ap.add_argument("--phase", default="",
                    help=argparse.SUPPRESS)  # internal: run one child phase
    args = ap.parse_args()
    if args.phase:
        sys.path.insert(0, HERE)
        return child(args)
    return parent(args)


if __name__ == "__main__":
    sys.exit(main())

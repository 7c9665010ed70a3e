"""Serving-layer soak: N concurrent clients against one warm query server.

Not collected by pytest (no ``test_`` prefix) — run directly, like
``fuzz_soak.py``:

    python tests/soak_serve.py [seconds] [clients] [--faults]

It runs on whatever platform JAX selects (``JAX_PLATFORMS=cpu`` for a CPU
run) and names it in its report (``platform``); under pytest the suite's
conftest pins the CPU.

Defaults: 20 s x 100 clients. Every client keeps exactly one query in
flight over its own TCP connection, drawing from a mixed TCK-shaped
corpus (counts, filtered scans, multi-hop expands, parameterized lookups,
ORDER BY/LIMIT, OPTIONAL MATCH); a 100-client run therefore sustains 100
concurrent queries against the admission scheduler end-to-end.

Checked per query: the streamed rows must be byte-identical (JSON wire
form) to serial in-process execution of the same query on the same
session — degrade-ladder rungs included. Reported at the end, one JSON
line prefixed ``SERVE_SOAK``:

    {"queries", "failures", "qps", "p50_ms", "p99_ms",
     "recompiles_after_warmup", "batched_dispatch_ratio", "chaos"}

* ``recompiles_after_warmup`` — XLA compile delta across the whole soak
  (the corpus is warmed first); MUST be 0 in non-chaos runs and is
  allowed to be nonzero under chaos (degraded rungs compile their own
  programs: bucket-exact/chunked shapes are new by design).
* ``batched_dispatch_ratio`` — batched dispatches / all dispatches; > 0
  proves same-bucket bursts coalesced into shared device work.
* ``--faults`` — chaos mode: ~1/3 of submits carry a random
  ``TPU_CYPHER_FAULTS``-grammar spec, scoped to that client's query only
  (``faults.scoped_spec`` via the server); results must STILL match the
  serial goldens and p99 stays bounded while neighbors degrade.
* ``--workers N`` — multi-process mode: the same soak drives a
  ``ClusterServer`` router over N supervised engine-worker processes
  (``serve/cluster.py``). ``recompiles_after_warmup`` is ``None`` here
  (workers compile in their own processes; the front end cannot see the
  delta) and the report gains ``workers``/``worker_restarts``/
  ``worker_kills``/``replica_retries``.
* ``--kill-workers`` — process-chaos mode (implies ``--workers``): a
  killer task SIGKILLs a random live worker every ~2 s (always leaving
  at least one alive). The invariants stay absolute: ZERO client-visible
  failures and every row set byte-identical to serial execution — dead
  workers are the router's problem, not the clients'.
* ``--repeat-ratio R`` — each client re-issues its previous submission
  with probability R (the dashboard-refresh traffic shape the result
  cache exists for). The report gains ``cached_queries`` /
  ``cache_hit_ratio``; run with ``--cache-bytes 0`` for the honest
  pre-cache baseline at the same ratio.
* ``--write-ratio R`` — mutation mode: the soak graph becomes a
  WAL-backed delta-CSR store and each submission is, with probability R,
  a unique-key ``MERGE`` on a ``:W`` label (disjoint from ``:P``, so
  every read golden stays valid mid-mutation). MERGE makes the write
  idempotent under replica retry, so combined with ``--kill-workers``
  this is the crash-recovery soak: mid-write SIGKILLs must stay
  invisible to clients. After the soak the WAL is replayed OFFLINE into
  a fresh store and every acknowledged write must be present — an ack
  that does not survive replay is counted as a failure. The report
  gains ``writes``/``acked_writes``/``recovered_writes``/
  ``missing_committed_writes``/``compactions``. Write mode forces the
  pow2 bucket lattice and compaction at the min delta bucket, so the
  ``recompiles_after_warmup == 0`` gate also pins "zero warm recompiles
  across compactions" (in-process, non-chaos).
* ``stage_breakdown`` — accumulated wall seconds per serving stage
  (queue_wait / route / dispatch / serialize / demux), the latency
  attribution table in docs/serving.md.
"""

import asyncio
import json
import os
import sys
import time

os.environ.setdefault("JAX_ENABLE_X64", "1")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (query, {param: [values to draw from]} | None) — TCK-shaped mix; every
# entry is plan-cacheable so bursts can share dispatches
CORPUS = [
    ("MATCH (a:P) RETURN count(a) AS n", None),
    ("MATCH (a:P)-[:K]->(b:P) RETURN count(b) AS n", None),
    ("MATCH (a:P) WHERE a.id >= 10 RETURN count(a) AS n", None),
    ("MATCH (a:P) RETURN a.id AS id ORDER BY id LIMIT 7", None),
    ("MATCH (a:P)-[:K]->(b:P)-[:K]->(c:P) RETURN count(c) AS n", None),
    ("OPTIONAL MATCH (a:P {id: -1})-[:K]->(b:P) RETURN count(b) AS n", None),
    ("MATCH (a:P {id: $i})-[:K]->(b:P) RETURN b.id AS id ORDER BY id",
     {"i": [0, 1, 2, 3]}),
    ("MATCH (a:P)-[:K]->(b:P) WHERE b.id < $x RETURN count(*) AS c",
     {"x": [8, 24]}),
]

FAULT_SITES = ("join", "expand", "filter", "compact", "agg")
FAULT_KINDS = ("oom", "compile", "lost")


def _create_query(n=48) -> str:
    parts = [f"(n{i}:P {{id: {i}}})" for i in range(n)]
    parts += [f"(n{i})-[:K]->(n{(i + 1) % n})" for i in range(n)]
    parts += [f"(n{i})-[:K]->(n{(i + 11) % n})" for i in range(n)]
    return "CREATE " + ", ".join(parts)


def _build_graph(session, n=48):
    return session.create_graph_from_create_query(_create_query(n))


def _combos():
    """Flatten the corpus into concrete (query, params) submissions."""
    out = []
    for q, space in CORPUS:
        if not space:
            out.append((q, {}))
            continue
        key = next(iter(space))
        for v in space[key]:
            out.append((q, {key: v}))
    return out


def _random_fault_spec(rng) -> str:
    site = FAULT_SITES[int(rng.integers(0, len(FAULT_SITES)))]
    kind = FAULT_KINDS[int(rng.integers(0, len(FAULT_KINDS)))]
    occ = "*" if rng.random() < 0.25 else str(int(rng.integers(1, 3)))
    return f"{kind}@{site}:{occ}"


async def _client(i, host, port, t_end, combos, goldens, rng, chaos, stats,
                  repeat_ratio=0.0, write_ratio=0.0):
    reader, writer = await asyncio.open_connection(host, port)
    tenant = f"t{i % 4}"
    k = 0
    prev = None
    try:
        while time.monotonic() < t_end:
            # with --write-ratio, this submission is a unique-key MERGE on
            # the :W label (disjoint from :P — read goldens stay valid);
            # unique wid per (client, seq) makes the offline WAL replay
            # differential able to name exactly which acks went missing
            wid = None
            if write_ratio > 0 and rng.random() < write_ratio:
                wid = i * 1_000_000 + k
                q = "MERGE (w:W {wid: $wid})"
                params = {"wid": wid}
                stats["writes"] += 1
            elif prev is not None and rng.random() < repeat_ratio:
                # with --repeat-ratio, re-issue the previous submission
                # (the dashboard-refresh shape the result cache exists for)
                q, params = prev
            else:
                q, params = combos[int(rng.integers(0, len(combos)))]
            if wid is None:
                prev = (q, params)
            qid = f"c{i}-{k}"
            k += 1
            sub = {"op": "submit", "id": qid, "graph": "soak", "query": q,
                   "parameters": params, "tenant": tenant}
            # chaos specs ride reads only: a faulted commit is a typed
            # client-visible failure BY DESIGN (atomic rollback), which
            # would break this soak's zero-failure invariant
            if chaos and wid is None and rng.random() < 0.33:
                sub["faults"] = _random_fault_spec(rng)
            t0 = time.perf_counter()
            writer.write((json.dumps(sub) + "\n").encode())
            await writer.drain()
            rows, terminal = [], None
            while terminal is None:
                raw = await asyncio.wait_for(reader.readline(), 60)
                if not raw:
                    terminal = {"type": "error", "error": "disconnect"}
                    break
                m = json.loads(raw)
                if m.get("id") != qid:
                    continue
                if m["type"] == "rows":
                    rows.extend(m["rows"])
                elif m["type"] in ("done", "error", "cancelled"):
                    terminal = m
            stats["latencies"].append(time.perf_counter() - t0)
            stats["queries"] += 1
            if terminal.get("type") != "done":
                stats["failures"] += 1
                stats["errors"].append(
                    f"{qid} {q!r}: {terminal.get('error')}: "
                    f"{terminal.get('message', '')[:200]}"
                )
            elif wid is not None:
                # the ack is the durability promise the offline WAL
                # replay differential holds the store to
                stats["acked_writes"].add(wid)
            elif json.dumps(rows, sort_keys=True) != goldens[(q, _pkey(params))]:
                stats["failures"] += 1
                stats["errors"].append(
                    f"{qid} {q!r} params={params}: rows diverged from serial"
                )
            else:
                if terminal.get("batched", 1) > 1:
                    stats["batched_queries"] += 1
                if terminal.get("cached"):
                    stats["cached_queries"] += 1
    finally:
        writer.close()


def _pkey(params):
    return tuple(sorted(params.items()))


def _hit_ratio(hits, misses):
    total = hits + misses
    return round(hits / total, 4) if total else None


async def _worker_killer(supervisor, t_end, kills, period_s=2.0):
    """SIGKILL a random ready worker every ``period_s``, always leaving at
    least one alive — the router must hide every death from the clients."""
    import numpy as np

    rng = np.random.default_rng(1234)
    while time.monotonic() < t_end - 1.0:
        await asyncio.sleep(period_s * (0.75 + 0.5 * rng.random()))
        ready = [
            w for w in supervisor.ready_workers
            if w.transport is not None and w.transport.poll() is None
        ]
        if len(ready) < 2:
            continue  # never orphan the fleet
        victim = ready[int(rng.integers(0, len(ready)))]
        os.kill(victim.transport.pid, 9)  # SIGKILL: no goodbye, no unwind
        kills.append(victim.worker_id)


def main(budget_s: float = 20.0, clients: int = 100, chaos: bool = False,
         seed: int = 0, batch_window_ms: float = 5.0,
         max_concurrent: int = 8, workers: int = 0,
         kill_workers: bool = False, repeat_ratio: float = 0.0,
         cache_bytes=None, write_ratio: float = 0.0,
         compact_max=None, mutable: bool = False) -> dict:
    import numpy as np

    from tpu_cypher.backend.tpu import bucketing
    from tpu_cypher.relational.session import CypherSession
    from tpu_cypher.serve import ClusterServer, QueryServer
    from tpu_cypher.serve.batching import DISPATCHES
    from tpu_cypher.serve.result_cache import HITS, MISSES
    from tpu_cypher.serve.router import REPLICA_RETRIES
    from tpu_cypher.serve.server import _encode_rows
    from tpu_cypher.utils.config import COMPACT_DELTA_MAX, COMPACT_MIN_BUCKET

    # --mutable serves the SAME delta-CSR store (identically primed)
    # with zero writes: the apples-to-apples read-only baseline for the
    # mixed-traffic qps ratio — same storage, same lattice, same serving
    # stack, only the 10% write stream differs
    mutable = mutable or write_ratio > 0
    wal_path = None
    if mutable:
        # the zero-recompile pin needs stable delta shapes: pow2 lattice +
        # compaction at the min bucket means a growing delta never crosses
        # a bucket boundary before compaction folds it into the base. Env
        # (not just the override) so spawned cluster workers inherit it.
        os.environ.setdefault("TPU_CYPHER_BUCKET", "pow2")
        if compact_max is None:
            # the delta overlay is host-padded to the 32-lane lattice
            # floor no matter how few rows it holds, so compacting any
            # earlier than a full bucket buys zero shape stability — it
            # only multiplies full-base rebuilds. Compact exactly when
            # the delta would outgrow its one bucket.
            compact_max = max(32, int(COMPACT_MIN_BUCKET.get()))
        os.environ["TPU_CYPHER_COMPACT_DELTA_MAX"] = str(int(compact_max))
        COMPACT_DELTA_MAX.set(int(compact_max))

    combos = _combos()
    if workers > 0:
        import tempfile

        server = ClusterServer(
            workers=workers, port=0, max_concurrent=max_concurrent * workers,
            batch_window_ms=batch_window_ms, cache_bytes=cache_bytes,
            # a soak starts from an empty log: never the deployment's
            # durable default beside the compile cache
            wal_dir=tempfile.mkdtemp(prefix="tpu-cypher-soak-wal-"),
        )
        server.register_graph("soak", _create_query(),
                              mutable=mutable)
        # worker-side warmup: the unparameterized corpus shapes (readiness
        # is gated on it); parameterized shapes compile on first use
        server.warmup([q for q, space in CORPUS if not space], "soak")
        session, graph = server.session, server._graphs["soak"]
        if mutable:
            wal_path = os.path.join(server.wal_dir, "soak.wal")
    else:
        import tempfile

        session = CypherSession.tpu()
        if mutable:
            from tpu_cypher.storage import mutable_graph_from_create_query

            wal_path = os.path.join(
                tempfile.mkdtemp(prefix="tpu-cypher-soak-wal-"), "soak.wal"
            )
            graph = mutable_graph_from_create_query(
                session, _create_query(), name="soak", wal_path=wal_path
            )
        else:
            graph = _build_graph(session)
        server = QueryServer(
            session, port=0, max_concurrent=max_concurrent,
            batch_window_ms=batch_window_ms, cache_bytes=cache_bytes,
        )
        server.register_graph("soak", graph)

    # serial goldens double as warmup: every corpus shape compiles here,
    # so the soak itself must add zero compiles (non-chaos, in-process)
    goldens = {}
    for q, params in combos:
        records = graph.cypher(q, params).records
        goldens[(q, _pkey(params))] = json.dumps(
            _encode_rows(records.collect(), records.columns), sort_keys=True
        )
    mutable = graph._graph if (mutable and workers == 0) else None
    if mutable is not None:
        # warm past the base->snapshot transition AND past the :W bucket
        # crossings the measured window would otherwise hit: W starts
        # empty and grows one node per write, so every live-count-derived
        # bucket in the scan pipeline crosses pow2 boundaries as it
        # grows: the :W element table at round_size(W), and the all-nodes
        # universe the expand path scans at round_size(48 + W). Those
        # crossings are legitimate lattice growth (O(log n) lifetime
        # compiles) — but they must land in priming, not in the measured
        # window, for the ACROSS-COMPACTIONS zero-recompile pin to be
        # observable. Prime writes (negative wids, disjoint from the >=0
        # client wids) until the nearest upcoming crossing is at least a
        # write-rate margin away, then run full read passes at the
        # compaction edges of the last two cycles so every corpus shape
        # is warm on the settled lattice in both delta phases before the
        # compile snapshot is taken. Delta FILL never re-keys anything
        # (the overlay is one fixed bucket), so only the two phase
        # structures — live overlay and freshly-compacted — need reads.
        cm = int(compact_max)
        base_nodes = 48  # _create_query(n=48); writes only ever add :W

        def _next_crossing(w: int) -> int:
            firsts = []
            for off in (0, base_nodes):
                p = 32  # lattice floor
                while p < max(w + off, 32):
                    p *= 2
                firsts.append(p - off + 1)  # first W past the boundary
            return min(f for f in firsts if f > w)

        margin = max(120, int(budget_s * 30))
        prime_writes = 2 * cm
        while _next_crossing(prime_writes) - prime_writes < margin:
            # jump one full compaction cycle past that crossing
            nc = _next_crossing(prime_writes)
            prime_writes = ((nc + cm - 1) // cm + 1) * cm
        read_tail = prime_writes - 2 * cm
        for w in range(1, prime_writes + 1):
            graph.cypher("MERGE (w:W {wid: $wid})", {"wid": -w})
            # read passes straddle each compaction edge (delta just
            # emptied, then delta=1) plus the final priming state
            if w > read_tail and (w % cm <= 1 or w == prime_writes):
                for q, params in combos:
                    graph.cypher(q, params).records.collect()

    async def run():
        stats = {"queries": 0, "failures": 0, "batched_queries": 0,
                 "cached_queries": 0, "writes": 0, "acked_writes": set(),
                 "latencies": [], "errors": []}
        kills = []
        compactions_before = mutable.compactions if mutable is not None else 0
        disp_before = {
            lbl["batched"]: int(v) for lbl, v in DISPATCHES.items()
        }
        retries_before = sum(int(v) for _, v in REPLICA_RETRIES.items())
        hits_before, misses_before = int(HITS.value()), int(MISSES.value())
        compiles_before = bucketing.compile_snapshot()
        async with server:
            # clock starts AFTER the server (and, in cluster mode, every
            # worker boot + warmup) is up — qps measures serving, not boot
            t0 = time.monotonic()
            tasks = [
                _client(i, server.host, server.port, t0 + budget_s, combos,
                        goldens, np.random.default_rng(seed + i), chaos,
                        stats, repeat_ratio=repeat_ratio,
                        write_ratio=write_ratio)
                for i in range(clients)
            ]
            if kill_workers and workers > 0:
                tasks.append(
                    _worker_killer(server.supervisor, t0 + budget_s, kills)
                )
            await asyncio.gather(*tasks)
            elapsed = time.monotonic() - t0
            # snap the compile delta at window end, BEFORE the offline
            # WAL-replay differential below: that rebuild is a fresh
            # store in a fresh session and legitimately compiles its own
            # programs — those are boot compiles, not warm recompiles
            window_compiles = (
                None if workers > 0 else int(
                    bucketing.compile_delta(compiles_before)["compiles"]
                )
            )
        recovered_writes = None
        missing = []
        if write_ratio > 0 and wal_path and os.path.exists(wal_path):
            # offline crash-recovery differential: replay the WAL into a
            # FRESH store in a fresh session; every acknowledged write
            # must be there — an ack that does not survive replay is a
            # durability lie and counts as a failure
            from tpu_cypher.storage import mutable_graph_from_create_query

            rebuilt = mutable_graph_from_create_query(
                CypherSession.tpu(), _create_query(), name="soak",
                wal_path=wal_path,
            )
            recovered_writes = rebuilt._graph.replayed_batches
            got = {
                dict(r)["wid"]
                for r in rebuilt.cypher(
                    "MATCH (w:W) RETURN w.wid AS wid"
                ).records.collect()
            }
            missing = sorted(stats["acked_writes"] - got)
            if missing:
                stats["failures"] += len(missing)
                stats["errors"].append(
                    f"{len(missing)} acked writes missing after WAL "
                    f"replay: {missing[:5]}"
                )
        disp_after = {lbl["batched"]: int(v) for lbl, v in DISPATCHES.items()}
        disp = {
            k: disp_after.get(k, 0) - disp_before.get(k, 0)
            for k in ("true", "false")
        }
        total_disp = max(disp["true"] + disp["false"], 1)
        lat_ms = np.asarray(stats["latencies"]) * 1000.0
        if workers > 0:  # the front end holds no device: ask a worker
            platform = server.supervisor.workers[0].device.get("platform")
        else:
            import jax

            platform = jax.devices()[0].platform
        report = {
            "platform": platform,
            "queries": stats["queries"],
            "failures": stats["failures"],
            "clients": clients,
            "qps": round(stats["queries"] / max(elapsed, 1e-9), 1),
            "p50_ms": round(float(np.percentile(lat_ms, 50)), 2) if len(lat_ms) else None,
            "p99_ms": round(float(np.percentile(lat_ms, 99)), 2) if len(lat_ms) else None,
            # workers compile in their own processes: the front end cannot
            # observe their delta, so the field is None in cluster mode
            "recompiles_after_warmup": window_compiles,
            "batched_dispatch_ratio": round(disp["true"] / total_disp, 4),
            "batched_queries": stats["batched_queries"],
            "cached_queries": stats["cached_queries"],
            "cache_hit_ratio": _hit_ratio(
                int(HITS.value()) - hits_before,
                int(MISSES.value()) - misses_before,
            ),
            "repeat_ratio": repeat_ratio,
            # where the non-engine time went: accumulated wall seconds per
            # serving stage (docs/serving.md, "Latency attribution")
            "stage_breakdown": {
                k: round(v, 3) for k, v in sorted(server.stages.items())
            },
            "chaos": chaos,
            "workers": workers,
            "errors": stats["errors"][:10],
        }
        if write_ratio > 0:
            report.update(
                write_ratio=write_ratio,
                writes=stats["writes"],
                acked_writes=len(stats["acked_writes"]),
                recovered_writes=recovered_writes,
                missing_committed_writes=len(missing),
                compactions=(
                    mutable.compactions - compactions_before
                    if mutable is not None else None
                ),
            )
        if workers > 0:
            report.update(
                worker_kills=len(kills),
                worker_restarts=server.supervisor.total_restarts,
                replica_retries=(
                    sum(int(v) for _, v in REPLICA_RETRIES.items())
                    - retries_before
                ),
            )
        return report

    return asyncio.run(run())


if __name__ == "__main__":
    argv = sys.argv[1:]
    chaos, kill_workers, workers, args = False, False, 0, []
    repeat_ratio, cache_bytes, write_ratio = 0.0, None, 0.0
    mutable = False
    i = 0
    while i < len(argv):
        a = argv[i]
        if a == "--faults":
            chaos = True
        elif a == "--mutable":
            mutable = True
        elif a == "--kill-workers":
            kill_workers = True
        elif a == "--workers":
            i += 1
            workers = int(argv[i])
        elif a.startswith("--workers="):
            workers = int(a.split("=", 1)[1])
        elif a == "--repeat-ratio":
            i += 1
            repeat_ratio = float(argv[i])
        elif a.startswith("--repeat-ratio="):
            repeat_ratio = float(a.split("=", 1)[1])
        elif a == "--write-ratio":
            i += 1
            write_ratio = float(argv[i])
        elif a.startswith("--write-ratio="):
            write_ratio = float(a.split("=", 1)[1])
        elif a == "--cache-bytes":
            i += 1
            cache_bytes = int(argv[i])
        elif a.startswith("--cache-bytes="):
            cache_bytes = int(a.split("=", 1)[1])
        else:
            args.append(a)
        i += 1
    if kill_workers and workers == 0:
        workers = 2
    budget = float(args[0]) if len(args) > 0 else 20.0
    clients = int(args[1]) if len(args) > 1 else 100
    report = main(budget, clients, chaos=chaos, workers=workers,
                  kill_workers=kill_workers, repeat_ratio=repeat_ratio,
                  cache_bytes=cache_bytes, write_ratio=write_ratio,
                  mutable=mutable)
    errors = report.pop("errors")
    print("SERVE_SOAK " + json.dumps(report))
    for e in errors:
        print("  " + e)
    bad = report["failures"] > 0
    if (not chaos and report["recompiles_after_warmup"] is not None
            and report["recompiles_after_warmup"] > 0):
        print("FAIL: recompiles after warmup in a non-chaos soak")
        bad = True
    sys.exit(1 if bad else 0)

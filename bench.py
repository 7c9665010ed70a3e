#!/usr/bin/env python
"""Benchmark ladder: the BASELINE.md workloads THROUGH THE QUERY ENGINE.

North star (BASELINE.md config #3): >= 100M edge-expansions/sec on the LDBC
SNB 2-hop MATCH at SF10 scale on one TPU chip. This bench runs the full
ladder on LDBC-SNB-shaped graphs from ``tpu_cypher.io.ldbc.generate_snb``:

* 2-hop friends-of-friends count        (config #2/#3 query, fused SpMV chain)
* 2-hop with DISTINCT endpoints         (config #2's Expand->Expand->Distinct)
* directed triangle close               (config #3, exercises CsrExpandIntoOp)
* bounded var-length ``*1..3``          (config #4, frontier-loop throughput)

each at SF1 (~10k persons / ~450k KNOWS) and SF10 (~100k / ~4.5M) scale.
Every shape is validated against the local oracle on a small graph first,
and the fused operators are asserted present in the executed plans.

One process, one chip: ``python bench.py`` raises when JAX's platform is
not a TPU — a measurement path has no CPU fallback. ``TPU_CYPHER_BENCH_
FORCE_CPU=1`` is the explicit CPU dry run (counts and correctness only;
its numbers are not device numbers and ``vs_baseline`` stays 0.0). Every
result names the platform, ``device_kind`` and device count it ran on, and
the roofline denominators come from a table keyed by ``device_kind`` (an
unknown device is an error). The legs that start child processes run their
children pinned to the CPU and say so: this process holds the chip, and a
chip belongs to one process.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
"""

import contextlib
import json
import os
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

NORTH_STAR = 1.0e8  # edge-expansions/sec target (BASELINE.json)

TWO_HOP = (
    "MATCH (a:Person)-[:KNOWS]->(b:Person)-[:KNOWS]->(c:Person) "
    "RETURN count(*) AS c"
)
TWO_HOP_DISTINCT = (
    "MATCH (a:Person)-[:KNOWS]->(b:Person)-[:KNOWS]->(c:Person) "
    "WITH DISTINCT a, c RETURN count(*) AS pairs"
)
TRIANGLE = (
    "MATCH (a:Person)-[:KNOWS]->(b:Person)-[:KNOWS]->(c:Person)-[:KNOWS]->(a) "
    "RETURN count(*) AS triangles"
)
VAR_LENGTH = (
    # the WITH boundary anchors the source filter BEFORE the var-length
    # expansion (the walk set is genuinely materialized — edge-uniqueness
    # semantics need per-path state — so the frontier must be bounded)
    "MATCH (a:Person) WHERE a.id >= $lo AND a.id < $hi WITH a "
    "MATCH (a)-[:KNOWS*1..3]->(b:Person) RETURN count(*) AS walks"
)
CLIQUE4 = (
    # directed 4-clique: the triangle plus a fourth vertex every corner
    # points at — two cycle-closing ExpandIntos, so the WCOJ plan runs a
    # 2-close multiway intersection where the binary plan joins 6 scans
    "MATCH (a:Person)-[:KNOWS]->(b:Person)-[:KNOWS]->(c:Person)-[:KNOWS]->(a), "
    "(a)-[:KNOWS]->(d:Person), (b)-[:KNOWS]->(d), (c)-[:KNOWS]->(d) "
    "RETURN count(*) AS cliques"
)
CLIQUE4_MAT = (
    # the MATERIALIZING 4-clique: property expressions force the WCOJ
    # materialize tier (the count tier never sees d.id), and the distinct
    # aggregate answers on the compressed form without ever decompressing
    # the flat row set — the factorized-execution acceptance shape
    "MATCH (a:Person)-[:KNOWS]->(b:Person)-[:KNOWS]->(c:Person)-[:KNOWS]->(a), "
    "(a)-[:KNOWS]->(d:Person), (b)-[:KNOWS]->(d), (c)-[:KNOWS]->(d) "
    "RETURN count(DISTINCT d.id) AS hubs"
)


# ---------------------------------------------------------------------------
# workloads
# ---------------------------------------------------------------------------


def validate_against_oracle() -> bool:
    """Every benchmarked query shape must agree with the local oracle on a
    small random graph, and the fused operators must be in the TPU plans."""
    from tpu_cypher import CypherSession

    rng = np.random.default_rng(7)
    n, e = 40, 160
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    parts = [f"(n{i}:Person {{id:{i * 7 + 1}}})" for i in range(n)]
    parts += [f"(n{s})-[:KNOWS]->(n{d})" for s, d in zip(src, dst)]
    create = "CREATE " + ", ".join(parts)

    g_local = CypherSession.local().create_graph_from_create_query(create)
    g_tpu = CypherSession.tpu().create_graph_from_create_query(create)
    params = {"lo": 7 * 5 + 1, "hi": 7 * 25 + 1}
    ok = True
    for q in (TWO_HOP, TWO_HOP_DISTINCT, TRIANGLE, VAR_LENGTH):
        lv = g_local.cypher(q, parameters=params).records.collect()
        tv = g_tpu.cypher(q, parameters=params).records.collect()
        if [dict(r) for r in lv] != [dict(r) for r in tv]:
            sys.stderr.write(f"VALIDATION FAILED for {q}: {lv} vs {tv}\n")
            ok = False
    for q, op_name in (
        (TWO_HOP, "CsrExpandOp"),
        (TRIANGLE, "CsrExpandIntoOp"),
        (VAR_LENGTH, "CsrVarExpandOp"),
    ):
        plans = g_tpu.cypher(q, parameters=params).plans
        if op_name not in plans:
            sys.stderr.write(f"VALIDATION FAILED: {op_name} not in plan for {q}\n")
            ok = False
    return ok


def _host_graph_stats(graph):
    """Host-side degree math for the metric + memory gates (NOT timed):
    2-hop path count and per-hop var-length frontier estimates."""
    from tpu_cypher.io.ldbc import EDGE_ID_OFFSET  # noqa: F401 (doc anchor)

    node_scan = [s for s in graph.scans if s.is_node][0]
    rel_scan = [s for s in graph.scans if not s.is_node][0]
    ids = np.asarray(node_scan.table._cols["id"].data)[: node_scan.table.size]
    src = np.asarray(rel_scan.table._cols["source"].data)[: rel_scan.table.size]
    dst = np.asarray(rel_scan.table._cols["target"].data)[: rel_scan.table.size]
    order = np.argsort(ids)
    ids_sorted = ids[order]
    s = np.searchsorted(ids_sorted, src)
    d = np.searchsorted(ids_sorted, dst)
    n = len(ids)
    outdeg = np.bincount(s, minlength=n).astype(np.int64)
    two_hop = int(outdeg[d].sum())
    return ids_sorted, s, d, outdeg, two_hop


def _tier_snapshot():
    # tier counters ride the unified obs registry since PR 4
    # (tpu_cypher_mxu_tier_total / _native_tier_total /
    # _pallas_launch_total); these dict views keep the per-rung tier
    # strings stable
    from tpu_cypher.backend.tpu import expand_op as X
    from tpu_cypher.backend.tpu.pallas import dispatch as PD

    from tpu_cypher.backend.tpu import wcoj as W

    return {
        **{f"mxu_{k}": v for k, v in X.MXU_TIER_COUNTS.items()},
        **{f"native_{k}": v for k, v in X.NATIVE_TIER_COUNTS.items()},
        # which tier answered each multiway-intersect pull (count /
        # materialize / shadow) — the per-rung tier strings record e.g.
        # "wcoj_count"
        **{f"wcoj_{k}": v for k, v in W.WCOJ_TIER_COUNTS.items()},
        # which Pallas kernels actually launched (vs fell back) — the
        # per-rung tier strings record e.g. "pallas_segment_agg"
        **{f"pallas_{k}": v["pallas"] for k, v in PD.use_counts().items()},
    }


def _metrics_snapshot():
    """The schema-versioned ``metrics`` object on the bench JSON line: a
    flat dump of the whole obs registry at end of run (compiles, tiers,
    fault sites, stage timings). Must never kill the line."""
    try:
        from tpu_cypher.obs.metrics import EVENT_SCHEMA_VERSION, REGISTRY

        return {"schema_version": EVENT_SCHEMA_VERSION, **REGISTRY.flat()}
    except Exception as exc:  # fault-ok: telemetry only
        return {"error": str(exc)[:200]}


def _lint_clean() -> dict:
    """Static-analyzer verdict for the engine tree this rung ran
    (``python -m tpu_cypher.analysis tpu_cypher/``): the trajectory records
    analyzer health next to the perf numbers, so an invariant regression
    (host-sync, recompile hazard, pad discipline...) shows up in the same
    JSON line as the BENCH delta it will eventually cause — and names the
    regressed rule, with per-rule finding counts rather than one opaque
    boolean. Never raises."""
    try:
        from tpu_cypher.analysis import engine_lint_summary

        return engine_lint_summary()
    except Exception as exc:  # fault-ok: telemetry only
        return {"clean": False, "findings_by_rule": {}, "error": str(exc)[:200]}


def _shape_facts() -> dict:
    """The abstract shape interpreter's engine-wide summary
    (``analysis.shapes``): how many padded-shape facts the sweep emitted
    and how many sites remain data-dependent (each one a declared
    exact-size boundary) vs lattice-bounded. A drop in ``bucketed_sites``
    or a rise in ``data_dependent_sites`` flags a shape-discipline
    regression in the same JSON line as the perf delta it will cause.
    Never raises (mirrors ``lint_clean``)."""
    try:
        from tpu_cypher.analysis.shapes import engine_shape_summary

        return engine_shape_summary()
    except Exception as exc:  # fault-ok: telemetry only
        return {
            "facts_emitted": 0,
            "data_dependent_sites": -1,
            "bucketed_sites": -1,
            "error": str(exc)[:200],
        }



def _mutation_soak() -> dict:
    """Mixed read/write serving health: the 90/10 soak against the
    WAL-backed delta-CSR store vs the read-only soak against the SAME
    primed store — identical lattice, identical serving stack, only the
    write stream differs. Both legs run with the result cache off so the
    ratio measures engine-bound serving capacity (with the cache on, the
    read-only side serves ~100% from cache while every write invalidates
    the mixed side's entries — a cache benchmark, not a write-cost one).
    ``recompiles_after_compaction`` is the across-compaction pin from
    docs/mutation.md: the mixed window spans multiple delta compactions
    and MUST stay 0. ``recovered_writes`` counts WAL batches replayed
    into a fresh store by the offline differential; acked writes missing
    after replay surface as failures. Never raises — a broken write path
    reports {"error": ...} instead of killing the bench."""
    try:
        tests_dir = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tests"
        )
        if tests_dir not in sys.path:
            sys.path.insert(0, tests_dir)
        import soak_serve

        mixed = soak_serve.main(budget_s=4.0, clients=16, write_ratio=0.1,
                                cache_bytes=0)
        read_only = soak_serve.main(budget_s=4.0, clients=16, mutable=True,
                                    cache_bytes=0)
        return {
            "mixed_qps": mixed["qps"],
            "read_only_qps": read_only["qps"],
            "ratio": round(mixed["qps"] / max(read_only["qps"], 1e-9), 3),
            "recovered_writes": mixed["recovered_writes"],
            "missing_committed_writes": mixed["missing_committed_writes"],
            "recompiles_after_compaction": mixed["recompiles_after_warmup"],
            "compactions": mixed["compactions"],
            "failures": mixed["failures"] + read_only["failures"],
        }
    except Exception as exc:  # fault-ok: telemetry only
        return {"error": str(exc)[:200]}


# what the legs that start child processes report as their platform: this
# process has touched JAX and holds the chip, and a chip belongs to one
# process, so their children are pinned to the CPU — and say so
_CHILDREN_ON_CPU = "cpu (children pinned: the bench process holds the chip)"


@contextlib.contextmanager
def _children_on_cpu():
    """Children started inside inherit ``JAX_PLATFORMS=cpu``; this
    process's own (already initialised) backend is unaffected."""
    before = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        yield
    finally:
        if before is None:
            os.environ.pop("JAX_PLATFORMS", None)
        else:
            os.environ["JAX_PLATFORMS"] = before


def _serve_soak() -> dict:
    """Serving-layer health for the trajectory: a short non-chaos soak of
    the multi-tenant query server (tests/soak_serve.py — concurrent
    clients, admission scheduling, micro-batching) distilled to the four
    numbers that regress: qps, p99_ms, recompiles_after_warmup (must stay
    0: the serving layer adds no shape churn), batched_dispatch_ratio
    (must stay > 0: bursts still coalesce), plus the result-cache leg
    (pre-cache baseline vs cached qps under repeat traffic) and the
    cursor-streaming leg (large result under the fixed RSS ceiling).
    Like ``lint_clean``, never raises — a broken server reports
    {"error": ...} in the same JSON line instead of killing the bench."""
    try:
        tests_dir = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tests"
        )
        if tests_dir not in sys.path:
            sys.path.insert(0, tests_dir)
        import soak_serve

        rep = soak_serve.main(budget_s=4.0, clients=24, chaos=False)
        out = {
            "qps": rep["qps"],
            "p99_ms": rep["p99_ms"],
            "recompiles_after_warmup": rep["recompiles_after_warmup"],
            "batched_dispatch_ratio": rep["batched_dispatch_ratio"],
            "failures": rep["failures"],
        }
    except Exception as exc:  # fault-ok: telemetry only
        return {"error": str(exc)[:200]}
    # result-cache leg: the same soak under dashboard-shaped traffic
    # (each client repeats its previous submission half the time), cache
    # off vs on — the honest pre-cache baseline and the speedup over it,
    # plus the hit ratio that explains the gap
    try:
        pre = soak_serve.main(budget_s=4.0, clients=24, repeat_ratio=0.5,
                              cache_bytes=0)
        hot = soak_serve.main(budget_s=4.0, clients=24, repeat_ratio=0.5)
        out["cache"] = {
            "qps_precache": pre["qps"],
            "qps_cached": hot["qps"],
            "speedup": round(hot["qps"] / max(pre["qps"], 1e-9), 2),
            "cache_hit_ratio": hot["cache_hit_ratio"],
            "failures": pre["failures"] + hot["failures"],
        }
    except Exception as exc:  # fault-ok: telemetry only
        out["cache"] = {"error": str(exc)[:200]}
    out["streaming"] = _serve_streaming()
    # cluster legs: the same soak through the multi-process router at 1
    # and 2 workers, under SIGKILL chaos at 2 — tracks whether replica
    # fan-out scales (scaling_efficiency = qps_2 / (2 * qps_1)) and
    # whether worker death stays invisible (failures must be 0)
    try:
        with _children_on_cpu():
            r1 = soak_serve.main(budget_s=4.0, clients=24, workers=1)
            r2 = soak_serve.main(budget_s=6.0, clients=24, workers=2,
                                 kill_workers=True)
        out["cluster"] = {
            "platform": _CHILDREN_ON_CPU,
            "qps_1w": r1["qps"],
            "qps_2w": r2["qps"],
            "scaling_efficiency": round(
                r2["qps"] / max(2 * r1["qps"], 1e-9), 3
            ),
            "workers": 2,
            "worker_kills": r2["worker_kills"],
            "worker_restarts": r2["worker_restarts"],
            "replica_retries": r2["replica_retries"],
            "failures": r1["failures"] + r2["failures"],
        }
    except Exception as exc:  # fault-ok: telemetry only
        out["cluster"] = {"error": str(exc)[:200]}
    return out


_SERVE_STREAMING_CODE = r"""
import asyncio, json, resource, time

from tpu_cypher.relational.session import CypherSession
from tpu_cypher.serve import QueryServer


def peak_rss_mb():
    # VmHWM, not ru_maxrss: a forked child's ru_maxrss starts at the
    # PARENT's resident size on Linux, polluting the reading
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmHWM:"):
                    return int(line.split()[1]) // 1024
    except OSError:
        pass
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss // 1024

N = 64  # N**3 = 262,144 rows through the cursor protocol

async def main():
    session = CypherSession.tpu()
    parts = [f"(n{i}:P {{id: {i}}})" for i in range(N)]
    graph = session.create_graph_from_create_query("CREATE " + ", ".join(parts))
    server = QueryServer(session, port=0)
    server.register_graph("g", graph)
    total, t0 = 0, None
    async with server:
        reader, writer = await asyncio.open_connection(server.host, server.port)
        sub = {"op": "submit", "id": "s", "graph": "g", "stream": True,
               "query": "MATCH (a:P), (b:P), (c:P) "
                        "RETURN a.id AS x, b.id AS y, c.id AS z"}
        writer.write((json.dumps(sub) + "\n").encode())
        await writer.drain()
        t0 = time.perf_counter()
        while True:
            msg = json.loads(await asyncio.wait_for(reader.readline(), 120))
            t = msg.get("type")
            if t == "rows":
                total += len(msg["rows"])
                writer.write((json.dumps({"op": "next", "id": "s"}) + "\n")
                             .encode())
                await writer.drain()
            elif t == "done":
                break
            elif t != "accepted":
                raise RuntimeError(json.dumps(msg)[:200])
        seconds = time.perf_counter() - t0
        writer.close()
    print(json.dumps({"rows": total, "peak_rss_mb": peak_rss_mb(),
                      "seconds": round(seconds, 3)}))

asyncio.run(main())
"""


def _serve_streaming() -> dict:
    """Cursor-streaming health: one large result (262k rows) pulled
    through the credit-window protocol in a subprocess (the memory
    high-water mark is process-lifetime, so the ceiling must be measured
    in its own process). Reports the fixed host-memory ceiling the test
    suite pins and the delivered row throughput. Never raises."""
    try:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        # a forced multi-device host platform (virtual-mesh test envs)
        # would multiply every device buffer; the ceiling is one-device
        env.pop("XLA_FLAGS", None)
        proc = subprocess.run(
            [sys.executable, "-c", _SERVE_STREAMING_CODE],
            capture_output=True, text=True, timeout=420, env=env,
        )
        if proc.returncode != 0:
            return {"error": (proc.stderr or proc.stdout)[-200:]}
        rep = json.loads(proc.stdout.strip().splitlines()[-1])
        return {
            "platform": _CHILDREN_ON_CPU,
            "rows": rep["rows"],
            "peak_rss_mb": rep["peak_rss_mb"],
            "ceiling_mb": 768,  # the pin in tests/test_serve.py
            "throughput_rows_s": int(rep["rows"] / max(rep["seconds"], 1e-9)),
        }
    except Exception as exc:  # fault-ok: telemetry only
        return {"error": str(exc)[:200]}


_MESH_SCALING_CODE = r"""
import json, os, sys, time
sys.path.insert(0, os.environ["_TPU_CYPHER_BENCH_DIR"])
import numpy as np
import jax
import bench
from tpu_cypher import CypherSession
from tpu_cypher.parallel import mesh as PM
from tpu_cypher.backend.tpu import bucketing

rng = np.random.default_rng(11)
n, e = 120, 900
src = rng.integers(0, n, e)
dst = rng.integers(0, n, e)
keep = src != dst
src, dst = src[keep], dst[keep]
parts = ["(n{}:Person {{id:{}}})".format(i, i + 1) for i in range(n)]
parts += ["(n{})-[:KNOWS]->(n{})".format(s, d) for s, d in zip(src, dst)]
g = CypherSession.tpu().create_graph_from_create_query(
    "CREATE " + ", ".join(parts)
)

def run_once():
    return [
        [dict(r) for r in g.cypher(q).records.collect()]
        for q in (bench.TWO_HOP, bench.TRIANGLE)
    ]

def leg(repeats=3):
    rows = run_once()  # warm: compiles + CSR build land here, not the timing
    t0 = time.perf_counter()
    for _ in range(repeats):
        run_once()
    return repeats * 2 / (time.perf_counter() - t0), rows

bucketing.install_compile_listener()
qps_1d, rows_1d = leg()
mesh = PM.make_row_mesh(jax.devices())
with PM.use_mesh(mesh):
    qps_8d, rows_8d = leg()
    before = bucketing.compile_snapshot()
    run_once()  # warm rerun: the per-shard lattice must add ZERO compiles
    shard_recompiles = bucketing.compile_delta(before)["compiles"]
print(json.dumps({
    "devices": jax.device_count(),
    "qps_1d": round(qps_1d, 2),
    "qps_8d": round(qps_8d, 2),
    "scaling_efficiency": round(
        qps_8d / max(jax.device_count() * qps_1d, 1e-9), 3
    ),
    "shard_recompiles": shard_recompiles,
    "rows_identical": rows_1d == rows_8d,
}))
"""


def _mesh_scaling() -> dict:
    """Mesh-execution health for the trajectory: two-hop + triangle on 1
    vs 8 VIRTUAL devices (``--xla_force_host_platform_device_count=8`` in
    a child's env — the parent process has already pinned its own device
    count, so the 8-device world needs a fresh interpreter). Reports
    ``qps_1d``/``qps_8d``/``scaling_efficiency`` (same convention as the
    serve-soak cluster leg: qps_8 / (8 * qps_1) — virtual devices on one
    host share the same cores, so this tracks SHARDING OVERHEAD, not real
    speedup) and ``shard_recompiles`` (a warm rerun under the mesh: the
    per-shard bucket lattice must add zero compiles). Like the other
    telemetry legs, never raises — a broken mesh path reports
    {"error": ...} instead of killing the JSON line."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    env["TPU_CYPHER_BUCKET"] = "pow2"
    env.pop("TPU_CYPHER_MESH", None)  # the legs pick their own meshes
    env["_TPU_CYPHER_BENCH_DIR"] = os.path.dirname(os.path.abspath(__file__))
    try:
        proc = subprocess.run(
            [sys.executable, "-c", _MESH_SCALING_CODE],
            capture_output=True, text=True, env=env, timeout=600,
        )
        for line in reversed(proc.stdout.splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    return {"platform": _CHILDREN_ON_CPU, **json.loads(line)}
                except ValueError:
                    continue
        tail = (proc.stderr + proc.stdout)[-300:]
        return {"error": f"child rc={proc.returncode} with no JSON line; "
                         f"tail: {tail}"}
    except Exception as exc:  # fault-ok: telemetry only
        return {"error": str(exc)[:200]}


def _time_query(g, query, params=None, repeats=3):
    """Median wall time of a warmed query (warmup compiles + builds CSR)
    plus WHICH tier answered (MXU dense/tiled, native C++, or the device
    frontier programs as the residual)."""
    out = g.cypher(query, parameters=params).records.collect()
    before = _tier_snapshot()
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        g.cypher(query, parameters=params).records.collect()
        times.append(time.perf_counter() - t0)
    after = _tier_snapshot()
    hits = sorted(k for k in after if after[k] > before[k])
    tier = "+".join(hits) if hits else "device"
    return float(np.median(times)), out, tier


# single-chip peaks (Google Cloud documentation, "TPU v5e"): the roofline
# denominators, keyed by ``jax.devices()[0].device_kind``. A device that is
# not in the table is an error, not a default.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "bytes": 819e9},  # bf16 FLOP/s, HBM B/s
}


def device_peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peak table entry for device_kind {device_kind!r} "
            f"(known: {sorted(PEAKS)}); add its published peaks with "
            "their source before reporting a roofline share on it"
        )
    return PEAKS[device_kind]


def _roofline(n: int, e: int, paths: int, dt: float, peaks) -> dict:
    """First-order model of the fused 2-hop count: stream row_ptr + both
    col_idx passes (4B lanes) and one multiply-add per edge-expansion.
    ``paths`` enters the flop count (each 2-hop path is one accumulate).
    ``peaks``: the device's ``device_peaks`` entry, or None on a CPU dry
    run — which reports the byte/flop MODEL only (utilization against a
    TPU peak would be meaningless)."""
    bytes_moved = 4.0 * (n + 1) + 8.0 * e + 8.0 * n
    flops = 2.0 * (e + paths)
    entry = {
        "est_bytes": int(bytes_moved),
        "est_flops": int(flops),
        "arith_intensity": round(flops / max(bytes_moved, 1.0), 4),
    }
    if peaks is not None and dt > 0:
        t_mem = bytes_moved / peaks["bytes"]
        t_cmp = flops / peaks["flops"]
        entry["bandwidth_util"] = round(bytes_moved / dt / peaks["bytes"], 6)
        entry["mfu"] = round(flops / dt / peaks["flops"], 6)
        entry["bound"] = "memory" if t_mem >= t_cmp else "compute"
        entry["roofline_frac"] = round(max(t_mem, t_cmp) / dt, 6)
    return entry


def _wcoj_vs_binary(
    g, feasible_binary: bool, est_rows: dict, budget_rows: int
) -> dict:
    """Triangle + 4-clique counting under the multiway-intersect plan vs
    the binary-join plan, in the same process on the same warm graph. The
    mode override works at PLAN time (``plan_multiway_intersect_fastpath``
    reads ``TPU_CYPHER_WCOJ`` per query), so each leg replans; counts must
    match bit-identically whenever both legs run.

    Each shape is gated by a host-side transient estimate — the same
    degrade-to-a-skip-note contract as the distinct rung, because an
    over-scaled leg OOM-kills the whole JSON line. Both transients are
    count-tier expanded-lane totals (lean ~40B lanes, so both get the
    distinct gate's x8 slack): triangle's is the sum of min end degrees,
    clique4's the 3-walk lane bound. Clique4 used to get NO slack because
    a multi-close pure count degraded to the acyclic shadow and
    materialized the 3-walk row set at fat sort-buffered width (the
    878M-row r06 note); the WCOJ count tier now answers multi-close
    shapes directly with range-count products, so the leg measures
    instead of recording an OOM skip."""
    from tpu_cypher.utils.config import WCOJ_MODE

    entry = {}
    for label, query, key, cap_mult in (
        ("triangle", TRIANGLE, "triangles", 8),
        ("clique4", CLIQUE4, "cliques", 8),
    ):
        est = int(est_rows[label])
        if est > budget_rows * cap_mult:
            entry[label] = {
                "wcoj_seconds": None,
                "binary_seconds": None,
                "skipped": f"transient rows {est} over budget",
            }
            continue
        WCOJ_MODE.set("force")
        try:
            dtw, outw, tierw = _time_query(g, query, repeats=1)
        finally:
            WCOJ_MODE.reset()
        leg = {
            "wcoj_seconds": round(dtw, 6),
            "count": int(outw[0][key]),
            "wcoj_tier": tierw,
        }
        # clique4's binary plan DOES materialize the 3-walk row set at fat
        # sort-buffered width — its sub-leg keeps the old no-slack bound
        # even though the WCOJ count leg above ran with lane slack
        # (triangle's binary transient is the 2-hop set, already covered
        # by ``feasible_binary``)
        if feasible_binary and (label != "clique4" or est <= budget_rows):
            WCOJ_MODE.set("off")
            try:
                dtb, outb, tierb = _time_query(g, query, repeats=1)
            finally:
                WCOJ_MODE.reset()
            leg["binary_seconds"] = round(dtb, 6)
            leg["binary_tier"] = tierb
            leg["counts_match"] = int(outb[0][key]) == leg["count"]
            leg["wcoj_speedup"] = round(dtb / max(dtw, 1e-9), 2)
        else:
            leg["binary_seconds"] = None
            leg["binary_skipped"] = "binary transient arrays over budget"
        entry[label] = leg
    entry["clique4_materialize"] = _factorized_materialize(
        g,
        est_lane_rows=est_rows.get("clique4_lanes", est_rows["clique4"]),
        est_flat_rows=est_rows["clique4"],
        budget_rows=budget_rows,
    )
    return entry


def _factorized_materialize(
    g, est_lane_rows: int, est_flat_rows: int, budget_rows: int
) -> dict:
    """The clique4 MATERIALIZE leg: the shape that used to record an
    unconditional ``transient rows ... over budget`` skip, because the
    flat 3-walk row set (878M rows at SF1, the r06 note) cannot be
    admitted. The factorized tier (``backend/tpu/factorized.py``) stores
    that intermediate as prefix lanes + per-lane suffix runs, so its
    transient is the LANE extent — the leg now measures under
    ``TPU_CYPHER_FACTORIZE=force`` and only degrades to a typed skip when
    the factorized (lane) estimate itself busts the budget. A flat
    comparison sub-leg runs when the flat estimate fits, yielding the
    ``factorized_vs_flat`` speedup; both sub-legs degrade to notes, never
    raises — an exception here must not kill the JSON line."""
    from tpu_cypher import errors as ERR
    from tpu_cypher.utils.config import FACTORIZE, WCOJ_MODE

    leg = {
        "est_lane_rows": int(est_lane_rows),
        "est_flat_rows": int(est_flat_rows),
    }
    # lanes are lean (prefix ids + run bounds), so the lane estimate gets
    # the same x8 slack as the count-tier legs above
    if est_lane_rows > budget_rows * 8:
        leg["factorized_seconds"] = None
        leg["skipped"] = (
            f"factorized lane rows {int(est_lane_rows)} over budget"
        )
        return leg
    WCOJ_MODE.set("force")
    FACTORIZE.set("force")
    try:
        dtf, outf, tierf = _time_query(g, CLIQUE4_MAT, repeats=1)
        leg["factorized_seconds"] = round(dtf, 6)
        leg["hubs"] = int(outf[0]["hubs"])
        leg["factorized_tier"] = tierf
    except ERR.AdmissionRejected as exc:
        leg["factorized_seconds"] = None
        leg["skipped"] = f"admission rejected: {exc}"[:200]
        return leg
    except Exception as exc:
        leg["factorized_seconds"] = None
        leg["error"] = f"{type(exc).__name__}: {exc}"[:200]
        return leg
    finally:
        WCOJ_MODE.reset()
        FACTORIZE.reset()
    if est_flat_rows <= budget_rows:
        WCOJ_MODE.set("force")
        FACTORIZE.set("off")
        try:
            dtl, outl, _ = _time_query(g, CLIQUE4_MAT, repeats=1)
            leg["flat_seconds"] = round(dtl, 6)
            leg["counts_match"] = int(outl[0]["hubs"]) == leg["hubs"]
            leg["factorized_vs_flat"] = round(dtl / max(dtf, 1e-9), 2)
        except Exception as exc:
            leg["flat_seconds"] = None
            leg["flat_error"] = f"{type(exc).__name__}: {exc}"[:200]
        finally:
            WCOJ_MODE.reset()
            FACTORIZE.reset()
    else:
        leg["flat_seconds"] = None
        leg["flat_skipped"] = f"flat rows {int(est_flat_rows)} over budget"
    return leg


def run_config(
    name: str, scale: float, session, results: dict, budget_rows: int,
    peaks=None,
):
    """One ladder rung: build the SNB graph, run the four shapes."""
    from tpu_cypher.io.ldbc import generate_snb
    from tpu_cypher.relational.session import PropertyGraph

    scan_graph = generate_snb(scale, session)
    g = PropertyGraph(session, scan_graph)
    ids_sorted, s, d, outdeg, two_hop_paths = _host_graph_stats(scan_graph)
    n, e = len(ids_sorted), len(s)
    expansions = e + two_hop_paths
    rung = {"nodes": n, "edges": e, "two_hop_paths": two_hop_paths}

    dt, out, tier = _time_query(g, TWO_HOP)
    if int(out[0]["c"]) != two_hop_paths:
        sys.stderr.write(
            f"ENGINE COUNT MISMATCH {name}: {out[0]['c']} != {two_hop_paths}\n"
        )
        results["validated"] = False
    rung["seconds_two_hop"] = round(dt, 6)
    rung["expansions_per_sec"] = round(expansions / dt, 1)
    rung["tier_two_hop"] = tier
    rung["roofline_two_hop"] = _roofline(n, e, two_hop_paths, dt, peaks)

    # the fused distinct path materializes one packed key per 2-hop row
    # (plus sort buffers); gate so an over-scaled run degrades to a skip
    # note instead of an OOM that kills the JSON line
    if two_hop_paths <= budget_rows * 8:
        dt, out, tier = _time_query(g, TWO_HOP_DISTINCT, repeats=1)
        rung["seconds_two_hop_distinct"] = round(dt, 6)
        rung["distinct_pairs"] = int(out[0]["pairs"])
        rung["tier_two_hop_distinct"] = tier
    else:
        rung["seconds_two_hop_distinct"] = None
        rung["distinct_skipped"] = f"2-hop rows {two_hop_paths} over budget"

    # the triangle always runs: oversized rungs route through the WCOJ
    # multiway intersection (auto eligibility — the degree-stats estimate
    # E*max_deg dwarfs TPU_CYPHER_WCOJ_MIN_ROWS at ladder scale), whose
    # count tier never materializes the 2-hop row set, so the old
    # ``triangle_skipped`` budget bail is gone
    dt, out, tier = _time_query(g, TRIANGLE, repeats=1)
    rung["seconds_triangle"] = round(dt, 6)
    rung["triangles"] = int(out[0]["triangles"])
    rung["tier_triangle"] = tier

    # host walk estimates w_k[v] = number of k-walks from v, by iterated
    # degree-weighted SpMV: sized both the WCOJ gate and the var-length
    # source window below
    w1 = outdeg.astype(np.float64)
    w2 = np.bincount(s, weights=w1[d], minlength=n) if e else np.zeros(n)
    w3 = np.bincount(s, weights=w2[d], minlength=n) if e else np.zeros(n)

    # WCOJ-vs-binary differential rung: the same cyclic shapes timed under
    # both plans in the same run (the ISSUE-10 / ROADMAP-2 acceptance
    # measurement). Each leg is skipped when its transient arrays would
    # blow the budget — exactly the regime WCOJ exists for.
    min_deg_sum = int(np.minimum(outdeg[s], outdeg[d]).sum()) if e else 0
    rung["wcoj_vs_binary"] = _wcoj_vs_binary(
        g,
        feasible_binary=two_hop_paths <= budget_rows * 8,
        # clique4_lanes: the factorized materialize stores lanes (triangle
        # prefixes, bounded by the 2-walk count), not the flat 3-walk set
        est_rows={
            "triangle": min_deg_sum,
            "clique4": int(w3.sum()),
            "clique4_lanes": int(w2.sum()),
        },
        budget_rows=budget_rows,
    )

    # var-length: pick a mid-range source-id window (away from the zipf
    # hubs at low ids) sized so the projected <=3-hop walk count stays
    # within budget (walks are genuinely materialized rows — Cypher
    # edge-uniqueness needs per-path state).
    est = w1 + w2 + w3
    start = n // 2
    cum = np.cumsum(est[start:])
    k = max(1, int(np.searchsorted(cum, budget_rows)))
    k = min(k, n - start)
    lo = int(ids_sorted[start])
    # exclusive upper bound: one past the last window id (ids are sorted)
    hi = int(ids_sorted[start + k - 1]) + 1
    dt, out, tier = _time_query(g, VAR_LENGTH, params={"lo": lo, "hi": hi}, repeats=1)
    rung["seconds_var_length"] = round(dt, 6)
    rung["tier_var_length"] = tier
    rung["var_length_walks"] = int(out[0]["walks"])
    rung["var_length_sources"] = k
    rung["walks_per_sec"] = round(int(out[0]["walks"]) / max(dt, 1e-9), 1)

    results["ladder"][name] = rung
    return rung


# join-order leg: chain/cycle shapes with skewed label/type selectivities —
# the regime where the cost-based optimizer's anchor + order choice departs
# from syntax order. Each query is timed under TPU_CYPHER_OPT=syntax and
# =force on the same warm graph (the plan-cache key carries the mode, so
# each leg replans); counts must agree or the leg reports the mismatch.
_JOIN_ORDER_QUERIES = (
    ("rare_last", "MATCH (a:Person)-[:KNOWS]->(b:Person)-[:RARE]->(c:Admin) "
                  "RETURN count(*) AS c"),
    ("rare_mid", "MATCH (a:Person)-[:KNOWS]->(b)-[:RARE]->(c)-[:KNOWS]->(d:Person) "
                 "RETURN count(*) AS c"),
    ("label_last", "MATCH (a:Person)-[:KNOWS]->(b:Person)-[:KNOWS]->(c:Admin) "
                   "RETURN count(*) AS c"),
    ("cycle_close", "MATCH (a:Person)-[:KNOWS]->(b:Person)-[:RARE]->(c)-[:KNOWS]->(a) "
                    "RETURN count(*) AS c"),
    ("filter_hoist", "MATCH (a:Person)-[:KNOWS]->(b:Person)-[:KNOWS]->(c:Admin) "
                     "WHERE c.id < 40 RETURN count(*) AS c"),
)


def _join_order_graph(session):
    """Skewed two-label / two-reltype graph, built from arrays (a CREATE
    string at this scale would spend the whole leg parsing). Big enough
    that expand cost is row-volume-bound — the regime the padded-row cost
    model prices — rather than fixed per-operator overhead: ~30k nodes
    (1-in-50 Admin), 300k KNOWS, 600 RARE."""
    from tpu_cypher.api import types as T
    from tpu_cypher.api.mapping import NodeMapping, RelationshipMapping
    from tpu_cypher.api.schema import PropertyGraphSchema
    from tpu_cypher.relational.graphs import ElementTable, ScanGraph

    rng = np.random.default_rng(17)
    n, dense_e, rare_e = 30_000, 300_000, 600
    ids = np.arange(n, dtype=np.int64)
    admin = ids % 50 == 0
    prop_types = {"id": T.CTInteger.nullable}

    def rel_edges(count, id_base):
        src = rng.integers(0, n, count)
        dst = rng.integers(0, n, count)
        keep = src != dst
        src, dst = src[keep], dst[keep]
        eids = np.arange(len(src), dtype=np.int64) + id_base
        return session.table_cls.from_arrays(
            {"id": eids, "source": src, "target": dst}
        )

    tables = []
    for label, mask in (("Person", ~admin), ("Admin", admin)):
        tables.append(
            ElementTable(
                NodeMapping(
                    id_key="id",
                    implied_labels=frozenset({label}),
                    property_mapping=(("id", "id"),),
                ),
                session.table_cls.from_arrays({"id": ids[mask]}),
            )
        )
    for rtype, table in (
        ("KNOWS", rel_edges(dense_e, 1 << 40)),
        ("RARE", rel_edges(rare_e, 1 << 41)),
    ):
        tables.append(
            ElementTable(
                RelationshipMapping(
                    id_key="id",
                    source_key="source",
                    target_key="target",
                    rel_type=rtype,
                ),
                table,
            )
        )
    schema = (
        PropertyGraphSchema.empty()
        .with_node_combination(frozenset({"Person"}), prop_types)
        .with_node_combination(frozenset({"Admin"}), prop_types)
        .with_relationship_type("KNOWS", {})
        .with_relationship_type("RARE", {})
    )
    from tpu_cypher.relational.session import PropertyGraph

    return PropertyGraph(session, ScanGraph(tables, schema))


def _join_order_leg(session) -> dict:
    """Optimizer-vs-syntax join-order speedup per query (the ISSUE-14 /
    ROADMAP-2 acceptance measurement): wins_frac is the share of queries
    the model's order beats syntax order, max_regression the worst
    optimizer/syntax slowdown. Regression-gated in CI by
    tests/test_optimizer.py on result equality; the timing ratios ride
    the trajectory here. Never raises — an over-scaled or faulted leg
    degrades to an error note."""
    from tpu_cypher.utils.config import OPT_MODE

    try:
        g = _join_order_graph(session)
        queries = {}
        wins = 0
        worst = 1.0
        mismatches = 0
        for name, query in _JOIN_ORDER_QUERIES:
            OPT_MODE.set("syntax")
            try:
                dts, outs, _ = _time_query(g, query, repeats=3)
            finally:
                OPT_MODE.reset()
            OPT_MODE.set("force")
            try:
                dto, outo, _ = _time_query(g, query, repeats=3)
            finally:
                OPT_MODE.reset()
            match = outs == outo
            speedup = dts / max(dto, 1e-9)
            wins += speedup > 1.0
            worst = min(worst, speedup)
            mismatches += not match
            queries[name] = {
                "syntax_seconds": round(dts, 6),
                "optimizer_seconds": round(dto, 6),
                "speedup": round(speedup, 3),
                "rows_match": match,
            }
        return {
            "queries": queries,
            "wins_frac": round(wins / len(_JOIN_ORDER_QUERIES), 3),
            "max_regression": round(worst, 3),
            "mismatches": mismatches,
        }
    except Exception as exc:  # fault-ok: telemetry only
        return {"error": str(exc)[:200]}


def main():
    force_cpu = os.environ.get("TPU_CYPHER_BENCH_FORCE_CPU") == "1"
    if force_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    dev = jax.devices()[0]
    tpu_ok = dev.platform == "tpu"
    if not tpu_ok and not force_cpu:
        raise RuntimeError(
            f"bench.py measures the chip, and JAX's platform here is "
            f"{dev.platform!r}. There is no CPU fallback; "
            "TPU_CYPHER_BENCH_FORCE_CPU=1 asks for the CPU dry run "
            "(counts and correctness only)."
        )
    peaks = device_peaks(dev.device_kind) if tpu_ok else None

    from tpu_cypher import CypherSession

    scale_mult = float(os.environ.get("TPU_CYPHER_BENCH_SCALE", "1.0"))
    results = {"ladder": {}, "validated": validate_against_oracle()}

    session = CypherSession.tpu()
    # the full ladder runs at BOTH scales on any device: since round 4 the
    # count shapes never materialize their row sets (native stamping / DFS
    # kernels on host, fused walks + MXU matmuls on TPU), so SF10
    # (~100k persons / ~4.5M KNOWS) costs under a second per shape on CPU
    configs = [
        ("SF1", 1.0 * scale_mult, 20_000_000),
        ("SF10", 10.0 * scale_mult, 60_000_000),
    ]
    for name, scale, budget in configs:
        rung = run_config(name, scale, session, results, budget, peaks=peaks)
        headline, headline_name = rung, name  # last rung wins

    rate = headline["expansions_per_sec"]
    result = {
        "metric": "edge_expansions_per_sec_2hop_engine",
        "value": rate,
        "unit": "expansions/s",
        # a CPU run is not comparable to the TPU north star — report 0
        "vs_baseline": round(rate / NORTH_STAR, 4) if tpu_ok else 0.0,
        "validated_vs_engine": results["validated"],
        "measured_callable": "CypherSession.tpu() g.cypher(...) pipeline",
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "headline_config": headline_name,
        "ladder": results["ladder"],
        "metrics": _metrics_snapshot(),
        # analyzer health rides the trajectory: False here means a rung ran
        # with unsuppressed invariant violations (tpu_cypher.analysis)
        "lint_clean": _lint_clean(),
        # the shape interpreter's fact counts: data_dependent_sites up or
        # bucketed_sites down means a compile-cache-stability regression
        "shape_facts": _shape_facts(),
        # serving-layer health (multi-tenant query server): qps/p99 of a
        # short concurrent soak + the two regression tripwires
        # (recompiles_after_warmup, batched_dispatch_ratio)
        "serve_soak": _serve_soak(),
        # mixed read/write serving health against the delta-CSR store:
        # {mixed_qps, read_only_qps, ratio, recovered_writes,
        # recompiles_after_compaction} — the ISSUE-17 acceptance numbers
        "mutation_soak": _mutation_soak(),
        # mesh-execution health: 1d vs 8d virtual-device qps for two-hop +
        # triangle, plus the zero-warm-recompile proof of the per-shard
        # bucket lattice ({qps_1d, qps_8d, scaling_efficiency,
        # shard_recompiles})
        "mesh_scaling": _mesh_scaling(),
        # cost-based optimizer health: per-query optimizer-vs-syntax
        # join-order speedups ({queries, wins_frac, max_regression,
        # mismatches}) — the ISSUE-14 acceptance measurement
        "join_order": _join_order_leg(session),
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()

"""Microbenchmarks: the metrics the reference's JMH harness defines.

The reference ships a JMH module with two benchmarks and no recorded
results (SURVEY §6): DataFrame self-join throughput as a function of the
element-id REPRESENTATION (``morpheus-jmh/.../JoinBenchmark.scala:40-120``
— Long vs Array[Long] vs String vs varint byte[]), and multi-column concat
cost (``ConcatColumnBenchmark.scala:44-68`` — concat_ws vs codegen
serialize). This is the TPU-native equivalent:

* join throughput over int64 ids, graph-TAGGED int64 ids (high-bits tag —
  our EncodeLong/AddPrefix replacement), dictionary-coded strings, and f64
  keys — all through ``TpuTable.join``;
* composite-key factorization cost: multi-key lexsort vs the packed
  single-int64 sort (our Serialize.scala replacement) via ``distinct``;
* column concat (``union_all``) for plain vs vocab-remapped strings.

Prints one JSON line per metric:
  {"metric": ..., "value": ..., "unit": "rows/s", ...}

Run:  python benchmarks/micro.py   (on whatever platform JAX selects;
      JAX_PLATFORMS=cpu for a CPU run — every kernel line names it)
Env:  MICRO_ROWS (default 200000), MICRO_REPS (default 3)
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def _bench(fn, reps):
    """(median warm seconds, warm-phase compile count). A nonzero compile
    count in the TIMED phase means the metric is measuring XLA, not the
    kernel — the compiled-once/run-many regression signal per metric."""
    from tpu_cypher.backend.tpu import bucketing

    fn()  # warm (compile caches, vocab builds)
    before = bucketing.compile_snapshot()
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    compiles = bucketing.compile_delta(before)["compiles"]
    return float(np.median(times)), int(compiles)


def main():
    rows = int(os.environ.get("MICRO_ROWS", "200000"))
    reps = int(os.environ.get("MICRO_REPS", "3"))

    from tpu_cypher.backend.tpu import bucketing
    from tpu_cypher.backend.tpu.table import TpuTable

    bucketing.install_compile_listener()

    rng = np.random.default_rng(11)
    build_n = rows // 2
    probe_ids = rng.integers(0, build_n, rows).astype(np.int64)
    build_ids = np.arange(build_n, dtype=np.int64)
    payload = rng.standard_normal(build_n)

    def emit(metric, bench_out, n=rows, **extra):
        secs, compiles = bench_out
        out = {
            "metric": metric,
            "value": round(n / secs, 1),
            "unit": "rows/s",
            "seconds": round(secs, 6),
            # compiles observed in the TIMED (warm) reps: nonzero means the
            # metric measured XLA compilation, not the kernel
            "compiles_warm": compiles,
        }
        out.update(extra)
        print(json.dumps(out))

    # -- join throughput by key representation ---------------------------
    l_int = TpuTable.from_numpy({"k": probe_ids})
    r_int = TpuTable.from_numpy({"j": build_ids, "p": payload})
    emit(
        "join_int64_ids",
        _bench(lambda: l_int.join(r_int, "inner", [("k", "j")]), reps),
    )

    tag = np.int64(3) << 54  # graph tag in high bits (EncodeLong/AddPrefix analog)
    l_tag = TpuTable.from_numpy({"k": probe_ids | tag})
    r_tag = TpuTable.from_numpy({"j": build_ids | tag, "p": payload})
    emit(
        "join_tagged_int64_ids",
        _bench(lambda: l_tag.join(r_tag, "inner", [("k", "j")]), reps),
    )

    strs = np.array([f"id{v:08d}" for v in range(build_n)])
    l_str = TpuTable.from_columns({"k": strs[probe_ids % build_n].tolist()})
    r_str = TpuTable.from_columns({"j": strs.tolist(), "p": payload.tolist()})
    emit(
        "join_string_ids",
        _bench(lambda: l_str.join(r_str, "inner", [("k", "j")]), reps),
    )

    l_f = TpuTable.from_numpy({"k": probe_ids.astype(np.float64)})
    r_f = TpuTable.from_numpy({"j": build_ids.astype(np.float64), "p": payload})
    emit(
        "join_float_keys",
        _bench(lambda: l_f.join(r_f, "inner", [("k", "j")]), reps),
    )

    # -- composite-key distinct: packed single sort (Serialize analog) ---
    a = rng.integers(0, 1000, rows).astype(np.int64)
    b = rng.integers(0, 1000, rows).astype(np.int64)
    t2 = TpuTable.from_numpy({"a": a, "b": b})
    emit("distinct_two_int_keys_packed", _bench(lambda: t2.distinct(["a", "b"]), reps))
    emit(
        "distinct_count_two_int_keys",
        _bench(lambda: t2.distinct_count(["a", "b"]), reps),
    )

    # -- column concat (union_all) ---------------------------------------
    emit(
        "union_all_int_columns",
        _bench(lambda: l_int.union_all(l_int), reps),
        n=rows * 2,
    )
    # two DISTINCT overlapping vocabularies so the union exercises a real
    # vocab merge + code remap
    vhalf = build_n // 2
    s1 = TpuTable.from_columns({"k": strs[:vhalf].tolist()})
    s2 = TpuTable.from_columns({"k": strs[vhalf // 2 : vhalf // 2 + vhalf].tolist()})
    emit(
        "union_all_string_columns_vocab_merge",
        _bench(lambda: s1.union_all(s2), reps),
        n=2 * vhalf,
    )

    # -- engine cold vs warm: plan -> records latency --------------------
    # The production signal behind shape bucketing + the persistent cache:
    # a COLD query pays parse/plan/compile, a WARM re-run of the same plan
    # should pay dispatch only (compiles_warm == 0). With
    # TPU_CYPHER_BUCKET set, re-running at a different MICRO_ROWS keeps
    # compiles_cold near zero too once the bucket lattice is warm.
    from tpu_cypher import CypherSession
    from tpu_cypher.io.ldbc import generate_snb
    from tpu_cypher.relational.session import PropertyGraph

    session = CypherSession.tpu()
    g = PropertyGraph(session, generate_snb(0.1, session))
    two_hop = (
        "MATCH (a:Person)-[:KNOWS]->(b:Person)-[:KNOWS]->(c:Person) "
        "RETURN count(*) AS c"
    )

    def run_once():
        t0 = time.perf_counter()
        before = bucketing.compile_snapshot()
        result = session.cypher(two_hop, graph=g)
        result.records.collect()
        compiles = bucketing.compile_delta(before)["compiles"]
        # per-phase span summary from the obs trace (rounded ms; phases
        # absent on a plan-cache hit stay absent — that IS the signal)
        phases = {
            k: round(v * 1000.0, 3)
            for k, v in result.profile(execute=False).phase_seconds().items()
        }
        return (time.perf_counter() - t0) * 1000.0, int(compiles), phases

    cold_ms, cold_compiles, cold_phases = run_once()
    warm = [run_once() for _ in range(reps)]
    warm_ms = float(np.median([w[0] for w in warm]))
    print(json.dumps({
        "metric": "plan_to_result_ms_2hop",
        "value": round(warm_ms, 3),
        "unit": "ms",
        "cold_ms": round(cold_ms, 3),
        "warm_ms": round(warm_ms, 3),
        "compiles_cold": cold_compiles,
        "compiles_warm": int(sum(w[1] for w in warm)),
        "bucket_mode": bucketing.mode(),
    }))
    # cold-vs-warm per-phase breakdown: where the cold-path milliseconds
    # go (parse/plan/execute/collect) vs the warmed re-run — the span-tree
    # view of the same cold/warm story as compiles_cold/compiles_warm
    print(json.dumps({
        "metric": "phase_spans_2hop",
        "value": round(sum(warm[-1][2].values()), 3),
        "unit": "ms",
        "cold_ms": round(sum(cold_phases.values()), 3),
        "warm_ms": round(sum(warm[-1][2].values()), 3),
        "cold": cold_phases,
        "warm": warm[-1][2],
    }))
    # -- bucket-reuse proof: a DIFFERENT row count, zero new compiles ----
    # With TPU_CYPHER_BUCKET set, re-running the warmed join at another
    # size INSIDE the warmed bucket must compile nothing: the acceptance
    # signal that the lattice, not the exact size, keys programs. The
    # second size is derived from the bucket (3/4 of the bucket cap is
    # always in (cap/2, cap], i.e. the same bucket as ``rows``) — a naive
    # fraction of MICRO_ROWS can fall into the bucket below.
    if bucketing.enabled():
        cap = bucketing.round_size(rows)
        rows2 = cap * 3 // 4 if cap * 3 // 4 != rows else cap * 5 // 8
        build2 = rows2 // 2
        l2 = TpuTable.from_numpy(
            {"k": rng.integers(0, build2, rows2).astype(np.int64)}
        )
        r2 = TpuTable.from_numpy(
            {"j": np.arange(build2, dtype=np.int64),
             "p": rng.standard_normal(build2)}
        )
        before = bucketing.compile_snapshot()
        l2.join(r2, "inner", [("k", "j")])
        print(json.dumps({
            "metric": "join_rebucket_compiles",
            "value": bucketing.compile_delta(before)["compiles"],
            "unit": "xla_compiles",
            "rows": rows2,
            "warmed_rows": rows,
            "bucket_mode": bucketing.mode(),
        }))

    _kernel_tier_benches(rows, reps)

    print(json.dumps({
        "metric": "compile_count",
        "value": bucketing.compile_count(),
        "unit": "xla_compiles",
        **bucketing.compile_snapshot(),
        "bucket_mode": bucketing.mode(),
        "persistent_cache_dir": bucketing.persistent_cache_dir(),
    }))


def _kernel_tier_benches(rows, reps):
    """Pallas-vs-jnp microbench (cold and warm) for the kernel behind
    ``backend/tpu/pallas/``, next to the formulation it replaces. Off-TPU
    the Pallas program runs INTERPRETED — those numbers prove parity and
    cache behavior, not speed (``pallas_mode`` says which was measured;
    the jnp number is the honest CPU baseline)."""
    import jax
    import jax.numpy as jnp

    from tpu_cypher.backend.tpu import jit_ops as J
    from tpu_cypher.backend.tpu.pallas import aggregate as PA

    on_tpu = jax.default_backend() == "tpu"
    interpret = not on_tpu
    rng = np.random.default_rng(23)

    def timed_ms(fn):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())  # cold: includes compile
        cold = (time.perf_counter() - t0) * 1000.0
        warms = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            warms.append((time.perf_counter() - t0) * 1000.0)
        return cold, float(np.median(warms))

    # masked grouped count
    k = 64
    data = jnp.asarray(rng.integers(-1000, 1000, rows))
    valid = jnp.asarray(rng.random(rows) < 0.9)
    seg = jnp.asarray(rng.integers(0, k, rows))
    cold, warm = timed_ms(
        lambda: PA._segment_aggregate_pallas(
            data, valid, seg, name="count", k=k, interpret=interpret
        )
    )
    _, jnp_warm = timed_ms(
        lambda: J.segment_aggregate(
            data, valid, None, seg, name="count", kind="i64", k=k
        )
    )
    print(json.dumps({
        "metric": "pallas_segment_agg",
        "value": round(warm, 3),
        "unit": "ms",
        "cold_ms": round(cold, 3),
        "warm_ms": round(warm, 3),
        "jnp_warm_ms": round(jnp_warm, 3),
        "platform": jax.default_backend(),
        "pallas_mode": "compiled" if on_tpu else "interpret",
        "speedup_vs_jnp": round(jnp_warm / max(warm, 1e-9), 3),
    }))


if __name__ == "__main__":
    main()

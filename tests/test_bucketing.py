"""Shape-bucketed execution (``TPU_CYPHER_BUCKET``): correctness + the
compiled-once/run-many regression.

Two guarantees under test:

* DIFFERENTIAL — bucketing changes WHICH static sizes programs compile at
  (rounded up the lattice, true counts traced, pad lanes masked dead), and
  must never change a result: every corpus query returns the identical
  record bag under ``pow2``/``1.25`` and ``off``.
* NO-RECOMPILE — the whole point of the lattice: re-running the same plan
  shape at a DIFFERENT data size whose counts share the warmed buckets
  must compile zero new XLA programs (the ``jax.monitoring``-fed counter
  in ``backend.tpu.bucketing`` observes real ``backend_compile`` events
  only — jit-cache hits count nothing).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from tpu_cypher import CypherSession
from tpu_cypher.backend.tpu import bucketing


@pytest.fixture
def bucket_mode(request):
    """In-process override of TPU_CYPHER_BUCKET, always reset."""
    bucketing.MODE.set(request.param)
    yield request.param
    bucketing.MODE.reset()


# ---------------------------------------------------------------------------
# differential: bucketed records == off records, query corpus
# ---------------------------------------------------------------------------

# one seeded random graph: labels, props with nulls, parallel structure,
# loops excluded (kept simple — loop semantics are covered elsewhere)
def _create_query(n=29, e=61, seed=7):
    rng = np.random.default_rng(seed)
    parts = []
    for i in range(n):
        props = [f"id:{i * 3 + 1}"]
        if i % 4 != 0:  # every 4th node: null age
            props.append(f"age:{int(rng.integers(18, 70))}")
        props.append(f"name:'p{i:02d}'")
        label = "Person" if i % 5 else "Admin:Person"
        parts.append(f"(n{i}:{label} {{{', '.join(props)}}})")
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    for s, d in zip(src, dst):
        if s == d:
            continue
        since = int(rng.integers(2000, 2024))
        parts.append(f"(n{s})-[:KNOWS {{since:{since}}}]->(n{d})")
    return "CREATE " + ", ".join(parts)


# the acceptance-suite shapes, one query per device code path: scans,
# filters, expands (directed/undirected/2-hop/into/var-length/optional),
# joins, aggregation, distinct, order/limit, union, unwind, coalesce
CORPUS = [
    "MATCH (a:Person) RETURN a.name, a.age ORDER BY a.name",
    "MATCH (a:Person) WHERE a.age > 40 RETURN count(*) AS c",
    "MATCH (a:Person) WHERE a.age IS NULL RETURN a.name ORDER BY a.name",
    "MATCH (a:Admin) RETURN count(*) AS c",
    "MATCH (a:Person)-[r:KNOWS]->(b:Person) RETURN a.name, b.name, r.since",
    "MATCH (a:Person)-[:KNOWS]->(b) WHERE b.age >= 30 RETURN a.name, b.age",
    "MATCH (a)-[:KNOWS]-(b) RETURN count(*) AS c",
    "MATCH (a:Person)-[:KNOWS]->(b:Person)-[:KNOWS]->(c:Person) "
    "RETURN count(*) AS c",
    "MATCH (a:Person)-[:KNOWS]->(b:Person)-[:KNOWS]->(c:Person) "
    "WITH DISTINCT a, c RETURN count(*) AS pairs",
    "MATCH (a)-[:KNOWS]->(b), (b)-[:KNOWS]->(c), (a)-[:KNOWS]->(c) "
    "RETURN count(*) AS tri",
    "MATCH (a:Person)-[:KNOWS*1..3]->(b:Person) RETURN count(*) AS walks",
    "MATCH (a:Person) OPTIONAL MATCH (a)-[:KNOWS]->(b) "
    "RETURN a.name, b.name",
    "MATCH (a:Person)-[r:KNOWS]->(b) RETURN r.since AS y, count(*) AS c "
    "ORDER BY c DESC, y LIMIT 7",
    "MATCH (a:Person) RETURN DISTINCT a.age AS age ORDER BY age",
    "MATCH (a:Person) RETURN sum(a.age) AS s, min(a.age) AS lo, "
    "max(a.age) AS hi, avg(a.age) AS m",
    "MATCH (a:Admin) RETURN a.name AS x UNION ALL "
    "MATCH (b:Person) WHERE b.age < 25 RETURN b.name AS x",
    "UNWIND [1, 2, 3, 4] AS v RETURN v * 2 AS d",
    "MATCH (a:Person) RETURN coalesce(a.age, -1) AS age ORDER BY age",
    "MATCH (a:Person) WITH a.age AS age WHERE age > 30 "
    "RETURN count(*) AS c",
    "MATCH (a:Person)-[:KNOWS]->(b) WHERE a.age > b.age "
    "RETURN a.name, b.name",
]


@pytest.mark.parametrize("bucket_mode", ["pow2", "1.25"], indirect=True)
def test_bucketed_records_identical_to_off(bucket_mode):
    create = _create_query()
    bucketing.MODE.set("off")
    g_off = CypherSession.tpu().create_graph_from_create_query(create)
    expected = {q: g_off.cypher(q).records.to_bag() for q in CORPUS}
    bucketing.MODE.set(bucket_mode)
    g_on = CypherSession.tpu().create_graph_from_create_query(create)
    for q in CORPUS:
        got = g_on.cypher(q).records.to_bag()
        assert got == expected[q], (
            f"\nbucket mode {bucket_mode} diverged\nquery: {q}"
            f"\ngot: {got!r}\nexpected: {expected[q]!r}"
        )


# grouped aggregations and ORDER BY over tail-padded tables: the pad-aware
# group (``TpuTable._group_bucketed``) and the sort with the pad last, and
# the shapes that decline to the exact path (DISTINCT, collect)
GROUPED = [
    "MATCH (a:Person) RETURN a.age AS age, count(*) AS c, sum(a.id) AS s "
    "ORDER BY age",
    "MATCH (a:Person) RETURN a.age % 7 AS r, min(a.name) AS lo, "
    "max(a.age) AS hi, avg(a.id) AS m, count(a.age) AS n ORDER BY r DESC",
    "MATCH (a:Person) WITH a.age % 5 AS r, count(*) AS c "
    "RETURN c, count(*) AS k, sum(r) AS s ORDER BY c",
    "MATCH (a:Person)-[r:KNOWS]->(b) RETURN a.name AS a, r.since % 3 AS y, "
    "count(*) AS c ORDER BY a DESC, y",
    "MATCH (a:Person) WHERE a.age > 30 RETURN a.age AS age, count(*) AS c",
    "MATCH (a:Person) RETURN a.age AS age, count(DISTINCT a.name) AS c "
    "ORDER BY age",
    "MATCH (a:Person) RETURN a.age % 3 AS r, collect(a.id) AS ids ORDER BY r",
    "MATCH (a:Person) RETURN a.name AS n ORDER BY a.age DESC, n",
]


@pytest.mark.parametrize("query", GROUPED)
@pytest.mark.parametrize("bucket_mode", ["pow2", "1.25"], indirect=True)
def test_bucketed_grouping_identical_to_off(bucket_mode, query):
    create = _create_query(n=53, e=140, seed=11)
    bucketing.MODE.set("off")
    expected = CypherSession.tpu().create_graph_from_create_query(
        create
    ).cypher(query).records.collect()
    bucketing.MODE.set(bucket_mode)
    got = CypherSession.tpu().create_graph_from_create_query(
        create
    ).cypher(query).records.collect()
    assert [dict(r) for r in got] == [dict(r) for r in expected]
    local = CypherSession.local().create_graph_from_create_query(
        create
    ).cypher(query).records.collect()
    assert [dict(r) for r in got] == [dict(r) for r in local]


# ---------------------------------------------------------------------------
# no-recompile regression: same plan, different data sizes, shared buckets
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "query",
    [
        # the groups stay three; the rows move within their bucket
        "MATCH (a:P) RETURN a.x % 3 AS r, count(*) AS c, sum(a.x) AS s "
        "ORDER BY r",
        # the groups move with the rows, within their bucket too
        "MATCH (a:P) WITH a.x AS x, count(*) AS c "
        "RETURN x, c ORDER BY x DESC",
    ],
)
@pytest.mark.parametrize("bucket_mode", ["pow2"], indirect=True)
def test_group_and_order_no_recompile_within_bucket(bucket_mode, query):
    session = CypherSession.tpu()

    def run(n):
        g = _ring_graph(session, n)
        g.cypher("MATCH (a:P) RETURN count(*) AS c").records.collect()
        before = bucketing.compile_snapshot()
        rows = g.cypher(query).records.collect()
        assert sum(r["c"] for r in rows) == n
        return bucketing.compile_delta(before)["compiles"]

    run(40)
    assert run(48) == 0
    assert run(56) == 0


def test_round_fine_lattice():
    assert bucketing.round_fine(1) == bucketing.FINE_FLOOR
    assert bucketing.round_fine(4097) == 4096 + 128
    # every graph500-22 drawn from a seed: one size, 1.2% over the count
    sizes = {bucketing.round_fine(n) for n in (2_394_573, 2_396_090, 2_396_798)}
    assert sizes == {37 * 2**16}
    for n in (5_000, 65_645, 448_626, 2**22 - 1, 2**22):
        r = bucketing.round_fine(n)
        assert n <= r <= n * 1.032 and r <= bucketing.round_up_pow2(n)


def _ring_graph(session, n):
    """Deterministic n-cycle: every intermediate count equals n, so all
    sizes in (32, 64] land in identical buckets across the whole plan."""
    parts = [f"(n{i}:P {{x:{i}}})" for i in range(n)]
    parts += [f"(n{i})-[:R]->(n{(i + 1) % n})" for i in range(n)]
    return session.create_graph_from_create_query("CREATE " + ", ".join(parts))


@pytest.mark.parametrize("bucket_mode", ["pow2"], indirect=True)
def test_two_hop_no_recompile_across_graph_sizes(bucket_mode):
    session = CypherSession.tpu()
    query = "MATCH (a:P)-[:R]->(b:P)-[:R]->(c:P) RETURN a.x, c.x"

    def run(n):
        # the window covers ingest + index build + plan execution: every
        # compile anywhere on the path counts
        before = bucketing.compile_snapshot()
        g = _ring_graph(session, n)
        result = g.cypher(query)
        rows = result.records.collect()
        assert len(rows) == n  # ring: exactly one 2-hop path per node
        assert result.compile_stats is not None
        return bucketing.compile_delta(before)["compiles"]

    run(40)  # cold: compiles the bucket-64 lattice programs
    # warmed: 48- and 56-ring intermediates share every bucket with the
    # 40-ring — each jit composite must have compiled AT MOST once above
    assert run(48) == 0
    assert run(56) == 0


@pytest.mark.parametrize("bucket_mode", ["pow2"], indirect=True)
def test_join_no_recompile_within_bucket(bucket_mode):
    from tpu_cypher.backend.tpu.table import TpuTable

    def join_at(n):
        # unique build keys: every probe row matches exactly once, so the
        # match total is n — all of 40/48/56 share the 64 bucket end to end
        left = TpuTable.from_numpy({"k": np.arange(n, dtype=np.int64)})
        right = TpuTable.from_numpy(
            {
                "j": np.arange(n, dtype=np.int64),
                "p": np.arange(n, dtype=np.int64) * 10,
            }
        )
        before = bucketing.compile_snapshot()
        out = left.join(right, "inner", [("k", "j")])
        assert out.size == n
        return bucketing.compile_delta(before)["compiles"]

    join_at(40)  # cold
    assert join_at(48) == 0
    assert join_at(56) == 0


@pytest.mark.parametrize("bucket_mode", ["pow2"], indirect=True)
def test_filter_no_recompile_within_bucket(bucket_mode):
    # materializing filter: scan -> bucketed predicate + compaction ->
    # host delivery (terminal EXACT-size eager ops like aggregation are
    # out of the bucketing contract and would compile per size)
    session = CypherSession.tpu()
    query = "MATCH (a:P) WHERE a.x >= 2 RETURN a.x"

    def run(n):
        before = bucketing.compile_snapshot()
        g = _ring_graph(session, n)
        result = g.cypher(query)
        assert len(result.records.collect()) == n - 2
        return bucketing.compile_delta(before)["compiles"]

    run(40)
    assert run(48) == 0
    assert run(56) == 0


# ---------------------------------------------------------------------------
# warmup + telemetry surface
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bucket_mode", ["pow2"], indirect=True)
def test_warmup_second_pass_compiles_nothing(bucket_mode):
    session = CypherSession.tpu()
    g = _ring_graph(session, 40)
    corpus = [
        "MATCH (a:P)-[:R]->(b:P) RETURN a.x, b.x",
        "MATCH (a:P) WHERE a.x > 5 RETURN count(*) AS c",
    ]
    first = session.warmup(corpus, graph=g)
    assert first["queries"] == 2
    assert len(first["per_query"]) == 2
    second = session.warmup(corpus, graph=g)
    assert second["compiles"] == 0


def test_compile_stats_always_populated():
    g = CypherSession.tpu().create_graph_from_create_query(
        "CREATE (a:P {x:1})-[:R]->(b:P {x:2})"
    )
    result = g.cypher("MATCH (a:P)-[:R]->(b:P) RETURN a.x, b.x")
    result.records.collect()
    assert result.compile_stats is not None
    assert set(result.compile_stats) == {
        "compiles",
        "compile_seconds",
        "persistent_cache_hits",
        "persistent_cache_misses",
    }
    assert result.compile_stats["compiles"] >= 0


# ---------------------------------------------------------------------------
# the lattice itself
# ---------------------------------------------------------------------------


def test_round_size_off_is_identity():
    bucketing.MODE.set("off")
    try:
        assert [bucketing.round_size(n) for n in (0, 1, 7, 100)] == [0, 1, 7, 100]
    finally:
        bucketing.MODE.reset()


def test_round_size_pow2_lattice():
    bucketing.MODE.set("pow2")
    try:
        assert bucketing.round_size(0) == 0  # empty keeps its own program
        assert bucketing.round_size(1) == 32  # floor
        assert bucketing.round_size(33) == 64
        assert bucketing.round_size(64) == 64
        assert bucketing.round_size(65) == 128
    finally:
        bucketing.MODE.reset()


def test_round_size_125_lattice_monotone():
    bucketing.MODE.set("1.25")
    try:
        sizes = [bucketing.round_size(n) for n in range(1, 4000, 13)]
        assert all(
            s >= n for s, n in zip(sizes, range(1, 4000, 13))
        )
        assert sizes == sorted(sizes)
        # <= 25% overhead above the floor
        for n in (100, 500, 3000):
            assert bucketing.round_size(n) <= int(n * 1.25) + 1
    finally:
        bucketing.MODE.reset()


def test_round_up_pow2_shared_helper():
    assert bucketing.round_up_pow2(1) == 1
    assert bucketing.round_up_pow2(3) == 4
    assert bucketing.round_up_pow2(16) == 16
    assert bucketing.round_up_pow2(17) == 32
    assert bucketing.round_up_pow2(5, floor=16) == 16


# ---------------------------------------------------------------------------
# the persistent compile cache is placed from OUTSIDE
# ---------------------------------------------------------------------------

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    """Snapshot/restore of the three JAX cache options the engine touches
    (the suite runs with the persistent cache OFF — tests/conftest.py)."""
    names = (
        "jax_enable_compilation_cache",
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    before = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in before.items():
        jax.config.update(n, v)


def test_default_cache_dir_is_fixed_inside_the_checkout():
    assert bucketing.DEFAULT_CACHE_DIR == os.path.join(_REPO, ".jax_cache")


def test_suite_runs_with_the_persistent_cache_off():
    """conftest's switch, inherited by every child a test spawns: cold
    compile counts keep their meaning, and no WAL or calibration file
    lands beside a cache nobody asked for."""
    assert os.environ["JAX_ENABLE_COMPILATION_CACHE"] == "false"
    assert jax.config.jax_enable_compilation_cache is False
    CypherSession.tpu()
    assert bucketing.persistent_cache_dir() is None


def test_enable_persistent_cache_defaults_only_when_nothing_is_set(cache_config):
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", None)
    CypherSession.tpu()
    assert jax.config.jax_compilation_cache_dir == bucketing.DEFAULT_CACHE_DIR
    assert bucketing.persistent_cache_dir() == bucketing.DEFAULT_CACHE_DIR
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_enable_persistent_cache_yields_to_a_configured_directory(
    cache_config, tmp_path
):
    """What JAX_COMPILATION_CACHE_DIR sets (JAX reads it into this option
    at start-up) is never overridden."""
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    CypherSession.tpu()
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    assert bucketing.persistent_cache_dir() == str(tmp_path)


def test_engine_places_the_cache_in_exactly_one_place():
    """``jax_compilation_cache_dir`` is updated in one place of the package,
    and nothing places a cache (or anything else of the serve tier) in a
    directory that moves."""
    updates, movers = [], []
    for dirpath, _dirs, files in os.walk(os.path.join(_REPO, "tpu_cypher")):
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            text = open(path).read()
            for i, line in enumerate(text.splitlines(), 1):
                if 'update("jax_compilation_cache_dir"' in line:
                    updates.append(f"{os.path.relpath(path, _REPO)}:{i}")
                if "mkdtemp" in line and os.sep + "serve" + os.sep in path:
                    movers.append(f"{os.path.relpath(path, _REPO)}:{i}")
    assert len(updates) == 1 and updates[0].startswith(
        os.path.join("tpu_cypher", "backend", "tpu", "bucketing.py")
    ), updates
    assert movers == []


_CACHE_PROBE = r"""
import json
from tpu_cypher import CypherSession
g = CypherSession.tpu().create_graph_from_create_query(
    "CREATE (a:P {x:1})-[:R]->(b:P {x:2})-[:R]->(c:P {x:3})")
r = g.cypher("MATCH (a:P)-[:R]->(b:P) RETURN a.x AS a, b.x AS b ORDER BY a")
rows = [dict(x) for x in r.records.collect()]
from tpu_cypher.backend.tpu import bucketing
print(json.dumps({"rows": rows, "dir": bucketing.persistent_cache_dir(),
                  **r.compile_stats}))
"""


def test_second_process_hits_the_cache_the_environment_placed(tmp_path):
    """JAX_COMPILATION_CACHE_DIR=/x: a first process fills /x, a second one
    answers the same query from it — persistent-cache hits, zero compiles."""
    env = dict(
        os.environ,
        JAX_ENABLE_COMPILATION_CACHE="true",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path),
        PYTHONPATH=_REPO,
    )
    reports = []
    for _ in range(2):
        proc = subprocess.run(
            [sys.executable, "-c", _CACHE_PROBE], env=env, cwd=str(tmp_path),
            capture_output=True, text=True, timeout=300,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        reports.append(json.loads(proc.stdout.strip().splitlines()[-1]))
    cold, warm = reports
    assert cold["dir"] == warm["dir"] == str(tmp_path)
    assert cold["rows"] == warm["rows"] == [{"a": 1, "b": 2}, {"a": 2, "b": 3}]
    assert cold["persistent_cache_misses"] > 0 and cold["compiles"] > 0
    assert warm["persistent_cache_hits"] > 0
    assert warm["persistent_cache_misses"] == 0 and warm["compiles"] == 0
    assert os.listdir(tmp_path)

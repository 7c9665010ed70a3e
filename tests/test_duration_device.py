"""Device-resident DURATION execution:
durations ride as int64 (n, 3) device triples — months / days / total
microseconds (the reference's CalendarInterval model, ``TemporalUdafs.scala``
aggregates + ``okapi-api Duration.scala`` components) — so duration columns,
equality, component accessors, +/- arithmetic, DISTINCT/group keys, ORDER BY,
and min/max/sum/avg/count aggregates run with ZERO host islands. Every query
is differential vs the local oracle."""

import pytest

from tpu_cypher import CypherSession
from tpu_cypher.api.values import Duration
from tpu_cypher.backend.tpu.column import Column, DUR
from tpu_cypher.backend.tpu.table import FALLBACK_COUNTER

CREATE = (
    "CREATE (a:E {d: duration('P1Y2M3DT4H5M6S'), n: 1}), "
    "(b:E {d: duration('P1M'), n: 2}), "
    "(c:E {d: duration('P30DT12H'), n: 2}), "
    "(e:E {d: duration('-P1M'), n: 3}), "
    "(f:E {n: 3})"  # null duration: aggregation skips, IS NULL sees
)

DEVICE_QUERIES = [
    "MATCH (x:E) RETURN x.d AS d ORDER BY d",
    "MATCH (x:E) RETURN x.d AS d ORDER BY d DESC",
    "MATCH (x:E) RETURN min(x.d) AS lo, max(x.d) AS hi, avg(x.d) AS a, "
    "count(x.d) AS c",
    "MATCH (x:E) WHERE x.d IS NOT NULL "
    "RETURN sum(x.d) AS s, min(x.d) AS lo",
    "MATCH (x:E) RETURN x.n AS k, min(x.d) AS lo, max(x.d) AS hi, "
    "count(x.d) AS c ORDER BY k",
    "MATCH (x:E) WITH DISTINCT x.d AS d RETURN count(*) AS c",
    "MATCH (x:E) RETURN count(DISTINCT x.d) AS c",
    "MATCH (x:E) WHERE x.d = duration('P1M') RETURN count(*) AS c",
    "MATCH (x:E) WHERE x.d <> duration('P1M') RETURN count(*) AS c",
    "MATCH (x:E) WHERE x.d IS NULL RETURN count(*) AS c",
    "MATCH (x:E) RETURN x.d + duration('P1D') AS s ORDER BY s",
    "MATCH (x:E) RETURN x.d - duration('PT1H') AS s ORDER BY s",
    "MATCH (x:E) RETURN -x.d AS neg ORDER BY neg",
    "MATCH (x:E) RETURN x.d.years AS y, x.d.months AS m, "
    "x.d.monthsOfYear AS my, x.d.weeks AS w, x.d.days AS dd, "
    "x.d.hours AS h, x.d.minutes AS mi, x.d.seconds AS s, "
    "x.d.milliseconds AS ms, x.d.microseconds AS us ORDER BY m, dd",
    "MATCH (x:E) RETURN x.d AS d, count(*) AS c ORDER BY d",
    "MATCH (x:E) RETURN collect(x.d) AS all",
]


@pytest.fixture(scope="module")
def graphs():
    return (
        CypherSession.local().create_graph_from_create_query(CREATE),
        CypherSession.tpu().create_graph_from_create_query(CREATE),
    )


@pytest.mark.parametrize("q", DEVICE_QUERIES)
def test_duration_differential_device(graphs, q):
    gl, gt = graphs
    want = [dict(r) for r in gl.cypher(q).records.collect()]
    FALLBACK_COUNTER.reset()
    got = [dict(r) for r in gt.cypher(q).records.collect()]
    assert got == want, f"{q}: {got} vs {want}"
    islands = {
        k: v
        for k, v in FALLBACK_COUNTER.snapshot().items()
        if k.startswith("island:") or k.startswith("table:")
    }
    assert not islands, f"{q}: duration host islands {islands}"


def test_duration_column_roundtrip():
    vals = [
        Duration(months=14, days=3, seconds=14706),
        None,
        Duration(months=-1),
        Duration(microseconds=1_500_000),  # normalizes to 1s + 500000us
        Duration(days=2, microseconds=-1),  # negative micros borrow seconds
    ]
    c = Column.from_values(vals)
    assert c.kind == DUR
    assert c.to_values() == vals


def test_duration_sum_empty_group_falls_back():
    """The oracle sums an all-null duration group to INTEGER 0 — the device
    column cannot hold mixed kinds, so it must defer (and stay correct)."""
    create = "CREATE (a:G {k: 1}), (b:G {k: 1})"
    gl = CypherSession.local().create_graph_from_create_query(create)
    gt = CypherSession.tpu().create_graph_from_create_query(create)
    q = "MATCH (x:G) RETURN x.k AS k, sum(x.d) AS s"
    want = [dict(r) for r in gl.cypher(q).records.collect()]
    got = [dict(r) for r in gt.cypher(q).records.collect()]
    assert got == want


def test_duration_order_ties_are_stable():
    """1 month and 30.4375 days share the average-length order key: ORDER BY
    must keep first-occurrence order on both backends (stable sorts)."""
    create = (
        "CREATE (a:T {i: 1, d: duration('P1M')}), "
        "(b:T {i: 2, d: duration({days: 30, hours: 10, minutes: 30})})"
    )
    gl = CypherSession.local().create_graph_from_create_query(create)
    gt = CypherSession.tpu().create_graph_from_create_query(create)
    q = "MATCH (x:T) RETURN x.i AS i, x.d AS d ORDER BY d, i"
    want = [dict(r) for r in gl.cypher(q).records.collect()]
    got = [dict(r) for r in gt.cypher(q).records.collect()]
    assert got == want

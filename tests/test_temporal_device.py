"""Device-resident temporal execution:
date = int32 days-since-epoch, localdatetime = int64 micros-since-epoch
device columns; accessors/comparisons/aggregates run as traced calendar math
(reference executes these on executors, ``TemporalUdfs.scala:40-160``)."""

import datetime as dt

import numpy as np
import pytest

from tpu_cypher import CypherSession
from tpu_cypher.backend.tpu import temporal as TP
from tpu_cypher.backend.tpu.column import Column, DATE, LDT
from tpu_cypher.backend.tpu.table import FALLBACK_COUNTER


def test_civil_calendar_roundtrip_vs_python():
    """civil_from_days/days_from_civil/iso fields vs datetime over ±200y."""
    import jax.numpy as jnp

    rng = np.random.default_rng(3)
    days = rng.integers(-73000, 73000, 4000)  # ~1770..2170
    z = jnp.asarray(days)
    y, m, d = (np.asarray(a) for a in TP.civil_from_days(z))
    back = np.asarray(TP.days_from_civil(jnp.asarray(y), jnp.asarray(m), jnp.asarray(d)))
    dow = np.asarray(TP.iso_weekday(z))
    week, weekyear = (np.asarray(a) for a in TP.iso_week_and_year(z))
    for i, zi in enumerate(days):
        pd = dt.date.fromordinal(int(zi) + TP.EPOCH_ORDINAL)
        assert (y[i], m[i], d[i]) == (pd.year, pd.month, pd.day), pd
        assert back[i] == zi
        assert dow[i] == pd.isoweekday(), pd
        iso = pd.isocalendar()
        assert (week[i], weekyear[i]) == (iso[1], iso[0]), pd


def test_column_roundtrip():
    vals = [
        dt.date(1987, 6, 15),
        None,
        dt.date(1969, 12, 31),
        dt.date(2400, 2, 29),
    ]
    c = Column.from_values(vals)
    assert c.kind == DATE
    assert c.to_values() == vals
    dts = [
        dt.datetime(2001, 3, 4, 5, 6, 7, 123456),
        dt.datetime(1969, 12, 31, 23, 59, 59, 999999),
        None,
    ]
    c2 = Column.from_values(dts)
    assert c2.kind == LDT
    assert c2.to_values() == dts
    # mixed date/datetime stays host-exact
    assert Column.from_values([dt.date(2020, 1, 1), dt.datetime(2020, 1, 1)]).kind == "obj"
    # fixed-offset zoned datetimes are device columns (round 5): UTC
    # instant lane + column-level offset metadata
    zvals = [
        dt.datetime(2020, 1, 1, 12, 0, tzinfo=dt.timezone.utc),
        dt.datetime(2020, 6, 1, 9, 30, 0, 5, tzinfo=dt.timezone.utc),
        None,
    ]
    cz = Column.from_values(zvals)
    assert cz.kind == "zdt"
    assert cz.to_values() == zvals
    # per-row MIXED offsets and region-named zones stay host-exact: a
    # device round-trip would lose the zone name / per-row offsets
    mixed = [
        dt.datetime(2020, 1, 1, tzinfo=dt.timezone.utc),
        dt.datetime(2020, 1, 1, tzinfo=dt.timezone(dt.timedelta(hours=1))),
    ]
    assert Column.from_values(mixed).kind == "obj"
    import zoneinfo

    named = [dt.datetime(2020, 1, 1, tzinfo=zoneinfo.ZoneInfo("Europe/Berlin"))]
    assert Column.from_values(named).kind == "obj"
    # zoned/naive times
    tz = dt.timezone(dt.timedelta(hours=1))
    tvals = [dt.time(9, 30, tzinfo=tz), dt.time(17, 0, 0, 250, tzinfo=tz), None]
    ct = Column.from_values(tvals)
    assert ct.kind == "zt"
    assert ct.to_values() == tvals
    lvals = [dt.time(9, 30), None, dt.time(23, 59, 59, 999999)]
    cl = Column.from_values(lvals)
    assert cl.kind == "lt"
    assert cl.to_values() == lvals


CREATE = (
    "CREATE (:E {d: date('1987-06-15'), t: localdatetime('2001-03-04T05:06:07.123456')}), "
    "(:E {d: date('2020-02-29'), t: localdatetime('1999-12-31T23:59:59')}), "
    "(:E {d: date('1970-01-01')}), "
    "(:E {t: localdatetime('1970-01-01T00:00:00')})"
)

DEVICE_QUERIES = [
    "MATCH (e:E) RETURN e.d AS d ORDER BY d",
    "MATCH (e:E) WHERE e.d > date('1980-01-01') RETURN count(*) AS c",
    "MATCH (e:E) RETURN e.d.year AS y, e.d.month AS m, e.d.day AS dd, "
    "e.d.week AS w, e.d.weekYear AS wy, e.d.dayOfWeek AS dw, "
    "e.d.ordinalDay AS od, e.d.quarter AS q, e.d.dayOfQuarter AS dq ORDER BY y",
    "MATCH (e:E) RETURN e.t.year AS y, e.t.hour AS h, e.t.minute AS mi, "
    "e.t.second AS s, e.t.millisecond AS ms, e.t.microsecond AS us ORDER BY y",
    "MATCH (e:E) RETURN min(e.d) AS lo, max(e.d) AS hi, count(e.d) AS c",
    "MATCH (e:E) WITH DISTINCT e.d AS d RETURN count(*) AS c",
    "MATCH (e:E) RETURN e.d AS d, count(*) AS c ORDER BY d LIMIT 2",
    "MATCH (a:E), (b:E) WHERE a.d = b.d RETURN count(*) AS c",
    "MATCH (e:E) WHERE e.t >= localdatetime('1999-01-01T00:00:00') RETURN count(*) AS c",
    "MATCH (e:E) WHERE e.d = e.t RETURN count(*) AS c",
    "MATCH (e:E) RETURN e.d AS d ORDER BY e.d DESC LIMIT 2",
]


@pytest.fixture(scope="module")
def graphs():
    return (
        CypherSession.local().create_graph_from_create_query(CREATE),
        CypherSession.tpu().create_graph_from_create_query(CREATE),
    )


@pytest.mark.parametrize("query", DEVICE_QUERIES)
def test_temporal_differential_no_host_islands(graphs, query):
    g_local, g_tpu = graphs
    expected = [dict(r) for r in g_local.cypher(query).records.collect()]
    FALLBACK_COUNTER.reset()
    got = [dict(r) for r in g_tpu.cypher(query).records.collect()]
    islands = {
        k: v
        for k, v in FALLBACK_COUNTER.snapshot().items()
        if k.startswith("island") or "obj" in k
    }
    assert got == expected, f"{query}: {got} vs {expected}"
    assert not islands, f"temporal host islands for {query}: {islands}"


def test_temporal_join_on_date(graphs):
    g_local, g_tpu = graphs
    q = (
        "MATCH (a:E), (b:E) WHERE a.d = b.d AND a.t IS NULL "
        "RETURN count(*) AS c"
    )
    lv = [dict(r) for r in g_local.cypher(q).records.collect()]
    tv = [dict(r) for r in g_tpu.cypher(q).records.collect()]
    assert lv == tv

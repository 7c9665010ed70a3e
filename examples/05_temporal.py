"""Temporal queries on device: dates/datetimes as integer device columns.

The reference runs temporal UDFs on Spark executors
(``TemporalUdfs.scala:40-160``); here date = int32 days-since-epoch and
localdatetime = int64 micros-since-epoch live in HBM, and accessors,
range filters, grouping, and min/max run as branch-free calendar math on
the VPU — ``session.record_fallbacks`` proves no host islands.

A CPU tool by purpose (a toy-sized walkthrough of the API): it defaults
``JAX_PLATFORMS`` to ``cpu``; set the variable to run it elsewhere.

Run:  python examples/05_temporal.py
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from tpu_cypher import CypherSession


def main():
    session = CypherSession.tpu()
    session.record_fallbacks = True
    g = session.create_graph_from_create_query(
        """
        CREATE (:Person {name: 'ada',   born: date('1815-12-10')}),
               (:Person {name: 'grace', born: date('1906-12-09')}),
               (:Person {name: 'alan',  born: date('1912-06-23')}),
               (:Person {name: 'edsger', born: date('1930-05-11')}),
               (:Event {name: 'launch', at: localdatetime('2019-03-09T11:45:22')})
        """
    )

    queries = [
        # range filter + accessor projection — all on device
        "MATCH (p:Person) WHERE p.born >= date('1900-01-01') "
        "RETURN p.name AS name, p.born.year AS year ORDER BY year",
        # grouping by truncated decade
        "MATCH (p:Person) WITH date.truncate('decade', p.born) AS dec, count(*) AS c "
        "RETURN toString(dec) AS decade, c ORDER BY decade",
        # duration arithmetic
        "MATCH (p:Person) RETURN p.name AS name, "
        "duration.between(p.born, date('2020-01-01')).years AS age ORDER BY age DESC LIMIT 2",
        # datetime accessors
        "MATCH (e:Event) RETURN e.at.hour AS h, e.at.minute AS m, e.at.dayOfWeek AS dow",
    ]
    for q in queries:
        result = g.cypher(q)
        print(f"\n>>> {q}")
        print(result.records.show())
        print(f"host fallbacks: {result.fallbacks}")


if __name__ == "__main__":
    main()

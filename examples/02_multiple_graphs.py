"""Multiple-graph Cypher: catalog, CONSTRUCT, views, graph union.

Mirrors the reference's ``MultipleGraphExample``: CATALOG CREATE GRAPH,
FROM GRAPH, CONSTRUCT ... RETURN GRAPH, and parameterized views.

A CPU tool by purpose (a toy-sized walkthrough of the API): it defaults
``JAX_PLATFORMS`` to ``cpu``; set the variable to run it elsewhere.

Run:  python examples/02_multiple_graphs.py
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from tpu_cypher import CypherSession


def main():
    session = CypherSession.tpu()
    g = session.create_graph_from_create_query(
        "CREATE (:Person {name:'Alice', age:23}), (:Person {name:'Bob', age:42}),"
        "(:Person {name:'Carol', age:55})"
    )
    session.store_graph("people", g)

    # derive a new graph with CONSTRUCT and store it in the catalog
    session.cypher(
        "CATALOG CREATE GRAPH adults { FROM GRAPH session.people "
        "MATCH (p:Person) WHERE p.age >= 30 "
        "CONSTRUCT NEW (:Adult {name: p.name}) RETURN GRAPH }"
    )
    print(session.cypher("FROM GRAPH adults MATCH (a:Adult) RETURN a.name").records.show())

    # a parameterized view re-plans per argument graph + parameters
    session.cypher(
        "CATALOG CREATE VIEW older($g) { FROM GRAPH $g MATCH (p:Person) "
        "WHERE p.age > $cut CONSTRUCT NEW (:Hit {name: p.name}) RETURN GRAPH }"
    )
    print(
        session.cypher(
            "FROM GRAPH older(people) MATCH (h:Hit) RETURN h.name", {"cut": 40}
        ).records.show()
    )


if __name__ == "__main__":
    main()

"""Customer 360: integrate two source graphs into one view via multiple
graphs.

The TPU-native analog of the reference's ``Customer360Example``: customer
records live in two systems (CRM and web analytics) with their own id
spaces; Graph DDL-style element tables feed each source graph, CONSTRUCT
stitches them on a shared business key, and a single Cypher query answers
over the integrated graph.

A CPU tool by purpose (a toy-sized walkthrough of the API): it defaults
``JAX_PLATFORMS`` to ``cpu``; set the variable to run it elsewhere.

Run:  python examples/07_customer360.py
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_X64", "1")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    from tpu_cypher import CypherSession

    session = CypherSession.tpu()
    crm = session.create_graph_from_create_query(
        """
        CREATE (:Customer {email: 'ada@example.com', name: 'Ada', tier: 'gold'}),
               (:Customer {email: 'bob@example.com', name: 'Bob', tier: 'basic'})
        """
    )
    web = session.create_graph_from_create_query(
        """
        CREATE (a:Visitor {email: 'ada@example.com', visits: 41}),
               (b:Visitor {email: 'bob@example.com', visits: 3}),
               (a)-[:VIEWED]->(:Product {sku: 'tpu-pod'}),
               (a)-[:VIEWED]->(:Product {sku: 'ici-cable'}),
               (b)-[:VIEWED]->(:Product {sku: 'tpu-pod'})
        """
    )
    session.store_graph("crm", crm)
    session.store_graph("web", web)

    # stitch: one :Profile node per matched (customer, visitor) pair,
    # carrying fields from BOTH sources, linked to the product views
    session.cypher(
        """
        CATALOG CREATE GRAPH c360 {
          FROM GRAPH session.crm
          MATCH (c:Customer)
          FROM GRAPH session.web
          MATCH (v:Visitor {email: c.email})-[:VIEWED]->(p:Product)
          CONSTRUCT
            NEW (profile:Profile {email: c.email, name: c.name,
                                  tier: c.tier, visits: v.visits})
            NEW (profile)-[:INTERESTED_IN]->(q COPY OF p)
          RETURN GRAPH
        }
        """
    )
    g = session.graph("c360")
    out = [
        dict(r)
        for r in g.cypher(
            """
            MATCH (pr:Profile)-[:INTERESTED_IN]->(p:Product)
            RETURN pr.name AS name, pr.tier AS tier, pr.visits AS visits,
                   count(p) AS products
            ORDER BY name
            """
        ).records.collect()
    ]
    for row in out:
        print(
            f"customer360 {row['name']}: tier={row['tier']} "
            f"visits={row['visits']} products={row['products']}"
        )
    assert out[0] == {"name": "Ada", "tier": "gold", "visits": 41, "products": 2}
    print("profiles:", len(out))


if __name__ == "__main__":
    main()

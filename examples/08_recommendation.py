"""Collaborative-filtering recommendations in pure Cypher.

The TPU-native analog of the reference's ``RecommendationExample``:
customers who bought the same products recommend each other's other
purchases. The 3-hop co-purchase pattern compiles to the engine's fused
CSR expand chain; the NOT-exists filter rides the semijoin flag planning.

A CPU tool by purpose (a toy-sized walkthrough of the API): it defaults
``JAX_PLATFORMS`` to ``cpu``; set the variable to run it elsewhere.

Run:  python examples/08_recommendation.py
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_X64", "1")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    from tpu_cypher import CypherSession

    g = CypherSession.tpu().create_graph_from_create_query(
        """
        CREATE (ada:Person {name: 'Ada'}), (bob:Person {name: 'Bob'}),
               (cyd:Person {name: 'Cyd'}),
               (tpu:Item {name: 'tpu-pod'}), (hbm:Item {name: 'hbm-stick'}),
               (ici:Item {name: 'ici-cable'}), (fan:Item {name: 'fan'}),
               (ada)-[:BOUGHT]->(tpu), (ada)-[:BOUGHT]->(hbm),
               (bob)-[:BOUGHT]->(tpu), (bob)-[:BOUGHT]->(ici),
               (cyd)-[:BOUGHT]->(fan)
        """
    )
    out = [
        dict(r)
        for r in g.cypher(
            """
            MATCH (me:Person)-[:BOUGHT]->(:Item)<-[:BOUGHT]-(peer:Person),
                  (peer)-[:BOUGHT]->(rec:Item)
            WHERE me <> peer AND NOT (me)-[:BOUGHT]->(rec)
            RETURN me.name AS customer, rec.name AS recommend,
                   count(peer) AS strength
            ORDER BY customer, strength DESC, recommend
            """
        ).records.collect()
    ]
    for row in out:
        print(
            f"recommend {row['recommend']} to {row['customer']} "
            f"(strength {row['strength']})"
        )
    assert {"customer": "Ada", "recommend": "ici-cable", "strength": 1} in out
    assert {"customer": "Bob", "recommend": "hbm-stick", "strength": 1} in out
    assert all(r["customer"] != "Cyd" for r in out), "no co-purchases for Cyd"
    print("recommendations:", len(out))


if __name__ == "__main__":
    main()

"""Parameterized graph views.

The TPU-native analog of the reference's ``ViewsExample``: a view is a
stored Cypher text producing a graph, re-planned per use with its graph
parameters (reference ``CypherCatalog`` views / CREATE VIEW).

A CPU tool by purpose (a toy-sized walkthrough of the API): it defaults
``JAX_PLATFORMS`` to ``cpu``; set the variable to run it elsewhere.

Run:  python examples/09_views.py
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_X64", "1")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    from tpu_cypher import CypherSession

    session = CypherSession.tpu()
    products = session.create_graph_from_create_query(
        """
        CREATE (:Product {name: 'pod', price: 90}),
               (:Product {name: 'rack', price: 45}),
               (:Product {name: 'cable', price: 5})
        """
    )
    session.store_graph("products", products)

    # a view body is stored as TEXT and re-planned per use; $g binds the
    # argument graph at invocation
    session.cypher(
        """
        CATALOG CREATE VIEW premium($g) {
          FROM GRAPH $g
          MATCH (p:Product) WHERE p.price > 20
          CONSTRUCT NEW (q COPY OF p) SET q:Premium
          RETURN GRAPH
        }
        """
    )
    out = [
        dict(r)
        for r in session.cypher(
            """
            FROM GRAPH premium(session.products)
            MATCH (p:Premium) RETURN p.name AS name, p.price AS price
            ORDER BY price DESC
            """
        ).records.collect()
    ]
    for row in out:
        print(f"premium {row['name']}: {row['price']}")
    assert [r["name"] for r in out] == ["pod", "rack"]
    print("premium products:", len(out))


if __name__ == "__main__":
    main()

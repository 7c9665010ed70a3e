"""Social-network quickstart: build a graph from element tables, query it.

The TPU-native analog of the reference's ``morpheus-examples``
``CaseClassExample``/``DataFrameInputExample``: tables in, Cypher out.

A CPU tool by purpose (a toy-sized walkthrough of the API): it defaults
``JAX_PLATFORMS`` to ``cpu``; set the variable to run it elsewhere.

Run:  python examples/01_social_network.py
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from tpu_cypher import CypherSession
from tpu_cypher.api.mapping import NodeMappingBuilder, RelationshipMappingBuilder
from tpu_cypher.relational.graphs import ElementTable


def main():
    session = CypherSession.tpu()

    people = session.table_cls.from_columns(
        {
            "id": [1, 2, 3, 4],
            "name": ["Alice", "Bob", "Carol", "Dave"],
            "age": [23, 42, 55, 19],
        }
    )
    person = (
        NodeMappingBuilder.on("id")
        .with_implied_label("Person")
        .with_property_keys("name", "age")
        .build()
    )
    knows = session.table_cls.from_columns(
        {"rid": [100, 101, 102], "src": [1, 2, 1], "dst": [2, 3, 3], "since": [2019, 2020, 2021]}
    )
    knows_m = (
        RelationshipMappingBuilder.on("rid")
        .from_("src")
        .to("dst")
        .with_relationship_type("KNOWS")
        .with_property_key("since")
        .build()
    )

    g = session.read_from(ElementTable(person, people), ElementTable(knows_m, knows))

    print(
        g.cypher(
            "MATCH (a:Person)-[k:KNOWS]->(b:Person) "
            "WHERE a.age < b.age RETURN a.name, b.name, k.since ORDER BY k.since"
        ).records.show()
    )
    print(
        g.cypher(
            "MATCH (a:Person)-[:KNOWS]->()-[:KNOWS]->(c) RETURN a.name, c.name"
        ).records.show()
    )


if __name__ == "__main__":
    main()

"""DataFrame round-trip: tables in, Cypher, DataFrame out.

The TPU-native analog of the reference's ``DataFrameInputExample`` /
``DataFrameOutputExample`` / ``CustomDataFrameInputExample``: existing
tabular data (a pandas DataFrame here) becomes a property graph through
element mappings, and query results come back as a DataFrame for the
surrounding data pipeline.

A CPU tool by purpose (a toy-sized walkthrough of the API): it defaults
``JAX_PLATFORMS`` to ``cpu``; set the variable to run it elsewhere.

Run:  python examples/13_dataframe_roundtrip.py
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_X64", "1")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import pandas as pd


def main():
    from tpu_cypher import CypherSession
    from tpu_cypher.api.mapping import (
        NodeMappingBuilder,
        RelationshipMappingBuilder,
    )
    from tpu_cypher.relational.graphs import ElementTable

    people = pd.DataFrame(
        {
            "id": [0, 1, 2],
            "name": ["Alice", "Bob", "Eve"],
            "age": [42, 23, 84],
        }
    )
    friendships = pd.DataFrame(
        {"rid": [10, 11], "src": [0, 1], "dst": [1, 2], "since": [2017, 2021]}
    )

    session = CypherSession.tpu()
    nodes = session.table_cls.from_columns(
        {c: people[c].tolist() for c in people.columns}
    )
    rels = session.table_cls.from_columns(
        {c: friendships[c].tolist() for c in friendships.columns}
    )
    g = session.read_from(
        ElementTable(
            NodeMappingBuilder.on("id")
            .with_implied_label("Person")
            .with_property_keys("name", "age")
            .build(),
            nodes,
        ),
        ElementTable(
            RelationshipMappingBuilder.on("rid")
            .from_("src")
            .to("dst")
            .with_relationship_type("FRIEND_OF")
            .with_property_key("since")
            .build(),
            rels,
        ),
    )

    df = g.cypher(
        "MATCH (a:Person)-[f:FRIEND_OF]->(b:Person) "
        "RETURN a.name AS a, f.since AS since, b.name AS b ORDER BY since"
    ).records.to_pandas()
    print(df.to_string(index=False))
    assert list(df.columns) == ["a", "since", "b"]
    assert df["a"].tolist() == ["Alice", "Bob"]
    assert df["since"].tolist() == [2017, 2021]
    print("rows out:", len(df))


if __name__ == "__main__":
    main()

"""Filesystem graph persistence round-trip.

The TPU-native analog of the reference's ``DataSourceExample``: mount a
filesystem data source under a catalog namespace, store a graph (parquet
tables in the reference's directory layout, written in parallel), and load
it back through the catalog in a fresh session.

A CPU tool by purpose (a toy-sized walkthrough of the API): it defaults
``JAX_PLATFORMS`` to ``cpu``; set the variable to run it elsewhere.

Run:  python examples/10_fs_roundtrip.py
"""

import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_X64", "1")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    from tpu_cypher import CypherSession
    from tpu_cypher.io.fs import FSGraphSource

    with tempfile.TemporaryDirectory() as root:
        session = CypherSession.tpu()
        session.register_source("fs", FSGraphSource(root))
        g = session.create_graph_from_create_query(
            """
            CREATE (a:Person {name: 'Ada', age: 36})-[:KNOWS {since: 2019}]->
                   (b:Person:Admin {name: 'Bob', age: 29}),
                   (a)-[:KNOWS {since: 2021}]->(:Person {name: 'Cyd', age: 41})
            """
        )
        session.store_graph("fs.team", g)
        print("stored under", sorted(os.listdir(os.path.join(root, "team"))))

        fresh = CypherSession.tpu()
        fresh.register_source("fs", FSGraphSource(root))
        loaded = fresh.graph("fs.team")
        out = [
            dict(r)
            for r in loaded.cypher(
                "MATCH (a:Person)-[k:KNOWS]->(b:Person) "
                "RETURN a.name AS a, k.since AS since, b.name AS b "
                "ORDER BY since"
            ).records.collect()
        ]
        for row in out:
            print(f"roundtrip {row['a']} -[KNOWS {row['since']}]-> {row['b']}")
        assert out == [
            {"a": "Ada", "since": 2019, "b": "Bob"},
            {"a": "Ada", "since": 2021, "b": "Cyd"},
        ]
        admins = [
            dict(r)
            for r in loaded.cypher(
                "MATCH (n:Admin) RETURN n.name AS n"
            ).records.collect()
        ]
        assert admins == [{"n": "Bob"}]
        print("labels and properties survived the round-trip")


if __name__ == "__main__":
    main()

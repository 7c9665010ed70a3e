"""Neo4j workflow: query-side integration and the bulk import sink.

The TPU-native analog of the reference's ``Neo4jWorkflowExample`` /
``Neo4jReadWriteExample``: graphs flow between this engine and Neo4j.
The live read/merge paths need a running server + driver
(`tpu_cypher.io.neo4j.Neo4jGraphSource` / `merge_graph` — label-combo
readers, MERGE write-back with index creation, exactly the reference's
``Neo4jGraphMerge`` recipe); this example exercises the server-FREE leg:
the **bulk CSV sink** (reference ``Neo4jBulkCSVDataSink``), which writes
a graph as `neo4j-admin import`-ready CSVs plus the load script.

A CPU tool by purpose (a toy-sized walkthrough of the API): it defaults
``JAX_PLATFORMS`` to ``cpu``; set the variable to run it elsewhere.

Run:  python examples/12_neo4j_workflow.py
"""

import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_X64", "1")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    from tpu_cypher import CypherSession
    from tpu_cypher.io.neo4j import Neo4jBulkCSVDataSink

    session = CypherSession.tpu()
    g = session.create_graph_from_create_query(
        """
        CREATE (a:Person {name: 'Ada', age: 36})-[:KNOWS {since: 2019}]->
               (b:Person:Admin {name: 'Bob', age: 29}),
               (a)-[:KNOWS {since: 2021}]->(:Person {name: 'Cyd', age: 41})
        """
    )

    with tempfile.TemporaryDirectory() as root:
        sink = Neo4jBulkCSVDataSink(root)
        sink.store("team", g._graph)
        files = []
        for dirpath, _, names in os.walk(root):
            for n in sorted(names):
                rel = os.path.relpath(os.path.join(dirpath, n), root)
                files.append(rel)
        for f in sorted(files):
            print("bulk-csv", f)
        csvs = [f for f in files if f.endswith(".csv")]
        assert any("Person" in f for f in csvs), "node CSVs written"
        assert any("KNOWS" in f for f in csvs), "relationship CSVs written"
        # spot-check a node file carries the header + rows
        node_csv = next(
            os.path.join(root, f) for f in csvs if "Person" in f and "Admin" not in f
        )
        with open(node_csv) as fh:
            content = fh.read()
        assert "Ada" in content and "Cyd" in content
        print("rows present; hand the directory to neo4j-admin import")


if __name__ == "__main__":
    main()

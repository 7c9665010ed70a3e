"""Distributed execution: the same Cypher query over a device mesh.

While a ``use_mesh`` context is active, TpuTable columns and the CSR
topology carry ``NamedSharding(mesh, P('rows'))`` and XLA GSPMD inserts
the collectives — the TPU-native replacement for Spark/Flink shuffle
(SURVEY §2.3). On one chip this is a no-op; on a v5e-8 slice the same
code shards across ICI. Runs on whatever platform JAX selects, over every
device it reports (at most eight), and prints which; on a CPU host it
asks XLA for eight virtual devices.

Run:  python examples/03_sharded_mesh.py
"""

import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def main():
    import jax

    from tpu_cypher import CypherSession
    from tpu_cypher.api.mapping import NodeMappingBuilder, RelationshipMappingBuilder
    from tpu_cypher.parallel.mesh import make_row_mesh, use_mesh
    from tpu_cypher.relational.graphs import ElementTable

    devices = jax.devices()[:8]
    print(f"platform: {devices[0].platform}, {len(devices)} device(s)")
    mesh = make_row_mesh(devices)
    n, e = 64, 256
    rng = np.random.default_rng(0)
    ids = np.arange(n, dtype=np.int64)
    src, dst = rng.integers(0, n, e), rng.integers(0, n, e)

    with use_mesh(mesh):
        s = CypherSession.tpu()
        nodes = s.table_cls.from_columns({"id": ids.tolist()})
        nm = NodeMappingBuilder.on("id").with_implied_label("V").build()
        rels = s.table_cls.from_columns(
            {
                "rid": (np.arange(e) + n).tolist(),
                "s": ids[src].tolist(),
                "t": ids[dst].tolist(),
            }
        )
        rm = (
            RelationshipMappingBuilder.on("rid")
            .from_("s")
            .to("t")
            .with_relationship_type("E")
            .build()
        )
        g = s.read_from(ElementTable(nm, nodes), ElementTable(rm, rels))
        r = g.cypher("MATCH (a:V)-[:E]->(b)-[:E]->(c) RETURN count(*) AS paths")
        print(r.records.show())
        print("executed over mesh:", mesh)


if __name__ == "__main__":
    main()

"""Graph-algorithm interop: PageRank over the engine's CSR, results back
into Cypher.

The TPU-native analog of the reference's ``GraphXPageRankExample``: there,
a Morpheus graph round-trips through GraphX for PageRank and the scores
re-enter as node properties. Here the exported edge list becomes a CSR,
PageRank runs as a jitted ``segment_sum`` power iteration (an SpMV — the
TPU-shaped formulation), and the scores flow back through ``read_from``
as a property column queryable by Cypher.

A CPU tool by purpose (a toy-sized walkthrough of the API): it defaults
``JAX_PLATFORMS`` to ``cpu``; set the variable to run it elsewhere.

Run:  python examples/06_pagerank_csr.py
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_X64", "1")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def main():
    import jax
    import jax.numpy as jnp

    from tpu_cypher import CypherSession
    from tpu_cypher.api.mapping import NodeMappingBuilder, RelationshipMappingBuilder
    from tpu_cypher.relational.graphs import ElementTable

    session = CypherSession.tpu()
    g = session.create_graph_from_create_query(
        """
        CREATE (home:Page {name: 'home'}), (docs:Page {name: 'docs'}),
               (blog:Page {name: 'blog'}), (faq:Page {name: 'faq'}),
               (home)-[:LINKS]->(docs), (home)-[:LINKS]->(blog),
               (docs)-[:LINKS]->(home), (docs)-[:LINKS]->(faq),
               (blog)-[:LINKS]->(home), (faq)-[:LINKS]->(home),
               (faq)-[:LINKS]->(docs)
        """
    )

    # 1. export the topology through Cypher (id-stable)
    rows = [
        dict(r)
        for r in g.cypher(
            "MATCH (a:Page)-[:LINKS]->(b:Page) RETURN id(a) AS s, id(b) AS t"
        ).records.collect()
    ]
    names = {
        dict(r)["i"]: dict(r)["n"]
        for r in g.cypher("MATCH (p:Page) RETURN id(p) AS i, p.name AS n").records.collect()
    }
    ids = np.array(sorted(names), dtype=np.int64)
    pos = {int(v): i for i, v in enumerate(ids)}
    src = np.array([pos[r["s"]] for r in rows], dtype=np.int64)
    dst = np.array([pos[r["t"]] for r in rows], dtype=np.int64)
    n = len(ids)

    # 2. PageRank as a jitted SpMV power iteration (segment_sum over edges)
    deg = np.bincount(src, minlength=n).astype(np.float64)

    @jax.jit
    def step(rank):
        contrib = rank[src] / jnp.asarray(deg)[src]
        spread = jax.ops.segment_sum(contrib, dst, num_segments=n)
        return 0.15 / n + 0.85 * spread

    rank = jnp.full(n, 1.0 / n)
    for _ in range(50):
        rank = step(rank)
    rank = np.asarray(rank)

    # 3. scores re-enter the graph as a node property
    nt = session.table_cls.from_columns(
        {
            "id": ids.tolist(),
            "name": [names[int(i)] for i in ids],
            "rank": [float(x) for x in rank],
        }
    )
    nm = (
        NodeMappingBuilder.on("id")
        .with_implied_label("Page")
        .with_property_keys("name", "rank")
        .build()
    )
    rel_rows = session.table_cls.from_columns(
        {
            "rid": list(range(10_000, 10_000 + len(src))),
            "s": ids[src].tolist(),
            "t": ids[dst].tolist(),
        }
    )
    rm = (
        RelationshipMappingBuilder.on("rid")
        .from_("s")
        .to("t")
        .with_relationship_type("LINKS")
        .build()
    )
    ranked = session.read_from(ElementTable(nm, nt), ElementTable(rm, rel_rows))
    out = [
        dict(r)
        for r in ranked.cypher(
            "MATCH (p:Page) RETURN p.name AS page, round(p.rank * 1000) / 1000 AS pr "
            "ORDER BY pr DESC, page"
        ).records.collect()
    ]
    for row in out:
        print(f"pagerank {row['page']}: {row['pr']}")
    assert out[0]["page"] == "home", "home has the most inlinks"
    print("top page:", out[0]["page"])


if __name__ == "__main__":
    main()

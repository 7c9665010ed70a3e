"""Whole-plan fusion: counting shapes that never materialize a row set.

The reference executes a k-hop ``MATCH ... RETURN count(*)`` as 2k hash
joins followed by a global aggregate. This engine recognizes the shape at
the physical level and runs the WHOLE plan as one XLA program:

* ``count(*)`` over an expand chain -> one right-to-left pass over
  per-node completion counts (``path_count_chain``): degrees from
  ``row_ptr``, a gather and a sum under a whole frontier, a scatter-free
  CSR SpMV elsewhere; one dispatch + one scalar fetch;
* ``count(*)`` over a chain ``WHERE a <> c`` / ``NOT (a)-[:KNOWS]->(c)``
  between two of its nodes -> the same chain count, less the wedges that
  break the constraint (a cached count per edge for ``a = c``, an int8
  matrix product for the closing edge): no row of the chain is built;
* ``WITH DISTINCT a, c RETURN count(*)`` -> per-hop (key, position)
  programs ending in a packed values-only sort count;
* ``ORDER BY ... LIMIT k`` -> one ``lax.top_k`` over a packed rank.

The printed plans show the fused operators; the timings show that query
latency is dominated by round trips, not rows. Runs on whatever platform
JAX selects and prints which.

Run:  python examples/04_fused_counting.py
"""

import os
import sys
import time

os.environ.setdefault("JAX_ENABLE_X64", "1")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def main():
    import jax

    from tpu_cypher import CypherSession
    from tpu_cypher.api.mapping import NodeMappingBuilder, RelationshipMappingBuilder
    from tpu_cypher.relational.graphs import ElementTable

    print(f"platform: {jax.devices()[0].platform}")
    rng = np.random.default_rng(7)
    n, e = 20_000, 200_000
    ids = np.arange(n, dtype=np.int64) * 3 + 11
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    keep = src != dst
    src, dst = src[keep], dst[keep]

    session = CypherSession.tpu()
    nodes = session.table_cls.from_columns({"id": ids.tolist()})
    nm = NodeMappingBuilder.on("id").with_implied_label("Person").build()
    rel_ids = np.arange(len(src), dtype=np.int64) + int(ids.max()) + 1
    rels = session.table_cls.from_columns(
        {"rid": rel_ids.tolist(), "s": ids[src].tolist(), "t": ids[dst].tolist()}
    )
    rm = (
        RelationshipMappingBuilder.on("rid")
        .from_("s")
        .to("t")
        .with_relationship_type("KNOWS")
        .build()
    )
    g = session.read_from(ElementTable(nm, nodes), ElementTable(rm, rels))

    queries = [
        ("2-hop count (fused SpMV chain)",
         "MATCH (a:Person)-[:KNOWS]->(b)-[:KNOWS]->(c) RETURN count(*) AS c"),
        ("3-hop count (fused SpMV chain)",
         "MATCH (a:Person)-[:KNOWS]->(b)-[:KNOWS]->(c)-[:KNOWS]->(d) RETURN count(*) AS c"),
        ("2-hop count, ends distinct (chain count less the two-cycles)",
         "MATCH (a:Person)-[:KNOWS]->(b)-[:KNOWS]->(c) WHERE a <> c RETURN count(*) AS c"),
        ("2-hop count, ends distinct and not friends (closing program on the MXU)",
         "MATCH (a:Person)-[:KNOWS]->(b)-[:KNOWS]->(c) "
         "WHERE a <> c AND NOT (a)-[:KNOWS]->(c) RETURN count(*) AS c"),
        ("distinct endpoint pairs (fused sort count)",
         "MATCH (a:Person)-[:KNOWS]->(b)-[:KNOWS]->(c) WITH DISTINCT a, c RETURN count(*) AS pairs"),
        ("var-length walk count (fused frontier loop)",
         "MATCH (a:Person)-[:KNOWS*1..2]->(b) RETURN count(*) AS walks"),
        ("top-5 by degree (packed top-k)",
         "MATCH (a:Person)-[:KNOWS]->(b) RETURN id(a) AS i, count(*) AS deg ORDER BY deg DESC, i LIMIT 5"),
    ]
    for label, q in queries:
        g.cypher(q).records.collect()  # warm: index build + compile
        t0 = time.perf_counter()
        rows = [dict(r) for r in g.cypher(q).records.collect()]
        dt = time.perf_counter() - t0
        print(f"{label}\n  {q}\n  -> {rows}  ({dt*1000:.1f} ms warm)\n")

    plans = g.cypher(queries[0][1]).plans
    print(plans[plans.index("=== Relational plan ===") :])


if __name__ == "__main__":
    main()

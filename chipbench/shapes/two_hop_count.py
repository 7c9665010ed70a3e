"""Every two-hop path of the graph, counted: the fused count chain."""

QUERY = (
    "MATCH (a:Person)-[:KNOWS]->(b:Person)-[:KNOWS]->(c:Person) "
    "RETURN count(*) AS c"
)


def draw_params(ref, rng):
    return {}


def reference(ref, params):
    return [{"c": int(ref.outdeg[ref.d].sum())}]

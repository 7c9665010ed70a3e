"""LSQB Q9: Q6 where the two ends are not friends themselves — the negated
edge between two nodes of the chain."""

import lsqb_reference

QUERY = (
    "MATCH (p1:Person)-[:KNOWS]->(p2:Person)-[:KNOWS]->(p3:Person)"
    "-[:HAS_INTEREST]->(t:Tag) WHERE p1 <> p3 AND NOT (p1)-[:KNOWS]->(p3) "
    "RETURN count(*) AS c"
)


def draw_params(ref, rng):
    return {}


def reference(ref, params):
    return [{"c": lsqb_reference.counts(ref)["q9"]}]

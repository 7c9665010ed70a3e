"""LSQB Q6: friends of friends and their interests, the two ends distinct.
LSQB writes both KNOWS hops undirected over pairs stored once; here every
pair is stored in both directions and the hops are written ``->``, which
walks the same wedges (the configuration's file says why)."""

import lsqb_reference

QUERY = (
    "MATCH (p1:Person)-[:KNOWS]->(p2:Person)-[:KNOWS]->(p3:Person)"
    "-[:HAS_INTEREST]->(t:Tag) WHERE p1 <> p3 RETURN count(*) AS c"
)


def draw_params(ref, rng):
    return {}


def reference(ref, params):
    return [{"c": lsqb_reference.counts(ref)["q6"]}]

"""Every KNOWS edge between persons, counted."""

QUERY = "MATCH (a:Person)-[:KNOWS]->(b:Person) RETURN count(*) AS c"


def draw_params(ref, rng):
    return {}


def reference(ref, params):
    return [{"c": int(ref.e)}]

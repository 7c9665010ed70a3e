"""LSQB Q7: Q4's star with the liker and the replying comment OPTIONAL — a
message nobody likes or answers still counts once a tag."""

import lsqb_tree_reference

QUERY = (
    "MATCH (:Tag)<-[:HAS_TAG]-(message:Message)-[:HAS_CREATOR]->(creator:Person) "
    "OPTIONAL MATCH (message)<-[:LIKES]-(liker:Person) "
    "OPTIONAL MATCH (message)<-[:REPLY_OF]-(comment:Comment) "
    "RETURN count(*) AS count"
)


def draw_params(ref, rng):
    return {}


def reference(ref, params):
    return [{"count": lsqb_tree_reference.counts(ref)["q7"]}]

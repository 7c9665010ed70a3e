"""LSQB Q4: the star round a message — a tag, the creator, a liker, a
replying comment; a message is a post or a comment (``Message``)."""

import lsqb_tree_reference

QUERY = (
    "MATCH (:Tag)<-[:HAS_TAG]-(message:Message)-[:HAS_CREATOR]->(creator:Person), "
    "(message)<-[:LIKES]-(liker:Person), "
    "(message)<-[:REPLY_OF]-(comment:Comment) RETURN count(*) AS count"
)


def draw_params(ref, rng):
    return {}


def reference(ref, params):
    return [{"count": lsqb_tree_reference.counts(ref)["q4"]}]

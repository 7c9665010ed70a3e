"""LSQB Q1: the path over all nine labels — a country, a city in it, a person
there, a forum the person is a member of, a post in the forum, a comment
that replies to the post, a tag of the comment, the tag's class."""

import lsqb_tree_reference

QUERY = (
    "MATCH (:Country)<-[:IS_PART_OF]-(:City)<-[:IS_LOCATED_IN]-(:Person)"
    "<-[:HAS_MEMBER]-(:Forum)-[:CONTAINER_OF]->(:Post)<-[:REPLY_OF]-(:Comment)"
    "-[:HAS_TAG]->(:Tag)-[:HAS_TYPE]->(:TagClass) RETURN count(*) AS count"
)


def draw_params(ref, rng):
    return {}


def reference(ref, params):
    return [{"count": lsqb_tree_reference.counts(ref)["q1"]}]

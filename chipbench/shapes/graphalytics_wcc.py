"""LDBC Graphalytics WCC: the weakly connected component of every vertex
(the smallest id in it), read back as a row a component size — how many
components have it and the sum of their ids. One wrong label moves a row."""

import graphalytics_reference

QUERY = (
    "CALL algo.wcc('EDGE') YIELD node, component "
    "WITH component, count(*) AS size "
    "RETURN size, count(*) AS components, sum(component) AS id_sum ORDER BY size"
)


def draw_params(ref, rng):
    return {}


def reference(ref, params):
    return graphalytics_reference.wcc_summary(ref)

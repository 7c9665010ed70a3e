"""LDBC Graphalytics BFS: the depth of every vertex from one source, read
back as a row a depth — how many vertices and the sum of their ids, the
unreachable (null depth) last. One wrong depth moves a row.

The source is drawn from ``--seed`` uniformly over the vertices of the
largest component of the graph (the one of the smallest id among equals):
Graphalytics names one source per data set, and graph500-22's is not
known here. The number of levels then follows the source (``PERF.md``)."""

import numpy as np

import graphalytics_reference

QUERY = (
    "CALL algo.bfs($source, 'EDGE') YIELD node, depth "
    "RETURN depth, count(*) AS vertices, sum(node.id) AS id_sum ORDER BY depth"
)


def draw_params(ref, rng):
    component = graphalytics_reference.components(ref)
    labels, sizes = np.unique(component, return_counts=True)
    largest = ref.ids[component == labels[np.argmax(sizes)]]
    return {"source": int(largest[rng.integers(len(largest))])}


def reference(ref, params):
    return graphalytics_reference.bfs_summary(ref, params["source"])

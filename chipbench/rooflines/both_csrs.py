"""Least traffic of a whole-graph BFS or weakly connected components,
whatever computes them.

Graphalytics's graphs are undirected, and this engine keeps an undirected
type's edges once, in two CSR orientations. Any program has to read both
once — a row pointer per vertex and one more, and a column index per edge,
at the configuration's index width — and to write one 64-bit value per
vertex (a depth, a component's id). That is all this counts: a lower bound.
A label propagation reads every lane at every step, so its share cannot
pass 1 / steps of what it moves; a top-down BFS reads each lane of the
source's component once, and a hooking WCC could.
"""

ORIENTATIONS = 2


def least_bytes(vertices: int, edges: int, itemsize: int) -> int:
    return ORIENTATIONS * ((vertices + 1) + edges) * itemsize + 8 * vertices


def least_seconds(sizes: dict, itemsize: int, peaks: dict) -> float:
    return least_bytes(sizes["persons"], sizes["edges"], itemsize) / peaks["bytes"]

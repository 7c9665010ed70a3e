"""Least traffic of the closed-wedge sum — over the wedges ``a -> b -> c``
of KNOWS that a KNOWS edge from ``a`` to ``c`` closes, ``c``'s weight —
whatever computes it.

Any program has to read every KNOWS column index and the row pointers once
(the adjacency is the input: no closed wedge is found without it) and one
weight per person, and write one number per person before the last sum.
That is all this counts, at the configuration's index width: a lower
bound, far under what a sparse intersection or a dense product moves, so
the share reads well under 1%. No operation count is given: the dense form
spends 2 n^3 int8 operations where a sparse one spends sum_e min(deg)
probes, and neither is the work the sum needs.
"""


def least_bytes(persons: int, edges: int, itemsize: int) -> int:
    return edges * itemsize + (persons + 1) * itemsize + 2 * persons * itemsize


def least_seconds(sizes: dict, itemsize: int, peaks: dict) -> float:
    return least_bytes(sizes["persons"], sizes["edges"], itemsize) / peaks["bytes"]

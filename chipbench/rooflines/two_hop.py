"""Least traffic of a whole-graph two-hop path count, whatever computes it.

The count is ``sum_e outdeg[dst[e]]``: two sparse matrix-vector products
over the CSR. Each hop reads every column index once and every node weight
once, and writes one weight per node; the row pointers are read once per
hop. No operation count is given: the work is integer adds, one per edge
and hop, for which the chip publishes no peak, and memory bounds it.
"""

HOPS = 2


def least_bytes(persons: int, edges: int, itemsize: int) -> int:
    per_hop = edges * itemsize + (persons + 1) * itemsize + 2 * persons * itemsize
    return HOPS * per_hop


def least_seconds(sizes: dict, itemsize: int, peaks: dict) -> float:
    return least_bytes(sizes["persons"], sizes["edges"], itemsize) / peaks["bytes"]

"""The Graph500 Kronecker graph as LDBC Graphalytics publishes it (data sets
``graph500-<scale>``): undirected, without self-loops, duplicate edges or
isolated vertices.

    snb_arrays(persons, knows, seed) -> {"ids", "src", "dst"}

(the name and arguments every generator of the harness has). From
``persons`` (the vertices the configuration publishes) comes the scale,
``ceil(log2(persons))``: 22 for graph500-22's 2,396,657. Graph500's own
recipe, written from memory of its specification (``assumed``): ``16 *
2**scale`` edges, each placed by ``scale`` draws of a quadrant with the
initiator A, B, C, D = 0.57, 0.19, 0.19, 0.05 (one uniform a level picks
the quadrant: the same distribution as the reference code's two draws, not
its stream), the vertex labels then permuted. The edge factor is Graph500's
16 whatever ``knows`` says: the edges left after the cleaning are the
outcome, as in Graphalytics. Each undirected edge is stored once, ``src <
dst``, sorted; a vertex's id is its permuted Kronecker label, under
``2**scale``. NumPy only, in chunks.
"""

from __future__ import annotations

import math

import numpy as np

INITIATOR = (0.57, 0.19, 0.19, 0.05)
EDGE_FACTOR = 16
CHUNK = 1 << 22  # edges drawn at a time


def scale_of(persons: int) -> int:
    return max(int(math.ceil(math.log2(max(persons, 2)))), 1)


def kronecker_edges(scale: int, rng) -> tuple:
    """The ``EDGE_FACTOR * 2**scale`` raw (row, column) pairs, labels not
    yet permuted."""
    a, b, c, _ = INITIATOR
    m = EDGE_FACTOR << scale
    rows = np.empty(m, np.int64)
    cols = np.empty(m, np.int64)
    for lo in range(0, m, CHUNK):
        k = min(CHUNK, m - lo)
        i = np.zeros(k, np.int32)
        j = np.zeros(k, np.int32)
        for bit in range(scale):
            u = rng.random(k, dtype=np.float32)
            i |= (u >= a + b).astype(np.int32) << bit  # quadrant C or D
            j |= (((u >= a) & (u < a + b)) | (u >= a + b + c)).astype(np.int32) << bit
        rows[lo:lo + k] = i
        cols[lo:lo + k] = j
    return rows, cols


def snb_arrays(persons: int, knows: int, seed: int) -> dict:
    rng = np.random.default_rng(seed)
    scale = scale_of(persons)
    n = 1 << scale
    rows, cols = kronecker_edges(scale, rng)
    label = rng.permutation(n).astype(np.int64)
    u, v = label[rows], label[cols]
    del rows, cols
    lo, hi = np.minimum(u, v), np.maximum(u, v)
    del u, v
    keep = lo != hi
    keys = np.unique(lo[keep] * n + hi[keep])  # sorted, each edge once
    del lo, hi, keep
    src, dst = keys // n, keys % n
    ids = np.unique(np.concatenate([src, dst]))  # no isolated vertex
    return {"ids": ids, "src": src, "dst": dst}

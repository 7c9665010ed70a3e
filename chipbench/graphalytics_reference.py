"""The plain reference of the Graphalytics cell: BFS depths and weakly
connected components over the generated arrays, and the summaries the two
shapes return.

Imports nothing of the program. The edges are ``ref.s`` / ``ref.d`` (the
rows of each edge's ends), so a control that drops edges (``stale_snapshot``)
drops them here too; every sum passes through ``ref.held``, so ``int32``
wraps them. SciPy's ``csgraph`` does the traversals where SciPy is present
(both undirected), plain NumPy otherwise: a frontier loop, and minimum
labels propagated over the edges with pointer jumping. The work is done
once per reference and kept on it.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

try:
    from scipy.sparse import coo_matrix
    from scipy.sparse import csgraph
except ImportError:  # pragma: no cover - the NumPy forms below
    csgraph = None


def _memo(ref) -> dict:
    return ref.__dict__.setdefault("_graphalytics", {})


def _adjacency(ref):
    memo = _memo(ref)
    if "adjacency" not in memo:
        ones = np.ones(len(ref.s), np.int8)
        memo["adjacency"] = coo_matrix(
            (ones, (ref.s, ref.d)), shape=(ref.n, ref.n)
        ).tocsr()
    return memo["adjacency"]


def _numpy_depths(ref, source: int) -> np.ndarray:
    a = np.concatenate([ref.s, ref.d])
    b = np.concatenate([ref.d, ref.s])
    order = np.argsort(a, kind="stable")
    b = b[order]
    ptr = np.searchsorted(a[order], np.arange(ref.n + 1))
    depth = np.full(ref.n, -1, np.int64)
    depth[source] = 0
    frontier, level = [source], 0
    while len(frontier):
        level += 1
        near = np.unique(np.concatenate([b[ptr[v]:ptr[v + 1]] for v in frontier]))
        frontier = near[depth[near] < 0]
        depth[frontier] = level
    return depth


def _numpy_labels(ref) -> np.ndarray:
    label = np.arange(ref.n)
    while True:
        new = label.copy()
        np.minimum.at(new, ref.s, label[ref.d])
        np.minimum.at(new, ref.d, label[ref.s])
        new = new[new]
        if (new == label).all():
            return label
        label = new


def components(ref) -> np.ndarray:
    """Per row: the smallest vertex id of its component."""
    memo = _memo(ref)
    if "components" not in memo:
        if csgraph is not None:
            _, label = csgraph.connected_components(_adjacency(ref), directed=False)
        else:
            label = _numpy_labels(ref)
        least = np.full(label.max() + 1, np.iinfo(np.int64).max)
        np.minimum.at(least, label, ref.ids)
        memo["components"] = least[label]
    return memo["components"]


def depths(ref, source_id: int) -> np.ndarray:
    """Per row: hops from the vertex ``source_id``, -1 where it cannot
    reach."""
    memo = _memo(ref)
    key = ("depths", source_id)
    if key not in memo:
        source = int(np.flatnonzero(ref.ids == source_id)[0])
        if csgraph is not None:
            dist = csgraph.shortest_path(
                _adjacency(ref), directed=False, unweighted=True, indices=source
            )
            memo[key] = np.where(np.isinf(dist), -1, dist).astype(np.int64)
        else:
            memo[key] = _numpy_depths(ref, source)
    return memo[key]


def _sums_by(keys: np.ndarray, values: np.ndarray):
    """(sorted distinct keys, rows of each, sum of ``values`` for each)."""
    order = np.argsort(keys, kind="stable")
    keys, values = keys[order], values[order]
    first = np.flatnonzero(np.r_[True, keys[1:] != keys[:-1]])
    counts = np.diff(np.r_[first, len(keys)])
    return keys[first], counts, np.add.reduceat(values, first) if len(keys) else values


def bfs_summary(ref, source_id: int) -> List[Dict[str, Optional[int]]]:
    """``RETURN depth, count(*) AS vertices, sum(node.id) AS id_sum ORDER BY
    depth``: a row a depth, the unreachable (null) last."""
    depth = depths(ref, source_id)
    levels, counts, sums = _sums_by(depth, ref.ids.astype(np.int64))
    rows = [{"depth": int(d) if d >= 0 else None, "vertices": int(c),
             "id_sum": int(s)} for d, c, s in zip(levels, counts, sums)]
    rows.sort(key=lambda r: (r["depth"] is None, r["depth"] or 0))
    return ref.held(rows)


def wcc_summary(ref) -> List[Dict[str, int]]:
    """``WITH component, count(*) AS size RETURN size, count(*) AS
    components, sum(component) AS id_sum ORDER BY size``."""
    comp, size, _ = _sums_by(components(ref), np.zeros(ref.n, np.int64))
    sizes, counts, sums = _sums_by(size, comp)
    return ref.held([
        {"size": int(s), "components": int(c), "id_sum": int(t)}
        for s, c, t in zip(sizes, counts, sums)
    ])

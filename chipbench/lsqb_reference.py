"""LSQB Q6 and Q9 by enumeration, in plain NumPy: every wedge
``p1 -> p2 -> p3`` of the reference's KNOWS rows is written out, a block of
first edges at a time (the 7e8 wedges of scale factor 10 never stand in
memory at once), tested as the query says — ``p1 <> p3``, and for Q9 no
KNOWS row from ``p1`` to ``p3`` (membership of ``p1 * n + p3`` in the sorted
edge keys) — and weighted by ``p3``'s number of interests. Nothing of the
program, and none of its closed forms. Both counts come from one pass and
are kept on the reference they were computed from.
"""

import numpy as np

BLOCK = 1 << 14  # first edges a step: about 2M wedges at scale factor 10


def counts(ref):
    got = ref.__dict__.get("_lsqb_counts")
    if got is None:
        got = ref.__dict__["_lsqb_counts"] = _enumerate(ref)
    return got


def _enumerate(ref):
    n, s, d = ref.n, ref.s, ref.d
    order = np.argsort(ref.ids)
    holder = order[np.searchsorted(ref.ids[order], ref.arrays["interest_person"])]
    interests = np.bincount(holder, minlength=n).astype(np.int64)
    by_source = np.argsort(s, kind="stable")
    first, friend = s[by_source], d[by_source]
    start = np.searchsorted(first, np.arange(n + 1))
    keys = np.sort(s.astype(np.int64) * n + d)
    q6 = q9 = 0
    for lo in range(0, len(s), BLOCK):
        p1, p2 = s[lo:lo + BLOCK], d[lo:lo + BLOCK]
        fan = start[p2 + 1] - start[p2]
        edge = np.repeat(np.arange(len(p1)), fan)
        nth = np.arange(len(edge)) - np.repeat(np.cumsum(fan) - fan, fan)
        p3 = friend[start[p2][edge] + nth]
        a = p1[edge]
        apart = a != p3
        probe = a.astype(np.int64) * n + p3
        at = np.searchsorted(keys, probe)
        known = keys[np.minimum(at, len(keys) - 1)] == probe
        worth = interests[p3]
        q6 += int(worth[apart].sum())
        q9 += int(worth[apart & ~known].sum())
    return {"q6": q6, "q9": q9}

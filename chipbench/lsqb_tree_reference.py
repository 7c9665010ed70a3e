"""LSQB Q1, Q4 and Q7 by enumeration, in plain NumPy: each query as it is
written, a join at a time over the generated arrays — the rows of a step
are the rows of the step before, each repeated once per matching edge whose
far end carries the label the pattern asks for, a block of rows at a time
(Q1's rows never stand in memory at once). An ``OPTIONAL MATCH`` is a
left-outer step over row POSITIONS: a row without a match stays once, its
new variable null, whatever an earlier optional step left null and however
many rows are equal. A row is the nodes its later steps still read; the
last step's rows are counted, not built (their number is the sum of the
matches of the rows before). Nothing of the program, and none of its
closed forms: no multiplicity per node is ever taken.

The controls: ``int32`` is ``reference.Reference.held``'s; the stale
snapshot of ``reference.py`` lacks the last 1/64 of the KNOWS rows, which
none of the three queries reads, so this module drops the same share (told
from ``ref.e`` against the generated KNOWS rows) from the tail of every
edge table it reads.
"""

import numpy as np

BLOCK = 1 << 21  # rows a step may hold before it is cut in two

NODES = {
    "Person": ("ids",), "City": ("city_ids",), "Country": ("country_ids",),
    "Tag": ("tag_ids",), "TagClass": ("tagclass_ids",), "Forum": ("forum_ids",),
    "Post": ("post_ids",), "Comment": ("comment_ids",),
    "Message": ("post_ids", "comment_ids"),
}
EDGES = {
    "IS_LOCATED_IN": (("ids",), ("person_city",)),
    "IS_PART_OF": (("city_ids",), ("city_country",)),
    "HAS_TYPE": (("tag_ids",), ("tag_class",)),
    "HAS_MEMBER": (("member_forum",), ("member_person",)),
    "CONTAINER_OF": (("post_forum",), ("post_ids",)),
    "HAS_CREATOR": (("post_ids", "comment_ids"), ("post_creator", "comment_creator")),
    "LIKES": (("like_person",), ("like_message",)),
    "REPLY_OF": (("comment_ids",), ("comment_parent",)),
    "HAS_TAG": (("msgtag_message",), ("msgtag_tag",)),
}

# a query: the label its rows start from, then its steps — (the variable
# expanded from, the new variable, the type, does the relationship point
# from the old to the new, the new node's label, is the step OPTIONAL)
Q1 = ("a", "Country", [
    ("a", "b", "IS_PART_OF", False, "City", False),
    ("b", "c", "IS_LOCATED_IN", False, "Person", False),
    ("c", "d", "HAS_MEMBER", False, "Forum", False),
    ("d", "e", "CONTAINER_OF", True, "Post", False),
    ("e", "f", "REPLY_OF", False, "Comment", False),
    ("f", "g", "HAS_TAG", True, "Tag", False),
    ("g", "h", "HAS_TYPE", True, "TagClass", False),
])
_HEAD = [("t", "message", "HAS_TAG", False, "Message", False),
         ("message", "creator", "HAS_CREATOR", True, "Person", False)]
Q4 = ("t", "Tag", _HEAD + [
    ("message", "liker", "LIKES", False, "Person", False),
    ("message", "comment", "REPLY_OF", False, "Comment", False)])
Q7 = ("t", "Tag", _HEAD + [
    ("message", "liker", "LIKES", False, "Person", True),
    ("message", "comment", "REPLY_OF", False, "Comment", True)])
QUERIES = {"q1": Q1, "q4": Q4, "q7": Q7}


class _Tables:
    """The generated node and edge tables by position in one sorted id
    space; an edge table's rows grouped by either end, made on first use."""

    def __init__(self, ref):
        self.arrays = ref.arrays
        self.lost = 1.0 - ref.e / max(len(ref.arrays["src"]), 1)
        self.ids = np.sort(np.concatenate(
            [ref.arrays[k] for keys in NODES.values() for k in keys
             if len(keys) == 1]))
        self._labels, self._runs = {}, {}

    def position(self, ids):
        return np.searchsorted(self.ids, ids)

    def carries(self, label):
        if label not in self._labels:
            flag = np.zeros(len(self.ids), dtype=bool)
            for key in NODES[label]:
                flag[self.position(self.arrays[key])] = True
            self._labels[label] = flag
        return self._labels[label]

    def runs(self, rel_type, forward):
        """(start, far): node ``p``'s far ends are ``far[start[p]:start[p + 1]]``."""
        if (rel_type, forward) not in self._runs:
            source, target = (
                np.concatenate([self.arrays[k] for k in keys])
                for keys in EDGES[rel_type])
            keep = len(source) - int(len(source) * self.lost)
            near, far = (source, target) if forward else (target, source)
            near, far = self.position(near[:keep]), self.position(far[:keep])
            order = np.argsort(near, kind="stable")
            start = np.searchsorted(near[order], np.arange(len(self.ids) + 1))
            self._runs[rel_type, forward] = (start, far[order])
        return self._runs[rel_type, forward]


def _matches(tables, rows, step):
    """Per row of the block the matching far ends of one step: ``(row,
    far)``, a row once per match; a null (-1) near node matches nothing."""
    near, _, rel_type, forward, label, _ = step
    start, far = tables.runs(rel_type, forward)
    at = rows[near]
    bound = at >= 0
    lo = np.where(bound, start[np.maximum(at, 0)], 0)
    fan = np.where(bound, start[np.maximum(at, 0) + 1] - lo, 0)
    row = np.repeat(np.arange(len(at)), fan)
    nth = np.arange(len(row)) - np.repeat(np.cumsum(fan) - fan, fan)
    found = far[lo[row] + nth]
    keep = tables.carries(label)[found]
    return row[keep], found[keep]


def _count(tables, rows, steps, size):
    """The rows the remaining steps make of this block of rows, counted."""
    if not steps:
        return size
    if size > BLOCK:
        half = size // 2
        return (
            _count(tables, {k: v[:half] for k, v in rows.items()}, steps, half)
            + _count(tables, {k: v[half:] for k, v in rows.items()}, steps, size - half))
    step, later = steps[0], steps[1:]
    row, found = _matches(tables, rows, step)
    if step[5]:  # OPTIONAL: a row without a match stays, the new node null
        alone = np.flatnonzero(np.bincount(row, minlength=size) == 0)
        row = np.concatenate([row, alone])
        found = np.concatenate([found, np.full(len(alone), -1, dtype=found.dtype)])
    if not later:
        return len(row)
    read = {s[0] for s in later}
    grown = {k: v[row] for k, v in rows.items() if k in read}
    if step[1] in read:
        grown[step[1]] = found
    return _count(tables, grown, later, len(row))


def counts(ref):
    got = ref.__dict__.get("_lsqb_tree_counts")
    if got is None:
        tables = _Tables(ref)
        got = {}
        for name, (var, label, steps) in QUERIES.items():
            first = np.flatnonzero(tables.carries(label))
            got[name] = int(_count(tables, {var: first}, steps, len(first)))
        ref.__dict__["_lsqb_tree_counts"] = got
    return got

"""Pairs of a person of the first joining period with every person born on
the same day, counted: a value join (no edge is walked). The window is part
of the query's text (2199023255552 = 1 << 41: the ids of the first of the
generator's seventeen joining periods); the build side is the whole Person
table. At SF100 the window holds about 26,000 persons and the join
yields about 3.2 million pairs (3,244,164 at seed 3500000001)."""

import numpy as np

JOINED_BEFORE = 1 << 41

QUERY = (
    f"MATCH (a:Person) WHERE a.id < {JOINED_BEFORE} WITH a "
    "MATCH (b:Person) WHERE b.birthday = a.birthday RETURN count(*) AS c"
)


def draw_params(ref, rng):
    return {}


def reference(ref, params):
    bday = ref.column("birthday")
    days, day_of, born = np.unique(bday, return_inverse=True, return_counts=True)
    return [{"c": int(born[day_of[ref.ids < JOINED_BEFORE]].sum())}]

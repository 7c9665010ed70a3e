"""Persons per browser: count, youngest, oldest and the sum of the ids.
A grouped aggregate over a string key; the sums are about 10**18."""

import numpy as np

QUERY = (
    "MATCH (a:Person) RETURN a.browserUsed AS browser, count(a.id) AS n, "
    "min(a.birthday) AS lo, max(a.birthday) AS hi, sum(a.id) AS s "
    "ORDER BY browser"
)


def draw_params(ref, rng):
    return {}


def reference(ref, params):
    browser, bday = ref.column("browserUsed"), ref.column("birthday")
    out = []
    for name in np.unique(browser):
        rows = browser == name
        out.append({"browser": str(name), "n": int(rows.sum()),
                    "lo": int(bday[rows].min()), "hi": int(bday[rows].max()),
                    "s": int(ref.ids[rows].sum())})
    return out

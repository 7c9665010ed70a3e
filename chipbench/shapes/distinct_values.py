"""How many different birthdays the persons have: DISTINCT over one 64-bit
column, counted. Every person is a row of the exchange; the answer is at
most the generator's 3,653 days."""

import numpy as np

QUERY = "MATCH (a:Person) WITH DISTINCT a.birthday AS b RETURN count(*) AS c"


def draw_params(ref, rng):
    return {}


def reference(ref, params):
    return [{"c": int(len(np.unique(ref.column("birthday"))))}]

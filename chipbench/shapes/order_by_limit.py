"""The ten youngest persons: a full sort with a LIMIT."""

import numpy as np

QUERY = (
    "MATCH (a:Person) RETURN a.id AS id, a.birthday AS b "
    "ORDER BY b DESC, id ASC LIMIT 10"
)


def draw_params(ref, rng):
    return {}


def reference(ref, params):
    bday = ref.column("birthday")
    top = np.lexsort((ref.ids, -bday))[:10]
    return [{"id": int(ref.ids[i]), "b": int(bday[i])} for i in top]

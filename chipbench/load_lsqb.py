"""The generated LSQB tables into the program, by its public ingest of any
number of node and relationship tables (``tpu_cypher.io.ldbc
.graph_from_tables``): four labels over one id space, four relationship
types. As LSQB's projected files do, a node carries its ``id`` and nothing
else, and an edge nothing but its ends. KNOWS is stored in both directions
(``gen_snb`` hands it over so); the other three as they point.
"""

from __future__ import annotations

from tpu_cypher.api import types as T
from tpu_cypher.io.ldbc import graph_from_tables

NODES = {"Person": "ids", "City": "city_ids", "Country": "country_ids",
         "Tag": "tag_ids"}
RELATIONSHIPS = {
    "KNOWS": ("src", "dst"),
    "IS_LOCATED_IN": ("ids", "person_city"),
    "IS_PART_OF": ("city_ids", "city_country"),
    "HAS_INTEREST": ("interest_person", "interest_tag"),
}


def load(session, arrays):
    return graph_from_tables(
        session,
        {label: (arrays[key], {"id": (arrays[key], T.CTInteger.nullable)})
         for label, key in NODES.items()},
        {rel_type: (arrays[source], arrays[target], {})
         for rel_type, (source, target) in RELATIONSHIPS.items()},
    )

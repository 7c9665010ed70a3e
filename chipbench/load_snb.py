"""The generated Person/KNOWS arrays into the program, by its public
ingest: column tables (``session.table_cls.from_arrays``), a node and a
relationship mapping, a ``ScanGraph``. What ``tpu_cypher.io.ldbc`` does for
LDBC's CSV files, with the whole Person row and KNOWS's ``creationDate``;
the 64-bit columns stay NumPy (one copy to the device each), the strings
go in as lists (the program builds their dictionaries).
"""

from __future__ import annotations

import numpy as np

PERSON = ("firstName", "lastName", "gender", "birthday", "creationDate",
          "locationIP", "browserUsed")
EDGE_ID_FROM = 1 << 53  # KNOWS ids, clear of every person id


def load(session, arrays):
    from tpu_cypher.api import types as T
    from tpu_cypher.api.mapping import NodeMapping, RelationshipMapping
    from tpu_cypher.api.schema import PropertyGraphSchema
    from tpu_cypher.relational.graphs import ElementTable, ScanGraph

    def held(column):
        return column if column.dtype == np.int64 else column.tolist()

    def cypher_type(column):
        return (T.CTInteger if column.dtype == np.int64 else T.CTString).nullable

    person = {"id": arrays["ids"], **{k: held(arrays[k]) for k in PERSON}}
    person_types = {"id": T.CTInteger.nullable,
                    **{k: cypher_type(arrays[k]) for k in PERSON}}
    knows = {
        "id": np.arange(len(arrays["src"]), dtype=np.int64) + EDGE_ID_FROM,
        "source": arrays["src"],
        "target": arrays["dst"],
        "creationDate": arrays["knows_creationDate"],
    }
    schema = (
        PropertyGraphSchema.empty()
        .with_node_combination(frozenset({"Person"}), person_types)
        .with_relationship_type("KNOWS", {"creationDate": T.CTInteger.nullable})
    )
    return ScanGraph(
        [
            ElementTable(
                NodeMapping(
                    id_key="id",
                    implied_labels=frozenset({"Person"}),
                    property_mapping=tuple((k, k) for k in person_types),
                ),
                session.table_cls.from_arrays(person),
            ),
            ElementTable(
                RelationshipMapping(
                    id_key="id",
                    source_key="source",
                    target_key="target",
                    rel_type="KNOWS",
                    property_mapping=(("creationDate", "creationDate"),),
                ),
                session.table_cls.from_arrays(knows),
            ),
        ],
        schema,
    )

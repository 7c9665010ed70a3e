"""The generated Graph500 graph into the program, by its public ingest
(``tpu_cypher.io.ldbc.graph_from_tables``): one label, ``Vertex``, whose
``id`` is the vertex id, and one relationship type, ``EDGE``, each
undirected edge stored once (``src < dst``) — the procedures walk a type
both ways, so nothing is stored twice.
"""

from __future__ import annotations

from tpu_cypher.api import types as T
from tpu_cypher.io.ldbc import graph_from_tables


def load(session, arrays):
    ids = arrays["ids"]
    return graph_from_tables(
        session,
        {"Vertex": (ids, {"id": (ids, T.CTInteger.nullable)})},
        {"EDGE": (arrays["src"], arrays["dst"], {})},
    )

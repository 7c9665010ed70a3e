"""LDBC SNB graph support: datagen CSV loader + synthetic generator.

The driver-defined benchmark ladder (``BASELINE.md``) is LDBC Social Network
Benchmark shaped: Person/KNOWS at SF1..SF100 with 2-hop friends-of-friends,
triangle closure, and IS3-style property queries. Two entry points:

* ``load_snb_csv(dir)``  — reads the LDBC datagen "social_network" CSV layout
  (``person_0_0.csv``, ``person_knows_person_0_0.csv``, pipe-delimited with
  headers) into a property graph.
* ``generate_snb(scale)`` — synthesizes an SNB-like Person/KNOWS graph with
  power-law degrees for benchmarks when datagen output is unavailable
  (deterministic per seed).

The reference has no LDBC loader — its benchmark story is a JMH microbench
harness (``morpheus-jmh``); this module exists to back the TPU bench ladder.
"""

from __future__ import annotations

import csv
import os
from typing import Any, Dict, List, Mapping, Optional, Tuple

import numpy as np

from ..api import types as T
from ..api.mapping import NodeMapping, RelationshipMapping
from ..api.schema import PropertyGraphSchema
from ..relational.graphs import ElementTable, ScanGraph
from .datasource import DataSourceError

PERSON_LABEL = "Person"
KNOWS_TYPE = "KNOWS"

# LDBC person ids collide with nothing; KNOWS edge ids go in a disjoint range
EDGE_ID_OFFSET = 1 << 53


def _read_csv(path: str, delimiter: str = "|") -> Tuple[List[str], List[List[str]]]:
    with open(path, newline="") as f:
        r = csv.reader(f, delimiter=delimiter)
        header = next(r)
        return header, list(r)


def load_snb_csv(directory: str, session, delimiter: str = "|") -> ScanGraph:
    """Load the LDBC datagen person/knows slice from a ``social_network``
    CSV directory. Recognizes both ``person_0_0.csv`` (datagen v0.3) and
    ``Person.csv`` style names."""

    def find(*names: str) -> Optional[str]:
        for n in names:
            p = os.path.join(directory, n)
            if os.path.isfile(p):
                return p
        return None

    person_path = find("person_0_0.csv", "Person.csv", "person.csv")
    knows_path = find(
        "person_knows_person_0_0.csv", "Person_knows_Person.csv",
        "person_knows_person.csv",
    )
    if person_path is None or knows_path is None:
        raise DataSourceError(
            f"No LDBC person/knows CSVs under {directory!r} "
            "(expected person_0_0.csv + person_knows_person_0_0.csv)"
        )

    header, rows = _read_csv(person_path, delimiter)
    cols = {h.split(":")[0].lower(): i for i, h in enumerate(header)}
    if "id" not in cols:
        raise DataSourceError(f"LDBC person CSV lacks an id column: {header}")
    ids = [int(r[cols["id"]]) for r in rows]
    person_cols: Dict[str, List] = {"id": ids}
    prop_types: Dict[str, T.CypherType] = {}
    for key, ct in (
        ("firstname", T.CTString),
        ("lastname", T.CTString),
        ("gender", T.CTString),
        ("birthday", T.CTString),
        ("creationdate", T.CTString),
    ):
        if key in cols:
            person_cols[key] = [r[cols[key]] for r in rows]
            prop_types[key] = ct.nullable

    kh, krows = _read_csv(knows_path, delimiter)
    kcols = {h.split(":")[0].lower(): i for i, h in enumerate(kh)}
    # datagen names the endpoint columns Person1Id/Person2Id (or :START_ID)
    s_i = kcols.get("person1id", kcols.get("person.id", 0))
    t_i = kcols.get("person2id", 1 if len(kh) > 1 else 0)
    src = [int(r[s_i]) for r in krows]
    dst = [int(r[t_i]) for r in krows]

    # LDBC datagen stores KNOWS once per unordered pair; Cypher's SNB queries
    # traverse it both ways, so both orientations are stored (the reference
    # models undirected traversal as a union of orientations at plan time;
    # storing both keeps every hop a plain directed expand)
    return graph_from_tables(
        session,
        {PERSON_LABEL: (
            np.asarray(ids, dtype=np.int64),
            {k: (person_cols[k], t) for k, t in prop_types.items()},
        )},
        {KNOWS_TYPE: (
            np.asarray(src + dst, dtype=np.int64),
            np.asarray(dst + src, dtype=np.int64),
            {},
        )},
    )


def snb_arrays(scale: float, seed: int = 42) -> Dict[str, np.ndarray]:
    """The generator's host arrays, before any ingest: ``ids``,
    ``birthday`` and (up to 200k persons) ``firstname`` per person, and
    the KNOWS endpoints ``src``/``dst`` as person ids. ``scale=1.0``
    approximates SF1 density (~10k persons, ~450k directed KNOWS edges);
    degrees are power-law-ish (preferential-attachment flavored).
    Deterministic per seed."""
    num_people = max(2, int(10_000 * scale))
    num_knows = int(num_people * 45)
    rng = np.random.default_rng(seed)
    ids = np.arange(num_people, dtype=np.int64) * 7 + 1
    head = rng.zipf(1.35, size=num_knows) % num_people
    uni = rng.integers(0, num_people, size=num_knows)
    src_i = np.where(rng.random(num_knows) < 0.5, head, uni)
    dst_i = rng.integers(0, num_people, size=num_knows)
    keep = src_i != dst_i
    arrays = {
        "ids": ids,
        "src": ids[src_i[keep]],
        "dst": ids[dst_i[keep]],
        # birthday: days-since-epoch ints (IS3-style property filters)
        "birthday": rng.integers(0, 18_000, size=num_people, dtype=np.int64),
    }
    if num_people <= 200_000:  # string props only at list-walkable sizes
        arrays["firstname"] = np.array([f"p{i}" for i in range(num_people)])
    return arrays


def generate_snb(
    scale: float, session, seed: int = 42
) -> ScanGraph:
    """Synthetic SNB-like Person/KNOWS graph from ``snb_arrays``."""
    return graph_from_snb_arrays(session, snb_arrays(scale, seed))


def graph_from_snb_arrays(session, arrays: Dict[str, np.ndarray]) -> ScanGraph:
    """Ingest ``snb_arrays`` output. Columns stay numpy so the bulk
    ingestion path is one H2D copy per column at SF10 scale and beyond."""
    # expose the id column as a property too (LDBC queries anchor on
    # ``a.id`` ranges; the bench's var-length source filter does the same)
    props: Dict[str, Tuple[Any, T.CypherType]] = {
        "id": (arrays["ids"], T.CTInteger.nullable),
        "birthday": (arrays["birthday"], T.CTInteger.nullable),
    }
    if "firstname" in arrays:
        props["firstname"] = (arrays["firstname"].tolist(), T.CTString.nullable)
    return graph_from_tables(
        session,
        {PERSON_LABEL: (arrays["ids"], props)},
        {KNOWS_TYPE: (arrays["src"], arrays["dst"], {})},
    )


# ``graph_from_tables`` takes a label SET a node table (``("Message",
# "Post")``), not one label alone: what a loader of multi-label data asks for
LABEL_SETS = True


def graph_from_tables(
    session,
    nodes: Mapping[Any, Tuple[Any, Mapping[str, Tuple[Any, T.CypherType]]]],
    relationships: Mapping[
        str, Tuple[Any, Any, Mapping[str, Tuple[Any, T.CypherType]]]
    ],
) -> ScanGraph:
    """A ``ScanGraph`` of any number of node and relationship tables, one
    per label combination and per relationship type, from host arrays:

    * ``nodes[labels] = (ids, {property: (column, cypher_type)})`` — int64
      element ids, unique over ALL tables (the graph has one id space), and
      the table's property columns. ``labels`` is one label, or a tuple (or
      frozenset) of the labels every node of the table carries:
      ``("Message", "Post")`` and ``("Message", "Comment")`` are two tables
      that ``(:Message)`` scans both of. A property named ``id`` is the id
      column itself, exposed to queries as ``n.id``;
    * ``relationships[rel_type] = (source_ids, target_ids, {property:
      (column, cypher_type)})`` — the endpoints as node ids, one row per
      stored direction (a loader that wants an undirected edge walkable both
      ways stores both rows). Relationship ids are assigned here, in a range
      no node id reaches.

    Numeric and boolean NumPy columns take the bulk path (one copy to the
    device each); any other column (a list of strings) is decoded per
    value."""
    tables: List[ElementTable] = []
    schema = PropertyGraphSchema.empty()
    for label, (ids, props) in nodes.items():
        labels = frozenset({label} if isinstance(label, str) else label)
        if not labels or not all(isinstance(x, str) for x in labels):
            raise DataSourceError(
                f"a node table's labels are a label or a tuple of labels, got {label!r}"
            )
        ids = np.asarray(ids, dtype=np.int64)
        if len(ids) and int(ids.max()) >= EDGE_ID_OFFSET:
            raise DataSourceError(
                f"{label} ids exceed the supported id range (< 2**53)"
            )
        cols: Dict[str, Any] = {"id": ids}
        for key, (column, _) in props.items():
            if key != "id":
                cols[key] = column
        schema = schema.with_node_combination(
            labels, {k: t for k, (_, t) in props.items()}
        )
        tables.append(
            ElementTable(
                NodeMapping(
                    id_key="id",
                    implied_labels=labels,
                    property_mapping=tuple((k, k) for k in props),
                ),
                session.table_cls.from_arrays(cols),
            )
        )
    next_rel_id = EDGE_ID_OFFSET
    for rel_type, (src, dst, props) in relationships.items():
        src = np.asarray(src, dtype=np.int64)
        dst = np.asarray(dst, dtype=np.int64)
        if len(src) != len(dst):
            raise DataSourceError(
                f"{rel_type}: {len(src)} sources for {len(dst)} targets"
            )
        cols = {
            "id": np.arange(len(src), dtype=np.int64) + next_rel_id,
            "source": src,
            "target": dst,
        }
        next_rel_id += len(src)
        for key, (column, _) in props.items():
            cols[f"p_{key}"] = column  # clear of id / source / target
        schema = schema.with_relationship_type(
            rel_type, {k: t for k, (_, t) in props.items()}
        )
        tables.append(
            ElementTable(
                RelationshipMapping(
                    id_key="id",
                    source_key="source",
                    target_key="target",
                    rel_type=rel_type,
                    property_mapping=tuple((k, f"p_{k}") for k in props),
                ),
                session.table_cls.from_arrays(cols),
            )
        )
    return ScanGraph(tables, schema)


# The SNB query shapes the benchmark ladder runs (BASELINE.md configs 2-4)
FRIENDS_OF_FRIENDS = (
    "MATCH (a:Person)-[:KNOWS]->(b)-[:KNOWS]->(c) "
    "RETURN count(*) AS paths"
)
TRIANGLES = (
    "MATCH (a:Person)-[:KNOWS]->(b)-[:KNOWS]->(c)-[:KNOWS]->(a) "
    "RETURN count(*) AS triangles"
)

"""The generated LSQB tables — nine labels over one id space, eleven
relationship types — into the program, by its public ingest of any number of
node and relationship tables (``tpu_cypher.io.ldbc.graph_from_tables``). A
post is ``Message:Post`` and a comment ``Message:Comment``: two node tables
under a label set each, which the ingest takes since PR 34
(``LABEL_SETS``: a program without it ends here, at the import, at once).
As LSQB's projected files do, a node carries its ``id`` and nothing else,
and an edge nothing but its ends. KNOWS is stored in both directions
(``gen_snb`` hands it over so); the other ten as they point.
"""

from __future__ import annotations

import numpy as np

from tpu_cypher.api import types as T
from tpu_cypher.io.ldbc import LABEL_SETS, graph_from_tables  # noqa: F401

NODES = {
    "Person": "ids", "City": "city_ids", "Country": "country_ids",
    "Tag": "tag_ids", "TagClass": "tagclass_ids", "Forum": "forum_ids",
    ("Message", "Post"): "post_ids", ("Message", "Comment"): "comment_ids",
}
# a name of two keys: the table of both (the posts' rows, then the comments')
RELATIONSHIPS = {
    "KNOWS": ("src", "dst"),
    "IS_LOCATED_IN": ("ids", "person_city"),
    "IS_PART_OF": ("city_ids", "city_country"),
    "HAS_INTEREST": ("interest_person", "interest_tag"),
    "HAS_TYPE": ("tag_ids", "tag_class"),
    "HAS_MEMBER": ("member_forum", "member_person"),
    "CONTAINER_OF": ("post_forum", "post_ids"),
    "HAS_CREATOR": ("post_ids+comment_ids", "post_creator+comment_creator"),
    "LIKES": ("like_person", "like_message"),
    "REPLY_OF": ("comment_ids", "comment_parent"),
    "HAS_TAG": ("msgtag_message", "msgtag_tag"),
}


def column(arrays, name):
    return np.concatenate([arrays[key] for key in name.split("+")])


def load(session, arrays):
    return graph_from_tables(
        session,
        {labels: (arrays[key], {"id": (arrays[key], T.CTInteger.nullable)})
         for labels, key in NODES.items()},
        {rel_type: (column(arrays, source), column(arrays, target), {})
         for rel_type, (source, target) in RELATIONSHIPS.items()},
    )

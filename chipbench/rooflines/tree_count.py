"""Least traffic of LSQB Q4's count — over every message the product of its
tags, creators, likers and replying comments, summed — whatever computes it.

Any program has to read the four adjacencies the star walks once (hasTag,
hasCreator, likes and replyOf, each a row pointer per node of the one id
space and a column index per edge: no message's count is known without
them) and to write one 64-bit number a message and branch before they
multiply. That is all this counts, at the configuration's index width: a
lower bound, under what four masked prefix scans move, so the share reads
well under 1%. The harness hands a roofline the persons and the KNOWS rows
alone; every other table's count is the generator's own share of the
persons (``gen_lsqb_full.table_counts``: what it aims at, within 3% of
what it draws).
"""

import gen_lsqb_full

BRANCHES = 4


def least_bytes(persons: int, itemsize: int) -> int:
    c = gen_lsqb_full.table_counts(persons)
    nodes = (
        persons + c["messages"] + c["forums"] + c["tag_classes"]
        + c["cities"] + c["countries"] + c["tags"]
    )
    edges = c["message_tags"] + c["messages"] + c["likes"] + c["comments"]
    return (
        BRANCHES * (nodes + 1) * itemsize + edges * itemsize
        + BRANCHES * c["messages"] * 8
    )


def least_seconds(sizes: dict, itemsize: int, peaks: dict) -> float:
    return least_bytes(sizes["persons"], itemsize) / peaks["bytes"]

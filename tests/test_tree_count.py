"""``count(*)`` over a pattern of expands that is a tree — a path, a star,
OPTIONAL leaves, a branch two hops deep, a branch under a label mask, the
plan started from either end or from the middle — is answered without a row
of the pattern (``CsrExpandOp.tree_count`` → ``jit_ops.tree_count``, asked
by ``AggregateOp._input_row_count``): the engine against an enumeration
that builds every row, on seeded random graphs of several labels where a
message is ``Message:Post`` or ``Message:Comment`` (two tables of one label
set each, ``io.ldbc.graph_from_tables``), with parallel edges and with
neighbours of another label on three of the four types. Two hops of one type
that can meet decline (relationship uniqueness), counted, and still answer
right. ``OPTIONAL MATCH`` itself is held to the same enumeration, row for
row, through both sessions: a left row comes out once per match or once with
nulls whatever an earlier OPTIONAL MATCH left null and however many left
rows are equal (``RelationalPlanner._plan_Optional`` joined on every shared
field until PR 34, and lost both)."""

import contextlib
from collections import Counter

import numpy as np
import pytest

from tpu_cypher import CypherSession
from tpu_cypher.api import types as T
from tpu_cypher.backend.tpu import expand_op
from tpu_cypher.backend.tpu import jit_ops as J
from tpu_cypher.io.ldbc import graph_from_tables
from tpu_cypher.obs.metrics import REGISTRY
from tpu_cypher.relational import planner as relational_planner
from tpu_cypher.relational.session import PropertyGraph

SERIES = "tpu_cypher_count_pushdown_total{op=tree,outcome=%s}"
LANES = "tpu_cypher_tree_count_lanes_total"
NODE_LANES = "tpu_cypher_count_scan_node_lanes_total"
HOPS = "tpu_cypher_count_chain_hops_total{form=%s}"

# programs that build rows of a pattern: none may run under a tree count
ROW_BUILDERS = (
    "expand_materialize", "expand_materialize_counted",
    "optional_expand_materialize", "join_materialize",
    "join_materialize_counted",
)


def make_tables(seed):
    """Node ids per label set and (source, target) rows per type. HAS_TAG:
    messages and a few forums to tags; LIKES: persons and a few forums to
    messages, some pairs twice; HAS_CREATOR: one person a message (every
    target a Person: the index proves that label); REPLY_OF: a comment to a
    post or to an earlier comment, some messages with many replies and most
    with none."""
    rng = np.random.default_rng(seed)
    person = np.arange(100, 112, dtype=np.int64)
    tag = np.arange(300, 306, dtype=np.int64)
    forum = np.arange(400, 404, dtype=np.int64)
    post = np.arange(500, 510, dtype=np.int64)
    comment = np.arange(600, 618, dtype=np.int64)
    message = np.concatenate([post, comment])

    def rows(src, dst, count):
        return rng.choice(src, count), rng.choice(dst, count)

    ts, td = rows(message[::2], tag, 26)
    fs, fd = rows(forum, tag, 5)
    ls, ld = rows(person, message[:20], 34)
    gs, gd = rows(forum, message, 6)
    parent = (rng.random(len(comment)) ** 2 * (len(post) + np.arange(len(comment)))).astype(int)
    nodes = {
        "Person": person, "Tag": tag, "Forum": forum,
        ("Message", "Post"): post, ("Message", "Comment"): comment,
        "Ghost": np.zeros(0, np.int64),
    }
    rels = {
        "HAS_TAG": (np.concatenate([ts, fs]), np.concatenate([td, fd])),
        "LIKES": (np.concatenate([ls, ls[:4], gs]), np.concatenate([ld, ld[:4], gd])),
        "HAS_CREATOR": (message, rng.choice(person, len(message))),
        "REPLY_OF": (comment, message[parent]),
    }
    return nodes, rels


def make_graph(session, nodes, rels):
    return PropertyGraph(session, graph_from_tables(
        session,
        {labels: (ids, {"id": (ids, T.CTInteger.nullable)})
         for labels, ids in nodes.items()},
        {t: (s, d, {}) for t, (s, d) in rels.items()},
    ))


def enumerate_rows(nodes, rels, start, clauses):
    """Every row of the pattern, built a hop at a time. ``start``: ``(var,
    labels)``; ``clauses``: ``(optional, hops)`` with a hop ``(near, far,
    type, forward, far_labels)`` — ``forward``: the relationship points
    from ``near`` to ``far`` (None: either way, a self-loop once). A clause
    repeats no relationship (it is one
    MATCH); an OPTIONAL clause without a match keeps its row once, the new
    variables null."""
    labels_of = {}
    for labels, ids in nodes.items():
        for i in ids.tolist():
            labels_of[i] = {labels} if isinstance(labels, str) else set(labels)
    edges = {t: list(zip(s.tolist(), d.tolist())) for t, (s, d) in rels.items()}
    var, need = start
    table = [{var: n} for n in sorted(labels_of) if set(need) <= labels_of[n]]
    for optional, hops in clauses:
        grown = []
        for row in table:
            found = [(row, frozenset())]
            for near, far, rel_type, forward, far_labels in hops:
                step = []
                for r, used in found:
                    if r[near] is None:
                        continue
                    for e, (s, d) in enumerate(edges[rel_type]):
                        ways = {True: [(s, d)], False: [(d, s)],
                                None: [(s, d)] + [(d, s)] * (s != d)}[forward]
                        for here, there in ways:
                            if (here == r[near] and (rel_type, e) not in used
                                    and set(far_labels) <= labels_of[there]):
                                step.append(
                                    ({**r, far: there}, used | {(rel_type, e)}))
                found = step
            if found or not optional:
                grown.extend(r for r, _ in found)
            else:
                grown.append({**row, **{h[1]: None for h in hops}})
        table = grown
    return table


HEAD = "MATCH (t:Tag)<-[:HAS_TAG]-(m:Message)-[:HAS_CREATOR]->(p:Person)"
HEAD_HOPS = [("t", "m", "HAS_TAG", False, ("Message",)),
             ("m", "p", "HAS_CREATOR", True, ("Person",))]
LIKES = ("m", "l", "LIKES", False, ("Person",))
REPLY = ("m", "c", "REPLY_OF", False, ("Comment",))

# name: (query, where the enumeration starts, its clauses, the node the
# plan has to start from, the programs that count)
SHAPES = {
    "path": (HEAD + " RETURN count(*) AS n", ("t", ("Tag",)),
             [(False, HEAD_HOPS)], None, "path_count_chain"),
    "star": (HEAD + ", (m)<-[:LIKES]-(l:Person), (m)<-[:REPLY_OF]-(c:Comment) "
             "RETURN count(*) AS n", ("t", ("Tag",)),
             [(False, HEAD_HOPS + [LIKES, REPLY])], None, "tree_count"),
    "star_one_optional": (
        HEAD + " OPTIONAL MATCH (m)<-[:LIKES]-(l:Person) RETURN count(*) AS n",
        ("t", ("Tag",)), [(False, HEAD_HOPS), (True, [LIKES])], None, "tree_count"),
    "star_two_optional": (
        HEAD + " OPTIONAL MATCH (m)<-[:LIKES]-(l:Person) "
        "OPTIONAL MATCH (m)<-[:REPLY_OF]-(c:Comment) RETURN count(*) AS n",
        ("t", ("Tag",)), [(False, HEAD_HOPS), (True, [LIKES]), (True, [REPLY])],
        None, "tree_count"),
    # LSQB Q7 as the cell sends it: no variable on the tag
    "q7": (
        "MATCH (:Tag)<-[:HAS_TAG]-(message:Message)-[:HAS_CREATOR]->(creator:Person) "
        "OPTIONAL MATCH (message)<-[:LIKES]-(liker:Person) "
        "OPTIONAL MATCH (message)<-[:REPLY_OF]-(comment:Comment) "
        "RETURN count(*) AS n",
        ("t", ("Tag",)), [(False, HEAD_HOPS), (True, [LIKES]), (True, [REPLY])],
        None, "tree_count"),
    "branch_two_deep": (
        "MATCH (m:Message)<-[:REPLY_OF]-(c:Comment)-[:HAS_CREATOR]->(p:Person), "
        "(m)-[:HAS_TAG]->(t:Tag), (c)<-[:LIKES]-(l) RETURN count(*) AS n",
        ("m", ("Message",)),
        [(False, [("m", "c", "REPLY_OF", False, ("Comment",)),
                  ("c", "p", "HAS_CREATOR", True, ("Person",)),
                  ("m", "t", "HAS_TAG", True, ("Tag",)),
                  ("c", "l", "LIKES", False, ())])],
        None, "tree_count"),
    # forums like messages too: the mask on the liker is not proven away;
    # a forum has tags: nor is the mask on the message
    "branch_under_a_mask": (
        "MATCH (t:Tag)<-[:HAS_TAG]-(m:Post), (m)<-[:LIKES]-(l:Person) "
        "OPTIONAL MATCH (m)<-[:LIKES]-(f:Forum) RETURN count(*) AS n",
        ("t", ("Tag",)),
        [(False, [("t", "m", "HAS_TAG", False, ("Post",)),
                  ("m", "l", "LIKES", False, ("Person",))]),
         (True, [("m", "f", "LIKES", False, ("Forum",))])],
        None, "tree_count"),
    "from_the_far_end": (
        "MATCH (t)<-[:HAS_TAG]-(m)-[:HAS_CREATOR]->(p:Person), (m)<-[:LIKES]-(l) "
        "RETURN count(*) AS n", ("p", ("Person",)),
        [(False, [("p", "m", "HAS_CREATOR", False, ()),
                  ("m", "t", "HAS_TAG", True, ()),
                  ("m", "l", "LIKES", False, ())])], "p", "tree_count"),
    "from_the_middle": (
        "MATCH (t)<-[:HAS_TAG]-(m:Message:Comment)-[:HAS_CREATOR]->(p) "
        "RETURN count(*) AS n", ("m", ("Message", "Comment")),
        [(False, [("m", "t", "HAS_TAG", True, ()),
                  ("m", "p", "HAS_CREATOR", True, ())])], "m", "tree_count"),
    # an undirected branch: every REPLY_OF source is a Comment, a target is
    # a post as often — the mask stays unless BOTH orientations prove it
    "an_undirected_branch": (
        "MATCH (m:Message)-[:HAS_TAG]->(t:Tag), (m)<-[:LIKES]-(l:Person), "
        "(c:Comment)-[:REPLY_OF]-(m) RETURN count(*) AS n", ("m", ("Message",)),
        [(False, [("m", "t", "HAS_TAG", True, ("Tag",)), LIKES,
                  ("m", "c", "REPLY_OF", None, ("Comment",))])],
        None, "tree_count"),
    # the stack's deepest operator is the OPTIONAL one (the TCK's "optional
    # match on a bound node preserves multiplicity")
    "optional_off_the_scan": (
        "MATCH (m:Message) OPTIONAL MATCH (m)<-[:LIKES]-(l:Person) "
        "RETURN count(*) AS n", ("m", ("Message",)), [(True, [LIKES])],
        "m", "tree_count"),
    "no_such_label": (
        HEAD + ", (m)<-[:LIKES]-(l:Ghost) RETURN count(*) AS n", ("t", ("Tag",)),
        [(False, HEAD_HOPS + [("m", "l", "LIKES", False, ("Ghost",))])],
        None, None),
}


@pytest.fixture(scope="module", params=[11, 12])
def world(request):
    nodes, rels = make_tables(request.param)
    return nodes, rels, make_graph(CypherSession.tpu(), nodes, rels)


def _counters():
    flat = REGISTRY.flat()
    names = [SERIES % "count", SERIES % "rows", LANES, NODE_LANES]
    names += [HOPS % form for form in ("degree", "reduce", "scan")]
    return {name: flat.get(name, 0.0) for name in names}


@contextlib.contextmanager
def spied(monkeypatch):
    calls = Counter()

    def counting(name):
        fn = getattr(J, name)

        def wrapper(*a, **k):
            calls[name] += 1
            return fn(*a, **k)

        return wrapper

    for name in ROW_BUILDERS + ("tree_count", "path_count_chain"):
        monkeypatch.setattr(J, name, counting(name))
    yield calls


def _counted(plan):
    """The operator the plan's count asks: the top of the stacked expands."""
    op = plan
    while not hasattr(op, "tree_count"):
        op = op.children[0]
    return op


def _base_frontier(plan):
    """The node the stacked expands under the plan's count start from."""
    return expand_op._tree_ops(_counted(plan))[-1].frontier_fld


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_a_tree_of_expands_is_counted_without_a_row(world, monkeypatch, shape):
    nodes, rels, graph = world
    query, start, clauses, starts_from, program = SHAPES[shape]
    want = len(enumerate_rows(nodes, rels, start, clauses))
    before = _counters()
    with spied(monkeypatch) as calls:
        result = graph.cypher(query)
        got = result.records.collect()
    assert [dict(r) for r in got] == [{"n": want}]
    after = _counters()
    if program is None:  # the scan of a label no node has: nothing to ask
        return
    assert after[SERIES % "count"] - before[SERIES % "count"] == 1
    assert after[SERIES % "rows"] == before[SERIES % "rows"]
    assert calls[program] == 1, dict(calls)
    assert not any(calls[name] for name in ROW_BUILDERS), dict(calls)
    hops = sum(len(h) for _, h in clauses)
    forms = {f: after[HOPS % f] - before[HOPS % f] for f in ("degree", "reduce", "scan")}
    assert sum(forms.values()) == hops, forms
    assert (after[LANES] > before[LANES]) == bool(forms["reduce"] + forms["scan"])
    assert (after[NODE_LANES] > before[NODE_LANES]) == bool(forms["scan"])
    if starts_from is not None:
        assert _base_frontier(result.relational_plan) == starts_from


def test_a_proven_far_label_costs_no_mask_and_a_leaf_no_edge(world):
    """Every HAS_CREATOR target is a Person and every REPLY_OF source a
    Comment (facts of the index build): those leaves are degrees. A forum
    likes messages too: the liker's mask stays, and its hop reads edges."""
    nodes, rels, graph = world
    before = _counters()
    graph.cypher(SHAPES["star_two_optional"][0]).records.collect()
    after = _counters()
    forms = {f: after[HOPS % f] - before[HOPS % f] for f in ("degree", "reduce", "scan")}
    # creator and comment: degree; liker (masked) and the hop to the tag
    # from the root (the messages' weights are not constant): edge passes
    assert forms == {"degree": 2, "reduce": 0, "scan": 2}


UNIQUE = ("MATCH (m:Message)<-[:LIKES]-(a:Person), (m)<-[:LIKES]-(b:Person)%s "
          "RETURN count(*) AS n")


@pytest.mark.parametrize("tagged", [False, True], ids=["a_path", "a_star"])
def test_two_hops_of_one_type_that_can_meet_keep_their_uniqueness(world, tagged):
    """``a`` and ``b`` may not like ``m`` by one relationship. Two such hops
    alone are a path (a - m - b): the chain's own walk carries the edge.
    With a third branch they are a tree whose vectors cannot tell two edges
    apart: it declines, counted, and the rows are built."""
    nodes, rels, graph = world
    hops = [("m", "a", "LIKES", False, ("Person",)),
            ("m", "b", "LIKES", False, ("Person",))]
    if tagged:
        hops.append(("m", "t", "HAS_TAG", True, ("Tag",)))
    want = len(enumerate_rows(nodes, rels, ("m", ("Message",)), [(False, hops)]))
    before = _counters()
    query = UNIQUE % (", (m)-[:HAS_TAG]->(t:Tag)" if tagged else "")
    got = graph.cypher(query).records.collect()
    after = _counters()
    assert [dict(r) for r in got] == [{"n": want}]
    moved = {o: after[SERIES % o] - before[SERIES % o] for o in ("count", "rows")}
    assert moved == ({"count": 0, "rows": 1} if tagged else {"count": 1, "rows": 0})


def test_a_hop_out_of_an_optional_node_declines_and_answers_right(world):
    nodes, rels, graph = world
    query = ("MATCH (m:Post) OPTIONAL MATCH (m)<-[:REPLY_OF]-(c:Comment) "
             "OPTIONAL MATCH (c)-[:HAS_CREATOR]->(p:Person) RETURN count(*) AS n")
    want = len(enumerate_rows(
        nodes, rels, ("m", ("Post",)),
        [(True, [("m", "c", "REPLY_OF", False, ("Comment",))]),
         (True, [("c", "p", "HAS_CREATOR", True, ("Person",))])],
    ))
    before = _counters()
    got = graph.cypher(query).records.collect()
    after = _counters()
    assert [dict(r) for r in got] == [{"n": want}]
    assert after[SERIES % "rows"] - before[SERIES % "rows"] == 1


# the first OPTIONAL MATCH rides the join (its predicate keeps it off the
# fused expand), so the stack under the count starts at the second one, over
# rows whose ``c`` is null wherever the first found nothing
NULL_ROOT = ("MATCH (m:Post) OPTIONAL MATCH (m)<-[:REPLY_OF]-(c:Comment) "
             "WHERE c.id > 0 OPTIONAL MATCH (c)-[:HAS_CREATOR]->(p:Person) ")
NULL_ROOT_CLAUSES = [(True, [("m", "c", "REPLY_OF", False, ("Comment",))]),
                     (True, [("c", "p", "HAS_CREATOR", True, ("Person",))])]
NULL_ROOTS = {
    # every branch at the root OPTIONAL: a row with a null root stays once
    "every_branch_optional": (
        NULL_ROOT + "RETURN count(*) AS n", NULL_ROOT_CLAUSES),
    "two_optional_branches": (
        NULL_ROOT + "OPTIONAL MATCH (c)<-[:LIKES]-(l) RETURN count(*) AS n",
        NULL_ROOT_CLAUSES + [(True, [("c", "l", "LIKES", False, ())])]),
    # a required branch at the root drops it
    "a_required_branch": (
        NULL_ROOT + "MATCH (c)<-[:LIKES]-(l) RETURN count(*) AS n",
        NULL_ROOT_CLAUSES + [(False, [("c", "l", "LIKES", False, ())])]),
}


@pytest.mark.parametrize("case", sorted(NULL_ROOTS))
def test_an_input_row_with_a_null_root_is_counted_as_the_rows_road_builds_it(
        either_session, case):
    """The deepest operator of the stack is an OPTIONAL expand over rows an
    earlier OPTIONAL MATCH left null: no node weighs them, and each is one
    row of nulls unless a required branch hangs on the same node."""
    nodes, rels, graph = either_session
    query, clauses = NULL_ROOTS[case]
    rows = enumerate_rows(nodes, rels, ("m", ("Post",)), clauses)
    assert any(r["c"] is None for r in enumerate_rows(
        nodes, rels, ("m", ("Post",)), NULL_ROOT_CLAUSES))
    before = _counters()
    result = graph.cypher(query)
    got = result.records.collect()
    after = _counters()
    assert [dict(r) for r in got] == [{"n": len(rows)}]
    if graph.session.table_cls.__name__ == "TpuTable":
        assert after[SERIES % "count"] - before[SERIES % "count"] == 1
        assert after[SERIES % "rows"] == before[SERIES % "rows"]
        ops = expand_op._tree_ops(_counted(result.relational_plan))
        assert isinstance(ops[-1], expand_op.CsrOptionalExpandOp)
        assert not isinstance(ops[-1].children[0], type(ops[-1]))


@pytest.mark.parametrize("session", ["local", "tpu"])
def test_an_unwound_null_under_an_optional_expand_is_one_row(session):
    nodes, rels = make_tables(11)
    graph = make_graph(getattr(CypherSession, session)(), nodes, rels)
    comment = nodes[("Message", "Comment")]
    asked = [int(comment[0]), None, int(comment[3]), -1, int(comment[0])]
    creators = Counter(rels["HAS_CREATOR"][0].tolist())
    want = sum(max(creators[i], 1) if i in creators else 1 for i in asked)
    got = graph.cypher(
        "UNWIND $ids AS i OPTIONAL MATCH (c:Comment {id: i}) "
        "OPTIONAL MATCH (c)-[:HAS_CREATOR]->(p:Person) RETURN count(*) AS n",
        {"ids": asked},
    ).records.collect()
    assert [dict(r) for r in got] == [{"n": want}]


def test_scan_node_lanes_are_the_windows_of_the_scan_hops(world):
    """The counter moves by the row pointers each ``scan`` hop gathers at —
    its CSR's window and one — and by none for the ``degree`` leaves; the
    span carries the same number as ``node_lanes``."""
    from tpu_cypher.backend.tpu.graph_index import GraphIndex

    nodes, rels, graph = world
    before = _counters()
    result = graph.cypher(SHAPES["star_two_optional"][0])
    result.records.collect()
    after = _counters()
    ops = expand_op._tree_ops(_counted(result.relational_plan))
    tree, order = expand_op._read_tree(ops)
    gi, ctx = GraphIndex.of(graph._graph), ops[-1].context
    hops = [expand_op._hop_arrays(gi, op, ctx) for op in order]
    forms = J.tree_forms(tree, [h[5] is not None for h in hops], False)
    assert sorted(forms) == ["degree", "degree", "scan", "scan"]
    want = sum(
        gi.csr_row_span(op.types_key, op.backwards, ctx).row_ptr.shape[0]
        for op, form in zip(order, forms) if form == "scan"
    )
    # the tags' and the messages' runs, not two whole id spaces
    assert 0 < want < 2 * (gi.num_nodes + 1)
    assert after[NODE_LANES] - before[NODE_LANES] == want
    assert f"node_lanes={want}" in str(result.profile())


def test_hops_that_scan_nothing_gather_at_no_row_pointer(world):
    """Two leaves that are degrees (the plan starts from the middle): edge
    lanes or none, but no prefix sum and so no row pointer gathered at.
    (``test_count_chain_forms`` holds the same of every ``reduce`` hop.)"""
    nodes, rels, graph = world
    before = _counters()
    got = graph.cypher(
        "MATCH (a)-[:LIKES]->(b)-[:HAS_CREATOR]->(c) RETURN count(*) AS n"
    ).records.collect()
    after = _counters()
    assert got[0]["n"] > 0
    forms = {f: after[HOPS % f] - before[HOPS % f] for f in ("degree", "reduce", "scan")}
    assert forms["scan"] == 0 and forms["degree"] + forms["reduce"] == 2
    assert after[NODE_LANES] == before[NODE_LANES]


def test_scan_node_lanes_are_exported_as_zero_before_any_query():
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, "-c",
         "import tpu_cypher.backend.tpu.expand_op\n"
         "from tpu_cypher.obs.metrics import REGISTRY\n"
         "print(REGISTRY.prometheus_text())"],
        capture_output=True, text=True, timeout=300, check=True,
    ).stdout
    assert f"\n{NODE_LANES} 0\n" in out


def test_both_series_of_the_tree_count_are_exported_from_the_start():
    text = REGISTRY.prometheus_text()
    for outcome in ("count", "rows"):
        assert f'tpu_cypher_count_pushdown_total{{op="tree",outcome="{outcome}"}}' in text


def test_the_tree_count_leaves_its_span_and_notes(world):
    nodes, rels, graph = world
    result = graph.cypher(SHAPES["star_two_optional"][0])
    result.records.collect()
    text = str(result.profile())
    assert "AggregateOp" in text and "count_from=tree" in text
    for noted in ("tree_count", "hops=4", "optional_branches=2", "branches=3",
                  "edge_lanes=", "node_lanes=", "sites=expand:1", "count_only=True"):
        assert noted in text, noted


# -- graph_from_tables -------------------------------------------------------


def test_graph_from_tables_takes_a_label_set_a_table(world):
    nodes, rels, graph = world
    counts = {
        "Message": len(nodes[("Message", "Post")]) + len(nodes[("Message", "Comment")]),
        "Post": len(nodes[("Message", "Post")]),
        "Comment": len(nodes[("Message", "Comment")]),
        "Message:Comment": len(nodes[("Message", "Comment")]),
        "Person": len(nodes["Person"]),
    }
    for labels, want in counts.items():
        got = graph.cypher(f"MATCH (n:{labels}) RETURN count(n) AS n").records.collect()
        assert dict(got[0]) == {"n": want}, labels
    got = graph.cypher(
        "MATCH (n:Post) RETURN labels(n) AS l, count(*) AS n").records.collect()
    assert [(sorted(r["l"]), r["n"]) for r in got] == [
        (["Message", "Post"], counts["Post"])]
    combos = graph.schema.label_combinations
    assert frozenset({"Message", "Post"}) in combos
    assert frozenset({"Message", "Comment"}) in combos
    assert frozenset({"Person"}) in combos


def test_graph_from_tables_one_label_a_table_as_before_and_bad_labels():
    from tpu_cypher.io.datasource import DataSourceError

    session = CypherSession.tpu()
    ids = np.arange(5, dtype=np.int64)
    graph = PropertyGraph(session, graph_from_tables(
        session, {"Person": (ids, {"id": (ids, T.CTInteger.nullable)})},
        {"KNOWS": (ids[:4], ids[1:], {})},
    ))
    got = graph.cypher(
        "MATCH (a:Person)-[:KNOWS]->(b:Person) RETURN count(*) AS n").records.collect()
    assert dict(got[0]) == {"n": 4}
    for bad in ((), ("Person", 3)):
        with pytest.raises(DataSourceError):
            graph_from_tables(session, {bad: (ids, {})}, {})


# -- OPTIONAL MATCH, row for row ---------------------------------------------

LIKE_CLAUSE = (True, [LIKES])
REPLY_ANY = (True, [("m", "c", "REPLY_OF", False, ("Message",))])

# name: (query, start, clauses, how often every left row stands, returned)
OPTIONALS = {
    # the second OPTIONAL over a row whose first found nothing
    "stacked_over_a_null": (
        "MATCH (m:Message) OPTIONAL MATCH (m)<-[:LIKES]-(l:Person) "
        "OPTIONAL MATCH (m)<-[:REPLY_OF]-(c:Message) "
        "RETURN m.id AS m, l.id AS l, c.id AS c",
        ("m", ("Message",)), [LIKE_CLAUSE, REPLY_ANY], 1, ("m", "l", "c")),
    # a predicate in the second keeps it off the fused expand on the device
    "stacked_through_the_join": (
        "MATCH (m:Message) OPTIONAL MATCH (m)<-[:LIKES]-(l:Person) "
        "OPTIONAL MATCH (m)<-[:REPLY_OF]-(c:Message) WHERE c.id > 0 "
        "RETURN m.id AS m, l.id AS l, c.id AS c",
        ("m", ("Message",)), [LIKE_CLAUSE, REPLY_ANY], 1, ("m", "l", "c")),
    "equal_left_rows": (
        "UNWIND [1, 1, 1] AS x MATCH (m:Message) "
        "OPTIONAL MATCH (m)<-[:LIKES]-(l:Person) RETURN m.id AS m, l.id AS l",
        ("m", ("Message",)), [LIKE_CLAUSE], 3, ("m", "l")),
    "equal_left_rows_through_the_join": (
        "UNWIND [1, 1] AS x MATCH (m:Message) "
        "OPTIONAL MATCH (m)<-[:LIKES]-(l:Person) WHERE l.id > 0 "
        "RETURN m.id AS m, l.id AS l",
        ("m", ("Message",)), [LIKE_CLAUSE], 2, ("m", "l")),
    # WITH keeps one m a tag: duplicates of m alone
    "after_a_with_that_keeps_duplicates": (
        "MATCH (m:Message)-[:HAS_TAG]->(t:Tag) WITH m "
        "OPTIONAL MATCH (m)<-[:LIKES]-(l:Person) RETURN m.id AS m, l.id AS l",
        ("m", ("Message",)),
        [(False, [("m", "t", "HAS_TAG", True, ("Tag",))]), LIKE_CLAUSE], 1,
        ("m", "l")),
    "two_hops_in_the_optional": (
        "MATCH (m:Post) OPTIONAL MATCH (m)<-[:LIKES]-(l:Person) "
        "OPTIONAL MATCH (m)<-[:REPLY_OF]-(c:Comment)-[:HAS_CREATOR]->(p:Person) "
        "RETURN m.id AS m, l.id AS l, c.id AS c, p.id AS p",
        ("m", ("Post",)),
        [LIKE_CLAUSE, (True, [("m", "c", "REPLY_OF", False, ("Comment",)),
                              ("c", "p", "HAS_CREATOR", True, ("Person",))])],
        1, ("m", "l", "c", "p")),
}


@pytest.fixture(scope="module", params=["local", "tpu"])
def either_session(request):
    nodes, rels = make_tables(11)
    session = getattr(CypherSession, request.param)()
    return nodes, rels, make_graph(session, nodes, rels)


@pytest.mark.parametrize("case", sorted(OPTIONALS))
def test_optional_match_keeps_every_left_row_once_a_match_or_once_with_nulls(
        either_session, case):
    nodes, rels, graph = either_session
    query, start, clauses, copies, returned = OPTIONALS[case]
    rows = enumerate_rows(nodes, rels, start, clauses)
    want = Counter(tuple(r[v] for v in returned) for r in rows for _ in range(copies))
    got = Counter(
        tuple(r[v] for v in returned) for r in graph.cypher(query).records.collect()
    )
    assert got == want


def test_the_join_on_every_shared_field_loses_the_second_optional(monkeypatch):
    """What the planner did until PR 34, rebuilt here (the reference's keys:
    every field the two sides share): the reply of a message nobody likes
    is lost, because the null liker is a join key that matches nothing."""
    nodes, rels = make_tables(11)
    query, start, clauses, _, returned = OPTIONALS["stacked_through_the_join"]
    want = Counter(
        tuple(r[v] for v in returned)
        for r in enumerate_rows(nodes, rels, start, clauses)
    )
    def on_every_shared_field(self, op):
        lhs, rhs = self.process(op.lhs), self.process(op.rhs)
        return relational_planner.JoinOp(
            lhs, rhs, self._common_join_pairs(lhs, rhs), "left_outer")

    monkeypatch.setattr(
        relational_planner.RelationalPlanner, "_plan_Optional", on_every_shared_field
    )
    graph = make_graph(CypherSession.local(), nodes, rels)
    got = Counter(
        tuple(r[v] for v in returned) for r in graph.cypher(query).records.collect()
    )
    assert got != want
    lost = [k for k in want if k[1] is None and k[2] is not None]
    assert lost and not any(k in got for k in lost)

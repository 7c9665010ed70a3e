"""The benchmark cell ``lsqb-sf3.lsqb-tree`` on the CPU at a small share of
its size: the generator is deterministic per seed, keeps ``gen_snb``'s
persons and friendships to the row, draws the Person side as ``gen_lsqb``
does with the static tables at the specification's counts, and gives the
shapes the configuration's file promises (a reply forest, half the comments on posts, likes and replies on
the same few messages, a creator who is a member of the forum); the three
queries over its data answer as the enumeration does, each without a row of
its pattern; a rehearsal comes out correct with three shapes a pass, the
stale control not correct; and the planner as it was until PR 34 answers Q7
wrongly on this very data. (The readers and the roofline the cell brings are
held to hand-made windows in ``chipbench/tests/test_lsqb_tree_cell.py``.)"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIPBENCH = os.path.join(ROOT, "chipbench")
CELL = "lsqb-sf3.lsqb-tree"
SHARE = "0.03"
SEED = 3_400_000_123


@pytest.fixture(scope="module")
def bench():
    """The benchmark's own modules, by the names they import each other."""
    sys.path.insert(0, CHIPBENCH)
    try:
        import gen_lsqb
        import gen_lsqb_full
        import gen_snb
        import load_lsqb_full
        import lsqb_tree_reference
        import reference
        shapes = {}
        for q in ("q1", "q4", "q7"):
            sys.path.insert(0, os.path.join(CHIPBENCH, "shapes"))
            shapes[q] = __import__(f"lsqb_{q}")
            sys.path.pop(0)
        yield dict(gen_lsqb=gen_lsqb, gen=gen_lsqb_full, gen_snb=gen_snb,
                   load=load_lsqb_full,
                   tree=lsqb_tree_reference, reference=reference, shapes=shapes)
    finally:
        sys.path.remove(CHIPBENCH)


@pytest.fixture(scope="module")
def arrays(bench):
    return bench["gen"].snb_arrays(700, 16_000, SEED)


def test_generator_is_deterministic_and_draws_the_person_side_as_gen_lsqb(bench, arrays):
    gen = bench["gen"]
    again = gen.snb_arrays(700, 16_000, SEED)
    other = gen.snb_arrays(700, 16_000, SEED + 1)
    assert sorted(arrays) == sorted(again)
    assert all(np.array_equal(arrays[k], again[k]) for k in arrays)
    assert not np.array_equal(arrays["comment_parent"], other["comment_parent"])
    # gen_lsqb's rules and random stream: at gen_lsqb's own numbers of
    # cities, countries and tags its tables come out to the row
    theirs = bench["gen_lsqb"].snb_arrays(700, 16_000, SEED)
    for key in ("ids", "src", "dst"):
        assert np.array_equal(arrays[key], theirs[key]), key
    same = gen._person_side(
        bench["gen_snb"].snb_arrays(700, 16_000, SEED), SEED,
        {name: len(theirs[key]) for name, key in (
            ("cities", "city_ids"), ("countries", "country_ids"), ("tags", "tag_ids"))})
    for key, column in theirs.items():
        assert np.array_equal(same[key], column), key
    # the static tables at the specification's counts, the same at every
    # scale factor (gen_lsqb has them at a third of that for these persons)
    full = gen.table_counts(gen.SF3_PERSONS)
    assert [full[k] for k in ("cities", "countries", "tags", "tag_classes")] == [
        1_343, 111, 16_080, 71]
    want = gen.table_counts(len(arrays["ids"]))
    for key, aim in (("city_ids", "cities"), ("country_ids", "countries"),
                     ("tag_ids", "tags"), ("tagclass_ids", "tag_classes"),
                     ("forum_ids", "forums"), ("post_ids", "posts"),
                     ("comment_ids", "comments")):
        assert len(arrays[key]) == want[aim], key
    assert len(arrays["tag_ids"]) > len(theirs["tag_ids"])
    assert np.isin(arrays["interest_tag"], arrays["tag_ids"]).all()
    assert np.isin(arrays["person_city"], arrays["city_ids"]).all()
    for key, aim in (("member_forum", "members"), ("like_person", "likes"),
                     ("msgtag_message", "message_tags")):
        assert 0.9 * want[aim] <= len(arrays[key]) <= want[aim], key
    # one id space of nine labels, every id under the relationship ids
    nodes = ("ids", "city_ids", "country_ids", "tag_ids", "tagclass_ids",
             "forum_ids", "post_ids", "comment_ids")
    every = np.concatenate([arrays[k] for k in nodes])
    assert len(np.unique(every)) == len(every) and every.max() < 1 << 53


def test_generator_gives_the_shapes_the_configuration_promises(arrays):
    a = arrays
    posts, comments = a["post_ids"], a["comment_ids"]
    messages = np.concatenate([posts, comments])
    order = np.argsort(messages)

    def per_message(ids):
        at = order[np.searchsorted(messages[order], ids)]
        assert np.array_equal(messages[at], ids)
        return np.bincount(at, minlength=len(messages))

    # a reply forest: every comment has one parent, a post or an EARLIER
    # comment; about half reply to a post
    parent = a["comment_parent"]
    assert len(parent) == len(comments) and np.isin(parent, messages).all()
    on_post = np.isin(parent, posts)
    assert 0.45 < on_post.mean() < 0.55
    earlier = np.searchsorted(comments, parent[~on_post])
    assert (earlier < np.flatnonzero(~on_post)).all()
    # heavy tails that go together: few messages collect most replies and
    # most likes, and they are the same messages
    replies, likes = per_message(parent), per_message(a["like_message"])
    for count in (replies, likes):
        assert (count == 0).mean() > 0.4
        assert np.sort(count)[-(len(count) // 10):].sum() > 0.35 * count.sum()
        assert count.max() > 50 * count.mean()
    assert np.corrcoef(replies, likes)[0, 1] > 0.5
    pairs = np.stack([a["like_person"], a["like_message"]])
    assert len(np.unique(pairs, axis=1)[0]) == pairs.shape[1]
    tagging = np.stack([a["msgtag_message"], a["msgtag_tag"]])
    assert len(np.unique(tagging, axis=1)[0]) == tagging.shape[1]
    assert np.isin(a["msgtag_tag"], a["tag_ids"]).all()
    # a post's creator is a member of its forum, or the forum has none
    members = set(zip(a["member_forum"].tolist(), a["member_person"].tolist()))
    with_members = set(a["member_forum"].tolist())
    held = [(f, p) in members for f, p in
            zip(a["post_forum"].tolist(), a["post_creator"].tolist())
            if f in with_members]
    assert held and all(held)
    # a comment's tags are mostly its parent's: far more often than chance
    tags_of = {}
    for m, t in zip(a["msgtag_message"].tolist(), a["msgtag_tag"].tolist()):
        tags_of.setdefault(m, set()).add(t)
    shared = [bool(tags_of[c] & tags_of.get(p, set()))
              for c, p in zip(comments.tolist(), parent.tolist()) if c in tags_of]
    assert np.mean(shared) > 0.4


SERIES = "tpu_cypher_count_pushdown_total{op=tree,outcome=%s}"
BUILDERS = ("expand_materialize", "expand_materialize_counted",
            "optional_expand_materialize", "join_materialize",
            "join_materialize_counted")


def test_the_three_queries_answer_as_the_enumeration_without_a_row(
        bench, arrays, monkeypatch):
    from tpu_cypher import CypherSession
    from tpu_cypher.backend.tpu import jit_ops as J
    from tpu_cypher.obs.metrics import REGISTRY
    from tpu_cypher.relational.session import PropertyGraph

    built = []
    for name in BUILDERS:
        fn = getattr(J, name)
        monkeypatch.setattr(
            J, name, lambda *a, _fn=fn, _n=name, **k: (built.append(_n), _fn(*a, **k))[1])
    ref = bench["reference"].Reference(arrays)
    want = bench["tree"].counts(ref)
    assert all(n > 0 for n in want.values()) and want["q7"] > want["q4"]
    session = CypherSession.tpu()
    graph = PropertyGraph(session, bench["load"].load(session, arrays))
    before = REGISTRY.flat()
    for q, shape in bench["shapes"].items():
        got = graph.cypher(shape.QUERY).records.collect()
        assert [dict(r) for r in got] == shape.reference(ref, {}) == [{"count": want[q]}]
    after = REGISTRY.flat()
    assert after[SERIES % "count"] - before.get(SERIES % "count", 0) == 3
    assert after[SERIES % "rows"] == before.get(SERIES % "rows", 0)
    assert built == []
    # the new metric's file, read as the harness reads it over this pass:
    # the row pointers the eight scan hops gathered at, far fewer than the
    # eight whole id spaces the program read until PR 35
    import types

    sys.path.insert(0, os.path.join(CHIPBENCH, "readers"))
    try:
        import counter_per_pass
    finally:
        sys.path.pop(0)
    with open(os.path.join(CHIPBENCH, "metrics", "scan_node_lanes.lsqb_tree.json")) as f:
        spec = json.load(f)
    window = types.SimpleNamespace(
        counters={k: v - before.get(k, 0) for k, v in after.items()}, passes=1)
    lanes = counter_per_pass.read(window, **spec["args"])
    from tpu_cypher.backend.tpu.graph_index import GraphIndex

    space = GraphIndex.of(graph._graph).num_nodes + 1
    assert 8 * 32 < lanes < 8 * space and lanes == int(lanes)
    assert counter_per_pass.read(
        types.SimpleNamespace(counters={}, passes=1), **spec["args"]) is None


def test_the_planner_as_it_was_answers_q7_wrongly_on_this_data(
        bench, arrays, monkeypatch):
    """An ``Optional`` joined on every field its sides share (the parent's
    keys, rebuilt here) loses the replies of every message nobody likes: the null liker is a
    key that matches nothing. 3,914 tests passed over it, because the
    oracle planned through the same function."""
    from tpu_cypher import CypherSession
    from tpu_cypher.relational import planner
    from tpu_cypher.relational.session import PropertyGraph

    small = bench["gen"].snb_arrays(64, 700, SEED)
    ref = bench["reference"].Reference(small)
    want = bench["tree"].counts(ref)["q7"]

    def on_every_shared_field(self, op):  # the parent's keys
        lhs, rhs = self.process(op.lhs), self.process(op.rhs)
        return planner.JoinOp(
            lhs, rhs, self._common_join_pairs(lhs, rhs), "left_outer")

    monkeypatch.setattr(
        planner.RelationalPlanner, "_plan_Optional", on_every_shared_field)
    session = CypherSession.local()
    graph = PropertyGraph(session, bench["load"].load(session, small))
    got = graph.cypher(bench["shapes"]["q7"].QUERY).records.collect()[0]["count"]
    messages = np.concatenate([small["post_ids"], small["comment_ids"]])
    order = np.argsort(messages)

    def per(ids):
        return np.bincount(order[np.searchsorted(messages[order], ids)],
                           minlength=len(messages))

    tags, likes = per(small["msgtag_message"]), per(small["like_message"])
    replies = per(small["comment_parent"])
    lost = int((tags * np.where(likes > 0, likes * np.maximum(replies, 1), 1)).sum())
    assert got == lost < want


def _rehearse(*extra):
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    proc = subprocess.run(
        [sys.executable, os.path.join(CHIPBENCH, "run.py"), "--workload", CELL,
         "--seconds", "2", "--rehearse-cpu", SHARE, *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    with open(os.path.join(CHIPBENCH, "out", f"{CELL}.last.json")) as f:
        left = json.load(f)
    return json.loads(proc.stdout.strip().splitlines()[-1]), left, proc


def test_rehearsal_is_correct_with_three_shapes_a_pass():
    result, left, proc = _rehearse("--seed", "3400000011", "--trace", "1")
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is True and result["failed"] == 0
    assert all(v["value"] == 0 for v in result["compared"].values())
    assert left["passes"] >= 1
    assert result["attempted"] == 3 * left["passes"]
    assert result["metrics"] == {}  # a CPU's numbers are withheld
    assert "metrics read and withheld" in proc.stdout
    # the traced rehearsal loads every per-layer metric of the cell by its
    # file: PR 35's among them, and the counter it names is exported
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = [m for m in json.load(f)["per_layer"]
                  if m["name"] == "scan_node_lanes.lsqb_tree"]
    assert [m["workloads"] for m in listed] == [[CELL]]
    with open(os.path.join(CHIPBENCH, "metrics", "scan_node_lanes.lsqb_tree.json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "counter_per_pass"
    assert spec["args"]["counters"] == ["tpu_cypher_count_scan_node_lanes_total"]


def test_the_stale_control_comes_out_not_correct():
    # no count of the pass passes 2**31 (at any size: PERF.md, section 4),
    # so the 32-bit control has nothing to wrap here
    result, _, _ = _rehearse("--seed", "3400000013", "--trace", "0",
                             "--control", "stale_snapshot")
    assert all(v == 0 for v in result["program_compared"].values())
    stale = result["controls"]["stale_snapshot"]
    assert stale["wrong_answers"] == result["attempted"]
    assert result["correct"] is False

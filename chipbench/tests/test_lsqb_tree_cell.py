"""What the cell ``lsqb-sf3.lsqb-tree`` brings to the benchmark, held to
hand-made windows: the roofline of the star's count, the seven metric files
and the readers they name; the reference's enumeration against per-message
products (Q4, Q7) and a chain of vector products (Q1) on a small graph; the
stale control on all three counts, and the 32-bit control on a count it
would wrap (none of this cell's passes 2**31: ``PERF.md``, section 4)."""

import json
import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIPBENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(CHIPBENCH)
sys.path[:0] = [CHIPBENCH, ROOT]

import client  # noqa: E402
import gen_lsqb  # noqa: E402
import gen_lsqb_full  # noqa: E402
import lsqb_tree_reference  # noqa: E402
import reference  # noqa: E402
import trace_reduce as tr  # noqa: E402

CELL = "lsqb-sf3.lsqb-tree"
PUSH = 'tpu_cypher_count_pushdown_total{op="tree",outcome="%s"}'
LANES = "tpu_cypher_tree_count_lanes_total"
MINE = ["q1_device_s.lsqb_tree", "q4_device_s.lsqb_tree", "q7_device_s.lsqb_tree",
        "tree_count_lanes.lsqb_tree", "tree_count_roofline.lsqb_tree",
        "tree_count_rows.lsqb_tree", "tree_counts.lsqb_tree"]


def window(counters=None, passes=4, requests=()):
    trace = tr.Trace(slice=(0.0, 10.0), busy=[[(1.0, 2.0), (4.0, 7.0)]],
                     modules={}, requests=list(requests))
    return types.SimpleNamespace(
        trace=trace, counters=counters or {}, passes=passes,
        config={"chips": 1, "index_itemsize": 4},
        sizes={"persons": 24_328, "edges": 1_130_494},
        peaks=lambda: {"bytes": 819e9},
        roofline=lambda name: client.load_module("rooflines", name),
    )


def read(metric, w):
    with open(os.path.join(CHIPBENCH, "metrics", f"{metric}.json")) as f:
        spec = json.load(f)
    assert spec["name"] == metric
    return client.load_module("readers", spec["reader"]).read(w, **spec["args"])


def test_the_cells_metrics_configuration_and_traffic_are_in_the_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"] if m["name"] in MINE]
    assert sorted(m["name"] for m in mine) == MINE
    assert all(m["workloads"] == [CELL] and m["moves"] == "analytic_pass_s"
               for m in mine)
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("lsqb-sf3", "lsqb-tree", 1)
    config = next(c for c in bench["configs"] if c["name"] == "lsqb-sf3")
    with open(os.path.join(ROOT, config["file"])) as f:
        stated = json.load(f)
    assert (stated["persons"], stated["knows"]) == (24_328, 565_247)
    assert stated["reduced"] == config["reduced"] == ["entities"]
    for key in ("source", "deployment", "assumed", "guarantees", "queries"):
        assert stated[key], key
    with open(os.path.join(CHIPBENCH, "traffic", "lsqb-tree.json")) as f:
        mix = json.load(f)
    assert [s["shape"] for s in mix["shapes"]] == ["lsqb_q1", "lsqb_q4", "lsqb_q7"]
    assert mix["order"] == "pass" and mix["server"] == {"cache_bytes": 0}
    # every generic metric the other LSQB cell reports, this one does too
    other = "lsqb-sf10-person.lsqb-chain"
    for m in bench["per_layer"]:
        if other in m.get("workloads", []) and not m["name"].endswith(".lsqb"):
            assert CELL in m["workloads"], m["name"]


def test_roofline_counts_four_adjacencies_once_and_a_number_a_message_and_branch():
    roofline = client.load_module("rooflines", "tree_count")
    c = gen_lsqb_full.table_counts(24_328)
    nodes = 24_328 + c["messages"] + c["forums"] + 71 + 1_343 + 111 + 16_080
    edges = c["message_tags"] + c["messages"] + c["likes"] + c["comments"]
    want = 4 * (nodes + 1) * 4 + edges * 4 + 4 * c["messages"] * 8
    assert roofline.least_bytes(24_328, 4) == want
    assert 5.0e8 < want < 6.0e8
    least = roofline.least_seconds({"persons": 24_328, "edges": 1_130_494},
                                   4, {"bytes": 819e9})
    assert least == pytest.approx(want / 819e9)
    requests = [("lsqb_q1", 0.5, 2.5), ("lsqb_q4", 3.0, 8.0), ("lsqb_q7", 8.0, 9.5)]
    w = window(requests=requests)
    assert read("q1_device_s.lsqb_tree", w) == pytest.approx(1.0)
    assert read("q4_device_s.lsqb_tree", w) == pytest.approx(3.0)
    assert read("q7_device_s.lsqb_tree", w) is None or read("q7_device_s.lsqb_tree", w) == 0
    share = read("tree_count_roofline.lsqb_tree", w)
    assert share == pytest.approx(100.0 * least / 3.0) and 0 < share < 1
    # no request of the shape in the slice, or no trace: nothing, never 0
    assert read("tree_count_roofline.lsqb_tree", window()) is None
    assert read("q4_device_s.lsqb_tree", types.SimpleNamespace(trace=None)) is None


def test_counters_read_per_pass_and_nothing_from_a_program_without_them():
    counters = {PUSH % "count": 12.0, PUSH % "rows": 0.0, LANES: 4.0 * 71_344_640,
                'tpu_cypher_count_pushdown_total{op="filter",outcome="count"}': 7.0}
    w = window(counters)
    assert read("tree_counts.lsqb_tree", w) == 3.0
    assert read("tree_count_rows.lsqb_tree", w) == 0.0
    assert read("tree_count_lanes.lsqb_tree", w) == 71_344_640.0
    parent = window({'tpu_cypher_count_pushdown_total{op="filter",outcome="count"}': 7.0})
    for metric in ("tree_counts.lsqb_tree", "tree_count_rows.lsqb_tree", "tree_count_lanes.lsqb_tree"):
        assert read(metric, parent) is None


@pytest.fixture(scope="module")
def small():
    arrays = gen_lsqb_full.snb_arrays(400, 9_000, 3_400_000_021)
    return arrays, reference.Reference(arrays)


def test_enumeration_equals_the_products_it_never_takes(small):
    arrays, ref = small
    a = arrays
    got = lsqb_tree_reference.counts(ref)
    messages = np.concatenate([a["post_ids"], a["comment_ids"]])
    order = np.argsort(messages)

    def per_message(ids):
        return np.bincount(order[np.searchsorted(messages[order], ids)],
                           minlength=len(messages))

    tags, likes = per_message(a["msgtag_message"]), per_message(a["like_message"])
    replies = per_message(a["comment_parent"])
    assert got["q4"] == int((tags * likes * replies).sum())
    assert got["q7"] == int(
        (tags * np.maximum(likes, 1) * np.maximum(replies, 1)).sum())
    assert 0 < got["q4"] < got["q7"]
    # Q1 from the far end: tags a comment, comments a post, posts a forum,
    # members a forum (every tag has a class, every member a city and a
    # country: one each)
    comment_tags = per_message(a["msgtag_message"])[len(a["post_ids"]):]
    on_post = np.isin(a["comment_parent"], a["post_ids"])
    post_at = np.searchsorted(a["post_ids"], a["comment_parent"][on_post])
    post_weight = np.bincount(post_at, weights=comment_tags[on_post],
                              minlength=len(a["post_ids"]))
    forum_at = np.searchsorted(a["forum_ids"], a["post_forum"])
    forum_weight = np.bincount(forum_at, weights=post_weight,
                               minlength=len(a["forum_ids"]))
    members = np.bincount(np.searchsorted(a["forum_ids"], a["member_forum"]),
                          minlength=len(a["forum_ids"]))
    assert got["q1"] == int((forum_weight * members).sum()) > 0
    for shape, key in (("lsqb_q1", "q1"), ("lsqb_q4", "q4"), ("lsqb_q7", "q7")):
        module = client.load_module("shapes", shape)
        assert module.reference(ref, {}) == [{"count": got[key]}]
        assert "count(*)" in module.QUERY and "$" not in module.QUERY
    assert "OPTIONAL MATCH" in client.load_module("shapes", "lsqb_q7").QUERY


def test_blocks_of_rows_change_no_count(small, monkeypatch):
    arrays, ref = small
    whole = lsqb_tree_reference.counts(ref)
    monkeypatch.setattr(lsqb_tree_reference, "BLOCK", 257)
    again = lsqb_tree_reference.counts(reference.Reference(arrays))
    assert again == whole


def test_the_stale_control_changes_all_three_counts_and_int32_a_count_past_2_31(small):
    arrays, ref = small
    right = lsqb_tree_reference.counts(ref)
    stale = reference.Reference(arrays, **reference.CONTROLS["stale_snapshot"])
    lost = lsqb_tree_reference.counts(stale)
    assert all(lost[q] < right[q] for q in ("q1", "q4", "q7"))
    narrow = reference.Reference(arrays, **reference.CONTROLS["int32"])
    assert narrow.held([{"count": right["q4"]}]) == [{"count": right["q4"]}]
    big = [{"count": 1 << 33}]
    assert narrow.held(big) != big and ref.held(big) == big


def test_the_generator_keeps_gen_lsqbs_persons_and_its_rules_for_their_side(small):
    arrays, _ = small
    theirs = gen_lsqb.snb_arrays(400, 9_000, 3_400_000_021)
    for key in ("ids", "src", "dst"):
        assert np.array_equal(arrays[key], theirs[key]), key
    want = gen_lsqb_full.table_counts(len(arrays["ids"]))
    assert len(arrays["tag_ids"]) == want["tags"] > len(theirs["tag_ids"])
    assert len(arrays["city_ids"]) == want["cities"]
    assert len(arrays["country_ids"]) == want["countries"]

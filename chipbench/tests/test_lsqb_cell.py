"""What the cell ``lsqb-sf10-person.lsqb-chain`` brings to the benchmark,
held to hand-made windows: the roofline of the closed-wedge sum, the six
metric files and the readers they name; the reference's enumeration against
a dense product on a small graph; and both controls at a size where the
counts pass 2**31."""

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
import lsqb_reference  # noqa: E402
import reference  # noqa: E402
import trace_reduce as tr  # noqa: E402

CELL = "lsqb-sf10-person.lsqb-chain"
PUSH = 'tpu_cypher_count_pushdown_total{op="chain_constraint",outcome="%s"}'
LANES = "tpu_cypher_chain_constraint_wedge_lanes_total"


def window(counters=None, passes=4, requests=()):
    trace = tr.Trace(slice=(0.0, 10.0), busy=[[(1.0, 2.0), (4.0, 7.0)]],
                     modules={}, requests=list(requests))
    return types.SimpleNamespace(
        trace=trace, counters=counters or {}, passes=passes,
        config={"chips": 1, "index_itemsize": 8},
        sizes={"persons": 65_645, "edges": 3_877_032},
        peaks=lambda: {"bytes": 819e9},
        roofline=lambda name: client.load_module("rooflines", name),
    )


def read(metric, w):
    with open(os.path.join(CHIPBENCH, "metrics", f"{metric}.json")) as f:
        spec = json.load(f)
    assert spec["name"] == metric
    return client.load_module("readers", spec["reader"]).read(w, **spec["args"])


def test_the_cells_metrics_are_in_the_benchmark_under_its_name_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"] if m["name"].endswith(".lsqb")]
    assert sorted(m["name"] for m in mine) == [
        "chain_constraint_counts.lsqb", "chain_constraint_rows.lsqb",
        "q6_device_s.lsqb", "q9_device_s.lsqb", "wedge_close_roofline.lsqb",
        "wedge_lanes.lsqb",
    ]
    assert all(m["workloads"] == [CELL] and m["moves"] == "analytic_pass_s"
               for m in mine)


def test_roofline_counts_the_adjacency_once_and_two_weights_a_person():
    roofline = client.load_module("rooflines", "wedge_close")
    assert roofline.least_bytes(10, 100, 8) == 100 * 8 + 11 * 8 + 2 * 10 * 8
    least = roofline.least_seconds({"persons": 65_645, "edges": 3_877_032},
                                   8, {"bytes": 819e9})
    assert least == pytest.approx((3_877_032 + 65_646 + 2 * 65_645) * 8 / 819e9)
    requests = [("lsqb_q6", 0.5, 2.5), ("lsqb_q9", 3.0, 8.0)]
    w = window(requests=requests)
    assert read("q6_device_s.lsqb", w) == pytest.approx(1.0)
    assert read("q9_device_s.lsqb", w) == pytest.approx(3.0)
    share = read("wedge_close_roofline.lsqb", w)
    assert share == pytest.approx(100.0 * least / 3.0) and 0 < share < 1
    # no request of the shape in the slice, or no trace: nothing, never 0
    assert read("wedge_close_roofline.lsqb", window()) is None
    assert read("q9_device_s.lsqb", types.SimpleNamespace(trace=None)) is None


def test_counters_read_per_pass_and_nothing_from_a_program_without_them():
    counters = {PUSH % "count": 12.0, PUSH % "rows": 0.0, LANES: 4.0 * 4_382_392_320,
                'tpu_cypher_count_pushdown_total{op="filter",outcome="count"}': 7.0}
    w = window(counters)
    assert read("chain_constraint_counts.lsqb", w) == 3.0
    assert read("chain_constraint_rows.lsqb", w) == 0.0
    assert read("wedge_lanes.lsqb", w) == 4_382_392_320.0
    parent = window({'tpu_cypher_count_pushdown_total{op="filter",outcome="count"}': 7.0})
    for metric in ("chain_constraint_counts.lsqb", "chain_constraint_rows.lsqb",
                   "wedge_lanes.lsqb"):
        assert read(metric, parent) is None


@pytest.fixture(scope="module")
def small():
    arrays = gen_lsqb.snb_arrays(700, 20_000, 3_200_000_021)
    return arrays, reference.Reference(arrays)


def test_enumeration_equals_a_dense_product(small):
    arrays, ref = small
    n = ref.n
    a = np.zeros((n, n), np.int64)
    a[ref.s, ref.d] = 1
    order = np.argsort(ref.ids)
    holder = order[np.searchsorted(ref.ids[order], arrays["interest_person"])]
    worth = np.bincount(holder, minlength=n)
    wedges = a @ a
    np.fill_diagonal(wedges, 0)  # p1 <> p3
    got = lsqb_reference.counts(ref)
    assert got["q6"] == int((wedges @ worth).sum())
    assert got["q9"] == int(((wedges * (1 - a)) @ worth).sum())
    assert 0 < got["q9"] < got["q6"]
    for shape, key in (("lsqb_q6", "q6"), ("lsqb_q9", "q9")):
        module = client.load_module("shapes", shape)
        assert module.reference(ref, {}) == [{"c": got[key]}]
        assert "count(*)" in module.QUERY and "$" not in module.QUERY


def test_both_controls_change_both_counts(small):
    arrays, ref = small
    right = lsqb_reference.counts(ref)
    stale = reference.Reference(arrays, **reference.CONTROLS["stale_snapshot"])
    lost = lsqb_reference.counts(stale)
    assert lost["q6"] < right["q6"] and lost["q9"] < right["q9"]
    narrow = reference.Reference(arrays, **reference.CONTROLS["int32"])
    big = [{"c": 16_850_524_165}]  # Q6 at scale factor 10, seed 3100000001
    assert narrow.held(big) != big and ref.held(big) == big

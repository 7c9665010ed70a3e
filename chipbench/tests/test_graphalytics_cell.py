"""What the cell ``graphalytics-g500-22.bfs-wcc`` brings to the benchmark:
the generator's graph at a small scale (undirected, each edge once with
``src < dst``, no self-loop, no duplicate, no isolated vertex, Kronecker's
skew); the reference's depths and components against a queue and a
disjoint-set forest; both summaries under the 32-bit control; the two
rooflines' bytes by hand; the six metric files and the readers they name,
held to hand-made windows; and a rehearsal of the cell on the CPU at a small
share — correct, and not correct under the stale control, the program's own
answers of that window right."""

import collections
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIPBENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(CHIPBENCH)
sys.path[:0] = [CHIPBENCH, ROOT]

import client  # noqa: E402
import gen_graph500  # noqa: E402
import graphalytics_reference  # noqa: E402
import reference  # noqa: E402
import trace_reduce as tr  # noqa: E402

CELL = "graphalytics-g500-22.bfs-wcc"
MINE = ["bfs_device_s.graphalytics", "bfs_roofline.graphalytics",
        "procedure_edge_lanes.graphalytics", "procedure_iterations.graphalytics",
        "wcc_device_s.graphalytics", "wcc_roofline.graphalytics"]
ITER = 'tpu_cypher_procedure_iterations_total{procedure="%s"}'
LANES = 'tpu_cypher_procedure_edge_lanes_total{procedure="%s"}'


@pytest.fixture(scope="module")
def arrays():
    return gen_graph500.snb_arrays(3_000, 0, 3_900_000_011)


def test_the_generator_draws_graphalytics_cleaned_kronecker_graph(arrays):
    ids, s, d = arrays["ids"], arrays["src"], arrays["dst"]
    assert gen_graph500.scale_of(3_000) == 12 and gen_graph500.scale_of(2_396_657) == 22
    assert (s < d).all()  # undirected, each edge once, no self-loop
    keys = s * 4096 + d
    assert (np.diff(keys) > 0).all()  # sorted, no duplicate
    assert np.array_equal(ids, np.unique(np.concatenate([s, d])))  # no isolated vertex
    assert ids.max() < 4096 and 0.5 * 4096 < len(ids) < 4096
    assert 0.6 * 16 * 4096 < len(s) < 16 * 4096  # duplicates go: a quarter at this scale
    degree = np.bincount(np.concatenate([s, d]))[ids]
    assert degree.max() > 20 * degree.mean()  # Kronecker's skew
    again = gen_graph500.snb_arrays(3_000, 0, 3_900_000_011)
    assert all(np.array_equal(arrays[k], again[k]) for k in arrays)
    other = gen_graph500.snb_arrays(3_000, 0, 3_900_000_012)
    assert not np.array_equal(arrays["src"], other["src"])


def _brute(ids, s, d, source):
    near = collections.defaultdict(list)
    for a, b in zip(s.tolist(), d.tolist()):
        near[a].append(b)
        near[b].append(a)
    depth, queue = {source: 0}, collections.deque([source])
    while queue:
        v = queue.popleft()
        for w in near[v]:
            if w not in depth:
                depth[w] = depth[v] + 1
                queue.append(w)
    parent = {int(v): int(v) for v in ids}

    def root(v):
        while parent[v] != v:
            v = parent[v]
        return v

    for a, b in zip(s.tolist(), d.tolist()):
        ra, rb = root(a), root(b)
        parent[max(ra, rb)] = min(ra, rb)
    return ([depth.get(int(v), -1) for v in ids], [root(int(v)) for v in ids])


def _graph_with_small_components(arrays):
    """The generated graph, three more components (a path of four vertices,
    two single edges) and the edges shuffled."""
    extra_s = np.array([5000, 5001, 5003, 6000, 7000])
    extra_d = np.array([5001, 5002, 5002, 6001, 7001])
    s = np.concatenate([arrays["src"], extra_s])
    d = np.concatenate([arrays["dst"], extra_d])
    perm = np.random.default_rng(1).permutation(len(s))
    ids = np.unique(np.concatenate([arrays["ids"], extra_s, extra_d]))
    return {"ids": ids, "src": s[perm], "dst": d[perm]}


@pytest.mark.parametrize("scipy_present", [True, False])
def test_the_reference_against_a_queue_and_a_disjoint_set_forest(arrays, monkeypatch, scipy_present):
    if not scipy_present:
        monkeypatch.setattr(graphalytics_reference, "csgraph", None)
    g = _graph_with_small_components(arrays)
    ref = reference.Reference(g)
    source = int(g["ids"][np.argmax(np.bincount(np.concatenate([g["src"], g["dst"]]))[g["ids"]])])
    depth, comp = _brute(g["ids"], g["src"], g["dst"], source)
    assert graphalytics_reference.depths(ref, source).tolist() == depth
    assert graphalytics_reference.components(ref).tolist() == comp
    bfs = graphalytics_reference.bfs_summary(ref, source)
    assert bfs[0] == {"depth": 0, "vertices": 1, "id_sum": source}
    assert bfs[-1]["depth"] is None and bfs[-1]["vertices"] == depth.count(-1) >= 7
    assert sum(r["vertices"] for r in bfs) == len(g["ids"])
    assert sum(r["id_sum"] for r in bfs) == int(g["ids"].sum())
    wcc = graphalytics_reference.wcc_summary(ref)
    assert [r["size"] for r in wcc] == sorted(r["size"] for r in wcc)
    assert {"size": 2, "components": 2, "id_sum": 6000 + 7000} in wcc
    assert {"size": 4, "components": 1, "id_sum": 5000} in wcc
    assert sum(r["size"] * r["components"] for r in wcc) == len(g["ids"])


def test_the_source_is_drawn_uniformly_over_the_largest_component(arrays):
    g = _graph_with_small_components(arrays)
    ref = reference.Reference(g)
    shape = client.load_module("shapes", "graphalytics_bfs")
    comp = graphalytics_reference.components(ref)
    labels, sizes = np.unique(comp, return_counts=True)
    largest = set(g["ids"][comp == labels[np.argmax(sizes)]].tolist())
    drawn = [shape.draw_params(ref, np.random.default_rng(seed))["source"]
             for seed in range(3_900_000_000, 3_900_000_200)]
    assert set(drawn) <= largest
    assert len(set(drawn)) > 150  # not one vertex (a hub) but a uniform draw
    hub = int(g["ids"][np.argmax(np.bincount(np.concatenate([g["src"], g["dst"]]))[g["ids"]])])
    assert drawn.count(hub) < 5


def test_the_32_bit_control_wraps_the_sums_of_ids():
    ids = (3 << 30) + np.arange(4096, dtype=np.int64) * 1024
    g = {"ids": ids, "src": ids[:-1], "dst": ids[1:]}
    full = reference.Reference(g)
    narrow = reference.Reference(g, **reference.CONTROLS["int32"])
    source = int(ids[0])
    assert graphalytics_reference.bfs_summary(full, source) != graphalytics_reference.bfs_summary(narrow, source)
    wide = graphalytics_reference.wcc_summary(full)
    assert wide == [{"size": 4096, "components": 1, "id_sum": int(ids[0])}]
    assert graphalytics_reference.wcc_summary(narrow) != wide
    stale = reference.Reference(g, **reference.CONTROLS["stale_snapshot"])
    assert graphalytics_reference.wcc_summary(stale) != wide


def test_both_rooflines_count_two_orientations_once_and_a_word_a_vertex():
    v, e = 2_396_657, 64_155_735
    want = 2 * (v + 1) * 4 + 2 * e * 4 + 8 * v
    for metric in ("bfs_roofline.graphalytics", "wcc_roofline.graphalytics"):
        with open(os.path.join(CHIPBENCH, "metrics", f"{metric}.json")) as f:
            roofline = client.load_module("rooflines", json.load(f)["args"]["roofline"])
        assert roofline.least_bytes(v, e, 4) == want
        assert roofline.least_seconds({"persons": v, "edges": e}, 4, {"bytes": 819e9}) == pytest.approx(want / 819e9)
    assert 5.5e8 < want < 5.7e8


def window(counters=None, passes=2, requests=()):
    trace = tr.Trace(slice=(0.0, 30.0), busy=[[(1.0, 13.0), (14.0, 25.0)]],
                     modules={}, requests=list(requests))
    return types.SimpleNamespace(
        trace=trace, counters=counters or {}, passes=passes,
        config={"chips": 1, "index_itemsize": 4},
        sizes={"persons": 2_396_657, "edges": 64_155_735},
        peaks=lambda: {"bytes": 819e9},
        roofline=lambda name: client.load_module("rooflines", name),
    )


def read(metric, w):
    with open(os.path.join(CHIPBENCH, "metrics", f"{metric}.json")) as f:
        spec = json.load(f)
    assert spec["name"] == metric
    return client.load_module("readers", spec["reader"]).read(w, **spec["args"])


def test_the_six_metrics_read_the_window_and_nothing_from_a_parent():
    counters = {ITER % "bfs": 12.0, ITER % "wcc": 10.0,
                LANES % "bfs": 12.0 * 2 ** 27, LANES % "wcc": 10.0 * 2 ** 27}
    w = window(counters, requests=[("graphalytics_bfs", 0.5, 13.5), ("graphalytics_wcc", 13.5, 25.5)])
    assert read("procedure_iterations.graphalytics", w) == 11.0
    assert read("procedure_edge_lanes.graphalytics", w) == 11.0 * 2 ** 27
    assert read("bfs_device_s.graphalytics", w) == pytest.approx(12.0)
    assert read("wcc_device_s.graphalytics", w) == pytest.approx(11.0)
    least = client.load_module("rooflines", "both_csrs").least_seconds(w.sizes, 4, w.peaks())
    share = read("bfs_roofline.graphalytics", w)
    assert share == pytest.approx(100.0 * least / 12.0) and 0 < share < 1
    assert read("wcc_roofline.graphalytics", w) == pytest.approx(100.0 * least / 11.0)
    parent = window({"tpu_cypher_host_syncs_total{site=\"agg\"}": 4.0})
    for metric in MINE:
        assert read(metric, parent) is None, metric


def test_the_cell_is_in_the_benchmark_with_its_traffic_and_configuration():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"] if m["name"] in MINE]
    assert sorted(m["name"] for m in mine) == MINE
    assert all(m["workloads"] == [CELL] and m["moves"] == "analytic_pass_s" for m in mine)
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("graphalytics-g500-22", "bfs-wcc", 1)
    config = next(c for c in bench["configs"] if c["name"] == "graphalytics-g500-22")
    with open(os.path.join(ROOT, config["file"])) as f:
        stated = json.load(f)
    assert (stated["persons"], stated["knows"]) == (2_396_657, 64_155_735)
    assert stated["reduced"] == config["reduced"] == []
    for key in ("source", "deployment", "assumed", "guarantees", "published", "drawn"):
        assert stated[key], key
    with open(os.path.join(CHIPBENCH, "traffic", "bfs-wcc.json")) as f:
        mix = json.load(f)
    assert [s["shape"] for s in mix["shapes"]] == ["graphalytics_bfs", "graphalytics_wcc"]
    assert mix["order"] == "pass" and mix["server"] == {"cache_bytes": 0}
    assert mix["trace_slice"] == {"skip_passes": 1, "passes": 1}
    # the generic metrics every LSQB cell reports, this one does too, but
    # the eight that test_idle_by_kind.py pins to the five cells before it;
    # and the dense segment reductions, which its grouped aggregates take
    pinned = {"idle_operator_self_s.analytic", "idle_dispatch_s.analytic",
              "idle_step_s.analytic", "idle_sync_s.analytic", "dispatches.analytic",
              "window_traces.analytic", "window_cache_loads.analytic",
              "feedback_persist_s.analytic"}
    for m in bench["per_layer"]:
        if "lsqb-sf3.lsqb-tree" in m.get("workloads", []) and not m["name"].endswith(".lsqb_tree") \
                and m["name"] != "chain_edge_passes.analytic":
            assert (CELL in m["workloads"]) is (m["name"] not in pinned), m["name"]
    dense = next(m for m in bench["per_layer"] if m["name"] == "agg_dense_reductions.analytic")
    assert dense["workloads"][-1] == CELL


def _rehearse(*extra):
    out = subprocess.run(
        [sys.executable, os.path.join(CHIPBENCH, "run.py"), "--workload", CELL,
         "--seed", "3900000077", "--seconds", "2", "--trace", "0",
         "--rehearse-cpu", "0.001", *extra],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_a_rehearsal_is_correct_and_the_stale_control_is_not():
    result = _rehearse("--control", "stale_snapshot")
    assert result["rehearsal"] and result["attempted"] >= 2
    assert all(v == 0 for v in result["program_compared"].values())
    assert result["correct"] is False
    assert result["controls"]["stale_snapshot"]["wrong_answers"] > 0

"""The reader that labels the device's idle gaps by the KIND of the
program's span open over them, on the hand-made windows of
``test_idle_by_span.py``: the kinds and ``unattributed`` add up to that
reader's total, a ``dispatch`` leaf takes its own interval's gaps and no
more, a tree rendered in another process is skipped, and nothing to read
gives ``None``; then the eight metric files this reader and the counters
came with."""

import json
import os

import pytest

import test_idle_by_span as base  # the windows, by hand (its sys.path too)

client = base.client
by_span = base.idle
by_kind = client.load_module("readers", "idle_by_kind")
node, tree, window = base.node, base.tree, base.window

KINDS = ["operator", "dispatch", "step", "sync", "kernel", "build", "mesh",
         "serve", "phase", "query", "span"]


def operator_tree(qid="w-0-1"):
    """An operator [100.13, 100.69] with a dispatch leaf, a step, a sync
    and a second dispatch leaf inside it; the device is busy over
    [100.1, 100.2] and [100.5, 100.9] (``window``)."""
    return tree(
        node("request", "serve", 100.06, 100.94,
             node("dispatch", "serve", 100.09, 100.90,
                  node("engine", "query", 100.10, 100.88,
                       node("execute", "phase", 100.12, 100.70,
                            node("LimitOp", "operator", 100.13, 100.69,
                                 node("jit_order_permutation", "dispatch",
                                      100.18, 100.26),
                                 node("depad", "step", 100.27, 100.31),
                                 node("order", "sync", 100.33, 100.45),
                                 node("jit_cols_take", "dispatch",
                                      100.46, 100.52))),
                       node("collect", "phase", 100.71, 100.80)))),
        qid,
    )


def test_kinds_and_unattributed_add_up_to_idle_by_span_to_the_microsecond(
        monkeypatch, capsys):
    w = window([operator_tree()], monkeypatch)
    found = by_kind.table(w)
    total = sum(by_span.table(w)["by_leaf"].values())
    assert sum(found["by_kind"].values()) == pytest.approx(total, abs=1e-9)
    assert total == pytest.approx(w.trace.window_s - w.trace.busy_s, abs=1e-12)
    seconds = sum(by_kind.read(w, kinds=[k]) for k in KINDS)
    seconds += found["by_kind"][by_span.UNATTRIBUTED]
    assert seconds == pytest.approx(total, abs=1e-9)
    out = capsys.readouterr().out
    assert out.count("idle by kind:") == 1  # once a window, not once a metric
    assert out.count("idle by span:") == 1


def test_a_dispatch_leaf_takes_its_own_intervals_gaps_and_no_more(monkeypatch):
    w = window([operator_tree()], monkeypatch)
    # the gap [100.2, 100.5]: the first leaf holds [100.2, 100.26], the
    # step [100.27, 100.31], the sync [100.33, 100.45], the second leaf
    # [100.46, 100.5]; the operator's own time is what lies between them
    assert by_kind.read(w, kinds=["dispatch"]) == pytest.approx(0.06 + 0.04)
    assert by_kind.read(w, kinds=["step"]) == pytest.approx(0.04)
    assert by_kind.read(w, kinds=["sync"]) == pytest.approx(0.12)
    assert by_kind.read(w, kinds=["operator"]) == pytest.approx(0.01 + 0.02 + 0.01)
    assert by_kind.read(w, kinds=["kernel"]) == 0.0
    leaves = by_span.table(w)["by_leaf"]
    path = "request/dispatch/engine/execute/LimitOp"
    assert leaves[path + "/jit_order_permutation"] == pytest.approx(0.06)
    assert leaves[path + "/jit_cols_take"] == pytest.approx(0.04)
    assert leaves[path] == pytest.approx(0.04)


def test_a_program_without_the_new_kinds_reads_zero_for_them(monkeypatch):
    w = window([base.request_tree("w-0-1")], monkeypatch)
    assert by_kind.read(w, kinds=["dispatch"]) == 0.0
    assert by_kind.read(w, kinds=["step"]) == 0.0
    assert by_kind.read(w, kinds=["sync"]) == pytest.approx(0.30)
    assert by_kind.read(w, kinds=["operator"]) == 0.0


def test_a_tree_rendered_in_another_process_is_skipped(monkeypatch):
    log = operator_tree()
    engine = log["root"]["children"][0]["children"][0]
    engine["clock"] = "worker"  # as ClusterServer grafts a worker's tree
    w = window([log], monkeypatch)
    found = by_kind.table(w)["by_kind"]
    assert set(found) <= {"serve", by_span.UNATTRIBUTED}
    assert sum(found.values()) == pytest.approx(
        sum(by_span.table(w)["by_leaf"].values()), abs=1e-12)


@pytest.mark.parametrize("case", ["no trace", "empty log", "left the log",
                                  "no log in the program"])
def test_none_where_idle_by_span_gives_none(case, monkeypatch):
    log = [operator_tree()]
    if case == "no trace":
        w = window(log, monkeypatch, trace=None)
    elif case == "empty log":
        w = window([], monkeypatch)
    elif case == "left the log":
        w = window([operator_tree("some-later-request")], monkeypatch)
    else:
        w = window(log, monkeypatch)
        monkeypatch.delattr(base.program_trace, "recent")
    assert by_span.read(w, phases=["execute"]) is None
    assert by_kind.read(w, kinds=["operator"]) is None
    assert by_kind.read(w, kinds=KINDS) is None


NEW = ["idle_operator_self_s.analytic", "idle_dispatch_s.analytic",
       "idle_step_s.analytic", "idle_sync_s.analytic", "dispatches.analytic",
       "window_traces.analytic", "window_cache_loads.analytic",
       "feedback_persist_s.analytic"]


@pytest.mark.parametrize("name", NEW)
def test_the_metric_files_read_through_their_readers(name, monkeypatch):
    entry = next(m for m in base.BENCH["per_layer"] if m["name"] == name)
    assert entry["moves"] == "analytic_pass_s" and len(entry["workloads"]) == 5
    with open(os.path.join(base.CHIPBENCH, "metrics", name + ".json")) as f:
        spec = json.load(f)
    assert spec["name"] == name
    w = window([operator_tree()], monkeypatch)
    w.counters = {
        'tpu_cypher_program_dispatches_total{program="jit_cols_take"}': 6.0,
        'tpu_cypher_program_dispatches_total{program="jit_run"}': 2.0,
        "tpu_cypher_jit_traces_total": 0.0,
        "tpu_cypher_persistent_cache_hits_total": 0.0,
        "tpu_cypher_feedback_persist_seconds_total": 0.5,
    }
    value = client.load_module("readers", spec["reader"]).read(w, **spec["args"])
    want = {"dispatches.analytic": 4.0, "window_traces.analytic": 0.0,
            "window_cache_loads.analytic": 0.0,
            "feedback_persist_s.analytic": 0.25,
            "idle_dispatch_s.analytic": 0.10, "idle_step_s.analytic": 0.04,
            "idle_sync_s.analytic": 0.12,
            "idle_operator_self_s.analytic": 0.04}[name]
    assert value == pytest.approx(want)
    # a program from before this PR has no such counter: nothing, not 0
    if spec["reader"] == "counter_per_pass":
        w.counters = {}
        assert client.load_module("readers", spec["reader"]).read(
            w, **spec["args"]) is None

"""The reader that puts the device's idle gaps down to the program's spans,
on a hand-built trace and span log, and the two counter readers; then one
traced rehearsal of each cell that reads the new metrics and withholds
them."""

import json
import os
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIPBENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(CHIPBENCH)
sys.path[:0] = [CHIPBENCH, ROOT]

import client  # noqa: E402
import trace_reduce as tr  # noqa: E402
from tpu_cypher.obs import trace as program_trace  # noqa: E402

idle = client.load_module("readers", "idle_by_span")

SHIFT = 90.0  # the trace's clock = this process's perf_counter + SHIFT

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def node(name, kind, lo, hi, *children):
    """A rendered span by its ends on the trace's clock; start_s is filled
    in by ``tree``."""
    return {"name": name, "kind": kind, "lo": lo, "hi": hi,
            "children": list(children)}


def tree(root, qid):
    origin = root["lo"]

    def render(n):
        out = {"name": n["name"], "kind": n["kind"],
               "start_s": n["lo"] - origin, "seconds": n["hi"] - n["lo"]}
        if n["children"]:
            out["children"] = [render(c) for c in n["children"]]
        return out

    rendered = render(root)
    rendered["attrs"] = {"id": qid}
    return {"schema_version": 2, "start_perf_s": origin - SHIFT,
            "root": rendered}


def request_tree(qid="w-0-0"):
    return tree(
        node("request", "serve", 100.06, 100.94,
             node("batch_window", "serve", 100.06, 100.08),
             node("dispatch", "serve", 100.09, 100.90,
                  node("route", "serve", 100.09, 100.10),
                  node("engine", "query", 100.10, 100.88,
                       node("plan_cache", "phase", 100.10, 100.11),
                       node("execute", "phase", 100.12, 100.70,
                            node("CsrExpandOp", "operator", 100.13, 100.69,
                                 node("expand", "sync", 100.15, 100.60))),
                       node("collect", "phase", 100.71, 100.80),
                       node("encode", "phase", 100.81, 100.82)),
                  node("route", "serve", 100.89, 100.90)),
             node("serialize", "serve", 100.91, 100.92),
             node("demux", "serve", 100.92, 100.93)),
        qid,
    )


def window(log, monkeypatch, trace="default", passes=2):
    """A window of two one-request passes; the second lies in the slice."""
    monkeypatch.setattr(program_trace, "recent", lambda: list(log),
                        raising=False)
    if trace == "default":
        trace = tr.Trace(
            slice=(100.0, 101.0),
            busy=[[(100.1, 100.2), (100.5, 100.9)]],
            modules={},
            requests=[("a", 100.05, 100.95)],
        )
    requests = [  # the same shape twice: told apart by how long each took
        client.Request(0, "a", 0, "w-0-%d" % k, submitted=lo - SHIFT,
                       finished=hi - SHIFT)
        for k, (lo, hi) in enumerate(((99.05, 99.93), (100.05, 100.95)))
    ]
    return types.SimpleNamespace(trace=trace, requests=requests,
                                 passes=passes, counters={})


SERVE = ["request", "batch_window", "dispatch", "route", "serialize", "demux"]


def test_each_gap_goes_to_the_deepest_span_open_over_it(monkeypatch, capsys):
    w = window([request_tree("w-0-1")], monkeypatch)
    # gaps: [100.0, 100.1] 0.06 before the request, batch_window 0.02, the
    # root 0.01, route 0.01; [100.2, 100.5] inside the sync; [100.9, 101.0]
    # root 0.01, serialize 0.01, demux 0.01, root 0.01, 0.06 after it
    assert idle.read(w, phases=SERVE) == pytest.approx(0.08)
    assert idle.read(w, phases=["execute"]) == pytest.approx(0.30)
    assert idle.read(w, phases=["plan_cache", "parse"]) == 0.0
    assert idle.read(w, phases=["collect", "encode"]) == 0.0
    assert idle.read(w, share="unattributed") == pytest.approx(24.0)
    found = idle.table(w)
    assert found["by_leaf"][
        "request/dispatch/engine/execute/CsrExpandOp/sync:expand"
    ] == pytest.approx(0.30)
    assert found["by_leaf"]["request"] == pytest.approx(0.03)
    out = capsys.readouterr().out
    assert out.count("idle by span:") == 1  # once a window, not once a metric
    assert "sync:expand 0.300000" in out and "clock residual" in out


def test_the_pieces_add_up_to_the_idle_seconds(monkeypatch):
    w = window([request_tree("w-0-1")], monkeypatch)
    found = idle.table(w)
    want = w.trace.window_s - w.trace.busy_s
    assert sum(found["by_phase"].values()) == pytest.approx(want, abs=1e-12)
    assert sum(found["by_leaf"].values()) == pytest.approx(want, abs=1e-12)
    metrics = [m for m in BENCH["per_layer"] if m["name"].startswith("idle_")]
    seconds = 0.0
    for m in metrics:  # the benchmark's own metric files cover every phase
        with open(os.path.join(CHIPBENCH, "metrics", m["name"] + ".json")) as f:
            args = json.load(f)["args"]
        if "phases" in args:
            seconds += idle.read(w, **args)
    share = idle.read(w, share="unattributed")
    assert seconds + share / 100.0 * want == pytest.approx(want, abs=1e-12)
    assert w.trace.idle_share * w.trace.window_s == pytest.approx(want)


def test_the_clock_offset_is_recovered_from_the_q_intervals(monkeypatch):
    trace = tr.Trace(
        slice=(100.0, 104.0), busy=[[(100.5, 103.5)]], modules={},
        requests=[("a", 100.10001, 101.0), ("b", 101.10002, 102.0),
                  ("a", 102.10003, 103.0), ("b", 104.5, 105.0)],
    )
    sent = [
        client.Request(0, shape, 0, f"w-0-{k}", submitted=10.1 + k,
                       finished=10.9 + k)
        for k, shape in enumerate("babab", start=-1)
    ]
    offset, residual, matched = idle.clock_offset(trace, sent)
    assert offset == pytest.approx(SHIFT + 2e-5, abs=1e-9)
    assert residual == pytest.approx(1e-5, abs=1e-9)
    assert [r.qid for r in matched] == ["w-0-0", "w-0-1", "w-0-2"]
    # shapes that are no run of the window's: nothing to place the spans by
    trace.requests[1] = ("a", 101.10002, 102.0)
    assert idle.clock_offset(trace, sent) is None


def test_a_gap_under_no_span_is_unattributed(monkeypatch):
    far = tree(node("request", "serve", 100.94, 100.96), "w-0-1")
    w = window([far], monkeypatch)
    assert idle.read(w, share="unattributed") == pytest.approx(
        100.0 * (0.5 - 0.02) / 0.5)
    assert idle.read(w, phases=["request"]) == pytest.approx(0.02)


def test_requests_that_overlap_give_the_gap_to_the_one_that_started_last(
        monkeypatch):
    first = tree(node("request", "serve", 100.00, 100.50), "w-0-1")
    later = tree(node("request", "serve", 100.20, 100.40,
                      node("queue_wait", "serve", 100.30, 100.40)), "other")
    w = window([first, later], monkeypatch)
    found = idle.table(w)
    # [100.0, 100.1] and [100.4, 100.5] under the first; [100.2, 100.3]
    # under the second's root, [100.3, 100.4] under its span
    assert found["by_phase"]["request"] == pytest.approx(0.3)
    assert found["by_phase"]["queue_wait"] == pytest.approx(0.1)


@pytest.mark.parametrize("case", ["no trace", "empty log", "left the log",
                                  "no log in the program"])
def test_nothing_to_read_gives_none(case, monkeypatch):
    log = [request_tree("w-0-1")]
    if case == "no trace":
        w = window(log, monkeypatch, trace=None)
    elif case == "empty log":
        w = window([], monkeypatch)
    elif case == "left the log":
        w = window([request_tree("some-later-request")], monkeypatch)
    else:
        w = window(log, monkeypatch)
        monkeypatch.delattr(program_trace, "recent")
    assert idle.read(w, phases=SERVE) is None
    assert idle.read(w, share="unattributed") is None


def test_counter_per_pass_and_registry_total():
    per_pass = client.load_module("readers", "counter_per_pass")
    total = client.load_module("readers", "registry_total")
    w = types.SimpleNamespace(passes=4, counters={
        'tpu_cypher_host_syncs_total{site="expand"}': 8.0,
        'tpu_cypher_host_syncs_total{site="agg"}': 12.0,
        "tpu_cypher_xla_compiles_total": 3.0,
    })
    assert per_pass.read(w, ["tpu_cypher_host_syncs_total"]) == 5.0
    assert per_pass.read(w, ["tpu_cypher_no_such_counter"]) is None
    from tpu_cypher.obs.metrics import REGISTRY

    c = REGISTRY.counter("t_chipbench_reader_total", labels=("k",))
    before = total.read(w, ["t_chipbench_reader_total"]) or 0.0
    c.inc(2.5, k="a")
    c.inc(0.5, k="b")
    assert total.read(w, ["t_chipbench_reader_total"]) == before + 3.0
    assert total.read(w, ["tpu_cypher_no_such_counter"]) is None


@pytest.mark.parametrize("cell", [c["name"] for c in BENCH["workloads"]])
def test_traced_rehearsal_reads_the_new_metrics_and_withholds_them(cell):
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    proc = subprocess.run(
        [sys.executable, os.path.join(CHIPBENCH, "run.py"), "--workload",
         cell, "--seed", "4000000011", "--seconds", "2", "--trace", "1",
         "--rehearse-cpu", "0.02"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["metrics"] == {}
    # a CPU's trace has no device plane, so the five idle metrics read
    # nothing; the four that read counters do, beside the two that did
    assert "rehearsal: 6 metrics read and withheld" in proc.stdout
    for m in BENCH["per_layer"]:
        assert m["name"] not in proc.stdout

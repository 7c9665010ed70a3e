"""The trace reducer on hand-made intervals and on a recorded trace, and the
CPU rehearsal of ``run.py``."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIPBENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(CHIPBENCH)
sys.path.insert(0, CHIPBENCH)

import trace_reduce as tr  # noqa: E402

RECORDED = os.path.join(HERE, "recorded.xplane.pb")


def test_union_counts_nested_and_overlapping_once():
    merged = tr.union([(5, 6), (0, 2), (1, 3), (1.5, 1.6), (3, 4)])
    assert merged == [(0, 4), (5, 6)]
    assert tr.covered(merged, 0, 10) == 5
    assert tr.covered(merged, 3.5, 5.5) == 1.0


def test_gaps_are_what_the_union_leaves():
    merged = [(1, 2), (4, 5)]
    assert tr.gaps(merged, 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert tr.gaps(merged, 1.5, 4.5) == [(2, 4)]
    assert tr.gaps([], 0, 1) == [(0, 1)]


def make_trace():
    return tr.Trace(
        slice=(0.0, 10.0),
        busy=[[(1.0, 2.0), (4.0, 5.0)], [(1.0, 3.0)]],
        modules={"jit_a": 2.5, "jit_b": 1.5},
        requests=[("x", 0.5, 2.5), ("y", 3.0, 6.0), ("x", 9.0, 11.0)],
    )


def test_busy_idle_and_device_time_inside_a_request():
    t = make_trace()
    assert t.window_s == 10.0
    assert t.busy_s == pytest.approx((2.0 + 2.0) / 2)  # mean over devices
    assert t.idle_share == pytest.approx(0.8)
    assert t.busy_in(0.5, 2.5) == pytest.approx((1.0 + 1.5) / 2)
    # the third request ends outside the slice, so it is not "in" it
    assert [r[0] for r in t.requests_in_slice()] == ["x", "y"]
    assert t.busy_in_shape("x") == pytest.approx(1.25)
    assert t.busy_in_shape("z") is None


def test_idle_gaps_go_to_the_request_in_flight():
    idle = make_trace().idle_by_request()
    # device 0's gaps: [0,1] x covers .5; [2,4] y covers 1, x .5; [5,10] y 1, x 1
    assert idle == {"q:x": 1.0, "q:y": 7.0}
    assert sum(idle.values()) == pytest.approx(8.0)
    b = make_trace().breakdown()
    assert b["device_ops"] == [["jit_a", 2.5], ["jit_b", 1.5]]
    assert b["idle_gaps"][0] == ["q:y", 7.0]


def test_recorded_trace():
    """A slice recorded on the chip (TPU v5 lite), cut short: the planes and
    lines are found by the names the profiler gives them."""
    t = tr.reduce_trace(RECORDED)
    assert t is not None and len(t.busy) == 1
    with open(os.path.join(HERE, "recorded.expected.json")) as f:
        want = json.load(f)
    assert t.window_s == pytest.approx(want["window_s"])
    assert t.busy_s == pytest.approx(want["busy_s"])
    assert 0.0 < t.idle_share < 1.0
    assert len(t.requests_in_slice()) == want["requests_in_slice"]
    assert t.breakdown()["device_ops"][0][0] == want["top_module"]
    assert sum(t.idle_by_request().values()) == pytest.approx(
        t.window_s - t.busy_s)


def test_a_trace_without_a_device_plane_gives_nothing(tmp_path):
    empty = tmp_path / "empty.xplane.pb"
    empty.write_bytes(b"")
    assert tr.reduce_trace(str(empty)) is None


def run_py(*args, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, os.path.join(CHIPBENCH, "run.py"), *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )


@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_names_the_cpu_and_no_metric(trace):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = bench["workloads"][-1]["name"]
    proc = run_py("--workload", cell, "--seed", "4000000007", "--seconds",
                  "2", "--trace", trace, "--rehearse-cpu", "0.02")
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["device"]["platform"] == "cpu" and last["rehearsal"] is True
    assert last["metrics"] == {} and last["correct"] is True
    assert "busy_s" not in last["device"] and "breakdown" not in last
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert m["name"] not in proc.stdout


def test_off_the_chip_it_fails_and_prints_no_result():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    proc = run_py("--workload", cell, "--seed", "1", "--seconds", "1",
                  "--trace", "0", env_extra={"JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 3
    assert "{" not in proc.stdout

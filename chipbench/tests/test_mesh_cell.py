"""The four-chip cell on four of the CPU's virtual devices: a rehearsal at 2%
of its size comes out correct with seven shapes a pass, each control comes
out not correct, and the two readers the cell brings read hand-made traces
as they should."""

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

CELL = "snb-sf100-mesh4.analytic-mesh"
SHAPES_A_PASS = 7


def rehearse(*extra):
    """One ``--rehearse-cpu 0.02`` of the cell in a child that has four
    virtual devices; (its result line, what it left in ``out/``)."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    proc = subprocess.run(
        [sys.executable, os.path.join(CHIPBENCH, "run.py"), "--workload",
         CELL, "--seconds", "3", "--rehearse-cpu", "0.02", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    with open(os.path.join(CHIPBENCH, "out", f"{CELL}.last.json")) as f:
        left = json.load(f)
    return json.loads(proc.stdout.strip().splitlines()[-1]), left, proc


def test_rehearsal_on_four_devices_is_correct_with_seven_shapes_a_pass():
    result, left, proc = rehearse("--seed", "2800000011", "--trace", "1")
    assert result["device"] == {"platform": "cpu", "kind": "cpu", "count": 4}
    assert result["correct"] is True and result["failed"] == 0
    assert left["passes"] >= 1
    assert result["attempted"] == SHAPES_A_PASS * left["passes"]
    assert result["metrics"] == {}  # a CPU's numbers are withheld
    assert "metrics read and withheld" in proc.stdout


def test_each_control_comes_out_not_correct():
    result, _, _ = rehearse("--seed", "2800000013", "--trace", "0",
                            "--control", "int32",
                            "--control", "stale_snapshot")
    assert all(v == 0 for v in result["program_compared"].values())
    assert sorted(result["controls"]) == ["int32", "stale_snapshot"]
    for name, compared in result["controls"].items():
        assert compared["wrong_answers"] > 0, name
    assert result["correct"] is False


def window(busy, chips, requests=(("two_hop_count", 1.0, 3.0),)):
    trace = tr.Trace(slice=(0.0, 10.0), busy=busy, modules={},
                     requests=list(requests))
    return types.SimpleNamespace(
        trace=trace,
        config={"chips": chips, "index_itemsize": 8},
        sizes={"persons": 1000, "edges": 50_000},
        peaks=lambda: {"bytes": 819e9},
        roofline=lambda name: client.load_module("rooflines", name),
    )


def test_busy_skew_reads_the_busiest_chip_over_the_mean():
    skew = client.load_module("readers", "busy_skew")
    assert skew.read(window([[(1.0, 3.0)]], 1)) == 1.0
    four = [[(0.0, 4.0)], [(1.0, 2.0), (5.0, 6.0)], [(2.0, 4.0)], [(0.0, 2.0)]]
    assert skew.read(window(four, 4)) == pytest.approx(4.0 / ((4 + 2 + 2 + 2) / 4))
    # busy seconds outside the slice do not count
    assert skew.read(window([[(8.0, 12.0)], [(8.0, 10.0)]], 2)) == 1.0
    assert skew.read(types.SimpleNamespace(trace=None)) is None


def test_roofline_share_of_chips_is_a_quarter_on_four():
    of_one = client.load_module("readers", "roofline_share")
    of_chips = client.load_module("readers", "roofline_share_of_chips")
    args = {"roofline": "two_hop", "shape": "two_hop_count"}
    busy = [[(1.0, 2.0)], [(1.5, 2.5)], [(2.0, 3.0)], [(1.0, 2.0)]]
    one, four = window(busy, 1), window(busy, 4)
    assert one.trace.busy_in_shape("two_hop_count") == pytest.approx(1.0)
    assert of_chips.read(four, **args) == pytest.approx(of_one.read(four, **args) / 4)
    assert of_chips.read(one, **args) == pytest.approx(of_one.read(one, **args))
    least = client.load_module("rooflines", "two_hop").least_seconds(
        four.sizes, 8, four.peaks())
    assert of_chips.read(four, **args) == pytest.approx(100.0 * least / 4 / 1.0)
    # no request of the shape in the slice, or no trace: nothing, never 0
    assert of_chips.read(window(busy, 4, requests=()), **args) is None
    assert of_chips.read(types.SimpleNamespace(trace=None), **args) is None

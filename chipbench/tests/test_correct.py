"""The comparison that decides ``correct`` can fail, and does where it should.

Two kinds of case per cell, each a whole run of ``run.py`` on the CPU at a
small share of the cell's size (``--rehearse-cpu``, which stands in for the
look for a chip):

* each control — the reference in 32-bit integers, and the reference over a
  stale snapshot, put in the program's place after a real window — has to
  come out not correct, while the program's own answers of that window come
  out correct;
* the timed path broken underneath — an answer altered where the program
  produces it (``wire.encode_rows``) — has to come out not correct.
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIPBENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(CHIPBENCH)
SHARE = "0.02"

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    CELLS = [c["name"] for c in json.load(f)["workloads"]]

ALTERED = """
import os, sys
os.environ.update({"JAX_PLATFORMS": "cpu", "JAX_ENABLE_X64": "1"})
sys.path.insert(0, %(chipbench)r)
import run
from tpu_cypher.serve import wire

real, calls = wire.encode_rows, [0]

def altered(rows, columns):
    out = real(rows, columns)
    calls[0] += 1
    if calls[0] %% 3 == 0 and out:  # every third answer, one value off by one
        key = next(k for k, v in out[0].items() if isinstance(v, int))
        out[0] = {**out[0], key: out[0][key] + 1}
    return out

wire.encode_rows = altered
sys.exit(run.main(%(argv)r))
"""


def last_line(cmd):
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    proc = subprocess.run(
        cmd, cwd=ROOT, env=env, capture_output=True, text=True, timeout=600
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc


def argv(cell, seed):
    return ["--workload", cell, "--seed", str(seed), "--seconds", "3",
            "--trace", "0", "--rehearse-cpu", SHARE]


@pytest.mark.parametrize("control", ["int32", "stale_snapshot"])
@pytest.mark.parametrize("cell", CELLS)
def test_control_comes_out_not_correct(cell, control):
    result, proc = last_line(
        [sys.executable, os.path.join(CHIPBENCH, "run.py"),
         *argv(cell, 2_400_000_011), "--control", control]
    )
    assert list(result["controls"]) == [control]
    assert all(v == 0 for v in result["program_compared"].values())
    assert result["correct"] is False
    assert result["compared"]["wrong_answers"]["value"] > 0
    assert "compared wrong_answers:" in proc.stderr.splitlines()[-5]


@pytest.mark.parametrize("cell", CELLS)
def test_altered_answer_comes_out_not_correct(cell):
    script = ALTERED % {"chipbench": CHIPBENCH, "argv": argv(cell, 17)}
    result, _ = last_line([sys.executable, "-c", script])
    assert result["correct"] is False
    assert result["failed"] == result["compared"]["wrong_answers"]["value"] > 0
    assert result["compared"]["unanswered"]["value"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_comes_out_correct(cell):
    result, _ = last_line(
        [sys.executable, os.path.join(CHIPBENCH, "run.py"), *argv(cell, 23)]
    )
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0

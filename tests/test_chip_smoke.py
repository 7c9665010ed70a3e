"""``chip_smoke.py`` off the chip: what must fail, fails.

The chip check itself runs through the chip tool. Here, on the CPU:

* the REHEARSAL — ``chip_smoke.py --rehearse-cpu`` drives every phase of the
  one-chip path (server, wire client, NumPy references, WAL write and
  replay, router + worker) at a tiny scale, names the CPU on every line,
  and can never print the TPU success line;
* NO FALLBACK — without the rehearsal flag a machine without an accelerator
  gets a non-zero exit and no result line, before anything is loaded; so
  does a directory that holds the script and nothing else of the repo.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, cwd=REPO, script=SMOKE, timeout=600):
    return subprocess.run(
        [sys.executable, script, *args], cwd=cwd, capture_output=True,
        text=True, timeout=timeout,
    )


def _last_json(stdout: str):
    lines = [l for l in stdout.splitlines() if l.strip()]
    return json.loads(lines[-1]) if lines and lines[-1].startswith("{") else None


@pytest.fixture(scope="module")
def rehearsal():
    return _run(["--rehearse-cpu", "--scale", "0.05"])


def test_rehearsal_passes_every_phase(rehearsal):
    assert rehearsal.returncode == 0, rehearsal.stdout + rehearsal.stderr
    out = rehearsal.stdout
    for phase in ("serve", "cluster"):
        assert f"phase {phase} passed" in out, out
    # the shapes the issue lists all answered, equal to their references
    for name in (
        "scan_filter", "two_hop_count", "grouped_aggregate",
        "order_by_limit", "property_projection", "expand_materialize",
        "sort_probe_join", "distinct_two_hop", "triangle_close",
        "var_length", "star_optional", "string_starts_with",
    ):
        assert f"query {name}: rows=" in out, name
    assert "read back after attach_wal replay" in out
    assert "front end holds no device" in out


def test_rehearsal_names_the_cpu_on_every_line(rehearsal):
    lines = [l for l in rehearsal.stdout.splitlines() if l.strip()]
    assert len(lines) > 20
    for line in lines[:-1]:
        assert line.startswith("[cpu rehearsal] "), line
    last = json.loads(lines[-1])
    assert last["device"]["platform"] == "cpu" and last["rehearsal"] is True


def test_rehearsal_can_never_print_the_tpu_success_line(rehearsal):
    assert '"platform": "tpu"' not in rehearsal.stdout


def test_mesh_phase_sends_its_queries_through_the_server():
    """``--chips 4``'s mesh phase on four virtual devices: the queries go
    through ``QueryServer`` and the wire, every sharded tier's counter
    moves (the join's among them) and nothing declines."""
    proc = _run(["--rehearse-cpu", "--scale", "0.5", "--chips", "4",
                 "--only", "mesh"])
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = proc.stdout
    assert "phase mesh passed" in out, out
    assert "QueryServer on " in out and "mesh of 4" in out
    for name in ("two_hop_count", "grouped_aggregate", "sort_probe_join",
                 "distinct_values"):
        assert f"query {name}: rows=" in out, name
    moved = next(l for l in out.splitlines() if "counters moved" in l)
    for series in ("tpu_cypher_mesh_expand_total", "tpu_cypher_mesh_agg_total",
                   "tpu_cypher_mesh_distinct_total",
                   "tpu_cypher_mesh_join_total",
                   "tpu_cypher_mesh_exchange_bytes_total"):
        assert series in moved, (series, moved)
    assert "tpu_cypher_mesh_declines_total" not in moved, moved


def test_no_accelerator_fails_before_loading_anything():
    proc = _run(["--scale", "0.05"])
    assert proc.returncode not in (0, None)
    assert _last_json(proc.stdout) is None, proc.stdout
    assert "deployment:" not in proc.stdout  # nothing was loaded
    assert "no accelerator" in proc.stderr


def test_script_alone_in_a_directory_fails(tmp_path):
    """Without the program beside it the script cannot pass — on the chip
    either (there the driver runs exactly this)."""
    alone = shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    for args in (["--scale", "0.05"], ["--rehearse-cpu", "--scale", "0.05"]):
        proc = _run(args, cwd=tmp_path, script=alone)
        assert proc.returncode != 0
        assert _last_json(proc.stdout) is None, proc.stdout


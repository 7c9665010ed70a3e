"""The benchmark cell ``lsqb-sf10-person.lsqb-chain`` on the CPU at a small
share of its size: the generator is deterministic per seed and gives
``snb-sf10``'s own persons and friendships, a rehearsal comes out correct
with two shapes a pass and without a row of the chain, and each control
comes out not correct. (The readers and the roofline the cell brings are
held to hand-made windows in ``chipbench/tests/test_lsqb_cell.py``.)"""

import json
import os
import subprocess
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIPBENCH = os.path.join(ROOT, "chipbench")
CELL = "lsqb-sf10-person.lsqb-chain"
SHARE = "0.02"


def _generators():
    sys.path.insert(0, CHIPBENCH)
    try:
        import gen_lsqb
        import gen_snb
    finally:
        sys.path.remove(CHIPBENCH)
    return gen_lsqb, gen_snb


def test_generator_is_deterministic_and_keeps_snb_sf10s_persons_and_friendships():
    gen_lsqb, gen_snb = _generators()
    one = gen_lsqb.snb_arrays(1500, 40_000, 3_200_000_123)
    two = gen_lsqb.snb_arrays(1500, 40_000, 3_200_000_123)
    other = gen_lsqb.snb_arrays(1500, 40_000, 3_200_000_124)
    assert sorted(one) == sorted(two)
    assert all(np.array_equal(one[k], two[k]) for k in one)
    assert not np.array_equal(one["interest_tag"], other["interest_tag"])
    snb = gen_snb.snb_arrays(1500, 40_000, 3_200_000_123)
    for key in ("ids", "src", "dst"):
        assert np.array_equal(one[key], snb[key]), key
    # one id space of four labels; one city a person, one country a city;
    # no (person, tag) pair twice; about 23.2 interests a person
    every = np.concatenate([one[k] for k in ("ids", "city_ids", "country_ids", "tag_ids")])
    assert len(np.unique(every)) == len(every) and every.max() < 1 << 53
    assert len(one["person_city"]) == len(one["ids"])
    assert np.isin(one["person_city"], one["city_ids"]).all()
    assert len(one["city_country"]) == len(one["city_ids"])
    assert np.isin(one["city_country"], one["country_ids"]).all()
    pairs = np.stack([one["interest_person"], one["interest_tag"]])
    assert len(np.unique(pairs, axis=1)[0]) == pairs.shape[1]
    assert np.isin(one["interest_person"], one["ids"]).all()
    # (at this size 368 tags: more double draws are dropped than at SF10)
    assert 18.0 < pairs.shape[1] / len(one["ids"]) < 25.0


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


def test_rehearsal_is_correct_with_two_shapes_a_pass():
    result, left, proc = _rehearse("--seed", "3200000011", "--trace", "1")
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is True and result["failed"] == 0
    assert all(v["value"] == 0 for v in result["compared"].values())
    assert left["passes"] >= 1
    assert result["attempted"] == 2 * left["passes"]
    assert result["metrics"] == {}  # a CPU's numbers are withheld
    assert "metrics read and withheld" in proc.stdout


def test_each_control_comes_out_not_correct():
    # at this share the counts stay under 2**31, so the 32-bit control
    # wraps nothing: the full-size runs on the chip are where it fails
    result, _, _ = _rehearse("--seed", "3200000013", "--trace", "0",
                             "--control", "stale_snapshot")
    assert all(v == 0 for v in result["program_compared"].values())
    stale = result["controls"]["stale_snapshot"]
    assert stale["wrong_answers"] == result["attempted"]
    assert result["correct"] is False

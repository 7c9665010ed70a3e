"""Open-ended differential soak: fresh-seed fuzzing until a time budget.

Not collected by pytest (no ``test_`` prefix) — run directly when you want
hours of randomized oracle-vs-TPU differential coverage beyond the fixed
regression seeds in ``test_fuzz_differential.py``:

    python tests/fuzz_soak.py [seconds] [seed] [--faults]

It runs on whatever platform JAX selects (``JAX_PLATFORMS=cpu`` for a CPU
run) and names it in its last line.

Every query from all three grammar families (general, adversarial
uniqueness graphs, temporal) must produce identical bags on both
backends; any divergence prints the reproducing query + seed and exits
nonzero so a CI wrapper can promote it to a fixed regression seed.
Round-5 soak: 1,400+ queries, zero divergences.

``--faults`` — chaos mode: random ``TPU_CYPHER_FAULTS`` specs (random
site/kind/occurrence, including ``:*`` full-device-outage specs) are
injected around the TPU side of roughly half the queries, so the
degrade-and-retry ladder (docs/robustness.md) is soaked differentially:
under ANY injected fault schedule the result bags must still match the
oracle, and no raw (untyped) error may escape.
"""

import os
import sys
import time

os.environ.setdefault("JAX_ENABLE_X64", "1")

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

FAULT_SITES = ("join", "expand", "var_expand", "filter", "compact", "shuffle")
FAULT_KINDS = ("oom", "compile", "lost")


def _random_fault_spec(rng) -> str:
    parts = []
    for _ in range(int(rng.integers(1, 3))):
        site = FAULT_SITES[int(rng.integers(0, len(FAULT_SITES)))]
        kind = FAULT_KINDS[int(rng.integers(0, len(FAULT_KINDS)))]
        occ = "*" if rng.random() < 0.3 else str(int(rng.integers(1, 4)))
        parts.append(f"{kind}@{site}:{occ}")
    return ",".join(parts)


def main(budget_s: float, seed: int, chaos: bool = False) -> int:
    from test_fuzz_differential import (
        _build,
        _build_temporal,
        _gen_query,
        _gen_temporal_query,
        _gen_uniqueness_query,
        _graph_args,
        _graph_args_adversarial,
        _temporal_graph,
    )

    from tpu_cypher import CypherSession
    from tpu_cypher.errors import TpuCypherError
    from tpu_cypher.runtime import faults

    rng = np.random.default_rng(seed)
    pairs = []
    for build, gen_args in (
        (_build, _graph_args(seed + 1)),
        (_build, _graph_args_adversarial(seed + 2)),
        (_build_temporal, _temporal_graph(seed + 3)),
    ):
        pairs.append(
            (
                build(CypherSession.local(), *gen_args),
                build(CypherSession.tpu(), *gen_args),
            )
        )

    fails = n = 0
    t_end = time.time() + budget_s
    while time.time() < t_end:
        fam = int(rng.integers(0, 3))
        gl, gt = pairs[fam]
        if fam == 0:
            q = str(_gen_query(rng))
        elif fam == 1:
            q = (
                str(_gen_uniqueness_query(rng))
                if rng.random() < 0.6
                else str(_gen_query(rng))
            )
        else:
            q = _gen_temporal_query(rng)
        spec = None
        if chaos and rng.random() < 0.5:
            spec = _random_fault_spec(rng)
        try:
            want = gl.cypher(q).records.to_bag()
            faults.set_spec(spec)
            try:
                got = gt.cypher(q).records.to_bag()
            finally:
                faults.set_spec(None)
            if got != want:
                fails += 1
                print(f"DIVERGENCE (seed {seed}, faults {spec}): {q}")
        except TpuCypherError as exc:
            # a typed terminal error is only LEGAL under an injected
            # full-outage spec whose fault the ladder cannot absorb; the
            # soak treats any typed error on these ladder-coverable specs
            # as a failure too (every site has a host rung)
            fails += 1
            print(
                f"TYPED ESCAPE (seed {seed}, faults {spec}): {q}\n"
                f"  {type(exc).__name__}: {exc}"
            )
        except Exception as exc:  # noqa: BLE001 - soak reports everything
            fails += 1
            kind = "RAW ESCAPE" if spec else "CRASH"
            print(f"{kind} (seed {seed}, faults {spec}): {q}\n  {type(exc).__name__}: {exc}")
        n += 1
    mode = " (chaos)" if chaos else ""
    import jax

    print(
        f"fuzz soak{mode} on {jax.devices()[0].platform}: {n} queries in "
        f"{budget_s:.0f}s, {fails} failures"
    )
    return 1 if fails else 0


if __name__ == "__main__":
    args = [a for a in sys.argv[1:] if a != "--faults"]
    chaos = "--faults" in sys.argv[1:]
    budget = float(args[0]) if len(args) > 0 else 300.0
    seed = int(args[1]) if len(args) > 1 else int(time.time())
    sys.exit(main(budget, seed, chaos=chaos))

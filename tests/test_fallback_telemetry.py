"""Device-coverage telemetry: per-query
fallback recording on CypherResult, and a regression gate on the aggregate
fallback rate across the TCK corpus run on the TPU backend — a silent
device-coverage regression (joins/group/distinct dropping to the oracle)
fails here visibly with the reasons table printed."""

import os

from tpu_cypher import CypherSession
from tpu_cypher.backend.tpu.table import FALLBACK_COUNTER
from tpu_cypher.tck import ScenariosFor, TckRunner, load_features
from tpu_cypher.tck.runner import load_blacklist

HERE = os.path.dirname(os.path.abspath(__file__))

# Measured 2026-07-30 (round 4, 500+-scenario corpus): the per-scenario
# fallback rate sits under ~1 event/scenario, all host-by-design value
# shapes (lists, maps, quantifiers, host functions) — durations moved on
# device this round. The gate has headroom: a wholesale category regression
# (device joins, group, distinct, filters) adds hundreds of events and
# trips it.
MAX_EVENTS_PER_SCENARIO = 1.5


def test_per_query_fallback_recording():
    s = CypherSession.tpu()
    s.record_fallbacks = True
    g = s.create_graph_from_create_query(
        "CREATE (:P {a: 1, l: [1, 2]})-[:K]->(:P {a: 2, l: [3]})"
    )
    clean = g.cypher("MATCH (n:P) WHERE n.a > 1 RETURN count(*) AS c")
    clean.records.collect()
    assert clean.fallbacks == {}, clean.fallbacks
    listy = g.cypher("MATCH (n:P) WHERE n.l[0] = 1 RETURN count(*) AS c")
    listy.records.collect()
    assert listy.fallbacks, "list-indexing predicate should record islands"


def test_tck_corpus_fallback_rate_under_threshold():
    scenarios = ScenariosFor(
        load_features(os.path.join(HERE, "tck", "features")),
        load_blacklist(os.path.join(HERE, "tck", "blacklist")),
    )
    runner = TckRunner(CypherSession.tpu)
    FALLBACK_COUNTER.reset()
    n = 0
    for sc in scenarios.white_list:
        runner.run(sc)
        n += 1
    snap = FALLBACK_COUNTER.snapshot()
    FALLBACK_COUNTER.reset()
    total = sum(snap.values())
    table = "\n".join(
        f"  {v:6d}  {k}" for k, v in sorted(snap.items(), key=lambda kv: -kv[1])
    )
    print(f"\nfallbacks: {total} events / {n} scenarios\n{table}")
    assert n > 0
    assert total / n <= MAX_EVENTS_PER_SCENARIO, (
        f"device-coverage regression: {total} fallback events over {n} "
        f"scenarios ({total / n:.2f}/scenario, gate "
        f"{MAX_EVENTS_PER_SCENARIO}).\n{table}"
    )

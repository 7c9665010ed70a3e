"""The abstract shape interpreter's external contracts (ISSUE 12).

Three surfaces under test:

* AGREEMENT — the static padded-shape predictor
  (``analysis.shapes.predict_padded``) must equal what the bucket lattice
  actually does at runtime: pinned directly against
  ``bucketing.round_size`` over the lattice modes, and end-to-end against
  the padded-vs-true ``rows_pairs`` that obs spans stamp while the
  differential corpus executes.
* FACTS — ``python -m tpu_cypher.analysis --facts-out`` emits the
  schema-versioned per-operator padded-shape formulas the cost model
  (ROADMAP item 2) consumes.
* RULES — the three shape rules fire at EXACTLY their seeded bad-fixture
  lines and nowhere on the clean fixtures.
"""

import json
import os
import subprocess
import sys

import pytest

from tpu_cypher import analysis
from tpu_cypher.analysis import shapes
from tpu_cypher.analysis.shapes import predict_padded
from tpu_cypher.backend.tpu import bucketing

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "lint_fixtures")
REPO = os.path.dirname(HERE)

MODES = ("off", "pow2", "1.25")
NS = (0, 1, 31, 32, 33, 63, 64, 100, 1000, 12345)


# ---------------------------------------------------------------------------
# predictor == lattice, by construction and forever
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", MODES)
def test_predict_padded_matches_round_size(mode):
    """the no-drift pin: the analyzer's pure reimplementation of the
    lattice equals ``bucketing.round_size`` pointwise, per mode"""
    with bucketing.force_mode(mode):
        for n in NS:
            assert predict_padded(n, mode) == bucketing.round_size(n), (
                f"mode={mode} n={n}"
            )


def test_predict_padded_is_monotone_and_covering():
    for mode in ("pow2", "1.25"):
        prev = 0
        for n in range(0, 300):
            p = predict_padded(n, mode)
            assert p >= n, f"mode={mode}: pad below true count at {n}"
            assert p >= prev, f"mode={mode}: lattice not monotone at {n}"
            prev = p


# ---------------------------------------------------------------------------
# static-vs-dynamic agreement over the differential corpus: every
# (true, padded) pair an operator span records at runtime must equal the
# static prediction for the active mode
# ---------------------------------------------------------------------------


def _spans_with_pairs(result):
    prof = result.profile()
    return [s for s in prof.trace.spans() if "rows_pairs" in s.attrs]


@pytest.mark.parametrize("mode", ["pow2", "1.25"])
def test_runtime_rows_pairs_match_static_prediction(mode):
    import test_bucketing as TB
    from tpu_cypher import CypherSession

    with bucketing.force_mode(mode):
        g = CypherSession.tpu().create_graph_from_create_query(
            TB._create_query()
        )
        checked = 0
        operators = set()
        for q in TB.CORPUS:
            result = g.cypher(q)
            result.records.collect()
            for span in _spans_with_pairs(result):
                operators.add(span.name)
                for true_rows, padded in span.attrs["rows_pairs"]:
                    assert predict_padded(true_rows, mode) == padded, (
                        f"mode={mode} span={span.name} "
                        f"true={true_rows} padded={padded} "
                        f"predicted={predict_padded(true_rows, mode)}\n"
                        f"query: {q}"
                    )
                    checked += 1
        # the corpus routes through enough bucketed materializes that an
        # empty sweep means the span plumbing broke, not that all is well
        assert checked >= 20, f"only {checked} pairs observed"
        assert operators, "no operator spans carried rows_pairs"


def test_rows_pairs_sum_to_rows_totals():
    """per-pair retention is consistent with the pre-existing running
    sums (below the retention cap they must agree exactly)"""
    import test_bucketing as TB
    from tpu_cypher import CypherSession

    with bucketing.force_mode("pow2"):
        g = CypherSession.tpu().create_graph_from_create_query(
            TB._create_query()
        )
        result = g.cypher(TB.CORPUS[4])
        result.records.collect()
        spans = _spans_with_pairs(result)
        assert spans
        for span in spans:
            pairs = span.attrs["rows_pairs"]
            if len(pairs) < span.ROWS_PAIRS_CAP:
                assert sum(p[0] for p in pairs) == span.attrs["rows_true"]
                assert sum(p[1] for p in pairs) == span.attrs["rows_padded"]


def test_sharded_span_pairs_match_local_prediction():
    """ISSUE 13: while a mesh is active, spans stamp the PER-SHARD
    (true, padded) pair alongside the global sums, and every local padded
    extent must equal the static prediction of the LOCAL true extent —
    the per-shard lattice invariant the zero-warm-recompile guarantee
    rests on (the same programs compile at any shard count)."""
    import jax
    import test_bucketing as TB
    from tpu_cypher import CypherSession
    from tpu_cypher.parallel.mesh import make_row_mesh, use_mesh

    mode = "pow2"
    nsh = 8
    with bucketing.force_mode(mode):
        mesh = make_row_mesh(jax.devices()[:nsh])
        with use_mesh(mesh):
            g = CypherSession.tpu().create_graph_from_create_query(
                TB._create_query()
            )
            checked = 0
            for q in TB.CORPUS:
                result = g.cypher(q)
                result.records.collect()
                for span in _spans_with_pairs(result):
                    pairs = span.attrs.get("shard_rows_pairs")
                    if not pairs:
                        continue
                    assert span.attrs["shards"] == nsh
                    for local_true, local_padded in pairs:
                        assert predict_padded(local_true, mode) == local_padded, (
                            f"span={span.name} local_true={local_true} "
                            f"local_padded={local_padded} "
                            f"predicted={predict_padded(local_true, mode)}\n"
                            f"query: {q}"
                        )
                        checked += 1
        assert checked >= 10, f"only {checked} sharded pairs observed"


# ---------------------------------------------------------------------------
# the facts artifact: --facts-out emits the schema the cost model consumes
# ---------------------------------------------------------------------------


def _facts(tmp_path):
    out = str(tmp_path / "facts.json")
    proc = subprocess.run(
        [sys.executable, "-m", "tpu_cypher.analysis", "--facts-out", out],
        capture_output=True,
        text=True,
        cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    with open(out) as f:
        return json.load(f)


def test_facts_artifact_schema(tmp_path):
    facts = _facts(tmp_path)
    assert facts["schema_version"] == shapes.FACTS_SCHEMA_VERSION == 1
    assert set(facts) == {
        "schema_version", "lattice", "operators", "sites", "summary",
    }
    lattice = facts["lattice"]
    assert lattice["floor"] == 32
    assert set(lattice["modes"]) == {"off", "pow2", "1.25"}


def test_facts_per_operator_formulas(tmp_path):
    facts = _facts(tmp_path)
    ops = {o["op"]: o for o in facts["operators"]}
    assert len(ops) >= 20
    for o in ops.values():
        assert o["padded_shape"], o
        assert o["class"], o
    # the formulas a cost model needs first: the sized gathers
    assert "size" in ops["jnp.nonzero"]["padded_shape"]
    assert "total_repeat_length" in ops["jnp.repeat"]["padded_shape"]


def test_facts_sites_and_summary(tmp_path):
    facts = _facts(tmp_path)
    sites = facts["sites"]
    assert sites, "engine sweep produced no fact sites"
    for s in sites:
        assert set(s) >= {"path", "line", "op", "args", "verdict"}
        assert s["verdict"] in ("bounded", "unbounded", "unknown")
        assert not os.path.isabs(s["path"])
    summary = facts["summary"]
    assert set(summary) == {
        "facts_emitted", "data_dependent_sites", "bucketed_sites",
    }
    assert summary["facts_emitted"] == len(sites) + len(facts["operators"])
    assert summary["bucketed_sites"] > 0
    # every residual unbounded site is a DECLARED exact-size boundary: its
    # line carries an allow[pad-invariant] with a reason
    engine = analysis.check_engine()
    declared = {
        (e["path"], e["line"])
        for e in engine.suppression_entries
        if "pad-invariant" in e["rules"]
    }
    for s in sites:
        if s["verdict"] == "unbounded":
            # the allow comment sits on the site's own line or the one above
            covered = {(s["path"], s["line"]), (s["path"], s["line"] - 1)}
            assert covered & declared, (
                f"undeclared unbounded site {s['path']}:{s['line']}"
            )


def test_engine_shape_summary_never_raises():
    """the facts summary over the engine"""
    s = shapes.engine_shape_summary()
    assert set(s) >= {
        "facts_emitted", "data_dependent_sites", "bucketed_sites",
    }
    assert s["facts_emitted"] > 0
    assert "error" not in s


# ---------------------------------------------------------------------------
# the rules fire at exactly their seeded lines
# ---------------------------------------------------------------------------

EXPECTED_LINES = {
    ("shape_stability", "shape-stability"): [12, 18, 29, 35],
    ("pad_mask", "pad-mask-discipline"): [11, 18, 25],
    ("bucket_cardinality", "bucket-cardinality"): [21, 27],
    # ISSUE 13: the rules must look THROUGH shard_map factories and judge
    # the per-shard kernel bodies (the sharded tiers' compile boundary)
    ("shard_map", "pad-mask-discipline"): [19, 30],
    ("shard_map", "shape-stability"): [40],
    # the factorized run layout (backend/tpu/factorized.py): the rules
    # classify run-count prefixes, sentinel-masked cumsums, and the
    # mixed-radix decode extent like any other bucketed materialize
    ("factorized", "shape-stability"): [11],
    ("factorized", "pad-mask-discipline"): [21, 28],
}


@pytest.mark.parametrize(
    "fixture,rule_id", sorted(EXPECTED_LINES), ids=lambda v: str(v)
)
def test_shape_rule_findings_pinned_exactly(fixture, rule_id):
    report = analysis.run_paths(
        [os.path.join(FIXTURES, fixture, "bad")], rules=[rule_id]
    )
    lines = sorted(f.line for f in report.blocking if f.rule == rule_id)
    assert lines == EXPECTED_LINES[(fixture, rule_id)], report.render_text()
    clean = analysis.run_paths(
        [os.path.join(FIXTURES, fixture, "clean")], rules=[rule_id]
    )
    assert clean.clean, clean.render_text()


def test_shape_rules_have_distinct_messages():
    for (fixture, rule_id), _ in sorted(EXPECTED_LINES.items()):
        report = analysis.run_paths(
            [os.path.join(FIXTURES, fixture, "bad")], rules=[rule_id]
        )
        for f in report.blocking:
            assert f.message and rule_id != f.message
            assert f.path.endswith("mat.py")

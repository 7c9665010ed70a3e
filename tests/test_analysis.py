"""The engine-aware lint framework (ISSUE 5): rule registry, fixture
corpus, suppression and baseline semantics, CLI, and the tier-1 gate that
keeps the engine lint-clean.

Every rule must (a) fire on its known-bad fixture and (b) stay silent on
its known-clean fixture — the corpus under ``tests/lint_fixtures/``
mirrors the path scoping the rules use (``backend/tpu/``, ``pallas/``,
``utils/config.py``), so the fixtures exercise the same code paths the
engine run does.
"""

import json
import os
import subprocess
import sys

import pytest

from tpu_cypher import analysis
from tpu_cypher.analysis import baseline as baseline_mod
from tpu_cypher.analysis.core import FileContext
from tpu_cypher.analysis.rules import ALL_RULES, RULES_BY_ID
from tpu_cypher.utils import config

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "lint_fixtures")
REPO = os.path.dirname(HERE)

# rule id -> fixture directory name
RULE_FIXTURES = {
    "host-sync": "host_sync",
    "recompile-hazard": "recompile",
    "pad-invariant": "pad_invariant",
    "env-var-registry": "env_registry",
    "exception-hygiene": "exception_hygiene",
    "obs-emission": "obs_emission",
    "async-blocking": "async_blocking",
    "contextvar-discipline": "contextvar_discipline",
    "shared-state-race": "shared_state_race",
    "shape-stability": "shape_stability",
    "pad-mask-discipline": "pad_mask",
    "bucket-cardinality": "bucket_cardinality",
}

SHAPE_RULES = ("shape-stability", "pad-mask-discipline", "bucket-cardinality")


def _run_fixture(rule_id: str, which: str):
    path = os.path.join(FIXTURES, RULE_FIXTURES[rule_id], which)
    assert os.path.isdir(path), f"missing fixture corpus: {path}"
    return analysis.run_paths([path], rules=[rule_id])


# ---------------------------------------------------------------------------
# registry shape
# ---------------------------------------------------------------------------


def test_rule_registry_shape():
    ids = [r.id for r in ALL_RULES]
    assert len(ids) == len(set(ids)), "duplicate rule ids"
    assert set(ids) == set(RULE_FIXTURES), (
        "every rule needs a fixture dir (and vice versa)"
    )
    for r in ALL_RULES:
        assert r.id and r.title and r.rationale, r


# ---------------------------------------------------------------------------
# fixture corpus: every rule fires on bad, stays silent on clean
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rule_id", sorted(RULE_FIXTURES))
def test_rule_fires_on_known_bad(rule_id):
    report = _run_fixture(rule_id, "bad")
    hits = [f for f in report.blocking if f.rule == rule_id]
    assert hits, f"{rule_id} produced no findings on its bad fixture"


@pytest.mark.parametrize("rule_id", sorted(RULE_FIXTURES))
def test_rule_silent_on_known_clean(rule_id):
    report = _run_fixture(rule_id, "clean")
    hits = [f for f in report.blocking if f.rule == rule_id]
    assert not hits, (
        f"{rule_id} false-positives on its clean fixture:\n"
        + "\n".join(f"{f.location()}: {f.message}" for f in hits)
    )


def test_bad_fixture_findings_carry_locations():
    report = _run_fixture("host-sync", "bad")
    for f in report.blocking:
        assert f.path.endswith(".py") and f.line >= 1


# ---------------------------------------------------------------------------
# interprocedural host-sync: the cross-module syncs the file-local rule
# (PR 5) provably missed
# ---------------------------------------------------------------------------


def test_host_sync_interprocedural_cross_module():
    """interproc.py has NO device-prefixed call in-file: every sync there
    classifies as device-valued only through the cross-module
    return-summary taint (1-deep, 2-deep, and .item() on a helper value)."""
    report = _run_fixture("host-sync", "bad")
    interproc = sorted(
        f.line for f in report.blocking if f.path.endswith("interproc.py")
    )
    assert len(interproc) == 3, report.render_text()


def test_host_sync_file_local_fixtures_unchanged():
    """regression: the PR-5 file-local corpus (sync.py, byte-unchanged)
    still yields exactly its four findings under the semantic rule."""
    report = _run_fixture("host-sync", "bad")
    local = [f for f in report.blocking if f.path.endswith("/sync.py")]
    assert len(local) == 4, report.render_text()


def test_async_blocking_reports_transitive_chain():
    report = _run_fixture("async-blocking", "bad")
    chained = [f for f in report.blocking if "->" in f.message]
    assert chained, "the 2-deep helper chain must be named in the message"
    assert any("time.sleep" in f.message for f in chained)


def test_contextvar_discipline_resolves_imported_vars():
    """uses.py only IMPORTS the ContextVar — flagging its set() requires
    cross-module resolution of the receiver."""
    report = _run_fixture("contextvar-discipline", "bad")
    assert any(f.path.endswith("/uses.py") for f in report.blocking)


# ---------------------------------------------------------------------------
# suppression semantics
# ---------------------------------------------------------------------------

_VIOLATION = (
    "import jax.numpy as jnp\n"
    "\n"
    "\n"
    "def unguarded(mask):\n"
    "    return int(jnp.sum(mask))\n"
)


def _write_tpu_file(tmp_path, body, name="sync.py"):
    d = tmp_path / "backend" / "tpu"
    d.mkdir(parents=True, exist_ok=True)
    p = d / name
    p.write_text(body)
    return str(tmp_path)


def test_suppression_with_reason_silences(tmp_path):
    body = _VIOLATION.replace(
        "    return int(jnp.sum(mask))",
        "    # tpulint: allow[host-sync] reason=fixture proves suppression\n"
        "    return int(jnp.sum(mask))",
    )
    root = _write_tpu_file(tmp_path, body)
    report = analysis.run_paths([root], rules=["host-sync"])
    assert report.clean
    assert len(report.suppressed) == 1
    reason = report.suppress_reasons[report.suppressed[0]]
    assert reason == "fixture proves suppression"


def test_suppression_same_line_form(tmp_path):
    body = _VIOLATION.replace(
        "    return int(jnp.sum(mask))",
        "    return int(jnp.sum(mask))  "
        "# tpulint: allow[host-sync] reason=same-line form",
    )
    root = _write_tpu_file(tmp_path, body)
    report = analysis.run_paths([root], rules=["host-sync"])
    assert report.clean and len(report.suppressed) == 1


def test_suppression_without_reason_is_a_finding(tmp_path):
    body = _VIOLATION.replace(
        "    return int(jnp.sum(mask))",
        "    # tpulint: allow[host-sync]\n"
        "    return int(jnp.sum(mask))",
    )
    root = _write_tpu_file(tmp_path, body)
    report = analysis.run_paths([root], rules=["host-sync"])
    assert not report.clean
    rules = {f.rule for f in report.blocking}
    # the reason-less allow is itself a finding AND does not suppress
    assert rules == {"suppression", "host-sync"}


def test_suppression_wrong_rule_does_not_silence(tmp_path):
    body = _VIOLATION.replace(
        "    return int(jnp.sum(mask))",
        "    # tpulint: allow[pad-invariant] reason=names the wrong rule\n"
        "    return int(jnp.sum(mask))",
    )
    root = _write_tpu_file(tmp_path, body)
    report = analysis.run_paths([root], rules=["host-sync"])
    assert [f.rule for f in report.blocking] == ["host-sync"]


def test_malformed_tpulint_comment_is_a_finding(tmp_path):
    body = _VIOLATION + "# tpulint: alow[host-sync] reason=typo\n"
    root = _write_tpu_file(tmp_path, body)
    report = analysis.run_paths([root], rules=["host-sync"])
    assert "suppression" in {f.rule for f in report.blocking}


def test_stale_suppression_is_a_finding(tmp_path):
    """an allow whose rule no longer fires on its line is itself reported
    — the inventory stays honest as rules get smarter"""
    body = (
        "def fine(x):\n"
        "    # tpulint: allow[host-sync] reason=site was fixed long ago\n"
        "    return x + 1\n"
    )
    root = _write_tpu_file(tmp_path, body)
    report = analysis.run_paths([root], rules=["host-sync"])
    assert [f.rule for f in report.blocking] == ["suppression"]
    assert "stale" in report.blocking[0].message


def test_stale_detection_skips_inactive_rules(tmp_path):
    """an allow naming a rule OUTSIDE the active set is never judged stale
    — a restricted run cannot know whether that rule still fires there"""
    body = (
        "def fine(x):\n"
        "    # tpulint: allow[pad-invariant] reason=judged when pad runs\n"
        "    return x + 1\n"
    )
    root = _write_tpu_file(tmp_path, body)
    report = analysis.run_paths([root], rules=["host-sync"])
    assert report.clean


def test_fired_suppression_is_not_stale(tmp_path):
    body = _VIOLATION.replace(
        "    return int(jnp.sum(mask))",
        "    # tpulint: allow[host-sync] reason=fixture proves suppression\n"
        "    return int(jnp.sum(mask))",
    )
    root = _write_tpu_file(tmp_path, body)
    report = analysis.run_paths([root], rules=["host-sync"])
    assert report.clean and len(report.suppressed) == 1
    [entry] = report.suppression_entries
    assert entry["active"] is True and entry["rules"] == ["host-sync"]


# ---------------------------------------------------------------------------
# baseline semantics
# ---------------------------------------------------------------------------


def test_baseline_grandfathers_exact_findings(tmp_path):
    root = _write_tpu_file(tmp_path, _VIOLATION)
    report = analysis.run_paths([root], rules=["host-sync"])
    assert len(report.blocking) == 1
    base_file = str(tmp_path / "baseline.json")
    baseline_mod.save(base_file, report.blocking)

    again = analysis.run_paths(
        [root], rules=["host-sync"], baseline_path=base_file
    )
    assert again.clean
    assert len(again.baselined) == 1


def test_baseline_does_not_cover_new_identical_finding(tmp_path):
    root = _write_tpu_file(tmp_path, _VIOLATION)
    report = analysis.run_paths([root], rules=["host-sync"])
    base_file = str(tmp_path / "baseline.json")
    baseline_mod.save(base_file, report.blocking)

    # a SECOND identical violation in the same file: multiplicity matters
    doubled = _VIOLATION + (
        "\n\ndef unguarded2(mask):\n    return int(jnp.sum(mask))\n"
    )
    root = _write_tpu_file(tmp_path, doubled)
    again = analysis.run_paths(
        [root], rules=["host-sync"], baseline_path=base_file
    )
    assert len(again.baselined) == 1
    assert len(again.blocking) == 1


def test_missing_baseline_file_is_empty(tmp_path):
    root = _write_tpu_file(tmp_path, _VIOLATION)
    report = analysis.run_paths(
        [root],
        rules=["host-sync"],
        baseline_path=str(tmp_path / "nope.json"),
    )
    assert len(report.blocking) == 1


def test_malformed_baseline_raises(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text("[]")
    with pytest.raises(ValueError):
        baseline_mod.load(str(bad))


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "tpu_cypher.analysis", *args],
        capture_output=True,
        text=True,
        cwd=REPO,
    )


def test_cli_bad_fixture_exits_1_json():
    proc = _cli(
        os.path.join(FIXTURES, "host_sync", "bad"),
        "--format",
        "json",
        "--baseline",
        "",
        "--rules",
        "host-sync",
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["clean"] is False
    assert all(f["rule"] == "host-sync" for f in payload["findings"])


def test_cli_clean_fixture_exits_0():
    proc = _cli(
        os.path.join(FIXTURES, "host_sync", "clean"),
        "--baseline",
        "",
        "--rules",
        "host-sync",
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_list_rules():
    proc = _cli("--list-rules")
    assert proc.returncode == 0
    for rid in RULE_FIXTURES:
        assert rid in proc.stdout


def test_cli_unknown_rule_exits_2():
    proc = _cli("--rules", "not-a-rule")
    assert proc.returncode == 2


def test_cli_write_baseline_ratchet(tmp_path):
    base = str(tmp_path / "base.json")
    bad = os.path.join(FIXTURES, "host_sync", "bad")
    proc = _cli(bad, "--rules", "host-sync", "--baseline", base, "--write-baseline")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    # with the written baseline the same tree is green
    proc = _cli(bad, "--rules", "host-sync", "--baseline", base)
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# shared-pass internals
# ---------------------------------------------------------------------------


def test_file_context_scope_resolution():
    src = (
        "import jax.numpy as jnp\n"
        "def f(x):\n"
        "    a = jnp.sum(x)\n"
        "    return a\n"
    )
    ctx = FileContext("mem.py", "mem.py", src)
    fn = ctx.functions[0]
    assert ctx.enclosing_function(ctx.calls[0]) is fn
    assert len(ctx.assignments(fn, "a")) == 1
    assert ctx.param_names(fn) == ["x"]


def test_unparsable_file_is_a_parse_finding(tmp_path):
    p = tmp_path / "broken.py"
    p.write_text("def broken(:\n")
    report = analysis.run_paths([str(tmp_path)])
    assert [f.rule for f in report.blocking] == ["parse"]


# ---------------------------------------------------------------------------
# the typed config registry (env-var-registry's other half)
# ---------------------------------------------------------------------------


def test_config_registry_enumerates_engine_surface():
    opts = config.options()
    assert set(opts) >= {
        "TPU_CYPHER_PRINT_TIMINGS",
        "TPU_CYPHER_BUCKET",
        "TPU_CYPHER_MEM_BUDGET",
        "TPU_CYPHER_LADDER",
        "TPU_CYPHER_CHUNK_ROWS",
        "TPU_CYPHER_QUERY_DEADLINE_S",
        "TPU_CYPHER_FAULTS",
        "TPU_CYPHER_PALLAS",
        "TPU_CYPHER_MXU_DENSE",
        "TPU_CYPHER_BROADCAST_LIMIT",
        "TPU_CYPHER_ISLAND_WARN_ROWS",
        "TPU_CYPHER_METRICS_FILE",
        "TPU_CYPHER_PROFILE_DIR",
    }
    for name, opt in opts.items():
        assert opt.name == name


def test_print_timings_is_one_shared_declaration():
    """The PR-5 satellite: the TPU_CYPHER_PRINT_TIMINGS read in
    obs.metrics and the one in utils.config are the SAME object, so an
    override through either path is seen by both."""
    from tpu_cypher.obs import metrics as OM

    assert OM.PRINT_TIMINGS is config.PRINT_TIMINGS
    config.PRINT_TIMINGS.set(True)
    try:
        assert OM.PRINT_TIMINGS.get() is True
    finally:
        config.PRINT_TIMINGS.reset()


def test_scattered_module_options_alias_the_registry():
    from tpu_cypher.backend.tpu import bucketing
    from tpu_cypher.backend.tpu.pallas import dispatch
    from tpu_cypher.runtime import guard

    assert bucketing.MODE is config.BUCKET_MODE
    assert bucketing.MEM_BUDGET is config.MEM_BUDGET
    assert dispatch.MODE is config.PALLAS_MODE
    assert guard.CHUNK_ROWS is config.CHUNK_ROWS
    assert guard.DEADLINE_S is config.DEADLINE_S
    assert guard.LADDER_MODE is config.LADDER_MODE


def test_declare_is_idempotent():
    a = config.declare("TPU_CYPHER_BUCKET", "off", str)
    assert a is config.BUCKET_MODE


# ---------------------------------------------------------------------------
# the tier-1 gate: the WHOLE engine lints clean with the committed
# (empty) baseline — new findings need a fix or an inline reason
# ---------------------------------------------------------------------------


def test_committed_baseline_is_empty():
    with open(os.path.join(REPO, "tpu_cypher", "analysis", "baseline.json")) as f:
        data = json.load(f)
    assert data["findings"] == [], (
        "the committed baseline must stay empty: fix findings or suppress "
        "them inline with a reason"
    )


def test_engine_lints_clean():
    report = analysis.check_engine()
    assert report.files_checked > 80, "engine sweep looks truncated"
    assert report.clean, (
        "tpu_cypher/ has unsuppressed lint findings — fix them or add "
        "'# tpulint: allow[rule] reason=...' where the site is deliberate:\n"
        + report.render_text()
    )
    # every suppression in the engine carries a non-trivial reason
    for f in report.suppressed:
        assert len(report.suppress_reasons[f]) >= 10, (
            f"suppression at {f.location()} has a throwaway reason"
        )
    # ... and every one still fires: none are stale, all are in the
    # inventory as active
    assert report.suppression_entries, "suppression inventory is empty"
    for entry in report.suppression_entries:
        assert entry["active"] is True, f"stale engine suppression: {entry}"


def test_serve_has_no_inline_suppressions():
    """The concurrency pack's first-run findings in serve/ were fixed
    structurally (blocking setup moved off the loop, ownership annotated)
    — not suppressed. Keep serve/ suppression-free."""
    serve = os.path.join(REPO, "tpu_cypher", "serve")
    for dirpath, _, fnames in os.walk(serve):
        for fname in fnames:
            if not fname.endswith(".py"):
                continue
            with open(os.path.join(dirpath, fname)) as f:
                assert "tpulint" not in f.read(), (
                    f"serve/{fname}: no inline suppressions in the serving "
                    "tier — fix the finding structurally"
                )


def test_cli_engine_wide_exits_0():
    """the tier-1 CLI gate: the analyzer exits 0 over the whole engine
    with the committed (empty) baseline"""
    proc = _cli()
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# analyzer performance surface: parse cache + --changed-only + bench field
# ---------------------------------------------------------------------------


def test_parse_cache_reuses_unchanged_files(tmp_path):
    from tpu_cypher.analysis import runner

    root = _write_tpu_file(tmp_path, _VIOLATION)
    p = os.path.join(root, "backend", "tpu", "sync.py")
    r1 = analysis.run_paths([root], rules=["host-sync"])
    ctx1 = runner._PARSE_CACHE[os.path.abspath(p)][1]
    r2 = analysis.run_paths([root], rules=["host-sync"])
    assert runner._PARSE_CACHE[os.path.abspath(p)][1] is ctx1
    assert len(r1.blocking) == len(r2.blocking) == 1
    # a rewrite (new mtime/size) invalidates the entry
    with open(p, "w") as f:
        f.write("x = 1\n")
    r3 = analysis.run_paths([root], rules=["host-sync"])
    assert r3.clean
    assert runner._PARSE_CACHE[os.path.abspath(p)][1] is not ctx1


def test_cli_changed_only_scopes_to_git_changes(tmp_path):
    """--changed-only restricts RULE execution to git-reported changes;
    a violation in a file git does not list (the tmp fixture lives outside
    the work tree) is out of scope and must not fail the run."""
    root = _write_tpu_file(tmp_path, _VIOLATION)
    proc = _cli(root, "--rules", "host-sync", "--baseline", "", "--changed-only")
    if proc.returncode == 2 and "git" in proc.stderr:
        pytest.skip("no git work tree available")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    # the same tree WITHOUT the flag does fail
    proc = _cli(root, "--rules", "host-sync", "--baseline", "")
    assert proc.returncode == 1


def test_json_output_carries_suppressions_inventory():
    proc = _cli("--format", "json")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    sup = payload["suppressions"]
    assert sup["schema_version"] == 1
    assert sup["entries"], "engine inventory should list its suppressions"
    for entry in sup["entries"]:
        assert set(entry) == {"path", "line", "rules", "reason", "active"}
        assert entry["active"] is True


def test_engine_lint_summary_reports_per_rule_counts():
    """the lint summary: per-rule counts, never raises"""
    from tpu_cypher.analysis import engine_lint_summary

    s = engine_lint_summary()
    assert s["clean"] is True
    assert s["findings_by_rule"] == {}
    assert s["files_checked"] > 80 and s["suppressed"] >= 1


# ---------------------------------------------------------------------------
# cross-process spawn lane (PR 11): multiprocessing.Process targets are
# lane roots just like Thread targets
# ---------------------------------------------------------------------------


def test_shared_state_race_process_spawn_lane():
    """A bound method handed to ``multiprocessing.Process(target=..)``
    drags ``self`` across the spawn boundary: a sync mutator of a
    ``shared-by: loop`` class reached that way must fire the rule
    (bad/spawn.py), while a module-level target and an async mutator
    stay silent (clean/spawn.py — covered by the generic clean test)."""
    report = _run_fixture("shared-state-race", "bad")
    spawn_hits = [
        f for f in report.blocking
        if f.rule == "shared-state-race" and f.path.endswith("spawn.py")
    ]
    assert spawn_hits, "Process(target=self.bump) did not register as a lane"
    assert any("bump" in f.message for f in spawn_hits), spawn_hits


# ---------------------------------------------------------------------------
# the shape pack (PR 12): tier-1 shape-clean gate, perf bound, cache
# surface, and the pre-commit hook
# ---------------------------------------------------------------------------


def test_engine_is_shape_clean():
    """the 3 shape rules alone find nothing in the engine with the
    committed (empty) baseline — compile-cache stability and pad-mask
    discipline are proven, not aspirational"""
    report = analysis.check_engine(rules=list(SHAPE_RULES))
    assert report.clean, report.render_text()
    # ...and none of that cleanliness is bought with suppressions: every
    # shape-rule true positive was fixed structurally
    for entry in report.suppression_entries:
        assert not set(entry["rules"]) & set(SHAPE_RULES), (
            f"shape rule suppressed at {entry['path']}:{entry['line']} — "
            "fix the site structurally instead"
        )


def test_full_engine_run_under_time_bound():
    """perf regression gate: all 12 rules (9 legacy + 3 shape, with the
    interprocedural shape fixpoint) cold over the whole engine. The
    standalone budget is 5s (measured ~4.1s; the analyzer CLI and bench
    hold that); inside the full tier-1 suite the same run measures
    ~1.7x slower from process load, so the gate asserts 10s — loose
    enough to ignore scheduler noise, tight enough to catch the
    quadratic-blowup class of regression (a missing memo/cache shows up
    as 10s+ immediately at 128 files x 1900 functions)."""
    import time

    from tpu_cypher.analysis import runner, shapes

    runner._PARSE_CACHE.clear()
    shapes._SUMMARY_CACHE.clear()
    t0 = time.monotonic()
    report = analysis.check_engine()
    elapsed = time.monotonic() - t0
    assert report.clean
    assert elapsed < 10.0, f"cold 12-rule engine run took {elapsed:.2f}s"


def test_report_surfaces_cache_stats():
    """parse-cache and shape-summary-cache hit counts ride on the report;
    a warm in-process rerun is all hits"""
    r1 = analysis.check_engine()
    assert set(r1.cache_stats) == {
        "parse_hits", "parse_misses", "summary_hits", "summary_misses",
    }
    r2 = analysis.check_engine()
    stats = r2.cache_stats
    assert stats["parse_misses"] == 0 and stats["parse_hits"] > 80
    assert stats["summary_hits"] == 1 and stats["summary_misses"] == 0


def test_json_output_carries_cache_stats():
    proc = _cli("--format", "json")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    caches = payload["caches"]
    assert set(caches) == {
        "parse_hits", "parse_misses", "summary_hits", "summary_misses",
    }
    # a fresh process starts cold: everything is a miss
    assert caches["parse_misses"] > 80 and caches["parse_hits"] == 0
    assert caches["summary_misses"] == 1 and caches["summary_hits"] == 0


def test_precommit_hook_runs_changed_only_lint():
    """scripts/precommit-lint exists, is executable, and drives the
    analyzer in --changed-only mode (the cheap pre-commit path)"""
    hook = os.path.join(REPO, "scripts", "precommit-lint")
    assert os.path.isfile(hook), "scripts/precommit-lint is missing"
    assert os.access(hook, os.X_OK), "scripts/precommit-lint not executable"
    with open(hook) as f:
        body = f.read()
    assert "tpu_cypher.analysis" in body and "--changed-only" in body

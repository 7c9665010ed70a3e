"""Engine-aware static analysis: machine-check the invariants the engine's
correctness rests on.

The execution model (PR 1-4) created invariants that no general-purpose
linter knows about: size-changing materializes must round through the
bucket lattice (docs/pad-invariants.md), device syncs must sit behind a
``fault_point`` so the ladder and the deadline see them, ``TPU_CYPHER_*``
configuration must flow through the typed registry in ``utils.config``,
broad excepts in the TPU backend must re-raise device faults, and every
kernel launch / counter emission must go through obs. Before this package
those invariants lived in ad-hoc AST walkers duplicated across three test
files — exactly the invariant-drift failure mode EmptyHeaded (arxiv
1503.02368) describes when one algebra is lowered through many specialized
code paths: the paths diverge silently until a query is wrong or slow.

This package is the real static-analysis pass:

* one parsed-AST + scope-resolution pass per file (``core.FileContext``),
  shared by every rule, so the whole engine lints in seconds;
* a rule registry (``rules.ALL_RULES``) with six engine-grounded rules —
  see ``docs/static-analysis.md`` for the rule table;
* inline suppressions ``# tpulint: allow[rule-id] reason=...`` with the
  reason MANDATORY (an allow without a reason is itself a finding);
* a committed baseline (``analysis/baseline.json``) for grandfathered
  findings — kept EMPTY: new debt needs an inline reason, not a baseline
  entry;
* a CLI: ``python -m tpu_cypher.analysis [--format text|json]
  [--baseline FILE] [paths...]`` — exit 0 only when every finding is
  fixed, suppressed-with-reason, or baselined.

The three legacy test walkers (test_obs / test_fault_ladder /
test_pallas_dispatch) are reimplemented as framework rules; the tests now
invoke the framework (``check_engine``) so test-time and lint-time enforce
the SAME predicate.
"""

from __future__ import annotations

from .core import FileContext, Finding, Rule
from .runner import (
    ENGINE_ROOT,
    check_engine,
    engine_lint_summary,
    run_paths,
)
from .rules import ALL_RULES

__all__ = [
    "ALL_RULES",
    "ENGINE_ROOT",
    "FileContext",
    "Finding",
    "Rule",
    "check_engine",
    "engine_lint_summary",
    "run_paths",
]

"""repro-lint: AST-based invariant checker for the repo's determinism,
replay and engine-parity contracts.

Every headline guarantee of this reproduction — bit-for-bit offline replay
of cap schedules, drain decisions and alerts; float-identical traces across
the event/batched/vector engines; ``[seed, k]`` prefix-stable
Monte-Carlo populations — rests on coding invariants.  This package
mechanizes them as lint rules so a violation is rejected before it can rot
a guarantee the equivalence tests only catch after the fact:

  RPL001  unseeded / wall-clock-seeded RNG outside tests/
  RPL002  wall-clock calls where only the injectable simulated clock is
          legal (src/repro/{core,serve,telemetry,obs,launch}, benchmarks)
  RPL003  json.dump(s) without allow_nan=False + sort_keys=True, and NaN /
          Inf literals bypassing the {"$float": ...} envelope
  RPL004  unordered-collection iteration (sets, os.listdir, glob) feeding
          emission or aggregation
  RPL005  engine-parity drift: config dataclass fields read by one engine
          family but not the other
  RPL006  artifact writers emitting format/version keys not declared in
          the central schema registry
  RPL007  bare float == comparisons in replay/equivalence paths
  RPL008  Watchdog-style classes taking a default wall clock instead of an
          injected one

Entry points: ``python -m repro lint`` and ``scripts/check_invariants.py``
(the CI hook).  See docs/analysis.md for the rule catalog, the baseline
workflow and the exit-code contract.
"""
from repro.analysis.baseline import (Baseline, BaselineEntry, load_baseline,
                                     update_baseline)
from repro.analysis.linter import Finding, LintResult, lint_paths, run_lint
from repro.analysis.report import render_json, render_text
from repro.analysis.rules import RULES
from repro.analysis.schema_registry import SCHEMAS, schema_version

__all__ = [
    "Baseline", "BaselineEntry", "Finding", "LintResult", "RULES",
    "SCHEMAS", "lint_paths", "load_baseline", "render_json", "render_text",
    "run_lint", "schema_version", "update_baseline",
]

"""tpuml-lint — the plugin static-analysis gate for this repo.

Grown from the six generic checks in the seed ``tools/lint.py`` into a
domain-aware analyzer ("Memory Safe Computations with XLA Compiler"
argues this class of defect belongs to static program analysis, not
post-hoc profiling; the reference build's analogue was
``-Xfatal-warnings`` + apache-rat). Four checker families ride one
stdlib-ast engine:

  - **generic**  — docstrings, unused imports, bare except, mutable
    defaults, ``import *`` (the seed checks, unchanged in spirit).
  - **jax**      — host-sync calls and Python-branch-on-traced-value
    inside jitted/segment functions; static args that vary per loop
    iteration (retrace bait).
  - **locks**    — the ``# guarded-by: <lock>`` convention: guarded
    attributes may only be touched under their lock.
  - **knobs**    — every ``TPUML_*`` knob reads through
    ``utils/envknobs``, is registered in ``envknobs.KNOBS``, is
    documented in ``docs/PARITY.md``, and is read by some swept file.
  - **drift**    — ``emit()`` callsites conform to
    ``events.py::SCHEMA``; metric names follow the dotted rule.

Suppression: ``# tpuml: noqa[rule-id]`` on the flagged line (bare
``# tpuml: noqa`` suppresses every rule there). Legacy findings live in
the committed ``tools/tpuml_lint/baseline.json``; ``--validate-baseline``
(the CI mode) fails on stale entries so the baseline can only shrink.

Run: ``python -m tools.tpuml_lint [--format json] [--validate-baseline]``.
"""

from __future__ import annotations

from pathlib import Path
from typing import List, Tuple

from tools.tpuml_lint import (  # noqa: F401 - re-exported submodules
    baseline,
    drift,
    generic,
    jax_hazards,
    knobs,
    locks,
)
from tools.tpuml_lint.engine import (  # noqa: F401
    ModuleContext,
    RepoContext,
    iter_python_files,
    lint_file,
    run_paths,
)
from tools.tpuml_lint.findings import RULES, Finding  # noqa: F401

#: Per-module checkers, in report order.
CHECKERS = (
    generic.check,
    jax_hazards.check,
    locks.check,
    knobs.check,
    drift.check,
)

#: Once-per-run repo-level checkers.
REPO_CHECKERS = (knobs.check_repo,)

REPO_ROOT = Path(__file__).resolve().parents[2]

#: The acceptance surface: every tree the CI gate sweeps.
DEFAULT_PATHS = (
    "spark_rapids_ml_tpu", "tests", "tools", "chip_smoke.py",
)

DEFAULT_BASELINE = Path(__file__).resolve().parent / "baseline.json"


def run(root: Path = REPO_ROOT, paths=None) -> Tuple[List[Finding], int]:
    """Lint ``paths`` (default: the full acceptance surface) under
    ``root``; returns (findings, files checked). Baseline NOT applied —
    callers split with :func:`baseline.apply`."""
    root = Path(root)
    targets = [
        root / p if not Path(p).is_absolute() else Path(p)
        for p in (paths or DEFAULT_PATHS)
    ]
    targets = [t for t in targets if t.exists()]
    return run_paths(root, targets, CHECKERS, REPO_CHECKERS)

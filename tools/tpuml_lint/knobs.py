"""Checker family (c): the ``TPUML_*`` environment-knob registry.

Four rules close the loop between code, registry, and docs:

  - ``knob-raw-environ``: reading a ``TPUML_*`` variable through
    ``os.environ`` / ``os.getenv`` instead of the ``utils/envknobs``
    accessors. Keys are resolved through module-level string constants
    (``FAULTS_ENV = "TPUML_FAULTS"``), and any ``*_ENV``-named constant
    read is treated as a knob read even when the value is imported from
    another module. Writes (``os.environ[X] = ...`` for subprocess
    launches) are allowed.
  - ``knob-unregistered``: a ``TPUML_*`` string literal (docstrings and
    prefix strings ending in ``_`` excluded) with no ``Knob`` entry in
    ``envknobs.KNOBS``.
  - ``knob-undocumented`` (repo-level): a registered knob missing from
    the knob tables in ``docs/PARITY.md``.
  - ``knob-unread`` (repo-level): a registered knob whose name no swept
    file outside ``tests/`` holds, as a literal or through a
    constructed name (``f"TPUML_PRECISION_{family}"`` reads every
    registered ``TPUML_PRECISION_*``). An option nothing reads is dead
    weight in the table and in the docs. Judged only when the sweep
    took in the registry itself: a run over one file says nothing of
    what the package reads.

``TPUML_TEST_*`` names are harness inputs, not runtime knobs, and are
exempt everywhere; ``utils/envknobs.py`` itself is exempt from the raw-
read rule (it IS the accessor layer).
"""

from __future__ import annotations

import ast
import re
from typing import List, Optional

from tools.tpuml_lint.engine import ModuleContext, RepoContext
from tools.tpuml_lint.findings import Finding

_KNOB_NAME = re.compile(r"^TPUML_[A-Z0-9]+(?:_[A-Z0-9]+)*$")
#: The constant head of a constructed name: f"TPUML_PRECISION_{family}".
_KNOB_PREFIX = re.compile(r"^TPUML_(?:[A-Z0-9]+_)+$")


def _is_test_knob(name: str) -> bool:
    return name.startswith("TPUML_TEST_")


def _environ_read_key(node: ast.Call) -> Optional[ast.AST]:
    """The key expression when ``node`` reads the environment:
    ``os.environ.get(k, ...)`` or ``os.getenv(k, ...)``."""
    f = node.func
    if (
        isinstance(f, ast.Attribute)
        and f.attr == "get"
        and isinstance(f.value, ast.Attribute)
        and f.value.attr == "environ"
        and isinstance(f.value.value, ast.Name)
        and f.value.value.id == "os"
    ):
        return node.args[0] if node.args else None
    if (
        isinstance(f, ast.Attribute)
        and f.attr == "getenv"
        and isinstance(f.value, ast.Name)
        and f.value.id == "os"
    ):
        return node.args[0] if node.args else None
    return None


def _env_constant_name(key: ast.AST) -> Optional[str]:
    """The ``*_ENV`` constant a key expression names, if any."""
    if isinstance(key, ast.Name) and key.id.endswith("_ENV"):
        return key.id
    if isinstance(key, ast.Attribute) and key.attr.endswith("_ENV"):
        return key.attr
    return None


def check(module: ModuleContext, repo: RepoContext) -> List[Finding]:
    findings: List[Finding] = []
    rel = module.rel
    is_accessor_layer = rel == RepoContext.ENVKNOBS_REL

    # --- raw environment reads ---
    if not is_accessor_layer:
        for node in ast.walk(module.tree):
            key = None
            if isinstance(node, ast.Call):
                key = _environ_read_key(node)
            elif (
                isinstance(node, ast.Subscript)
                and isinstance(node.ctx, ast.Load)
                and isinstance(node.value, ast.Attribute)
                and node.value.attr == "environ"
                and isinstance(node.value.value, ast.Name)
                and node.value.value.id == "os"
            ):
                key = node.slice
            if key is None:
                continue
            resolved = module.resolve_str(key)
            knob = None
            if resolved is not None:
                if resolved.startswith("TPUML_") and not _is_test_knob(resolved):
                    knob = resolved
            else:
                knob = _env_constant_name(key)
            if knob is not None:
                findings.append(Finding(
                    rel, node.lineno, node.col_offset, "knob-raw-environ",
                    f"raw os.environ read of {knob} — use the "
                    "utils/envknobs accessors (env_int/env_float/"
                    "env_str/env_choice)",
                ))

    # --- knob names this file holds: reads gathered for knob-unread
    # (the tests set knobs, they read none), unregistered literals ---
    if is_accessor_layer:
        repo.swept_registry = True
        return findings
    counts_as_read = not rel.startswith("tests/")
    for node in ast.walk(module.tree):
        if isinstance(node, ast.JoinedStr):
            head = node.values[0] if len(node.values) > 1 else None
            if (
                counts_as_read
                and isinstance(head, ast.Constant)
                and isinstance(head.value, str)
                and _KNOB_PREFIX.match(head.value)
            ):
                repo.knob_read_prefixes.add(head.value)
            continue
        if not (
            isinstance(node, ast.Constant)
            and isinstance(node.value, str)
            and id(node) not in module.docstring_nodes
        ):
            continue
        name = node.value
        if not _KNOB_NAME.match(name):
            continue
        if counts_as_read:
            repo.knob_reads.add(name)
        if repo.knobs is None or _is_test_knob(name) or name in repo.knobs:
            continue
        findings.append(Finding(
            rel, node.lineno, node.col_offset, "knob-unregistered",
            f"{name} has no Knob entry in envknobs.KNOBS — register "
            "it (and document it in docs/PARITY.md)",
        ))
    return findings


def check_repo(repo: RepoContext) -> List[Finding]:
    """Repo-level cross-checks of the registry: every registered knob
    appears in PARITY.md's knob tables, and (after a sweep that took in
    the registry) some swept file outside the tests reads it."""
    findings: List[Finding] = []
    if repo.knobs is None:
        return findings
    for name, line in sorted(repo.knobs.items()):
        if repo.parity_text is not None and name not in repo.parity_text:
            findings.append(Finding(
                RepoContext.ENVKNOBS_REL, line, 0, "knob-undocumented",
                f"registered knob {name} is missing from "
                f"{RepoContext.PARITY_REL}'s knob tables",
            ))
        if (
            repo.swept_registry
            and name not in repo.knob_reads
            and not any(name.startswith(p) for p in repo.knob_read_prefixes)
        ):
            findings.append(Finding(
                RepoContext.ENVKNOBS_REL, line, 0, "knob-unread",
                f"registered knob {name} is read by no swept file outside "
                "tests/ — delete the entry (and its docs/PARITY.md row) "
                "with the last code that read it",
            ))
    return findings

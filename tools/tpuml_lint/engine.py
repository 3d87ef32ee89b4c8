"""The analyzer engine: per-module context, repo-wide context, runner.

Checkers are plain functions ``check(module, repo) -> list[Finding]``
registered in :data:`CHECKERS` (tpuml_lint/__init__.py). The engine owns
everything they share:

  - parsing + parent links (``ModuleContext.parent_of``),
  - module-level string constants (``FAULTS_ENV = "TPUML_FAULTS"`` style),
  - import bindings (who is ``emit`` in THIS module?),
  - docstring positions (so string-literal scans skip prose),
  - ``# tpuml: noqa[rule-a,rule-b]`` suppression, applied AFTER checkers
    run so a suppressed line suppresses every rule named on it,
  - repo-wide facts parsed once: the ``envknobs.KNOBS`` table, the
    ``events.py::SCHEMA`` record types, and the PARITY.md knob docs.

Everything is stdlib ``ast`` — the image ships no ruff/mypy/pyflakes
(the reference enforced quality with ``-Xfatal-warnings`` + apache-rat;
this is that gate, grown domain-aware).
"""

from __future__ import annotations

import ast
import re
from pathlib import Path
from typing import Dict, List, Optional, Set, Tuple

from tools.tpuml_lint.findings import Finding

_NOQA_RE = re.compile(r"#\s*tpuml:\s*noqa(?:\[([a-z0-9_,\s-]*)\])?")

#: Directories never linted (vendored stubs model a foreign API surface).
SKIP_DIR_NAMES = {"pyspark_stub", "__pycache__", ".git"}


class ModuleContext:
    """One parsed file + the derived maps every checker needs."""

    def __init__(self, root: Path, path: Path, source: str,
                 tree: Optional[ast.Module], syntax_error=None):
        self.root = root
        self.path = path
        try:
            self.rel = path.relative_to(root).as_posix()
        except ValueError:  # outside the root (temp fixtures, abs targets)
            self.rel = path.as_posix()
        self.source = source
        self.lines = source.splitlines()
        self.tree = tree
        self.syntax_error = syntax_error
        self._parents: Dict[int, ast.AST] = {}
        self.constants: Dict[str, str] = {}
        self.import_bindings: Dict[str, str] = {}
        self.docstring_nodes: Set[int] = set()
        if tree is not None:
            self._index()

    # --- derived maps ---

    def _index(self) -> None:
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                self._parents[id(child)] = node
        # Module-level NAME = "literal" constants (lets the knob checker
        # resolve os.environ.get(FAULTS_ENV)).
        for stmt in self.tree.body:
            if (
                isinstance(stmt, ast.Assign)
                and len(stmt.targets) == 1
                and isinstance(stmt.targets[0], ast.Name)
                and isinstance(stmt.value, ast.Constant)
                and isinstance(stmt.value.value, str)
            ):
                self.constants[stmt.targets[0].id] = stmt.value.value
            elif (
                isinstance(stmt, ast.AnnAssign)
                and isinstance(stmt.target, ast.Name)
                and isinstance(stmt.value, ast.Constant)
                and isinstance(stmt.value.value, str)
            ):
                self.constants[stmt.target.id] = stmt.value.value
        # Import bindings: local name -> dotted origin.
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    local = a.asname or a.name.split(".")[0]
                    self.import_bindings[local] = a.name
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    if a.name == "*":
                        continue
                    local = a.asname or a.name
                    self.import_bindings[local] = f"{node.module}.{a.name}"
        # Docstring constants (module/class/function first-statement strings).
        scopes = [self.tree] + [
            n for n in ast.walk(self.tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef))
        ]
        for scope in scopes:
            body = getattr(scope, "body", [])
            if (
                body
                and isinstance(body[0], ast.Expr)
                and isinstance(body[0].value, ast.Constant)
                and isinstance(body[0].value.value, str)
            ):
                self.docstring_nodes.add(id(body[0].value))

    def parent_of(self, node: ast.AST) -> Optional[ast.AST]:
        return self._parents.get(id(node))

    def binds_to(self, local: str, *origins: str) -> bool:
        """True when ``local`` was imported from one of ``origins``
        (exact dotted-origin match)."""
        return self.import_bindings.get(local) in origins

    def resolve_str(self, node: ast.AST) -> Optional[str]:
        """The string a key expression holds: a literal, or a module-level
        constant Name."""
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return node.value
        if isinstance(node, ast.Name):
            return self.constants.get(node.id)
        return None

    # --- suppression ---

    def suppressed_rules(self, line: int) -> Optional[Set[str]]:
        """The rule ids a ``# tpuml: noqa[...]`` comment on ``line``
        names; an empty set means "all rules"; None means no comment."""
        if not (1 <= line <= len(self.lines)):
            return None
        m = _NOQA_RE.search(self.lines[line - 1])
        if m is None:
            return None
        if m.group(1) is None:
            return set()
        return {r.strip() for r in m.group(1).split(",") if r.strip()}


def iter_scopes(tree: ast.Module):
    """The module's analyzable scopes: ``(class_name, fn)`` for every
    method defined directly in a top-level class body, and ``(None, fn)``
    for every top-level function. This is the node set interprocedural
    checkers (the guarded-by lock pass) build their per-class/module
    call graphs over; nested defs stay part of their enclosing scope."""
    for stmt in tree.body:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield None, stmt
        elif isinstance(stmt, ast.ClassDef):
            for sub in stmt.body:
                if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    yield stmt.name, sub


def call_target(node: ast.Call) -> Optional[Tuple[str, str]]:
    """Resolve an intra-module call edge: ``self.helper(...)`` ->
    ``("self", "helper")``, ``helper(...)`` -> ``("local", "helper")``,
    anything else (imported names resolve elsewhere, attribute chains
    cross object boundaries) -> None."""
    fn = node.func
    if (
        isinstance(fn, ast.Attribute)
        and isinstance(fn.value, ast.Name)
        and fn.value.id == "self"
    ):
        return ("self", fn.attr)
    if isinstance(fn, ast.Name):
        return ("local", fn.id)
    return None


class RepoContext:
    """Facts parsed once per run from the repo's own source of truth."""

    ENVKNOBS_REL = "spark_rapids_ml_tpu/utils/envknobs.py"
    EVENTS_REL = "spark_rapids_ml_tpu/observability/events.py"
    PARITY_REL = "docs/PARITY.md"

    def __init__(self, root: Path):
        self.root = Path(root)
        self.knobs: Optional[Dict[str, int]] = self._parse_knobs()
        self.event_schema: Optional[Dict[str, Set[str]]] = self._parse_schema()
        parity = self.root / self.PARITY_REL
        self.parity_text: Optional[str] = (
            parity.read_text() if parity.is_file() else None
        )
        # Gathered by the knob checker as the sweep goes (knob-unread):
        # the knob names and constructed-name prefixes the swept files
        # hold, and whether the sweep took in the registry itself.
        self.knob_reads: Set[str] = set()
        self.knob_read_prefixes: Set[str] = set()
        self.swept_registry = False

    def _parse_knobs(self) -> Optional[Dict[str, int]]:
        """{knob name: declaration line} from the ``KNOBS`` table —
        textual AST parse, so linting never imports the package."""
        path = self.root / self.ENVKNOBS_REL
        if not path.is_file():
            return None
        try:
            tree = ast.parse(path.read_text())
        except SyntaxError:
            return None
        for node in ast.walk(tree):
            targets = (
                node.targets
                if isinstance(node, ast.Assign)
                else [node.target] if isinstance(node, ast.AnnAssign) else []
            )
            if not any(
                isinstance(t, ast.Name) and t.id == "KNOBS" for t in targets
            ):
                continue
            out: Dict[str, int] = {}
            for call in ast.walk(node.value):
                if (
                    isinstance(call, ast.Call)
                    and isinstance(call.func, ast.Name)
                    and call.func.id == "Knob"
                    and call.args
                    and isinstance(call.args[0], ast.Constant)
                    and isinstance(call.args[0].value, str)
                ):
                    out[call.args[0].value] = call.lineno
            return out
        return None

    def _parse_schema(self) -> Optional[Dict[str, Set[str]]]:
        """{event type: required fields} from ``events.py::SCHEMA``."""
        path = self.root / self.EVENTS_REL
        if not path.is_file():
            return None
        try:
            tree = ast.parse(path.read_text())
        except SyntaxError:
            return None
        for node in ast.walk(tree):
            targets = (
                node.targets
                if isinstance(node, ast.Assign)
                else [node.target] if isinstance(node, ast.AnnAssign) else []
            )
            if not any(
                isinstance(t, ast.Name) and t.id == "SCHEMA" for t in targets
            ):
                continue
            value = node.value
            if not isinstance(value, ast.Dict):
                return None
            out: Dict[str, Set[str]] = {}
            for k, v in zip(value.keys, value.values):
                if not (isinstance(k, ast.Constant) and isinstance(k.value, str)):
                    continue
                fields: Set[str] = set()
                for c in ast.walk(v):
                    if isinstance(c, ast.Constant) and isinstance(c.value, str):
                        fields.add(c.value)
                out[k.value] = fields
            return out
        return None


def iter_python_files(paths: List[Path]) -> List[Path]:
    files: List[Path] = []
    for p in paths:
        if p.is_dir():
            for f in sorted(p.rglob("*.py")):
                if not any(part in SKIP_DIR_NAMES for part in f.parts):
                    files.append(f)
        elif p.suffix == ".py":
            files.append(p)
    return files


def lint_file(root: Path, path: Path, checkers) -> List[Finding]:
    """All findings for one file, suppression already applied."""
    source = path.read_text()
    try:
        tree = ast.parse(source, filename=str(path))
        module = ModuleContext(root, path, source, tree)
    except SyntaxError as e:
        module = ModuleContext(root, path, source, None, syntax_error=e)
    repo = RepoContext(root)
    return _run_checkers(module, repo, checkers)


def _run_checkers(module: ModuleContext, repo: RepoContext, checkers) -> List[Finding]:
    if module.syntax_error is not None:
        e = module.syntax_error
        return [
            Finding(module.rel, e.lineno or 1, e.offset or 0, "syntax-error",
                    f"syntax error: {e.msg}")
        ]
    findings: List[Finding] = []
    for check in checkers:
        findings.extend(check(module, repo))
    kept = []
    for f in findings:
        rules = module.suppressed_rules(f.line)
        if rules is not None and (not rules or f.rule in rules):
            continue
        kept.append(f)
    kept.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return kept


def run_paths(root: Path, paths: List[Path], checkers,
              repo_checkers=()) -> Tuple[List[Finding], int]:
    """Lint every file under ``paths``; returns (findings, file count).
    ``repo_checkers`` run once against the :class:`RepoContext` (e.g.
    the knob-undocumented docs cross-check)."""
    root = Path(root)
    repo = RepoContext(root)
    findings: List[Finding] = []
    files = iter_python_files([Path(p) for p in paths])
    for path in files:
        source = path.read_text()
        try:
            tree = ast.parse(source, filename=str(path))
            module = ModuleContext(root, path, source, tree)
        except SyntaxError as e:
            module = ModuleContext(root, path, source, None, syntax_error=e)
        findings.extend(_run_checkers(module, repo, checkers))
    for check in repo_checkers:
        findings.extend(check(repo))
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return findings, len(files)

"""Build quality gates — the ``-Xfatal-warnings`` / apache-rat analogue
(pom.xml:194,361-397). The image ships no ruff/mypy, so the gate is the
``tools/tpuml_lint`` plugin analyzer (generic hygiene + the four domain
checker families: JAX hazards, lock discipline, knob registry,
observability drift) plus an import sweep of every module (which catches
module-scope NameErrors, bad decorators, and circular imports the way a
compiler pass would). The analyzer's own unit suite (rule fixtures,
suppression, baseline round-trips) lives in tests/test_tpuml_lint.py."""

import importlib
import pkgutil
import re
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))


def test_lint_clean():
    """The live tree is clean modulo the committed baseline — the same
    contract CI enforces via `python -m tools.tpuml_lint
    --validate-baseline` (stale baseline entries fail too, so the
    baseline can only shrink)."""
    import tools.tpuml_lint as tl
    from tools.tpuml_lint import baseline as bl

    findings, n_files = tl.run()
    assert n_files > 100  # the sweep really covered the tree
    entries = bl.load(tl.DEFAULT_BASELINE)
    new, _, stale = bl.apply(findings, entries)
    assert not new, "\n".join(f.render() for f in new)
    assert not stale, f"stale baseline entries: {stale}"


def test_every_module_imports():
    """Import every package module (catches decorator-time NameErrors etc.
    that only explode at import). spark.adapter self-gates on pyspark."""
    import spark_rapids_ml_tpu

    failures = []
    for mod in pkgutil.walk_packages(
        spark_rapids_ml_tpu.__path__, prefix="spark_rapids_ml_tpu."
    ):
        if mod.name.endswith("libtpuml_host"):
            continue  # ctypes shared library, not a Python extension module
        try:
            importlib.import_module(mod.name)
        except Exception as e:  # noqa: BLE001 - we want the full report
            failures.append(f"{mod.name}: {type(e).__name__}: {e}")
    assert not failures, "\n".join(failures)


def test_lint_catches_planted_defects(tmp_path):
    """The gate itself must work: plant each generic defect class and
    assert the analyzer flags it (the domain families have their own
    seeded-violation suite in tests/test_tpuml_lint.py)."""
    from tools.tpuml_lint import CHECKERS, lint_file

    cases = {
        "unused import": "'''doc'''\nimport os\n",
        "bare except": "'''doc'''\ntry:\n    pass\nexcept:\n    pass\n",
        "mutable default": "'''doc'''\ndef f(a=[]):\n    return a\n",
        "import *": "'''doc'''\nfrom os.path import *\n",
        "missing module docstring": "x = 1\n",
        "syntax error": "def broken(:\n",
    }
    for name, src in cases.items():
        f = tmp_path / "planted.py"
        f.write_text(src)
        assert lint_file(tmp_path, f, CHECKERS), f"lint missed: {name}"
    clean = tmp_path / "clean.py"
    clean.write_text("'''doc'''\nimport os\n\nprint(os.sep)\n")
    assert not lint_file(tmp_path, clean, CHECKERS)


def test_legacy_entry_point_still_works(tmp_path):
    """``python tools/lint.py`` (the seed entry) delegates to the
    package and keeps its exit-code contract."""
    import subprocess

    bad = tmp_path / "bad.py"
    bad.write_text("x = 1\n")  # missing docstring
    r = subprocess.run(
        [sys.executable, str(REPO / "tools" / "lint.py"),
         "--no-baseline", str(bad)],
        capture_output=True, text=True, cwd=str(REPO),
    )
    assert r.returncode == 1, r.stdout + r.stderr
    assert "missing-docstring" in r.stdout


#: Directories whose files the documents cite by path. `benchmarks` is
#: listed so that a citation of the retired stack (PR 32) fails: it is
#: no directory of this tree.
_DOC_PATH_PREFIXES = tuple(
    f"{directory}/" for directory in (
        "spark_rapids_ml_tpu", "tools", "tests", "perfbench", "benchmarks",
        "docs", "native",
    )
)


def _cited_paths(text):
    """The back-quoted paths a document cites: a token under one of the
    tree's directories, or a bare ``*.py`` name. A ``::name`` suffix and
    a ``:line`` suffix are cut; commands (a space) and placeholders
    (``<``, ``*``, ``…``) are not paths."""
    text = re.sub(r"```.*?```", "", text, flags=re.S)  # fenced blocks
    for m in re.finditer(r"`([^`\n]+)`", text):
        token = m.group(1)
        if " " in token or any(c in token for c in "<*…"):
            continue
        token = re.sub(r":[\d,:–-]+$", "", token.split("::")[0])
        if token.startswith(_DOC_PATH_PREFIXES) or re.fullmatch(r"\w+\.py", token):
            yield token


@pytest.mark.parametrize(
    "doc",
    ["README.md", "docs/PARITY.md", "CONTRIBUTING.md",
     ".claude/skills/verify/SKILL.md"],
)
def test_documents_cite_files_that_exist(doc):
    """A document that names a file names one the tree has: the day a
    script goes, every sentence that pointed at it fails here. A path
    under a directory is looked up as written; a bare ``name.py`` is a
    top-level file or the short name of a module somewhere under the
    cited directories."""
    short_names = {
        f.name
        for prefix in _DOC_PATH_PREFIXES
        for f in (REPO / prefix).rglob("*.py")
    }
    missing = sorted({
        token for token in _cited_paths((REPO / doc).read_text())
        if not (
            (REPO / token).exists()
            or ("/" not in token and token in short_names)
        )
    })
    assert not missing, f"{doc} cites files that do not exist: {missing}"

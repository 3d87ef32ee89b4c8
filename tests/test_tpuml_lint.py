"""Unit suite for the tpuml-lint analyzer (tools/tpuml_lint/).

One true positive AND one clean negative per rule family (JAX hazards,
lock discipline, knob registry, observability drift), the
``# tpuml: noqa[rule]`` suppression contract, baseline round-trips
(including stale-entry detection — the ratchet), and the CLI exit-code
contract: non-zero on a seeded violation of EVERY family, zero on the
shipped tree (the acceptance criterion CI enforces).

The analyzer is pure stdlib-ast — no jax import anywhere in these tests,
so the whole suite runs in milliseconds.
"""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

import tools.tpuml_lint as tl  # noqa: E402
from tools.tpuml_lint import baseline as bl  # noqa: E402
from tools.tpuml_lint.findings import RULES, Finding  # noqa: E402


def lint_src(tmp_path, src, name="fixture.py", root=None):
    f = tmp_path / name
    f.write_text(textwrap.dedent(src))
    return tl.lint_file(root or tmp_path, f, tl.CHECKERS)


def rules_of(findings):
    return {f.rule for f in findings}


@pytest.fixture
def mini_repo(tmp_path):
    """A tiny repo with its own KNOBS table, event SCHEMA, and PARITY
    doc, so registry/docs rules are testable hermetically."""
    env = tmp_path / "spark_rapids_ml_tpu" / "utils"
    env.mkdir(parents=True)
    (env / "envknobs.py").write_text(textwrap.dedent('''
        """Mini knob registry."""
        KNOBS = {
            "TPUML_GOOD_KNOB": Knob("TPUML_GOOD_KNOB", "int", "t", "m"),
            "TPUML_ORPHAN_KNOB": Knob("TPUML_ORPHAN_KNOB", "int", "t", "m"),
        }
    '''))
    obs = tmp_path / "spark_rapids_ml_tpu" / "observability"
    obs.mkdir(parents=True)
    (obs / "events.py").write_text(textwrap.dedent('''
        """Mini schema."""
        SCHEMA = {
            "serving": frozenset({"action"}),
            "run": frozenset({"action", "kind", "label"}),
        }
    '''))
    docs = tmp_path / "docs"
    docs.mkdir()
    (docs / "PARITY.md").write_text(
        "# knobs\n\n| `TPUML_GOOD_KNOB` | good | - |\n"
    )
    return tmp_path


# --- family (a): JAX hazards -------------------------------------------


class TestJaxHazards:
    def test_host_sync_true_positives(self, tmp_path):
        findings = lint_src(tmp_path, '''
            """f"""
            import jax
            import numpy as np


            @jax.jit
            def bad(x):
                print("traced", x)
                y = np.asarray(x)
                z = float(x + 1)
                return y.item() + z
        ''')
        msgs = [f.message for f in findings if f.rule == "jax-host-sync"]
        assert len(msgs) == 4, findings
        assert any("print" in m for m in msgs)
        assert any("asarray" in m for m in msgs)
        assert any("float" in m for m in msgs)
        assert any(".item" in m for m in msgs)

    def test_traced_branch_and_clean_static(self, tmp_path):
        findings = lint_src(tmp_path, '''
            """f"""
            import jax
            from functools import partial


            @partial(jax.jit, static_argnames=("flag",))
            def f(x, flag):
                if flag:            # static: fine
                    return x
                if x.shape[0] > 4:  # shape: static under tracing, fine
                    return x + 1
                if x is None:       # identity: fine
                    return x
                if x > 0:           # traced: HAZARD
                    return -x
                return x
        ''')
        hits = [f for f in findings if f.rule == "jax-traced-branch"]
        assert len(hits) == 1 and "x" in hits[0].message

    def test_segment_functions_are_traced_regions(self, tmp_path):
        findings = lint_src(tmp_path, '''
            """f"""


            def _lloyd_segment(x, centers, max_iter: int):
                if max_iter > 3:  # int-annotated = static config: fine
                    pass
                print(x)          # HAZARD even without a jit decorator
                return centers
        ''')
        assert rules_of(findings) == {"jax-host-sync"}

    def test_static_loop_arg(self, tmp_path):
        findings = lint_src(tmp_path, '''
            """f"""
            import jax
            from functools import partial


            @partial(jax.jit, static_argnames=("k",))
            def topk(x, k):
                return x[:k]


            def sweep(xs):
                out = [topk(xs, k=8)]          # constant static: fine
                for k in range(10):
                    out.append(topk(xs, k))    # HAZARD: retrace per k
                return out
        ''')
        hits = [f for f in findings if f.rule == "jax-static-loop-arg"]
        assert len(hits) == 1

    def test_plain_function_not_flagged(self, tmp_path):
        findings = lint_src(tmp_path, '''
            """Host-side code may sync and branch freely."""
            import numpy as np


            def host(x):
                print(x)
                if x > 0:
                    return float(np.asarray(x))
                return x.item()
        ''')
        assert not rules_of(findings) & {"jax-host-sync", "jax-traced-branch"}


def lint_model_src(tmp_path, src, name="fake.py"):
    """Write a fixture under the models/ package path — the
    jax-whole-dataset-put rule only audits model fit files."""
    pkg = tmp_path / "spark_rapids_ml_tpu" / "models"
    pkg.mkdir(parents=True, exist_ok=True)
    return lint_src(
        tmp_path, src,
        name=f"spark_rapids_ml_tpu/models/{name}", root=tmp_path,
    )


class TestWholeDatasetPut:
    BAD_FIT = '''
        """f"""
        import jax
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.core.ingest import extract_features


        class M:
            def _fit(self, dataset):
                rows = extract_features(dataset, "features")
                a = jnp.asarray(rows)          # HAZARD: extractor-tainted
                b = jax.device_put(dataset)    # HAZARD: raw fit param
                return a, b
    '''

    def test_true_positives(self, tmp_path):
        findings = lint_model_src(tmp_path, self.BAD_FIT)
        hits = [f for f in findings if f.rule == "jax-whole-dataset-put"]
        assert len(hits) == 2, findings
        assert all("ingest" in h.message for h in hits)

    def test_only_models_fit_paths_audited(self, tmp_path):
        # Same source outside models/ — rule does not fire.
        findings = lint_src(tmp_path, self.BAD_FIT, name="ops_fake.py")
        assert not [f for f in findings if f.rule == "jax-whole-dataset-put"]
        # Same source in models/ but not a _fit* function — no finding.
        findings = lint_model_src(tmp_path, self.BAD_FIT.replace(
            "def _fit(", "def transform("
        ))
        assert not [f for f in findings if f.rule == "jax-whole-dataset-put"]

    def test_tuple_unpack_taints_matrix_only(self, tmp_path):
        findings = lint_model_src(tmp_path, '''
            """f"""
            import jax.numpy as jnp

            from spark_rapids_ml_tpu.core.ingest import _extract_xy


            class M:
                def _fit(self, dataset):
                    x, y = _extract_xy(dataset, "f", "l")
                    bad = jnp.asarray(x)   # HAZARD: the (n, d) matrix
                    ok = jnp.asarray(y)    # labels are O(n): fine
                    return bad, ok
        ''')
        hits = [f for f in findings if f.rule == "jax-whole-dataset-put"]
        assert len(hits) == 1 and "x" in hits[0].message

    def test_guarded_and_bounded_paths_clean(self, tmp_path):
        findings = lint_model_src(tmp_path, '''
            """f"""
            import jax.numpy as jnp

            from spark_rapids_ml_tpu.core.ingest import (
                extract_features,
                place_array,
                prepare_rows,
            )


            class M:
                def _fit(self, dataset):
                    rows = extract_features(dataset, "features")
                    x = prepare_rows(rows)         # the guarded funnel
                    xj = place_array(rows)         # the guarded chokepoint
                    sample = rows[:256]
                    s = jnp.asarray(sample)        # bounded slice: fine
                    return x, xj, s
        ''')
        assert not [f for f in findings if f.rule == "jax-whole-dataset-put"]


# --- family (b): lock discipline ---------------------------------------


class TestLockDiscipline:
    CLASS_SRC = '''
        """f"""
        import threading


        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self._items = []  # guarded-by: _lock

            def good(self, v):
                with self._lock:
                    self._items.append(v)

            def bad(self, v):
                self._items.append(v)
    '''

    def test_class_attr_violation_and_clean(self, tmp_path):
        findings = lint_src(tmp_path, self.CLASS_SRC)
        hits = [f for f in findings if f.rule == "lock-guarded"]
        assert len(hits) == 1 and "Box.bad()" in hits[0].message

    def test_inheritance_within_module(self, tmp_path):
        findings = lint_src(tmp_path, '''
            """f"""
            import threading


            class Base:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._state = {}  # guarded-by: _lock


            class Child(Base):
                def bad(self):
                    return len(self._state)
        ''')
        hits = [f for f in findings if f.rule == "lock-guarded"]
        assert len(hits) == 1 and "Child.bad()" in hits[0].message

    def test_module_global_violation(self, tmp_path):
        findings = lint_src(tmp_path, '''
            """f"""
            import threading

            _LOCK = threading.Lock()
            _CACHE = {}  # guarded-by: _LOCK


            def good(k):
                with _LOCK:
                    return _CACHE.get(k)


            def bad(k):
                return _CACHE.get(k)
        ''')
        hits = [f for f in findings if f.rule == "lock-guarded"]
        assert len(hits) == 1 and "bad" not in hits[0].message  # names global

    def test_unknown_lock_flagged(self, tmp_path):
        findings = lint_src(tmp_path, '''
            """f"""


            class Box:
                def __init__(self):
                    self._items = []  # guarded-by: _lockk
        ''')
        assert rules_of(findings) == {"lock-unknown"}

    def test_init_exempt(self, tmp_path):
        findings = lint_src(tmp_path, '''
            """f"""
            import threading


            class Box:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._items = []  # guarded-by: _lock
                    self._items.append(1)  # construction: not shared yet
        ''')
        assert not findings


# --- family (c): knob registry -----------------------------------------


class TestKnobRegistry:
    def test_raw_read_literal_and_constant(self, tmp_path, mini_repo):
        findings = lint_src(mini_repo, '''
            """f"""
            import os

            GOOD_ENV = "TPUML_GOOD_KNOB"
            a = os.environ.get("TPUML_GOOD_KNOB")     # raw read: HAZARD
            b = os.environ.get(GOOD_ENV, "1")         # via constant: HAZARD
            c = os.getenv("TPUML_GOOD_KNOB")          # HAZARD
            d = os.environ["TPUML_GOOD_KNOB"]         # HAZARD
            os.environ["TPUML_GOOD_KNOB"] = "1"       # write: fine
            e = os.environ.get("TPUML_TEST_WHATEVER") # harness input: fine
            f = os.environ.get("PATH")                # not a knob: fine
        ''', root=mini_repo)
        hits = [f for f in findings if f.rule == "knob-raw-environ"]
        assert len(hits) == 4, findings

    def test_unregistered_literal(self, tmp_path, mini_repo):
        findings = lint_src(mini_repo, '''
            """f"""
            NAME = "TPUML_NOT_IN_TABLE"
            GOOD = "TPUML_GOOD_KNOB"
            TESTY = "TPUML_TEST_ANYTHING"
            PREFIX = "TPUML_CHECKPOINT_"
        ''', root=mini_repo)
        hits = [f for f in findings if f.rule == "knob-unregistered"]
        assert "TPUML_NOT_IN_TABLE" in hits[0].message  # tpuml: noqa[knob-unregistered]
        assert len(hits) == 1

    def test_undocumented_knob(self, mini_repo):
        from tools.tpuml_lint.engine import RepoContext
        from tools.tpuml_lint.knobs import check_repo

        findings = check_repo(RepoContext(mini_repo))
        assert [f.rule for f in findings] == ["knob-undocumented"]
        assert "TPUML_ORPHAN_KNOB" in findings[0].message  # tpuml: noqa[knob-unregistered]

    def _sweep_unread(self, repo_root):
        findings, _ = tl.run_paths(
            repo_root,
            [repo_root / "spark_rapids_ml_tpu", repo_root / "tests"],
            tl.CHECKERS, tl.REPO_CHECKERS,
        )
        return [f for f in findings if f.rule == "knob-unread"]

    def test_unread_knob_flagged(self, mini_repo):
        """A registered name that only the registry, a docstring and a
        test hold is read by nothing: the finding the eight
        benchmark-shape entries drew the day their scripts went (PR 32)."""
        pkg = mini_repo / "spark_rapids_ml_tpu"
        (pkg / "reader.py").write_text(textwrap.dedent('''
            """Reads one knob; TPUML_ORPHAN_KNOB is prose here."""
            from spark_rapids_ml_tpu.utils.envknobs import env_int

            ROWS = env_int("TPUML_GOOD_KNOB", 1)
        '''))
        (mini_repo / "tests").mkdir()
        (mini_repo / "tests" / "test_x.py").write_text(textwrap.dedent('''
            """A test that sets a knob reads nothing."""
            NAME = "TPUML_ORPHAN_KNOB"
        '''))
        hits = self._sweep_unread(mini_repo)
        assert len(hits) == 1 and "TPUML_ORPHAN_KNOB" in hits[0].message  # tpuml: noqa[knob-unregistered]
        assert hits[0].path == "spark_rapids_ml_tpu/utils/envknobs.py"

    def test_literal_and_constructed_reads_are_clean(self, mini_repo):
        """A literal read and a constructed one (the
        `f"TPUML_PRECISION_{family}"` spelling of ops/precision.py) both
        count; and a run that did not sweep the registry judges
        nothing."""
        from tools.tpuml_lint.engine import RepoContext
        from tools.tpuml_lint.knobs import check_repo

        pkg = mini_repo / "spark_rapids_ml_tpu"
        (pkg / "reader.py").write_text(textwrap.dedent('''
            """Reads both knobs."""
            from spark_rapids_ml_tpu.utils.envknobs import env_int

            ROWS = env_int("TPUML_GOOD_KNOB", 1)


            def per_family(family):
                """One knob per family."""
                return env_int(f"TPUML_ORPHAN_{family.upper()}", 0)
        '''))
        assert self._sweep_unread(mini_repo) == []
        one_file = tl.run_paths(
            mini_repo, [pkg / "observability"], tl.CHECKERS, tl.REPO_CHECKERS
        )[0]
        assert "knob-unread" not in rules_of(one_file)
        assert "knob-unread" not in rules_of(check_repo(RepoContext(mini_repo)))


# --- family (d): observability drift -----------------------------------


class TestObservabilityDrift:
    def test_emit_schema_conformance(self, tmp_path, mini_repo):
        findings = lint_src(mini_repo, '''
            """f"""
            from spark_rapids_ml_tpu.observability.events import emit


            def g(**extra):
                emit("serving", action="hit")            # fine
                emit("run", action="start", kind="fit", label="x")  # fine
                emit("nonsense", action="x")             # unknown type
                emit("run", action="start")              # missing fields
                emit("run", **extra)                     # splat: skipped
        ''', root=mini_repo)
        assert [f.rule for f in findings] == [
            "event-unknown-type", "event-missing-field",
        ]
        assert "kind" in findings[1].message and "label" in findings[1].message

    def test_local_emit_not_confused(self, tmp_path, mini_repo):
        findings = lint_src(mini_repo, '''
            """A benchmarks-style local emit is not the event log."""


            def emit(payload):
                print(payload)


            def g():
                emit("whatever shape it likes")
        ''', root=mini_repo)
        assert not rules_of(findings) & {
            "event-unknown-type", "event-missing-field", "jax-host-sync",
        }

    def test_telemetry_dir_raw_read_rule(self, tmp_path, mini_repo):
        findings = lint_src(mini_repo, '''
            """f"""
            import os

            TELEMETRY_DIR_ENV = "TPUML_TELEMETRY_DIR"


            def g():
                a = os.environ.get("TPUML_TELEMETRY_DIR")      # HAZARD
                b = os.environ["TPUML_TELEMETRY_DIR"]          # HAZARD
                c = os.getenv(TELEMETRY_DIR_ENV)               # HAZARD
                os.environ["TPUML_TELEMETRY_DIR"] = "/x"       # write: fine
                return a, b, c
        ''', root=mini_repo)
        hits = [f for f in findings if f.rule == "telemetry-dir-raw-read"]
        assert len(hits) == 3
        assert all(f.severity == "error" for f in hits)

    def test_telemetry_dir_accessor_and_other_knobs_clean(
        self, tmp_path, mini_repo
    ):
        findings = lint_src(mini_repo, '''
            """The envknobs accessor path and OTHER knob reads are not
            this rule's business (knob-raw-environ owns those)."""
            import os

            from spark_rapids_ml_tpu.utils.envknobs import env_str


            def g():
                ok = env_str("TPUML_TELEMETRY_DIR")
                other = os.environ.get("TPUML_GOOD_KNOB")
                return ok, other
        ''', root=mini_repo)
        assert "telemetry-dir-raw-read" not in rules_of(findings)
        # the sibling family still flags the other raw read
        assert "knob-raw-environ" in rules_of(findings)

    def test_metric_name_rule(self, tmp_path, mini_repo):
        findings = lint_src(mini_repo, '''
            """f"""
            from spark_rapids_ml_tpu.observability.metrics import counter
            from spark_rapids_ml_tpu.utils.tracing import bump_counter


            def g(n):
                counter("serving.requests").inc()   # fine
                bump_counter("retry.site.attempts") # fine
                bump_counter(f"serving.shed.{n}")   # dynamic: skipped
                counter("BadName")                  # HAZARD
                bump_counter("single")              # HAZARD: one segment
        ''', root=mini_repo)
        hits = [f for f in findings if f.rule == "metric-name"]
        assert len(hits) == 2


# --- suppression --------------------------------------------------------


class TestSuppression:
    def test_named_noqa_suppresses_only_that_rule(self, tmp_path):
        findings = lint_src(tmp_path, '''
            """f"""
            import jax


            @jax.jit
            def f(x):
                print(x)  # tpuml: noqa[jax-host-sync]
                if x > 0:  # tpuml: noqa[jax-host-sync]
                    return x
                return -x
        ''')
        # print suppressed; the branch's noqa names the WRONG rule.
        assert rules_of(findings) == {"jax-traced-branch"}

    def test_bare_noqa_suppresses_all(self, tmp_path):
        findings = lint_src(tmp_path, '''
            """f"""
            import jax


            @jax.jit
            def f(x):
                return float(x)  # tpuml: noqa
        ''')
        assert not findings


# --- baseline -----------------------------------------------------------


class TestBaseline:
    def _findings(self):
        return [
            Finding("a.py", 3, 0, "bare-except", "bare except"),
            Finding("a.py", 9, 0, "bare-except", "bare except"),
            Finding("b.py", 1, 0, "missing-docstring", "missing module docstring"),
        ]

    def test_round_trip(self, tmp_path):
        path = tmp_path / "baseline.json"
        bl.save(path, self._findings())
        entries = bl.load(path)
        new, baselined, stale = bl.apply(self._findings(), entries)
        assert not new and not stale and len(baselined) == 3

    def test_multiplicity_counts(self, tmp_path):
        path = tmp_path / "baseline.json"
        bl.save(path, self._findings()[:1])  # ONE bare-except baselined
        new, baselined, stale = bl.apply(self._findings(), bl.load(path))
        assert len(new) == 2 and len(baselined) == 1 and not stale

    def test_stale_detection(self, tmp_path):
        path = tmp_path / "baseline.json"
        bl.save(path, self._findings())
        new, baselined, stale = bl.apply(self._findings()[:1], bl.load(path))
        assert not new and len(stale) == 2

    def test_line_moves_do_not_invalidate(self, tmp_path):
        path = tmp_path / "baseline.json"
        bl.save(path, [Finding("a.py", 3, 0, "bare-except", "bare except")])
        moved = [Finding("a.py", 300, 4, "bare-except", "bare except")]
        new, baselined, stale = bl.apply(moved, bl.load(path))
        assert not new and not stale and len(baselined) == 1


# --- CLI contract -------------------------------------------------------


SEEDED = {
    "jax-host-sync": '''
        """f"""
        import jax


        @jax.jit
        def f(x):
            return float(x)
    ''',
    "lock-guarded": '''
        """f"""
        import threading


        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self._v = 0  # guarded-by: _lock

            def bad(self):
                return self._v
    ''',
    "knob-raw-environ": '''
        """f"""
        import os

        x = os.environ.get("TPUML_SERVE_QUEUE")
    ''',
    "event-missing-field": '''
        """f"""
        from spark_rapids_ml_tpu.observability.events import emit

        emit("serving")
    ''',
}


class TestCLI:
    def _run(self, *args):
        return subprocess.run(
            [sys.executable, "-m", "tools.tpuml_lint", *args],
            capture_output=True, text=True, cwd=str(REPO),
        )

    @pytest.mark.parametrize("rule", sorted(SEEDED))
    def test_exits_nonzero_on_each_family(self, tmp_path, rule):
        f = tmp_path / "seeded.py"
        f.write_text(textwrap.dedent(SEEDED[rule]))
        r = self._run("--no-baseline", str(f))
        assert r.returncode == 1, r.stdout + r.stderr
        assert rule in r.stdout

    def test_shipped_tree_is_clean_with_baseline(self):
        """The acceptance criterion: zero exit over the whole tree in CI
        mode, JSON output parseable as the CI artifact."""
        r = self._run("--format", "json", "--validate-baseline")
        assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-2000:]
        doc = json.loads(r.stdout)
        assert doc["ok"] and not doc["new"] and not doc["stale"]
        assert doc["files"] > 100

    def test_rule_catalog_documented(self):
        """Every rule id the analyzer can report appears in
        CONTRIBUTING.md's rule table."""
        text = (REPO / "CONTRIBUTING.md").read_text()
        missing = [r for r in RULES if f"`{r}`" not in text]
        assert not missing, f"rules missing from CONTRIBUTING.md: {missing}"

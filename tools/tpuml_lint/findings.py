"""Finding model + the rule catalog for the tpuml-lint analyzer.

Every rule has a stable kebab-case id (the name used in baselines, in
``# tpuml: noqa[rule]`` suppressions, and in CONTRIBUTING.md's rule
table) and a severity: ``error`` findings gate CI; ``warning`` findings
print but do not fail the run unless ``--strict-warnings``.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict

ERROR = "error"
WARNING = "warning"


@dataclass(frozen=True)
class Rule:
    id: str
    family: str
    severity: str
    rationale: str


#: The rule catalog — single source of truth for ids, severities, and the
#: one-line rationales CONTRIBUTING.md lists.
RULES: Dict[str, Rule] = {
    r.id: r
    for r in (
        # generic (the seed tools/lint.py checks)
        Rule("syntax-error", "generic", ERROR,
             "the file must parse before anything else can be checked"),
        Rule("missing-docstring", "generic", ERROR,
             "every module documents itself (the apache-rat header analogue)"),
        Rule("unused-import", "generic", ERROR,
             "dead imports hide real dependencies and slow cold starts"),
        Rule("bare-except", "generic", ERROR,
             "swallowing BaseException hides KeyboardInterrupt and worker kills"),
        Rule("mutable-default", "generic", ERROR,
             "mutable default arguments alias state across calls"),
        Rule("import-star", "generic", ERROR,
             "star imports defeat the unused-import and name-resolution checks"),
        # (a) JAX retrace/sync hazards
        Rule("jax-host-sync", "jax", ERROR,
             "a host conversion inside a jitted function blocks the device "
             "pipeline (or silently runs at trace time only)"),
        Rule("jax-traced-branch", "jax", ERROR,
             "Python control flow on a traced value raises ConcretizationError "
             "or silently specializes the program to one trace"),
        Rule("jax-static-loop-arg", "jax", ERROR,
             "a static argument that varies per loop iteration compiles a new "
             "program every pass — the retrace bait PR 2/5 exist to kill"),
        Rule("jax-whole-dataset-put", "jax", ERROR,
             "a model fit path uploading the raw extracted dataset with a "
             "bare jnp.asarray/jax.device_put bypasses the ingest "
             "chokepoint (fault point, OOM retry, cache reclaim) the "
             "memory-safe data plane gates fits through — use "
             "prepare_rows or ingest.place_array"),
        # (b) lock discipline
        Rule("lock-guarded", "locks", ERROR,
             "an attribute annotated '# guarded-by: <lock>' was touched "
             "outside a 'with <lock>:' block in its owning scope"),
        Rule("lock-unknown", "locks", ERROR,
             "a guarded-by annotation names a lock the owning scope never "
             "defines — the convention must stay checkable"),
        Rule("lock-order", "locks", ERROR,
             "two locks in one module are acquired in both nesting orders "
             "— a cycle in the static acquisition-order graph is a "
             "potential deadlock the moment two threads interleave"),
        Rule("lock-leak", "locks", ERROR,
             "a lock acquired via .acquire() without a guaranteed-release "
             "path (no try/finally release, no with) stays held forever "
             "on the first exception — use 'with lock:'"),
        # (c) envknob registry
        Rule("knob-raw-environ", "knobs", ERROR,
             "TPUML_* knobs must go through utils/envknobs accessors so "
             "malformed values raise a named error and the registry stays "
             "the single source of truth"),
        Rule("knob-unregistered", "knobs", ERROR,
             "every TPUML_* name must have a Knob entry in envknobs.KNOBS "
             "(TPUML_TEST_* harness inputs are exempt)"),
        Rule("knob-undocumented", "knobs", ERROR,
             "every registered knob must appear in docs/PARITY.md's knob "
             "tables — docs that can drift are docs that will"),
        Rule("knob-unread", "knobs", ERROR,
             "a registered knob that no swept file outside tests/ reads is "
             "an option of nothing: it goes with the last code that read it"),
        # (d) observability drift
        Rule("event-unknown-type", "drift", ERROR,
             "emit() with a record type events.py::SCHEMA does not declare "
             "writes lines the validator (and the CI gate) will reject"),
        Rule("event-missing-field", "drift", ERROR,
             "emit() must pass every required field its record type declares"),
        Rule("metric-name", "drift", ERROR,
             "metric names are lowercase dotted (subsystem.metric[.detail]) "
             "so the Prometheus exposition and dashboards stay uniform"),
        Rule("telemetry-dir-raw-read", "drift", ERROR,
             "TPUML_TELEMETRY_DIR reads must go through utils/envknobs "
             "(events.telemetry_dir): a layer resolving the shard dir on "
             "its own can split one gang's shards across two places"),
    )
}


@dataclass(frozen=True)
class Finding:
    """One rule violation at one source location."""

    path: str  # repo-relative, "/"-separated
    line: int
    col: int
    rule: str
    message: str
    severity: str = field(default=ERROR)

    def render(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule}: {self.message}"

    def baseline_key(self) -> tuple:
        # Line/col excluded: a baseline must survive unrelated edits above
        # the finding.
        return (self.path, self.rule, self.message)

    def to_json(self) -> dict:
        return {
            "path": self.path,
            "line": self.line,
            "col": self.col,
            "rule": self.rule,
            "severity": self.severity,
            "message": self.message,
        }

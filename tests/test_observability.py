"""Structured run telemetry (observability/): the metrics registry, the
JSONL event log, fit/serve reports, heartbeats, and the span and counter surface
(utils/tracing.py).

The acceptance case (TestAcceptance) is the ISSUE 4 contract: one
``LogisticRegression.fit`` + ``transform`` on the fault-injection
harness — one injected retry, checkpointing enabled — yields one JSONL
stream from which this suite reconstructs the stage-timing tree, the
retry attempt count (matching the ``retry.*.attempts`` counters), every
checkpoint write, and the serving cache hit/miss totals, all sharing one
``run_id``; with the knob unset, zero events are emitted and the range
path stays allocation-light (the budget test).
"""

import importlib.util
import json
import os
import threading
import time
import tracemalloc

import numpy as np
import pytest

from spark_rapids_ml_tpu.core import serving
from spark_rapids_ml_tpu.models.logistic_regression import LogisticRegression
from spark_rapids_ml_tpu.models.pca import PCA
from spark_rapids_ml_tpu.observability import events
from spark_rapids_ml_tpu.observability.heartbeat import (
    GangHeartbeat,
    heartbeat_scope,
)
from spark_rapids_ml_tpu.observability.metrics import (
    MetricError,
    Registry,
    default_registry,
    dump_snapshot,
)
from spark_rapids_ml_tpu.observability.report import build_stage_tree
from spark_rapids_ml_tpu.robustness.checkpoint import FitCheckpointer
from spark_rapids_ml_tpu.robustness.faults import inject
from spark_rapids_ml_tpu.robustness.retry import RetryExhaustedError, RetryPolicy
from spark_rapids_ml_tpu.utils import tracing
from spark_rapids_ml_tpu.utils.envknobs import env_str


# --- sink plumbing ------------------------------------------------------

_PREV_LOG = env_str(events.EVENT_LOG_ENV)


def _restore_sink():
    # Re-wire whatever the session started with: the explicit path when
    # TPUML_EVENT_LOG was set, else re-resolve from env so a session-wide
    # TPUML_TELEMETRY_DIR shard resumes (CI runs tier-1 under one).
    events.configure(_PREV_LOG if _PREV_LOG else None)


@pytest.fixture
def event_log(tmp_path):
    """A fresh per-test event-log file wired as the active sink."""
    path = tmp_path / "events.jsonl"
    events.configure(str(path))
    try:
        yield path
    finally:
        _restore_sink()


@pytest.fixture
def no_event_log():
    events.configure("")
    try:
        yield
    finally:
        _restore_sink()


_STUB = os.path.join(os.path.dirname(os.path.abspath(__file__)), "pyspark_stub")


@pytest.fixture
def stub_spark():
    """The pyspark stub installed as ``pyspark`` (the contract-suite
    arrangement — see tests/test_chaos.py)."""
    import sys

    saved = {n: m for n, m in sys.modules.items() if n.startswith("pyspark")}
    for n in list(saved):
        del sys.modules[n]
    sys.path.insert(0, _STUB)
    try:
        from pyspark.sql import SparkSession

        yield SparkSession.builder.master("local[2]").getOrCreate()
    finally:
        sys.path.remove(_STUB)
        for n in [n for n in sys.modules if n.startswith("pyspark")]:
            del sys.modules[n]
        sys.modules.update(saved)


def _records(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _load_cli():
    spec = importlib.util.spec_from_file_location(
        "tpuml_metrics",
        os.path.join(os.path.dirname(__file__), "..", "tools", "tpuml_metrics.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _double_kernel(x):
    return x * 2.0


# --- the typed registry -------------------------------------------------


class TestMetricsRegistry:
    def test_counter_inc_value_and_labels(self):
        r = Registry()
        c = r.counter("c.hits")
        c.inc()
        c.inc(4)
        assert c.value() == 5
        c.inc(2, site="a")
        assert c.value(site="a") == 2
        assert c.value() == 5  # unlabeled series untouched

    def test_gauge_set_and_callable(self):
        r = Registry()
        g = r.gauge("g.size")
        g.set(7)
        assert g.value() == 7
        g.set_function(lambda: 1.25, process="3")
        assert g.value(process="3") == 1.25
        snap = r.snapshot()
        assert snap["gauges"]["g.size"] == 7
        assert snap["gauges"]['g.size{process="3"}'] == 1.25

    def test_histogram_buckets_sum_count(self):
        r = Registry()
        h = r.histogram("h.lat", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 5.0, 50.0):
            h.observe(v)
        out = h.value()
        assert out["count"] == 4
        assert out["sum"] == pytest.approx(55.55)
        assert out["buckets"][0.1] == 1
        assert out["buckets"][1.0] == 2
        assert out["buckets"][10.0] == 3
        assert out["buckets"][float("inf")] == 4

    def test_kind_clash_raises(self):
        r = Registry()
        r.counter("x")
        with pytest.raises(MetricError):
            r.gauge("x")

    def test_prometheus_exposition(self):
        r = Registry()
        r.counter("serving.cache.hit", "hits").inc(3)
        r.gauge("cache.size").set(2)
        r.histogram("lat", buckets=(1.0,)).observe(0.5, solver="k")
        text = r.render_prometheus()
        assert "# TYPE tpuml_serving_cache_hit counter" in text
        assert "tpuml_serving_cache_hit 3.0" in text
        assert "# HELP tpuml_serving_cache_hit hits" in text
        assert "tpuml_cache_size 2.0" in text
        assert 'tpuml_lat_bucket{le="1.0",solver="k"} 1' in text
        assert 'tpuml_lat_bucket{le="+Inf",solver="k"} 1' in text
        assert 'tpuml_lat_count{solver="k"} 1' in text

    def test_snapshot_is_json_ready(self):
        r = Registry()
        r.counter("a").inc()
        r.histogram("h", buckets=(1.0,)).observe(2.0)
        json.dumps(r.snapshot())  # must not raise

    def test_clear_by_prefix_and_kind(self):
        r = Registry()
        r.counter("p.a").inc()
        r.gauge("p.b").set(1)
        r.clear("p.", kinds=("counter",))
        names = set(r.metrics())
        assert "p.a" not in names and "p.b" in names

    def test_bump_counter_alias_is_registry_backed(self):
        tracing.clear_counters("alias.")
        tracing.bump_counter("alias.x", 3)
        assert default_registry.counter("alias.x").value() == 3
        assert tracing.counters("alias.") == {"alias.x": 3}
        assert tracing.counter_value("alias.x") == 3
        tracing.clear_counters("alias.")
        assert tracing.counters("alias.") == {}

    def test_dump_snapshot_formats(self, tmp_path):
        default_registry.counter("dump.test").inc()
        j = tmp_path / "m.json"
        p = tmp_path / "m.prom"
        dump_snapshot(str(j))
        dump_snapshot(str(p))
        assert "dump.test" in json.load(open(j))["counters"]
        assert "tpuml_dump_test" in open(p).read()


# --- TraceRange satellite: exception opacity + stage tree ---------------


class TestTraceRangeSpans:
    def test_ok_and_exception_type_recorded(self, event_log):
        with pytest.raises(ValueError):
            with tracing.TraceRange("boom"):
                raise ValueError("x")
        recs = [r for r in _records(event_log) if r["event"] == "span"]
        assert recs[-1]["name"] == "boom"
        assert recs[-1]["ok"] is False
        assert recs[-1]["exc"] == "ValueError"

    def test_depth_parent_rebuild_stage_tree(self, event_log):
        with events.run_scope("job", "tree"):
            with tracing.TraceRange("outer"):
                with tracing.TraceRange("mid"):
                    with tracing.TraceRange("leaf"):
                        pass
                with tracing.TraceRange("sibling"):
                    pass
        spans = [r for r in _records(event_log) if r["event"] == "span"]
        tree = build_stage_tree(spans)
        outer = next(n for n in tree if n["name"] == "outer")
        assert [c["name"] for c in outer["children"]] == ["mid", "sibling"]
        assert outer["children"][0]["children"][0]["name"] == "leaf"
        depths = {r["name"]: r["depth"] for r in spans}
        assert depths["outer"] == 0 and depths["mid"] == 1 and depths["leaf"] == 2

    def test_ring_buffer_keeps_3tuple_shape(self):
        tracing.clear_events()
        with tracing.TraceRange("compat"):
            pass
        (name, start, end), = tracing.recent_events()[-1:]
        assert name == "compat" and end >= start


# --- event log ----------------------------------------------------------


class TestEventLog:
    def test_every_record_type_schema_validates(self, event_log, tmp_path):
        # Drive the real emitters for each record type in SCHEMA's core.
        with events.run_scope("job", "schema"):          # run start/end
            with tracing.TraceRange("a span"):           # span
                pass
            policy = RetryPolicy(max_attempts=2, base_delay=0.0)
            calls = {"n": 0}

            def flaky():
                calls["n"] += 1
                if calls["n"] == 1:
                    raise OSError("transient")
                return 1

            policy.run(flaky, name="obs.unit")           # retry
            with inject("persistence.write=0"):          # fault arm/disarm
                pass
            ck = FitCheckpointer(
                str(tmp_path / "ck"), uid="u", param_hash="p", data_fp="d",
                every=1,
            )
            ck.save_async(3, (np.zeros(2),))             # checkpoint write
            ck.wait()
            ck.restore_latest(template=(np.zeros(2),))   # checkpoint restore
            GangHeartbeat(process_id=9, interval=10).beat()  # heartbeat
            serving.serve_rows(                          # serving hit/miss
                _double_kernel, np.ones((4, 3)), name="obs.schema"
            )
            # counters flush + report ride the fit recorder.
            PCA().setK(2).fit(np.random.default_rng(0).standard_normal((24, 5)))
        recs = _records(event_log)
        problems = [p for r in recs for p in events.validate_record(r)]
        assert problems == []
        seen = {r["event"] for r in recs}
        for required in ("run", "span", "retry", "fault", "checkpoint",
                         "heartbeat", "serving", "counters", "report"):
            assert required in seen, f"no {required} record emitted"

    def test_degrade_and_persistence_records(self, event_log, tmp_path, monkeypatch):
        from spark_rapids_ml_tpu.models.logistic_regression import (
            LogisticRegressionModel,
        )
        from spark_rapids_ml_tpu.robustness.degrade import (
            DegradationWarning,
            run_degradable,
        )

        monkeypatch.setenv("TPUML_DEGRADE", "cpu")

        def accel():
            raise RetryExhaustedError("site.x", 2, OSError("gone"), "why")

        with pytest.warns(DegradationWarning):
            assert run_degradable(accel, lambda: 42, what="unit") == 42
        m = LogisticRegressionModel("u", np.zeros((3, 1)), np.zeros(1))
        m.save(str(tmp_path / "model"))
        recs = _records(event_log)
        assert problems_free(recs)
        kinds = {r["event"] for r in recs}
        assert "degrade" in kinds and "persistence" in kinds

    def test_stderr_sink(self, capsys):
        events.configure("stderr")
        try:
            events.emit("fault", action="arm")
        finally:
            _restore_sink()
        err = capsys.readouterr().err
        assert '"event": "fault"' in err

    def test_run_id_joins_across_threads_async_writer(self, event_log, tmp_path):
        ck = FitCheckpointer(
            str(tmp_path / "ck"), uid="u2", param_hash="p", data_fp="d",
            every=1,
        )
        main_thread = threading.get_ident()
        with events.run_scope("fit", "threaded") as ctx:
            with tracing.TraceRange("driver side"):
                ck.save_async(1, (np.arange(4.0),))
                ck.wait()
            rid = ctx.run_id
        recs = _records(event_log)
        writes = [r for r in recs if r["event"] == "checkpoint"
                  and r["action"] == "write"]
        assert writes and all(w["run_id"] == rid for w in writes)
        spans = [r for r in recs if r["event"] == "span"]
        assert {s["run_id"] for s in spans} == {rid}
        # The checkpoint-write span landed from the WRITER thread yet
        # carries the fit's run_id — the copied-context contract.
        writer_spans = [s for s in spans if s["name"] == "checkpoint write"]
        assert writer_spans and writer_spans[0]["thread"] != main_thread

    def test_zero_events_when_unset(self, no_event_log):
        before = events.emitted_count()
        assert not events.enabled()
        with tracing.TraceRange("silent"):
            pass
        tracing.bump_counter("silent.counter")
        with inject("persistence.write=0"):
            pass
        assert events.emitted_count() == before

    @pytest.mark.parametrize(
        "make",
        [lambda: tracing.TraceRange("budget"), lambda: tracing.StageRange("solve")],
        ids=["range", "stage"],
    )
    def test_range_path_allocation_budget(self, no_event_log, make):
        n = 300
        with make():
            pass
        tracemalloc.start()
        base, _ = tracemalloc.get_traced_memory()
        for _ in range(n):
            with make():
                pass
        current, peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        # Disabled path: a range object, an annotation, one ring tuple —
        # nowhere near 4 KiB each. A span-record dict per range would
        # blow this bound, which is the regression the test pins. A stage
        # adds two counter increments and keeps nothing.
        assert peak - base < n * 4096

    def test_stage_costs_a_range_and_two_counter_increments(
        self, no_event_log, monkeypatch
    ):
        monkeypatch.undo()  # production's StageRange, without conftest's nesting check

        def per_call(body, n=2000, repeats=7):
            best = float("inf")
            for _ in range(repeats):
                t0 = time.perf_counter()
                for _ in range(n):
                    body()
                best = min(best, (time.perf_counter() - t0) / n)
            return best

        def a_range():
            with tracing.TraceRange("budget"):
                pass

        def a_stage():
            with tracing.StageRange("solve"):
                pass

        def two_bumps():
            tracing.bump_counter("budget.ns", 12345)
            tracing.bump_counter("budget.calls")

        # The least of seven rounds each, taken in one process: what is
        # left over the sum pays for the counters' names and one int().
        budget = per_call(a_range) + per_call(two_bumps)
        assert per_call(a_stage) < 1.5 * budget


# --- heartbeats ---------------------------------------------------------


class TestHeartbeat:
    def test_beats_emit_and_gauge_reads_age(self, event_log):
        with heartbeat_scope(process_id=3, interval=0.02) as hb:
            time.sleep(0.12)
            assert hb.age_seconds() < 1.0
            # Live member: the age gauge reads as a CURRENT age.
            g = default_registry.gauge("gang.heartbeat.age_seconds")
            assert g.value(process="3") >= 0.0
            snap = default_registry.snapshot()
            assert 'gang.heartbeat.age_seconds{process="3"}' in snap["gauges"]
        recs = [r for r in _records(event_log) if r["event"] == "heartbeat"]
        assert len(recs) >= 3
        seqs = [r["seq"] for r in recs]
        assert seqs == sorted(seqs) and seqs[0] == 1
        assert all(r["interval"] == 0.02 for r in recs)
        # Finished member: the series is retired, not left to grow.
        snap = default_registry.snapshot()
        assert 'gang.heartbeat.age_seconds{process="3"}' not in snap["gauges"]

    def test_zero_interval_disables_thread(self, no_event_log):
        hb = GangHeartbeat(process_id=1, interval=0).start()
        assert hb._thread is None
        hb.stop()

    def test_barrier_worker_heartbeats(self, event_log, stub_spark, monkeypatch):
        from spark_rapids_ml_tpu.spark.barrier import barrier_gang_run

        monkeypatch.setenv("TPUML_GANG_HEARTBEAT_EVERY", "0.01")
        df = stub_spark.createDataFrame(
            [(float(i),) for i in range(4)], ["v"], numPartitions=2
        )

        def task(ctx, it):
            time.sleep(0.05)
            return [sum(r.v for r in it)]

        out = barrier_gang_run(df.rdd, task)
        assert sum(out) == sum(range(4))
        beats = [r for r in _records(event_log) if r["event"] == "heartbeat"]
        assert beats and all(r["what"] == "barrier" for r in beats)
        assert {r["process"] for r in beats} == {0, 1}  # one stream per member


# --- reports ------------------------------------------------------------


class TestReports:
    def test_fit_report_stage_tree_and_counters(self, no_event_log):
        rng = np.random.default_rng(1)
        X = rng.standard_normal((48, 4))
        y = (X[:, 0] > 0).astype(int)
        model = LogisticRegression().setMaxIter(4).fit((X, y))
        rep = model.fit_report()
        assert rep is not None and rep.ok
        assert rep.kind == "fit" and rep.label == "LogisticRegression"
        totals = rep.stage_totals()
        assert "logreg fit" in totals and "ingest" in totals
        tree = rep.stage_tree()
        fit_node = next(n for n in tree if n["name"] == "logreg fit")
        assert any(c["name"] == "ingest" for c in fit_node["children"])
        text = str(rep)
        assert "logreg fit" in text and rep.run_id in text
        assert rep.wall_seconds > 0
        json.dumps(rep.summary())  # picklable/serializable shape

    def test_pca_fit_report(self, no_event_log):
        rng = np.random.default_rng(2)
        model = PCA().setK(2).fit(rng.standard_normal((32, 6)))
        rep = model.fit_report()
        assert rep is not None and rep.label == "PCA"

    def test_nested_fit_joins_outer_run(self, no_event_log):
        rng = np.random.default_rng(3)
        X = rng.standard_normal((32, 4))
        with events.run_scope("job", "outer") as ctx:
            model = PCA().setK(2).fit(X)
        assert model.fit_report().run_id == ctx.run_id

    def test_serving_report(self, no_event_log):
        from spark_rapids_ml_tpu.observability.report import serving_report

        serving.serve_rows(_double_kernel, np.ones((6, 2)), name="obs.rep")
        rep = serving_report()
        assert rep["cache"]["size"] >= 1
        assert rep["cache_size_gauge"] == rep["cache"]["size"]
        assert rep["batch_rows"]["count"] >= 1

    def test_profile_dir_knob(self, no_event_log, tmp_path, monkeypatch):
        prof = tmp_path / "profile"
        monkeypatch.setenv("TPUML_PROFILE_DIR", str(prof))
        rng = np.random.default_rng(4)
        PCA().setK(2).fit(rng.standard_normal((24, 5)))
        # jax writes a plugins/ or .trace dir tree under the profile dir.
        assert prof.exists() and any(prof.rglob("*"))


# --- serving cache-size gauge (satellite) -------------------------------


class TestServingCacheGauge:
    def test_size_gauge_tracks_cache_under_lock(self, no_event_log):
        serving.clear_program_cache()
        g = default_registry.gauge("serving.cache.size")
        assert g.value() == 0
        serving.serve_rows(_double_kernel, np.ones((4, 2)), name="obs.gauge")
        assert g.value() == serving.program_cache_stats()["size"] >= 1
        serving.clear_program_cache()
        assert g.value() == 0


# --- the acceptance scenario -------------------------------------------


class TestAcceptance:
    def test_fit_transform_one_stream_one_run_id(
        self, event_log, tmp_path, monkeypatch
    ):
        monkeypatch.setenv("TPUML_CHECKPOINT_DIR", str(tmp_path / "ck"))
        monkeypatch.setenv("TPUML_CHECKPOINT_EVERY", "2")
        rng = np.random.default_rng(7)
        X = rng.standard_normal((96, 5))
        y = (X @ np.arange(1.0, 6.0) > 0).astype(int)

        c0 = {
            k: tracing.counter_value(k)
            for k in (
                "retry.ingest.device_put.attempts",
                "checkpoint.write",
                "serving.cache.hit",
                "serving.cache.miss",
            )
        }
        with events.run_scope("job", "acceptance") as ctx:
            with inject("ingest.device_put=1") as plan:  # ONE injected retry
                model = LogisticRegression().setMaxIter(8).fit((X, y))
            assert plan.fired == [("ingest.device_put", 0)]
            model.predict(X[:10])   # miss + compile
            model.predict(X[:10])   # hit
            rid = ctx.run_id
        delta = {
            k: tracing.counter_value(k) - v for k, v in c0.items()
        }

        recs = _records(event_log)
        assert problems_free(recs)
        # ONE run_id across the whole episode — fit spans, retry, fault,
        # checkpoint writes (async thread included), serving traffic.
        assert {r["run_id"] for r in recs} == {rid}

        # Stage-timing tree reconstructs from the stream alone.
        spans = [r for r in recs if r["event"] == "span"]
        tree = build_stage_tree(spans)
        fit_node = next(n for n in tree if n["name"] == "logreg fit")
        ingest = next(c for c in fit_node["children"] if c["name"] == "ingest")
        retry_nodes = [
            c for c in ingest["children"] if c["name"].startswith("retry:")
        ]
        # Attempt 0 dies at the injected fault (before the placement);
        # attempt 1 carries it, as the `place` stage.
        assert len(retry_nodes) == 2
        assert any(
            g["name"] == "place" for rn in retry_nodes
            for g in rn["children"]
        )
        assert any(s["name"] == "checkpoint write" for s in spans)

        # Retry attempts in the stream == the counters.
        retries = [r for r in recs if r["event"] == "retry"
                   and r["site"] == "ingest.device_put"]
        assert len(retries) == delta["retry.ingest.device_put.attempts"] == 2
        assert {r["outcome"] for r in retries} == {"retry", "ok"}
        fires = [r for r in recs if r["event"] == "fault"
                 and r.get("action") == "fire"]
        assert len(fires) == 1 and fires[0]["site"] == "ingest.device_put"

        # Every checkpoint write is in the stream.
        writes = [r for r in recs if r["event"] == "checkpoint"
                  and r["action"] == "write"]
        assert len(writes) == delta["checkpoint.write"] >= 1
        assert all(os.path.basename(w["path"]).startswith("ckpt-")
                   for w in writes)

        # Serving cache hit/miss totals match the counters.
        hits = [r for r in recs if r["event"] == "serving"
                and r["action"] == "hit"]
        misses = [r for r in recs if r["event"] == "serving"
                  and r["action"] == "miss"]
        assert len(hits) == delta["serving.cache.hit"] >= 1
        assert len(misses) == delta["serving.cache.miss"] >= 1

        # The fit report rides the same run and counts the activity.
        rep = model.fit_report()
        assert rep.run_id == rid
        assert rep.checkpoint_activity().get("checkpoint.write", 0) >= 1


# --- the CLI ------------------------------------------------------------


class TestMetricsCLI:
    def test_events_summary_and_validation(self, event_log, tmp_path, capsys):
        with events.run_scope("job", "cli") as ctx:
            with tracing.TraceRange("cli span"):
                pass
        cli = _load_cli()
        recs, problems = cli.parse_lines(open(event_log))
        assert problems == [] and recs
        summary = cli.summarize(recs)
        assert ctx.run_id in summary["runs"]
        assert summary["runs"][ctx.run_id]["spans"] >= 1
        assert cli.main(["events", str(event_log), "--validate"]) == 0
        out = capsys.readouterr().out
        assert ctx.run_id in out

    def test_validate_flags_malformed_lines(self, tmp_path, capsys):
        bad = tmp_path / "bad.jsonl"
        bad.write_text('{"event": "span"}\nnot json\n')
        cli = _load_cli()
        assert cli.main(["events", str(bad), "--validate"]) == 1
        err = capsys.readouterr().err
        assert "INVALID" in err

    def test_snapshot_prometheus_rendering(self, tmp_path, capsys):
        default_registry.counter("cli.test").inc(2)
        snap = tmp_path / "m.json"
        dump_snapshot(str(snap))
        cli = _load_cli()
        assert cli.main(["snapshot", str(snap), "--format", "prom"]) == 0
        out = capsys.readouterr().out
        assert "tpuml_cli_test 2.0" in out


def problems_free(recs):
    problems = [p for r in recs for p in events.validate_record(r)]
    assert problems == [], problems
    return True

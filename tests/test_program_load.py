"""Program load (utils/tracing.py::register_program_listener): what jax
reports of every program it traces, lowers, compiles or loads from the
persistent cache, counted in ``program.<phase>.ns`` / ``.calls``, in the
per-program table ``program_loads()`` and as a ``program <name>`` span of
the run that asked for it.

A real persistent cache cannot be used here (XLA:CPU's serializer crashes,
tests/conftest.py), so the cache's events are sent through jax.monitoring
by hand, as compiler.py::compile_or_get_cached sends them.
"""

import threading
import time
from collections import deque

import jax
import numpy as np
import pytest
from jax import lax
from jax._src import monitoring as jax_monitoring

from spark_rapids_ml_tpu.core import serving
from spark_rapids_ml_tpu.models.pca import PCA
from spark_rapids_ml_tpu.observability import events
from spark_rapids_ml_tpu.utils import tracing

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"
READ = "/jax/compilation_cache/cache_retrieval_time_sec"


def program_counters() -> dict:
    return tracing.counters("program.")


def moved(before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in program_counters().items()}


def cache_load(name: str, read_s: float, backend_s: float) -> None:
    """A persistent-cache hit as jax reports it: the hit and the read inside
    the backend event of the program it loads."""
    t0 = time.time()
    jax.monitoring.record_event(HIT)
    jax.monitoring.record_event_duration_secs(READ, read_s)
    jax.monitoring.record_event_time_span(BACKEND, t0, t0 + backend_s, fun_name=f"jit({name})")


class TestCounters:
    def test_a_fresh_program_is_traced_lowered_and_compiled_once(self):
        def fresh_add_mul(x):
            return lax.mul(lax.add(x, x), x)

        f = jax.jit(fresh_add_mul)
        x = np.arange(5.0)
        before = program_counters()
        f(x).block_until_ready()
        first = moved(before)
        for phase in ("trace", "lower", "compile"):
            assert first[f"program.{phase}.calls"] == 1, first
            assert first[f"program.{phase}.ns"] > 0
        assert first["program.cache_load.calls"] == 0
        row = tracing.program_loads()["fresh_add_mul"]
        assert row["compiles"] == 1 and row["cache_loads"] == 0 and row["trace_ns"] > 0
        again = program_counters()
        f(x).block_until_ready()
        assert moved(again) == dict.fromkeys(again, 0)

    def test_a_nested_trace_is_counted_once_in_the_union(self):
        spans = []

        def listen(event, start, end, **kwargs):
            if event == TRACE:
                spans.append((kwargs["fun_name"], start, end))

        @jax.jit
        def nested_inner(x):
            return lax.mul(x, x)

        @jax.jit
        def nested_outer(x):
            return lax.add(nested_inner(x), x)

        jax.monitoring.register_event_time_span_listener(listen)
        try:
            before = program_counters()
            nested_outer(np.arange(3.0)).block_until_ready()
        finally:
            jax.monitoring.unregister_event_time_span_listener(listen)
        got = moved(before)
        (_, s_in, e_in), = [s for s in spans if s[0] == "nested_inner"]
        (_, s_out, e_out), = [s for s in spans if s[0] == "nested_outer"]
        assert s_out <= s_in <= e_in <= e_out  # the inner trace inside the outer one
        assert got["program.trace.calls"] == 2
        assert 0 < got["program.trace.ns"] <= (e_out - s_out) * 1e9 + 1
        table = tracing.program_loads()
        assert (table["nested_inner"]["trace_ns"] + table["nested_outer"]["trace_ns"]
                == got["program.trace.ns"])

    def test_a_trace_inside_a_lowering_is_counted_once(self):
        before = program_counters()
        t0 = time.time()
        jax.monitoring.record_event_time_span(TRACE, t0 + 0.1, t0 + 0.2, fun_name="traced_while_lowering")
        jax.monitoring.record_event_time_span(LOWER, t0, t0 + 0.5, fun_name="jit(lowered_around_it)")
        got = moved(before)
        assert got["program.trace.ns"] == pytest.approx(0.1e9, rel=1e-4)
        assert got["program.lower.ns"] == pytest.approx(0.4e9, rel=1e-4)
        assert tracing.program_loads()["lowered_around_it"]["lower_ns"] == got["program.lower.ns"]

    def test_the_phases_of_a_fit_add_up_to_no_more_than_its_wall(self):
        # the device fit lowers random-key functions by tracing them inside
        # its own lowering: counted once, the phases never exceed the wall
        x = jax.device_put(np.random.default_rng(40).standard_normal((700, 9)))
        before = program_counters()
        t0 = time.perf_counter()
        PCA().setK(2).fit(x)
        wall_ns = (time.perf_counter() - t0) * 1e9
        got = moved(before)
        loaded = sum(got[f"program.{p}.ns"] for p in tracing.PROGRAM_PHASES)
        assert got["program.compile.calls"] >= 1 and 0 < loaded <= wall_ns

    @pytest.mark.parametrize(
        "intervals, expected_ns",
        [
            ([(0.0, 1.0), (2.0, 3.0)], [1e9, 1e9]),                    # disjoint: each its own
            ([(1.0, 2.0), (0.0, 3.0)], [1e9, 2e9]),                    # inner closes first
            ([(1.0, 1.5), (2.0, 2.5), (0.0, 3.0)], [5e8, 5e8, 2e9]),   # two inside one
            ([(1.2, 1.4), (1.0, 2.0), (0.0, 3.0)], [2e8, 8e8, 2e9]),   # three deep
        ],
    )
    def test_the_union_rule_on_hand_made_intervals(self, intervals, expected_ns):
        closed = deque()
        got = [tracing._union_ns(closed, s, e) for s, e in intervals]
        assert got == pytest.approx(expected_ns, abs=2)
        # what is kept is the outermost intervals, disjoint
        kept = list(closed)
        assert all(a[1] <= b[0] for a, b in zip(kept, kept[1:]))


class TestCacheLoads:
    def test_a_hit_is_a_cache_load_of_the_program_whose_backend_event_closes_next(self):
        before = program_counters()
        cache_load("synthetic_eigensolver", read_s=0.25, backend_s=0.3)
        got = moved(before)
        assert got["program.cache_load.calls"] == 1 and got["program.compile.calls"] == 0
        assert got["program.cache_load.ns"] == pytest.approx(0.25e9, abs=1e3)
        # the backend event less its read: the cache key's computation
        assert got["program.compile.ns"] == pytest.approx(0.05e9, rel=1e-3)
        row = tracing.program_loads()["synthetic_eigensolver"]
        assert row["cache_loads"] == 1 and row["compiles"] == 0
        assert row["cache_load_ns"] == got["program.cache_load.ns"]

    def test_a_hit_is_spent_by_its_backend_event_and_is_the_thread_s_own(self):
        jax.monitoring.record_event(HIT)  # a hit on this thread ...
        jax.monitoring.record_event_duration_secs(READ, 0.1)
        before = program_counters()
        t0 = time.time()
        other = threading.Thread(  # ... is not another thread's
            target=jax.monitoring.record_event_time_span,
            args=(BACKEND, t0, t0 + 0.2), kwargs={"fun_name": "jit(synthetic_other)"},
        )
        other.start()
        other.join(timeout=30)
        assert not other.is_alive()
        jax.monitoring.record_event_time_span(BACKEND, t0, t0 + 0.2, fun_name="jit(synthetic_own)")
        jax.monitoring.record_event_time_span(BACKEND, t0, t0 + 0.2, fun_name="jit(synthetic_next)")
        got = moved(before)
        assert got["program.cache_load.calls"] == 1 and got["program.compile.calls"] == 2
        table = tracing.program_loads()
        assert table["synthetic_own"]["cache_loads"] == 1
        assert table["synthetic_other"]["compiles"] == 1 and table["synthetic_next"]["compiles"] == 1

    def test_the_table_keeps_256_names_and_folds_the_rest(self, monkeypatch):
        monkeypatch.setattr(tracing, "_program_loads", {})
        t0 = time.time()
        for i in range(tracing.MAX_PROGRAMS + 44):
            jax.monitoring.record_event_time_span(LOWER, t0 + i, t0 + i + 0.001, fun_name=f"jit(p{i})")
        table = tracing.program_loads()
        assert tracing.MAX_PROGRAMS == 256
        assert len(table) == 257 and "p255" in table and "p256" not in table
        assert table["other"]["lower_ns"] == pytest.approx(44 * 1e6, rel=1e-2)

    def test_a_failing_listener_never_fails_the_compile(self, monkeypatch, caplog):
        def broken(*_):
            raise RuntimeError("planted")

        monkeypatch.setattr(tracing, "_count_phase", broken)
        y = jax.jit(lambda x: lax.sub(x, x))(np.arange(7.0))
        assert np.all(np.asarray(y) == 0)
        assert "program-load listener failed" in caplog.text


class TestRegistration:
    def test_registering_twice_adds_no_second_listener(self):
        counts = (len(jax_monitoring.get_event_time_span_listeners()),
                  len(jax_monitoring.get_event_duration_listeners()),
                  len(jax_monitoring.get_event_listeners()))
        assert tracing.register_program_listener() is False
        assert counts == (len(jax_monitoring.get_event_time_span_listeners()),
                          len(jax_monitoring.get_event_duration_listeners()),
                          len(jax_monitoring.get_event_listeners()))
        assert jax_monitoring.get_event_time_span_listeners().count(tracing._on_time_span) == 1

    def test_every_counter_exists_before_anything_loads(self):
        names = {f"program.{p}.{k}" for p in tracing.PROGRAM_PHASES for k in ("ns", "calls")}
        assert names <= set(program_counters())


class TestRunReport:
    def test_a_fit_s_programs_are_spans_under_the_stage_that_loaded_them(self):
        rows = np.random.default_rng(38).standard_normal((41, 7))  # a shape no other test fits
        t0 = time.perf_counter()
        report = PCA().setK(2).fit(rows).fit_report()
        t1 = time.perf_counter()
        programs = [s for s in report.spans if s["name"].startswith("program ")]
        assert programs
        ids = {s["span"]: s for s in report.spans}
        for span in programs:
            assert t0 <= span["start"] <= span["end"] <= t1  # the perf_counter clock
            parent = ids.get(span["parent"])
            assert parent is not None and span["depth"] == parent["depth"] + 1
            assert span["ok"] and span["exc"] is None

    def test_a_fresh_shape_reports_its_compiles_and_a_repeat_none(self):
        rows = np.random.default_rng(39).standard_normal((43, 6))
        first = PCA().setK(2).fit(rows).fit_report()
        repeat = PCA().setK(2).fit(rows).fit_report()
        assert first.compile_count() >= 1 and first.summary()["compiles"] == first.compile_count()
        assert first.counters["program.compile.calls"] >= 1
        assert repeat.compile_count() == 0
        assert f"compiles: {first.compile_count()}" in str(first)

    def test_a_serving_compile_is_a_program_span(self):
        serving.clear_program_cache()

        def _triple(x):
            return x * 3.0

        with events.run_scope("job", "serve") as ctx:
            serving.serve_rows(_triple, np.ones((5, 3)), name="program_load.triple")
            spans = ctx.span_window(0)
        names = [s["name"] for s in spans]
        assert "program _triple" in names and "serving compile" not in names

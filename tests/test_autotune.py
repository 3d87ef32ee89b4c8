"""Ledger-driven autotuner contracts (ISSUE 14).

The load-bearing promises, each pinned here:

  - ``TPUML_AUTOTUNE=off`` (the default) is today's behavior bit-for-bit:
    the serving path adds zero compiles (``jax_log_compiles``-asserted),
    zero autotune counters/events, and stays allocation-light;
  - the cost model recovers wall = a·rows + b and bytes = a·rows + b from
    synthetic ledger entries;
  - commit-or-revert NEVER accepts a seeded regression;
  - the serving ladder admits a proven-hot exact batch size — including
    sizes below the 8-row pow-2 minimum — invalidates the program cache,
    and the recompile classifies as a legitimate bucket, not a retrace;
  - the tune store round-trips through JSON and falls back to an empty
    store (counted) on a corrupt file;
  - ``fit_memory_guard`` prices through the fitted bytes model when one
    exists and is bit-identical to the static arithmetic when not;
  - the double-buffered training streams are value- and order-identical
    to the plain loops, with the overlap counter-asserted.
"""

import json
import logging
import os
import tracemalloc

import jax
import numpy as np
import pytest

from spark_rapids_ml_tpu.core.data import DEFAULT_FIT_BLOCK_ROWS, fit_block_rows
from spark_rapids_ml_tpu.core.serving import (
    bucket_rows,
    clear_program_cache,
    ladder_bucket_rows,
    prefetch_blocks,
    serve_rows,
)
from spark_rapids_ml_tpu.observability import autotune, costs, events
from spark_rapids_ml_tpu.observability.autotune import (
    Autotuner,
    TuneStore,
    fit_cost_models,
)
from spark_rapids_ml_tpu.observability.costs import ProgramCost
from spark_rapids_ml_tpu.utils.tracing import clear_counters, counter_value


def _kernel(x, w):
    return x @ w


@pytest.fixture
def tuner(monkeypatch, tmp_path):
    """An armed tuner (hot_min=3, tmp-file store) over a clean serving
    layer; tears back down to off + disarmed ledger."""
    monkeypatch.setenv("TPUML_AUTOTUNE", "on")
    monkeypatch.setenv("TPUML_AUTOTUNE_HOT_MIN", "3")
    monkeypatch.setenv("TPUML_TUNE_STORE", str(tmp_path / "tune.json"))
    clear_program_cache()
    clear_counters("autotune.")
    clear_counters("compile.")
    clear_counters("fit.")
    costs.reset_for_tests()
    autotune.reset_for_tests()
    t = autotune.active()
    assert t is not None
    assert costs.active() is not None  # the tuner arms the ledger
    yield t
    autotune.configure(enable=False)
    costs.configure(enable=False)
    clear_program_cache()


@pytest.fixture
def off(monkeypatch):
    monkeypatch.delenv("TPUML_AUTOTUNE", raising=False)
    monkeypatch.delenv("TPUML_COST_LEDGER", raising=False)
    clear_program_cache()
    clear_counters("autotune.")
    clear_counters("compile.")
    costs.reset_for_tests()
    autotune.reset_for_tests()
    assert autotune.active() is None
    yield
    clear_program_cache()


def _inject_entry(
    led, family, rows, *, wall=0.0, invocations=0, arg=None, temp=None,
    out=None,
):
    """Seed one synthetic program entry straight into a live ledger —
    the model-fitting tests need measured-looking evidence without
    compiling one program per data point."""
    key = f"{family}|aot|{rows}x4:float32|{rows:010d}"
    entry = ProgramCost(
        key=key, family=family, kind="aot", static="", rows=int(rows),
        spec=f"{rows}x4:float32", classification="new_program",
        argument_bytes=arg, temp_bytes=temp, output_bytes=out,
        invocations=int(invocations), wall_seconds=float(wall),
    )
    with led._lock:
        led._entries[key] = entry
    return key


# ---------------------------------------------------------------------------
# off mode: bit identity, zero compiles, zero allocation
# ---------------------------------------------------------------------------


class TestOffMode:
    def test_off_serving_bit_identity_zero_compiles(self, off, rng, caplog):
        """Off, the ladder helper IS bucket_rows, results repeat
        bit-for-bit, and the warm path never recompiles."""
        import jax.numpy as jnp

        w = jnp.asarray(rng.normal(size=(6, 3)).astype(np.float32))
        batches = [rng.normal(size=(n, 6)).astype(np.float32)
                   for n in (3, 30, 200)]
        for n in (1, 3, 7, 8, 9, 100):
            assert ladder_bucket_rows(n, name="off.kern", width=6) == bucket_rows(n)
        first = [np.asarray(serve_rows(_kernel, x, (w,), name="off.kern"))
                 for x in batches]
        jax.config.update("jax_log_compiles", True)
        try:
            with caplog.at_level(logging.WARNING, logger="jax._src.dispatch"):
                second = [
                    np.asarray(serve_rows(_kernel, x, (w,), name="off.kern"))
                    for x in batches
                ]
        finally:
            jax.config.update("jax_log_compiles", False)
        assert [
            r for r in caplog.records if "XLA compilation" in r.getMessage()
        ] == []
        for a, b in zip(first, second):
            assert np.array_equal(a, b)
        assert counter_value("autotune.commit") == 0
        assert counter_value("autotune.ladder.grow") == 0

    def test_off_fit_block_rows_is_static_default(self, off):
        assert fit_block_rows() == DEFAULT_FIT_BLOCK_ROWS
        assert fit_block_rows("kmeans", width=64) == DEFAULT_FIT_BLOCK_ROWS

    def test_env_knob_beats_tuner(self, tuner, monkeypatch):
        """An explicitly set TPUML_FIT_BLOCK_ROWS wins even with the
        tuner on — operator overrides are never second-guessed."""
        monkeypatch.setenv("TPUML_FIT_BLOCK_ROWS", "1234")
        assert fit_block_rows("anything", width=8) == 1234

    def test_off_zero_allocation_guard(self, off, rng):
        """Warm off-mode serving stays allocation-light and emits no
        autotune events — the disabled tuner costs one None check."""
        import jax.numpy as jnp

        w = jnp.asarray(rng.normal(size=(6, 3)).astype(np.float32))
        x = rng.normal(size=(16, 6)).astype(np.float32)
        serve_rows(_kernel, x, (w,), name="off.alloc")  # compile outside
        before_events = events.emitted_count()
        n = 50
        tracemalloc.start()
        base, _ = tracemalloc.get_traced_memory()
        for _ in range(n):
            serve_rows(_kernel, x, (w,), name="off.alloc")
        current, peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        assert events.emitted_count() == before_events
        assert counter_value("autotune.commit") == 0
        assert peak - base < n * 65536


# ---------------------------------------------------------------------------
# the cost model
# ---------------------------------------------------------------------------


class TestCostModel:
    def test_fit_recovers_synthetic_coefficients(self):
        """wall = 2e-6·rows + 5e-4 and bytes = 48·rows + 1000 seeded at
        three row counts come back within 1%."""
        A, B, BA, BB = 2e-6, 5e-4, 48.0, 1000.0
        entries = []
        for rows in (100, 400, 1600):
            entries.append(ProgramCost(
                key=f"m|aot|{rows}", family="m.serve", kind="aot",
                static="", spec="", rows=rows, classification="new_program",
                invocations=4, wall_seconds=4 * (A * rows + B),
                argument_bytes=int(BA * rows + BB), temp_bytes=0,
                output_bytes=0,
            ))
        models = fit_cost_models(entries)
        m = models["m.serve"]
        assert m.wall_a == pytest.approx(A, rel=0.01)
        assert m.wall_b == pytest.approx(B, rel=0.01)
        assert m.bytes_a == pytest.approx(BA, rel=0.01)
        assert m.bytes_b == pytest.approx(BB, rel=0.01)
        assert m.points == 3 and len(m.evidence) == 3
        assert m.predict_wall(1000) == pytest.approx(A * 1000 + B, rel=0.01)
        assert m.predict_bytes(1000) == pytest.approx(BA * 1000 + BB, rel=0.01)

    def test_single_point_and_compile_exclusion(self):
        """One distinct row count degrades to a=y/x, b=0; an entry that
        only ever compiled (zero invocations) contributes no wall point;
        entries without rows contribute nothing at all."""
        entries = [
            ProgramCost(
                key="s|1", family="s", kind="aot", static="", spec="",
                rows=200, classification="new_program", invocations=2,
                wall_seconds=2 * 0.01, compile_seconds=99.0,
            ),
            ProgramCost(
                key="s|2", family="cold", kind="aot", static="", spec="",
                rows=100, classification="new_program", invocations=0,
                wall_seconds=0.0,
            ),
            ProgramCost(
                key="s|3", family="rowless", kind="fallback", static="",
                spec="", rows=None, classification="new_program",
                invocations=5, wall_seconds=1.0,
            ),
        ]
        models = fit_cost_models(entries)
        assert models["s"].wall_a == pytest.approx(0.01 / 200)
        assert models["s"].wall_b == 0.0
        assert "cold" not in models  # no wall AND no bytes points
        assert "rowless" not in models


# ---------------------------------------------------------------------------
# commit-or-revert
# ---------------------------------------------------------------------------


class TestCommitOrRevert:
    def test_seeded_regression_never_accepted(self, tuner):
        assert tuner.record_trial("fit_block_rows", "fam", 16384, 1.0) is True
        assert counter_value("autotune.commit") == 1
        # The seeded regression: slower candidate must be rejected.
        assert tuner.record_trial("fit_block_rows", "fam", 65536, 2.0) is False
        assert counter_value("autotune.revert") == 1
        dec = tuner.store.get("fit_block_rows", "fam")
        assert dec["value"] == 16384 and dec["metric"] == 1.0
        assert dec["rejected"][-1] == {
            "value": 65536, "metric": 2.0, "reason": "regression",
        }
        # And it stays rejected no matter how often it is re-offered.
        assert tuner.record_trial("fit_block_rows", "fam", 65536, 1.5) is False
        assert tuner.store.get("fit_block_rows", "fam")["value"] == 16384

    def test_better_candidate_supersedes(self, tuner):
        tuner.record_trial("fit_block_rows", "fam", 16384, 1.0)
        assert tuner.record_trial("fit_block_rows", "fam", 32768, 0.5) is True
        dec = tuner.store.get("fit_block_rows", "fam")
        assert dec["value"] == 32768
        assert {"value": 16384, "metric": 1.0, "reason": "superseded"} in dec["rejected"]

    def test_measure_and_commit_collects_ledger_evidence(self, tuner, rng):
        import jax.numpy as jnp

        w = jnp.asarray(rng.normal(size=(4, 2)).astype(np.float32))
        x = rng.normal(size=(64, 4)).astype(np.float32)

        result, metric, committed = tuner.measure_and_commit(
            "fit_block_rows", "mc.fam", 64,
            lambda: serve_rows(_kernel, x, (w,), name="mc.kern"),
            rows=64,
        )
        assert committed is True and metric > 0.0
        dec = tuner.store.get("fit_block_rows", "mc.fam")
        assert any("mc.kern" in e for e in dec["evidence"])
        assert np.asarray(result).shape == (64, 2)

    def test_committed_block_rows_drive_fit_block_rows(self, tuner):
        tuner.record_trial("fit_block_rows", "famx", 16384, 0.1)
        assert tuner.recommend_block_rows("famx", default=DEFAULT_FIT_BLOCK_ROWS) == 16384
        assert fit_block_rows("famx") == 16384


# ---------------------------------------------------------------------------
# the learned serving ladder
# ---------------------------------------------------------------------------


class TestLadder:
    def test_hot_tiny_size_gets_exact_bucket(self, tuner, rng, caplog):
        """A steady 3-row stream pads to the 8-row min bucket until the
        histogram proves it hot; then the ladder admits an exact 3-row
        rung, the cache invalidates, and exactly ONE new program compiles
        at rows=3 — classified as a bucket, not a retrace."""
        import jax.numpy as jnp

        w = jnp.asarray(rng.normal(size=(6, 3)).astype(np.float32))
        x3 = rng.normal(size=(3, 6)).astype(np.float32)
        cold = [np.asarray(serve_rows(_kernel, x3, (w,), name="lad.kern"))
                for _ in range(2)]
        assert tuner.peek_serving_bucket("lad.kern", 6, 3, bucket_rows(3)) == 8
        # The third sighting crosses hot_min: the ladder admits an exact
        # 3-row rung, invalidates the cache, and THIS call compiles the
        # one rows=3 program; the follow-up calls ride the cache.
        jax.config.update("jax_log_compiles", True)
        try:
            with caplog.at_level(logging.WARNING, logger="jax._src.dispatch"):
                grew = np.asarray(serve_rows(_kernel, x3, (w,), name="lad.kern"))
                warm1 = np.asarray(serve_rows(_kernel, x3, (w,), name="lad.kern"))
                warm2 = np.asarray(serve_rows(_kernel, x3, (w,), name="lad.kern"))
        finally:
            jax.config.update("jax_log_compiles", False)
        compiles = [
            r for r in caplog.records if "XLA compilation" in r.getMessage()
        ]
        assert len(compiles) == 1
        assert counter_value("autotune.ladder.grow") == 1
        assert counter_value("compile.retrace") == 0
        assert tuner.peek_serving_bucket("lad.kern", 6, 3, bucket_rows(3)) == 3
        assert tuner.is_ladder_bucket(3)
        # Bit-identical outputs WITHIN a program: the 8-row padded one
        # (cold) and the exact 3-row one (grew and its cache hits).
        assert np.array_equal(cold[1], cold[0])
        assert np.array_equal(warm1, grew) and np.array_equal(warm2, grew)
        # ACROSS the transition the two shapes are two XLA:CPU programs
        # and may order the six-term float32 contraction differently
        # (the last ulp moves). Padding does not reach the result: pad
        # rows of any value leave the first three rows of the 8-row
        # program bit for bit. Each sum is off by at most
        # 6 * 2^-24 * sum|a_i b_i| (4e-7 of an O(1) sum), which is the
        # atol; an entry that cancels has no tighter RELATIVE bound.
        np.testing.assert_allclose(grew, cold[0], rtol=1e-6, atol=1e-6)
        # Cold sizes still round up through the pow-2 ladder.
        assert tuner.peek_serving_bucket("lad.kern", 6, 5, bucket_rows(5)) == 8

    def test_ladder_cuts_padded_rows_in_the_ledger(self, tuner, rng):
        """The count the retired closed-loop script took from the cost
        ledger (rows x invocations per program): of ten 37-row requests
        only those before the size proved hot (hot_min = 3) run the
        64-row bucket; the rest run an exact 37-row program and pad
        nothing. The committed rung is in the store, and on disk."""
        import jax.numpy as jnp

        w = jnp.asarray(rng.normal(size=(32, 8)).astype(np.float32))
        probe = rng.normal(size=(37, 32)).astype(np.float32)
        for _ in range(10):
            serve_rows(_kernel, probe, (w,), name="pad.kern")
        assert counter_value("autotune.ladder.grow") == 1
        invocations = {
            e.rows: e.invocations
            for e in costs.active().entries() if e.family == "pad.kern"
        }
        assert invocations == {64: 2, 37: 8}
        assert tuner.store.get("serving_ladder", "pad.kern|32")["value"] == [37]
        assert os.path.exists(tuner.store.path)

    def test_ladder_decision_persists_and_reloads(self, tuner, tmp_path):
        for _ in range(3):
            tuner.serving_bucket("per.kern", 4, 100, bucket_rows(100))
        dec = tuner.store.get("serving_ladder", "per.kern|4")
        assert dec["value"] == [100]
        # A fresh tuner over the same store starts with the ladder live.
        t2 = Autotuner(TuneStore(tuner.store.path), hot_min=3)
        assert t2.peek_serving_bucket("per.kern", 4, 100, bucket_rows(100)) == 100
        assert t2.is_ladder_bucket(100)

    def test_pricing_peek_agrees_without_observing(self, tuner):
        for _ in range(3):
            tuner.serving_bucket("pr.kern", 4, 37, bucket_rows(37))
        counts_before = dict(tuner._batch_counts[("pr.kern", 4)])
        assert tuner.peek_serving_bucket("pr.kern", 4, 37, bucket_rows(37)) == 37
        assert tuner._batch_counts[("pr.kern", 4)] == counts_before


# ---------------------------------------------------------------------------
# the tune store
# ---------------------------------------------------------------------------


class TestTuneStore:
    def test_round_trip(self, tmp_path):
        path = str(tmp_path / "store.json")
        s = TuneStore(path)
        s.put({
            "knob": "fit_block_rows", "key": "fam", "value": 8192,
            "metric": 0.25, "metric_name": "seconds_per_row",
            "evidence": ["k|aot|x"], "rejected": [], "trials": 1,
            "updated": 0.0,
        })
        s2 = TuneStore(path)
        assert s2.get("fit_block_rows", "fam")["value"] == 8192
        assert s2.get("fit_block_rows", "fam")["evidence"] == ["k|aot|x"]
        doc = json.load(open(path))
        assert doc["version"] == 1 and "fit_block_rows|fam" in doc["decisions"]
        # Atomic write leaves no tmp droppings.
        assert [f for f in os.listdir(tmp_path) if ".tmp." in f] == []

    def test_corrupt_file_falls_back_empty(self, tmp_path):
        path = str(tmp_path / "store.json")
        with open(path, "w") as f:
            f.write("{this is not json")
        clear_counters("autotune.store")
        s = TuneStore(path)
        assert s.corrupt is True
        assert s.snapshot() == []
        assert counter_value("autotune.store.corrupt") == 1
        # The store still works — and heals the file on the next commit.
        s.put({"knob": "k", "key": "f", "value": 1, "metric": 1.0,
               "metric_name": "m", "evidence": [], "rejected": [],
               "trials": 1, "updated": 0.0})
        assert TuneStore(path).get("k", "f")["value"] == 1

    def test_memory_only_store(self):
        s = TuneStore(None)
        s.put({"knob": "k", "key": "f", "value": 2})
        assert s.get("k", "f")["value"] == 2


# ---------------------------------------------------------------------------
# membudget pricing (decision d)
# ---------------------------------------------------------------------------


class TestMembudgetPricing:
    def _guard(self, family, x):
        from spark_rapids_ml_tpu.core.membudget import fit_memory_guard
        from spark_rapids_ml_tpu.robustness.degrade import DegradationWarning

        # Every guard call here is sized to degrade — the warning is the
        # expected outcome, not noise.
        with pytest.warns(DegradationWarning, match="exceeds the fit memory"):
            return fit_memory_guard(
                family, x, can_stream=True, dtype=np.float32,
            )

    def test_parity_without_model(self, tuner, off_budget_env, rng):
        """Tuner on but NO fitted model for the family: admission prices
        exactly like the static arithmetic (bit-identical needed_bytes)."""
        x = rng.normal(size=(1000, 8)).astype(np.float32)
        on = self._guard("nomodel", x)
        autotune.configure(enable=False)
        try:
            off_adm = self._guard("nomodel", x)
        finally:
            autotune.configure(enable=True)
        assert on.degrade and off_adm.degrade
        assert on.needed_bytes == off_adm.needed_bytes
        assert counter_value("fit.admission.model_priced") == 0

    def test_model_prices_admission(self, tuner, off_budget_env, rng):
        """With byte evidence in the ledger, admission prices through the
        fitted model instead of the padding arithmetic."""
        led = costs.active()
        _inject_entry(
            led, "modfam.solve", 500, arg=5000, temp=2500, out=2500,
        )
        x = rng.normal(size=(1000, 8)).astype(np.float32)
        adm = self._guard("modfam", x)
        assert counter_value("fit.admission.model_priced") == 1
        # Single point: bytes_a = 10000/500 = 20/row -> 20000 at n=1000.
        assert adm.needed_bytes == 20000
        assert adm.degrade  # 20000 > the 15000 budget below

    def test_oom_ceiling_caps_recommendations(self, tuner):
        tuner.record_trial("fit_block_rows", "oomfam", 65536, 0.5)
        tuner.note_oom("oomfam", 65536)
        rec = tuner.recommend_block_rows("oomfam", default=DEFAULT_FIT_BLOCK_ROWS)
        assert rec <= 32768  # never at/above the ledgered-fatal block
        # The ceiling survives a store reload.
        t2 = Autotuner(TuneStore(tuner.store.path), hot_min=3)
        assert t2.recommend_block_rows(
            "oomfam", default=DEFAULT_FIT_BLOCK_ROWS
        ) <= 32768


@pytest.fixture
def off_budget_env(monkeypatch):
    monkeypatch.setenv("TPUML_FIT_MEM_BUDGET", "15000")
    clear_counters("fit.admission")
    yield


# ---------------------------------------------------------------------------
# deadline + shard threshold (decision c)
# ---------------------------------------------------------------------------


class TestMeasuredDeadlines:
    def test_delay_tracks_p95_wall(self, tuner):
        assert tuner.recommend_delay_s("cold.kern", 0.005) == 0.005
        for _ in range(20):
            tuner.observe_wall("hot.kern", 256, 0.020)
        assert tuner.recommend_delay_s("hot.kern", 0.005) == pytest.approx(0.020)

    def test_delay_shrinks_for_fast_programs(self, tuner):
        for _ in range(20):
            tuner.observe_wall("fast.kern", 256, 0.0002)
        assert tuner.recommend_delay_s("fast.kern", 0.005) == pytest.approx(0.0002)

    def test_shard_rows_from_wall_model(self, tuner):
        led = costs.active()
        # 1 ms/1k rows slope, measured at two row counts.
        _inject_entry(led, "sh.kern", 1000, wall=4 * 0.001, invocations=4)
        _inject_entry(led, "sh.kern", 4000, wall=4 * 0.004, invocations=4)
        assert tuner.recommend_shard_rows("sh.kern") is None  # no samples yet
        for _ in range(10):
            tuner.observe_wall("sh.kern", 4000, 0.004)
        rows = tuner.recommend_shard_rows("sh.kern")
        # 4x the p95 wall (0.016s) at 1us/row -> 16000 rows, next pow2.
        assert rows == 16384
        assert rows >= 2 * 4000

    def test_batcher_uses_tuned_delay(self, tuner, monkeypatch):
        """The MicroBatcher's gather deadline derives from the tuner."""
        from spark_rapids_ml_tpu.serving.batcher import MicroBatcher

        class _Sig:
            name = "bat.kern"

        class _MV:
            signature = _Sig()

        class _Req:
            version = _MV()

        mb = MicroBatcher.__new__(MicroBatcher)
        mb.max_delay_s = 0.005
        for _ in range(20):
            tuner.observe_wall("bat.kern", 64, 0.001)
        assert mb._delay_s_for(_Req()) == pytest.approx(0.001)
        autotune.configure(enable=False)
        try:
            assert mb._delay_s_for(_Req()) == 0.005
        finally:
            autotune.configure(enable=True)


# ---------------------------------------------------------------------------
# double-buffered training streams (satellite 1)
# ---------------------------------------------------------------------------


class TestDoubleBuffer:
    def test_prefetch_values_order_and_counter(self, off):
        clear_counters("fit.stream")
        blocks = [np.full((2, 2), i, np.float32) for i in range(5)]
        seen = []

        def prepare(b):
            seen.append(int(b[0, 0]))
            return b * 2.0

        got = list(prefetch_blocks(blocks, prepare))
        assert len(got) == 5
        for g, b in zip(got, blocks):
            assert np.array_equal(g, b * 2.0)
        # prepare ran in order, one block ahead of the yields.
        assert seen == [0, 1, 2, 3, 4]
        assert counter_value("fit.stream.prefetched") == 4

    def test_prefetch_empty_and_single(self, off):
        clear_counters("fit.stream")
        assert list(prefetch_blocks([], lambda b: b)) == []
        assert list(prefetch_blocks([np.ones(2)], lambda b: b)) == [
            pytest.approx(np.ones(2))
        ]
        assert counter_value("fit.stream.prefetched") == 0

    def test_linear_streaming_bit_identical(self, off, rng):
        """normal_eq_stats_streaming (now prefetched) == the plain loop
        it replaced, bit for bit."""
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops.linear import (
            normal_eq_stats_streaming,
            raw_moments,
        )

        blocks = [
            (rng.normal(size=(n, 5)), rng.normal(size=(n,)))
            for n in (64, 32, 1, 128)
        ]
        clear_counters("fit.stream")
        got = normal_eq_stats_streaming(blocks, dtype=np.float64)
        assert counter_value("fit.stream.prefetched") == len(blocks) - 1
        # The pre-change loop, replayed verbatim.
        acc = None
        for xb, yb in blocks:
            xj = jnp.asarray(np.ascontiguousarray(xb), dtype=np.float64)
            yj = jnp.asarray(np.ascontiguousarray(yb), dtype=np.float64)
            mask = jnp.ones(xj.shape[0], dtype=xj.dtype)
            stats = raw_moments(xj, yj, mask, precision="highest")
            acc = stats if acc is None else tuple(
                a + s for a, s in zip(acc, stats)
            )
        for g, e in zip(got, acc):
            assert np.array_equal(np.asarray(g), np.asarray(e))

    def test_covariance_streaming_bit_identical(self, off, rng):
        """The prefetched shifted scan == the plain scan, bit for bit."""
        from spark_rapids_ml_tpu.ops.covariance import (
            centered_gram,
            shifted_block_scan,
        )
        import jax.numpy as jnp

        blocks = [rng.normal(size=(n, 4)) for n in (32, 16, 64)]
        zeros = jnp.zeros((4,), dtype=jnp.float64)

        def gram_fn(bs):
            return centered_gram(jnp.asarray(bs, dtype=jnp.float64), zeros)

        shift, gram, s, n = shifted_block_scan(blocks, True, gram_fn)
        # The pre-change loop, replayed verbatim.
        shift2 = gram2 = s2 = None
        n2 = 0
        for b in blocks:
            b = np.asarray(b)
            if shift2 is None:
                shift2 = b.mean(axis=0)
            bs = b - shift2
            g = gram_fn(bs)
            gram2 = g if gram2 is None else gram2 + g
            sb = bs.sum(axis=0)
            s2 = sb if s2 is None else s2 + sb
            n2 += b.shape[0]
        assert np.array_equal(np.asarray(shift), np.asarray(shift2))
        assert np.array_equal(np.asarray(gram), np.asarray(gram2))
        assert np.array_equal(np.asarray(s), np.asarray(s2))
        assert n == n2

    def test_kmeans_streaming_overlap_counted(self, off, rng):
        """lloyd_streaming runs through the prefetch path (overlap
        counter) and stays deterministic across runs."""
        from spark_rapids_ml_tpu.ops.kmeans import lloyd_streaming

        x = rng.normal(size=(200, 3)).astype(np.float64)
        init = x[:4].copy()
        blocks = lambda: (x[i:i + 64] for i in range(0, 200, 64))
        clear_counters("fit.stream")
        c1, cost1, it1 = lloyd_streaming(blocks, init, max_iter=3)
        assert counter_value("fit.stream.prefetched") > 0
        c2, cost2, it2 = lloyd_streaming(blocks, init, max_iter=3)
        assert np.array_equal(np.asarray(c1), np.asarray(c2))
        assert float(cost1) == float(cost2) and it1 == it2


# ---------------------------------------------------------------------------
# the report + prof surfaces
# ---------------------------------------------------------------------------


class TestSurfaces:
    def test_serving_report_carries_tuner_section(self, tuner):
        from spark_rapids_ml_tpu.observability.report import serving_report

        tuner.record_trial("fit_block_rows", "rep.fam", 4096, 0.5)
        doc = serving_report()
        assert doc["autotune"]["enabled"] is True
        assert any(
            d["key"] == "rep.fam" for d in doc["autotune"]["decisions"]
        )

    def test_serving_report_omits_section_when_off(self, off):
        from spark_rapids_ml_tpu.observability.report import serving_report

        assert "autotune" not in serving_report()

    def test_prof_tune_subcommand(self, tuner, capsys):
        from tools import tpuml_prof

        tuner.record_trial(
            "fit_block_rows", "prof.fam", 8192, 0.5, evidence=["e|aot|1"],
        )
        tuner.record_trial("fit_block_rows", "prof.fam", 16384, 0.9)
        assert tpuml_prof.main(["tune", tuner.store.path]) == 0
        out = capsys.readouterr().out
        assert "fit_block_rows[prof.fam] = 8192" in out
        assert "rejected 16384" in out and "regression" in out

    def test_prof_tune_explain(self, tuner, tmp_path, capsys):
        from tools import tpuml_prof

        led = costs.active()
        _inject_entry(
            led, "ex.kern", 1000, wall=2 * 0.002, invocations=2,
            arg=4000, temp=100, out=200,
        )
        ledger_path = str(tmp_path / "ledger.json")
        costs.dump_ledger(ledger_path)
        tuner.record_trial("fit_block_rows", "ex.kern", 2048, 0.1)
        assert tpuml_prof.main(
            ["tune", tuner.store.path, "--explain", "ex.kern",
             "--ledger", ledger_path]
        ) == 0
        out = capsys.readouterr().out
        assert "wall(rows)" in out and "bytes(rows)" in out
        assert "fit_block_rows[ex.kern] = 2048" in out

    def test_prof_tune_corrupt_store(self, tmp_path, capsys):
        from tools import tpuml_prof

        bad = str(tmp_path / "bad.json")
        with open(bad, "w") as f:
            f.write("]]]")
        assert tpuml_prof.main(["tune", bad]) == 2

"""Serving-path program cache (core/serving.py).

The steady-state contract: repeated transform/predict calls are
COMPILE-FREE once their row bucket has been seen — compiles scale with
the number of distinct buckets, never with the number of calls — and
copy-minimal (weights resident across calls, padded scratch donated).
The retrace-regression tests pin this with the serving layer's own
counters AND a ``jax_log_compiles`` capture, so a regression that
sneaks a per-shape retrace into the serving path (the pre-cache
behavior) fails loudly.
"""

import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_ml_tpu.core import serving
from spark_rapids_ml_tpu.core.serving import bucket_rows
from spark_rapids_ml_tpu.utils import tracing


@pytest.fixture(autouse=True)
def _fresh_cache():
    serving.clear_program_cache()
    tracing.clear_counters("serving.")
    yield
    serving.clear_program_cache()


@pytest.fixture(scope="module")
def pca_model():
    from spark_rapids_ml_tpu.feature import PCA

    rng = np.random.default_rng(11)
    return PCA().setK(3).fit(rng.standard_normal((256, 8)))


def _pca_oracle(model, x):
    return np.asarray(x, dtype=np.float64) @ model.pc


class TestBucketPolicy:
    def test_pow2_rounding(self):
        assert bucket_rows(1) == serving.MIN_ROW_BUCKET
        assert bucket_rows(8) == 8
        assert bucket_rows(9) == 16
        assert bucket_rows(100) == 128
        assert bucket_rows(1000) == 1024
        assert bucket_rows(8192) == 8192

    def test_rejects_empty(self):
        with pytest.raises(ValueError, match="at least one row"):
            bucket_rows(0)


class TestRetraceRegression:
    """ISSUE 2 acceptance: compiles == number of distinct buckets."""

    SIZES = (100, 1000, 8192)  # buckets 128 / 1024 / 8192

    def test_compiles_equal_buckets_not_calls(self, pca_model):
        rng = np.random.default_rng(0)
        batches = [rng.standard_normal((n, 8)) for n in self.SIZES]
        base = serving.program_cache_stats()["compiles"]
        # Each size twice, interleaved — 6 calls, 3 buckets.
        for x in batches + batches:
            out = pca_model.transform(x)
            np.testing.assert_allclose(out, _pca_oracle(pca_model, x), atol=1e-8)
        stats = serving.program_cache_stats()
        n_buckets = len({bucket_rows(n) for n in self.SIZES})
        assert stats["compiles"] - base == n_buckets
        assert stats["misses"] == n_buckets
        assert stats["hits"] == 2 * len(self.SIZES) - n_buckets

    def test_warm_path_zero_xla_compiles(self, pca_model, caplog):
        """Second-and-later calls at a seen bucket trigger ZERO XLA
        compiles anywhere in the call — asserted against jax's own
        compile log, not just this layer's counters."""
        rng = np.random.default_rng(1)
        warm = [rng.standard_normal((n, 8)) for n in (100, 90, 1000, 999)]
        for x in warm:
            pca_model.transform(x)  # cold: populate the two buckets
        jax.config.update("jax_log_compiles", True)
        try:
            with caplog.at_level(logging.WARNING, logger="jax._src.dispatch"):
                for x in warm:
                    pca_model.transform(x)
        finally:
            jax.config.update("jax_log_compiles", False)
        compile_lines = [
            r for r in caplog.records if "XLA compilation" in r.getMessage()
        ]
        assert compile_lines == []
        assert serving.program_cache_stats()["compiles"] == 2  # 128 + 1024

    def test_within_bucket_sizes_share_one_program(self, pca_model):
        rng = np.random.default_rng(2)
        for n in (513, 700, 900, 1024):  # all bucket 1024
            pca_model.transform(rng.standard_normal((n, 8)))
        assert serving.program_cache_stats()["compiles"] == 1


class TestServeRows:
    def test_padding_rows_never_leak(self, pca_model):
        rng = np.random.default_rng(3)
        x = rng.standard_normal((5, 8))  # bucket 8, 3 padding rows
        out = pca_model.transform(x)
        assert out.shape == (5, 3)
        np.testing.assert_allclose(out, _pca_oracle(pca_model, x), atol=1e-8)

    def test_device_in_device_out(self, pca_model):
        xd = jnp.asarray(np.random.default_rng(4).standard_normal((33, 8)))
        out = pca_model.transform(xd)
        from spark_rapids_ml_tpu.core.data import is_device_array

        assert is_device_array(out)
        assert out.shape == (33, 3)
        np.testing.assert_allclose(
            np.asarray(out), _pca_oracle(pca_model, np.asarray(xd)), atol=1e-6
        )

    def test_lru_bound_and_evictions(self, pca_model, monkeypatch):
        monkeypatch.setenv("TPUML_SERVING_CACHE_SIZE", "2")
        rng = np.random.default_rng(5)
        for n in (8, 100, 1000, 8192):  # 4 distinct buckets, capacity 2
            pca_model.transform(rng.standard_normal((n, 8)))
        stats = serving.program_cache_stats()
        assert stats["size"] <= 2
        assert stats["evictions"] == 2

    def test_counters_published_via_tracing(self, pca_model):
        pca_model.transform(np.random.default_rng(6).standard_normal((10, 8)))
        snap = tracing.counters("serving.")
        assert snap.get("serving.cache.miss", 0) >= 1
        assert snap.get("serving.compile", 0) >= 1

    def test_donation_only_on_owned_scratch(self, pca_model):
        """A caller's exact-bucket device array must NOT be donated (the
        caller may reuse it); padded/host-ingested scratch may be."""
        xd = jnp.asarray(
            np.random.default_rng(7).standard_normal((16, 8)), dtype=jnp.float32
        )
        out1 = pca_model.transform(xd)
        out2 = pca_model.transform(xd)  # would crash if xd were donated
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2))


class TestServeStream:
    def test_double_buffered_stream_matches_batch(self, pca_model):
        rng = np.random.default_rng(8)
        blocks = [rng.standard_normal((n, 8)) for n in (64, 100, 17, 64)]

        def batches():
            yield from blocks

        outs = list(pca_model.transform(batches()))
        assert [o.shape[0] for o in outs] == [64, 100, 17, 64]
        for blk, out in zip(blocks, outs):
            np.testing.assert_allclose(out, _pca_oracle(pca_model, blk), atol=1e-8)
        # 64-row blocks share one program: buckets {64, 128, 32}.
        assert serving.program_cache_stats()["compiles"] == 3
        assert tracing.counter_value("serving.stream.blocks") == 4

    def test_partitioned_host_transform_uses_stream(self, pca_model):
        rng = np.random.default_rng(9)
        parts = [rng.standard_normal((40, 8)), rng.standard_normal((25, 8))]
        out = pca_model.transform(parts)
        assert out.shape == (65, 3)
        np.testing.assert_allclose(
            out, _pca_oracle(pca_model, np.concatenate(parts)), atol=1e-8
        )


class TestFamiliesServed:
    """Every family's predict/transform runs through the program cache and
    stays correct at off-bucket batch sizes."""

    def _assert_cached_call(self, fn, sizes, make_batch, check):
        for n in sizes:
            check(n, fn(make_batch(n)))
        before = serving.program_cache_stats()["compiles"]
        for n in sizes:
            check(n, fn(make_batch(n)))
        assert serving.program_cache_stats()["compiles"] == before

    def test_kmeans_predict(self):
        from spark_rapids_ml_tpu.clustering import KMeans

        rng = np.random.default_rng(10)
        x = np.concatenate([rng.normal(-4, 0.3, (60, 5)), rng.normal(4, 0.3, (60, 5))])
        model = KMeans().setK(2).setSeed(0).fit(x)
        centers = model.clusterCenters()

        def check(n, labels):
            assert labels.shape == (n,)
            batch = self._batches[n]
            d0 = np.linalg.norm(batch - centers[0], axis=1)
            d1 = np.linalg.norm(batch - centers[1], axis=1)
            np.testing.assert_array_equal(np.asarray(labels), (d1 < d0).astype(labels.dtype))

        self._batches = {n: rng.normal(0, 5, (n, 5)) for n in (7, 130)}
        self._assert_cached_call(
            model.predict, (7, 130), lambda n: self._batches[n], check
        )

    def test_logreg_predict_all(self):
        from spark_rapids_ml_tpu.classification import LogisticRegression

        rng = np.random.default_rng(11)
        x = rng.standard_normal((300, 6))
        y = (x @ np.arange(1, 7) > 0).astype(float)
        model = LogisticRegression().setMaxIter(30).fit((x, y))
        batches = {n: rng.standard_normal((n, 6)) for n in (9, 200)}

        def check(n, out):
            labels = out
            assert labels.shape == (n,)
            probs = model.predictProbability(batches[n])
            assert probs.shape == (n, 2)
            np.testing.assert_allclose(probs.sum(axis=1), 1.0, atol=1e-6)

        self._assert_cached_call(model.predict, (9, 200), lambda n: batches[n], check)

    def test_logreg_threshold_inside_program(self):
        from spark_rapids_ml_tpu.classification import LogisticRegression

        rng = np.random.default_rng(12)
        x = rng.standard_normal((200, 4))
        y = (x[:, 0] > 0).astype(float)
        model = LogisticRegression().setMaxIter(25).fit((x, y))
        q = rng.standard_normal((50, 4))
        probs = model.predictProbability(q)
        model.setThreshold(0.9)
        labels = model.predict(q)
        np.testing.assert_array_equal(
            np.asarray(labels), (probs[:, 1] > 0.9).astype(labels.dtype)
        )

    def test_linreg_predict(self):
        from spark_rapids_ml_tpu.regression import LinearRegression

        rng = np.random.default_rng(13)
        x = rng.standard_normal((200, 5))
        coef = np.array([1.0, -2.0, 0.5, 3.0, 0.0])
        model = LinearRegression().fit((x, x @ coef + 0.7))
        batches = {n: rng.standard_normal((n, 5)) for n in (3, 120)}

        def check(n, pred):
            assert pred.shape == (n,)
            np.testing.assert_allclose(pred, batches[n] @ coef + 0.7, atol=1e-5)

        self._assert_cached_call(model.predict, (3, 120), lambda n: batches[n], check)

    def test_random_forest_predict(self):
        from spark_rapids_ml_tpu.classification import RandomForestClassifier

        rng = np.random.default_rng(14)
        x = np.concatenate([rng.normal(-3, 0.5, (80, 4)), rng.normal(3, 0.5, (80, 4))])
        y = np.concatenate([np.zeros(80), np.ones(80)])
        model = (
            RandomForestClassifier().setNumTrees(5).setMaxDepth(3).fit((x, y))
        )
        batches = {
            n: np.concatenate(
                [rng.normal(-3, 0.3, (n // 2, 4)), rng.normal(3, 0.3, (n - n // 2, 4))]
            )
            for n in (10, 70)
        }

        def check(n, pred):
            assert pred.shape == (n,)
            expected = np.concatenate([np.zeros(n // 2), np.ones(n - n // 2)])
            np.testing.assert_array_equal(np.asarray(pred), expected)

        self._assert_cached_call(model.predict, (10, 70), lambda n: batches[n], check)

    def test_mesh_sharded_weights_take_jit_fallback(self):
        """Centers fitted under a mesh keep working through predict (the
        cached-jit path), not a strict-AOT sharding crash."""
        from jax.sharding import Mesh

        from spark_rapids_ml_tpu.clustering import KMeans

        devs = np.array(jax.devices()[:4]).reshape(4, 1)
        mesh = Mesh(devs, ("data", "model"))
        rng = np.random.default_rng(15)
        x = np.concatenate([rng.normal(-4, 0.3, (40, 4)), rng.normal(4, 0.3, (40, 4))])
        model = KMeans(mesh=mesh).setK(2).setSeed(0).fit(x)
        labels = model.predict(rng.normal(0, 5, (23, 4)))
        assert labels.shape == (23,)
        assert tracing.counter_value("serving.fallback") >= 1


class TestCompileCacheKnob:
    @pytest.fixture(autouse=True)
    def _fresh_wiring(self, monkeypatch):
        # The harness may itself have been handed a cache directory.
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.delenv("TPUML_COMPILE_CACHE_DIR", raising=False)
        serving._reset_compile_cache_wiring_for_tests()
        yield
        serving._reset_compile_cache_wiring_for_tests()

    @pytest.fixture
    def config_calls(self, monkeypatch):
        calls = {}
        monkeypatch.setattr(
            jax.config, "update", lambda k, v: calls.setdefault(k, v)
        )
        return calls

    def test_env_knob_wires_jax_config(self, tmp_path, monkeypatch, config_calls):
        monkeypatch.setenv("TPUML_COMPILE_CACHE_DIR", str(tmp_path / "cc"))
        # force=True stands in for a non-CPU backend (the CPU guard is
        # the point of the next test).
        active = serving.configure_compile_cache(force=True)
        assert active == str(tmp_path / "cc")
        assert config_calls["jax_compilation_cache_dir"] == str(tmp_path / "cc")
        assert config_calls["jax_persistent_cache_min_compile_time_secs"] == 0
        assert (tmp_path / "cc").is_dir()

    def test_cpu_backend_guard(self, tmp_path, monkeypatch):
        """XLA:CPU AOT (de)serialization is unstable on this jaxlib
        (tests/conftest.py) — the knob must be inert on CPU by default."""
        monkeypatch.setenv("TPUML_COMPILE_CACHE_DIR", str(tmp_path / "cc"))
        assert serving.configure_compile_cache() is None

    def test_unset_knob_is_noop(self):
        assert serving.configure_compile_cache() is None

    @pytest.mark.parametrize("force", [False, True])
    def test_jax_variable_wins_and_sets_nothing(
        self, tmp_path, monkeypatch, config_calls, force
    ):
        """Placed from outside: jax reads JAX_COMPILATION_CACHE_DIR itself,
        so neither the repo's own knob nor an explicit path may set a
        directory (or anything else) in code — on any backend."""
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "outside"))
        monkeypatch.setenv("TPUML_COMPILE_CACHE_DIR", str(tmp_path / "cc"))
        assert serving.configure_compile_cache(force=force) == str(
            tmp_path / "outside"
        )
        assert serving.configure_compile_cache(
            str(tmp_path / "explicit"), force=force
        ) == str(tmp_path / "outside")
        assert config_calls == {}
        assert not (tmp_path / "cc").exists()
        assert not (tmp_path / "explicit").exists()

    def test_neither_set_on_cpu_leaves_config_untouched(self, config_calls):
        assert serving.configure_compile_cache() is None
        assert config_calls == {}

    def test_neither_set_off_cpu_uses_the_fixed_checkout_path(
        self, tmp_path, monkeypatch, config_calls
    ):
        """force=True stands in for a non-CPU backend: with no directory
        named anywhere the cache goes to ONE fixed path under the
        checkout — never a temp-, pid- or time-made name (the directory
        is part of jax's cache key)."""
        fixed = serving.DEFAULT_COMPILE_CACHE_DIR
        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert fixed == os.path.join(repo_root, ".jax_compile_cache")
        monkeypatch.setattr(serving, "DEFAULT_COMPILE_CACHE_DIR", str(tmp_path / "fx"))
        assert serving.configure_compile_cache(force=True) == str(tmp_path / "fx")
        assert config_calls["jax_compilation_cache_dir"] == str(tmp_path / "fx")

    def test_fit_path_calls_the_one_function(self, monkeypatch):
        from spark_rapids_ml_tpu.feature import PCA

        seen = []
        monkeypatch.setattr(
            serving, "configure_compile_cache", lambda *a, **k: seen.append(1)
        )
        x = np.random.default_rng(0).normal(size=(32, 4))
        PCA().setK(2).fit(x)
        assert seen


class TestIngestWeightMask:
    """Satellite: user weights COMBINE with the padding-validity mask."""

    def test_mesh_padded_rows_never_gain_weight(self):
        from jax.sharding import Mesh

        from spark_rapids_ml_tpu.core.ingest import prepare_rows

        devs = np.array(jax.devices()[:4]).reshape(4, 1)
        mesh = Mesh(devs, ("data", "model"))
        rng = np.random.default_rng(16)
        x = jnp.asarray(rng.standard_normal((10, 4)))  # pads to 12 rows
        w = np.full(10, 2.5)
        prepared = prepare_rows(x, mesh=mesh, weights=w)
        mask = np.asarray(prepared.mask)
        assert prepared.x.shape[0] == 12
        np.testing.assert_allclose(mask[:10], 2.5)
        np.testing.assert_allclose(mask[10:], 0.0)

    def test_weight_length_mismatch_raises(self):
        from spark_rapids_ml_tpu.core.ingest import prepare_rows

        x = np.random.default_rng(17).standard_normal((10, 4))
        with pytest.raises(ValueError, match="weight vector has 7 entries"):
            prepare_rows(x, weights=np.ones(7))

    def test_single_device_weights_preserved(self):
        from spark_rapids_ml_tpu.core.ingest import prepare_rows

        x = np.random.default_rng(18).standard_normal((6, 3))
        w = np.array([1.0, 2.0, 3.0, 4.0, 5.0, 6.0])
        prepared = prepare_rows(x, weights=w)
        np.testing.assert_allclose(np.asarray(prepared.mask), w)


class TestDeviceFoldTuning:
    """Satellite: CV/TVS place tuning data on device once and reuse
    device-resident fold slices across the param grid."""

    def _data(self):
        rng = np.random.default_rng(19)
        x = rng.standard_normal((90, 5))
        y = x @ np.array([1.0, -1.0, 0.5, 2.0, 0.0]) + 0.3
        return x, y

    def test_prep_gates_on_family_and_container(self):
        from spark_rapids_ml_tpu.regression import LinearRegression
        from spark_rapids_ml_tpu.tuning import _device_fold_prep

        x, y = self._data()
        est = LinearRegression()
        prep = _device_fold_prep((x, y), est)
        assert prep is not None
        from spark_rapids_ml_tpu.core.data import is_device_array

        assert is_device_array(prep.x) and is_device_array(prep.y)

        class NotOurs:
            pass

        assert _device_fold_prep((x, y), NotOurs()) is None
        assert _device_fold_prep("not a dataset", est) is None

    def test_fold_slices_are_device_resident_views(self):
        from spark_rapids_ml_tpu.core.data import is_device_array
        from spark_rapids_ml_tpu.regression import LinearRegression
        from spark_rapids_ml_tpu.tuning import _device_fold_prep

        x, y = self._data()
        prep = _device_fold_prep((x, y), LinearRegression())
        idx = np.array([3, 1, 8])
        xs, ys = prep.slice(idx)
        assert is_device_array(xs) and is_device_array(ys)
        np.testing.assert_allclose(np.asarray(xs), x[idx])
        np.testing.assert_allclose(np.asarray(ys), y[idx])

    def test_cv_metrics_match_host_path(self):
        """Device-resident folds must not change the selected model or the
        per-cell metrics (same values, same fold assignment)."""
        from spark_rapids_ml_tpu.evaluation import RegressionEvaluator
        from spark_rapids_ml_tpu.regression import LinearRegression
        from spark_rapids_ml_tpu.tuning import CrossValidator, ParamGridBuilder

        x, y = self._data()
        lin = LinearRegression()
        grid = ParamGridBuilder().addGrid(lin.regParam, [0.0, 0.5]).build()

        def run(device_foldable):
            est = LinearRegression()
            if not device_foldable:
                est._device_foldable = False
            cv = (
                CrossValidator()
                .setEstimator(est)
                .setEstimatorParamMaps(grid)
                .setEvaluator(RegressionEvaluator())
                .setNumFolds(3)
                .setSeed(42)
            )
            m = cv.fit((x, y))
            return m.bestIndex, np.asarray(m.avgMetrics)

        best_dev, metrics_dev = run(True)
        best_host, metrics_host = run(False)
        assert best_dev == best_host
        np.testing.assert_allclose(metrics_dev, metrics_host, rtol=1e-9)

    def test_tvs_device_folds(self):
        from spark_rapids_ml_tpu.classification import LogisticRegression
        from spark_rapids_ml_tpu.evaluation import (
            MulticlassClassificationEvaluator,
        )
        from spark_rapids_ml_tpu.tuning import (
            ParamGridBuilder,
            TrainValidationSplit,
        )

        rng = np.random.default_rng(20)
        x = rng.standard_normal((120, 4))
        y = (x[:, 0] + 0.2 * x[:, 1] > 0).astype(float)
        lr = LogisticRegression().setMaxIter(25)
        grid = ParamGridBuilder().addGrid(lr.regParam, [0.0, 0.1]).build()
        tvs = (
            TrainValidationSplit()
            .setEstimator(lr)
            .setEstimatorParamMaps(grid)
            .setEvaluator(
                MulticlassClassificationEvaluator().setMetricName("accuracy")
            )
            .setSeed(7)
        )
        model = tvs.fit((x, y))
        assert model.bestModel is not None
        assert max(model.validationMetrics) > 0.8


class TestModelPickling:
    """Device-side serving caches never ship in pickles."""

    def test_models_roundtrip_after_serving(self):
        import pickle

        from spark_rapids_ml_tpu.classification import RandomForestClassifier
        from spark_rapids_ml_tpu.regression import LinearRegression

        rng = np.random.default_rng(21)
        x = rng.standard_normal((60, 4))
        y = (x[:, 0] > 0).astype(float)
        rf = RandomForestClassifier().setNumTrees(3).setMaxDepth(2).fit((x, y))
        lin = LinearRegression().fit((x, x[:, 0]))
        q = rng.standard_normal((12, 4))
        rf.predict(q)
        lin.predict(q)  # populate device caches
        rf2 = pickle.loads(pickle.dumps(rf))
        lin2 = pickle.loads(pickle.dumps(lin))
        assert rf2._forest_dev is None
        assert lin2._coef_dev is None
        np.testing.assert_array_equal(np.asarray(rf2.predict(q)), np.asarray(rf.predict(q)))
        np.testing.assert_allclose(
            np.asarray(lin2.predict(q)), np.asarray(lin.predict(q)), atol=1e-12
        )

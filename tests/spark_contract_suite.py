"""The pyspark adapter CONTRACT SUITE — one set of assertions, two
runners:

  - ``tests/test_spark_adapter.py`` runs it against ``tests/pyspark_stub``
    (the CI image has no pyspark; the stub implements the exact surface
    the adapter consumes with real partition semantics and cloudpickle
    serialization boundaries).
  - ``tests/test_spark_real.py`` runs the SAME classes against genuine
    pyspark when it is installed (``pytest.importorskip``), so the day an
    environment has pyspark the proof is one command.

Each runner module provides its own ``spark_env`` fixture; the helpers
here stay portable across both (e.g. partition counts via the documented
``repartition`` API when ``createDataFrame`` lacks the stub's
``numPartitions`` convenience).
"""

import numpy as np
import pytest


def _vector_df(spark, x, extra=None, n_parts=3):
    from pyspark.ml.linalg import Vectors

    cols = ["features"] + (list(extra) if extra else [])
    rows = []
    for i in range(x.shape[0]):
        row = [Vectors.dense(x[i])]
        if extra:
            row += [extra[c][i] for c in extra]
        rows.append(row)
    try:
        return spark.createDataFrame(rows, cols, numPartitions=n_parts)
    except TypeError:
        # Real pyspark: no numPartitions kwarg — repartition after.
        return spark.createDataFrame(rows, cols).repartition(n_parts)


class TestTpuPCA:
    def test_fit_transform_save_load(self, spark_env, rng, tmp_path):
        adapter, spark = spark_env
        x = rng.normal(size=(300, 6)) * np.linspace(1, 2, 6) + 5.0
        df = _vector_df(spark, x)
        est = adapter.TpuPCA(k=2, inputCol="features", outputCol="pca")
        model = est.fit(df)

        # Oracle: numpy eigh of the covariance, sign-invariant.
        from spark_rapids_ml_tpu.utils.testing import assert_components_close

        cov = np.cov(x, rowvar=False)
        w, v = np.linalg.eigh(cov)
        v = v[:, ::-1]
        pc = np.asarray(model.pc.toArray())
        assert_components_close(pc, v[:, :2], 1e-9)

        out = model.transform(df)
        proj = np.stack([np.asarray(r.pca.toArray()) for r in out.collect()])
        np.testing.assert_allclose(proj, x @ pc, atol=1e-9)

        path = str(tmp_path / "tpupca_model")
        model._save_impl(path)
        loaded = adapter.TpuPCAModel.load(path)
        np.testing.assert_allclose(np.asarray(loaded.pc.toArray()), pc)
        out2 = loaded.transform(df)
        proj2 = np.stack([np.asarray(r.pca.toArray()) for r in out2.collect()])
        np.testing.assert_allclose(proj2, proj)

    def test_estimator_persistence(self, spark_env, tmp_path):
        adapter, spark = spark_env
        est = adapter.TpuPCA(k=3, inputCol="features").setGpuId(0)
        path = str(tmp_path / "tpupca_est")
        est._save_impl(path)
        loaded = adapter.TpuPCA.load(path)
        assert loaded.getOrDefault(loaded.k) == 3
        assert loaded.getOrDefault(loaded.gpuId) == 0


class TestTpuKMeans:
    def test_distributed_lloyd_clusters(self, spark_env, rng, tmp_path):
        adapter, spark = spark_env
        centers_true = np.array([[0.0, 0.0], [8.0, 8.0], [0.0, 8.0]])
        x = np.concatenate(
            [c + rng.normal(scale=0.4, size=(80, 2)) for c in centers_true]
        )
        df = _vector_df(spark, x)
        model = adapter.TpuKMeans(k=3).setSeed(1).setMaxIter(20).fit(df)
        found = np.stack(model.clusterCenters())
        # Each true center has a found center within a small radius.
        for c in centers_true:
            assert np.min(np.linalg.norm(found - c, axis=1)) < 0.3

        out = model.transform(df)
        preds = np.asarray([r.prediction for r in out.collect()])
        # Points from one blob share a label.
        for g in range(3):
            blob = preds[g * 80 : (g + 1) * 80]
            assert len(np.unique(blob)) == 1

        path = str(tmp_path / "kmeans_model")
        model._save_impl(path)
        loaded = adapter.TpuKMeansModel.load(path)
        np.testing.assert_allclose(np.stack(loaded.clusterCenters()), found)


class TestTpuLinearRegression:
    def test_distributed_normal_equations(self, spark_env, rng, tmp_path):
        adapter, spark = spark_env
        d = 5
        x = rng.normal(size=(400, d)) + 10.0
        beta = np.arange(1.0, d + 1.0)
        y = x @ beta + 2.5 + 0.01 * rng.normal(size=400)
        df = _vector_df(spark, x, extra={"label": list(y)})
        model = adapter.TpuLinearRegression().fit(df)

        xi = np.concatenate([x, np.ones((400, 1))], axis=1)
        ref = np.linalg.lstsq(xi, y, rcond=None)[0]
        np.testing.assert_allclose(
            np.asarray(model.coefficients.toArray()), ref[:d], atol=1e-6
        )
        assert model.intercept == pytest.approx(ref[d], abs=1e-4)

        out = model.transform(df)
        preds = np.asarray([r.prediction for r in out.collect()])
        np.testing.assert_allclose(preds, xi @ ref, atol=1e-3)

        path = str(tmp_path / "linreg_model")
        model._save_impl(path)
        loaded = adapter.TpuLinearRegressionModel.load(path)
        np.testing.assert_allclose(
            np.asarray(loaded.coefficients.toArray()),
            np.asarray(model.coefficients.toArray()),
        )

    def test_rejects_elastic_net(self, spark_env, rng):
        adapter, spark = spark_env
        x = rng.normal(size=(20, 2))
        df = _vector_df(spark, x, extra={"label": list(x.sum(axis=1))})
        with pytest.raises(ValueError, match="elasticNetParam"):
            adapter.TpuLinearRegression().setElasticNetParam(0.5).fit(df)


class TestTpuLogisticRegression:
    def test_fit_transform_save_load(self, spark_env, rng, tmp_path):
        adapter, spark = spark_env
        x = rng.normal(size=(300, 4))
        y = (x[:, 0] + 0.5 * x[:, 1] > 0).astype(float)
        df = _vector_df(spark, x, extra={"label": list(y)})
        model = adapter.TpuLogisticRegression().setMaxIter(60).fit(df)

        out = model.transform(df)
        rows = out.collect()
        preds = np.asarray([r.prediction for r in rows])
        assert np.mean(preds == y) > 0.95
        probs = np.stack([np.asarray(r.probability.toArray()) for r in rows])
        assert probs.shape == (300, 2)
        np.testing.assert_allclose(probs.sum(axis=1), 1.0, atol=1e-6)
        raw = np.stack([np.asarray(r.rawPrediction.toArray()) for r in rows])
        assert raw.shape[0] == 300

        path = str(tmp_path / "logreg_model")
        model._save_impl(path)
        loaded = adapter.TpuLogisticRegressionModel.load(path)
        np.testing.assert_allclose(
            np.asarray(loaded.coefficients.toArray()),
            np.asarray(model.coefficients.toArray()),
            atol=1e-12,
        )
        out2 = loaded.transform(df)
        preds2 = np.asarray([r.prediction for r in out2.collect()])
        np.testing.assert_array_equal(preds2, preds)


class TestExecutorMath:
    """The numpy-only executor forwards must agree with the core (JAX)
    models bit-for-tolerance — they are what transform ships to executors
    that have no JAX at all."""

    def test_logistic_forward_matches_core(self, rng):
        from spark_rapids_ml_tpu.classification import LogisticRegression
        from spark_rapids_ml_tpu.spark.executor_math import logistic_forward

        x = rng.normal(size=(200, 4))
        y = (x[:, 0] - x[:, 2] > 0).astype(float)
        core = LogisticRegression().setMaxIter(40).fit((x, y))
        raw, probs, pred = logistic_forward(
            np.asarray(core.weights, dtype=np.float64),
            np.asarray(core.intercepts, dtype=np.float64),
            core.getThreshold(),
            x,
        )
        np.testing.assert_allclose(probs, core.predictProbability(x), atol=1e-6)
        np.testing.assert_allclose(raw, core.predictRaw(x), atol=1e-6)
        np.testing.assert_array_equal(pred, core.predict(x).astype(float))
        # raw really is margins: symmetric around zero for binomial.
        np.testing.assert_allclose(raw[:, 0], -raw[:, 1], atol=1e-12)

    def test_forest_forward_matches_core(self, rng):
        from spark_rapids_ml_tpu.classification import RandomForestClassifier
        from spark_rapids_ml_tpu.models.random_forest import _forest_depth
        from spark_rapids_ml_tpu.spark.executor_math import forest_forward

        x = rng.normal(size=(200, 5))
        y = ((x[:, 0] > 0) & (x[:, 1] > 0)).astype(float)
        core = RandomForestClassifier().setNumTrees(8).setMaxDepth(4).setSeed(3).fit((x, y))
        f = core._forest
        raw, probs, pred = forest_forward(
            np.asarray(f.feature),
            np.asarray(f.threshold, dtype=np.float64),
            np.asarray(f.is_leaf),
            np.asarray(f.leaf_value, dtype=np.float64),
            _forest_depth(f),
            x,
        )
        np.testing.assert_allclose(probs, core.predictProbability(x), atol=1e-6)
        np.testing.assert_allclose(raw, core.predictRaw(x), atol=1e-5)
        np.testing.assert_array_equal(pred, core.predict(x).astype(float))

    def test_executor_math_imports_no_jax(self):
        """Executors must be able to import the module without JAX: verify
        in a subprocess that blocks the jax import outright."""
        import os
        import subprocess
        import sys as _sys

        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        code = (
            "import sys; sys.path.insert(0, %r); "
            "sys.modules['jax'] = None; "  # any jax import -> ImportError
            "import spark_rapids_ml_tpu.spark.executor_math as m; "
            "import numpy as np; "
            "r, p, y = m.logistic_forward(np.ones((3, 1)), np.zeros(1), 0.5, np.ones((2, 3))); "
            "print('NOJAX_OK', p.shape)"
        ) % repo_root
        out = subprocess.run(
            [_sys.executable, "-c", code], capture_output=True, text=True
        )
        assert out.returncode == 0, out.stderr[-1500:]
        assert "NOJAX_OK" in out.stdout


class TestTpuRandomForest:
    def test_fit_transform_save_load(self, spark_env, rng, tmp_path):
        adapter, spark = spark_env
        x = rng.normal(size=(300, 4))
        y = ((x[:, 0] > 0) ^ (x[:, 1] > 0)).astype(float)  # XOR: needs depth
        df = _vector_df(spark, x, extra={"label": list(y)})
        model = (
            adapter.TpuRandomForestClassifier()
            .setNumTrees(15)
            .setMaxDepth(5)
            .setSeed(0)
            .fit(df)
        )
        assert model.numClasses == 2
        out = model.transform(df)
        rows = out.collect()
        preds = np.asarray([r.prediction for r in rows])
        assert np.mean(preds == y) > 0.9
        probs = np.stack([np.asarray(r.probability.toArray()) for r in rows])
        np.testing.assert_allclose(probs.sum(axis=1), 1.0, atol=1e-5)

        path = str(tmp_path / "rf_model")
        model._save_impl(path)
        loaded = adapter.TpuRandomForestClassificationModel.load(path)
        out2 = loaded.transform(df)
        preds2 = np.asarray([r.prediction for r in out2.collect()])
        np.testing.assert_array_equal(preds2, preds)


class TestTpuRandomForestRegressor:
    def test_fit_transform_save_load(self, spark_env, rng, tmp_path):
        adapter, spark = spark_env
        x = rng.uniform(0, 1, size=(300, 3))
        y = 3.0 * x[:, 0] - 2.0 * x[:, 1]
        df = _vector_df(spark, x, extra={"label": list(y)})
        model = (
            adapter.TpuRandomForestRegressor()
            .setNumTrees(20)
            .setMaxDepth(6)
            .setSeed(0)
            .fit(df)
        )
        out = model.transform(df)
        preds = np.asarray([r.prediction for r in out.collect()])
        rmse = float(np.sqrt(np.mean((preds - y) ** 2)))
        assert rmse < 0.4, rmse
        # Executor forward must equal the core (JAX) model's predictions.
        np.testing.assert_allclose(preds, model._core.predict(x), atol=1e-6)

        path = str(tmp_path / "rfr_model")
        model._save_impl(path)
        loaded = adapter.TpuRandomForestRegressionModel.load(path)
        preds2 = np.asarray(
            [r.prediction for r in loaded.transform(df).collect()]
        )
        np.testing.assert_allclose(preds2, preds)


class TestDistributedLogistic:
    def test_distributed_matches_core_optimum(self, spark_env, rng):
        """The per-iteration executor loss/grad fit (scipy L-BFGS-B on the
        driver, numpy treeReduce on executors) must land on the same
        convex optimum as the core single-machine solver."""
        adapter, spark = spark_env
        from spark_rapids_ml_tpu.classification import LogisticRegression

        x = rng.normal(size=(400, 5)) + 2.0
        y = (x[:, 0] - x[:, 1] > 2.0).astype(float)
        df = _vector_df(spark, x, extra={"label": list(y)}, n_parts=4)
        m_dist = (
            adapter.TpuLogisticRegression()
            .setMaxIter(200)
            .setRegParam(0.01)
            .fit(df)
        )
        m_core = (
            LogisticRegression().setMaxIter(400).setRegParam(0.01).fit((x, y))
        )
        # Tight: both optimize the identical objective (population-std
        # standardization matches the core scaler exactly).
        np.testing.assert_allclose(
            np.asarray(m_dist.coefficients.toArray()),
            m_core.coefficients,
            atol=5e-4,
        )
        assert m_dist.intercept == pytest.approx(m_core.intercept, abs=5e-3)

    def test_multinomial_distributed(self, spark_env, rng):
        adapter, spark = spark_env
        x = rng.normal(size=(450, 4))
        y = np.argmax(x[:, :3] + 0.3 * rng.normal(size=(450, 3)), axis=1).astype(float)
        df = _vector_df(spark, x, extra={"label": list(y)}, n_parts=3)
        model = adapter.TpuLogisticRegression().setMaxIter(150).fit(df)
        preds = np.asarray([r.prediction for r in model.transform(df).collect()])
        assert np.mean(preds == y) > 0.8

    def test_elastic_net_distributed_quality(self, spark_env, rng):
        adapter, spark = spark_env
        x = rng.normal(size=(200, 4))
        y = (x[:, 0] > 0).astype(float)
        df = _vector_df(spark, x, extra={"label": list(y)})
        model = (
            adapter.TpuLogisticRegression()
            .setMaxIter(100)
            .setRegParam(0.05)
            .setElasticNetParam(0.5)
            .fit(df)
        )
        preds = np.asarray([r.prediction for r in model.transform(df).collect()])
        assert np.mean(preds == y) > 0.9

    def test_elastic_net_distributed_matches_core_optimum(self, spark_env, rng):
        """Driver-side FISTA over executor gradient sums optimizes the
        same strictly convex objective as the core solver — coefficients
        must agree to optimizer tolerance."""
        adapter, spark = spark_env
        from spark_rapids_ml_tpu.classification import LogisticRegression

        x = rng.normal(size=(300, 5))
        y = (x[:, 0] + 0.5 * x[:, 1] > 0).astype(float)
        df = _vector_df(spark, x, extra={"label": list(y)}, n_parts=4)
        m_dist = (
            adapter.TpuLogisticRegression()
            .setMaxIter(500)
            .setRegParam(0.1)
            .setElasticNetParam(0.5)
            .fit(df)
        )
        m_core = (
            LogisticRegression()
            .setMaxIter(500)
            .setRegParam(0.1)
            .setElasticNetParam(0.5)
            .fit((x, y))
        )
        np.testing.assert_allclose(
            np.asarray(m_dist.coefficients.toArray()),
            m_core.coefficients,
            atol=2e-3,
        )
        assert m_dist.intercept == pytest.approx(m_core.intercept, abs=5e-3)
        # L1 sparsity must survive the distributed route: both solvers
        # zero the same noise features (or neither does).
        dist_zero = np.asarray(m_dist.coefficients.toArray()) == 0
        core_zero = np.asarray(m_core.coefficients) == 0
        np.testing.assert_array_equal(dist_zero, core_zero)

    def test_fractional_label_raises(self, spark_env, rng):
        adapter, spark = spark_env
        x = rng.normal(size=(60, 3))
        y = np.where(np.arange(60) == 7, 1.5, (x[:, 0] > 0).astype(float))
        df = _vector_df(spark, x, extra={"label": list(y)})
        with pytest.raises(ValueError, match="non-negative integers"):
            adapter.TpuLogisticRegression().fit(df)


class TestPySparkPinnedBehaviors:
    """Behaviors the stub pins to pyspark 3.5 documentation.
    Run against the stub these guard the pins; run against genuine
    pyspark (tests/test_spark_real.py) they validate that the pins match
    the real thing — the same assertions either way."""

    def test_tree_aggregate_semantics(self, spark_env, rng):
        adapter, spark = spark_env
        x = rng.normal(size=(20, 2))
        df = _vector_df(
            spark, x, extra={"label": [float(v) for v in range(20, 40)]},
            n_parts=3,
        )
        rdd = df.select("label").rdd
        total = rdd.treeAggregate(
            0.0, lambda acc, row: acc + float(row[0]), lambda a, b: a + b
        )
        assert total == pytest.approx(sum(range(20, 40)))
        # Each partition folds from its OWN zero: a shared mutable zero
        # would multiply-count across partitions.
        appended = rdd.treeAggregate(
            [], lambda acc, row: acc + [float(row[0])], lambda a, b: a + b
        )
        assert sorted(appended) == [float(v) for v in range(20, 40)]

    def test_params_are_instance_owned(self, spark_env):
        adapter, spark = spark_env
        a = adapter.TpuPCA(k=2, inputCol="features")
        b = adapter.TpuPCA(k=3, inputCol="features")
        # pyspark 3.5 Params.__init__ copies class params per instance.
        assert a.getParam("k").parent == a.uid
        assert b.getParam("k").parent == b.uid
        assert a.getParam("k") != b.getParam("k")
        # A foreign instance's Param fails ownership validation with the
        # documented 'does not belong to' ValueError (Params._shouldOwn).
        with pytest.raises(ValueError):
            a.getOrDefault(b.getParam("k"))

    def test_resolve_param_accepts_name_or_owned_param(self, spark_env):
        adapter, spark = spark_env
        est = adapter.TpuPCA(k=2, inputCol="features")
        assert est._resolveParam("k") is est.getParam("k")
        assert est._resolveParam(est.getParam("k")) is est.getParam("k")
        with pytest.raises(TypeError):
            est._resolveParam(42)

    def test_reset_uid_reparents_params(self, spark_env):
        adapter, spark = spark_env
        est = adapter.TpuPCA(k=4, inputCol="features")
        est._resetUid("TpuPCA_restored")
        assert est.uid == "TpuPCA_restored"
        assert est.getParam("k").parent == "TpuPCA_restored"
        assert est.getOrDefault(est.getParam("k")) == 4

    def test_pandas_udf_receives_arrow_typed_series(self, spark_env, rng):
        adapter, spark = spark_env
        from pyspark.ml.functions import vector_to_array
        from pyspark.sql.functions import col, pandas_udf

        x = rng.normal(size=(12, 3))
        df = _vector_df(spark, x, extra={"label": [1.0] * 12}, n_parts=2)

        # Observations must travel back through the udf's RETURN column:
        # on a real cluster the udf runs in a separate worker process, so
        # driver-side closure mutation would be silently discarded.
        @pandas_udf("double")
        def probe_array(series):
            import numpy as _np
            import pandas as pd

            def code(v):
                # pyspark 3.5 Arrow serializer pin: array<double>
                # elements arrive as numpy float64 ndarrays, never lists.
                is_nd = isinstance(v, _np.ndarray)
                is_f64 = is_nd and v.dtype == _np.float64
                return float(len(v)) + 0.25 * is_nd + 0.5 * is_f64

            return pd.Series([code(v) for v in series])

        out = df.withColumn("n", probe_array(vector_to_array(col("features"))))
        codes = [float(r.n) for r in out.collect()]
        assert codes == [3.75] * 12, codes  # len 3, ndarray, float64

        @pandas_udf("double")
        def probe_scalar(series):
            # A double column arrives as a float64-dtype Series, not
            # object; encode the dtype check into the returned values.
            ok = str(series.dtype) == "float64"
            return series + (0.5 if ok else -100.0)

        out2 = df.withColumn("lbl2", probe_scalar(col("label")))
        assert [float(r.lbl2) for r in out2.collect()] == [1.5] * 12


class TestNoDriverCollect:
    """Done-criterion of the distributed fits: instrument the stub RDD and assert
    the forest / elastic-net fits never collect the dataset to the driver
    (only the bounded quantile sample for forests)."""

    def _fetch_counter(self):
        try:
            from pyspark.sql import FETCHED_ROWS
        except ImportError:
            pytest.skip("driver-fetch instrumentation is stub-only")
        return FETCHED_ROWS

    def test_forest_fit_fetches_only_bounded_sample(
        self, spark_env, rng, monkeypatch
    ):
        adapter, spark = spark_env
        monkeypatch.setattr(adapter, "_QUANTILE_SAMPLE_CAP", 64)
        n = 600
        x = rng.normal(size=(n, 4))
        y = (x[:, 0] > 0).astype(float)
        df = _vector_df(spark, x, extra={"label": list(y)}, n_parts=4)
        counter = self._fetch_counter()
        counter["rows"] = 0
        model = (
            adapter.TpuRandomForestClassifier()
            .setNumTrees(8)
            .setMaxDepth(3)
            .fit(df)
        )
        # The inflated Bernoulli draw crosses ~1.2×cap rows (+1 for the
        # first() width probe); the RETAINED sample is strictly <= cap.
        # A 2× wire bound still proves no full collect (600 would fail).
        assert counter["rows"] <= 128, counter["rows"]
        preds = np.asarray(
            [r.prediction for r in model.transform(df).collect()]
        )
        assert np.mean(preds == y) > 0.9

    def test_forest_regressor_fit_fetches_only_bounded_sample(
        self, spark_env, rng, monkeypatch
    ):
        adapter, spark = spark_env
        monkeypatch.setattr(adapter, "_QUANTILE_SAMPLE_CAP", 64)
        n = 500
        x = rng.uniform(0, 1, size=(n, 3))
        y = 2.0 * x[:, 0] - x[:, 1]
        df = _vector_df(spark, x, extra={"label": list(y)}, n_parts=4)
        counter = self._fetch_counter()
        counter["rows"] = 0
        adapter.TpuRandomForestRegressor().setNumTrees(10).setMaxDepth(4).fit(df)
        assert counter["rows"] <= 128, counter["rows"]

    def test_elastic_net_fit_fetches_no_rows(self, spark_env, rng):
        adapter, spark = spark_env
        x = rng.normal(size=(400, 4))
        y = (x[:, 0] > 0).astype(float)
        df = _vector_df(spark, x, extra={"label": list(y)}, n_parts=4)
        counter = self._fetch_counter()
        counter["rows"] = 0
        adapter.TpuLogisticRegression().setMaxIter(50).setRegParam(
            0.05
        ).setElasticNetParam(0.5).fit(df)
        # The only driver fetch allowed is first() probing the width.
        assert counter["rows"] <= 2, counter["rows"]


class TestForestDistributedMatchesCore:
    def test_no_bootstrap_matches_core_predictions(self, spark_env, rng):
        """bootstrap=False at rate 1.0 makes the sample weights all-ones
        on both sides, the quantile sample covers the full (small)
        dataset, and split selection is literally shared
        (ops.trees.split_level) — so the distributed adapter fit and the
        core fit must agree on every training prediction."""
        adapter, spark = spark_env
        from spark_rapids_ml_tpu.classification import RandomForestClassifier

        x = rng.normal(size=(240, 4))
        y = ((x[:, 0] > 0.3) | (x[:, 1] < -0.5)).astype(float)
        df = _vector_df(spark, x, extra={"label": list(y)}, n_parts=3)
        m_dist = (
            adapter.TpuRandomForestClassifier()
            .setNumTrees(6)
            .setMaxDepth(4)
            .setBootstrap(False)
            .setFeatureSubsetStrategy("all")
            .setSeed(3)
            .fit(df)
        )
        m_core = (
            RandomForestClassifier()
            .setNumTrees(6)
            .setMaxDepth(4)
            .setBootstrap(False)
            .setFeatureSubsetStrategy("all")
            .setSeed(3)
            .fit((x, y))
        )
        preds = np.asarray(
            [r.prediction for r in m_dist.transform(df).collect()]
        )
        np.testing.assert_array_equal(preds, m_core.predict(x))

    def test_regressor_no_bootstrap_matches_core(self, spark_env, rng):
        adapter, spark = spark_env
        from spark_rapids_ml_tpu.regression import RandomForestRegressor

        x = rng.uniform(0, 1, size=(200, 3))
        y = 3.0 * x[:, 0] - 2.0 * x[:, 1] + 0.1 * rng.normal(size=200)
        df = _vector_df(spark, x, extra={"label": list(y)}, n_parts=3)
        m_dist = (
            adapter.TpuRandomForestRegressor()
            .setNumTrees(5)
            .setMaxDepth(4)
            .setBootstrap(False)
            .setFeatureSubsetStrategy("all")
            .setSeed(1)
            .fit(df)
        )
        m_core = (
            RandomForestRegressor()
            .setNumTrees(5)
            .setMaxDepth(4)
            .setBootstrap(False)
            .setFeatureSubsetStrategy("all")
            .setSeed(1)
            .fit((x, y))
        )
        preds = np.asarray(
            [r.prediction for r in m_dist.transform(df).collect()]
        )
        np.testing.assert_allclose(preds, m_core.predict(x), atol=1e-4)


class TestNeighborsAdapters:
    def test_nearest_neighbors(self, spark_env, rng):
        adapter, spark = spark_env
        items = rng.normal(size=(200, 6))
        df = _vector_df(spark, items)
        model = adapter.TpuNearestNeighbors(k=4).fit(df)
        out = model.kneighbors(df)
        rows = out.collect()
        idx = np.stack([np.asarray(r.indices) for r in rows]).astype(int)
        dist = np.stack([np.asarray(r.distances) for r in rows])
        assert idx.shape == (200, 4)
        np.testing.assert_array_equal(idx[:, 0], np.arange(200))  # self first
        np.testing.assert_allclose(dist[:, 0], 0.0, atol=1e-5)
        # Oracle check on a handful of rows.
        d2 = ((items[:10, None, :] - items[None]) ** 2).sum(-1)
        np.testing.assert_array_equal(idx[:10], np.argsort(d2, axis=1)[:, :4])

    def test_approximate_nearest_neighbors(self, spark_env, rng):
        adapter, spark = spark_env
        items = rng.normal(size=(300, 8))
        df = _vector_df(spark, items)
        model = (
            adapter.TpuApproximateNearestNeighbors(k=3)
            .setAlgorithm("ivfflat")
            .setAlgoParams({"nlist": 6, "nprobe": 6})
            .fit(df)
        )
        out = model.kneighbors(df)
        rows = out.collect()
        idx = np.stack([np.asarray(r.indices) for r in rows]).astype(int)
        assert idx.shape == (300, 3)
        # nprobe == nlist: exhaustive, so self must be the first hit.
        np.testing.assert_array_equal(idx[:, 0], np.arange(300))

    def test_ann_brute_approx_algorithm(self, spark_env, rng):
        adapter, spark = spark_env
        items = rng.normal(size=(200, 6))
        df = _vector_df(spark, items)
        model = (
            adapter.TpuApproximateNearestNeighbors(k=3)
            .setAlgorithm("brute_approx")
            .fit(df)
        )
        rows = model.kneighbors(df).collect()
        idx = np.stack([np.asarray(r.indices) for r in rows]).astype(int)
        np.testing.assert_array_equal(idx[:, 0], np.arange(200))

    def test_sharded_index_matches_collected(self, spark_env, rng):
        """indexMode='sharded': executor-local shards +
        treeReduce merge must return exactly the collected path's
        neighbors."""
        adapter, spark = spark_env
        items = rng.normal(size=(240, 6))
        df = _vector_df(spark, items)
        queries = rng.normal(size=(30, 6))
        qdf = _vector_df(spark, queries)
        collected = adapter.TpuNearestNeighbors(k=5).fit(df)
        sharded = (
            adapter.TpuNearestNeighbors(k=5).setIndexMode("sharded").fit(df)
        )
        rows_c = collected.kneighbors(qdf).collect()
        rows_s = sharded.kneighbors(qdf).collect()
        idx_c = np.stack([np.asarray(r.indices) for r in rows_c]).astype(int)
        idx_s = np.stack([np.asarray(r.indices) for r in rows_s]).astype(int)
        np.testing.assert_array_equal(idx_s, idx_c)
        d_c = np.stack([np.asarray(r.distances) for r in rows_c])
        d_s = np.stack([np.asarray(r.distances) for r in rows_s])
        np.testing.assert_allclose(d_s, d_c, atol=1e-9)

    def test_sharded_fit_never_collects_items(self, spark_env, rng):
        """The point of sharded mode: the ITEM SET never crosses
        executor->driver. The stub's fetch counter sees only the
        per-partition count rows during fit."""
        adapter, spark = spark_env
        try:
            from pyspark.sql import FETCHED_ROWS
        except ImportError:
            import pytest as _pytest

            _pytest.skip("fetch instrumentation is stub-only")
        items = rng.normal(size=(300, 5))
        df = _vector_df(spark, items)
        FETCHED_ROWS["rows"] = 0
        model = (
            adapter.TpuNearestNeighbors(k=3).setIndexMode("sharded").fit(df)
        )
        # Fit fetches one (partition, count) row per partition — never
        # an item row.
        n_parts = df.rdd.getNumPartitions()
        assert FETCHED_ROWS["rows"] <= n_parts, FETCHED_ROWS["rows"]
        # The search fetches the QUERY vectors (the small side), still
        # never the item set.
        queries = rng.normal(size=(20, 5))
        qdf = _vector_df(spark, queries)
        FETCHED_ROWS["rows"] = 0
        model.kneighbors(qdf).collect()
        assert FETCHED_ROWS["rows"] < 300, FETCHED_ROWS["rows"]

    def test_sharded_ann_brute_matches_collected(self, spark_env, rng):
        adapter, spark = spark_env
        items = rng.normal(size=(200, 6))
        df = _vector_df(spark, items)
        collected = (
            adapter.TpuApproximateNearestNeighbors(k=4)
            .setAlgorithm("brute")
            .fit(df)
        )
        sharded = (
            adapter.TpuApproximateNearestNeighbors(k=4)
            .setAlgorithm("brute")
            .setIndexMode("sharded")
            .fit(df)
        )
        idx_c = np.stack(
            [np.asarray(r.indices) for r in collected.kneighbors(df).collect()]
        ).astype(int)
        idx_s = np.stack(
            [np.asarray(r.indices) for r in sharded.kneighbors(df).collect()]
        ).astype(int)
        np.testing.assert_array_equal(idx_s, idx_c)

    def test_sharded_empty_query_dataset(self, spark_env, rng):
        """Regression (r4 review): an all-filtered query set must come
        back empty, not crash in np.stack."""
        adapter, spark = spark_env
        from pyspark.sql import DataFrame as StubDF

        items = rng.normal(size=(80, 4))
        df = _vector_df(spark, items)
        model = adapter.TpuNearestNeighbors(k=3).setIndexMode("sharded").fit(df)
        empty = StubDF(["features"], [[]])
        assert model.kneighbors(empty).collect() == []

    def test_sharded_ann_rejects_inverted_lists(self, spark_env, rng):
        adapter, spark = spark_env
        items = rng.normal(size=(60, 4))
        df = _vector_df(spark, items)
        with pytest.raises(ValueError, match="sharded"):
            adapter.TpuApproximateNearestNeighbors(k=3).setAlgorithm(
                "ivfflat"
            ).setIndexMode("sharded").fit(df)

    def test_kneighbors_empty_partition(self, spark_env, rng):
        """Empty query partitions (routine after filter/repartition) must
        not kill the kneighbors job (r2 review)."""
        adapter, spark = spark_env
        from pyspark.ml.linalg import Vectors
        from pyspark.sql import DataFrame as StubDF, Row

        items = rng.normal(size=(50, 4))
        df = _vector_df(spark, items)
        model = adapter.TpuNearestNeighbors(k=3).fit(df)
        rows = [Row(["features"], [Vectors.dense(v)]) for v in items[:10]]
        lopsided = StubDF(["features"], [rows[:7], [], rows[7:]])
        out = model.kneighbors(lopsided).collect()
        assert len(out) == 10
        idx = np.stack([np.asarray(r.indices) for r in out])
        assert idx.dtype.kind in "iu" or np.all(idx == idx.astype(int))
        np.testing.assert_array_equal(idx[:, 0].astype(int), np.arange(10))


class TestTpuDBSCANAndUMAP:
    def test_transform_closure_broadcast_once(self, spark_env, rng):
        """The training matrix + fitted values ship as ONE
        broadcast serialization, not one per task closure — the stub's
        torrent-broadcast counter proves it across a multi-partition
        transform and a REPEATED transform (the handle is cached)."""
        adapter, spark = spark_env
        try:
            from pyspark import BROADCAST_VALUE_PICKLES
        except ImportError:
            pytest.skip("broadcast instrumentation is stub-only")
        x = np.concatenate(
            [rng.normal(scale=0.2, size=(40, 3)) + c for c in ([0, 0, 0], [5, 5, 0])]
        )
        df = _vector_df(spark, x)
        model = adapter.TpuDBSCAN().setEps(0.7).setMinSamples(4).fit(df)
        BROADCAST_VALUE_PICKLES["count"] = 0
        model.transform(df).collect()
        model.transform(df).collect()  # cached handle: still one broadcast
        assert BROADCAST_VALUE_PICKLES["count"] == 1, BROADCAST_VALUE_PICKLES

    def test_dbscan(self, spark_env, rng):
        adapter, spark = spark_env
        x = np.concatenate(
            [rng.normal(scale=0.2, size=(50, 3)) + c for c in ([0, 0, 0], [4, 4, 0])]
            + [rng.uniform(-2, 6, size=(8, 3))]
        )
        df = _vector_df(spark, x)
        model = adapter.TpuDBSCAN().setEps(0.7).setMinSamples(4).fit(df)
        preds = np.asarray(
            [r.prediction for r in model.transform(df).collect()]
        ).astype(int)
        # Two dense blobs become two clusters; blob labels are uniform.
        assert len(set(preds[:50])) == 1 and len(set(preds[50:100])) == 1
        assert preds[0] != preds[50]
        np.testing.assert_array_equal(preds, model.labels_)

    def test_umap_build_algo_passthrough(self, spark_env, rng):
        adapter, spark = spark_env
        x = rng.normal(size=(60, 5))
        df = _vector_df(spark, x)
        model = (
            adapter.TpuUMAP()
            .setNEpochs(20)
            .setBuildAlgo("brute_approx")
            .fit(df)
        )
        emb = np.stack(
            [np.asarray(r.embedding.toArray()) for r in model.transform(df).collect()]
        )
        assert emb.shape == (60, 2) and np.isfinite(emb).all()

    def test_umap(self, spark_env, rng):
        adapter, spark = spark_env
        x = np.concatenate(
            [rng.normal(size=(40, 6)) + off for off in (0.0, 12.0)]
        )
        df = _vector_df(spark, x)
        model = (
            adapter.TpuUMAP()
            .setNNeighbors(8)
            .setNEpochs(200)
            .setSeed(0)
            .fit(df)
        )
        rows = model.transform(df).collect()
        emb = np.stack([np.asarray(r.embedding.toArray()) for r in rows])
        assert emb.shape == (80, 2)
        labels = np.repeat([0, 1], 40)
        c0, c1 = emb[labels == 0].mean(0), emb[labels == 1].mean(0)
        spread = np.mean(np.linalg.norm(emb[labels == 0] - c0, axis=1)) + 1e-9
        assert np.linalg.norm(c0 - c1) / spread > 2.0
        # Training rows return their FITTED coordinates exactly
        # (fit_transform semantics through per-partition Arrow batches).
        np.testing.assert_allclose(emb, model.embedding, atol=1e-12)

    def test_dbscan_umap_persistence(self, spark_env, rng, tmp_path):
        adapter, spark = spark_env
        x = np.concatenate(
            [rng.normal(scale=0.2, size=(40, 3)) + c for c in ([0, 0, 0], [4, 4, 0])]
        )
        df = _vector_df(spark, x)
        db = adapter.TpuDBSCAN().setEps(0.7).setMinSamples(4).fit(df)
        p1 = str(tmp_path / "dbscan")
        db._save_impl(p1)
        loaded = adapter.TpuDBSCANModel.load(p1)
        np.testing.assert_array_equal(loaded.labels_, db.labels_)
        preds = np.asarray([r.prediction for r in loaded.transform(df).collect()])
        np.testing.assert_array_equal(preds, db.labels_)

        um = adapter.TpuUMAP().setNNeighbors(8).setNEpochs(50).setSeed(0).fit(df)
        p2 = str(tmp_path / "umap")
        um._save_impl(p2)
        lu = adapter.TpuUMAPModel.load(p2)
        np.testing.assert_allclose(lu.embedding, um.embedding)

    def test_dbscan_lookup_matches_f32_core_storage(self, spark_env, rng, monkeypatch):
        """The fitted-row lookup hashes at the CORE dtype: a core model
        storing f32 (no-x64 platforms) must still match incoming f64 rows
        (r2 review — with x64 on in tests, simulate by downcasting)."""
        adapter, spark = spark_env
        x = np.concatenate(
            [rng.normal(scale=0.2, size=(30, 3)) + c for c in ([0, 0, 0], [4, 4, 0])]
        )
        df = _vector_df(spark, x)
        model = adapter.TpuDBSCAN().setEps(0.7).setMinSamples(4).fit(df)
        # Force the f32 storage a no-x64 platform would produce.
        from spark_rapids_ml_tpu.models.dbscan import DBSCANModel

        # Swap in a core whose STORAGE is genuinely f32 — the ctor casts
        # to the platform dtype (f64 under the x64 test harness), so the
        # f32 array is assigned post-construction to emulate the no-x64
        # platform exactly. The cache keys on core identity, so the swap
        # rebuilds the lookup.
        core32 = DBSCANModel(
            None,
            model._core.fitted,
            model._core.labels_,
            model._core.core_mask_,
        )
        core32.fitted = np.asarray(model._core.fitted, dtype=np.float32)
        assert core32.fitted.dtype == np.float32
        model._core = core32
        preds = np.asarray([r.prediction for r in model.transform(df).collect()])
        np.testing.assert_array_equal(preds, model.labels_)


class TestEstimatorPersistence:
    def test_every_estimator_roundtrips(self, spark_env, tmp_path):
        """Nine estimator classes round-trip their params here (the
        DefaultParamsWritable contract); TpuPCA's round-trip is covered by
        TestTpuPCA.test_estimator_persistence — ten families total."""
        adapter, spark = spark_env
        cases = [
            (adapter.TpuKMeans(k=4).setSeed(7), "k", 4),
            (adapter.TpuLinearRegression().setRegParam(0.5), "regParam", 0.5),
            (adapter.TpuLogisticRegression().setMaxIter(33), "maxIter", 33),
            (adapter.TpuRandomForestClassifier().setNumTrees(9), "numTrees", 9),
            (adapter.TpuRandomForestRegressor().setMaxDepth(7), "maxDepth", 7),
            (adapter.TpuDBSCAN().setEps(0.9), "eps", 0.9),
            (adapter.TpuUMAP().setNNeighbors(11), "nNeighbors", 11),
            (adapter.TpuNearestNeighbors(k=6), "k", 6),
            (adapter.TpuApproximateNearestNeighbors(k=7), "k", 7),
        ]
        for i, (est, pname, expected) in enumerate(cases):
            path = str(tmp_path / f"est_{i}")
            est._save_impl(path)
            loaded = type(est).load(path)
            assert loaded.getOrDefault(loaded.getParam(pname)) == expected, type(est)

    def test_model_picklable_after_transform(self, spark_env, rng):
        """Caching the fitted-row lookup must not break model pickling
        (Spark broadcasts models to executors) — r2 review."""
        adapter, spark = spark_env
        x = np.concatenate(
            [rng.normal(scale=0.2, size=(30, 3)) + c for c in ([0, 0, 0], [4, 4, 0])]
        )
        df = _vector_df(spark, x)
        model = adapter.TpuDBSCAN().setEps(0.7).setMinSamples(4).fit(df)
        model.transform(df).collect()  # builds + caches the lookup
        import cloudpickle

        clone = cloudpickle.loads(cloudpickle.dumps(model))
        preds = np.asarray([r.prediction for r in clone.transform(df).collect()])
        np.testing.assert_array_equal(preds, model.labels_)

    def test_estimator_load_restores_uid(self, spark_env, tmp_path):
        adapter, spark = spark_env
        est = adapter.TpuKMeans(k=3)
        path = str(tmp_path / "uid_est")
        est._save_impl(path)
        loaded = adapter.TpuKMeans.load(path)
        assert loaded.uid == est.uid

    def test_roundtrip_preserves_default_vs_set(self, spark_env, tmp_path):
        """Defaults must come back as DEFAULTS (isSet False) after a
        save/load round trip — DefaultParamsReader semantics (r2 review)."""
        adapter, spark = spark_env
        est = adapter.TpuKMeans(k=3)  # k set explicitly; maxIter a default
        path = str(tmp_path / "def_est")
        est._save_impl(path)
        loaded = adapter.TpuKMeans.load(path)
        assert loaded.isSet(loaded.k)
        assert not loaded.isSet(loaded.maxIter)
        assert loaded.getOrDefault(loaded.maxIter) == 20


class TestBarrierGangRecovery:
    """The documented barrier-stage gang-relaunch recipe
    (docs/PARITY.md "Failure detection / recovery"), EXECUTED — a
    partition task is killed mid-fit on its first attempt; the barrier
    stage must relaunch the WHOLE gang (not just the dead task) and the
    refit must come out correct. Fault injection is a filesystem sentinel
    (attempt state must live outside the task closure: every attempt
    re-deserializes the closure, exactly like a real cluster)."""

    @staticmethod
    def _moments_task(sentinel, log_dir, fail_pid):
        """Per-partition normal-equation moments with a one-shot injected
        failure on partition ``fail_pid``; records every launch."""

        def task(ctx, it):
            import os

            import numpy as _np

            pid = 0 if ctx is None else ctx.partitionId()
            with open(os.path.join(log_dir, f"launches_p{pid}"), "a") as fh:
                fh.write("launch\n")
            xs, ys = [], []
            for r in it:
                xs.append(_np.asarray(r.features.toArray(), dtype=float))
                ys.append(float(r.label))
            xs = _np.asarray(xs)
            ys = _np.asarray(ys)
            if pid == fail_pid and not os.path.exists(sentinel):
                open(sentinel, "w").close()
                raise RuntimeError("injected device failure mid-fit")
            yield (xs.T @ xs, xs.T @ ys)

        return task

    @staticmethod
    def _launch_counts(log_dir, n_parts):
        import os

        counts = []
        for pid in range(n_parts):
            p = os.path.join(log_dir, f"launches_p{pid}")
            counts.append(
                sum(1 for _ in open(p)) if os.path.exists(p) else 0
            )
        return counts

    def test_task_failure_relaunches_gang_and_refits(
        self, spark_env, rng, tmp_path
    ):
        adapter, spark = spark_env
        from spark_rapids_ml_tpu.spark.barrier import barrier_gang_run

        n, d = 200, 4
        x = rng.normal(size=(n, d))
        w_true = rng.normal(size=d)
        y = x @ w_true + 0.01 * rng.normal(size=n)
        df = _vector_df(spark, x, extra={"label": list(y)}, n_parts=2)

        task = self._moments_task(
            str(tmp_path / "fault_fired"), str(tmp_path), fail_pid=1
        )
        parts = barrier_gang_run(df.select("features", "label").rdd, task)

        # The refit after the gang relaunch is CORRECT.
        xtx = sum(p[0] for p in parts)
        xty = sum(p[1] for p in parts)
        w_fit = np.linalg.solve(xtx, xty)
        w_ref = np.linalg.lstsq(x, y, rcond=None)[0]
        np.testing.assert_allclose(w_fit, w_ref, atol=1e-8)

        # The fault really fired, and EVERY gang member relaunched — the
        # healthy partition too (stage-level retry, not per-task).
        import os

        assert os.path.exists(str(tmp_path / "fault_fired"))
        counts = self._launch_counts(str(tmp_path), 2)
        assert counts[1] >= 2, counts  # the killed task retried
        assert counts[0] >= 2, counts  # the healthy task ALSO relaunched

    def test_persistent_failure_escalates_to_driver(
        self, spark_env, rng, tmp_path
    ):
        """A fault that survives every relaunch fails the JOB — the
        escalation end of the reference's throw -> task-fail -> retry
        story (SURVEY §5, rapidsml_jni.cu:101-153 pattern)."""
        adapter, spark = spark_env
        from spark_rapids_ml_tpu.spark.barrier import barrier_gang_run

        x = rng.normal(size=(40, 3))
        df = _vector_df(spark, x, extra={"label": list(x[:, 0])}, n_parts=2)
        log_dir = str(tmp_path)

        def always_fails(ctx, it):
            import os

            pid = 0 if ctx is None else ctx.partitionId()
            with open(os.path.join(log_dir, f"launches_p{pid}"), "a") as fh:
                fh.write("launch\n")
            raise RuntimeError("unrecoverable injected failure")
            yield  # pragma: no cover - generator marker

        with pytest.raises(Exception):
            barrier_gang_run(df.select("features", "label").rdd, always_fails)

        # Stub-only: the scheduler burned its full stage-attempt budget.
        try:
            from pyspark.sql import BARRIER_MAX_ATTEMPTS
        except ImportError:
            pytest.skip("attempt-budget instrumentation is stub-only")
        assert self._launch_counts(log_dir, 1)[0] == BARRIER_MAX_ATTEMPTS

    def test_gang_relaunch_instrumentation_stub(self, spark_env, rng, tmp_path):
        """Stub-only: the barrier scheduler's launch log shows attempt 0
        touching both partitions, then attempt 1 relaunching both — the
        gang-as-a-unit schedule itself, not just its side effects."""
        adapter, spark = spark_env
        try:
            from pyspark.sql import BARRIER_TASK_LAUNCHES
        except ImportError:
            pytest.skip("barrier launch instrumentation is stub-only")
        from spark_rapids_ml_tpu.spark.barrier import barrier_gang_run

        x = rng.normal(size=(60, 3))
        df = _vector_df(spark, x, extra={"label": list(x[:, 0])}, n_parts=2)
        BARRIER_TASK_LAUNCHES.clear()
        task = self._moments_task(
            str(tmp_path / "fault2"), str(tmp_path), fail_pid=0
        )
        barrier_gang_run(df.select("features", "label").rdd, task)
        assert BARRIER_TASK_LAUNCHES == [(0, 0), (1, 0), (1, 1)]

    def test_gang_coordinates_derivation(self, spark_env, rng):
        """Each barrier task derives jax.distributed coordinates from the
        gang roster: same coordinator everywhere, process_id = partition,
        num_processes = gang size."""
        adapter, spark = spark_env
        from spark_rapids_ml_tpu.spark.barrier import (
            barrier_gang_run,
            gang_coordinates,
        )

        x = rng.normal(size=(40, 3))
        df = _vector_df(spark, x, n_parts=2)

        def task(ctx, it):
            list(it)
            if ctx is None:
                return
            yield gang_coordinates(ctx)

        coords = barrier_gang_run(df.select("features").rdd, task)
        assert len(coords) == 2
        assert {c["process_id"] for c in coords} == {0, 1}
        assert all(c["num_processes"] == 2 for c in coords)
        assert len({c["coordinator_address"] for c in coords}) == 1
        assert coords[0]["coordinator_address"].endswith(":8476")

    def test_relaunched_gang_gets_fresh_coordinator_port(
        self, spark_env, rng, tmp_path
    ):
        """The attempt number offsets the coordinator port: a RELAUNCHED
        gang (attempt 1) must derive a different coordinator address than
        the attempt it replaces, so it can never rejoin the dead cohort's
        coordination service (which may outlive its tasks by up to the
        heartbeat timeout while still bound to the old port)."""
        adapter, spark = spark_env
        from spark_rapids_ml_tpu.spark.barrier import (
            barrier_gang_run,
            gang_coordinates,
        )

        x = rng.normal(size=(40, 3))
        df = _vector_df(spark, x, n_parts=2)
        sentinel = str(tmp_path / "port_fault")
        log_dir = str(tmp_path)

        def task(ctx, it):
            import os

            list(it)
            if ctx is None:
                return
            coords = gang_coordinates(ctx)
            attempt = int(ctx.attemptNumber())
            with open(
                os.path.join(log_dir, f"addr_a{attempt}_p{ctx.partitionId()}"),
                "w",
            ) as fh:
                fh.write(coords["coordinator_address"])
            if not os.path.exists(sentinel):
                open(sentinel, "w").close()
                raise RuntimeError("injected failure on the first attempt")
            yield coords

        coords = barrier_gang_run(df.select("features").rdd, task)

        import os

        with open(os.path.join(log_dir, "addr_a0_p0")) as fh:
            addr_attempt0 = fh.read()
        addrs_final = {c["coordinator_address"] for c in coords}
        assert len(addrs_final) == 1  # the relaunched gang agrees
        addr_attempt1 = addrs_final.pop()
        assert addr_attempt1 != addr_attempt0
        host0, _, port0 = addr_attempt0.rpartition(":")
        host1, _, port1 = addr_attempt1.rpartition(":")
        assert host1 == host0
        assert int(port1) == int(port0) + 1  # port + attempt


class TestGangFitPublicAPI:
    """The hand-written per-partition moments gangs above, MIGRATED to
    the public API: ``spark.barrier.gang_fit`` runs one barrier stage
    whose members each call the ordinary ``Estimator.fit`` with
    ``deployMode='gang'``. The stub (and local-master pyspark) runs
    barrier tasks sequentially in one process, so these drive
    SINGLE-member gangs (one partition) — the full member lifecycle
    (coordinate derivation, deploy-mode switch, carrier/telemetry
    propagation, whole-stage relaunch) minus the cross-process
    collectives; tests/multiproc_gang_fit_worker.py proves those.
    Single-member merges are order-deterministic, so parity with the
    single-process fit holds to near-machine tolerance (1e-12 — the
    member's rows arrive as re-stacked partition blocks, whose GEMM
    blocking differs from the monolithic array in the last bit)."""

    def test_gang_fit_linear_matches_single_process(self, spark_env, rng):
        adapter, spark = spark_env
        from spark_rapids_ml_tpu.regression import LinearRegression
        from spark_rapids_ml_tpu.spark.barrier import gang_fit

        n, d = 120, 5
        x = rng.normal(size=(n, d))
        y = x @ rng.normal(size=d) + 0.01 * rng.normal(size=n)
        df = _vector_df(spark, x, extra={"label": list(y)}, n_parts=1)

        models = gang_fit(
            LinearRegression(), df.select("features", "label").rdd,
            labeled=True,
        )
        assert len(models) == 1
        ref = LinearRegression().fit((x, y))
        np.testing.assert_allclose(
            np.asarray(models[0].coefficients),
            np.asarray(ref.coefficients), atol=1e-12, rtol=0,
        )
        np.testing.assert_allclose(
            models[0].intercept, ref.intercept, atol=1e-12, rtol=0
        )

    def test_gang_fit_pca_merged_trace_strict_clean(
        self, spark_env, rng, tmp_path, monkeypatch
    ):
        """One gang fit through the public API leaves ONE merged trace
        that assembles strict-clean (no problems, no orphans): the
        barrier stage span, the member's fit run, and the gang_fit join
        events all share the driver's trace id."""
        adapter, spark = spark_env
        from spark_rapids_ml_tpu.feature import PCA
        from spark_rapids_ml_tpu.observability import events
        from spark_rapids_ml_tpu.observability import trace as tracelib
        from spark_rapids_ml_tpu.spark.barrier import gang_fit

        tdir = tmp_path / "telemetry"
        monkeypatch.setenv(events.TELEMETRY_DIR_ENV, str(tdir))
        events.configure()
        try:
            x = rng.normal(size=(90, 6)) * np.linspace(1, 2, 6)
            df = _vector_df(spark, x, n_parts=1)
            models = gang_fit(PCA().setK(2), df.select("features").rdd)
            events.flush_telemetry()
        finally:
            monkeypatch.delenv(events.TELEMETRY_DIR_ENV)
            events.configure()

        ref = PCA().setK(2).fit([x])
        np.testing.assert_allclose(
            np.asarray(models[0].pc), np.asarray(ref.pc),
            atol=1e-12, rtol=0,
        )

        merged = tracelib.assemble(str(tdir))
        assert merged["problems"] == []
        assert merged["orphan_problems"] == []
        assert len(merged["traces"]) == 1
        (cell,) = merged["traces"].values()
        names = {
            s["name"] for s in merged["trace_cells"][cell["trace_id"]]["spans"]
        }
        assert "barrier gang" in names
        joins = [
            r for r in merged["trace_cells"][cell["trace_id"]]["events"]
            if r["event"] == "gang_fit"
        ]
        assert any(r.get("action") == "join" for r in joins)
        # The tpuml_trace CLI itself is exercised against a gang-fit shard
        # set (strict, as a subprocess) by the 2-process acceptance test
        # in tests/test_gang_fit.py and by the CI "Gang fit" step; no need
        # to pay a second interpreter bring-up here.

    def test_gang_fit_relaunches_whole_stage_and_refits(
        self, spark_env, rng, tmp_path
    ):
        """The recovery story of TestBarrierGangRecovery, through the
        public surface: a member that dies on its first attempt relaunches
        the whole stage and the REFIT through fit() comes out correct."""
        import os

        adapter, spark = spark_env
        from spark_rapids_ml_tpu.regression import LinearRegression
        from spark_rapids_ml_tpu.spark.barrier import _gang_extract, gang_fit

        n, d = 100, 4
        x = rng.normal(size=(n, d))
        y = x @ rng.normal(size=d)
        df = _vector_df(spark, x, extra={"label": list(y)}, n_parts=1)
        sentinel = str(tmp_path / "gang_fit_fault")

        def extract(it):
            if not os.path.exists(sentinel):
                open(sentinel, "w").close()
                raise RuntimeError("injected member death mid-extract")
            return _gang_extract(it, labeled=True)

        models = gang_fit(
            LinearRegression(), df.select("features", "label").rdd,
            extract=extract,
        )
        assert os.path.exists(sentinel)
        ref = LinearRegression().fit((x, y))
        np.testing.assert_allclose(
            np.asarray(models[0].coefficients),
            np.asarray(ref.coefficients), atol=1e-12, rtol=0,
        )

"""Tuning + evaluation tests — CrossValidator / TrainValidationSplit over
real estimators, evaluator metrics vs sklearn oracles."""

import numpy as np
import pytest

from spark_rapids_ml_tpu.core.data import DataFrame
from spark_rapids_ml_tpu.evaluation import (
    BinaryClassificationEvaluator,
    MulticlassClassificationEvaluator,
    RegressionEvaluator,
)
from spark_rapids_ml_tpu.regression import LinearRegression
from spark_rapids_ml_tpu.classification import RandomForestClassifier
from spark_rapids_ml_tpu.tuning import (
    CrossValidator,
    ParamGridBuilder,
    TrainValidationSplit,
)


class TestEvaluators:
    def test_regression_metrics(self):
        y = np.array([1.0, 2.0, 3.0, 4.0])
        p = np.array([1.1, 1.9, 3.2, 3.8])
        ev = RegressionEvaluator()
        assert ev.evaluate((y, p)) == pytest.approx(np.sqrt(np.mean((y - p) ** 2)))
        assert ev.setMetricName("mae").evaluate((y, p)) == pytest.approx(
            np.mean(np.abs(y - p))
        )
        r2 = ev.setMetricName("r2").evaluate((y, p))
        sklearn_metrics = pytest.importorskip("sklearn.metrics")
        assert r2 == pytest.approx(sklearn_metrics.r2_score(y, p))
        assert ev.isLargerBetter()
        assert not ev.setMetricName("rmse").isLargerBetter()

    def test_multiclass_metrics(self):
        sklearn_metrics = pytest.importorskip("sklearn.metrics")
        rng = np.random.default_rng(0)
        y = rng.integers(0, 3, 200).astype(float)
        p = np.where(rng.uniform(size=200) < 0.7, y, rng.integers(0, 3, 200)).astype(float)
        ev = MulticlassClassificationEvaluator()
        # Spark's default metric is f1 (weighted), not accuracy.
        assert ev.getMetricName() == "f1"
        assert ev.evaluate((y, p)) == pytest.approx(
            sklearn_metrics.f1_score(y, p, average="weighted")
        )
        assert ev.setMetricName("accuracy").evaluate((y, p)) == pytest.approx(
            np.mean(y == p)
        )
        assert ev.setMetricName("f1").evaluate((y, p)) == pytest.approx(
            sklearn_metrics.f1_score(y, p, average="weighted")
        )
        assert ev.setMetricName("weightedPrecision").evaluate((y, p)) == pytest.approx(
            sklearn_metrics.precision_score(y, p, average="weighted")
        )
        assert ev.setMetricName("weightedRecall").evaluate((y, p)) == pytest.approx(
            sklearn_metrics.recall_score(y, p, average="weighted")
        )

    def test_binary_auc(self):
        sklearn_metrics = pytest.importorskip("sklearn.metrics")
        rng = np.random.default_rng(1)
        y = rng.integers(0, 2, 300).astype(float)
        s = y * 0.5 + rng.normal(size=300)
        ev = BinaryClassificationEvaluator()
        assert ev.evaluate((y, s)) == pytest.approx(
            sklearn_metrics.roc_auc_score(y, s), abs=1e-9
        )
        pr = ev.setMetricName("areaUnderPR").evaluate((y, s))
        # Trapezoidal PR-AUC differs slightly from sklearn's step-wise AP.
        assert pr == pytest.approx(sklearn_metrics.average_precision_score(y, s), abs=0.02)

    def test_binary_auc_vector_raw(self):
        # Vector-valued rawPrediction column: positive class = last component.
        y = [0.0, 1.0, 1.0, 0.0]
        raw = [np.array([0.8, 0.2]), np.array([0.1, 0.9]),
               np.array([0.3, 0.7]), np.array([0.6, 0.4])]
        df = DataFrame({"label": y, "rawPrediction": raw})
        assert BinaryClassificationEvaluator().evaluate(df) == 1.0

    def test_binary_auc_ties(self):
        sklearn_metrics = pytest.importorskip("sklearn.metrics")
        # All-tied scores: AUC must be exactly 0.5 regardless of row order.
        y = np.array([1.0, 0.0, 1.0, 0.0])
        s = np.full(4, 0.5)
        assert BinaryClassificationEvaluator().evaluate((y, s)) == pytest.approx(0.5)
        # Mixed ties agree with sklearn's tie-grouped AUC.
        rng = np.random.default_rng(3)
        y2 = rng.integers(0, 2, 100).astype(float)
        s2 = np.round(y2 * 0.5 + rng.normal(size=100), 1)  # heavy ties
        assert BinaryClassificationEvaluator().evaluate((y2, s2)) == pytest.approx(
            sklearn_metrics.roc_auc_score(y2, s2), abs=1e-9
        )

    def test_degenerate_single_class(self):
        assert BinaryClassificationEvaluator().evaluate(
            (np.ones(5), np.arange(5.0))
        ) == 0.0


class TestParamGridBuilder:
    def test_cartesian_product(self):
        lr = LinearRegression()
        grid = (
            ParamGridBuilder()
            .addGrid(lr.regParam, [0.0, 0.1, 1.0])
            .addGrid(lr.fitIntercept, [True, False])
            .build()
        )
        assert len(grid) == 6
        assert {pm[lr.regParam] for pm in grid} == {0.0, 0.1, 1.0}

    def test_base_on(self):
        lr = LinearRegression()
        grid = (
            ParamGridBuilder()
            .baseOn({lr.fitIntercept: False})
            .addGrid(lr.regParam, [0.0, 0.5])
            .build()
        )
        assert len(grid) == 2
        assert all(pm[lr.fitIntercept] is False for pm in grid)


def _ridge_data(rng, n=120, d=5):
    x = rng.normal(size=(n, d))
    beta = np.arange(1, d + 1, dtype=float)
    y = x @ beta + 0.1 * rng.normal(size=n)
    return x, y


class TestCrossValidator:
    def test_selects_low_regularization(self, rng):
        # True model is linear and nearly noiseless: heavy L2 must lose.
        x, y = _ridge_data(rng)
        lr = LinearRegression()
        grid = ParamGridBuilder().addGrid(lr.regParam, [0.0, 100.0]).build()
        cv = (
            CrossValidator()
            .setEstimator(lr)
            .setEstimatorParamMaps(grid)
            .setEvaluator(RegressionEvaluator())
            .setNumFolds(3)
            .setSeed(0)
        )
        model = cv.fit((x, y))
        assert model.bestIndex == 0
        assert len(model.avgMetrics) == 2
        assert model.avgMetrics[0] < model.avgMetrics[1]
        # Best model was refit on the full data and predicts well.
        preds = model.transform(x)
        assert np.sqrt(np.mean((preds - y) ** 2)) < 0.2

    def test_classifier_grid_dataframe(self, rng):
        x = rng.normal(size=(150, 4))
        y = (x[:, 0] + x[:, 1] > 0).astype(float)
        df = DataFrame({"features": list(x), "label": list(y)})
        rf = RandomForestClassifier().setNumTrees(5)
        grid = ParamGridBuilder().addGrid(rf.maxDepth, [1, 4]).build()
        cv = (
            CrossValidator()
            .setEstimator(rf)
            .setEstimatorParamMaps(grid)
            .setEvaluator(MulticlassClassificationEvaluator())
            .setNumFolds(3)
            .setSeed(1)
        )
        model = cv.fit(df)
        # Depth 4 beats a decision stump on a 2-feature interaction.
        assert model.bestIndex == 1
        out = model.transform(df)
        acc = np.mean(np.asarray(out.select("prediction")) == y)
        assert acc > 0.9

    def test_model_persistence_roundtrip(self, tmp_path, rng):
        from spark_rapids_ml_tpu.tuning import CrossValidatorModel

        x, y = _ridge_data(rng)
        lr = LinearRegression()
        grid = ParamGridBuilder().addGrid(lr.regParam, [0.0, 1.0]).build()
        model = (
            CrossValidator()
            .setEstimator(lr)
            .setEstimatorParamMaps(grid)
            .setEvaluator(RegressionEvaluator())
            .setSeed(0)
            .fit((x, y))
        )
        path = str(tmp_path / "cvm")
        model.save(path)
        loaded = CrossValidatorModel.load(path)
        assert loaded.bestIndex == model.bestIndex
        np.testing.assert_allclose(loaded.avgMetrics, model.avgMetrics)
        np.testing.assert_allclose(loaded.transform(x), model.transform(x), atol=1e-10)

    def test_binary_evaluator_gets_scores_not_labels(self, rng):
        """AUC on a tuple dataset must rank by continuous probabilities —
        hard 0/1 labels would tie whole grid cells."""
        from spark_rapids_ml_tpu.classification import LogisticRegression
        from spark_rapids_ml_tpu.tuning import _eval_dataset

        x = rng.normal(size=(200, 4))
        y = (x[:, 0] + 0.5 * x[:, 1] + 0.3 * rng.normal(size=200) > 0).astype(float)
        model = LogisticRegression().setMaxIter(50).fit((x, y))
        ev = BinaryClassificationEvaluator()
        y_out, scores = _eval_dataset(model, (x, y), ev)
        # Scores are continuous probabilities, not a handful of hard labels.
        assert len(np.unique(scores)) > 10
        np.testing.assert_array_equal(y_out, y)
        auc_scores = ev.evaluate((y_out, scores))
        auc_labels = ev.evaluate((y, model.predict(x).astype(float)))
        # Probability ranking must dominate the degenerate two-point ROC.
        assert auc_scores >= auc_labels
        assert auc_scores > 0.9

    def test_binary_evaluator_rejects_scoreless_model(self, rng):
        from spark_rapids_ml_tpu.tuning import _eval_dataset

        x, y = _ridge_data(rng)
        model = LinearRegression().fit((x, y))
        with pytest.raises(TypeError, match="predictProbability"):
            _eval_dataset(model, (x, y), BinaryClassificationEvaluator())

    def test_copy_preserves_mesh(self):
        rf = RandomForestClassifier(mesh="sentinel-mesh")
        assert rf.copy({}).mesh == "sentinel-mesh"

    def test_validation_errors(self):
        cv = CrossValidator()
        with pytest.raises(ValueError):
            cv.fit((np.zeros((10, 2)), np.zeros(10)))
        with pytest.raises(ValueError):
            CrossValidator().setNumFolds(1)
        lr = LinearRegression()
        cv = (
            CrossValidator()
            .setEstimator(lr)
            .setEstimatorParamMaps([{}])
            .setEvaluator(RegressionEvaluator())
            .setNumFolds(5)
        )
        with pytest.raises(ValueError):
            cv.fit((np.zeros((3, 2)), np.zeros(3)))


class TestTrainValidationSplit:
    def test_selects_best(self, rng):
        x, y = _ridge_data(rng)
        lr = LinearRegression()
        grid = ParamGridBuilder().addGrid(lr.regParam, [0.0, 100.0]).build()
        tvs = (
            TrainValidationSplit()
            .setEstimator(lr)
            .setEstimatorParamMaps(grid)
            .setEvaluator(RegressionEvaluator())
            .setTrainRatio(0.7)
            .setSeed(2)
        )
        model = tvs.fit((x, y))
        assert model.bestIndex == 0
        assert len(model.validationMetrics) == 2

    def test_ratio_validation(self):
        with pytest.raises(ValueError):
            TrainValidationSplit().setTrainRatio(1.0)
        with pytest.raises(ValueError):
            TrainValidationSplit().setTrainRatio(0.0)

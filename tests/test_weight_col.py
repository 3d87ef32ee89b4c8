"""weightCol (per-row sample weights) — oracle: sklearn sample_weight and
the duplicate-row equivalence (weight w == the row repeated w times)."""

import numpy as np
import pytest

from spark_rapids_ml_tpu.classification import LogisticRegression, RandomForestClassifier
from spark_rapids_ml_tpu.clustering import KMeans
from spark_rapids_ml_tpu.core.data import DataFrame
from spark_rapids_ml_tpu.regression import LinearRegression, RandomForestRegressor


def _wdf(x, y=None, w=None):
    cols = {"features": list(x)}
    if y is not None:
        cols["label"] = list(y)
    if w is not None:
        cols["weight"] = list(w)
    return DataFrame(cols)


class TestLinearWeights:
    def test_matches_sklearn_sample_weight(self, rng):
        linear_model = pytest.importorskip("sklearn.linear_model")
        x = rng.normal(size=(200, 5))
        y = x @ np.arange(1.0, 6.0) + 0.3 * rng.normal(size=200)
        w = rng.uniform(0.1, 3.0, size=200)
        model = LinearRegression().setWeightCol("weight").fit(_wdf(x, y, w))
        skl = linear_model.LinearRegression().fit(x, y, sample_weight=w)
        np.testing.assert_allclose(model.coefficients, skl.coef_, atol=1e-8)
        assert abs(model.intercept - skl.intercept_) < 1e-8

    def test_duplicate_row_equivalence(self, rng):
        x = rng.normal(size=(50, 3))
        y = x @ np.array([1.0, -2.0, 0.5])
        w = np.ones(50)
        w[:10] = 3.0  # first ten rows triple-weighted
        m_w = LinearRegression().setRegParam(0.1).setWeightCol("weight").fit(_wdf(x, y, w))
        x_dup = np.concatenate([x, x[:10], x[:10]])
        y_dup = np.concatenate([y, y[:10], y[:10]])
        m_dup = LinearRegression().setRegParam(0.1).fit((x_dup, y_dup))
        np.testing.assert_allclose(m_w.coefficients, m_dup.coefficients, atol=1e-6)

    def test_weight_validation(self, rng):
        x = rng.normal(size=(20, 3))
        y = x[:, 0]
        with pytest.raises(ValueError, match="non-negative"):
            LinearRegression().setWeightCol("weight").fit(
                _wdf(x, y, -np.ones(20))
            )
        with pytest.raises(TypeError, match="named columns"):
            LinearRegression().setWeightCol("weight").fit((x, y))
        # No weightCol set: tuples keep working.
        LinearRegression().fit((x, y))


class TestLogisticWeights:
    def test_matches_sklearn_sample_weight(self, rng):
        linear_model = pytest.importorskip("sklearn.linear_model")
        x = rng.normal(size=(300, 4))
        y = (x[:, 0] - x[:, 1] > 0).astype(float)
        w = rng.uniform(0.2, 2.0, size=300)
        n, reg = len(y), 0.1
        model = (
            LogisticRegression()
            .setRegParam(reg)
            .setStandardization(False)
            .setWeightCol("weight")
            .setTol(1e-12)
            .fit(_wdf(x, y, w))
        )
        # sklearn C maps through the WEIGHT SUM (our 1/n is 1/sum(w)).
        skl = linear_model.LogisticRegression(
            C=1.0 / (reg * w.sum()), tol=1e-12, max_iter=10_000
        ).fit(x, y, sample_weight=w)
        np.testing.assert_allclose(
            model.coefficients, skl.coef_.ravel(), atol=1e-4
        )

    def test_standardized_duplicate_equivalence(self, rng):
        # With standardization ON (the default) and L2, integer weights must
        # equal row duplication — this exercises the weighted feature
        # moments (a squared mask in the variance would break it).
        x = rng.normal(size=(120, 3)) * np.array([1.0, 10.0, 0.1])
        y = (x[:, 0] + 0.1 * x[:, 1] > 0).astype(float)
        w = np.ones(120)
        w[:30] = 2.0
        m_w = (
            LogisticRegression()
            .setRegParam(0.2)
            .setWeightCol("weight")
            .setTol(1e-12)
            .fit(_wdf(x, y, w))
        )
        x_dup = np.concatenate([x, x[:30]])
        y_dup = np.concatenate([y, y[:30]])
        m_dup = LogisticRegression().setRegParam(0.2).setTol(1e-12).fit((x_dup, y_dup))
        np.testing.assert_allclose(m_w.coefficients, m_dup.coefficients, atol=1e-6)

    def test_weights_shift_boundary(self, rng):
        # Upweighting one class pushes the decision boundary toward recall
        # on that class.
        x = rng.normal(size=(400, 2))
        y = (x[:, 0] > 0.3).astype(float)
        w_pos = np.where(y == 1, 10.0, 1.0)
        m_plain = LogisticRegression().fit((x, y))
        m_wpos = LogisticRegression().setWeightCol("weight").fit(_wdf(x, y, w_pos))
        recall_plain = np.mean(m_plain.predict(x)[y == 1] == 1)
        recall_w = np.mean(m_wpos.predict(x)[y == 1] == 1)
        assert recall_w >= recall_plain


class TestKMeansWeights:
    def test_weights_pull_centers(self, rng):
        # Two blobs; massively upweighting one point of blob A drags its
        # center toward that point.
        x = np.concatenate([rng.normal(size=(50, 2)), rng.normal(size=(50, 2)) + 8])
        w = np.ones(100)
        x[0] = [-5.0, -5.0]
        w[0] = 50.0
        model = KMeans().setK(2).setSeed(0).setWeightCol("weight").fit(_wdf(x, w=w))
        centers = model.clusterCenters()
        # One center must sit near the heavy point's pull direction.
        d_heavy = np.min(np.linalg.norm(centers - np.array([-5.0, -5.0]), axis=1))
        assert d_heavy < 4.0

    def test_duplicate_row_equivalence(self, rng):
        x = np.concatenate([rng.normal(size=(40, 3)), rng.normal(size=(40, 3)) + 6])
        w = np.ones(80)
        w[:5] = 4.0
        m_w = KMeans().setK(2).setSeed(1).setWeightCol("weight").fit(_wdf(x, w=w))
        x_dup = np.concatenate([x] + [x[:5]] * 3)
        m_dup = KMeans().setK(2).setSeed(1).fit(x_dup)
        # Same blobs recovered: centers agree up to ordering.
        c1 = np.asarray(sorted(m_w.clusterCenters().tolist()))
        c2 = np.asarray(sorted(m_dup.clusterCenters().tolist()))
        np.testing.assert_allclose(c1, c2, atol=0.5)


class TestForestWeights:
    def test_weighted_classes_change_leaves(self, rng):
        x = rng.normal(size=(300, 4))
        y = (x[:, 0] > 1.0).astype(float)  # imbalanced: ~16% positives
        w = np.where(y == 1, 8.0, 1.0)
        m_plain = RandomForestClassifier().setNumTrees(10).setSeed(0).fit((x, y))
        m_w = (
            RandomForestClassifier()
            .setNumTrees(10)
            .setSeed(0)
            .setWeightCol("weight")
            .fit(_wdf(x, y, w))
        )
        recall_plain = np.mean(m_plain.predict(x)[y == 1] == 1)
        recall_w = np.mean(m_w.predict(x)[y == 1] == 1)
        assert recall_w >= recall_plain

    def test_regressor_weighted_mean_leaves(self, rng):
        # Weight 0 rows are invisible: fitting with poisoned rows at weight
        # 0 equals fitting without them.
        x = rng.uniform(0, 1, size=(200, 2))
        y = 2.0 * x[:, 0]
        x_poison = np.concatenate([x, rng.uniform(0, 1, size=(50, 2))])
        y_poison = np.concatenate([y, np.full(50, 100.0)])
        w = np.concatenate([np.ones(200), np.zeros(50)])
        m_w = (
            RandomForestRegressor()
            .setNumTrees(5)
            .setSeed(2)
            .setBootstrap(False)
            .setWeightCol("weight")
            .fit(_wdf(x_poison, y_poison, w))
        )
        preds = m_w.predict(x)
        assert np.sqrt(np.mean((preds - y) ** 2)) < 0.3  # poison ignored

    def test_fractional_weights_route_to_exact_histograms(self):
        """bf16 one-pass histograms are only used when the full histogram
        operand — sample_weight * stat — survives bf16 rounding (a
        fractional weightCol could flip near-tie splits under DEFAULT
        precision; the bound must cover the bootstrap multiplicity too)."""
        from spark_rapids_ml_tpu.models.random_forest import _hist_exact_in_bf16

        onehot = np.eye(3, dtype=np.float32)[np.array([0, 1, 2, 1])]
        assert _hist_exact_in_bf16(onehot, np.ones(4))  # integer counts: exact
        assert _hist_exact_in_bf16(onehot * 8.0, np.full(4, 4.0))  # 32 <= 256
        assert not _hist_exact_in_bf16(onehot * 0.3, np.ones(4))  # fractional
        # bf16-exact stats whose product with a bootstrap draw of 3 exceeds
        # the bf16 odd-integer range (129 * 3 = 387 > 256): lossy.
        assert not _hist_exact_in_bf16(onehot * 129.0, np.full(4, 3.0))
        # fractional sample weights (not produced today) must also disqualify
        assert not _hist_exact_in_bf16(onehot, np.full(4, 0.3))

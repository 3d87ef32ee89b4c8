"""Elastic-net solvers — oracle is scikit-learn's coordinate descent/liblinear.

With ``standardization=False`` the objectives match sklearn's exactly:
  linear:   1/(2n)||y - Xb - b0||^2 + reg*(alpha*||b||_1 + (1-alpha)/2*||b||^2)
  logistic: (1/n) sum logloss + reg*(alpha*||w||_1 + (1-alpha)/2*||w||^2)
so fitted coefficients must agree to optimization tolerance.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from spark_rapids_ml_tpu.classification import LogisticRegression
from spark_rapids_ml_tpu.ops.linear import normal_eq_stats, solve_elastic_net
from spark_rapids_ml_tpu.regression import LinearRegression


def _sparse_problem(rng, n=300, d=12, informative=4, noise=0.05):
    x = rng.normal(size=(n, d))
    beta = np.zeros(d)
    beta[:informative] = np.array([3.0, -2.0, 1.5, 1.0])[:informative]
    y = x @ beta + 2.0 + noise * rng.normal(size=n)
    return x, y, beta


class TestLinearElasticNet:
    def test_lasso_matches_sklearn(self, rng):
        linear_model = pytest.importorskip("sklearn.linear_model")
        x, y, _ = _sparse_problem(rng)
        reg = 0.1
        stats = normal_eq_stats(jnp.asarray(x), jnp.asarray(y), jnp.ones(len(y)))
        coef, intercept, n_iter, _, _ = solve_elastic_net(
            stats, reg_param=reg, elastic_net_param=1.0,
            standardization=False, max_iter=2000, tol=1e-9,
        )
        skl = linear_model.Lasso(alpha=reg, max_iter=50_000, tol=1e-10).fit(x, y)
        np.testing.assert_allclose(np.asarray(coef), skl.coef_, atol=1e-4)
        assert abs(float(intercept) - skl.intercept_) < 1e-4

    def test_elastic_net_matches_sklearn(self, rng):
        linear_model = pytest.importorskip("sklearn.linear_model")
        x, y, _ = _sparse_problem(rng, n=400, d=10)
        reg, l1_ratio = 0.2, 0.5
        stats = normal_eq_stats(jnp.asarray(x), jnp.asarray(y), jnp.ones(len(y)))
        coef, intercept, *_ = solve_elastic_net(
            stats, reg_param=reg, elastic_net_param=l1_ratio,
            standardization=False, max_iter=2000, tol=1e-9,
        )
        skl = linear_model.ElasticNet(
            alpha=reg, l1_ratio=l1_ratio, max_iter=50_000, tol=1e-10
        ).fit(x, y)
        np.testing.assert_allclose(np.asarray(coef), skl.coef_, atol=1e-4)

    def test_alpha_zero_equals_ridge(self, rng):
        from spark_rapids_ml_tpu.ops.linear import solve_normal

        x, y, _ = _sparse_problem(rng)
        stats = normal_eq_stats(jnp.asarray(x), jnp.asarray(y), jnp.ones(len(y)))
        c_enet, i_enet, *_ = solve_elastic_net(
            stats, reg_param=0.3, elastic_net_param=0.0, max_iter=2000, tol=1e-9,
        )
        c_ridge, i_ridge = solve_normal(stats, reg_param=0.3)
        np.testing.assert_allclose(np.asarray(c_enet), np.asarray(c_ridge), atol=1e-5)
        assert abs(float(i_enet) - float(i_ridge)) < 1e-5

    def test_l1_produces_sparsity(self, rng):
        x, y, beta = _sparse_problem(rng, d=20, informative=3)
        model = (
            LinearRegression()
            .setRegParam(0.5)
            .setElasticNetParam(1.0)
            .setStandardization(False)
            .fit((x, y))
        )
        coef = model.coefficients
        # Noise features must be zeroed; informative ones survive.
        assert np.sum(np.abs(coef) > 1e-6) <= 6
        assert np.all(np.abs(coef[:3]) > 0.1)

    def test_estimator_path_no_intercept(self, rng):
        linear_model = pytest.importorskip("sklearn.linear_model")
        x, y, _ = _sparse_problem(rng)
        model = (
            LinearRegression()
            .setRegParam(0.1)
            .setElasticNetParam(1.0)
            .setFitIntercept(False)
            .setStandardization(False)
            .fit((x, y))
        )
        skl = linear_model.Lasso(
            alpha=0.1, fit_intercept=False, max_iter=50_000, tol=1e-10
        ).fit(x, y)
        np.testing.assert_allclose(model.coefficients, skl.coef_, atol=1e-4)
        assert model.intercept == 0.0

    def test_param_validation(self):
        with pytest.raises(ValueError):
            LinearRegression().setElasticNetParam(1.5)
        with pytest.raises(ValueError):
            LogisticRegression().setElasticNetParam(-0.1)

    def test_normal_solver_rejects_l1(self, rng):
        x, y, _ = _sparse_problem(rng)
        with pytest.raises(ValueError, match="solver='normal'"):
            (
                LinearRegression()
                .setSolver("normal")
                .setElasticNetParam(0.5)
                .setRegParam(0.1)
                .fit((x, y))
            )

    def test_zero_regparam_uses_exact_solve(self, rng):
        # enet > 0 with regParam == 0 is a zero penalty: must match the
        # exact unregularized solve, not a proximal approximation of it.
        x, y, _ = _sparse_problem(rng)
        m_enet = LinearRegression().setElasticNetParam(0.7).fit((x, y))
        m_ols = LinearRegression().fit((x, y))
        np.testing.assert_allclose(m_enet.coefficients, m_ols.coefficients, atol=1e-12)


class TestLogisticElasticNet:
    def test_l1_matches_sklearn(self, rng):
        linear_model = pytest.importorskip("sklearn.linear_model")
        x = rng.normal(size=(500, 8))
        logits = 2.0 * x[:, 0] - 1.5 * x[:, 1] + 0.5
        y = (rng.uniform(size=500) < 1 / (1 + np.exp(-logits))).astype(float)
        n, reg = len(y), 0.02
        model = (
            LogisticRegression()
            .setRegParam(reg)
            .setElasticNetParam(1.0)
            .setStandardization(False)
            .setMaxIter(3000)
            .setTol(1e-10)
            .fit((x, y))
        )
        # sklearn: min ||w||_1 + C sum logloss  <=>  ours with reg = 1/(C n).
        # saga, not liblinear: liblinear penalizes the intercept. penalty
        # must be EXPLICIT: without it sklearn keeps the default l2 and
        # silently ignores l1_ratio — the oracle would be a different
        # optimization problem.
        skl = linear_model.LogisticRegression(
            penalty="elasticnet", l1_ratio=1.0, C=1.0 / (reg * n),
            solver="saga", tol=1e-12, max_iter=100_000,
        ).fit(x, y)
        np.testing.assert_allclose(
            model.coefficients, skl.coef_.ravel(), atol=1e-4
        )
        assert abs(model.intercept - skl.intercept_[0]) < 1e-4

    def test_l1_sparsity_and_accuracy(self, rng):
        x = rng.normal(size=(400, 15))
        y = (x[:, 0] + x[:, 1] > 0).astype(float)
        model = (
            LogisticRegression()
            .setRegParam(0.05)
            .setElasticNetParam(1.0)
            .setMaxIter(2000)
            .fit((x, y))
        )
        coef = model.coefficients
        assert np.sum(np.abs(coef) > 1e-5) <= 6  # noise features pruned
        assert np.mean(model.predict(x) == y) > 0.9

    def test_multinomial_elastic_net(self, rng):
        x = rng.normal(size=(450, 6))
        y = np.argmax(x[:, :3], axis=1).astype(float)
        model = (
            LogisticRegression()
            .setRegParam(0.01)
            .setElasticNetParam(0.5)
            .setFamily("multinomial")
            .setMaxIter(2000)
            .fit((x, y))
        )
        assert np.mean(model.predict(x) == y) > 0.85
        assert model.coefficientMatrix.shape == (3, 6)

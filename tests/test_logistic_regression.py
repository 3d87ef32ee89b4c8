"""LogisticRegression suite. Oracle: scikit-learn's lbfgs solver — its
objective sum_i logloss + 1/(2C) ||w||^2 equals this framework's
(1/n) sum logloss + regParam/2 ||w||^2 at C = 1/(n*regParam) — plus
optimality-condition (gradient ~ 0) checks that need no external solver."""

import numpy as np
import pytest

from spark_rapids_ml_tpu.classification import LogisticRegression, LogisticRegressionModel
from spark_rapids_ml_tpu.core.data import DataFrame
from spark_rapids_ml_tpu.parallel.mesh import make_mesh


def make_binary(rng, n=400, d=5, sep=1.5):
    w = rng.normal(size=d)
    x = rng.normal(size=(n, d))
    logits = x @ w * sep
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-logits))).astype(np.int64)
    # ensure both classes present
    y[0], y[1] = 0, 1
    return x, y


def make_multiclass(rng, n=600, d=6, c=4):
    centers = rng.normal(size=(c, d)) * 2.0
    y = rng.integers(0, c, size=n)
    x = centers[y] + rng.normal(size=(n, d))
    for j in range(c):
        y[j] = j
    return x, y


def sklearn_logreg(x, y, reg, fit_intercept=True, multi=False):
    from sklearn.linear_model import LogisticRegression as SkLR

    n = len(y)
    c_val = 1.0 / (n * reg) if reg > 0 else 1e12
    clf = SkLR(
        C=c_val,
        fit_intercept=fit_intercept,
        solver="lbfgs",
        max_iter=5000,
        tol=1e-10,
    )
    clf.fit(x, y)
    return clf


class TestBinomial:
    def test_matches_sklearn_regularized(self, rng):
        x, y = make_binary(rng)
        reg = 0.1
        # standardization off => plain L2 in original space == sklearn's
        model = (
            LogisticRegression()
            .setRegParam(reg)
            .setStandardization(False)
            .setTol(1e-10)
            .setMaxIter(500)
            .fit((x, y))
        )
        clf = sklearn_logreg(x, y, reg)
        np.testing.assert_allclose(model.coefficients, clf.coef_[0], atol=2e-4)
        assert model.intercept == pytest.approx(clf.intercept_[0], abs=2e-4)

    def test_gradient_zero_at_solution(self, rng):
        """KKT check: gradient of the objective vanishes at the fit."""
        x, y = make_binary(rng)
        reg = 0.05
        model = (
            LogisticRegression()
            .setRegParam(reg)
            .setStandardization(False)
            .setTol(1e-10)
            .setMaxIter(500)
            .fit((x, y))
        )
        w, b = model.coefficients, model.intercept
        p = 1 / (1 + np.exp(-(x @ w + b)))
        grad_w = x.T @ (p - y) / len(y) + reg * w
        grad_b = np.mean(p - y)
        assert np.abs(grad_w).max() < 1e-6
        assert abs(grad_b) < 1e-6

    def test_standardization_matches_sklearn_on_scaled(self, rng):
        """standardization=True == sklearn trained on scaled features with
        coefficients mapped back."""
        x, y = make_binary(rng)
        x = x * np.array([10.0, 0.1, 1.0, 5.0, 0.5])  # wild scales
        reg = 0.1
        model = (
            LogisticRegression().setRegParam(reg).setTol(1e-10).setMaxIter(500).fit((x, y))
        )
        mu, sd = x.mean(0), x.std(0)
        clf = sklearn_logreg((x - mu) / sd, y, reg)
        coef_back = clf.coef_[0] / sd
        b_back = clf.intercept_[0] - (clf.coef_[0] * mu / sd).sum()
        np.testing.assert_allclose(model.coefficients, coef_back, atol=2e-4)
        assert model.intercept == pytest.approx(b_back, abs=2e-4)

    def test_no_intercept_standardized_matches_sklearn(self, rng):
        """fitIntercept=False must scale but NOT center (no intercept to
        absorb the shift): equals sklearn on x/sigma with coef mapped back."""
        x, y = make_binary(rng)
        x = x + 3.0  # nonzero means make centering bugs visible
        reg = 0.1
        model = (
            LogisticRegression()
            .setFitIntercept(False)
            .setRegParam(reg)
            .setTol(1e-10)
            .setMaxIter(500)
            .fit((x, y))
        )
        sd = x.std(0)
        clf = sklearn_logreg(x / sd, y, reg, fit_intercept=False)
        np.testing.assert_allclose(model.coefficients, clf.coef_[0] / sd, atol=2e-4)
        assert model.intercept == 0.0

    def test_separable_unregularized_predicts_perfectly(self, rng):
        x = rng.normal(size=(100, 3))
        y = (x[:, 0] > 0).astype(np.int64)
        model = LogisticRegression().setMaxIter(200).fit((x, y))
        assert (model.predict(x) == y).mean() == 1.0

    def test_threshold(self, rng):
        x, y = make_binary(rng)
        model = LogisticRegression().setRegParam(0.1).fit((x, y))
        p = model.predictProbability(x)
        assert p.shape == (len(y), 2)
        np.testing.assert_allclose(p.sum(1), 1.0, atol=1e-6)
        model.setThreshold(0.0)
        assert (model.predict(x) == 1).all()
        model.setThreshold(1.0)
        assert (model.predict(x) == 0).all()

    def test_probability_calibration_vs_sklearn(self, rng):
        x, y = make_binary(rng)
        model = (
            LogisticRegression().setRegParam(0.2).setStandardization(False).fit((x, y))
        )
        clf = sklearn_logreg(x, y, 0.2)
        np.testing.assert_allclose(
            model.predictProbability(x), clf.predict_proba(x), atol=1e-3
        )


class TestMultinomial:
    def test_matches_sklearn_multinomial(self, rng):
        x, y = make_multiclass(rng)
        reg = 0.1
        model = (
            LogisticRegression()
            .setRegParam(reg)
            .setStandardization(False)
            .setTol(1e-10)
            .setMaxIter(500)
            .fit((x, y))
        )
        clf = sklearn_logreg(x, y, reg, multi=True)
        # sklearn's multinomial softmax is also over-parameterized + L2 =>
        # same unique solution.
        np.testing.assert_allclose(model.coefficientMatrix, clf.coef_, atol=5e-4)
        np.testing.assert_allclose(model.interceptVector, clf.intercept_, atol=5e-4)

    def test_family_auto_picks_multinomial(self, rng):
        x, y = make_multiclass(rng, c=3)
        model = LogisticRegression().setRegParam(0.1).fit((x, y))
        assert model.numClasses == 3
        assert model.coefficientMatrix.shape == (3, x.shape[1])
        assert model.interceptVector.shape == (3,)
        with pytest.raises(AttributeError):
            model.coefficients

    def test_multinomial_two_class_consistent_with_binomial(self, rng):
        """Unregularized: the 2-class softmax and the sigmoid have the same
        optimum in probability space."""
        x, y = make_binary(rng)
        m_bin = LogisticRegression().setTol(1e-9).fit((x, y))
        m_mult = (
            LogisticRegression().setFamily("multinomial").setTol(1e-9).fit((x, y))
        )
        np.testing.assert_allclose(
            m_bin.predictProbability(x), m_mult.predictProbability(x), atol=1e-3
        )

    def test_multinomial_two_class_l2_relation(self, rng):
        """Under L2 the softmax splits the penalty across both class columns:
        in difference space D = w1 - w0 the softmax objective is
        logloss(D) + (reg/4)||D||^2, so multinomial(2*reg) == binomial(reg)
        in probability space."""
        x, y = make_binary(rng)
        m_bin = (
            LogisticRegression()
            .setRegParam(0.1)
            .setStandardization(False)
            .setTol(1e-10)
            .fit((x, y))
        )
        m_mult = (
            LogisticRegression()
            .setFamily("multinomial")
            .setRegParam(0.2)
            .setStandardization(False)
            .setTol(1e-10)
            .fit((x, y))
        )
        np.testing.assert_allclose(
            m_bin.predictProbability(x), m_mult.predictProbability(x), atol=1e-4
        )
        # and the softmax solution is antisymmetric: w0 = -w1
        cm = m_mult.coefficientMatrix
        np.testing.assert_allclose(cm[0], -cm[1], atol=1e-5)

    def test_unregularized_centered(self, rng):
        x, y = make_multiclass(rng, c=3)
        model = LogisticRegression().setMaxIter(100).fit((x, y))
        # identifiability pivot: class-axis mean of coefficients ~ 0
        np.testing.assert_allclose(
            model.coefficientMatrix.mean(axis=0), 0.0, atol=1e-6
        )

    def test_accuracy_on_separated_clusters(self, rng):
        x, y = make_multiclass(rng, c=4)
        model = LogisticRegression().setRegParam(0.01).fit((x, y))
        assert model.evaluate((x, y))["accuracy"] > 0.8


class TestAPI:
    def test_errors(self, rng):
        x, y = make_binary(rng)
        with pytest.raises(ValueError):
            LogisticRegression().setRegParam(-1.0)
        with pytest.raises(ValueError):
            LogisticRegression().setFamily("gaussian")
        with pytest.raises(ValueError):
            # In-range values route to FISTA (tests/test_elastic_net.py).
            LogisticRegression().setElasticNetParam(2.0)
        with pytest.raises(ValueError):
            LogisticRegression().fit((x, y + 0.5))  # non-integer labels
        with pytest.raises(ValueError):
            LogisticRegression().setFamily("binomial").fit(
                (x, np.arange(len(y)) % 3)
            )

    def test_dataframe_transform_columns(self, rng):
        x, y = make_binary(rng, n=50)
        df = DataFrame({"features": list(x), "label": list(y.astype(float))})
        model = LogisticRegression().setRegParam(0.1).fit(df)
        out = model.transform(df)
        assert "prediction" in out.columns
        assert "probability" in out.columns
        assert "rawPrediction" in out.columns

    def test_persistence_roundtrip(self, rng, tmp_path):
        x, y = make_multiclass(rng, c=3)
        model = LogisticRegression().setRegParam(0.1).fit((x, y))
        path = str(tmp_path / "lr")
        model.write.save(path)
        loaded = LogisticRegressionModel.load(path)
        np.testing.assert_array_equal(loaded.weights, model.weights)
        np.testing.assert_array_equal(loaded.intercepts, model.intercepts)
        assert loaded.numClasses == model.numClasses
        assert loaded.getRegParam() == 0.1
        np.testing.assert_array_equal(loaded.predict(x), model.predict(x))

    def test_copy_preserves_state(self, rng):
        x, y = make_binary(rng)
        model = LogisticRegression().setRegParam(0.1).fit((x, y))
        clone = model.copy() if hasattr(model, "copy") else model
        np.testing.assert_array_equal(clone.weights, model.weights)


class TestDistributed:
    def test_mesh_fit_matches_single_device(self, rng):
        x, y = make_binary(rng, n=203)  # not divisible by mesh
        mesh = make_mesh((4, 2))
        single = LogisticRegression().setRegParam(0.1).setTol(1e-10).fit((x, y))
        dist = (
            LogisticRegression(mesh=mesh).setRegParam(0.1).setTol(1e-10).fit((x, y))
        )
        np.testing.assert_allclose(dist.coefficients, single.coefficients, atol=1e-5)
        assert dist.intercept == pytest.approx(single.intercept, abs=1e-5)

    def test_mesh_multinomial(self, rng):
        x, y = make_multiclass(rng, n=301, c=3)
        mesh = make_mesh((8, 1))
        single = LogisticRegression().setRegParam(0.1).setTol(1e-10).fit((x, y))
        dist = LogisticRegression(mesh=mesh).setRegParam(0.1).setTol(1e-10).fit((x, y))
        np.testing.assert_allclose(
            dist.coefficientMatrix, single.coefficientMatrix, atol=1e-5
        )


class TestWarmStart:
    def test_resume_reaches_same_optimum_faster(self, rng):
        """A warm start from a near-converged model must reproduce the
        cold optimum in (far) fewer iterations — the resume/path-sweep
        semantics."""
        from spark_rapids_ml_tpu.classification import LogisticRegression

        x = rng.normal(size=(400, 6))
        y = (x[:, 0] + 0.5 * x[:, 1] > 0).astype(float)
        cold = LogisticRegression().setMaxIter(200).setTol(1e-9).fit((x, y))
        warm = (
            LogisticRegression()
            .setMaxIter(200)
            .setTol(1e-9)
            .setInitialModel(cold)
            .fit((x, y))
        )
        np.testing.assert_allclose(warm.weights, cold.weights, atol=1e-4)
        assert warm.numIter < cold.numIter / 2

    def test_rejects_elastic_net_path(self, rng):
        from spark_rapids_ml_tpu.classification import LogisticRegression

        x = rng.normal(size=(60, 3))
        y = (x[:, 0] > 0).astype(float)
        cold = LogisticRegression().setMaxIter(20).fit((x, y))
        with pytest.raises(ValueError, match="L-BFGS"):
            (
                LogisticRegression()
                .setRegParam(0.1)
                .setElasticNetParam(0.5)
                .setInitialModel(cold)
                .fit((x, y))
            )

    def test_shape_validation(self, rng):
        from spark_rapids_ml_tpu.classification import LogisticRegression

        x = rng.normal(size=(60, 3))
        y = (x[:, 0] > 0).astype(float)
        cold = LogisticRegression().setMaxIter(5).fit((x, y))
        with pytest.raises(ValueError, match="initial model weights"):
            LogisticRegression().setInitialModel(cold).fit((x[:, :2], y))

    def test_no_intercept_warm_start_drops_stale_intercepts(self, rng):
        """fitIntercept=False never optimizes b — a warm start must not
        leak the initial model's intercepts into predictions (r2 review)."""
        from spark_rapids_ml_tpu.classification import LogisticRegression

        x = rng.normal(size=(200, 4))
        y = (x[:, 0] > 0).astype(float)
        with_b = LogisticRegression().setMaxIter(100).fit((x, y))
        assert abs(with_b.intercept) > 0  # a nonzero intercept to leak
        warm = (
            LogisticRegression()
            .setFitIntercept(False)
            .setMaxIter(100)
            .setInitialModel(with_b)
            .fit((x, y))
        )
        np.testing.assert_allclose(warm.intercepts, 0.0, atol=1e-12)


class TestFusedObjective:
    """The blocked analytic passes (``_make_logistic_loss``): one sweep for
    value and gradient (FISTA's), and the margins / value-from-margins /
    gradient-from-margins the L-BFGS iteration works on, must equal autodiff
    of the plain objective; ``TPUML_LOGISTIC_FUSED=0`` keeps the autodiff
    formulation on the FISTA and streaming drivers, and the L-BFGS fit has
    one formulation."""

    @pytest.mark.parametrize("c,fit_intercept", [(1, True), (3, True), (3, False)])
    def test_blocked_passes_match_autodiff(self, rng, monkeypatch, c, fit_intercept):
        """Including the fori_loop slide-back blocking of a ragged last
        block."""
        import jax
        import jax.numpy as jnp

        import spark_rapids_ml_tpu.ops.logistic as lg

        n, d = 301, 6
        x = jnp.asarray(rng.normal(size=(n, d)))
        mask = jnp.asarray((rng.uniform(size=n) < 0.9).astype(np.float64))
        if c == 1:
            y_t = jnp.asarray(rng.integers(0, 2, n).astype(np.float64))
        else:
            y_t = jnp.asarray(np.eye(c)[rng.integers(0, c, n)])
        offset = jnp.asarray(rng.normal(size=d))
        scale = jnp.asarray(rng.uniform(0.5, 2.0, d))
        w = jnp.asarray(rng.normal(size=(d, c)) * 0.1)
        b = jnp.asarray(rng.normal(size=c) * 0.1)
        n_eff, reg = float(mask.sum()), 0.05

        def plain(params):
            # the objective as one expression, for autodiff
            w_, b_ = params
            logits = ((x - offset) / scale) @ w_ + (b_ if fit_intercept else 0.0)
            if c == 1:
                per_row = jax.nn.softplus(logits[:, 0]) - y_t * logits[:, 0]
            else:
                per_row = -jnp.sum(y_t * jax.nn.log_softmax(logits, axis=1), axis=1)
            return jnp.sum(per_row * mask) / n_eff + 0.5 * reg * jnp.sum(w_ * w_)

        val_ref, grad_ref = jax.value_and_grad(plain)((w, b))

        # Force the multi-block path: 301 rows over 64-row blocks needs
        # the slide-back + keep-mask for the ragged final block.
        monkeypatch.setattr(lg, "_FUSED_BLOCK_ROWS", 64)
        passes = lg._make_logistic_loss(
            x, y_t, mask, offset, scale, n_eff, reg, c, fit_intercept, "highest"
        )
        val, (gw, gb) = passes.value_and_grad((w, b))
        assert float(val) == pytest.approx(float(val_ref), rel=1e-12)
        np.testing.assert_allclose(gw, grad_ref[0], atol=1e-12)
        np.testing.assert_allclose(gb, grad_ref[1], atol=1e-12)

        # what the L-BFGS iteration uses: margins once, then both from them
        z = passes.margins(w, b)
        assert z.shape == (n, c)
        assert float(passes.value_at(z, w)) == pytest.approx(float(val_ref), rel=1e-12)
        gw_z, gb_z = passes.grad_at(z, (w, b))
        np.testing.assert_allclose(gw_z, grad_ref[0], atol=1e-12)
        np.testing.assert_allclose(gb_z, grad_ref[1], atol=1e-12)

    def test_streaming_fused_matches_legacy(self, rng):
        from spark_rapids_ml_tpu.ops.logistic import (
            fit_logistic_streaming,
            streaming_label_feature_stats,
        )

        x, y = make_binary(rng, n=500)
        blocks = [
            (x[i : i + 120], y[i : i + 120].astype(np.float64))
            for i in range(0, 500, 120)
        ]
        n, mean, sigma, y_max, ok = streaming_label_feature_stats(iter(blocks))
        assert ok and y_max == 1

        def fit(fused):
            return fit_logistic_streaming(
                lambda: iter(blocks), 2, n=n, mean=mean, sigma=sigma,
                reg_param=0.02, fused=fused,
            )

        f, g = fit(True), fit(False)
        np.testing.assert_allclose(f.weights, g.weights, atol=1e-5)
        np.testing.assert_allclose(f.intercepts, g.intercepts, atol=1e-5)

    def test_knob_moves_nothing_on_the_lbfgs_path(self, rng, monkeypatch):
        """The L-BFGS fit has one formulation: TPUML_LOGISTIC_FUSED governs
        FISTA and the streaming fit alone."""
        x, y = make_binary(rng)

        def fit(knob):
            monkeypatch.setenv("TPUML_LOGISTIC_FUSED", knob)
            est = LogisticRegression().setRegParam(0.01).setMaxIter(50)
            return est.fit((x, y.astype(np.float64)))

        m1, m0 = fit("1"), fit("0")
        assert np.array_equal(m1.coefficients, m0.coefficients)
        assert m1.intercept == m0.intercept and m1.xPasses == m0.xPasses

    def test_elastic_net_fused_matches_legacy(self, rng, monkeypatch):
        """FISTA's smooth part shares the fused builder: the knob must
        not move the elastic-net optimum."""
        x, y = make_binary(rng)

        def fit(knob):
            monkeypatch.setenv("TPUML_LOGISTIC_FUSED", knob)
            est = (
                LogisticRegression()
                .setRegParam(0.05)
                .setElasticNetParam(0.5)
                .setMaxIter(200)
            )
            return est.fit((x, y.astype(np.float64)))

        m1, m0 = fit("1"), fit("0")
        np.testing.assert_allclose(m1.coefficients, m0.coefficients, atol=1e-5)


# --- the L-BFGS iteration on cached margins -------------------------------


def plain_objective(x, y, n_classes, reg, fit_intercept, standardization, multinomial):
    """A plain float64 statement of Spark's objective, independent of the
    program: ``(f, grad)`` of ``theta`` = the ORIGINAL-space coefficients
    (d, c) then, with an intercept, the intercepts (c,). The penalty is on
    ``w_j sigma_j`` with ``standardization`` (the population deviation), on
    ``w_j`` without; the intercept is free."""
    from scipy.special import expit, log_softmax, softmax

    n, d = x.shape
    c = n_classes if multinomial else 1
    sigma = x.std(axis=0) if standardization else np.ones(d)
    onehot = np.eye(n_classes)[y] if multinomial else (y == 1).astype(np.float64)[:, None]

    def fun(theta):
        w = theta[: d * c].reshape(d, c)
        b = theta[d * c :] if fit_intercept else np.zeros(c)
        z = x @ w + b
        if multinomial:
            loss = -np.sum(onehot * log_softmax(z, axis=1)) / n
            dz = (softmax(z, axis=1) - onehot) / n
        else:
            loss = np.sum(np.logaddexp(0.0, z) - onehot * z) / n
            dz = (expit(z) - onehot) / n
        ws = w * sigma[:, None]
        grad = [(x.T @ dz + reg * ws * sigma[:, None]).ravel()]
        if fit_intercept:
            grad.append(dz.sum(axis=0))
        return loss + 0.5 * reg * np.sum(ws * ws), np.concatenate(grad)

    return fun, d * c + (c if fit_intercept else 0)


def to_returned_space(grad, x, fit_intercept, standardization):
    """``finalGradient`` (d + 1, c) is in the optimizer's space, w = w_orig
    sigma and b = b_orig + mean . w_orig: by the chain rule, with respect
    to the returned coefficients."""
    if not standardization:
        return grad
    mean = x.mean(axis=0) if fit_intercept else np.zeros(x.shape[1])
    gw = grad[:-1] * x.std(axis=0)[:, None] + mean[:, None] * grad[-1][None, :]
    return np.concatenate([gw, grad[-1:]])


class TestCachedMarginLbfgs:
    """The L-BFGS iteration keeps the margins of its point and searches
    its line on them: what it returns is held against a plain statement of
    the objective minimised by scipy, and what it costs against the
    configuration's formula."""

    @pytest.mark.parametrize("standardization", [True, False])
    @pytest.mark.parametrize("fit_intercept", [True, False])
    @pytest.mark.parametrize("multinomial", [False, True])
    def test_matches_the_plain_reference(
        self, rng, multinomial, fit_intercept, standardization
    ):
        from scipy.optimize import minimize

        if multinomial:
            x, y = make_multiclass(rng, c=3)
        else:
            x, y = make_binary(rng)
        x = x * np.linspace(0.5, 3.0, x.shape[1]) + 0.7  # columns that differ
        n_classes = 3 if multinomial else 2
        reg = 0.01
        model = (
            LogisticRegression()
            .setRegParam(reg)
            .setFitIntercept(fit_intercept)
            .setStandardization(standardization)
            .setMaxIter(200)
            .setTol(1e-9)
            .fit((x, y))
        )
        fun, size = plain_objective(
            x, y, n_classes, reg, fit_intercept, standardization, multinomial
        )
        best = minimize(fun, np.zeros(size), jac=True, method="L-BFGS-B",
                        options={"maxiter": 2000, "ftol": 1e-15, "gtol": 1e-10})
        got = [np.asarray(model.weights).ravel()]
        if fit_intercept:
            got.append(np.asarray(model.intercepts))
        f_got, grad_got = fun(np.concatenate(got))
        grad0 = fun(np.zeros(size))[1]
        # objective gap as a share, gradient norm over the norm at zero
        assert (f_got - best.fun) / best.fun < 1e-9
        # what the fit reports off its cached margins is the objective there
        assert model.finalObjective == pytest.approx(f_got, rel=1e-9)
        assert np.linalg.norm(grad_got) < 1e-6 * np.linalg.norm(grad0)
        assert 0 < model.numIter < 200
        # the gradient the last iteration computed from its cached margins
        # is the plain one at the returned coefficients
        reported = to_returned_space(model.finalGradient, x, fit_intercept, standardization)
        assert reported.shape == (x.shape[1] + 1, model.weights.shape[1])
        flat = [reported[:-1].ravel()] + ([reported[-1]] if fit_intercept else [])
        assert np.linalg.norm(np.concatenate(flat) - grad_got) < 1e-9 * np.linalg.norm(grad0)
        if not fit_intercept:
            assert not reported[-1].any()

    @pytest.mark.parametrize("standardization,start", [(False, 2), (True, 4)])
    def test_passes_over_the_rows_do_not_depend_on_the_seed(self, standardization, start):
        """tol 1e-30 in float32: every fit runs maxIter iterations, many of
        them at float32's floor where the line search finds no decrease.
        ``xPasses`` is the formula on every seed; only the trials differ."""
        max_iter = 40
        passes, trials = [], []
        for seed in range(8):
            x, y = make_binary(np.random.default_rng(seed), n=300, d=8)
            model = (
                LogisticRegression()
                .setRegParam(1e-3)
                .setStandardization(standardization)
                .setMaxIter(max_iter)
                .setTol(1e-30)
                .fit((x.astype(np.float32), y))
            )
            assert model.numIter == max_iter
            passes.append(model.xPasses)
            trials.append(model.linesearchTrials)
        assert passes == [start + 2 * max_iter] * 8
        assert min(trials) >= max_iter and len(set(trials)) >= 2

    @pytest.mark.parametrize("multinomial", [False, True])
    def test_segmented_is_bit_identical_to_monolithic(self, rng, multinomial):
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops.logistic import (
            fit_logistic,
            fit_logistic_resumable,
        )
        from spark_rapids_ml_tpu.robustness.checkpoint import EphemeralSegmenter

        x, y = make_multiclass(rng, c=3) if multinomial else make_binary(rng)
        args = (jnp.asarray(x), jnp.asarray(y), jnp.ones(len(y)))
        kwargs = dict(n_classes=3 if multinomial else 2, reg_param=0.01, max_iter=30,
                      tol=1e-12, multinomial=multinomial)
        whole = fit_logistic(*args, **kwargs)
        pieces = fit_logistic_resumable(*args, EphemeralSegmenter(7), **kwargs)
        import jax

        leaves = [jax.tree_util.tree_leaves(fit) for fit in (whole, pieces)]
        assert len(leaves[0]) == len(leaves[1]) == 8  # the gradient's pair too
        for a, b in zip(*leaves):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
        assert int(whole.x_passes) == 4 + 2 * int(whole.n_iter)

    def test_counters_and_solve_stage_show_in_the_fit_report(self, rng):
        from spark_rapids_ml_tpu.utils.tracing import counter_value

        x, y = make_binary(rng)
        before = {k: counter_value(f"logreg.lbfgs.{k}")
                  for k in ("iters", "x_passes", "linesearch_trials")}
        model = LogisticRegression().setRegParam(0.01).fit((x, y))
        report = model.fit_report()
        assert "solve" in report.stage_totals()
        assert not any(k.startswith("logreg.lbfgs.") for k in report.counters)
        # the counts cross to the host, and the counters move, on first read
        moved = {"iters": model.numIter, "x_passes": model.xPasses,
                 "linesearch_trials": model.linesearchTrials}
        assert moved["x_passes"] == 4 + 2 * moved["iters"]
        for k, v in moved.items():
            assert counter_value(f"logreg.lbfgs.{k}") - before[k] == v
            assert report.counters[f"logreg.lbfgs.{k}"] == v
        assert "logreg.lbfgs.x_passes" in str(report)
        _ = model.numIter  # a second read moves nothing
        assert counter_value("logreg.lbfgs.iters") - before["iters"] == moved["iters"]

    def test_a_copy_counts_its_fit_once(self, rng):
        from spark_rapids_ml_tpu.utils.tracing import counter_value

        x, y = make_binary(rng)
        before = counter_value("logreg.lbfgs.iters")
        model = LogisticRegression().setRegParam(0.01).fit((x, y))
        twin = model.copy()  # before either was read
        assert twin.numIter == model.numIter and twin.xPasses == model.xPasses
        assert np.array_equal(twin.finalGradient, model.finalGradient)
        assert counter_value("logreg.lbfgs.iters") - before == model.numIter

    @pytest.mark.parametrize("standardization", [True, False])
    def test_final_gradient_of_an_unconverged_fit(self, rng, standardization):
        """Five iterations leave a gradient far from nought: what the model
        reports is still the plain objective's gradient at what it returns,
        to float64's rounding (the margins carried, never refreshed)."""
        x, y = make_binary(rng)
        x = x * np.linspace(0.5, 3.0, x.shape[1]) + 0.7
        model = (
            LogisticRegression().setRegParam(0.01).setMaxIter(5).setTol(0.0)
            .setStandardization(standardization).fit((x, y))
        )
        fun, size = plain_objective(x, y, 2, 0.01, True, standardization, False)
        theta = np.append(np.asarray(model.weights).ravel(), model.intercepts)
        grad = fun(theta)[1]
        assert model.numIter == 5 and np.linalg.norm(grad) > 1e-4
        reported = to_returned_space(model.finalGradient, x, True, standardization)
        np.testing.assert_allclose(reported.ravel(), grad, atol=1e-12)

    def test_off_the_lbfgs_path_reports_no_passes(self, rng):
        x, y = make_binary(rng)
        model = (
            LogisticRegression().setRegParam(0.05).setElasticNetParam(0.5).fit((x, y))
        )
        assert model.numIter > 0
        assert model.xPasses is None and model.linesearchTrials is None
        assert model.finalGradient is None
        assert model.finalObjective > 0

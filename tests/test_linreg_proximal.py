"""LinearRegression's proximal (elastic-net) path against a plain numpy
statement of Spark's objective, the blocked moments under it, and what the
fit counts (PR 36: ``maxIter``, ``tol``, ``numIter``, ``finalObjective``,
``finalGradient``; no eigendecomposition for the step).

The tests' x64 makes the program's arithmetic float64, so "equal" below is to
float64's rounding.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_ml_tpu.feature import PCA
from spark_rapids_ml_tpu.ops.covariance import GRAM_BLOCK_ROWS
from spark_rapids_ml_tpu.ops.linear import (
    FISTA_POWER_ITERS,
    FISTA_STEP_MARGIN,
    normal_eq_stats,
    solve_elastic_net,
    solve_elastic_net_resumable,
)
from spark_rapids_ml_tpu.regression import LinearRegression
from spark_rapids_ml_tpu.utils.tracing import counter_value

REG = 0.05


def make_rows(seed=7, n=400, d=8):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)) * np.linspace(0.5, 3.0, d) + rng.normal(size=d)
    beta = np.where(np.arange(d) < d // 2, rng.uniform(1.0, 4.0, size=d), 0.0)
    return x, x @ beta + 1.5 + 0.3 * rng.normal(size=n)


def plain_problem(x, y, alpha, standardization, fit_intercept):
    """Spark's objective on these rows, written out: the quadratic part
    ``q``, its linear term, the soft-threshold levels and the L2 weights."""
    n = len(y)
    var = x.var(axis=0, ddof=1)
    xc, yc = (x - x.mean(axis=0), y - y.mean()) if fit_intercept else (x, y)
    w1, w2 = (np.sqrt(var), var) if standardization else (np.ones_like(var),) * 2
    return {"q": xc.T @ xc / n + np.diag(REG * (1 - alpha) * w2), "lin": xc.T @ yc / n,
            "l1": REG * alpha * w1, "l2": REG * (1 - alpha) * w2}


def plain_objective(x, y, b, b0, p):
    r = y - x @ b - b0
    return r @ r / (2 * len(y)) + np.sum(p["l1"] * np.abs(b)) + 0.5 * np.sum(p["l2"] * b * b)


def plain_fista(p, iters):
    """FISTA from zero with the program's step rule: ``FISTA_POWER_ITERS``
    power iterations from the fixed-key start, times the margin."""
    v = np.array(jax.random.normal(jax.random.key(0), p["lin"].shape, dtype=jnp.float64))
    v = v / np.linalg.norm(v)
    for _ in range(FISTA_POWER_ITERS):
        v = p["q"] @ v
        v = v / np.linalg.norm(v)
    lip = FISTA_STEP_MARGIN * np.linalg.norm(p["q"] @ v) + 1e-12
    assert lip >= np.linalg.eigvalsh(p["q"])[-1]  # the margin covers the estimate
    c = z = np.zeros_like(p["lin"])
    t = 1.0
    for _ in range(iters):
        u = z - (p["q"] @ z - p["lin"]) / lip
        c_new = np.sign(u) * np.maximum(np.abs(u) - p["l1"] / lip, 0.0)
        t_new = (1 + np.sqrt(1 + 4 * t * t)) / 2
        z, c, t = c_new + (t - 1) / t_new * (c_new - c), c_new, t_new
    return c


@pytest.mark.parametrize("max_iter", [3, 10, 200])
@pytest.mark.parametrize("fit_intercept", [True, False], ids=["intercept", "no-intercept"])
@pytest.mark.parametrize("standardization", [True, False], ids=["std", "no-std"])
@pytest.mark.parametrize("alpha", [0.5, 1.0])
def test_fit_is_spark_objective_and_as_many_fista_iterations(
    alpha, standardization, fit_intercept, max_iter
):
    x, y = make_rows()
    model = (
        LinearRegression().setRegParam(REG).setElasticNetParam(alpha)
        .setStandardization(standardization).setFitIntercept(fit_intercept)
        .setMaxIter(max_iter).setTol(1e-30).fit((x, y))
    )
    # a tol no arithmetic can meet: the loop's work follows from maxIter alone
    assert model.numIter == max_iter
    p = plain_problem(x, y, alpha, standardization, fit_intercept)
    want = plain_fista(p, max_iter)
    b, b0 = model.coefficients, model.intercept
    np.testing.assert_allclose(b, want, rtol=1e-9, atol=1e-11)
    assert b0 == pytest.approx(y.mean() - x.mean(axis=0) @ want if fit_intercept else 0.0,
                               rel=1e-9, abs=1e-11)
    # the objective reported, from the moments, is the plain one over the rows
    assert model.finalObjective == pytest.approx(plain_objective(x, y, b, b0, p), rel=1e-10)
    np.testing.assert_allclose(model.finalGradient, p["q"] @ b - p["lin"], rtol=1e-8, atol=1e-11)
    if max_iter == 200:  # near the optimum: its sub-gradient condition, to what 200 steps leave
        g = model.finalGradient
        on = np.abs(b) > 0
        np.testing.assert_allclose(g[on], -p["l1"][on] * np.sign(b[on]), atol=2e-3)
        assert np.all(np.abs(g[~on]) <= p["l1"][~on] + 2e-3)


def test_tol_stops_the_loop_and_defaults_are_sparks():
    est = LinearRegression()
    assert est.getMaxIter() == 100 and est.getTol() == 1e-6
    x, y = make_rows()
    est = est.setRegParam(REG).setElasticNetParam(0.5)
    loose = est.copy().setTol(1e-2).fit((x, y))
    tight = est.copy().setTol(1e-5).setMaxIter(5000).fit((x, y))
    assert 1 <= loose.numIter < tight.numIter < 5000
    np.testing.assert_allclose(loose.coefficients, tight.coefficients, atol=0.2)
    exact = LinearRegression().setRegParam(REG).fit((x, y))  # no proximal loop
    assert exact.numIter is None and exact.finalObjective is None and exact.finalGradient is None
    for bad in (lambda: est.setMaxIter(-1), lambda: est.setTol(-1.0)):
        with pytest.raises(ValueError):
            bad()


@pytest.mark.parametrize("every", [1, 4, 64])
def test_segmented_proximal_loop_is_bit_identical_to_monolithic(every):
    from spark_rapids_ml_tpu.robustness.checkpoint import EphemeralSegmenter

    x, y = make_rows(seed=11)
    moments = normal_eq_stats(jnp.asarray(x), jnp.asarray(y), None)
    kwargs = dict(reg_param=REG, elastic_net_param=0.5, max_iter=37, tol=1e-30)
    whole = solve_elastic_net(moments, **kwargs)
    pieces = solve_elastic_net_resumable(moments, checkpointer=EphemeralSegmenter(every), **kwargs)
    assert int(whole.n_iter) == int(pieces.n_iter) == 37
    for a, b in zip(whole, pieces):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    # a tol that CAN be met stops both at the same iteration
    kwargs.update(tol=1e-4, max_iter=500)
    whole = solve_elastic_net(moments, **kwargs)
    pieces = solve_elastic_net_resumable(moments, checkpointer=EphemeralSegmenter(every), **kwargs)
    assert 1 <= int(whole.n_iter) == int(pieces.n_iter) < 500
    assert np.asarray(whole.coef).tobytes() == np.asarray(pieces.coef).tobytes()


def test_no_eigendecomposition_on_the_proximal_path():
    x, y = make_rows()
    moments = normal_eq_stats(jnp.asarray(x), jnp.asarray(y), None)
    text = solve_elastic_net.lower(moments, REG, 0.5, max_iter=10, tol=1e-30).as_text()
    assert "eigh" not in text.lower() and "syevd" not in text.lower()


def test_counters_closed_forms_and_the_solve_stage():
    n, d, max_iter = 2 * GRAM_BLOCK_ROWS + 3457, 6, 7
    x, y = make_rows(n=n, d=d)
    names = ("gram.blocks", "gram.rows", "linreg.fista.power_iters", "linreg.fista.iters")
    before = {k: counter_value(k) for k in names}
    model = (
        LinearRegression().setRegParam(REG).setElasticNetParam(0.5).setMaxIter(max_iter)
        .setTol(1e-30).fit((jnp.asarray(x), jnp.asarray(y)))
    )
    moved = {k: counter_value(k) - before[k] for k in names}
    # bumped from shapes at dispatch: blocks of 10,000 rows, the short one too
    assert moved == {"gram.blocks": 3, "gram.rows": n,
                     "linreg.fista.power_iters": FISTA_POWER_ITERS, "linreg.fista.iters": 0}
    report = model.fit_report()
    assert report.stage_totals()["solve"]["calls"] == 1
    text = str(report)
    assert "linreg moments" in text and "linreg prox" in text
    assert "linreg.fista.iters" not in report.counters
    # the iteration count crosses to the host, and its counter moves, on first read
    assert model.numIter == max_iter
    assert counter_value("linreg.fista.iters") - before["linreg.fista.iters"] == max_iter
    assert report.counters["linreg.fista.iters"] == max_iter
    twin = model.copy()
    _ = model.numIter, twin.numIter  # a second read, or a copy's, moves nothing
    assert counter_value("linreg.fista.iters") - before["linreg.fista.iters"] == max_iter


@pytest.mark.parametrize("n", [2 * GRAM_BLOCK_ROWS, 2 * GRAM_BLOCK_ROWS + 3457],
                         ids=["whole-blocks", "remainder"])
def test_pca_on_resident_rows_agrees_with_the_host_partition_fit(n):
    """The fused device fit (blocks of resident rows) and the host-partition
    fit (a step a partition) of the same float32 rows: the same accumulator,
    held to ``pca_3000``'s limits (``ev_rel`` 1.5e-6, ``pc_abs`` 2e-6)."""
    d, k = 24, 3
    rng = np.random.default_rng(5)
    basis, _ = np.linalg.qr(rng.normal(size=(d, k)))
    x = rng.normal(size=(n, d)) + (rng.normal(size=(n, k)) * np.sqrt([15.0, 7.0, 3.0])) @ basis.T
    x = (x + 3.0 * rng.normal(size=d)).astype(np.float32)
    before = counter_value("gram.blocks")
    on_device = PCA().setK(k).fit(jnp.asarray(x))
    assert counter_value("gram.blocks") - before == -(-n // GRAM_BLOCK_ROWS)
    parts = [x[lo : lo + 7000] for lo in range(0, n, 7000)]
    from_host = PCA().setK(k).fit(parts)
    ev_a, ev_b = (np.asarray(m.explainedVariance, np.float64) for m in (on_device, from_host))
    pc_a, pc_b = (np.asarray(m.pc, np.float64) for m in (on_device, from_host))
    assert np.max(np.abs(ev_a - ev_b) / ev_b) <= 1.5e-6
    sign = np.sign(np.sum(pc_a * pc_b, axis=0))
    assert np.max(np.abs(pc_a * sign - pc_b)) <= 2e-6

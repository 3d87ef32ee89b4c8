"""Linear model kernels — normal-equation sufficient statistics on the MXU.

Beyond-PCA capability (the normal-equation GEMM path; measured in
``linreg_3000.device_rows``, PERF.md section 5). The sufficient statistics
are the CENTRED moments of ``[X | y]`` (:class:`Moments`): on resident rows
the blocked co-moment accumulator of ``ops/covariance.py`` with the label
riding along as one more column, never a raw ``X^T X`` centred afterwards
(that subtraction, and one float32 contraction over all rows, is what
PERF.md section 6, PR 24, measured reading low on the diagonal).

Solve semantics follow Spark ML's "normal" solver (WeightedLeastSquares):
    minimize 1/(2n) ||y - X b - b0||^2 + regParam * penalty(b)
with L2 penalty applied to coefficients of STANDARDIZED features when
``standardization`` is on, i.e. in original space
    (Xc^T Xc + n * regParam * diag(sigma^2)) b = Xc^T yc
(sigma = per-feature stddev; identity instead of diag(sigma^2) when
standardization is off), intercept b0 = mean(y) - mean(x)^T b.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from spark_rapids_ml_tpu.ops.covariance import comoment_resident
from spark_rapids_ml_tpu.ops.linalg import soft_threshold
from spark_rapids_ml_tpu.ops.precision import make_dot


class Moments(NamedTuple):
    """What every solver here consumes: the (weighted) row count, the
    means, and the second moments of ``[X | y]`` CENTRED on them."""

    count: jax.Array   # rows, or the sum of their weights
    x_mean: jax.Array  # (d,)
    y_mean: jax.Array
    a: jax.Array       # (d, d)  Xc^T Xc
    b: jax.Array       # (d,)    Xc^T yc
    yy: jax.Array      # yc^T yc

    def narrowed(self, d: int) -> "Moments":
        """The first ``d`` columns' moments (a mesh's model axis pads the
        columns with zeros)."""
        return self._replace(x_mean=self.x_mean[:d], a=self.a[:d, :d], b=self.b[:d])


@partial(jax.jit, static_argnames=("precision",))
def normal_eq_stats(
    x: jax.Array, y: jax.Array, mask: jax.Array | None, precision: str = "highest"
) -> Moments:
    """The :class:`Moments` of resident rows, summed in blocks by
    ``ops/covariance.py::comoment_resident`` with ``y`` as one more column
    of every block. ``mask`` (n,) or None: per-row weights (``weightCol``;
    nought for a padding row); None means every row real at weight 1."""
    d = x.shape[1]
    count, mean, mean_lo, m = comoment_resident(x, y, mask, precision=precision)
    mean = mean + mean_lo
    return Moments(count, mean[:d], mean[d], m[:d, :d], m[:d, d], m[d, d])


@partial(jax.jit, static_argnames=("precision",))
def raw_moments(
    x: jax.Array, y: jax.Array, mask: jax.Array | None, precision: str = "highest"
):
    """RAW moments (xtx, xty, x_sum, y_sum, yty, count) of one block in one
    contraction: what the routes that add blocks up by themselves still sum
    (host blocks in :func:`normal_eq_stats_streaming`, rows sharded over a
    mesh, where XLA inserts the ``psum``); :func:`moments_from_raw` centres
    them for the solvers. ``mask=None`` means "all rows real, weight 1"."""
    dot = make_dot(precision)
    if mask is None:
        xtx = dot(x.T, x)
        xty = dot(x.T, y)
        n = jnp.asarray(x.shape[0], x.dtype)
        return (xtx, xty, jnp.sum(x, axis=0), jnp.sum(y), jnp.sum(y * y), n)
    xm = x * mask[:, None]
    ym = y * mask
    xtx = dot(xm.T, x)
    xty = dot(xm.T, y)
    return (
        xtx,
        xty,
        jnp.sum(xm, axis=0),
        jnp.sum(ym),
        jnp.sum(ym * y),
        jnp.sum(mask),
    )


@jax.jit
def moments_from_raw(xtx, xty, x_sum, y_sum, yty, count) -> Moments:
    """Centre raw moments: ``Xc^T Xc = X^T X - n * mean mean^T`` (and the
    like for the label). The subtraction cancels where a column's mean is
    large against its spread; resident rows never come this way."""
    n = count
    x_mean = x_sum / n
    y_mean = y_sum / n
    return Moments(
        n,
        x_mean,
        y_mean,
        xtx - n * jnp.outer(x_mean, x_mean),
        xty - n * x_mean * y_mean,
        yty - n * y_mean * y_mean,
    )


def _quadratic(m: Moments, fit_intercept: bool, standardization: bool):
    """Shared pre-solve reduction: the second moments the objective is made
    of (centred with an intercept, about zero without one) and the
    per-feature variance used as the standardization penalty weight.

    sigma^2 is the TRUE feature variance (centered second moment) in both
    intercept modes — Spark standardizes by the feature stddev regardless
    of fitIntercept. Returns (a, b, yy, var_weights).
    """
    n = m.count
    if fit_intercept:
        a, b, yy = m.a, m.b, m.yy
    else:
        a = m.a + n * jnp.outer(m.x_mean, m.x_mean)
        b = m.b + n * m.x_mean * m.y_mean
        yy = m.yy + n * m.y_mean * m.y_mean
    if standardization:
        var = jnp.maximum(jnp.diag(m.a) / jnp.maximum(n - 1, 1), 0.0)
    else:
        var = jnp.ones(a.shape[0], dtype=a.dtype)
    return a, b, yy, var


@partial(jax.jit, static_argnames=("fit_intercept", "standardization"))
def solve_normal(
    moments: Moments,
    reg_param: float = 0.0,
    fit_intercept: bool = True,
    standardization: bool = True,
):
    """Solve the (regularized) normal equations from the moments.

    Returns (coefficients (d,), intercept scalar). Cholesky with a
    singularity fallback to eigh-based pseudo-solve (minimum-norm), which
    handles rank-deficient designs the way LAPACK-backed Spark does via
    quasi-Newton fallback.
    """
    n = moments.count
    x_mean, y_mean = moments.x_mean, moments.y_mean
    a, b, _, penalty = _quadratic(moments, fit_intercept, standardization)
    d = a.shape[0]
    a_reg = a + (n * reg_param) * jnp.diag(penalty)

    chol, low = jax.scipy.linalg.cho_factor(a_reg, lower=True)
    coef_chol = jax.scipy.linalg.cho_solve((chol, low), b)
    ok = jnp.all(jnp.isfinite(coef_chol))

    # minimum-norm pseudo-solve fallback for singular/indefinite systems
    w, v = jnp.linalg.eigh(a_reg)
    tol = jnp.max(jnp.abs(w)) * d * jnp.finfo(a.dtype).eps
    w_inv = jnp.where(w > tol, 1.0 / w, 0.0)
    coef_pinv = v @ (w_inv * (v.T @ b))

    coef = jnp.where(ok, coef_chol, coef_pinv)
    intercept = jnp.where(fit_intercept, y_mean - jnp.dot(x_mean, coef), 0.0)
    return coef, intercept


@partial(jax.jit, static_argnames=("precision",))
def predict_linear(x: jax.Array, coef: jax.Array, intercept, precision: str = "highest"):
    return make_dot(precision)(x, coef) + intercept


@jax.jit
def regression_metrics(y: jax.Array, pred: jax.Array, mask: jax.Array):
    """(mse, rmse, mae, r2) over unmasked rows."""
    n = jnp.sum(mask)
    resid = (y - pred) * mask
    sse = jnp.sum(resid * resid)
    mse = sse / n
    mae = jnp.sum(jnp.abs(resid)) / n
    y_mean = jnp.sum(y * mask) / n
    sst = jnp.sum(((y - y_mean) * mask) ** 2)
    r2 = 1.0 - sse / jnp.where(sst > 0, sst, 1.0)
    return mse, jnp.sqrt(mse), mae, r2


#: The proximal solver's step is ``1 / lip``, ``lip`` the quadratic part's
#: largest eigenvalue from this many power iterations times the margin (a
#: power iteration converges from below; ``ops/logistic.py``'s FISTA takes
#: the same two numbers). No eigendecomposition of the (d, d) matrix.
FISTA_POWER_ITERS = 30
FISTA_STEP_MARGIN = 1.1


class EnetResult(NamedTuple):
    coef: jax.Array       # (d,) original space
    intercept: jax.Array
    n_iter: jax.Array     # proximal iterations run
    objective: jax.Array  # Spark's objective at (coef, intercept), from the moments
    gradient: jax.Array   # (d,) of the objective's smooth part at coef


def _matvec(a, v):
    return jnp.matmul(a, v, precision="highest")


def _enet_problem(m: Moments, reg_param, elastic_net_param, fit_intercept, standardization):
    """The proximal problem of the moments: minimise ``1/2 c^T a_quad c -
    b_lin^T c + half_yy + sum_j l1_j |c_j|`` (the least-squares term over
    ``n`` with the L2 penalty folded into ``a_quad``), and ``lip``, the
    bound on ``a_quad``'s largest eigenvalue the step is taken from."""
    n = m.count
    a, b, yy, w2 = _quadratic(m, fit_intercept, standardization)
    w1 = jnp.sqrt(w2) if standardization else w2  # ones without standardization
    a_quad = a / n + reg_param * (1.0 - elastic_net_param) * jnp.diag(w2)
    # Fixed-key random start: a deterministic uniform vector can be exactly
    # orthogonal to the dominant eigenvector of a structured matrix.
    v = jax.random.normal(jax.random.key(0), b.shape, dtype=a.dtype)

    def power(_, v):
        u = _matvec(a_quad, v)
        return u / jnp.maximum(jnp.linalg.norm(u), 1e-30)

    v = jax.lax.fori_loop(0, FISTA_POWER_ITERS, power, v / jnp.linalg.norm(v))
    lip = FISTA_STEP_MARGIN * jnp.linalg.norm(_matvec(a_quad, v)) + 1e-12
    return a_quad, b / n, reg_param * elastic_net_param * w1, lip, yy / (2.0 * n)


def _fista_loop(a_quad, b_lin, l1, lip, tol, carry, max_iter, budget=None):
    """FISTA from ``carry = (coef, momentum point, t, iteration, delta)``
    until ``max_iter`` iterations, convergence, or ``budget`` more
    iterations: the one iteration body and stopping rule of the monolithic
    and the resumable driver. Converged means the widest change of a
    coefficient is at most ``tol`` times the widest coefficient (1 at
    least). A ``tol`` under the dtype's epsilon cannot be met by a change
    that arithmetic can show, and is read as "run ``max_iter`` iterations":
    the loop's work then follows from the params alone."""
    never = tol < jnp.finfo(a_quad.dtype).eps

    def unconverged(c, delta):
        return never | (delta > tol * jnp.maximum(jnp.max(jnp.abs(c)), 1.0))

    def cond(state):
        (c, _, _, it, delta), seg = state
        go = jnp.logical_and(it < max_iter, unconverged(c, delta))
        return go if budget is None else jnp.logical_and(go, seg < budget)

    def body(state):
        (c, z, t, it, _), seg = state
        grad = _matvec(a_quad, z) - b_lin
        c_new = soft_threshold(z - grad / lip, l1 / lip)
        t_new = (1.0 + jnp.sqrt(1.0 + 4.0 * t * t)) / 2.0
        z_new = c_new + ((t - 1.0) / t_new) * (c_new - c)
        delta = jnp.max(jnp.abs(c_new - c))
        return (c_new, z_new, t_new, it + 1, delta), seg + 1

    return jax.lax.while_loop(cond, body, (carry, 0))[0]


def _fista_start(a_quad, init_coef):
    """Warm start (partial_fit / regularization-path sweeps): FISTA from a
    previous optimum in the ORIGINAL coefficient space — the carry's own
    space, so no mapping is needed. Momentum restarts from the seed
    (z = c, t = 1): plain FISTA initialization, just not at zero."""
    d, dt = a_quad.shape[0], a_quad.dtype
    c0 = jnp.zeros(d, dtype=dt) if init_coef is None else jnp.asarray(init_coef, dtype=dt)
    return (c0, c0, jnp.asarray(1.0, dt), jnp.asarray(0), jnp.asarray(jnp.inf, dt))


def _enet_result(m: Moments, a_quad, b_lin, l1, half_yy, coef, n_iter, fit_intercept):
    """What a solve returns, from the moments alone (no pass over the
    rows): the intercept, the smooth part's gradient at the returned
    coefficients and Spark's objective there."""
    grad = _matvec(a_quad, coef) - b_lin
    objective = half_yy + 0.5 * jnp.dot(coef, grad - b_lin) + jnp.sum(l1 * jnp.abs(coef))
    intercept = jnp.where(fit_intercept, m.y_mean - jnp.dot(m.x_mean, coef), 0.0)
    return EnetResult(coef, intercept, n_iter, objective, grad)


@partial(jax.jit, static_argnames=("fit_intercept", "standardization"))
def solve_elastic_net(
    moments: Moments,
    reg_param: float,
    elastic_net_param: float,
    fit_intercept: bool = True,
    standardization: bool = True,
    max_iter: int = 100,
    tol: float = 1e-6,
    init_coef=None,
) -> EnetResult:
    """Elastic-net least squares from the SAME sufficient statistics.

    minimize 1/(2n)||y - Xb - b0||^2
             + regParam * (alpha * sum_j w1_j |b_j|
                           + (1-alpha)/2 * sum_j w2_j b_j^2)
    with w1 = sigma, w2 = sigma^2 under standardization (the original-space
    form of penalizing standardized coefficients, matching the L2 path),
    w = 1 otherwise. Solved by FISTA on the quadratic moment form — the
    gradient is (A b - B)/n with A = Xc^T Xc, so iterations are O(d^2)
    vector-matrix work independent of n: the data was consumed by ONE
    blocked pass (``normal_eq_stats``), the accelerated proximal loop never
    touches it again. ``max_iter`` and ``tol`` are the estimator's
    (:func:`_fista_loop` states the stopping rule)."""
    a_quad, b_lin, l1, lip, half_yy = _enet_problem(
        moments, reg_param, elastic_net_param, fit_intercept, standardization
    )
    coef, _, _, n_iter, _ = _fista_loop(
        a_quad, b_lin, l1, lip, tol, _fista_start(a_quad, init_coef), max_iter
    )
    return _enet_result(moments, a_quad, b_lin, l1, half_yy, coef, n_iter, fit_intercept)


_enet_prep = jax.jit(_enet_problem, static_argnames=("fit_intercept", "standardization"))


@partial(jax.jit, static_argnames=("max_iter", "every"))
def _enet_segment(a_quad, b_lin, l1, lip, tol, coef, z, t, it, delta, max_iter: int, every: int):
    """Up to ``every`` iterations of :func:`_fista_loop` from an explicit
    carry, the (coef, momentum, t, iteration, delta) state a pytree between
    segments."""
    return _fista_loop(a_quad, b_lin, l1, lip, tol, (coef, z, t, it, delta), max_iter, every)


_enet_finish = jax.jit(_enet_result, static_argnames=("fit_intercept",))


def solve_elastic_net_resumable(
    moments: Moments,
    reg_param: float,
    elastic_net_param: float,
    checkpointer,
    fit_intercept: bool = True,
    standardization: bool = True,
    max_iter: int = 100,
    tol: float = 1e-6,
    init_coef=None,
    mesh=None,
) -> EnetResult:
    """Preemption-tolerant :func:`solve_elastic_net`: host outer loop
    over jitted FISTA segments (:func:`_fista_loop` with a budget) with
    async checkpoint snapshots between them. Same returns, bit-identical."""
    from spark_rapids_ml_tpu.robustness.checkpoint import (
        replicate_state_onto_mesh,
        segment_boundary,
    )
    import time

    from spark_rapids_ml_tpu.observability.costs import ledgered_call
    from spark_rapids_ml_tpu.observability.metrics import observe_segment_seconds
    from spark_rapids_ml_tpu.robustness.faults import fault_point
    from spark_rapids_ml_tpu.utils.tracing import TraceColor, TraceRange, bump_counter

    a_quad, b_lin, l1, lip, half_yy = _enet_prep(
        moments, reg_param, elastic_net_param,
        fit_intercept=fit_intercept, standardization=standardization,
    )
    carry = _fista_start(a_quad, init_coef)
    restored = checkpointer.restore_latest(template=carry)
    if restored is not None:
        _, carry = restored
        if mesh is not None:
            carry = replicate_state_onto_mesh(carry, mesh)

    while True:
        it = int(carry[3])
        seg_t0 = time.perf_counter()
        with TraceRange("segment linear.enet", TraceColor.PURPLE):
            fault_point("solver.segment")
            carry = ledgered_call(
                _enet_segment, (a_quad, b_lin, l1, lip, tol, *carry),
                static=dict(max_iter=max_iter, every=checkpointer.every),
                name="linear.enet.segment",
            )
            done = int(carry[3]) - it
            if done == 0:  # the stopping rule held at the segment's first check
                break
            bump_counter("checkpoint.segments")
            bump_counter("checkpoint.solver_iters", done)
        observe_segment_seconds("linear.enet", time.perf_counter() - seg_t0)
        checkpointer.save_async(int(carry[3]), carry)
        segment_boundary(checkpointer)

    checkpointer.finalize_success()
    return _enet_finish(
        moments, a_quad, b_lin, l1, half_yy, carry[0], carry[3],
        fit_intercept=fit_intercept,
    )


def solve_normal_host(
    xtx,
    xty,
    x_sum,
    y_sum,
    count,
    reg_param: float = 0.0,
    fit_intercept: bool = True,
    standardization: bool = True,
):
    """Host fp64 twin of :func:`solve_normal` — same math, NumPy/LAPACK.

    The dd precision path accumulates its sufficient statistics as exact
    fp64 (ops.doubledouble.normal_eq_stats_dd); solving them through the
    jitted fp32 path would throw that precision away on a no-x64 platform,
    so the O(d^3) solve runs on the host in fp64 (the reference's
    driver-side breeze/LAPACK position, RapidsRowMatrix.scala:110-123).
    """
    import numpy as np

    xtx = np.asarray(xtx, dtype=np.float64)
    xty = np.asarray(xty, dtype=np.float64)
    x_sum = np.asarray(x_sum, dtype=np.float64)
    n = float(count)
    x_mean = x_sum / n
    y_mean = float(y_sum) / n
    if fit_intercept:
        a = xtx - n * np.outer(x_mean, x_mean)
        b = xty - n * x_mean * y_mean
    else:
        a = xtx
        b = xty
    if standardization:
        var = np.maximum(
            (np.diag(xtx) - n * x_mean * x_mean) / max(n - 1.0, 1.0), 0.0
        )
    else:
        var = np.ones(a.shape[0], dtype=np.float64)
    a_reg = a + (n * reg_param) * np.diag(var)
    try:
        coef = np.linalg.solve(a_reg, b)
        if not np.all(np.isfinite(coef)):
            raise np.linalg.LinAlgError
    except np.linalg.LinAlgError:
        w, v = np.linalg.eigh(a_reg)
        tol = np.max(np.abs(w)) * a.shape[0] * np.finfo(np.float64).eps
        w_inv = np.where(w > tol, 1.0 / np.where(w > tol, w, 1.0), 0.0)
        coef = v @ (w_inv * (v.T @ b))
    intercept = (y_mean - float(np.dot(x_mean, coef))) if fit_intercept else 0.0
    return coef, intercept


def normal_eq_stats_streaming(block_pairs, dtype=None, precision: str = "highest"):
    """Accumulate RAW sufficient statistics over an ITERABLE of (X, y)
    blocks (:func:`raw_moments` of each, added up) — the streaming
    sibling of :func:`normal_eq_stats`; :func:`moments_from_raw` centres
    the sums for the solvers.

    Every downstream solver (normal equations, ridge, elastic-net FISTA)
    consumes only these O(d^2) moments, so a dataset of any length fits in
    one block of device memory at a time. Blocks may come from a generator
    (e.g. ``native.NpyBlockReader.iter_blocks``) and are consumed lazily —
    nothing is concatenated on the host.

    Returns the same (xtx, xty, x_sum, y_sum, yty, count) tuple.
    """
    import numpy as np

    from spark_rapids_ml_tpu.robustness.faults import fault_point

    def _upload(pair):
        xb, yb = pair
        if getattr(xb, "shape", (1,))[0] == 0:
            # Empty partitions densify to (0, 0) — no rows, no width info.
            return None
        return (
            jnp.asarray(np.ascontiguousarray(xb), dtype=dtype),
            jnp.asarray(np.ascontiguousarray(yb), dtype=dtype),
        )

    from spark_rapids_ml_tpu.core.serving import prefetch_blocks

    acc = None
    d = None
    # Double-buffered: pair k+1 densifies/uploads while pair k's moment
    # program runs; accumulation order is unchanged (bit-identical).
    for pair in prefetch_blocks(block_pairs, _upload):
        if pair is None:
            continue
        xj, yj = pair
        fault_point("solver.segment")
        if d is None:
            d = xj.shape[1]
        elif xj.shape[1] != d:
            raise ValueError(
                f"inconsistent feature dims across blocks: {xj.shape[1]} vs {d}"
            )
        if xj.shape[0] != yj.shape[0]:
            raise ValueError(
                f"block rows mismatch: X has {xj.shape[0]}, y has {yj.shape[0]}"
            )
        mask = jnp.ones(xj.shape[0], dtype=xj.dtype)
        stats = raw_moments(xj, yj, mask, precision=precision)
        acc = stats if acc is None else tuple(a + s for a, s in zip(acc, stats))
    if acc is None:
        raise ValueError("no blocks to accumulate")
    return acc

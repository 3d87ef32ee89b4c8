"""Linear model kernels — normal-equation sufficient statistics on the MXU.

Beyond-PCA capability (the normal-equation GEMM path; no benchmark cell
yet: ROADMAP.md Reach 9). The sufficient statistics
(X^T X, X^T y, column sums) are one fused jitted computation — the same
masked/shardable shape as the covariance kernel, so the distributed story is
identical: row-shard x/y over the mesh data axis and XLA inserts the psum.

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
from typing import Tuple

import jax
import jax.numpy as jnp

from spark_rapids_ml_tpu.ops.linalg import soft_threshold
from spark_rapids_ml_tpu.ops.precision import make_dot


@partial(jax.jit, static_argnames=("precision",))
def normal_eq_stats(
    x: jax.Array, y: jax.Array, mask: jax.Array | None, precision: str = "highest"
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Masked sufficient statistics in one pass.

    Returns (xtx, xty, x_sum, y_sum, yty, count): raw (uncentered) moments;
    centering happens in the solver where it is O(d^2), not O(n d).

    ``mask=None`` means "all rows real, weight 1" and skips the masking
    multiplies entirely — at small d this config is bytes-bound and the
    x*mask pass would nearly double the HBM traffic for nothing.
    """
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


def _centered_moments(xtx, xty, x_sum, y_sum, count, fit_intercept, standardization):
    """Shared pre-solve reduction: centered Gram/cross moments, means, and
    the per-feature variance used as the standardization penalty weight.

    sigma^2 is the TRUE feature variance (centered second moment) in both
    intercept modes — Spark standardizes by the feature stddev regardless
    of fitIntercept. Returns (a, b, x_mean, y_mean, var_weights).
    """
    n = count
    x_mean = x_sum / n
    y_mean = y_sum / n
    if fit_intercept:
        # centered moments: Xc^T Xc = X^T X - n * mean mean^T
        a = xtx - n * jnp.outer(x_mean, x_mean)
        b = xty - n * x_mean * y_mean
    else:
        a = xtx
        b = xty
    if standardization:
        var = jnp.maximum(
            (jnp.diag(xtx) - n * x_mean * x_mean) / jnp.maximum(n - 1, 1), 0.0
        )
    else:
        var = jnp.ones(a.shape[0], dtype=a.dtype)
    return a, b, x_mean, y_mean, var


@partial(jax.jit, static_argnames=("fit_intercept", "standardization"))
def solve_normal(
    xtx: jax.Array,
    xty: jax.Array,
    x_sum: jax.Array,
    y_sum: jax.Array,
    count: jax.Array,
    reg_param: float = 0.0,
    fit_intercept: bool = True,
    standardization: bool = True,
):
    """Solve the (regularized) normal equations from raw moments.

    Returns (coefficients (d,), intercept scalar). Cholesky with a
    singularity fallback to eigh-based pseudo-solve (minimum-norm), which
    handles rank-deficient designs the way LAPACK-backed Spark does via
    quasi-Newton fallback.
    """
    n = count
    a, b, x_mean, y_mean, penalty = _centered_moments(
        xtx, xty, x_sum, y_sum, count, fit_intercept, standardization
    )
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


@partial(jax.jit, static_argnames=("fit_intercept", "standardization", "max_iter"))
def solve_elastic_net(
    xtx: jax.Array,
    xty: jax.Array,
    x_sum: jax.Array,
    y_sum: jax.Array,
    count: jax.Array,
    reg_param: float,
    elastic_net_param: float,
    fit_intercept: bool = True,
    standardization: bool = True,
    max_iter: int = 2000,
    tol: float = 1e-7,
    init_coef=None,
):
    """Elastic-net least squares from the SAME sufficient statistics.

    minimize 1/(2n)||y - Xb - b0||^2
             + regParam * (alpha * sum_j w1_j |b_j|
                           + (1-alpha)/2 * sum_j w2_j b_j^2)
    with w1 = sigma, w2 = sigma^2 under standardization (the original-space
    form of penalizing standardized coefficients, matching the L2 path),
    w = 1 otherwise. Solved by FISTA on the quadratic moment form — the
    gradient is (A b - B)/n with A = Xc^T Xc, so iterations are O(d^2)
    vector-matrix work independent of n: the data was consumed by ONE GEMM
    pass (``normal_eq_stats``), the accelerated proximal loop never touches
    it again. Returns (coefficients, intercept, n_iter).
    """
    n = count
    a, b, x_mean, y_mean, w2 = _centered_moments(
        xtx, xty, x_sum, y_sum, count, fit_intercept, standardization
    )
    d = a.shape[0]
    w1 = jnp.sqrt(w2) if standardization else jnp.ones(d, dtype=a.dtype)

    alpha = elastic_net_param
    a_quad = a / n + reg_param * (1.0 - alpha) * jnp.diag(w2)
    b_lin = b / n
    l1 = reg_param * alpha * w1  # per-coordinate soft-threshold level

    # Lipschitz constant of the quadratic part: its largest eigenvalue.
    lip = jnp.maximum(jnp.max(jnp.linalg.eigvalsh(a_quad)), 1e-12)

    def cond(carry):
        _, _, _, it, delta = carry
        return jnp.logical_and(it < max_iter, delta > tol)

    def body(carry):
        c, z, t, it, _ = carry
        grad = a_quad @ z - b_lin
        c_new = soft_threshold(z - grad / lip, l1 / lip)
        t_new = (1.0 + jnp.sqrt(1.0 + 4.0 * t * t)) / 2.0
        z_new = c_new + ((t - 1.0) / t_new) * (c_new - c)
        delta = jnp.max(jnp.abs(c_new - c))
        return c_new, z_new, t_new, it + 1, delta

    # Warm start (partial_fit / regularization-path sweeps): FISTA from
    # a previous optimum in the ORIGINAL coefficient space — the carry's
    # own space, so no mapping is needed. Momentum restarts from the
    # seed (z = c, t = 1): plain FISTA initialization, just not at zero.
    c0 = (
        jnp.zeros(d, dtype=a.dtype)
        if init_coef is None
        else jnp.asarray(init_coef, dtype=a.dtype)
    )
    init = (c0, c0, jnp.asarray(1.0, a.dtype), 0, jnp.asarray(jnp.inf, a.dtype))
    coef, _, _, n_iter, _ = jax.lax.while_loop(cond, body, init)
    intercept = jnp.where(fit_intercept, y_mean - jnp.dot(x_mean, coef), 0.0)
    return coef, intercept, n_iter


@partial(jax.jit, static_argnames=("fit_intercept", "standardization"))
def _enet_prep(
    xtx, xty, x_sum, y_sum, count, reg_param, elastic_net_param,
    fit_intercept: bool, standardization: bool,
):
    """:func:`solve_elastic_net`'s pre-loop reduction (quadratic form,
    soft-threshold levels, Lipschitz constant, means) as one small
    program, shared by every segment of a resumable solve."""
    n = count
    a, b, x_mean, y_mean, w2 = _centered_moments(
        xtx, xty, x_sum, y_sum, count, fit_intercept, standardization
    )
    d = a.shape[0]
    w1 = jnp.sqrt(w2) if standardization else jnp.ones(d, dtype=a.dtype)
    alpha = elastic_net_param
    a_quad = a / n + reg_param * (1.0 - alpha) * jnp.diag(w2)
    b_lin = b / n
    l1 = reg_param * alpha * w1
    lip = jnp.maximum(jnp.max(jnp.linalg.eigvalsh(a_quad)), 1e-12)
    return a_quad, b_lin, l1, lip, x_mean, y_mean


@partial(jax.jit, static_argnames=("max_iter", "every"))
def _enet_segment(
    a_quad, b_lin, l1, lip, tol, coef, z, t, it, delta,
    max_iter: int, every: int,
):
    """Up to ``every`` FISTA iterations from an explicit carry — exactly
    :func:`solve_elastic_net`'s loop body and stopping rule plus a
    segment budget, the (coef, momentum, t, iteration, delta) state a
    pytree between segments."""

    def cond(carry):
        _, _, _, it, delta, seg = carry
        return jnp.logical_and(
            jnp.logical_and(it < max_iter, delta > tol), seg < every
        )

    def body(carry):
        c, z, t, it, _, seg = carry
        grad = a_quad @ z - b_lin
        c_new = soft_threshold(z - grad / lip, l1 / lip)
        t_new = (1.0 + jnp.sqrt(1.0 + 4.0 * t * t)) / 2.0
        z_new = c_new + ((t - 1.0) / t_new) * (c_new - c)
        delta = jnp.max(jnp.abs(c_new - c))
        return c_new, z_new, t_new, it + 1, delta, seg + 1

    coef, z, t, it, delta, _ = jax.lax.while_loop(
        cond, body, (coef, z, t, it, delta, 0)
    )
    return coef, z, t, it, delta


def solve_elastic_net_resumable(
    xtx, xty, x_sum, y_sum, count,
    reg_param: float,
    elastic_net_param: float,
    checkpointer,
    fit_intercept: bool = True,
    standardization: bool = True,
    max_iter: int = 2000,
    tol: float = 1e-7,
    init_coef=None,
    mesh=None,
):
    """Preemption-tolerant :func:`solve_elastic_net`: host outer loop
    over jitted FISTA segments with async checkpoint snapshots between
    them. Same returns (coefficients, intercept, n_iter), bit-identical."""
    from spark_rapids_ml_tpu.robustness.checkpoint import (
        replicate_state_onto_mesh,
        segment_boundary,
    )
    import time

    from spark_rapids_ml_tpu.observability.costs import ledgered_call
    from spark_rapids_ml_tpu.observability.metrics import observe_segment_seconds
    from spark_rapids_ml_tpu.robustness.faults import fault_point
    from spark_rapids_ml_tpu.utils.tracing import TraceColor, TraceRange, bump_counter

    a_quad, b_lin, l1, lip, x_mean, y_mean = _enet_prep(
        xtx, xty, x_sum, y_sum, count, reg_param, elastic_net_param,
        fit_intercept=fit_intercept, standardization=standardization,
    )
    d = a_quad.shape[0]
    dt = a_quad.dtype
    # Same warm-start contract as solve_elastic_net: original-space seed,
    # momentum restarted at the seed.
    c0 = (
        jnp.zeros(d, dtype=dt)
        if init_coef is None
        else jnp.asarray(init_coef, dtype=dt)
    )
    carry = (
        c0, c0, jnp.asarray(1.0, dt), jnp.asarray(0), jnp.asarray(jnp.inf, dt)
    )
    restored = checkpointer.restore_latest(template=carry)
    if restored is not None:
        _, carry = restored
        if mesh is not None:
            carry = replicate_state_onto_mesh(carry, mesh)

    while True:
        it, delta = int(carry[3]), float(carry[4])
        if not (it < max_iter and delta > tol):
            break
        seg_t0 = time.perf_counter()
        with TraceRange("segment linear.enet", TraceColor.PURPLE):
            fault_point("solver.segment")
            carry = ledgered_call(
                _enet_segment, (a_quad, b_lin, l1, lip, tol, *carry),
                static=dict(max_iter=max_iter, every=checkpointer.every),
                name="linear.enet.segment",
            )
            bump_counter("checkpoint.segments")
            bump_counter("checkpoint.solver_iters", int(carry[3]) - it)
        observe_segment_seconds("linear.enet", time.perf_counter() - seg_t0)
        checkpointer.save_async(int(carry[3]), carry)
        segment_boundary(checkpointer)

    coef, _, _, n_iter, _ = carry
    intercept = jnp.where(fit_intercept, y_mean - jnp.dot(x_mean, coef), 0.0)
    checkpointer.finalize_success()
    return coef, intercept, n_iter


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
    """Accumulate the sufficient statistics over an ITERABLE of (X, y)
    blocks — the streaming form of :func:`normal_eq_stats`.

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
        stats = normal_eq_stats(xj, yj, mask, precision=precision)
        acc = stats if acc is None else tuple(a + s for a, s in zip(acc, stats))
    if acc is None:
        raise ValueError("no blocks to accumulate")
    return acc

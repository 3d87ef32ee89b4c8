"""Logistic regression kernels — masked softmax/sigmoid loss + jitted L-BFGS.

Beyond-the-reference capability (the reference ships only PCA — SURVEY.md §2);
the model surface mirrors ``org.apache.spark.ml.classification
.LogisticRegression``, whose optimizer is breeze L-BFGS over a
DiffFunction aggregated with treeAggregate. Here the entire optimization is
ONE jitted program: loss+gradient are masked GEMMs on the MXU and the L-BFGS
update (optax.lbfgs with zoom linesearch) runs inside ``lax.while_loop`` —
no per-iteration host round-trip. Under a mesh, ``x``/``y``/``mask`` arrive
row-sharded and XLA inserts the gradient psum over ICI (GSPMD), giving the
treeAggregate analogue for free.

Objective (Spark semantics):
    (1/n) sum_i logloss_i
      + regParam * (alpha ||w||_1 + (1 - alpha)/2 ||w||^2)
with the penalty on coefficients of STANDARDIZED features when
``standardization=True`` (optimize in scaled space, map back), intercept
never penalized. alpha = 0 (pure L2) runs jitted L-BFGS
(:func:`fit_logistic`); alpha > 0 runs FISTA proximal gradient
(:func:`fit_logistic_elastic_net`) — Spark's OWL-QN analogue. Multinomial
uses the over-parameterized softmax; when regParam == 0 the class axis is
mean-centered for identifiability (Spark does the same pivoting
correction).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from spark_rapids_ml_tpu.ops.linalg import soft_threshold
from spark_rapids_ml_tpu.ops.precision import as_dot, make_dot


class LogisticFit(NamedTuple):
    """Result of :func:`fit_logistic` (all device arrays)."""

    weights: jax.Array  # (d, c) coefficients in ORIGINAL feature space
    intercepts: jax.Array  # (c,)
    n_iter: jax.Array  # scalar int
    loss: jax.Array  # final objective value (standardized space)


#: Row-block length of the fused one-pass objective: big enough that the
#: per-evaluation GEMMs stay MXU-bound, small enough that a block's
#: standardized slice is a cache/VMEM-resident temporary instead of a
#: materialized (n, d) HBM array.
_FUSED_BLOCK_ROWS = 65536


def _make_logistic_loss(
    x, y_target, mask, offset, scale, n, reg_param, c, fit_intercept, dot,
    fused=False,
):
    """The ONE home of the (standardized-space) logistic objective —
    closed over by the monolithic :func:`fit_logistic` program, the
    segmented :func:`_lbfgs_segment` program, and the finalizer, so all
    three optimize/evaluate literally the same expression (the
    bit-identity bar of the checkpoint subsystem).

    ``fused=False`` returns the plain objective (gradients via autodiff,
    which saves the standardized (n, d) design as a residual — X is
    effectively streamed twice per evaluation). ``fused=True`` returns a
    ``jax.custom_vjp`` objective whose forward pass computes the value
    AND the analytic gradient in ONE blocked sweep over X — the algebra
    needs only X^T(p - y) and the logloss sum, so each row block's
    standardized slice lives and dies on-chip (the second
    X pass was ~16.7% of the fit's HBM traffic). The fused callable also
    exposes ``.value_and_grad(params)`` for drivers that want both
    without round-tripping through AD. Fused and legacy agree to float
    tolerance (per-block partial sums reduce in a different order);
    every segmented/monolithic pair shares ONE flag, so checkpoint
    bit-identity is preserved in both modes."""
    dot = as_dot(dot)

    def _block_terms(xb, yb, mb, w, b):
        """One row block's (masked loss sum, unnormalized dL/dw, dL/db)."""
        xs = (xb - offset) / scale
        logits = dot(xs, w)
        if fit_intercept:
            logits = logits + b
        if c == 1:
            z = logits[:, 0]
            # log(1+e^z) - y z, numerically stable via softplus
            per_row = jax.nn.softplus(z) - yb * z
            dz = ((jax.nn.sigmoid(z) - yb) * mb)[:, None]
        else:
            logp = jax.nn.log_softmax(logits, axis=1)
            per_row = -jnp.sum(yb * logp, axis=1)
            dz = (jnp.exp(logp) - yb) * mb[:, None]
        loss_b = jnp.sum(per_row * mb)
        gw_b = dot(xs.T, dz)
        gb_b = jnp.sum(dz, axis=0)
        return loss_b, gw_b, gb_b

    if not fused:

        def loss_fn(params):
            w, b = params
            xs = (x - offset) / scale
            logits = dot(xs, w)
            if fit_intercept:
                logits = logits + b
            if c == 1:
                z = logits[:, 0]
                # log(1+e^z) - y z, numerically stable via softplus
                per_row = jax.nn.softplus(z) - y_target * z
            else:
                per_row = -jnp.sum(
                    y_target * jax.nn.log_softmax(logits, axis=1), axis=1
                )
            data_loss = jnp.sum(per_row * mask) / n
            return data_loss + 0.5 * reg_param * jnp.sum(w * w)

        return loss_fn

    nrows = x.shape[0]
    bs = min(_FUSED_BLOCK_ROWS, nrows)

    def value_and_grad(params):
        w, b = params
        if nrows <= bs:
            loss_s, gw_s, gb_s = _block_terms(x, y_target, mask, w, b)
        else:
            nb = -(-nrows // bs)

            def body(i, acc):
                l_a, gw_a, gb_a = acc
                # The last block slides back to stay in bounds; rows the
                # previous block already counted mask to zero.
                start = jnp.minimum(i * bs, nrows - bs)
                xb = jax.lax.dynamic_slice_in_dim(x, start, bs)
                yb = jax.lax.dynamic_slice_in_dim(y_target, start, bs)
                mb = jax.lax.dynamic_slice_in_dim(mask, start, bs)
                keep = (start + jnp.arange(bs)) >= i * bs
                l_b, gw_b, gb_b = _block_terms(
                    xb, yb, mb * keep.astype(mb.dtype), w, b
                )
                return l_a + l_b, gw_a + gw_b, gb_a + gb_b

            loss_s, gw_s, gb_s = jax.lax.fori_loop(
                0, nb, body,
                (jnp.zeros((), x.dtype), jnp.zeros_like(w), jnp.zeros((c,), x.dtype)),
            )
        value = loss_s / n + 0.5 * reg_param * jnp.sum(w * w)
        gw = gw_s / n + reg_param * w
        gb = gb_s / n if fit_intercept else jnp.zeros_like(b)
        return value, (gw, gb.astype(b.dtype))

    @jax.custom_vjp
    def loss_fn(params):
        return value_and_grad(params)[0]

    def _fwd(params):
        value, grad = value_and_grad(params)
        return value, grad

    def _bwd(grad, ct):
        return (jax.tree_util.tree_map(lambda g: g * ct, grad),)

    loss_fn.defvjp(_fwd, _bwd)
    loss_fn.value_and_grad = value_and_grad
    return loss_fn


def _masked_feature_moments(x: jax.Array, mask: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Weighted per-feature mean and stddev (population, like Spark's scaler).

    The mask may carry fractional weightCol weights, so it must enter the
    variance LINEARLY — squaring it (masking the residual instead of the
    squared residual) would inflate sigma by sqrt(w) under uniform weights.
    """
    n = jnp.sum(mask)
    mean = jnp.sum(x * mask[:, None], axis=0) / n
    var = jnp.sum(((x - mean) ** 2) * mask[:, None], axis=0) / n
    return mean, jnp.sqrt(var)


@partial(
    jax.jit,
    static_argnames=(
        "n_classes",
        "fit_intercept",
        "standardization",
        "max_iter",
        "precision",
        "multinomial",
        "fused",
    ),
)
def fit_logistic(
    x: jax.Array,
    y: jax.Array,
    mask: jax.Array,
    n_classes: int,
    reg_param: float = 0.0,
    fit_intercept: bool = True,
    standardization: bool = True,
    max_iter: int = 100,
    tol: float = 1e-6,
    precision: str = "highest",
    multinomial: bool = False,
    init_w: jax.Array | None = None,
    init_b: jax.Array | None = None,
    fused: bool = True,
) -> LogisticFit:
    """Fit binomial or multinomial logistic regression.

    ``init_w`` (d, c) / ``init_b`` (c,) warm-start the optimizer from an
    ORIGINAL-space solution (e.g. a previous model) — mapped into the
    standardized optimization space internally; default zeros.

    ``x``: (n, d); ``y``: (n,) integer labels in [0, n_classes); ``mask``:
    (n,) 1.0 for real rows, 0.0 for padding (mesh row-sharding pads).
    Binomial (``n_classes == 2`` and not ``multinomial``) trains a single
    sigmoid column (c = 1); ``multinomial=True`` trains the full
    (d, n_classes) softmax matrix even at 2 classes — the two families'
    optima differ under L2 (softmax splits the penalty across both class
    columns), so the 2-class case must NOT be collapsed to sigmoid when
    multinomial semantics are requested.
    """
    if n_classes < 2:
        raise ValueError(f"need at least 2 classes, got {n_classes}")
    c = n_classes if (multinomial or n_classes > 2) else 1
    d = x.shape[1]
    dtype = x.dtype
    dot = make_dot(precision)
    n = jnp.sum(mask)

    mean, sigma = _masked_feature_moments(x, mask)
    # Padded / constant features have sigma 0 — scale by 1 there (their
    # coefficients stay 0: zero column => zero gradient under L2 from init 0).
    safe_sigma = jnp.where(sigma > 0, sigma, 1.0)
    if standardization:
        # Center ONLY when an intercept exists to absorb the shift back in
        # original space; without an intercept, scale-only (Spark does the
        # same — otherwise the returned coefficients would describe a
        # different function than the one optimized).
        offset = mean if fit_intercept else jnp.zeros_like(mean)
        scale = safe_sigma
    else:
        offset = jnp.zeros_like(mean)
        scale = jnp.ones_like(safe_sigma)

    if c == 1:
        y_target = (y == 1).astype(dtype)
    else:
        y_target = jax.nn.one_hot(y, c, dtype=dtype)

    loss_fn = _make_logistic_loss(
        x, y_target, mask, offset, scale, n, reg_param, c, fit_intercept, dot,
        fused=fused,
    )

    if init_w is None:
        w0 = jnp.zeros((d, c), dtype=dtype)
        b0 = jnp.zeros((c,), dtype=dtype)
    else:
        # Inverse of the final back-map: the optimizer works in
        # standardized space (w_std = w_orig * scale; the intercept
        # re-absorbs the centering offset).
        w_orig0 = jnp.asarray(init_w, dtype=dtype)
        w0 = w_orig0 * scale[:, None]
        if fit_intercept:
            # Absorb the centering offset whether or not an original-space
            # intercept was supplied — (w_orig, 0) must start as the SAME
            # decision function, not a shifted one.
            b_orig0 = (
                jnp.asarray(init_b, dtype=dtype)
                if init_b is not None
                else jnp.zeros((c,), dtype=dtype)
            )
            b0 = b_orig0 + dot(offset, w_orig0)
        else:
            # No intercept in the model: b is never optimized (zero
            # gradient), so a stale nonzero init would leak into predict.
            b0 = jnp.zeros((c,), dtype=dtype)
    params0 = (w0, b0)

    solver = optax.lbfgs()
    value_and_grad = optax.value_and_grad_from_state(loss_fn)
    state0 = solver.init(params0)

    def cond(carry):
        _params, _state, it, gnorm = carry
        return jnp.logical_and(it < max_iter, gnorm > tol)

    def body(carry):
        params, state, it, _ = carry
        value, grad = value_and_grad(params, state=state)
        updates, state = solver.update(
            grad, state, params, value=value, grad=grad, value_fn=loss_fn
        )
        params = optax.apply_updates(params, updates)
        gnorm = optax.global_norm(grad)
        return params, state, it + 1, gnorm

    init = (params0, state0, jnp.asarray(0), jnp.asarray(jnp.inf, dtype=dtype))
    (w, b), state, n_iter, _ = jax.lax.while_loop(cond, body, init)

    # Identifiability pivot for unregularized softmax (Spark's centering).
    if c > 1:
        do_center = reg_param == 0.0
        w = jnp.where(do_center, w - jnp.mean(w, axis=1, keepdims=True), w)
        b = jnp.where(do_center, b - jnp.mean(b), b)

    # Map standardized-space solution back to original feature space.
    w_orig = w / scale[:, None]
    b_orig = b - dot(offset, w_orig) if fit_intercept else b
    final_loss = loss_fn((w, b))
    return LogisticFit(w_orig, b_orig, n_iter, final_loss)


@partial(jax.jit, static_argnames=("fit_intercept", "standardization"))
def _logistic_prep(x, mask, fit_intercept: bool, standardization: bool):
    """The standardizer inputs of :func:`fit_logistic` — (offset, scale,
    n) as one small program, shared by every segment of a resumable fit
    instead of being refolded into each one."""
    n = jnp.sum(mask)
    mean, sigma = _masked_feature_moments(x, mask)
    safe_sigma = jnp.where(sigma > 0, sigma, 1.0)
    if standardization:
        offset = mean if fit_intercept else jnp.zeros_like(mean)
        scale = safe_sigma
    else:
        offset = jnp.zeros_like(mean)
        scale = jnp.ones_like(safe_sigma)
    return offset, scale, n


@partial(
    jax.jit,
    static_argnames=(
        "c", "fit_intercept", "max_iter", "every", "precision", "fused",
    ),
)
def _lbfgs_segment(
    x, y_target, mask, offset, scale, n, reg_param, tol,
    params, opt_state, it, gnorm,
    c: int, fit_intercept: bool, max_iter: int, every: int, precision: str,
    fused: bool = True,
):
    """Up to ``every`` L-BFGS iterations from an explicit optimizer
    state — exactly :func:`fit_logistic`'s loop body and stopping rule
    plus a segment budget, with the full (params, optax state, iteration,
    gradient norm) carry visible as a pytree between segments."""
    dot = make_dot(precision)
    loss_fn = _make_logistic_loss(
        x, y_target, mask, offset, scale, n, reg_param, c, fit_intercept, dot,
        fused=fused,
    )
    solver = optax.lbfgs()
    value_and_grad = optax.value_and_grad_from_state(loss_fn)

    def cond(carry):
        _params, _state, it, gnorm, seg = carry
        return jnp.logical_and(
            jnp.logical_and(it < max_iter, gnorm > tol), seg < every
        )

    def body(carry):
        params, state, it, _, seg = carry
        value, grad = value_and_grad(params, state=state)
        updates, state = solver.update(
            grad, state, params, value=value, grad=grad, value_fn=loss_fn
        )
        params = optax.apply_updates(params, updates)
        gnorm = optax.global_norm(grad)
        return params, state, it + 1, gnorm, seg + 1

    params, opt_state, it, gnorm, _ = jax.lax.while_loop(
        cond, body, (params, opt_state, it, gnorm, 0)
    )
    return params, opt_state, it, gnorm


@partial(
    jax.jit, static_argnames=("c", "fit_intercept", "precision", "fused")
)
def _logistic_finalize(
    x, y_target, mask, offset, scale, n, reg_param, w, b,
    c: int, fit_intercept: bool, precision: str, fused: bool = True,
):
    """:func:`fit_logistic`'s post-solve tail (identifiability pivot,
    back-map to original feature space, final objective) as its own
    program for the segmented driver."""
    dot = make_dot(precision)
    loss_fn = _make_logistic_loss(
        x, y_target, mask, offset, scale, n, reg_param, c, fit_intercept, dot,
        fused=fused,
    )
    if c > 1:
        do_center = reg_param == 0.0
        w = jnp.where(do_center, w - jnp.mean(w, axis=1, keepdims=True), w)
        b = jnp.where(do_center, b - jnp.mean(b), b)
    w_orig = w / scale[:, None]
    b_orig = b - dot(offset, w_orig) if fit_intercept else b
    return w_orig, b_orig, loss_fn((w, b))


def fit_logistic_resumable(
    x: jax.Array,
    y: jax.Array,
    mask: jax.Array,
    checkpointer,
    n_classes: int,
    reg_param: float = 0.0,
    fit_intercept: bool = True,
    standardization: bool = True,
    max_iter: int = 100,
    tol: float = 1e-6,
    precision: str = "highest",
    multinomial: bool = False,
    init_w: jax.Array | None = None,
    init_b: jax.Array | None = None,
    mesh=None,
    fused: bool = True,
) -> LogisticFit:
    """Preemption-tolerant :func:`fit_logistic` (the L-BFGS / L2 path):
    a host outer loop over jitted L-BFGS segments, the (params, optimizer
    state, iteration counter, gradient norm) pytree snapshotted
    asynchronously between segments, the fit resumed mid-solve from the
    latest valid checkpoint. Same returns, bit-identical solution."""
    from spark_rapids_ml_tpu.robustness.checkpoint import (
        replicate_state_onto_mesh,
        segment_boundary,
    )
    import time

    from spark_rapids_ml_tpu.observability.costs import ledgered_call
    from spark_rapids_ml_tpu.observability.metrics import observe_segment_seconds
    from spark_rapids_ml_tpu.robustness.faults import fault_point
    from spark_rapids_ml_tpu.utils.tracing import TraceColor, TraceRange, bump_counter

    if n_classes < 2:
        raise ValueError(f"need at least 2 classes, got {n_classes}")
    c = n_classes if (multinomial or n_classes > 2) else 1
    d = x.shape[1]
    dtype = x.dtype
    dot = make_dot(precision)
    offset, scale, n = _logistic_prep(
        x, mask, fit_intercept=fit_intercept, standardization=standardization
    )

    if c == 1:
        y_target = (y == 1).astype(dtype)
    else:
        y_target = jax.nn.one_hot(y, c, dtype=dtype)

    if init_w is None:
        w0 = jnp.zeros((d, c), dtype=dtype)
        b0 = jnp.zeros((c,), dtype=dtype)
    else:
        w_orig0 = jnp.asarray(init_w, dtype=dtype)
        w0 = w_orig0 * scale[:, None]
        if fit_intercept:
            b_orig0 = (
                jnp.asarray(init_b, dtype=dtype)
                if init_b is not None
                else jnp.zeros((c,), dtype=dtype)
            )
            b0 = b_orig0 + dot(offset, w_orig0)
        else:
            b0 = jnp.zeros((c,), dtype=dtype)

    params0 = (w0, b0)
    state0 = optax.lbfgs().init(params0)
    carry = (params0, state0, jnp.asarray(0), jnp.asarray(jnp.inf, dtype=dtype))
    restored = checkpointer.restore_latest(template=carry)
    if restored is not None:
        _, carry = restored
        if mesh is not None:
            carry = replicate_state_onto_mesh(carry, mesh)

    while True:
        it, gn = int(carry[2]), float(carry[3])
        if not (it < max_iter and gn > tol):
            break
        seg_t0 = time.perf_counter()
        with TraceRange("segment logistic.lbfgs", TraceColor.PURPLE):
            fault_point("solver.segment")
            params, opt_state, it_a, gn_a = ledgered_call(
                _lbfgs_segment,
                (x, y_target, mask, offset, scale, n,
                 reg_param, tol, carry[0], carry[1], carry[2], carry[3]),
                static=dict(
                    c=c, fit_intercept=fit_intercept, max_iter=max_iter,
                    every=checkpointer.every, precision=precision,
                    fused=fused,
                ),
                name="logistic.lbfgs.segment",
            )
            carry = (params, opt_state, it_a, gn_a)
            bump_counter("checkpoint.segments")
            bump_counter("checkpoint.solver_iters", int(it_a) - it)
        observe_segment_seconds("logistic.lbfgs", time.perf_counter() - seg_t0)
        checkpointer.save_async(int(it_a), carry)
        segment_boundary(checkpointer)

    (w, b), _, n_iter, _ = carry
    w_orig, b_orig, final_loss = _logistic_finalize(
        x, y_target, mask, offset, scale, n, reg_param, w, b,
        c=c, fit_intercept=fit_intercept, precision=precision, fused=fused,
    )
    checkpointer.finalize_success()
    return LogisticFit(w_orig, b_orig, n_iter, final_loss)


@partial(
    jax.jit,
    static_argnames=(
        "n_classes",
        "fit_intercept",
        "standardization",
        "max_iter",
        "precision",
        "multinomial",
        "fused",
    ),
)
def fit_logistic_elastic_net(
    x: jax.Array,
    y: jax.Array,
    mask: jax.Array,
    n_classes: int,
    reg_param: float,
    elastic_net_param: float,
    fit_intercept: bool = True,
    standardization: bool = True,
    max_iter: int = 500,
    tol: float = 1e-7,
    precision: str = "highest",
    multinomial: bool = False,
    fused: bool = True,
) -> LogisticFit:
    """Elastic-net logistic regression by FISTA (proximal gradient).

    Spark routes elasticNetParam > 0 to breeze OWL-QN; the TPU formulation
    is accelerated proximal gradient: the smooth part (log-loss + L2) takes
    one gradient GEMM pair per iteration, the L1 part is a soft-threshold
    prox on the coefficients (intercept never penalized), and the step is
    1/L with L from a power-iteration bound on the standardized Gram
    spectral norm — everything inside one ``lax.while_loop``.
    """
    if n_classes < 2:
        raise ValueError(f"need at least 2 classes, got {n_classes}")
    c = n_classes if (multinomial or n_classes > 2) else 1
    d = x.shape[1]
    dtype = x.dtype
    dot = make_dot(precision)
    n = jnp.sum(mask)

    mean, sigma = _masked_feature_moments(x, mask)
    safe_sigma = jnp.where(sigma > 0, sigma, 1.0)
    if standardization:
        offset = mean if fit_intercept else jnp.zeros_like(mean)
        scale = safe_sigma
    else:
        offset = jnp.zeros_like(mean)
        scale = jnp.ones_like(safe_sigma)

    if c == 1:
        y_target = (y == 1).astype(dtype)
    else:
        y_target = jax.nn.one_hot(y, c, dtype=dtype)

    reg1 = reg_param * elastic_net_param
    reg2 = reg_param * (1.0 - elastic_net_param)

    def xs_matvec(v):
        return dot((x - offset) / scale, v)

    def xs_rmatvec(u):
        return dot(((x - offset) / scale).T, u * mask)

    # Spectral norm of the masked standardized design via power iteration:
    # L_data = lambda_max(Xs^T M Xs) * curvature_bound / n, where the
    # per-row logistic curvature is <= 1/4 (sigmoid) or <= 1/2 (softmax).
    def power_body(_, v):
        u = xs_rmatvec(xs_matvec(v))
        return u / jnp.maximum(jnp.linalg.norm(u), 1e-30)

    # Randomized (fixed-key) start: a deterministic uniform vector can be
    # exactly orthogonal to the dominant eigenvector of a structured Gram
    # (e.g. d=2 with negative correlation), which would underestimate
    # lambda_max and make the fixed FISTA step divergent.
    v0 = jax.random.normal(jax.random.key(0), (d,), dtype=dtype)
    v0 = v0 / jnp.maximum(jnp.linalg.norm(v0), 1e-30)
    v = jax.lax.fori_loop(0, 30, power_body, v0)
    lam_max = jnp.linalg.norm(xs_rmatvec(xs_matvec(v)))
    curvature = 0.25 if c == 1 else 0.5
    # 1.1 safety margin: power iteration converges from below.
    lip = 1.1 * lam_max * curvature / n + reg2 + 1e-12

    # The FISTA smooth part (log-loss + L2 at reg2) IS the L-BFGS
    # objective at reg_param=reg2 — so the fused one-pass builder serves
    # both solvers from the same algebra.
    if fused:
        smooth_loss = _make_logistic_loss(
            x, y_target, mask, offset, scale, n, reg2, c, fit_intercept,
            dot, fused=True,
        )

        def grad_fn(params):
            return smooth_loss.value_and_grad(params)[1]

    else:

        def smooth_loss(params):
            w, b = params
            logits = xs_matvec(w)
            if fit_intercept:
                logits = logits + b
            if c == 1:
                z = logits[:, 0]
                per_row = jax.nn.softplus(z) - y_target * z
            else:
                per_row = -jnp.sum(
                    y_target * jax.nn.log_softmax(logits, axis=1), axis=1
                )
            return jnp.sum(per_row * mask) / n + 0.5 * reg2 * jnp.sum(w * w)

        grad_fn = jax.grad(smooth_loss)

    w0 = jnp.zeros((d, c), dtype=dtype)
    b0 = jnp.zeros((c,), dtype=dtype)

    def cond(carry):
        _, _, _, _, _, it, delta = carry
        return jnp.logical_and(it < max_iter, delta > tol)

    def body(carry):
        w, b, zw, zb, t, it, _ = carry
        gw, gb = grad_fn((zw, zb))
        w_new = soft_threshold(zw - gw / lip, reg1 / lip)
        b_new = jnp.where(fit_intercept, zb - gb / lip, zb)
        t_new = (1.0 + jnp.sqrt(1.0 + 4.0 * t * t)) / 2.0
        mom = (t - 1.0) / t_new
        zw_new = w_new + mom * (w_new - w)
        zb_new = b_new + mom * (b_new - b)
        delta = jnp.maximum(
            jnp.max(jnp.abs(w_new - w)), jnp.max(jnp.abs(b_new - b))
        )
        return w_new, b_new, zw_new, zb_new, t_new, it + 1, delta

    init = (
        w0, b0, w0, b0,
        jnp.asarray(1.0, dtype), jnp.asarray(0), jnp.asarray(jnp.inf, dtype),
    )
    w, b, _, _, _, n_iter, _ = jax.lax.while_loop(cond, body, init)

    w_orig = w / scale[:, None]
    b_orig = b - dot(offset, w_orig) if fit_intercept else b
    final_loss = smooth_loss((w, b)) + reg1 * jnp.sum(jnp.abs(w))
    return LogisticFit(w_orig, b_orig, n_iter, final_loss)


@partial(
    jax.jit, static_argnames=("c", "fit_intercept", "precision", "fused")
)
def _stream_block_value_grad(
    xb, yb, w, b, offset, scale, c, fit_intercept, precision,
    fused: bool = True,
):
    """UNnormalized block loss + gradient contribution for the streaming
    fit: sum_i logloss_i over this block only (the driver divides by the
    global n and adds the L2 term once). ``fused=True`` computes the
    value and the analytic gradient in one sweep of the block (no AD
    residual); ``fused=False`` keeps the autodiff formulation."""
    dot = make_dot(precision)
    dtype = xb.dtype
    if c == 1:
        y_t = (yb == 1).astype(dtype)
    else:
        y_t = jax.nn.one_hot(yb, c, dtype=dtype)

    if fused:
        xs = (xb - offset) / scale
        logits = dot(xs, w)
        if fit_intercept:
            logits = logits + b
        if c == 1:
            z = logits[:, 0]
            per_row = jax.nn.softplus(z) - y_t * z
            dz = (jax.nn.sigmoid(z) - y_t)[:, None]
        else:
            logp = jax.nn.log_softmax(logits, axis=1)
            per_row = -jnp.sum(y_t * logp, axis=1)
            dz = jnp.exp(logp) - y_t
        val = jnp.sum(per_row)
        gw = dot(xs.T, dz)
        gb = jnp.sum(dz, axis=0) if fit_intercept else jnp.zeros_like(b)
        return val, gw, gb

    def f(params):
        w_, b_ = params
        xs = (xb - offset) / scale
        logits = dot(xs, w_)
        if fit_intercept:
            logits = logits + b_
        if c == 1:
            z = logits[:, 0]
            per_row = jax.nn.softplus(z) - y_t * z
        else:
            per_row = -jnp.sum(y_t * jax.nn.log_softmax(logits, axis=1), axis=1)
        return jnp.sum(per_row)

    val, (gw, gb) = jax.value_and_grad(f)((w, b))
    return val, gw, gb


def streaming_label_feature_stats(pairs):
    """One pass over (X_block, y_block) pairs: feature moments in host
    fp64 (n, mean, sigma — the standardizer inputs) plus label integrality
    and range for the class count. O(d) state."""
    n = 0
    s = ss = None
    y_max = -1
    y_int_ok = True
    for xb, yb in pairs:
        b = np.asarray(xb, dtype=np.float64)
        yv = np.asarray(yb).ravel()
        if s is None:
            s = np.zeros(b.shape[1])
            ss = np.zeros(b.shape[1])
        s += b.sum(axis=0)
        ss += (b * b).sum(axis=0)
        n += b.shape[0]
        if yv.size:
            yi = yv.astype(np.int64)
            if not np.array_equal(yi, yv) or yi.min() < 0:
                y_int_ok = False
            y_max = max(y_max, int(yi.max()))
    if n == 0:
        raise ValueError("streaming source yielded no rows")
    mean = s / n
    sigma = np.sqrt(np.maximum(ss / n - mean * mean, 0.0))
    return n, mean, sigma, y_max, y_int_ok


def fit_logistic_streaming(
    pairs_factory,
    n_classes: int,
    n: int,
    mean: np.ndarray,
    sigma: np.ndarray,
    reg_param: float = 0.0,
    fit_intercept: bool = True,
    standardization: bool = True,
    max_iter: int = 100,
    tol: float = 1e-6,
    precision: str = "highest",
    multinomial: bool = False,
    dtype=None,
    fused: bool = True,
) -> LogisticFit:
    """Multi-pass L-BFGS fit over a RE-ITERABLE (X_block, y_block) source.

    Same objective and standardization semantics as :func:`fit_logistic`;
    memory is O(block + d*c): each objective evaluation streams the blocks
    through :func:`_stream_block_value_grad` (device GEMMs, device
    accumulation) while scipy's L-BFGS-B drives the O(d*c) optimizer state
    on host — the optimizer round trip per data pass is exactly the shape
    Spark's breeze-over-treeAggregate loop has (one driver update per
    distributed pass), so the streaming fit is also the faithful analogue
    of the reference lineage's execution model. Feature moments arrive
    precomputed (:func:`streaming_label_feature_stats`) so the caller's
    label scan and the standardizer share one pass.
    """
    from scipy.optimize import minimize

    if n_classes < 2:
        raise ValueError(f"need at least 2 classes, got {n_classes}")
    c = n_classes if (multinomial or n_classes > 2) else 1
    d = mean.shape[0]
    if dtype is None:
        dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    np_dtype = np.dtype(dtype)

    safe_sigma = np.where(sigma > 0, sigma, 1.0)
    if standardization:
        offset = mean if fit_intercept else np.zeros_like(mean)
        scale = safe_sigma
    else:
        offset = np.zeros_like(mean)
        scale = np.ones_like(safe_sigma)
    offset_j = jnp.asarray(offset, dtype=dtype)
    scale_j = jnp.asarray(scale, dtype=dtype)

    n_b = c if fit_intercept else 0

    def fun_grad(theta):
        from spark_rapids_ml_tpu.robustness.faults import fault_point

        fault_point("solver.segment")
        w = theta[: d * c].reshape(d, c)
        b = theta[d * c :] if fit_intercept else np.zeros(c)
        wj = jnp.asarray(w.astype(np_dtype))
        bj = jnp.asarray(b.astype(np_dtype))
        tot = jnp.zeros((), dtype)
        gw_acc = jnp.zeros((d, c), dtype)
        gb_acc = jnp.zeros((c,), dtype)

        def _upload(pair):
            xb, yb = pair
            return (
                jnp.asarray(np.ascontiguousarray(xb, dtype=np_dtype)),
                jnp.asarray(np.asarray(yb).ravel().astype(np.int32)),
            )

        from spark_rapids_ml_tpu.core.serving import prefetch_blocks

        # Double-buffered: pair k+1 densifies/uploads while pair k's
        # value+grad program runs; accumulation order is unchanged.
        for xj, yj in prefetch_blocks(pairs_factory(), _upload):
            v, gw, gb = _stream_block_value_grad(
                xj, yj, wj, bj, offset_j, scale_j, c, fit_intercept,
                precision, fused,
            )
            tot, gw_acc, gb_acc = tot + v, gw_acc + gw, gb_acc + gb
        val = float(tot) / n + 0.5 * reg_param * float(np.sum(w * w))
        g_w = np.asarray(gw_acc, dtype=np.float64) / n + reg_param * w
        out = [g_w.ravel()]
        if fit_intercept:
            out.append(np.asarray(gb_acc, dtype=np.float64) / n)
        return val, np.concatenate(out)

    theta0 = np.zeros(d * c + n_b)
    res = minimize(
        fun_grad,
        theta0,
        jac=True,
        method="L-BFGS-B",
        options={"maxiter": max_iter, "gtol": tol, "ftol": 1e-14},
    )
    w = res.x[: d * c].reshape(d, c)
    b = res.x[d * c :] if fit_intercept else np.zeros(c)

    if c > 1 and reg_param == 0.0:
        # Identifiability pivot for unregularized softmax (fit_logistic parity).
        w = w - w.mean(axis=1, keepdims=True)
        b = b - b.mean()

    w_orig = w / scale[:, None]
    b_orig = b - offset @ w_orig if fit_intercept else b
    return LogisticFit(
        w_orig, b_orig, np.int64(res.nit), np.float64(res.fun)
    )


@partial(jax.jit, static_argnames=("n_classes", "precision"))
def predict_logistic(
    x: jax.Array,
    weights: jax.Array,
    intercepts: jax.Array,
    n_classes: int,
    precision: str = "highest",
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(labels, probabilities (n, n_classes), raw logits (n, n_classes))."""
    dot = make_dot(precision)
    logits = dot(x, weights) + intercepts
    if weights.shape[1] == 1:
        z = logits[:, 0]
        p1 = jax.nn.sigmoid(z)
        probs = jnp.stack([1.0 - p1, p1], axis=1)
        raw = jnp.stack([-z, z], axis=1)
        labels = (p1 > 0.5).astype(jnp.int32)
    else:
        probs = jax.nn.softmax(logits, axis=1)
        raw = logits
        labels = jnp.argmax(logits, axis=1).astype(jnp.int32)
    return labels, probs, raw


@jax.jit
def classification_metrics(y: jax.Array, pred: jax.Array, mask: jax.Array):
    """(accuracy, error_rate) over unmasked rows."""
    n = jnp.sum(mask)
    correct = jnp.sum((y == pred).astype(mask.dtype) * mask)
    acc = correct / n
    return acc, 1.0 - acc

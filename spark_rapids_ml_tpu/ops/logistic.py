"""Logistic regression kernels — masked softmax/sigmoid loss + jitted L-BFGS.

Beyond-the-reference capability (the reference ships only PCA — SURVEY.md §2);
the model surface mirrors ``org.apache.spark.ml.classification
.LogisticRegression``, whose optimizer is breeze L-BFGS over a
DiffFunction aggregated with treeAggregate. Here the entire optimization is
ONE jitted program inside ``lax.while_loop`` — no per-iteration host
round-trip. The model is linear, so the L-BFGS iteration
(:func:`_lbfgs_iteration`) keeps the margins ``z = Xs w + b`` of the current
point and makes a CONSTANT number of passes over X: one for the direction's
margins ``u = Xs d_w + d_b``, then a strong-Wolfe zoom line search on
``phi(a) = mean(loss(z + a u)) + (reg/2)|w + a d_w|^2`` that reads only the
two cached (n, c) arrays (a trial step costs no pass over X), then one for
the gradient at the accepted point from ``z + a u``. The direction comes
from ``optax.scale_by_lbfgs``'s two-loop recursion. What a fit costs is
therefore a function of its configuration (``maxIter``, ``tol``), not of how
many trial steps its data asks of the line search. Under a mesh,
``x``/``y``/``mask`` arrive row-sharded and XLA inserts the gradient psum
over ICI (GSPMD), giving the treeAggregate analogue for free.

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
from typing import Callable, NamedTuple, Tuple

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
    #: Off the L-BFGS path the rest is None. Passes over the rows the fit
    #: made (moments, margins, gradients); trial steps its line searches
    #: took; the gradient (d, c), (c,) its last iteration computed at the
    #: returned point (standardized space, as ``loss``).
    x_passes: jax.Array | None = None
    linesearch_trials: jax.Array | None = None
    grad: tuple | None = None


#: Row-block length of the blocked passes over X: big enough that the
#: per-pass products stay bandwidth-bound, small enough that a block's
#: standardized slice is a cache/VMEM-resident temporary instead of a
#: materialized (n, d) HBM array.
_FUSED_BLOCK_ROWS = 65536

#: optax.lbfgs()'s own choices, kept: ten (s, y) pairs, a first step capped
#: to the unit ball, and its zoom search (20 trials, first guess 1).
_LBFGS_MEMORY = optax.scale_by_lbfgs(memory_size=10)
_ZOOM = optax.scale_by_zoom_linesearch(
    max_linesearch_steps=20, initial_guess_strategy="one"
)


class _RowPasses(NamedTuple):
    """The (standardized-space) logistic objective as the solvers use it:
    four functions of ``params = (w, b)`` and of margins ``z = xs @ w + b``,
    each blocked over ``_FUSED_BLOCK_ROWS`` rows, named by what they read."""

    #: ``params -> (value, grad)`` in one sweep: a block's two products,
    #: ``xs @ w`` and ``xs.T @ dz``, each read the block. FISTA's gradient.
    value_and_grad: Callable
    #: ``(w, b) -> z`` (n, c): ONE pass over X.
    margins: Callable
    #: ``(z, w) -> value``: O(n c), X not read.
    value_at: Callable
    #: ``(z, params) -> grad`` at ``params`` whose margins are ``z``:
    #: ``xs.T @ dz(z)``, ONE pass over X.
    grad_at: Callable


def _make_logistic_loss(
    x, y_target, mask, offset, scale, n, reg_param, c, fit_intercept, dot
) -> _RowPasses:
    """The ONE home of the (standardized-space) logistic objective —
    closed over by the monolithic :func:`fit_logistic` program, the
    segmented :func:`_lbfgs_segment` program, and FISTA, so all optimize
    literally the same expression (the bit-identity bar of the checkpoint
    subsystem).

    A block's standardized slice lives and dies in the block, and the
    gradient is analytic (no autodiff residual of the standardized design).
    The L-BFGS iteration never evaluates the objective at a point: it works
    on margins through ``margins`` / ``value_at`` / ``grad_at``, which cost
    one pass over X, none, and one."""
    dot = as_dot(dot)

    def _row_terms(logits, yb, mb):
        """(masked loss sum, masked dL/dlogits (rows, c)) of margins."""
        if c == 1:
            z = logits[:, 0]
            # log(1+e^z) - y z, numerically stable via softplus
            per_row = jax.nn.softplus(z) - yb * z
            dz = ((jax.nn.sigmoid(z) - yb) * mb)[:, None]
        else:
            logp = jax.nn.log_softmax(logits, axis=1)
            per_row = -jnp.sum(yb * logp, axis=1)
            dz = (jnp.exp(logp) - yb) * mb[:, None]
        return jnp.sum(per_row * mb), dz

    def _block_margins(xb, w, b):
        logits = dot((xb - offset) / scale, w)
        return logits + b if fit_intercept else logits

    def _block_grad(xb, dz):
        """One row block's unnormalized (dL/dw, dL/db) from its residuals."""
        return dot(((xb - offset) / scale).T, dz), jnp.sum(dz, axis=0)

    def _block_terms(xb, yb, mb, w, b):
        """One row block's (masked loss sum, unnormalized dL/dw, dL/db)."""
        loss_b, dz = _row_terms(_block_margins(xb, w, b), yb, mb)
        return (loss_b, *_block_grad(xb, dz))

    nrows = x.shape[0]
    bs = min(_FUSED_BLOCK_ROWS, nrows)
    nb = -(-nrows // bs)

    def _block(i, *rows):
        """Block ``i`` of X and of the row arrays ``rows``, with the mask
        of its rows that no earlier block counted: the last block slides
        back to stay in bounds."""
        start = jnp.minimum(i * bs, nrows - bs)
        keep = ((start + jnp.arange(bs)) >= i * bs).astype(x.dtype)
        cut = [jax.lax.dynamic_slice_in_dim(r, start, bs) for r in (x, *rows)]
        return start, keep, cut

    def _scaled(gw_s, gb_s, w, b):
        gb = gb_s / n if fit_intercept else jnp.zeros_like(b)
        return gw_s / n + reg_param * w, gb.astype(b.dtype)

    def value_at(z, w):
        return _row_terms(z, y_target, mask)[0] / n + 0.5 * reg_param * jnp.sum(w * w)

    def value_and_grad(params):
        w, b = params
        if nb == 1:
            loss_s, gw_s, gb_s = _block_terms(x, y_target, mask, w, b)
        else:

            def body(i, acc):
                _, keep, (xb, yb, mb) = _block(i, y_target, mask)
                return jax.tree_util.tree_map(
                    jnp.add, acc, _block_terms(xb, yb, mb * keep, w, b)
                )

            loss_s, gw_s, gb_s = jax.lax.fori_loop(
                0, nb, body,
                (jnp.zeros((), x.dtype), jnp.zeros_like(w), jnp.zeros((c,), x.dtype)),
            )
        value = loss_s / n + 0.5 * reg_param * jnp.sum(w * w)
        return value, _scaled(gw_s, gb_s, w, b)

    def margins(w, b):
        if nb == 1:
            return _block_margins(x, w, b)

        def body(i, z):
            start, _, (xb,) = _block(i)
            # rows of the slid-back block that an earlier one wrote are
            # written again with the same margins
            return jax.lax.dynamic_update_slice_in_dim(
                z, _block_margins(xb, w, b), start, axis=0
            )

        return jax.lax.fori_loop(0, nb, body, jnp.zeros((nrows, c), x.dtype))

    def grad_at(z, params):
        w, b = params
        if nb == 1:
            gw_s, gb_s = _block_grad(x, _row_terms(z, y_target, mask)[1])
        else:

            def body(i, acc):
                _, keep, (xb, zb, yb, mb) = _block(i, z, y_target, mask)
                dz = _row_terms(zb, yb, mb * keep)[1]
                return jax.tree_util.tree_map(jnp.add, acc, _block_grad(xb, dz))

            gw_s, gb_s = jax.lax.fori_loop(
                0, nb, body, (jnp.zeros_like(w), jnp.zeros((c,), x.dtype))
            )
        return _scaled(gw_s, gb_s, w, b)

    return _RowPasses(value_and_grad, margins, value_at, grad_at)


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


def _standardizer(x, mask, fit_intercept: bool, standardization: bool):
    """(offset, scale, n, passes over X): what the optimizer's space is."""
    n = jnp.sum(mask)
    if not standardization:
        d = x.shape[1]
        return jnp.zeros((d,), x.dtype), jnp.ones((d,), x.dtype), n, 0
    mean, sigma = _masked_feature_moments(x, mask)
    # Padded / constant features have sigma 0 — scale by 1 there (their
    # coefficients stay 0: zero column => zero gradient under L2 from init 0).
    scale = jnp.where(sigma > 0, sigma, 1.0)
    # Center ONLY when an intercept exists to absorb the shift back in
    # original space; without an intercept, scale-only (Spark does the
    # same — otherwise the returned coefficients would describe a
    # different function than the one optimized).
    offset = mean if fit_intercept else jnp.zeros_like(mean)
    return offset, scale, n, 2  # the means' pass, then the deviations'


def _class_targets(y, c: int, dtype):
    return (y == 1).astype(dtype) if c == 1 else jax.nn.one_hot(y, c, dtype=dtype)


def _start_params(init_w, init_b, offset, scale, d, c, fit_intercept, dot, dtype):
    """The optimizer's start in standardized space: zeros, or the inverse
    of the final back-map applied to an ORIGINAL-space warm start
    (w_std = w_orig * scale; the intercept re-absorbs the centering)."""
    if init_w is None:
        return jnp.zeros((d, c), dtype=dtype), jnp.zeros((c,), dtype=dtype)
    w_orig0 = jnp.asarray(init_w, dtype=dtype)
    if not fit_intercept:
        # No intercept in the model: b is never optimized (zero gradient),
        # so a stale nonzero init would leak into predict.
        return w_orig0 * scale[:, None], jnp.zeros((c,), dtype=dtype)
    # Absorb the centering offset whether or not an original-space
    # intercept was supplied — (w_orig, 0) must start as the SAME decision
    # function, not a shifted one.
    b_orig0 = (
        jnp.asarray(init_b, dtype=dtype) if init_b is not None
        else jnp.zeros((c,), dtype=dtype)
    )
    return w_orig0 * scale[:, None], b_orig0 + dot(offset, w_orig0)


class _LbfgsCarry(NamedTuple):
    """The whole solver state between two L-BFGS iterations — the
    ``while_loop`` carry of the monolithic fit, and the pytree a resumable
    fit snapshots between segments."""

    params: tuple  # (w (d, c), b (c,)) in standardized space
    memory: optax.OptState  # scale_by_lbfgs's (s, y) pairs
    margins: jax.Array  # (n, c): xs @ w + b at ``params``
    grad: tuple  # the objective's gradient at ``params``
    it: jax.Array  # iterations run
    gnorm: jax.Array  # norm of ``grad`` (infinite before the first iteration)
    x_passes: jax.Array  # passes over X so far
    trials: jax.Array  # line-search trial steps so far


def _lbfgs_start(passes: _RowPasses, params, prep_passes: int) -> _LbfgsCarry:
    """The carry at ``params``: one pass for its margins, one for its
    gradient. ``gnorm`` starts infinite: a fit runs one iteration at least,
    also from a warm start that is already at the optimum."""
    z = passes.margins(*params)
    grad = passes.grad_at(z, params)
    return _LbfgsCarry(
        params, _LBFGS_MEMORY.init(params), z, grad, jnp.asarray(0),
        jnp.asarray(jnp.inf, dtype=z.dtype),
        jnp.asarray(prep_passes + 2), jnp.asarray(0),
    )


def _lbfgs_iteration(passes: _RowPasses, carry: _LbfgsCarry) -> _LbfgsCarry:
    """One L-BFGS iteration, the ONE body of :func:`fit_logistic` and
    :func:`_lbfgs_segment`: two passes over X whatever the data. The line
    search sees the objective along the direction through the cached
    margins alone; where it finds no decrease (float32 at its floor) the
    step is 0 and the iteration ends all the same, its gradient pass made."""
    (w, b), z = carry.params, carry.margins
    step, memory = _LBFGS_MEMORY.update(carry.grad, carry.memory, carry.params)
    dw, db = jax.tree_util.tree_map(jnp.negative, step)
    u = passes.margins(dw, db)

    def phi(a):
        return passes.value_at(z + a * u, w + a * dw)

    zero = jnp.zeros((), z.dtype)
    phi0, slope0 = jax.value_and_grad(phi)(zero)
    a, search = _ZOOM.update(
        jnp.ones((), z.dtype), _ZOOM.init(zero), zero,
        value=phi0, grad=slope0, value_fn=phi,
    )
    params = (w + a * dw, b + a * db)
    z = z + a * u
    grad = passes.grad_at(z, params)
    return _LbfgsCarry(
        params, memory, z, grad, carry.it + 1, optax.global_norm(grad),
        carry.x_passes + 2, carry.trials + search.info.num_linesearch_steps,
    )


def _lbfgs_finish(passes, reg_param, carry, offset, scale, c, fit_intercept, dot):
    """The post-solve tail: final objective from the margins, the
    identifiability pivot, the back-map to original feature space."""
    w, b = carry.params
    final_loss = passes.value_at(carry.margins, w)
    # Identifiability pivot for unregularized softmax (Spark's centering).
    if c > 1:
        do_center = reg_param == 0.0
        w = jnp.where(do_center, w - jnp.mean(w, axis=1, keepdims=True), w)
        b = jnp.where(do_center, b - jnp.mean(b), b)
    # Map standardized-space solution back to original feature space.
    w_orig = w / scale[:, None]
    b_orig = b - dot(offset, w_orig) if fit_intercept else b
    return LogisticFit(
        w_orig, b_orig, carry.it, final_loss, carry.x_passes, carry.trials,
        carry.grad,
    )


@partial(
    jax.jit,
    static_argnames=(
        "n_classes",
        "fit_intercept",
        "standardization",
        "max_iter",
        "precision",
        "multinomial",
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

    ``max_iter`` iterations run unless the gradient's norm at the current
    point is at or under ``tol``; each makes the same passes over ``x``
    (:func:`_lbfgs_iteration`), so ``x_passes`` of the result follows from
    ``n_iter`` alone.
    """
    if n_classes < 2:
        raise ValueError(f"need at least 2 classes, got {n_classes}")
    c = n_classes if (multinomial or n_classes > 2) else 1
    dtype = x.dtype
    dot = make_dot(precision)
    offset, scale, n, prep_passes = _standardizer(
        x, mask, fit_intercept, standardization
    )
    passes = _make_logistic_loss(
        x, _class_targets(y, c, dtype), mask, offset, scale, n, reg_param, c,
        fit_intercept, dot,
    )
    params0 = _start_params(
        init_w, init_b, offset, scale, x.shape[1], c, fit_intercept, dot, dtype
    )
    carry = jax.lax.while_loop(
        lambda carry: jnp.logical_and(carry.it < max_iter, carry.gnorm > tol),
        partial(_lbfgs_iteration, passes),
        _lbfgs_start(passes, params0, prep_passes),
    )
    return _lbfgs_finish(
        passes, reg_param, carry, offset, scale, c, fit_intercept, dot
    )


@partial(
    jax.jit,
    static_argnames=("c", "fit_intercept", "standardization", "precision"),
)
def _lbfgs_prepare(
    x, y, mask, reg_param, init_w, init_b,
    c: int, fit_intercept: bool, standardization: bool, precision: str,
):
    """The head of :func:`fit_logistic` as its own program for the
    segmented driver: (offset, scale, n, class targets, first carry)."""
    dot = make_dot(precision)
    offset, scale, n, prep_passes = _standardizer(
        x, mask, fit_intercept, standardization
    )
    y_target = _class_targets(y, c, x.dtype)
    passes = _make_logistic_loss(
        x, y_target, mask, offset, scale, n, reg_param, c, fit_intercept, dot
    )
    params0 = _start_params(
        init_w, init_b, offset, scale, x.shape[1], c, fit_intercept, dot, x.dtype
    )
    return offset, scale, n, y_target, _lbfgs_start(passes, params0, prep_passes)


@partial(
    jax.jit,
    static_argnames=("c", "fit_intercept", "max_iter", "every", "precision"),
)
def _lbfgs_segment(
    x, y_target, mask, offset, scale, n, reg_param, tol, carry,
    c: int, fit_intercept: bool, max_iter: int, every: int, precision: str,
):
    """Up to ``every`` L-BFGS iterations from an explicit carry — exactly
    :func:`fit_logistic`'s loop body and stopping rule plus a segment
    budget, with the full :class:`_LbfgsCarry` visible as a pytree between
    segments."""
    passes = _make_logistic_loss(
        x, y_target, mask, offset, scale, n, reg_param, c, fit_intercept,
        make_dot(precision),
    )

    def cond(state):
        carry, seg = state
        return jnp.logical_and(
            jnp.logical_and(carry.it < max_iter, carry.gnorm > tol), seg < every
        )

    def body(state):
        carry, seg = state
        return _lbfgs_iteration(passes, carry), seg + 1

    return jax.lax.while_loop(cond, body, (carry, 0))[0]


@partial(jax.jit, static_argnames=("c", "fit_intercept", "precision"))
def _logistic_finalize(
    x, y_target, mask, offset, scale, n, reg_param, carry,
    c: int, fit_intercept: bool, precision: str,
):
    """:func:`fit_logistic`'s post-solve tail (:func:`_lbfgs_finish`) as
    its own program for the segmented driver."""
    dot = make_dot(precision)
    passes = _make_logistic_loss(
        x, y_target, mask, offset, scale, n, reg_param, c, fit_intercept, dot
    )
    return _lbfgs_finish(
        passes, reg_param, carry, offset, scale, c, fit_intercept, dot
    )


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
) -> LogisticFit:
    """Preemption-tolerant :func:`fit_logistic` (the L-BFGS / L2 path):
    a host outer loop over jitted L-BFGS segments, the :class:`_LbfgsCarry`
    pytree snapshotted asynchronously between segments, the fit resumed
    mid-solve from the latest valid checkpoint. Same returns, bit-identical
    solution."""
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
    shared = dict(c=c, fit_intercept=fit_intercept, precision=precision)
    offset, scale, n, y_target, carry = _lbfgs_prepare(
        x, y, mask, reg_param, init_w, init_b,
        standardization=standardization, **shared,
    )
    restored = checkpointer.restore_latest(template=carry)
    if restored is not None:
        _, carry = restored
        if mesh is not None:
            carry = replicate_state_onto_mesh(carry, mesh)

    while True:
        it, gn = int(carry.it), float(carry.gnorm)
        if not (it < max_iter and gn > tol):
            break
        seg_t0 = time.perf_counter()
        with TraceRange("segment logistic.lbfgs", TraceColor.PURPLE):
            fault_point("solver.segment")
            carry = ledgered_call(
                _lbfgs_segment,
                (x, y_target, mask, offset, scale, n, reg_param, tol, carry),
                static=dict(max_iter=max_iter, every=checkpointer.every, **shared),
                name="logistic.lbfgs.segment",
            )
            bump_counter("checkpoint.segments")
            bump_counter("checkpoint.solver_iters", int(carry.it) - it)
        observe_segment_seconds("logistic.lbfgs", time.perf_counter() - seg_t0)
        checkpointer.save_async(int(carry.it), carry)
        segment_boundary(checkpointer)

    result = _logistic_finalize(
        x, y_target, mask, offset, scale, n, reg_param, carry, **shared
    )
    checkpointer.finalize_success()
    return result


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
    offset, scale, n, _ = _standardizer(x, mask, fit_intercept, standardization)
    y_target = _class_targets(y, c, dtype)

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
    # objective at reg_param=reg2 — so the blocked one-sweep builder serves
    # both solvers from the same algebra.
    if fused:
        sweep = _make_logistic_loss(
            x, y_target, mask, offset, scale, n, reg2, c, fit_intercept, dot
        ).value_and_grad

        def smooth_loss(params):
            return sweep(params)[0]

        def grad_fn(params):
            return sweep(params)[1]

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

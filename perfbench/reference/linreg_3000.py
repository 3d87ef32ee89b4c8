"""The plain reference for ``linreg_3000``, its controls and faults.

Straight ``jax.numpy`` (block products at ``"highest"``) and numpy; imports
nothing of the program. The configuration's objective, as Spark states it
(``standardization`` true, its default: the penalty on the coefficients of the
STANDARDISED columns, written in the original space; the intercept free):

    f(b, b0) = 1/(2n) sum_i (y_i - x_i . b - b0)^2
               + regParam * (alpha * sum_j s_j |b_j| + (1 - alpha)/2 * sum_j s_j^2 b_j^2),

``s_j`` column j's standard deviation (n - 1 in its denominator), and at the
minimum over ``b0``: ``b0 = mean(y) - mean(x) . b``.

Two independent readings of the rows:

``moments``  the centred second moments of ``[X | y]``: float32 ``"highest"``
    block products of 10,000 rows, each added up in float64 on the host (as
    ``reference/pca_3000.py`` does). From them, in float64 on the host: the
    problem's optimum (FISTA to convergence) and the iterate of ``maxIter``
    plain FISTA iterations from zero with the EXACT largest eigenvalue as the
    step's constant.
``evaluate``  one pass over the rows at a given ``(b, b0)``: the residuals
    themselves, their squares' sum and their products with the centred rows,
    a block's float32 sums added up in float64 on the host. No moment enters,
    so nothing cancels: the objective and its smooth part's gradient there.

What a fit returned is judged by four numbers:

``objective_rel``  the objective the fit reports (``finalObjective``, which the
    program takes from its moments: terms a hundred thousand times larger
    cancel there) against ``evaluate``'s objective of the returned ``(b, b0)``,
    as a share.
``objective_gap``  ``evaluate``'s objective at the returned ``(b, b0)`` less its
    objective at the reference's own ``maxIter`` iterations, as a share of the
    latter; signed. A fit that stopped early, or whose steps were shorter,
    reads higher.
``coef_rel``  the distance of the returned coefficients from the float64
    optimum, over the optimum's norm: a fit of other rows, a lost coefficient.
``gradient_rel``  the gradient the fit reports at the returned coefficients
    (``finalGradient``: ``(A b - B)/n`` plus the L2 term, from ITS moments)
    against ``evaluate``'s there, the norm of the difference over the gradient's
    norm at zero. Near the optimum a gradient is what terms ten thousand times
    larger leave when they cancel, so it reads the arithmetic of the moments:
    three bf16 passes for six, or one float32 contraction over all the rows,
    do not average out of it.
"""

from __future__ import annotations

import hashlib
from functools import lru_cache

import numpy as np

# A float32 sum of squares over very many rows loses to rounding on this
# chip's matrix unit (PERF.md section 6, PR 24): the reference never sums more
# than this many rows in one product.
BLOCK_ROWS = 10_000
NUMBERS = ("objective_rel", "objective_gap", "coef_rel", "gradient_rel")


def _step_of(n: int) -> int:
    return next(s for s in range(min(n, BLOCK_ROWS), 0, -1) if n % s == 0)


@lru_cache(maxsize=None)
def _block_calls(step: int, precision: str):
    """The jitted per-block calls, blocks sliced in place (no copy of the rows)."""
    import jax
    import jax.numpy as jnp

    def take(a, lo):
        return jax.lax.dynamic_slice_in_dim(a, lo, step, axis=0)

    @jax.jit
    def sums(x, y, lo):
        return jnp.sum(take(x, lo), axis=0), jnp.sum(take(y, lo))

    @jax.jit
    def products(x, y, lo, x_mean, y_mean):
        c, cy = take(x, lo) - x_mean, take(y, lo) - y_mean
        return (jnp.matmul(c.T, c, precision=precision),
                jnp.matmul(cy, c, precision="highest"), jnp.sum(cy * cy))

    @jax.jit
    def residuals(x, y, lo, x_mean, b, b0):
        xb = take(x, lo)
        r = take(y, lo) - jnp.matmul(xb, b, precision="highest") - b0
        return jnp.sum(r * r), jnp.matmul(r, xb - x_mean, precision="highest")

    return sums, products, residuals


def moments(x, y, precision: str = "highest") -> dict:
    """n, the means and the centred second moments of ``[X | y]`` in float64:
    ``a = Xc^T Xc`` (d, d), ``b = Xc^T yc`` (d,), ``yy = yc^T yc``."""
    n = x.shape[0]
    step = _step_of(n)
    sums, products, _ = _block_calls(step, precision)
    x_sum, y_sum = 0.0, 0.0
    for lo in range(0, n, step):
        sx, sy = sums(x, y, lo)
        x_sum, y_sum = x_sum + np.asarray(sx, np.float64), y_sum + float(sy)
    x_mean, y_mean = x_sum / n, y_sum / n
    x_mean32, y_mean32 = x_mean.astype(np.float32), np.float32(y_mean)
    a, b, yy, pending = 0.0, 0.0, 0.0, []

    def add(parts):
        nonlocal a, b, yy
        a = a + np.asarray(parts[0], np.float64)
        b = b + np.asarray(parts[1], np.float64)
        yy = yy + float(parts[2])

    for lo in range(0, n, step):
        pending.append(products(x, y, lo, x_mean32, y_mean32))
        if len(pending) == 4:  # keep a few in flight, none for long
            add(pending.pop(0))
    for parts in pending:
        add(parts)
    # the blocks were centred on the float32 rounding of the means: put it right
    dx, dy = x_mean - x_mean32.astype(np.float64), y_mean - float(y_mean32)
    return {"n": n, "x_mean": x_mean, "y_mean": y_mean, "a": a - n * np.outer(dx, dx),
            "b": b - n * dx * dy, "yy": yy - n * dy * dy}


def one_shot_moments(x, y) -> dict:
    """The same moments as ONE float32 ``"highest"`` contraction over all the
    rows: how the program summed them until PR 36 (control ``one_shot_sum``)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def whole(x, y):
        x_mean, y_mean = jnp.mean(x, axis=0), jnp.mean(y)
        c, cy = x - x_mean, y - y_mean
        return (x_mean, y_mean, jnp.matmul(c.T, c, precision="highest"),
                jnp.matmul(cy, c, precision="highest"), jnp.sum(cy * cy))

    x_mean, y_mean, a, b, yy = (np.asarray(v, np.float64) for v in whole(x, y))
    return {"n": x.shape[0], "x_mean": x_mean, "y_mean": float(y_mean), "a": a, "b": b,
            "yy": float(yy)}


def problem(m: dict, config: dict) -> dict:
    """The proximal problem of the moments, float64: minimise ``1/2 c^T q c -
    lin^T c + sum_j l1_j |c_j|`` (+ ``yy / 2n``)."""
    if not (config["standardization"] and config["fit_intercept"]):
        raise ValueError("the reference states the objective with standardization and intercept")
    n, reg, alpha = m["n"], float(config["reg_param"]), float(config["elastic_net_param"])
    var = np.maximum(np.diag(m["a"]) / (n - 1), 0.0)
    q = m["a"] / n + np.diag(reg * (1.0 - alpha) * var)
    return {"q": q, "lin": m["b"] / n, "l1": reg * alpha * np.sqrt(var),
            "l2": reg * (1.0 - alpha) * var, "half_yy": m["yy"] / (2.0 * n),
            "lip": float(np.linalg.eigvalsh(q)[-1])}


def fista(p: dict, iters: int, tol: float = 0.0) -> np.ndarray:
    """Plain FISTA from zero with the exact largest eigenvalue: ``iters``
    iterations, or fewer once no coefficient moves by ``tol`` of the widest."""
    lip = p["lip"]
    c = z = np.zeros_like(p["lin"])
    t = 1.0
    for _ in range(iters):
        v = z - (p["q"] @ z - p["lin"]) / lip
        c_new = np.sign(v) * np.maximum(np.abs(v) - p["l1"] / lip, 0.0)
        t_new = (1.0 + np.sqrt(1.0 + 4.0 * t * t)) / 2.0
        z = c_new + ((t - 1.0) / t_new) * (c_new - c)
        moved = float(np.max(np.abs(c_new - c)))
        c, t = c_new, t_new
        if moved <= tol * max(float(np.max(np.abs(c))), 1.0):
            break
    return c


def solved_from(m: dict, config: dict) -> dict:
    """What a fit that took its moments from ``m`` would hand back: ``maxIter``
    FISTA iterations, the objective and the gradient there from ``m`` alone."""
    p = problem(m, config)
    c = fista(p, int(config["max_iter"]))
    grad = p["q"] @ c - p["lin"]
    return {"coefficients": c, "intercept": np.float64(m["y_mean"] - m["x_mean"] @ c),
            "n_iter": np.int64(config["max_iter"]), "gradient": grad,
            "objective": np.float64(p["half_yy"] + 0.5 * c @ (grad - p["lin"])
                                    + np.sum(p["l1"] * np.abs(c)))}


def evaluate(ref: dict, b: np.ndarray, b0: float) -> tuple:
    """(objective, gradient of its smooth part with respect to ``b`` (d,)) at
    ``(b, b0)``: one read of the rows, the residuals themselves."""
    import jax.numpy as jnp

    x, y, p = ref["x"], ref["y"], ref["problem"]
    n = x.shape[0]
    step = _step_of(n)
    residuals = _block_calls(step, "highest")[2]
    x_mean32 = jnp.asarray(ref["moments"]["x_mean"], jnp.float32)
    b32, b032 = jnp.asarray(b, jnp.float32), jnp.float32(b0)
    sse, xr = 0.0, 0.0
    for lo in range(0, n, step):
        s, v = residuals(x, y, lo, x_mean32, b32, b032)
        sse, xr = sse + float(s), xr + np.asarray(v, np.float64)
    objective = sse / (2.0 * n) + float(np.sum(p["l1"] * np.abs(b)) + 0.5 * np.sum(p["l2"] * b * b))
    return objective, -xr / n + p["l2"] * b


def reference(pair, config: dict) -> dict:
    """The reference's own moments and fits of the rows; what a fit returned is
    assessed in ``compare`` (answers that are the same to the byte once)."""
    import jax.numpy as jnp

    x, y = (jnp.asarray(a) for a in pair)
    m = moments(x, y)
    p = problem(m, config)
    ref = {"x": x, "y": y, "config": config, "moments": m, "problem": p, "seen": {},
           "optimum": fista(p, 20_000, tol=1e-14),
           "grad0_norm": float(np.linalg.norm(p["lin"]))}
    plain = fista(p, int(config["max_iter"]))
    ref["objective"] = evaluate(ref, plain, m["y_mean"] - m["x_mean"] @ plain)[0]
    return ref


def compare(result: dict, ref: dict) -> dict:
    config = ref["config"]
    b = np.asarray(result["coefficients"], dtype=np.float64)
    b0 = float(np.asarray(result["intercept"]))
    grad = np.asarray(result["gradient"], dtype=np.float64).ravel()
    bad = dict.fromkeys(NUMBERS, float("inf"))
    if b.shape != ref["optimum"].shape or grad.shape != b.shape:
        return bad
    if not (np.all(np.isfinite(b)) and np.isfinite(b0) and np.all(np.isfinite(grad))):
        return bad
    try:  # a fit off the proximal path reports neither
        n_iter, reported = int(np.asarray(result["n_iter"])), float(np.asarray(result["objective"]))
    except TypeError:
        return bad
    if not 1 <= n_iter <= int(config["max_iter"]):
        return bad
    key = hashlib.sha1(b.tobytes() + grad.tobytes() + np.float64([b0, reported]).tobytes()).hexdigest()
    if key not in ref["seen"]:
        f, at_point = evaluate(ref, b, b0)
        ref["seen"][key] = {
            "objective_rel": abs(reported - f) / f,
            "objective_gap": (f - ref["objective"]) / ref["objective"],
            "coef_rel": float(np.linalg.norm(b - ref["optimum"]) / np.linalg.norm(ref["optimum"])),
            "gradient_rel": float(np.linalg.norm(grad - at_point)) / ref["grad0_norm"],
        }
    return dict(ref["seen"][key], n_iter=n_iter)


def _fit(ctx, pair, **setters):
    from perfbench.drivers import fit_loop

    est = fit_loop.build_estimator(ctx.config)
    for name, value in setters.items():
        getattr(est, "set" + name)(value)
    return fit_loop.read_model(est.fit(pair), ctx.config)


def controls() -> dict:
    """name -> ``control(ctx, pair)``: a fit with one stated guarantee broken,
    put in the sound fit's place. Those the configuration lists have to come out
    NOT correct (``perfbench/tests/test_linreg_3000.py``; on the chip,
    ``perfbench.control``)."""
    import jax.numpy as jnp

    def setting(**setters):
        return lambda ctx, pair: _fit(ctx, pair, **setters)

    def one_shot_sum(ctx, pair):
        # "no more than a block of rows in one float32 contraction": the
        # parent's sum. Exact on a CPU (``controls_chip_only``)
        return solved_from(one_shot_moments(*(jnp.asarray(a) for a in pair)), ctx.config)

    return {
        # "float32 at 'highest'": the program's own path one step below it (a
        # CPU takes no notice of "high": ``controls_chip_only``)
        "three_pass": setting(Precision="high"),
        # "exactly maxIter iterations": three of the ten; and nine of them,
        # read and not held to fail (the configuration's ``controls_not_seen``)
        "early_stop": setting(MaxIter=3),
        "one_short": setting(MaxIter=9),
        "one_shot_sum": one_shot_sum,
    }


def faults() -> dict:
    """Planted faults of the timed path, name -> ``fault(ctx, pair)`` that
    returns what a broken fit would hand the comparison."""
    def half_rows(ctx, pair):
        # half of the rows left out, the fit is of the rest
        x, y = pair
        return _fit(ctx, (x[: x.shape[0] // 2], y[: x.shape[0] // 2]))

    def stale_model(ctx, pair):
        # the state left unchanged: the model of other rows handed back
        from perfbench import data

        gen = ctx.config["data"]
        return _fit(ctx, data.generate(gen["generator"], ctx.args.seed + 1,
                                       pair[0].shape[0] // 8, ctx.cols, gen["params"]))

    def altered_coefficient(ctx, pair):
        # an answer altered where it is produced: the write of one coefficient
        # lost, the largest (the columns have one spread)
        out = _fit(ctx, pair)
        b = out["coefficients"].copy()
        b[int(np.argmax(np.abs(b)))] = 0.0
        out["coefficients"] = b
        return out

    return {"half_rows": half_rows, "stale_model": stale_model,
            "altered_coefficient": altered_coefficient}

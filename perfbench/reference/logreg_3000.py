"""The plain reference for ``logreg_3000``, its controls and faults.

Straight ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
numpy and scipy; imports nothing of the program. The configuration's objective
(``standardization`` false, as upstream's script passes it: the columns as they
are, the penalty on the coefficients as returned, the intercept free) is

    f(w, b) = (1/n) sum_i [softplus(z_i) - y_i z_i] + regParam/2 * sum_j w_j^2,
    z_i = sum_j x_ij w_j + b.

Every evaluation reads all the rows: the objective and its gradient in blocks
of rows, a block's partial sums float32 on the device, added up in float64 on
the host. The reference's own fit is the straightforward one: scipy's L-BFGS-B
(ten pairs, its own line search), ``maxIter`` iterations from zero in float64,
every trial step of its line search a full evaluation.

Two hundred float32 L-BFGS iterations are not reproducible entry by entry (a
line search that takes one more step moves every later iterate), so the
returned coefficients are judged by what they are:

``objective_gap``  the reference's objective AT the returned ``(w, b)`` less
    the objective the reference's own fit reached in as many iterations as the
    configuration states, as a share of the latter. Nought or less where the
    program got as far as the plain fit; an early stop, a fit on other rows, a
    line search fed a lower precision's margins read higher.
``optimality_rel``  the reference's gradient norm at the returned ``(w, b)``
    over its gradient norm at zero: how far the iterations got.
``objective_rel``  the objective the fit reports (``finalObjective``, which
    the program reads off the margins it carried through its iterations) is
    the objective of the coefficients it returns over ALL the rows: the gap to
    the reference's objective there, as a share (the twin of KMeans's
    ``cost_rel``). A fit on other rows reports another objective; margins
    carried at a lower precision drift from their coefficients, and the report
    with them (by a signed share that can fall near nought).
``gradient_rel``  the gradient the fit's last iteration computed at the
    returned ``(w, b)`` (``finalGradient``: the optimiser's own, from the
    margins it carried) against the reference's gradient there: the norm of
    the difference over the reference's gradient norm at zero. After 200
    iterations a gradient is what a thousand times larger terms leave when
    they cancel, so it reads the arithmetic of the passes: products rounded to
    a lower precision do not average out of it as they do out of an objective.
"""

from __future__ import annotations

import hashlib
from functools import lru_cache

import numpy as np

BLOCK_ROWS = 25_000
NUMBERS = ("objective_gap", "optimality_rel", "objective_rel", "gradient_rel")


@lru_cache(maxsize=None)
def _sweep(step: int):
    """The jitted pass over the rows in blocks of ``step``: per block, float32
    (loss sum, residuals times rows (d,), residuals' sum)."""
    import jax
    import jax.numpy as jnp

    def sweep(x, y, w, b):
        def body(i, carry):
            loss, gw, gb = carry
            # sliced in place: a reshape to (blocks, step, d) copies all the rows
            xb = jax.lax.dynamic_slice_in_dim(x, i * step, step, axis=0)
            yb = jax.lax.dynamic_slice_in_dim(y, i * step, step, axis=0).astype(jnp.float32)
            z = jnp.matmul(xb, w) + b
            dz = jax.nn.sigmoid(z) - yb
            return (loss.at[i].set(jnp.sum(jax.nn.softplus(z) - yb * z)),
                    gw.at[i].set(jnp.matmul(dz, xb)), gb.at[i].set(jnp.sum(dz)))

        nb = x.shape[0] // step
        vec = jnp.zeros((nb,), jnp.float32)
        return jax.lax.fori_loop(0, nb, body, (vec, jnp.zeros((nb, x.shape[1]), jnp.float32), vec))

    return jax.jit(sweep)


def evaluate(x, y, w, b, reg_param: float) -> tuple:
    """(f, df/dw (d,), df/db) at float64 ``w`` (d,), ``b``: one read of the rows."""
    import jax
    import jax.numpy as jnp

    n = x.shape[0]
    step = next(s for s in range(min(n, BLOCK_ROWS), 0, -1) if n % s == 0)
    with jax.default_matmul_precision("highest"):
        sums = _sweep(step)(x, y, jnp.asarray(w, jnp.float32), jnp.asarray(b, jnp.float32))
    loss, gw, gb = (np.asarray(s, dtype=np.float64).sum(axis=0) for s in sums)
    return loss / n + 0.5 * reg_param * float(w @ w), gw / n + reg_param * w, gb / n


def plain_fit(x, y, reg_param: float, max_iter: int) -> dict:
    """``max_iter`` iterations of scipy's L-BFGS-B from zero: the objective it
    reached, and the gradient's norm where it started."""
    from scipy.optimize import minimize

    norms = []

    def fun(theta):
        f, gw, gb = evaluate(x, y, theta[:-1], theta[-1], reg_param)
        grad = np.append(gw, gb)
        norms.append(float(np.linalg.norm(grad)))
        return f, grad

    # ftol 0 and gtol 0: it stops at max_iter, or where float32 sums let its
    # line search find no lower point
    res = minimize(fun, np.zeros(x.shape[1] + 1), jac=True, method="L-BFGS-B",
                   options={"maxiter": max_iter, "maxfun": 20 * max_iter, "maxcor": 10,
                            "ftol": 0.0, "gtol": 0.0})
    return {"objective": float(res.fun), "grad0_norm": norms[0], "n_iter": int(res.nit),
            "n_evals": len(norms)}


def reference(pair, config: dict) -> dict:
    """The reference's own fit of the rows; what a fit returned is assessed in
    ``compare`` (answers that are the same to the byte once)."""
    import jax.numpy as jnp

    if config["standardization"]:
        raise ValueError("the reference states the objective of the columns as they are")
    x, y = (jnp.asarray(a) for a in pair)
    ref = plain_fit(x, y, float(config["reg_param"]), int(config["max_iter"]))
    ref.update(x=x, y=y, config=config, seen={})
    return ref


def compare(result: dict, ref: dict) -> dict:
    config = ref["config"]
    w = np.asarray(result["coefficients"], dtype=np.float64)
    b = float(np.asarray(result["intercept"]))
    n_iter, reported = int(np.asarray(result["n_iter"])), float(np.asarray(result["objective"]))
    grad = np.asarray(result["gradient"], dtype=np.float64).ravel()  # (d + 1, 1): w's rows, then b's
    bad = dict.fromkeys(NUMBERS, float("inf"))
    if w.shape != (ref["x"].shape[1],) or not (np.all(np.isfinite(w)) and np.isfinite(b)):
        return bad
    if grad.shape != (w.shape[0] + 1,):
        return bad
    if not 1 <= n_iter <= int(config["max_iter"]):
        return bad
    key = hashlib.sha1(w.tobytes() + grad.tobytes() + np.float64([b, reported]).tobytes()).hexdigest()
    if key not in ref["seen"]:
        f, gw, gb = evaluate(ref["x"], ref["y"], w, b, float(config["reg_param"]))
        at_point = np.append(gw, gb)
        ref["seen"][key] = {
            "objective_gap": (f - ref["objective"]) / ref["objective"],
            "optimality_rel": float(np.linalg.norm(at_point)) / ref["grad0_norm"],
            "objective_rel": abs(reported - f) / f,
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
    """name -> ``control(ctx, pair)``: the program with one stated guarantee
    broken, put in the sound fit's place. Those the configuration lists have to
    come out NOT correct (``perfbench/tests/test_logreg_3000.py``; on the chip,
    ``perfbench.control``)."""
    def setting(**setters):
        return lambda ctx, pair: _fit(ctx, pair, **setters)

    return {
        # "200 L-BFGS iterations": stop at half of them
        "early_stop": setting(MaxIter=100),
        # "float32 at 'highest'": the program's own paths below it. The
        # hand-cast bfloat16 path reaches the same objective and gradient norm
        # (its roundings average out over the rows): ``gradient_rel`` holds it.
        # "high" is read and NOT held to fail (the configuration's
        # ``controls_not_seen``): the passes are matrix-vector products, which
        # the chip's compiler makes float32 sums on the vector unit whatever
        # "high" asks (a CPU takes no notice of it either): the program's
        # answer bit for bit, so bfloat16 is the nearest precision below
        "one_pass": setting(Precision="bf16"),
        "three_pass": setting(Precision="high"),
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
        # lost, the one that weighs most (its size times its column's spread)
        out = _fit(ctx, pair)
        w = out["coefficients"].copy()
        w[int(np.argmax(np.abs(w) * np.asarray(pair[0][:10_000].std(axis=0))))] = 0.0
        out["coefficients"] = w
        return out

    return {"half_rows": half_rows, "stale_model": stale_model,
            "altered_coefficient": altered_coefficient}

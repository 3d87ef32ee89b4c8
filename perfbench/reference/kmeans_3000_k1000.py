"""The plain reference for ``kmeans_3000_k1000``, its controls and faults.

Straight ``jax.numpy`` and numpy; imports nothing of the program. Lloyd's
trajectory from a random start is chaotic (a near-tie that flips moves two
centres, and every later assignment with them), so nothing here follows it.
What a sound fit returns can be checked from the rows and the returned
centres alone:

``cost_rel``  the cost the model reports is the cost of the centres it
    returns over ALL the rows: the gap to the reference's cost, as a share.
``descent_rel``  how far the returned centres are from a fixed point of
    Lloyd's step: one reference step from them (every centre to the mean of
    the rows nearest to it) lowers the cost by
    ``sum_j n_j |mean_j - centre_j|^2``; its share of the cost. Nought where no
    centre would move; 30 iterations from a random start leave a few rows in
    motion, a few iterations leave thousands, and distances worked out at a
    lower precision put rows near a boundary on its wrong side.

The reference assigns in blocks of rows: the two nearest centres by the Gram
expansion at ``"highest"``, then the nearer of the two by the plain
difference, so a near-tie is decided in float32 of the distance itself and
not of the 1e5-sized terms of the expansion.
"""

from __future__ import annotations

import hashlib
from functools import partial

import numpy as np

BLOCK_ROWS = 20_000


def pieces_of(x) -> list:
    if isinstance(x, np.ndarray):
        return [x]
    if isinstance(x, (list, tuple)):
        return list(x)
    return [s.data for s in x.addressable_shards]


def assess(x, centers: np.ndarray) -> dict:
    """(cost, counts (k,), shift (k, d)): the cost of ``centers`` over all rows
    of ``x``, the rows nearest to each centre, and how far the mean of those
    rows lies from the centre."""
    import jax
    import jax.numpy as jnp

    k, d = centers.shape

    @partial(jax.jit, static_argnames="step")
    def blocks(s, c, step):
        c2 = jnp.sum(c * c, axis=1)

        def body(i, carry):
            resid, counts, costs = carry
            # sliced in place: a reshape to (blocks, step, d) copies all the rows
            xb = jax.lax.dynamic_slice_in_dim(s, i * step, step, axis=0)
            score = c2[None, :] - 2.0 * jnp.matmul(xb, c.T, precision="highest")
            _, two = jax.lax.top_k(-score, 2)
            diff = xb[:, None, :] - c[two]                     # (step, 2, d)
            dist = jnp.sum(diff * diff, axis=2)                # (step, 2)
            pick = jnp.argmin(dist, axis=1)
            label = jnp.take_along_axis(two, pick[:, None], axis=1)[:, 0]
            near = jnp.take_along_axis(diff, pick[:, None, None], axis=1)[:, 0, :]
            one_hot = jax.nn.one_hot(label, k, dtype=xb.dtype)
            resid = resid + jnp.matmul(one_hot.T, near, precision="highest")
            counts = counts + jnp.sum(one_hot, axis=0)
            return resid, counts, costs.at[i].set(jnp.sum(jnp.min(dist, axis=1)))

        nb = s.shape[0] // step
        init = (jnp.zeros((k, d), s.dtype), jnp.zeros((k,), s.dtype), jnp.zeros((nb,), s.dtype))
        return jax.lax.fori_loop(0, nb, body, init)

    c32 = jnp.asarray(centers, dtype=jnp.float32)
    cost, counts, resid = 0.0, 0.0, 0.0
    for piece in pieces_of(x):
        rows = piece.shape[0]
        step = next(b for b in range(min(rows, BLOCK_ROWS), 0, -1) if rows % b == 0)
        r, n, costs = blocks(jnp.asarray(piece), c32, step=step)
        cost += float(np.sum(np.asarray(costs, dtype=np.float64)))
        counts = counts + np.asarray(n, dtype=np.float64)
        resid = resid + np.asarray(r, dtype=np.float64)
    shift = resid / np.maximum(counts, 1.0)[:, None]
    return {"cost": cost, "counts": counts, "shift": shift}


def reference(x, config: dict) -> dict:
    """The comparison needs the returned centres, so the work is in
    ``compare``; answers that are the same to the byte are assessed once."""
    return {"x": x, "config": config, "seen": {}}


def compare(result: dict, ref: dict) -> dict:
    config = ref["config"]
    k, max_iter = int(config["k"]), int(config["max_iter"])
    centers = np.asarray(result["centers"], dtype=np.float64)
    cost = float(np.asarray(result["cost"]))
    n_iter = int(np.asarray(result["n_iter"]))
    d = pieces_of(ref["x"])[0].shape[1]
    bad = {"cost_rel": float("inf"), "descent_rel": float("inf")}
    if centers.shape != (k, d) or not np.all(np.isfinite(centers)):
        return bad
    if not 1 <= n_iter <= max_iter:
        return bad
    key = hashlib.sha1(centers.tobytes() + np.float64(cost).tobytes()
                       + np.int64(n_iter).tobytes()).hexdigest()
    if key not in ref["seen"]:
        a = assess(ref["x"], centers)
        descent = float(np.sum(a["counts"] * np.sum(a["shift"] ** 2, axis=1)) / a["cost"])
        ref["seen"][key] = {
            "cost_rel": abs(cost - a["cost"]) / a["cost"],
            "descent_rel": descent, "n_iter": n_iter,
            "empty": int(np.sum(a["counts"] == 0)),
        }
    return dict(ref["seen"][key])


def _fit(ctx, rows, **setters):
    from perfbench.drivers import fit_loop

    est = fit_loop.build_estimator(ctx.config)
    for name, value in setters.items():
        getattr(est, "set" + name)(value)
    return fit_loop.read_model(est.fit(rows), ctx.config)


def controls() -> dict:
    """name -> ``control(ctx, x)``: the program with one stated
    guarantee broken, put in the sound fit's place. Each has to come out NOT
    correct (``perfbench/tests/test_control.py``; on the chip,
    ``perfbench.control``)."""
    def setting(**setters):
        return lambda ctx, x: _fit(ctx, x, **setters)

    return {
        # "Lloyd iterations until no centre moves or maxIter = 30": stop at 10
        "early_stop": setting(MaxIter=10),
        # "float32 at the default policy ('highest')": the program's own
        # three-pass path, the step below (a CPU takes no notice of "high":
        # only the chip shows it), and its one-pass path
        "three_pass": setting(Precision="high"),
        "one_pass": setting(Precision="bf16"),
    }


def faults() -> dict:
    """Planted faults of the timed path, name -> ``fault(ctx, x)`` that
    returns what a broken fit would hand the comparison."""
    def half_rows(ctx, x):
        # half of the rows left out, centres and cost taken over the rest
        return _fit(ctx, x[: x.shape[0] // 2])

    def stale_model(ctx, x):
        # the state left unchanged: the model of other rows handed back
        from perfbench import data

        gen = ctx.config["data"]
        other = data.generate(gen["generator"], ctx.args.seed + 1, x.shape[0] // 8,
                              ctx.cols, gen["params"])
        return _fit(ctx, other)

    def altered_center(ctx, x):
        # an answer altered where it is produced: one centre written over
        # another's row
        out = _fit(ctx, x)
        centers = out["centers"].copy()
        centers[7] = centers[11]
        out["centers"] = centers
        return out

    return {"half_rows": half_rows, "stale_model": stale_model,
            "altered_center": altered_center}

"""The plain reference for ``pca_3000``, and its lower-precision control.

Straight ``jax.numpy`` and numpy; imports nothing of the program. Column
means, then the centred Gram at ``"highest"`` in blocks of 10,000 rows, each
block's sum and Gram added up in float64 on the host; the eigen-decomposition
is LAPACK's, in float64, on the host. Rows may live on one device, on the
host (one matrix or a list of partitions): every piece is read where it lives.

``control`` is the same arithmetic with the Gram at ``"high"`` (three bf16
passes), the step below the configuration's float32 at ``"highest"``.
"""

from __future__ import annotations

from functools import partial

import numpy as np

# A float32 sum of squares over very many rows loses to rounding on this
# chip's matrix unit (PERF.md, Findings): a block of at most this many rows
# reads within 2e-7 of numpy float64, so the reference never sums more at once.
BLOCK_ROWS = 10_000


def pieces_of(x) -> list:
    """Single-device or host pieces of the rows, wherever they live: the
    shards of a device array, the partitions of a host list, one host matrix."""
    if isinstance(x, np.ndarray):
        return [x]
    if isinstance(x, (list, tuple)):
        return list(x)
    return [s.data for s in x.addressable_shards]


def moments(x, precision: str):
    """(n, mean (d,) float64, centred Gram (d, d) float64). Blocks of at most
    BLOCK_ROWS rows, sliced inside the jitted call (no copy of device rows; a
    host piece is placed block by block), each block's sum and Gram added up in
    float64 on the host."""
    import jax
    import jax.numpy as jnp

    def block(s, lo, step):
        return jax.lax.dynamic_slice_in_dim(s, lo, step, axis=0)

    @partial(jax.jit, static_argnames="step")
    def col_sum(s, lo, step):
        return jnp.sum(block(s, lo, step), axis=0)

    @partial(jax.jit, static_argnames="step")
    def gram(s, lo, mean, step):
        c = block(s, lo, step) - mean
        if precision == "highest" or jax.default_backend() != "cpu":
            return jnp.matmul(c.T, c, precision=precision)
        # a CPU takes no notice of "high": write the chip's three bf16 passes
        # out, hi*hi + hi*lo + lo*hi with float32 accumulation. (On the chip
        # itself the written-out form is no control: the compiler may keep
        # excess precision and drop the roundings to bfloat16.)
        hi = c.astype(jnp.bfloat16)
        lo_ = (c - hi.astype(jnp.float32)).astype(jnp.bfloat16)
        dot = partial(jnp.matmul, preferred_element_type=jnp.float32)
        return dot(hi.T, hi) + dot(hi.T, lo_) + dot(lo_.T, hi)

    def each_block():
        for piece in pieces_of(x):
            rows = piece.shape[0]
            step = next(b for b in range(min(rows, BLOCK_ROWS), 0, -1) if rows % b == 0)
            for lo in range(0, rows, step):
                if isinstance(piece, np.ndarray):  # host rows: place one block
                    yield jax.device_put(piece[lo : lo + step]), 0, step
                else:
                    yield piece, lo, step

    n = sum(p.shape[0] for p in pieces_of(x))
    total = 0.0
    for s, lo, step in each_block():
        total = total + np.asarray(col_sum(s, lo, step=step), dtype=np.float64)
    mean = total / n
    mean32 = mean.astype(np.float32)
    g, pending = 0.0, []
    for s, lo, step in each_block():
        pending.append(gram(s, lo, mean32, step=step))
        if len(pending) == 4:  # keep a few in flight, none for long
            g = g + np.asarray(pending.pop(0), dtype=np.float64)
    for p in pending:
        g = g + np.asarray(p, dtype=np.float64)
    # the Gram was centred on the float32 rounding of the mean: put it right
    delta = mean - mean32.astype(np.float64)
    return n, mean, g - n * np.outer(delta, delta)


def top_k(n: int, gram: np.ndarray, k: int) -> dict:
    from scipy.linalg import eigh

    cov = gram / (n - 1)
    d = cov.shape[0]
    w, v = eigh(cov, subset_by_index=[d - k, d - 1])
    w, v = w[::-1], v[:, ::-1]
    return {"explained_variance": np.maximum(w, 0) / np.trace(cov), "pc": v}


def reference(x, config: dict) -> dict:
    n, _, gram = moments(x, "highest")
    return top_k(n, gram, int(config["k"]))


def control(x, config: dict) -> dict:
    n, _, gram = moments(x, "high")
    return top_k(n, gram, int(config["k"]))


def controls() -> dict:
    """name -> ``control(ctx, x)``: what is put in the sound fit's place and
    has to come out NOT correct."""
    return {"three_pass": lambda ctx, x: control(x, ctx.config)}


def compare(result: dict, ref: dict) -> dict:
    """``ev_rel``: widest relative gap of an explained-variance ratio.
    ``pc_abs``: widest absolute gap of a sign-aligned component entry."""
    ev = np.asarray(result["explained_variance"], dtype=np.float64)
    pc = np.asarray(result["pc"], dtype=np.float64)
    if ev.shape != ref["explained_variance"].shape or pc.shape != ref["pc"].shape:
        return {"ev_rel": float("inf"), "pc_abs": float("inf")}
    sign = np.sign(np.sum(pc * ref["pc"], axis=0))
    return {
        "ev_rel": float(np.max(np.abs(ev - ref["explained_variance"])
                               / ref["explained_variance"])),
        "pc_abs": float(np.max(np.abs(pc * sign - ref["pc"]))),
    }


def faults() -> dict:
    """Planted faults of the timed path, name -> ``fault(ctx, x)`` that
    returns what a broken fit would hand the comparison.
    ``perfbench/tests/test_faults.py`` puts each under a whole run and sees
    ``correct`` come out false."""
    from perfbench.drivers import fit_loop

    def fit(ctx, rows):
        model = fit_loop.build_estimator(ctx.config).fit(rows)
        return fit_loop.read_model(model, ctx.config)

    def half_rows(ctx, x):
        # half of the rows left out, the moments taken over the rest
        half = x[: len(x) // 2] if isinstance(x, list) else x[: x.shape[0] // 2]
        return fit(ctx, half)

    def stale_model(ctx, x):
        # the fit hands back a model that never saw these rows' last quarter
        # changed: rows of another seed stand in for "state left unchanged"
        from perfbench import data

        gen = ctx.config["data"]
        other = data.generate(gen["generator"], ctx.args.seed + 1, 1024, ctx.cols,
                              gen["params"])
        return fit(ctx, other)

    def altered_answer(ctx, x):
        # an answer altered where it is produced: one ratio off by 1e-5 of itself
        out = fit(ctx, x)
        out["explained_variance"] = out["explained_variance"] * (1.0 + np.array([0, 1e-5, 0]))
        return out

    def altered_component(ctx, x):
        out = fit(ctx, x)
        pc = out["pc"].copy()
        pc[7, 1] += 1e-3
        out["pc"] = pc
        return out

    return {
        "half_rows": half_rows, "stale_model": stale_model,
        "altered_answer": altered_answer, "altered_component": altered_component,
    }

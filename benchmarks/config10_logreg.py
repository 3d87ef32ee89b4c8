"""Config 10: LogisticRegression fit on HIGGS-shaped 11M x 28.

Binary L2 fit, fixed 20 L-BFGS iterations, through the PUBLIC estimator
on device-resident (X, y) — the whole optimization is one jitted
lax.while_loop (ops/logistic.fit_logistic), so the timed quantity is the
full training program. FLOP accounting: the forward logits GEMM + the
gradient X^T GEMM per objective evaluation (~1 evaluation per L-BFGS
iteration with optax's cached value_and_grad), 2*2*n*d each.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import bytes_roofline, emit, require_chip, roofline, time_median

N, D, ITERS = 11_000_000, 28, 20


def main() -> None:
    require_chip()

    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.classification import LogisticRegression

    kx, kw, ke = jax.random.split(jax.random.key(10), 3)
    x = jax.random.normal(kx, (N, D), dtype=jnp.float32)
    w = jax.random.normal(kw, (D,), dtype=jnp.float32)
    y = (x @ w + 0.5 * jax.random.normal(ke, (N,), dtype=jnp.float32) > 0).astype(
        jnp.float32
    )
    float(jnp.sum(x[0]) + float(y[0]))

    est = (
        LogisticRegression().setRegParam(0.01).setMaxIter(ITERS).setTol(0.0)
    )

    def run() -> None:
        model = est.fit((x, y))
        # Scalar readback syncs the fit's in-order device stream.
        float(model._w_raw[0, 0])

    elapsed = time_median(run)
    flop = 2.0 * 2.0 * N * D * ITERS  # fwd + grad GEMM per iteration
    emit(
        "logreg_fit_11Mx28_20iter",
        N * ITERS / elapsed,
        "row-iters/s",
        wall_s=round(elapsed, 4),
        through_estimator_api=True,
        **roofline(flop, elapsed, "highest"),
        # Each evaluation reads X twice (fwd + grad contraction).
        **bytes_roofline(2.0 * 4.0 * N * D * ITERS, elapsed),
    )


if __name__ == "__main__":
    main()

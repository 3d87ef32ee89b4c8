"""BASELINE config 4: LinearRegression/Ridge on HIGGS-shaped 11M x 28.

Synthetic data at the HIGGS shape (zero-egress image: no dataset download).

Since r4 this times the PUBLIC estimator — ``LinearRegression().fit((X, y))``
with device-resident arrays — not the ops-layer kernels:
the normal-equation path (XtX/Xty sufficient-statistics GEMM + jitted
device solve) runs end-to-end inside the fit, and the model's host views
convert lazily, so the timed quantity is exactly what a user gets.

Both rooflines reported: at d=28 the config is
bytes-bound by construction (1.6 kFLOP per 112-byte row), so
pct_hbm_roofline is the honest utilization figure and pct_ceiling just
documents how far from MXU-relevant this shape is.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import bytes_roofline, emit, require_chip, roofline, time_amortized

N, D = 11_000_000, 28


def main() -> None:
    require_chip()

    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.regression import LinearRegression

    key = jax.random.key(4)
    kx, kw, ke = jax.random.split(key, 3)
    x = jax.random.normal(kx, (N, D), dtype=jnp.float32)
    w_true = jax.random.normal(kw, (D,), dtype=jnp.float32)
    y = x @ w_true + 0.1 * jax.random.normal(ke, (N,), dtype=jnp.float32)
    float(jnp.sum(x[0]))

    est = LinearRegression().setRegParam(0.1)

    def dispatch():
        # Device-resident (X, y): the whole fit stays async; the returned
        # model's raw coefficient state is the device output to sync on.
        return est.fit((x, y))._coef_raw

    elapsed = time_amortized(dispatch, lambda coef: float(coef[0]))
    # Dominant GEMMs: XtX (2nd^2) + Xty (2nd); the solve is O(d^3) ~ 0.
    # Minimum traffic: one read of X and y.
    emit(
        "linreg_normal_11Mx28_ridge",
        N / elapsed,
        "rows/s",
        wall_s=round(elapsed, 4),
        through_estimator_api=True,
        **roofline(2.0 * N * D * (D + 1), elapsed, "highest"),
        **bytes_roofline(4.0 * N * (D + 1), elapsed),
    )


if __name__ == "__main__":
    main()

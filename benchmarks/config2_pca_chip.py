"""BASELINE config 2: PCA k=50 on MNIST-shaped 60k x 784, single chip.

Synthetic data at the MNIST shape (zero-egress image: no dataset download).

Since r4 this times the PUBLIC estimator — ``PCA().setK(50).fit(x_dev)``
on a device-resident array (the whole fit is ONE jitted XLA program,
linalg/row_matrix._pca_fit_device) — replacing the hand-composed inline
fit the r3 config used. Both rooflines reported.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import bytes_roofline, emit, require_chip, roofline, time_amortized

N, D, K = 60_000, 784, 50


def main() -> None:
    require_chip()

    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.feature import PCA

    x = jax.random.normal(jax.random.key(2), (N, D), dtype=jnp.float32)
    float(jnp.sum(x[0]))

    est = PCA().setK(K)

    def dispatch():
        # Device-resident fit stays async; sync on the raw device state.
        return est.fit(x)._ev_raw

    elapsed = time_amortized(dispatch, lambda ev: float(ev[0]))
    # Dominant GEMM: the 2*n*d^2 covariance (eigh adds ~0 FLOPs — whole-
    # fit MFU accounting, same convention as bench.py). Minimum traffic:
    # one streaming read of X + the (d, d) covariance write.
    emit(
        "pca_fit_chip_60kx784_k50",
        N / elapsed,
        "rows/s",
        wall_s=round(elapsed, 4),
        through_estimator_api=True,
        **roofline(2.0 * N * D * D, elapsed, "highest"),
        **bytes_roofline(4.0 * (N * D + D * D), elapsed),
    )


if __name__ == "__main__":
    main()

"""BASELINE config 3: KMeans k=100 on a 20M-row NYC-Taxi-shaped dataset.

Synthetic 20M x 16 float32 (taxi feature width after encoding; zero-egress
image: no dataset download) clustered around 100 planted centers.

Since r4 this times the PUBLIC estimator — ``KMeans().fit(device_array)``
— not the ops-layer kernel: the device-resident input
path makes the whole fit device-side, so the estimator number must land
within ~5% of the kernel number. Fixed 10 Lloyd iterations (tol=0) keeps
runs comparable. Reported variants:

  - headline: backend="fused" (pallas assignment+stats) at
    precision="highest" — reference-parity numerics;
  - fast: precision="default" (1-pass bf16 distance scores, f32
    accumulation; measured training-cost delta ~2e-4 relative) — the
    TPU-native speed point;
  - the XLA backend at "highest" for the backend comparison.

Both rooflines are reported. The bytes column counts the
MINIMUM traffic — (ITERS+1) streaming reads of X — which the fused kernel
actually achieves (its block temporaries live in VMEM), so its
pct_hbm_roofline is the honest "how far from the ideal pass" figure.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import bytes_roofline, emit, require_chip, roofline, time_median

N, D, K, ITERS = 20_000_000, 16, 100, 10


def main() -> None:
    require_chip()

    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.clustering import KMeans

    key = jax.random.key(3)
    kc, kx, ki = jax.random.split(key, 3)
    centers_true = jax.random.normal(kc, (K, D), dtype=jnp.float32) * 5.0
    assign = jax.random.randint(ki, (N,), 0, K)
    x = centers_true[assign] + jax.random.normal(kx, (N, D), dtype=jnp.float32)
    x = jax.device_put(x)
    float(jnp.sum(x[0]))

    def fit(backend: str, precision: str):
        est = (
            KMeans()
            .setK(K)
            .setMaxIter(ITERS)
            .setTol(0.0)
            .setInitMode("random")
            .setSeed(0)
            .setBackend(backend)
            .setPrecision(precision)
        )

        def run() -> None:
            model = est.fit(x)
            # ONE scalar readback syncs the whole in-order device stream
            # (the fit is fully async; a second sync would double-pay the
            # host round trip).
            float(model._cost_raw)

        return time_median(run)

    t_fused = fit("fused", "highest")
    t_fast = fit("fused", "default")
    t_xla = fit("xla", "highest")

    passes = ITERS + 1  # ITERS updates + final cost sweep
    # Dominant GEMMs: the (n,d)x(d,k) distance matmul every pass plus the
    # (k,n)x(n,d) one-hot stats matmul on the ITERS update passes.
    flop = 2.0 * N * D * K * passes + 2.0 * N * K * D * ITERS
    # Minimum HBM traffic: one streaming read of X per pass (block
    # temporaries are VMEM-resident in the fused kernel) + the one-time
    # transposed copy (read + write).
    min_bytes = 4.0 * N * D * (passes + 2)
    emit(
        "kmeans_20Mx16_k100_10iter",
        N * passes / t_fused,
        "row-iters/s",
        wall_s=round(t_fused, 4),
        through_estimator_api=True,
        backend="fused",
        precision="highest",
        default_precision_row_iters_per_s=round(N * passes / t_fast, 0),
        xla_backend_row_iters_per_s=round(N * passes / t_xla, 0),
        **roofline(flop, t_fused, "highest"),
        **bytes_roofline(min_bytes, t_fused),
    )


if __name__ == "__main__":
    main()

"""Config 9: RandomForest classification fit (the
families with no benchmark row).

500k x 16 synthetic, 8 trees, depth 6, 16 bins, 2 classes — through the
PUBLIC estimator on device-resident (X, y). The dominant compute is the
level-order histogram GEMM (ops/trees._level_histograms): per level l,
S einsums of (T, n, M_l) x (n, d*B) with M_l = 2^l nodes, so
FLOP = sum_l 2*S*T*n*2^l*d*B — the one-hot "scatter-free counting on the
MXU" design pays dense FLOPs for gather-free histograms, which is
exactly what the MFU column quantifies. Bytes: (ITERS-free, one level
pass reads x_binned int32 + stats per level).
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import bytes_roofline, emit, require_chip, roofline, time_amortized

N, D, TREES, DEPTH, BINS, CLASSES = 500_000, 16, 8, 6, 16, 2


def main() -> None:
    require_chip()

    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.classification import RandomForestClassifier

    kx, kw, ke = jax.random.split(jax.random.key(9), 3)
    x = jax.random.normal(kx, (N, D), dtype=jnp.float32)
    w = jax.random.normal(kw, (D,), dtype=jnp.float32)
    margin = x @ w + 0.3 * jax.random.normal(ke, (N,), dtype=jnp.float32)
    y = (margin > 0).astype(jnp.float32)
    float(jnp.sum(x[0]) + float(y[0]))

    est = (
        RandomForestClassifier()
        .setNumTrees(TREES)
        .setMaxDepth(DEPTH)
        .setMaxBins(BINS)
        .setSeed(0)
        # The Spark-metadata analogue: with the class count declared, a
        # device-resident fit dispatches with ZERO label readbacks, so
        # the whole fit (quantize + bin + grow, ONE XLA program since r5)
        # is async and the slope timing measures the device, not a
        # host round trip per readback.
        .setNumClasses(CLASSES)
    )

    elapsed = time_amortized(
        lambda: est.fit((x, y))._forest.leaf_value,
        lambda lv: float(lv[0, 0, 0]),
        inner=4,
    )
    flop = sum(
        2.0 * CLASSES * TREES * N * (2 ** level) * D * BINS
        for level in range(DEPTH)
    )
    # Traffic: one read of the binned matrix + stats + weights per level.
    level_bytes = 4.0 * N * (D + CLASSES + TREES)
    emit(
        "rf_classifier_fit_500kx16_t8_d6",
        N / elapsed,
        "rows/s",
        wall_s=round(elapsed, 4),
        through_estimator_api=True,
        # Ceiling at DEFAULT precision (honest): this unweighted
        # classification fit runs its histogram GEMMs one-pass bf16
        # (exact integer counts — ops/trees precision note), so the
        # 6-pass HIGHEST divisor would flatter the MFU 6x. The absolute
        # figure is small by design: the one-hot formulation PAYS dense
        # FLOPs to make histogramming gather-free, and the per-level
        # matmuls are narrow (M = 2^level output columns) — rows/s is
        # the metric this family competes on.
        **roofline(flop, elapsed, "default"),
        **bytes_roofline(level_bytes * DEPTH, elapsed),
    )


if __name__ == "__main__":
    main()

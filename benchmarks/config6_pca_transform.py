"""Config 6: batched PCA transform throughput — the path the reference
DISABLED as too slow (RapidsPCA.scala:172-185, "TODO(rongou): make this
faster and re-enable"; its JVM fallback does a per-row pc^T*v UDF).

Here the batched projection is the LIVE transform path and runs through
the public model API on a device-resident input (PCAModel.transform ->
ops.linalg.project_rows, one (n,d)x(d,k) MXU GEMM). At d=1024, k=16 the
op reads 4 GB per call against ~0.034 TFLOP of math — HBM-bound by
construction; pct_ceiling reports the MXU view, and the rows/s number is
the one that proves the reference's disabled path is a win here.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import bytes_roofline, emit, require_chip, roofline, time_amortized

N, D, K = 1_000_000, 1024, 16


def main() -> None:
    require_chip()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from spark_rapids_ml_tpu.models.pca import PCAModel

    x = jax.random.normal(jax.random.key(6), (N, D), dtype=jnp.float32)
    float(jnp.sum(x[0]))
    # Orthonormal components, as a fitted model would carry.
    q, _ = np.linalg.qr(np.random.default_rng(0).normal(size=(D, K)))
    model = PCAModel("bench", q, np.full(K, 1.0 / K))

    elapsed = time_amortized(
        lambda: model.transform(x), lambda out: float(out[0, 0]), inner=5
    )
    emit(
        "pca_transform_chip_1Mx1024_k16",
        N / elapsed,
        "rows/s",
        wall_s=round(elapsed, 4),
        **roofline(2.0 * N * D * K, elapsed, "highest"),
        # The transform is HBM-bound at k=16 (one streaming read of X
        # dominates; the (n, k) output is 64x smaller) — the bytes
        # roofline is the honest lens here, not the FLOP MFU.
        **bytes_roofline(4.0 * (N * D + N * K), elapsed),
    )


if __name__ == "__main__":
    main()

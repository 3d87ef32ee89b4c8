"""Config 1: PCA k=3 on 10k x 50 synthetic vectors, CPU path.

The correctness floor (no accelerator): the packed/spr-layout covariance with
host SVD — the analogue of the reference's useGemm=false, useCuSolverSVD=false
fallback (RapidsRowMatrix.scala:202-251, :110-123). This config IS the
no-accelerator floor, so it pins the CPU platform itself (the env var for
a jax not yet imported, the config update for one that is — the same
pattern as tests/conftest.py).
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"
import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np

from benchmarks.common import emit, roofline, time_median

N, D = 10_000, 50


def main() -> None:
    from spark_rapids_ml_tpu.models.pca import PCA

    rng = np.random.default_rng(1)
    x = rng.normal(size=(N, D))

    est = PCA().setK(3).setInputCol("features").setUseGemm(False).setUseCuSolverSVD(False)

    def run() -> None:
        est.fit(x)

    elapsed = time_median(run)
    # CPU floor: TFLOP/s reported for completeness; precision=None skips
    # pct_ceiling (the MXU roofline constant does not apply here).
    emit(
        "pca_fit_cpu_10kx50_k3",
        N / elapsed,
        "rows/s",
        wall_s=round(elapsed, 4),
        **roofline(2.0 * N * D * D, elapsed, precision=None),
    )


if __name__ == "__main__":
    main()

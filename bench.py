"""Benchmark: PCA().fit throughput through the PUBLIC estimator API.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...,
"device": {"platform", "device_kind", "count"}}. It is a chip benchmark:
where jax finds no chip listed in ``benchmarks.common.DEVICE_PEAKS`` it
prints ``{"ok": false, ...}`` and exits 1 instead of timing a CPU under a
chip metric's name.

Workload: `PCA().setK(16).fit(x)` on a 1M x 1024 float32 device-resident
row matrix — the north-star shape's single-chip slice (the north star is
100M x 1024 on 8 chips). The fit runs end-to-end through the estimator:
column means + fused centered covariance GEMM + self-selecting eigensolver
+ explained variance, compiled as ONE XLA program
(linalg.row_matrix._pca_fit_device), with the model's host view converted
lazily. This measures the same entry point a user calls (the reference
benchmarks PCA.fit implicitly via spark-submit, RapidsPCA.scala:111) — not
a hand-inlined kernel composition.

Data is generated on-device and timing covers the fit computation only (the
sync reads one model scalar): host->device transfer is set-up, not the
framework. The baseline is correspondingly compute-only: a roofline
estimate of the reference's fp64 cuBLAS DGEMM covariance + cuSolver syevd on
a V100 (the GPU class current when the reference was written; the reference
publishes no numbers): 2*n*d^2 / (7 TFLOP/s * 0.7) for the GEMM plus ~0.1 s
for syevd at d=1024.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

N_ROWS = 1_000_000
N_COLS = 1024
K = 16


def _baseline_rows_per_sec() -> float:
    gemm_t = (2.0 * N_ROWS * N_COLS * N_COLS) / (7.0e12 * 0.7)
    syevd_t = 0.1
    return N_ROWS / (gemm_t + syevd_t)


def main() -> None:
    from benchmarks.common import (
        _PRECISION_PASSES,
        device_peaks,
        require_chip,
        time_amortized,
    )

    device = require_chip()

    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.core.serving import configure_compile_cache
    from spark_rapids_ml_tpu.feature import PCA

    configure_compile_cache()

    x = jax.random.normal(jax.random.key(7), (N_ROWS, N_COLS), dtype=jnp.float32)
    float(jnp.sum(x[0]))  # materialize input before timing

    pca = PCA().setK(K)  # all defaults: precision/eigenSolver/solver = auto

    # Two-point-slope timing (benchmarks.common.time_amortized): per-exec
    # time comes from the slope between a small and a large queued batch,
    # so the fixed cost of a sync cancels exactly instead of leaving
    # fixed/inner ms in the figure. The sync reads the model's public
    # explainedVariance (host view converts lazily — only the final
    # model of each batch pays it). Two measurement rounds, best-of
    # (standard min-time practice): a host stall in a single round would
    # be recorded as the framework's throughput.
    elapsed = min(
        time_amortized(
            lambda: pca.fit(x),
            lambda model: float(model.explainedVariance[0]),
            inner=12,
        )
        for _ in range(2)
    )
    rows_per_sec = N_ROWS / elapsed

    # WHOLE-FIT MFU accounting, denominated in the covariance GEMM's
    # 2 n d^2 FLOPs (eigh/mean add ~0 FLOPs but real seconds). The
    # fp32-HIGHEST ceiling divisor lives in ONE place —
    # benchmarks.common._PRECISION_PASSES — shared with every per-config
    # pct_ceiling figure.
    flop = 2.0 * N_ROWS * N_COLS * N_COLS
    tflops = flop / elapsed / 1e12
    peak_bf16 = device_peaks()["bf16_tflops"]
    ceiling = peak_bf16 / _PRECISION_PASSES["highest"]
    print(
        json.dumps(
            {
                "metric": "pca_fit_rows_per_sec_single_chip_1Mx1024",
                "value": round(rows_per_sec, 1),
                "unit": "rows/s",
                "vs_baseline": round(rows_per_sec / _baseline_rows_per_sec(), 3),
                "whole_fit_tflops": round(tflops, 2),
                "whole_fit_mfu_vs_fp32_highest_ceiling": round(tflops / ceiling, 3),
                "whole_fit_mfu_vs_bf16_peak": round(tflops / peak_bf16, 3),
                "through_estimator_api": True,
                "device": device,
            }
        )
    )


if __name__ == "__main__":
    main()

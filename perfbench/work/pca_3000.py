"""What one PCA fit REQUIRES, from shapes alone, whatever computes it.

The covariance of n rows by d columns is one (d, n) x (n, d) product:
2*n*d*d floating-point operations (a multiply and an add per term; the
symmetry is not claimed) reading the n*d float32 matrix once. The top-k
eigensolve of the (d, d) result and the column means are of lower order and
not counted, so the whole fit requires the same operations as its GEMM.
A six-pass float32 GEMM therefore reads at most about a sixth of the bf16
peak here, and a cheaper way to the same answer reads higher: intended.
"""

from __future__ import annotations


def work(rows: int, cols: int, config: dict, results: list) -> dict:
    flops = 2.0 * rows * cols * cols
    return {
        "gemm_flops": flops,
        "gemm_bytes": 4.0 * rows * cols,
        "fit_flops": flops,
        "host_bytes": 4.0 * rows * cols,
    }

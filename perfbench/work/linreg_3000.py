"""What one elastic-net linear-regression fit REQUIRES, from shapes alone.

The fit needs the centred second moments of ``[X | y]``: the (d, n) x (n, d)
product of the rows with themselves and the (d, n) x (n,) product with the
labels, 2*n*d*(d + 1) floating-point operations (a multiply and an add per
term; the symmetry is not claimed), reading the n*(d + 1) float32 values once.
The proximal loop on the (d, d) moments, ``2 * d * d`` a matrix-vector product
for each of ``maxIter`` iterations and of the step size's power iterations, the
means and the labels' own square are of lower order and NOT counted (at
500,000 x 3000 with 10 + 31 products: 7.4e8 of 9.0e12). As in ``pca_3000``, a
six-pass float32 GEMM reads at most about a sixth of the bf16 peak here.
"""

from __future__ import annotations


def work(rows: int, cols: int, config: dict, results: list) -> dict:
    flops = 2.0 * rows * cols * (cols + 1)
    return {
        "gemm_flops": flops,
        "gemm_bytes": 4.0 * rows * (cols + 1),
        "fit_flops": flops,
    }

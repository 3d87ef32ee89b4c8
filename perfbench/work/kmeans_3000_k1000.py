"""What one KMeans fit REQUIRES, from shapes and the iterations it ran.

Each Lloyd iteration needs every row's distance to every centre: one
(n, d) x (d, k) product, 2*n*d*k floating-point operations reading the n*d
float32 matrix once; so does the pass that prices the returned centres. The
centre update needs n*d additions (a sum per cluster), of lower order and
not counted: a program that does it as a second (k, n) x (n, d) product
spends twice the required work and reads half the roofline. Iterations are
the ``n_iter`` each fit of the window reported, averaged: a fit that stops
early requires less.
"""

from __future__ import annotations


def work(rows: int, cols: int, config: dict, results: list) -> dict:
    k = int(config["k"])
    iters = [float(r["n_iter"]) for r in results]
    passes = (sum(iters) / len(iters) if iters else float(config["max_iter"])) + 1.0
    flops = passes * 2.0 * rows * cols * k
    return {
        "gemm_flops": flops,
        "gemm_bytes": passes * 4.0 * rows * cols,
        "fit_flops": flops,
        "host_bytes": 4.0 * rows * cols,
    }

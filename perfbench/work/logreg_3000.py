"""What one logistic-regression fit REQUIRES, from shapes and the iterations it ran.

An L-BFGS iteration needs the objective and its gradient at one new point at
least, and the start needs them once: ``numIter + 1`` points. Each is ONE
read of the (n, d) float32 matrix, 4nd bytes, for 4nd floating-point
operations (the product with the coefficients, 2nd, and the product of the
residuals with the rows, 2nd; the n sigmoids and the d-sized optimiser
algebra are of lower order and not counted). A second read of the rows at a
point (the direction's margins in one pass and the gradient in another, as
the program has it), trial steps of a line search: the program's choice, not
required, and read as a lower roofline: a program of two passes an iteration
reads 50% at most. Iterations are the ``n_iter`` each fit of the window
reported, averaged: a fit that stops early requires less.

``fit_flops`` is what ``fit_mfu`` reads and ``fit_bytes`` what
``objective_roofline`` is held against. There are no ``gemm_*`` keys: a
matrix-vector pass is no matrix multiplication, and ``gemm_roofline`` does not
list this configuration's cells. The least time is bytes over 819 GB/s (7.3 ms
a point at 500,000 x 3000) against FLOP over 197 TFLOP/s (0.03 ms):
memory-bound by 240.
"""

from __future__ import annotations


def work(rows: int, cols: int, config: dict, results: list) -> dict:
    iters = [float(r["n_iter"]) for r in results]
    points = (sum(iters) / len(iters) if iters else float(config["max_iter"])) + 1.0
    per_point = 4.0 * rows * cols  # as many operations as bytes
    return {"fit_flops": points * per_point, "fit_bytes": points * per_point}

"""What one UMAP fit REQUIRES, from shapes alone, whatever computes it.

The exact kNN graph needs every row's squared distance to every row: one
(n, d) x (d, n) product, 2*n*n*d floating-point operations reading the n*d
float32 rows once (the norms, the selection of the k nearest and the fuzzy
graph are of lower order and not counted). That is ``gemm_*`` and
``fit_flops``: FLOP-bound by far (1.9 s at 197 TFLOP/s against 3.7 ms for
the bytes at 250,000 x 3000).

A layout epoch needs the graph once, a neighbour id and a weight an edge
(8 bytes an edge, n*k edges), and the layout read and written once (2 * 4 *
n * nComponents bytes). Gathering a neighbour's position, the negatives'
pool and the tail accumulation are the program's way, not required, and
read as a lower roofline. ``layout_bytes`` is that over ``n_epochs``, what
``layout_roofline`` is held against: 34 MB an epoch at 250,000 rows and 15
neighbours, 41.5 us at 819 GB/s.
"""

from __future__ import annotations


def work(rows: int, cols: int, config: dict, results: list) -> dict:
    flops = 2.0 * rows * rows * cols
    k, dim = int(config["n_neighbors"]), int(config["n_components"])
    epoch_bytes = 8.0 * rows * k + 2.0 * 4.0 * rows * dim
    return {
        "gemm_flops": flops,
        "gemm_bytes": 4.0 * rows * cols,
        "fit_flops": flops,
        "layout_bytes": float(config["n_epochs"]) * epoch_bytes,
    }

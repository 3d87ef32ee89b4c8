"""What one random-forest fit REQUIRES, from shapes alone, whatever implements it.

T trees grow D levels over n rows; every row lies in ONE node of a tree and
every node looks at K of the d features (``features_per_node``), into B bins
and S classes.

``fit_bytes``: a tree-level reads, per row, the K bin ids its node looks at
(one byte each at up to 256 bins, two past that), its node id (4) and its
bootstrap weight (4), and writes its next node id (4): ``T * D * n * (K + 12)``,
67 B a row and level at K = 55 (2.8 GB a fit of ``rf_3000``: 3.5 ms at 819
GB/s). The matrix of bin ids is NOT required a level: a row's other d - K ids
are not looked at. Binning itself (one read of the float32 rows) is counted
once: ``4 * n * d``.

``fit_flops``: one addition a selected element and class, ``T * D * n * K * S``,
and the gain arithmetic a histogram cell: ``T * (2^D - 1) * K * B`` candidate
splits, each ``GAIN_FLOPS`` operations a class (the running sum, the right side
as a difference, two shares and their squares into two impurities, the weighted
sum). The binning's comparisons (a binary search: ``log2 B`` a value) are added.

There are no ``gemm_*`` keys: the histogram is a count, and whatever matrix
products a builder spends on it are its own choice, read as a low
``fit_mfu``. The least time is the bytes' (3.5 + 3.7 ms) against 0.03 ms of
operations: memory-bound, and by any measure a thousand times under a second.
"""

from __future__ import annotations

import math

GAIN_FLOPS = 9.0  # per candidate split and class: see the module note


def work(rows: int, cols: int, config: dict, results: list) -> dict:
    trees = float(config["num_trees"]["run"])
    depth = float(config["max_depth"])
    k = float(config["features_per_node"])
    bins = float(config["max_bins"])
    classes = float(config["num_classes"])
    id_bytes = 1.0 if bins <= 256 else 2.0
    tree_levels = trees * depth
    grow_bytes = tree_levels * rows * (k * id_bytes + 12.0)
    bin_bytes = 4.0 * rows * cols
    grow_flops = (tree_levels * rows * k * classes
                  + trees * (2.0**depth - 1.0) * k * bins * classes * GAIN_FLOPS)
    bin_flops = rows * cols * math.log2(bins)
    return {
        "fit_flops": grow_flops + bin_flops,
        "fit_bytes": grow_bytes,  # the growth's: what forest_grow_roofline is held against
        "bin_bytes": bin_bytes,
        "tree_levels": tree_levels,
        "selected_elems": tree_levels * rows * k,
    }

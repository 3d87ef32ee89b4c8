"""Config 13: UMAP fit, graph and SGD phases split.

50k x 64 -> 2-D, nNeighbors=15, 200 epochs — through the PUBLIC
estimator on device-resident input (buildAlgo="brute_approx", the
at-scale default of the cuML spark lineage). The phase split is measured
directly at the ops layer with the same shapes: the kNN graph build (the
O(n^2 d) stage) vs the whole fit (graph + smooth-kNN + layout SGD).
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import bytes_roofline, emit, require_chip, roofline, time_median

N, D, NN, EPOCHS = 50_000, 64, 15, 200


def main() -> None:
    require_chip()

    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.manifold import UMAP
    from spark_rapids_ml_tpu.models.umap import _knn_excluding_self

    x = jax.random.normal(jax.random.key(13), (N, D), dtype=jnp.float32)
    float(jnp.sum(x[0]))

    est = (
        UMAP()
        .setNNeighbors(NN)
        .setNEpochs(EPOCHS)
        .setBuildAlgo("brute_approx")
        .setInit("random")  # spectral's dense Laplacian eigh would dwarf SGD at 50k
        .setSeed(0)
    )

    def run() -> None:
        model = est.fit(x)
        # Scalar readback syncs the fit's in-order device stream.
        float(model._emb_raw[0, 0])

    elapsed = time_median(run)

    def graph_only() -> None:
        d_, i_ = _knn_excluding_self(x, NN, "euclidean", None, approx=True)
        int(i_[0, 0])  # scalar sync

    t_graph = time_median(graph_only)
    emit(
        "umap_fit_50kx64_nn15_e200",
        N / elapsed,
        "rows/s",
        wall_s=round(elapsed, 4),
        through_estimator_api=True,
        graph_phase_s=round(t_graph, 4),
        sgd_phase_s=round(max(elapsed - t_graph, 0.0), 4),
        **roofline(2.0 * N * N * D, elapsed, "highest"),
        **bytes_roofline(4.0 * N * D * 2 + 4.0 * N * NN * EPOCHS * 8, elapsed),
    )


if __name__ == "__main__":
    main()

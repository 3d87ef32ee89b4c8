"""Config 11: exact kNN through the PUBLIC NearestNeighbors estimator
(the families with no benchmark row).

1M items x 96, 10k queries, k=10 — the same shape as the ANN headline
(config 7) so the exact/approx gap is directly readable. Device-resident
items and queries; auto item blocking.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import bytes_roofline, emit, require_chip, roofline, time_amortized

N_ITEMS, D, N_QUERIES, K = 1_000_000, 96, 10_000, 10


def main() -> None:
    require_chip()

    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.neighbors import NearestNeighbors

    items = jax.random.normal(jax.random.key(0), (N_ITEMS, D), dtype=jnp.float32)
    queries = jax.random.normal(jax.random.key(1), (N_QUERIES, D), dtype=jnp.float32)
    float(jnp.sum(items[0]) + jnp.sum(queries[0]))

    model = NearestNeighbors().setK(K).setMetric("sqeuclidean").fit(items)
    elapsed = time_amortized(
        lambda: model.kneighbors(queries),
        lambda out: float(out[0][0, 0]),
        inner=3,
    )
    emit(
        "knn_exact_1Mx96_q10k_k10",
        N_QUERIES / elapsed,
        "queries/s",
        wall_s=round(elapsed, 4),
        through_estimator_api=True,
        **roofline(2.0 * N_QUERIES * N_ITEMS * D, elapsed, "highest"),
        **bytes_roofline(4.0 * N_ITEMS * D, elapsed),
    )


if __name__ == "__main__":
    main()

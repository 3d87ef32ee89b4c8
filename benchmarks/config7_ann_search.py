"""Config 7: ANN search throughput — the neighbor-family headline (the
modern RAPIDS Spark-ML line's approximateNearestNeighbors).

Measures the three single-chip search methods at 1M items x 96 dims,
10k queries, k=10 — since r4 through the PUBLIC estimator API
(``ApproximateNearestNeighbors().fit(items_dev).kneighbors(q_dev)`` with
device-resident arrays):

  - ``brute_approx`` (dense MXU distance GEMM + hardware approximate
    top-k, ``lax.approx_min_k``) — the headline: the TPU-first result is
    that this beats inverted lists at 0.995 recall, because TPU gathers
    are scalarized while dense GEMMs ride the systolic array;
  - ``brute`` (same GEMM, exact ``top_k`` merge);
  - ``ivfflat`` (n_lists=1024, n_probe=32 — the structure that wins on
    GPUs; reported for the crossover evidence).

FLOP accounting for the headline: the dense distance GEMM
(2*Q*N_items*d). Bytes: one read of the item matrix per query batch (the
query matrix and top-k state are cache-resident noise at this shape).
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import bytes_roofline, emit, require_chip, roofline, time_amortized

N_ITEMS, D, N_LISTS, N_QUERIES, N_PROBE, K = 1_000_000, 96, 1024, 10_000, 32, 10


def main() -> None:
    require_chip()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from spark_rapids_ml_tpu.neighbors import ApproximateNearestNeighbors

    items = jax.random.normal(jax.random.key(0), (N_ITEMS, D), dtype=jnp.float32)
    queries = jax.random.normal(jax.random.key(1), (N_QUERIES, D), dtype=jnp.float32)
    float(jnp.sum(items[0]) + jnp.sum(queries[0]))

    def timed_model(algorithm, algo_params=None):
        est = (
            ApproximateNearestNeighbors()
            .setK(K)
            .setAlgorithm(algorithm)
            .setMetric("sqeuclidean")
        )
        if algo_params:
            est = est.setAlgoParams(algo_params)
        model = est.fit(items)
        t = time_amortized(
            lambda: model.kneighbors(queries),
            lambda out: float(out[0][0, 0]),
            inner=3,
        )
        return t, model

    t_approx, m_approx = timed_model("brute_approx")
    t_exact, m_exact = timed_model("brute")
    t_ivf, _ = timed_model("ivfflat", {"nlist": N_LISTS, "nprobe": N_PROBE})

    # Recall of the approximate path against the exact one.
    ie = np.asarray(m_exact.kneighbors(queries)[1])
    ia = np.asarray(m_approx.kneighbors(queries)[1])
    sample = range(0, N_QUERIES, 37)
    recall = float(np.mean([len(set(ie[i]) & set(ia[i])) / K for i in sample]))

    emit(
        "ann_search_1Mx96_q10k_k10",
        N_QUERIES / t_approx,
        "queries/s",
        wall_s=round(t_approx, 4),
        through_estimator_api=True,
        method="brute_approx",
        recall_vs_exact=round(recall, 4),
        brute_exact_qps=round(N_QUERIES / t_exact, 1),
        ivfflat_qps=round(N_QUERIES / t_ivf, 1),
        **roofline(2.0 * N_QUERIES * N_ITEMS * D, t_approx, "highest"),
        **bytes_roofline(4.0 * N_ITEMS * D, t_approx),
    )


if __name__ == "__main__":
    main()

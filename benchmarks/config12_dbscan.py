"""Config 12: DBSCAN fit (the families with no benchmark
row).

100k x 16, eps tuned to planted blobs — through the PUBLIC estimator on
device-resident input. The dominant compute is the blocked eps-graph
distance GEMM (one (n, d) x (d, n) sweep) plus the min-label diffusion
sweeps; FLOPs count ONE full pairwise sweep (diffusion sweep count is
data-dependent), so the MFU column is conservative.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import bytes_roofline, emit, require_chip, roofline, time_median

N, D, CLUSTERS = 100_000, 16, 20


def main() -> None:
    require_chip()

    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.clustering import DBSCAN

    kc, kx, ki = jax.random.split(jax.random.key(12), 3)
    centers = jax.random.normal(kc, (CLUSTERS, D), dtype=jnp.float32) * 12.0
    assign = jax.random.randint(ki, (N,), 0, CLUSTERS)
    x = centers[assign] + 0.4 * jax.random.normal(kx, (N, D), dtype=jnp.float32)
    float(jnp.sum(x[0]))

    est = DBSCAN().setEps(2.0).setMinSamples(8)

    def run() -> None:
        model = est.fit(x)
        # Labels ARE the fitted output — the host pull is the result.
        int(model.labels_[0])

    elapsed = time_median(run)
    emit(
        "dbscan_fit_100kx16",
        N / elapsed,
        "rows/s",
        wall_s=round(elapsed, 4),
        through_estimator_api=True,
        **roofline(2.0 * N * N * D, elapsed, "highest"),
        **bytes_roofline(4.0 * N * D * 2, elapsed),
    )

    # Adversarial chain topology: one cluster whose
    # diameter equals n. The old diffusion converged in O(diameter)
    # expensive eps sweeps; with full path compression between sweeps the
    # sweep count is O(log n) (a small constant for a pure chain).
    from spark_rapids_ml_tpu.ops.dbscan import dbscan_labels

    n_chain = 100_000
    chain = jnp.stack(
        [jnp.arange(n_chain, dtype=jnp.float32) * 0.5, jnp.zeros(n_chain)],
        axis=1,
    )
    float(jnp.sum(chain[0]))

    sweeps_out = {}

    def run_chain() -> None:
        labels, _, sweeps = dbscan_labels(chain, 0.6, 2, return_sweeps=True)
        sweeps_out["sweeps"] = int(sweeps)  # scalar sync
        int(labels[0])

    t_chain = time_median(run_chain)
    emit(
        "dbscan_chain_100k_diameter_n",
        n_chain / t_chain,
        "rows/s",
        wall_s=round(t_chain, 4),
        eps_sweeps=sweeps_out["sweeps"],
        **roofline(2.0 * n_chain * n_chain * 2, t_chain, "highest"),
        **bytes_roofline(4.0 * n_chain * 2 * 2, t_chain),
    )


if __name__ == "__main__":
    main()

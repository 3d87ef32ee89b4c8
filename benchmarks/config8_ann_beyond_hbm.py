"""Config 8: the beyond-HBM ANN regime, settled by measurement
(the old "inverted lists remain for item counts beyond HBM"
docstring claim was folklore).

Three strategies compete at 1M x 128 — a stand-in scale: a literal
beyond-HBM item set spends the benchmark budget on the host<->device
transfer (the IVF build crosses host<->device once by design);
both competitors below are LINEAR in item count, so the measured RATES
and the bandwidth crossover transfer directly to the beyond-HBM regime:

  - resident ``brute_approx`` (the in-HBM champion, for scale);
  - resident ``ivfpq`` (M=32 subquantizers -> 32 MB of codes here: the
    ONLY structure whose residency keeps shrinking relative to raw items
    as they grow, so it is the only resident option once raw items
    exceed HBM). Refine is OFF by design — exact re-ranking gathers the
    RAW items, which are precisely what a beyond-HBM deployment cannot
    keep resident;
  - the STREAMED brute path (``knn_host_streamed``): per-block device
    merge throughput measured with a resident rotating block (host
    transfer excluded — it would measure the host link, not the
    architecture). The streamed wall-clock on real hardware is
    max(source_bandwidth_time, device_time), so the crossover against
    ivfpq is reported as the REQUIRED source bandwidth — above it
    streaming wins, below it compressed residency wins.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import bytes_roofline, emit, require_chip, roofline, time_amortized

N_ITEMS, D, N_QUERIES, K = 1_000_000, 128, 2_000, 10
BLOCK = 262_144


def main() -> None:
    require_chip()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from spark_rapids_ml_tpu.neighbors import ApproximateNearestNeighbors
    from spark_rapids_ml_tpu.ops.knn import _merge_block_topk

    # ONE item set for both competitors (recall must compare like with
    # like): generated on host, uploaded once for the brute side; the
    # ivfpq build consumes the host copy directly (host list packing —
    # a device-resident input would pay a device->host pull here).
    rng = np.random.default_rng(0)
    items_host = rng.standard_normal((N_ITEMS, D)).astype(np.float32)
    items = jax.device_put(items_host)
    queries = jax.random.normal(jax.random.key(1), (N_QUERIES, D), dtype=jnp.float32)
    float(jnp.sum(items[0]) + jnp.sum(queries[0]))

    def timed(dispatch, inner=3):
        return time_amortized(dispatch, lambda out: float(out[0][0, 0]), inner=inner)

    # Resident champion at this scale.
    brute = (
        ApproximateNearestNeighbors()
        .setK(K)
        .setAlgorithm("brute_approx")
        .setMetric("sqeuclidean")
        .fit(items)
    )
    t_brute = timed(lambda: brute.kneighbors(queries))
    idx_brute = np.asarray(brute.kneighbors(queries)[1])
    del brute

    # Compressed resident index (the only resident option beyond HBM).
    ivfpq = (
        ApproximateNearestNeighbors()
        .setK(K)
        .setAlgorithm("ivfpq")
        .setMetric("sqeuclidean")
        .setAlgoParams({"nlist": 512, "nprobe": 16, "M": 32,
                        "kmeans_iters": 3, "pq_iters": 3})
        .fit(items_host)
    )
    t_ivfpq = timed(lambda: ivfpq.kneighbors(queries))
    ia = np.asarray(ivfpq.kneighbors(queries)[1])
    sample = range(0, N_QUERIES, 17)
    recall_pq = float(
        np.mean([len(set(idx_brute[i]) & set(ia[i])) / K for i in sample])
    )

    # Streamed-path DEVICE throughput: one rotating resident block through
    # the jitted merge (upload excluded by design — see module docstring).
    q_sq = jnp.sum(queries * queries, axis=1)
    xb = items[:BLOCK]
    best_d = jnp.full((N_QUERIES, K), jnp.inf, jnp.float32)
    best_i = jnp.full((N_QUERIES, K), -1, jnp.int32)

    def merge_once():
        return _merge_block_topk(
            best_d, best_i, queries, q_sq, xb, jnp.int32(0), K,
            approx=True,
        )

    t_block = time_amortized(
        lambda: merge_once(), lambda out: float(out[0][0, 0]), inner=8
    )
    n_blocks = -(-N_ITEMS // BLOCK)
    t_stream_device = t_block * n_blocks
    # Crossover: streaming beats the compressed resident index when the
    # source can feed blocks faster than the ivfpq search budget allows.
    item_gb = 4.0 * N_ITEMS * D / 1e9
    bw_needed = item_gb / max(t_ivfpq - t_stream_device, 1e-9)

    emit(
        "ann_beyond_hbm_1Mx128_q2k_k10",
        N_QUERIES / t_ivfpq,
        "queries/s",
        wall_s=round(t_ivfpq, 4),
        through_estimator_api=True,
        method="ivfpq_resident",
        ivfpq_recall_vs_brute=round(recall_pq, 4),
        brute_approx_resident_qps=round(N_QUERIES / t_brute, 1),
        streamed_device_qps=round(N_QUERIES / t_stream_device, 1),
        streamed_source_bw_gbps_to_beat_ivfpq=(
            round(bw_needed, 1) if bw_needed > 0 else None
        ),
        # ADC accounting: each query probes nprobe/nlist = 1/32 of the
        # items and accumulates M=32 table adds per probed code.
        **roofline(2.0 * N_QUERIES * (N_ITEMS / 32) * 32, t_ivfpq, "highest"),
        **bytes_roofline(N_QUERIES * (N_ITEMS / 32) * 32, t_ivfpq),
    )


if __name__ == "__main__":
    main()

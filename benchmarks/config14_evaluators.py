"""Config 14: device evaluators (the last unbenchmarked
surface).

10M-row binary AUC through the PUBLIC BinaryClassificationEvaluator on
device-resident (labels, scores) — the on-device sort path
(the AUC no longer collects to host) — plus the regression and
multiclass device evaluators at the same scale. The AUC's dominant cost
is the device sort: O(n log n) comparisons, reported against the bytes
roofline (sorts are bandwidth-bound: ~log2(n) passes over the data).
"""

from __future__ import annotations

import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import bytes_roofline, emit, require_chip, time_amortized, time_median

N = 10_000_000


def main() -> None:
    require_chip()

    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.evaluation import (
        BinaryClassificationEvaluator,
        MulticlassClassificationEvaluator,
        RegressionEvaluator,
    )

    ky, kp = jax.random.split(jax.random.key(14))
    scores = jax.random.uniform(ky, (N,), dtype=jnp.float32)
    labels = (
        jax.random.uniform(kp, (N,), dtype=jnp.float32) < scores
    ).astype(jnp.float32)
    float(jnp.sum(scores[0:1]))

    # The timed quantity IS the public evaluate() call (rows
    # must time what through_estimator_api claims); evaluate returns a
    # Python float, so each run includes exactly one scalar-readback sync
    # — the honest per-call cost of the estimator API. Because that sync
    # is INSIDE every call, batching cannot amortize it, so the roofline
    # fields (device-bytes utilization) come from a separate slope-timed
    # run of the underlying device op, labeled as such.
    from spark_rapids_ml_tpu.ops.metrics import binary_auc_device

    auc_ev = BinaryClassificationEvaluator()
    t_auc = time_median(lambda: auc_ev.evaluate((labels, scores)))
    auc = auc_ev.evaluate((labels, scores))
    t_auc_device = time_amortized(
        lambda: binary_auc_device(labels, scores), lambda out: float(out)
    )

    reg_ev = RegressionEvaluator().setMetricName("rmse")
    t_reg = time_median(lambda: reg_ev.evaluate((labels, scores)))

    mc_ev = MulticlassClassificationEvaluator().setMetricName("accuracy")
    preds = (scores > 0.5).astype(jnp.float32)
    acc = mc_ev.evaluate((labels, preds))

    # Sort-bound traffic model: ~log2(n) full passes (read+write) of the
    # (score, label) pairs.
    sort_bytes = 2.0 * 8.0 * N * math.log2(N)
    emit(
        "binary_auc_device_10M",
        N / t_auc,
        "rows/s",
        wall_s=round(t_auc, 4),
        through_estimator_api=True,
        auc=round(float(auc), 4),
        multiclass_accuracy=round(float(acc), 4),
        regression_rmse_evaluate_wall_s=round(t_reg, 5),
        # Roofline against the slope-timed DEVICE wall (ops-layer
        # binary_auc_device): evaluate()'s internal sync is a fixed
        # host round trip per call that batching cannot amortize, so
        # the API wall above would understate device-bytes utilization.
        device_wall_s=round(t_auc_device, 4),
        **bytes_roofline(sort_bytes, t_auc_device),
    )


if __name__ == "__main__":
    main()

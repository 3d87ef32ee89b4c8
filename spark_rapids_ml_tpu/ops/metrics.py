"""Device-side evaluator kernels — metrics at dataset scale.

The host evaluators (evaluation.py) collect both columns to numpy, which
is right for validation folds but not for scoring 100M-row outputs
("AUC sort on host"). These jitted twins keep the
reduction on the accelerator: sorts/cumsums for AUC, a bincount confusion
matrix for multiclass, plain reductions for regression — the evaluators
route here automatically for device-resident or large inputs.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


@jax.jit
def regression_metrics_device(y: jax.Array, p: jax.Array):
    """(rmse, mse, mae, r2) — one fused reduction pass."""
    err = y - p
    mse = jnp.mean(err * err)
    mae = jnp.mean(jnp.abs(err))
    y_mean = jnp.mean(y)
    ss_tot = jnp.sum((y - y_mean) ** 2)
    r2 = jnp.where(ss_tot > 0, 1.0 - jnp.sum(err * err) / ss_tot, 0.0)
    return jnp.sqrt(mse), mse, mae, r2


@partial(jax.jit, static_argnames=("n_classes",))
def confusion_matrix_device(y: jax.Array, p: jax.Array, n_classes: int):
    """(C, C) confusion counts via ONE bincount of the composite label —
    no (n, C) one-hot materialization."""
    comp = y.astype(jnp.int32) * n_classes + p.astype(jnp.int32)
    return jnp.bincount(comp, length=n_classes * n_classes).reshape(
        n_classes, n_classes
    )


def multiclass_metrics_device(y, p, n_classes: int):
    """{accuracy, f1, weightedPrecision, weightedRecall} from the device
    confusion matrix (host math on the tiny (C, C) result)."""
    import numpy as np

    cm = np.asarray(confusion_matrix_device(y, p, n_classes), dtype=np.float64)
    n = cm.sum()
    tp = np.diag(cm)
    per_actual = cm.sum(axis=1)  # rows: true class counts
    per_pred = cm.sum(axis=0)
    weights = per_actual / n
    prec = np.where(per_pred > 0, tp / np.maximum(per_pred, 1), 0.0)
    rec = np.where(per_actual > 0, tp / np.maximum(per_actual, 1), 0.0)
    f1 = np.where(prec + rec > 0, 2 * prec * rec / np.maximum(prec + rec, 1e-300), 0.0)
    return {
        "accuracy": float(tp.sum() / n),
        "f1": float(weights @ f1),
        "weightedPrecision": float(weights @ prec),
        "weightedRecall": float(weights @ rec),
    }


def binary_auc_device(y: jax.Array, s: jax.Array, metric: str = "areaUnderROC"):
    """Tie-grouped AUC (ROC or PR) — ONE variadic sort + cumulative
    scans on the accelerator, the same tie treatment as the host
    evaluator (one curve point per distinct threshold, trapezoid
    through ties).

    Two sort-attack ideas (chosen before the chip and not measured on
    it: ROADMAP.md Design 14; no evaluator cell, Reach 12): (1) instead of ``argsort`` + label/score gathers, sort
    the label ALONG WITH the score key (`lax.sort` with ``num_keys=1``)
    — the n-element random-access gathers disappear and the permutation
    is never materialized; (2) instead of ``nonzero``-packing the
    per-distinct-threshold points (a full-length pack plus two more
    gathers), exploit that tp/fp cumsums are NONDECREASING: a running
    ``cummax`` over the cumsum masked to distinct positions yields the
    previous distinct point's counts in place — every trapezoid reads
    its left edge from a scan, not a gather. A third idea (packing the
    label into the score's mantissa LSB for a single one-operand sort)
    is exactness-rejected there.
    """
    from spark_rapids_ml_tpu.observability import costs

    ledger = costs.active()
    if ledger is not None:
        # Evaluator programs are ledgered like the fit families
        # (tests/test_costs.py holds the entry).
        import time

        lkey = costs.record_fallback(
            _binary_auc_jit,
            name="metrics.binary_auc",
            static={"metric": metric},
            args=(y, s),
            lower=lambda: _binary_auc_jit.lower(y, s, metric=metric),
        )
        t0 = time.perf_counter()
        out = _binary_auc_jit(y, s, metric=metric)
        ledger.note_invocation(lkey, time.perf_counter() - t0, rows=int(s.shape[0]))
        return out
    return _binary_auc_jit(y, s, metric=metric)


@partial(jax.jit, static_argnames=("metric",))
def _binary_auc_jit(y: jax.Array, s: jax.Array, metric: str = "areaUnderROC"):
    n = s.shape[0]
    if jax.config.jax_enable_x64 and s.dtype == jnp.float32:
        # Key-packing attack (predates the chip, not measured on it:
        # ROADMAP.md Design 14): fold
        # the f32 score through the standard monotone bit transform,
        # append the label as bit 0 of a uint64, and run ONE one-operand
        # sort. Tie groups are exact — the full 32 key bits survive, and
        # tie-grouped AUC reads only group-END cumsums, so the in-group
        # label order (which the packing changes) is immaterial. -0.0
        # canonicalizes to +0.0 first so both zeros share one group.
        # (Scores are assumed NaN-free, as in the host evaluator.)
        # (NOT `s + 0.0`: XLA folds that to `s`, resurrecting -0.0.)
        sz = jnp.where(s == 0, jnp.zeros_like(s), s)
        u = jax.lax.bitcast_convert_type(sz, jnp.uint32)
        flip = jnp.where(
            u >> 31 == 1, jnp.uint32(0xFFFFFFFF), jnp.uint32(0x80000000)
        )
        packed = ((u ^ flip).astype(jnp.uint64) << 1) | y.astype(jnp.uint64)
        srt = jax.lax.sort(packed)[::-1]  # descending score order
        is_pos = (srt & 1).astype(jnp.int32)
        key_desc = srt >> 1
        distinct = jnp.concatenate(
            [key_desc[1:] != key_desc[:-1], jnp.array([True])]
        )
    else:
        # Stable sort on the negated score carries the labels along in
        # the SAME order stable argsort(-s) would — bit-identical
        # grouping; the key output doubles as the threshold sequence.
        neg_sorted, y_sorted = jax.lax.sort((-s, y), num_keys=1, is_stable=True)
        s_desc = -neg_sorted
        is_pos = (y_sorted == 1).astype(jnp.int32)
        distinct = jnp.concatenate(
            [s_desc[1:] != s_desc[:-1], jnp.array([True])]
        )
    # Counts in int32: exact to 2^31 rows (f32 cumsums would silently
    # round odd counts past 2^24 — the very scale this path exists for).
    n_pos_i = jnp.sum(is_pos)
    n_pos = n_pos_i.astype(s.dtype)
    n_neg = (n - n_pos_i).astype(s.dtype)
    tp_cum = jnp.cumsum(is_pos)
    # fp = rank - tp: the second cumsum is free arithmetic.
    fp_cum = jnp.arange(1, n + 1, dtype=jnp.int32) - tp_cum
    # Previous distinct point's counts WITHOUT packing/gathering: mask
    # non-distinct slots to -1, cummax carries the latest distinct
    # cumsum forward (cumsums are nondecreasing, so "latest" == "max"),
    # and a one-slot shift turns "latest at <= i" into "latest BEFORE i".
    neg1 = jnp.full((1,), -1, jnp.int32)
    tp_last = jax.lax.cummax(jnp.where(distinct, tp_cum, -1))
    fp_last = jax.lax.cummax(jnp.where(distinct, fp_cum, -1))
    tp_prev = jnp.concatenate([neg1, tp_last[:-1]])
    fp_prev = jnp.concatenate([neg1, fp_last[:-1]])
    has_prev = tp_prev >= 0
    tp_p = jnp.maximum(tp_prev, 0).astype(s.dtype)
    fp_p = jnp.maximum(fp_prev, 0).astype(s.dtype)
    tp_k = tp_cum.astype(s.dtype)
    fp_k = fp_cum.astype(s.dtype)
    if metric == "areaUnderROC":
        xs = fp_k / jnp.maximum(n_neg, 1)
        ys = tp_k / jnp.maximum(n_pos, 1)
        x_prev = fp_p / jnp.maximum(n_neg, 1)
        y_prev = tp_p / jnp.maximum(n_pos, 1)
    else:
        xs = tp_k / jnp.maximum(n_pos, 1)  # recall
        ys = tp_k / jnp.maximum(tp_k + fp_k, 1.0)  # precision
        x_prev = tp_p / jnp.maximum(n_pos, 1)
        # The curve starts at precision 1.0 (Spark's convention).
        y_prev = jnp.where(
            has_prev, tp_p / jnp.maximum(tp_p + fp_p, 1.0), 1.0
        )
    seg = jnp.where(distinct, (xs - x_prev) * (ys + y_prev) / 2.0, 0.0)
    auc = jnp.sum(seg)
    degenerate = jnp.logical_or(n_pos == 0, n_neg == 0)
    return jnp.where(degenerate, 0.0, auc)


__all__ = [
    "regression_metrics_device",
    "confusion_matrix_device",
    "multiclass_metrics_device",
    "binary_auc_device",
]

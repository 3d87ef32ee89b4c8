"""Random-forest kernels — level-order histogram tree growth on the MXU.

Beyond-the-reference capability (the reference ships only PCA — SURVEY.md §2;
the modern RAPIDS Spark-ML line grew RandomForestClassifier/Regressor on
cuML). The CUDA lineage builds trees node-by-node with scatter-heavy
histogram kernels; the TPU-first formulation instead grows ALL trees and ALL
nodes of one depth level simultaneously with dense one-hot matmuls:

  hist[t, node, feature, bin, stat] =
      sum_r onehot_node[t, r, node] * onehot_bin[r, feature*B + bin]
            * weight[t, r] * row_stat[r, stat]

which is one (T*M, rows) x (rows, d*B) GEMM per stat channel per row block —
exactly the shape the systolic array wants. Rows stream through a
``lax.scan`` in fixed-size blocks so memory stays O(block * d * B) and every
shape is static. Split evaluation (prefix sums over bins, impurity, argmax)
is elementwise/reduction work XLA fuses behind the matmuls.

Trees are heap-indexed, static-shape arrays: node ``g`` has children
``2g+1`` / ``2g+2``; a ``max_depth`` forest always allocates
``2^(max_depth+1)-1`` slots. Prediction walks all trees in parallel with a
``fori_loop`` of gathers — no per-row Python, no recursion, no dynamic
shapes.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


class Forest(NamedTuple):
    """Heap-indexed forest arrays; N = 2^(max_depth+1) - 1 nodes per tree.

    ``feature`` is -1 at leaves; traversal is governed by ``is_leaf``. A row
    goes LEFT when ``x[feature] <= threshold``. ``leaf_value`` holds the
    class distribution (classification, S=C) or [mean] (regression, S=1).
    ``node_weight``/``node_gain`` feed featureImportances; ``node_impurity``
    is the node's own impurity (gini/entropy/variance), carried so the
    Spark NodeData on-disk format round-trips losslessly (its
    ``impurity``/``impurityStats`` fields — models/random_forest.py).
    """

    feature: jax.Array  # (T, N) int32
    threshold: jax.Array  # (T, N) float32
    is_leaf: jax.Array  # (T, N) bool
    leaf_value: jax.Array  # (T, N, S_out) float32
    node_weight: jax.Array  # (T, N) float32
    node_gain: jax.Array  # (T, N) float32
    node_impurity: jax.Array  # (T, N) float32


def quantize_features(
    x: jax.Array, max_bins: int, max_sample_rows: int = 262_144
) -> jax.Array:
    """Per-feature quantile bin edges, shape (d, max_bins - 1), ascending.

    Continuous-feature binning as in distributed tree learners: edges are
    the (i+1)/B quantiles of (a row-sample of) each feature. Duplicate edges
    from low-cardinality features simply produce empty bins, which can never
    win a split (zero weight on one side).
    """
    n = x.shape[0]
    if n > max_sample_rows:
        stride = -(-n // max_sample_rows)
        x = x[::stride]
    qs = jnp.arange(1, max_bins, dtype=x.dtype) / max_bins
    return jnp.quantile(x, qs, axis=0).T  # (d, B-1)


@jax.jit
def bin_features(x: jax.Array, edges: jax.Array) -> jax.Array:
    """Map raw features to bin ids: bin = #{edges e : x > e}, in [0, B-1].

    With this convention, "bin <= b" is exactly "x <= edges[b]", so raw
    thresholds for prediction are just the winning bin's upper edge.
    """
    # (n, d, B-1) comparison; blocked over rows to bound the temporary.
    n, d = x.shape
    block = max(1, min(n, 1 << 22) // max(1, d * edges.shape[1]) + 1)
    n_blocks = -(-n // block)
    pad = n_blocks * block - n
    xp = jnp.pad(x, ((0, pad), (0, 0))).reshape(n_blocks, block, d)

    def step(_, xb):
        return None, jnp.sum(xb[:, :, None] > edges[None, :, :], axis=2)

    _, bins = lax.scan(step, None, xp)
    return bins.reshape(-1, d)[:n].astype(jnp.int32)


def _impurity(stats: jax.Array, kind: str) -> Tuple[jax.Array, jax.Array]:
    """(impurity, total_weight) from a stats vector along the last axis.

    Classification stats = per-class weighted counts; regression stats =
    [w, w*y, w*y^2] (weighted variance impurity, as in Spark's Variance).
    """
    if kind in ("gini", "entropy"):
        w = jnp.sum(stats, axis=-1)
        p = stats / jnp.maximum(w, 1e-12)[..., None]
        if kind == "gini":
            imp = 1.0 - jnp.sum(p * p, axis=-1)
        else:
            # log2, matching Spark ML's Entropy — keeps minInfoGain
            # thresholds comparable across frameworks.
            imp = -jnp.sum(jnp.where(p > 0, p * jnp.log2(p), 0.0), axis=-1)
        return jnp.where(w > 0, imp, 0.0), w
    if kind == "variance":
        w = stats[..., 0]
        mean = stats[..., 1] / jnp.maximum(w, 1e-12)
        var = stats[..., 2] / jnp.maximum(w, 1e-12) - mean * mean
        return jnp.where(w > 0, jnp.maximum(var, 0.0), 0.0), w
    raise ValueError(f"unknown impurity {kind!r}")


def _level_histogram(
    node_idx: jax.Array,  # (T, n) global heap ids, -1 = inactive
    weights: jax.Array,  # (T, n)
    x_binned: jax.Array,  # (n, d)
    row_stats: jax.Array,  # (n, S)
    offset: int,
    n_nodes: int,
    n_bins: int,
    block_rows: int,
    prec=lax.Precision.HIGHEST,
) -> jax.Array:
    """(T, n_nodes, d, n_bins, S) histogram via blocked one-hot GEMMs."""
    T, n = node_idx.shape
    d = x_binned.shape[1]
    S = row_stats.shape[1]
    block = min(block_rows, n)
    n_blocks = -(-n // block)
    pad = n_blocks * block - n

    ni = jnp.pad(node_idx, ((0, 0), (0, pad)), constant_values=-1)
    w = jnp.pad(weights, ((0, 0), (0, pad)))
    xb = jnp.pad(x_binned, ((0, pad), (0, 0)))
    rs = jnp.pad(row_stats, ((0, pad), (0, 0)))

    ni = ni.reshape(T, n_blocks, block).transpose(1, 0, 2)  # (nb, T, bs)
    w = w.reshape(T, n_blocks, block).transpose(1, 0, 2)
    xb = xb.reshape(n_blocks, block, d)
    rs = rs.reshape(n_blocks, block, S)

    def step(hist, blk):
        ni_b, w_b, xb_b, rs_b = blk
        local = ni_b - offset
        in_level = (local >= 0) & (local < n_nodes)
        node_oh = (
            (local[:, :, None] == jnp.arange(n_nodes, dtype=jnp.int32))
            & in_level[:, :, None]
        ).astype(jnp.float32)  # (T, bs, M)
        bin_oh = (
            xb_b[:, :, None] == jnp.arange(n_bins, dtype=jnp.int32)
        ).astype(jnp.float32).reshape(block, d * n_bins)  # (bs, d*B)
        per_s = []
        for s in range(S):
            coef = w_b * rs_b[None, :, s]  # (T, bs)
            a = node_oh * coef[:, :, None]  # (T, bs, M)
            per_s.append(
                jnp.einsum("tbm,bq->tmq", a, bin_oh, precision=prec)
            )
        return hist + jnp.stack(per_s, axis=-1), None

    init = jnp.zeros((T, n_nodes, d * n_bins, S), dtype=jnp.float32)
    hist, _ = lax.scan(step, init, (ni, w, xb, rs))
    return hist.reshape(T, n_nodes, d, n_bins, S)


def _node_totals(
    node_idx: jax.Array,
    weights: jax.Array,
    row_stats: jax.Array,
    offset: int,
    n_nodes: int,
    block_rows: int,
    prec=lax.Precision.HIGHEST,
) -> jax.Array:
    """(T, n_nodes, S) per-node stat totals (no feature/bin split)."""
    T, n = node_idx.shape
    S = row_stats.shape[1]
    block = min(block_rows, n)
    n_blocks = -(-n // block)
    pad = n_blocks * block - n
    ni = jnp.pad(node_idx, ((0, 0), (0, pad)), constant_values=-1)
    w = jnp.pad(weights, ((0, 0), (0, pad)))
    rs = jnp.pad(row_stats, ((0, pad), (0, 0)))
    ni = ni.reshape(T, n_blocks, block).transpose(1, 0, 2)
    w = w.reshape(T, n_blocks, block).transpose(1, 0, 2)
    rs = rs.reshape(n_blocks, block, S)

    def step(tot, blk):
        ni_b, w_b, rs_b = blk
        local = ni_b - offset
        in_level = (local >= 0) & (local < n_nodes)
        node_oh = (
            (local[:, :, None] == jnp.arange(n_nodes, dtype=jnp.int32))
            & in_level[:, :, None]
        ).astype(jnp.float32) * w_b[:, :, None]
        return tot + jnp.einsum("tbm,bs->tms", node_oh, rs_b, precision=prec), None

    init = jnp.zeros((T, n_nodes, S), dtype=jnp.float32)
    tot, _ = lax.scan(step, init, (ni, w, rs))
    return tot


@partial(
    jax.jit,
    # `level` stays traced: fold_in takes a traced int, and keeping it out
    # of the program key avoids a per-level retrace on top of the
    # shape-driven one (tpuml-lint: jax-static-loop-arg).
    static_argnames=(
        "impurity", "feat_subset", "min_instances", "min_info_gain"
    ),
)
def split_level(
    hist: jax.Array,  # (T, M, d, B, S) level histogram (already merged)
    key: jax.Array,
    level: int,
    *,
    impurity: str,
    feat_subset: int,
    min_instances: int = 1,
    min_info_gain: float = 0.0,
):
    """Split decision for one tree level from its merged histogram — THE
    single home of split selection: :func:`grow_forest` calls it on
    device-local (psum-merged) histograms, and the pyspark adapter's
    distributed fit calls it on driver-merged executor partials
    (spark/adapter.py), so both deployments decide splits with literally
    the same math (the treeAggregate-then-driver-decide structure of
    RapidsRowMatrix.scala:207-233, applied to trees).

    Returns ``(best_f, best_b, best_gain, split_ok, total, w_parent)``
    with shapes (T, M) / (T, M, S) for total.
    """
    T, m_nodes, d, n_bins, _ = hist.shape
    min_w = float(min_instances)
    left = jnp.cumsum(hist, axis=3)
    total = left[:, :, 0, -1, :]  # (T, M, S): same for every feature
    right = total[:, :, None, None, :] - left
    imp_parent, w_parent = _impurity(total, impurity)  # (T, M)
    imp_l, w_l = _impurity(left, impurity)  # (T, M, d, B)
    imp_r, w_r = _impurity(right, impurity)
    gain = imp_parent[:, :, None, None] - (
        w_l * imp_l + w_r * imp_r
    ) / jnp.maximum(w_parent, 1e-12)[:, :, None, None]

    # Per-node random feature subset: exactly feat_subset features, at
    # zero extra histogram cost (all features were counted anyway).
    if feat_subset < d:
        u = jax.random.uniform(jax.random.fold_in(key, level), (T, m_nodes, d))
        kth = lax.top_k(u, feat_subset)[0][..., -1:]
        f_mask = u >= kth
    else:
        f_mask = jnp.ones((T, m_nodes, d), dtype=bool)

    valid = (
        (w_l >= min_w)
        & (w_r >= min_w)
        & (jnp.arange(n_bins) < n_bins - 1)[None, None, None, :]
        & f_mask[:, :, :, None]
    )
    gain = jnp.where(valid, gain, -jnp.inf)
    flat = gain.reshape(T, m_nodes, d * n_bins)
    best = jnp.argmax(flat, axis=2)
    best_gain = jnp.take_along_axis(flat, best[..., None], axis=2)[..., 0]
    best_f = (best // n_bins).astype(jnp.int32)
    best_b = (best % n_bins).astype(jnp.int32)
    split_ok = (
        (best_gain > 0)
        & (best_gain >= min_info_gain)
        & (w_parent > 0)
    )
    return best_f, best_b, best_gain, split_ok, total, w_parent


def _select_feature(x: jax.Array, f_r: jax.Array) -> jax.Array:
    """out[t, r] = x[r, f_r[t, r]] without a 2-D gather.

    TPU lowers the gather to a scalar loop (~7x slower than this even at
    d = 28); an unrolled where-select streams x once per feature, which the
    fusion turns into d vectorized passes. Falls back to the gather above
    ~256 features, where d passes over (T, n) would cost more.
    """
    d = x.shape[1]
    if d > 256:
        rows = jnp.arange(x.shape[0])
        return jax.vmap(lambda fr: x[rows, fr])(f_r)
    out = jnp.zeros(f_r.shape, x.dtype)
    for f in range(d):
        out = jnp.where(f_r == f, x[:, f][None, :], out)
    return out


def _leaf_prediction(stats: jax.Array, kind: str) -> jax.Array:
    """Per-node prediction from stats: class distribution or [mean]."""
    if kind in ("gini", "entropy"):
        w = jnp.sum(stats, axis=-1, keepdims=True)
        n_cls = stats.shape[-1]
        return jnp.where(w > 0, stats / jnp.maximum(w, 1e-12), 1.0 / n_cls)
    w = stats[..., 0]
    mean = stats[..., 1] / jnp.maximum(w, 1e-12)
    return jnp.where(w > 0, mean, 0.0)[..., None]


@partial(
    jax.jit,
    static_argnames=(
        "max_depth",
        "n_bins",
        "impurity",
        "feat_subset",
        "min_instances",
        "min_info_gain",
        "block_rows",
        "axis_name",
        "exact_counts",
    ),
)
def grow_forest(
    x_binned: jax.Array,  # (n, d) int32
    row_stats: jax.Array,  # (n, S) float32
    weights: jax.Array,  # (T, n) float32 per-tree sample weights
    edges: jax.Array,  # (d, n_bins - 1) float32
    key: jax.Array,
    *,
    max_depth: int,
    n_bins: int,
    impurity: str,
    feat_subset: int,
    min_instances: int = 1,
    min_info_gain: float = 0.0,
    block_rows: int = 4096,
    axis_name: str | None = None,
    exact_counts: bool = True,
) -> Forest:
    """Grow T trees level-synchronously; all shapes static, one XLA program.

    The depth loop is unrolled (max_depth is static and small); each level
    does one blocked-GEMM histogram pass over the data, a fused split
    search, and a gather-based row re-routing — the level-order analogue of
    cuML's node-batched builder, with the MXU doing the counting.

    Distributed mode (``axis_name`` set, under ``shard_map``): rows are
    sharded over the named mesh axis; each device builds its shard's partial
    histogram and one ``psum`` per level merges them over ICI — the Spark
    ``treeAggregate`` of the reference (RapidsRowMatrix.scala:207-233)
    becomes an XLA collective. Split selection then runs identically
    (replicated) on every device, so routing needs no further traffic.
    """
    T, n = weights.shape
    d = x_binned.shape[1]
    S = row_stats.shape[1]
    n_total = 2 ** (max_depth + 1) - 1
    s_out = S if impurity in ("gini", "entropy") else 1
    # Classification histogram entries are small-integer counts (one-hot x
    # Poisson weights <= ~hundreds): EXACT even under one-pass bf16
    # multiplies with fp32 accumulation, so the 6-pass HIGHEST route would
    # buy nothing. Regression stats carry real-valued label channels that
    # bf16 would round at 8 mantissa bits — keep those at HIGHEST. The same
    # rounding hazard applies to classification when a fractional weightCol
    # has been multiplied into row_stats (~2^-9 relative error can flip
    # near-tie splits), so the caller clears ``exact_counts`` in that case.
    hist_prec = (
        lax.Precision.DEFAULT
        if impurity in ("gini", "entropy") and exact_counts
        else lax.Precision.HIGHEST
    )

    feature = jnp.full((T, n_total), -1, dtype=jnp.int32)
    threshold = jnp.zeros((T, n_total), dtype=jnp.float32)
    is_leaf = jnp.zeros((T, n_total), dtype=bool)
    leaf_value = jnp.zeros((T, n_total, s_out), dtype=jnp.float32)
    node_weight = jnp.zeros((T, n_total), dtype=jnp.float32)
    node_gain = jnp.zeros((T, n_total), dtype=jnp.float32)
    node_imp = jnp.zeros((T, n_total), dtype=jnp.float32)

    node_idx = jnp.zeros((T, n), dtype=jnp.int32)  # all rows at the root

    for level in range(max_depth):
        offset = 2**level - 1
        m_nodes = 2**level
        hist = _level_histogram(
            node_idx, weights, x_binned, row_stats, offset, m_nodes, n_bins,
            block_rows, hist_prec,
        )  # (T, M, d, B, S)
        if axis_name is not None:
            hist = lax.psum(hist, axis_name)
        best_f, best_b, best_gain, split_ok, total, w_parent = split_level(
            hist, key, level,
            impurity=impurity, feat_subset=feat_subset,
            min_instances=min_instances, min_info_gain=min_info_gain,
        )

        sl = slice(offset, offset + m_nodes)
        feature = feature.at[:, sl].set(jnp.where(split_ok, best_f, -1))
        threshold = threshold.at[:, sl].set(
            jnp.where(split_ok, edges[best_f, best_b], 0.0)
        )
        is_leaf = is_leaf.at[:, sl].set(~split_ok)
        leaf_value = leaf_value.at[:, sl, :].set(
            _leaf_prediction(total, impurity)
        )
        node_weight = node_weight.at[:, sl].set(w_parent)
        node_gain = node_gain.at[:, sl].set(
            jnp.where(split_ok, best_gain, 0.0)
        )
        node_imp = node_imp.at[:, sl].set(_impurity(total, impurity)[0])

        # Route rows: leaf rows retire (-1); split rows descend. TPU gathers
        # are scalarized and slow (~0.5 s per (T, n) take_along_axis at 2M
        # rows), so the three per-node lookups are PACKED into one int32
        # table gather, and the per-row feature-value lookup becomes an
        # unrolled select over the (static, small) feature axis.
        local = node_idx - offset
        active = (local >= 0) & (local < m_nodes)
        lc = jnp.clip(local, 0, m_nodes - 1)
        packed = best_f * (2 * n_bins) + best_b * 2 + split_ok.astype(jnp.int32)
        packed_r = jnp.take_along_axis(packed, lc, axis=1)  # (T, n): ONE gather
        f_r = packed_r // (2 * n_bins)
        b_r = (packed_r % (2 * n_bins)) // 2
        ok_r = (packed_r % 2) == 1
        xb_r = _select_feature(x_binned, f_r)  # (T, n)
        child = 2 * node_idx + 1 + (xb_r > b_r)
        node_idx = jnp.where(active & ok_r, child, jnp.where(active, -1, node_idx))

    # Bottom level: every surviving node is a leaf.
    offset = 2**max_depth - 1
    m_nodes = 2**max_depth
    total = _node_totals(
        node_idx, weights, row_stats, offset, m_nodes, block_rows, hist_prec
    )
    if axis_name is not None:
        total = lax.psum(total, axis_name)
    sl = slice(offset, offset + m_nodes)
    is_leaf = is_leaf.at[:, sl].set(True)
    leaf_value = leaf_value.at[:, sl, :].set(_leaf_prediction(total, impurity))
    imp_bottom, w_bottom = _impurity(total, impurity)
    node_weight = node_weight.at[:, sl].set(w_bottom)
    node_imp = node_imp.at[:, sl].set(imp_bottom)

    return Forest(
        feature, threshold, is_leaf, leaf_value, node_weight, node_gain, node_imp
    )


@partial(
    jax.jit,
    static_argnames=(
        "max_depth",
        "n_bins",
        "impurity",
        "feat_subset",
        "min_instances",
        "min_info_gain",
        "block_rows",
        "exact_counts",
        "max_sample_rows",
    ),
)
def fit_forest_fused(
    x: jax.Array,  # (n, d) float32 RAW features
    row_stats: jax.Array,  # (n, S) float32
    weights: jax.Array,  # (T, n) float32 per-tree sample weights
    key: jax.Array,
    *,
    max_depth: int,
    n_bins: int,
    impurity: str,
    feat_subset: int,
    min_instances: int = 1,
    min_info_gain: float = 0.0,
    block_rows: int = 4096,
    exact_counts: bool = True,
    max_sample_rows: int = 262_144,
) -> Forest:
    """Whole-fit program: quantile edges + binning + level-order growth in
    ONE XLA executable.

    The estimator once ran at 38% of its own kernel's rate
    because quantize/bin/one-hot prep lived outside the jitted growth —
    each a separate host dispatch, with the quantile
    sort and binning pass unfused from the histogram scan that re-reads
    the same rows. Compiling the full pipeline as one program removes the
    dispatch gaps and lets XLA schedule the prep against the first level's
    histogram GEMMs. Semantics are identical to quantize_features +
    bin_features + grow_forest called in sequence (same ops, one program).
    """
    edges = quantize_features(x, n_bins, max_sample_rows)
    xb = bin_features(x, edges)
    return grow_forest(
        xb,
        row_stats,
        weights,
        edges.astype(jnp.float32),
        key,
        max_depth=max_depth,
        n_bins=n_bins,
        impurity=impurity,
        feat_subset=feat_subset,
        min_instances=min_instances,
        min_info_gain=min_info_gain,
        block_rows=block_rows,
        exact_counts=exact_counts,
    )


def grow_forest_sharded(
    mesh,
    x_binned: jax.Array,
    row_stats: jax.Array,
    weights: jax.Array,
    edges: jax.Array,
    key: jax.Array,
    **kwargs,
) -> Forest:
    """Mesh path: rows sharded over the data axis, per-shard partial
    histograms merged with one ``psum`` per level (see :func:`grow_forest`).

    Inputs are HOST arrays; rows are padded to a multiple of the data-axis
    size with zero weight (padded rows contribute nothing to any histogram).
    The returned forest is replicated — identical on every device.
    """
    from spark_rapids_ml_tpu.utils.compat import shard_map
    from jax.sharding import PartitionSpec as P

    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS

    n = x_binned.shape[0]
    dp = mesh.shape[DATA_AXIS]
    pad = (-n) % dp
    if pad:
        x_binned = jnp.concatenate(
            [x_binned, jnp.zeros((pad, x_binned.shape[1]), x_binned.dtype)]
        )
        row_stats = jnp.concatenate(
            [row_stats, jnp.zeros((pad, row_stats.shape[1]), row_stats.dtype)]
        )
        weights = jnp.concatenate(
            [weights, jnp.zeros((weights.shape[0], pad), weights.dtype)], axis=1
        )

    def local(xb, rs, w, e, k):
        return grow_forest(xb, rs, w, e, k, axis_name=DATA_AXIS, **kwargs)

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(None, DATA_AXIS), P(), P()),
        out_specs=Forest(P(), P(), P(), P(), P(), P(), P()),
        # psum'd histograms make every split decision replicated; the vma
        # checker cannot see that, so skip the static check (as in ops.knn).
        check_vma=False,
    )
    return fn(x_binned, row_stats, weights, edges, key)


@partial(jax.jit, static_argnames=("max_depth",))
def forest_apply(
    x: jax.Array, forest: Forest, max_depth: int
) -> jax.Array:
    """Leaf index per (tree, row): parallel root-to-leaf walk, (T, n) int32.

    Per step: feature id and leaf flag ride ONE packed int gather (TPU
    gathers are scalarized — see the routing note in :func:`grow_forest`),
    the threshold a second; the feature value is an unrolled select.
    """
    idx = jnp.zeros((forest.feature.shape[0], x.shape[0]), dtype=jnp.int32)
    packed = jnp.maximum(forest.feature, 0) * 2 + forest.is_leaf.astype(jnp.int32)

    def body(_, idx):
        p = jnp.take_along_axis(packed, idx, axis=1)
        f = p // 2
        leaf = (p % 2) == 1
        thr = jnp.take_along_axis(forest.threshold, idx, axis=1)
        xv = _select_feature(x, f)
        child = 2 * idx + 1 + (xv > thr)
        return jnp.where(leaf, idx, child.astype(jnp.int32))

    return lax.fori_loop(0, max_depth, body, idx)


@partial(jax.jit, static_argnames=("max_depth",))
def forest_predict_proba(x: jax.Array, forest: Forest, max_depth: int) -> jax.Array:
    """(n, C) mean of per-tree leaf class distributions.

    Gathered one class at a time: a (T, n, C) take_along_axis would tile-pad
    the tiny class axis to the 128-lane register width on TPU (a 64x memory
    blowup at C=2 — 20 GB at 2M rows x 20 trees).
    """
    idx = forest_apply(x, forest, max_depth)  # (T, n)
    n_classes = forest.leaf_value.shape[2]
    per_class = [
        jnp.mean(jnp.take_along_axis(forest.leaf_value[:, :, c], idx, axis=1), axis=0)
        for c in range(n_classes)
    ]
    return jnp.stack(per_class, axis=1)


@partial(jax.jit, static_argnames=("max_depth",))
def forest_predict_reg(x: jax.Array, forest: Forest, max_depth: int) -> jax.Array:
    """(n,) mean of per-tree leaf means."""
    idx = forest_apply(x, forest, max_depth)
    lv = jnp.take_along_axis(forest.leaf_value[:, :, 0], idx, axis=1)  # (T, n)
    return jnp.mean(lv, axis=0)


def sample_weights(
    key: jax.Array, n_trees: int, n_rows: int, subsampling_rate: float, bootstrap: bool
) -> jax.Array:
    """Per-tree row weights: Poisson(rate) with replacement (the standard
    distributed approximation of bootstrap resampling), Bernoulli(rate)
    without.

    Poisson draws clamp at 256 — the bf16-exactness bound of the one-pass
    histogram (ops.trees.grow_forest precision note). A clamp at 256 is
    semantically invisible (P[Poisson(rate <= 1) > 256] ~ 1e-600: no draw
    ever reaches it) but makes the unweighted classification histogram's
    exactness a STATIC fact — one-hot stats x integer weights <= 256 are
    exact bf16 products — so the fit no longer pays a device readback to
    verify it (each readback is a full host round trip that stalls the
    async dispatch stream)."""
    if bootstrap:
        w = jax.random.poisson(key, subsampling_rate, (n_trees, n_rows))
        return jnp.minimum(w, 256).astype(jnp.float32)
    return jax.random.bernoulli(key, subsampling_rate, (n_trees, n_rows)).astype(
        jnp.float32
    )


def feature_importances(forest: Forest, n_features: int) -> np.ndarray:
    """Impurity-based importances, Spark-style: per tree, each split
    contributes gain * node_weight to its feature; per-tree vectors are
    normalized, averaged over trees, then renormalized to sum to 1."""
    feat = np.asarray(forest.feature)  # (T, N)
    gain = np.asarray(forest.node_gain)
    w = np.asarray(forest.node_weight)
    T = feat.shape[0]
    per_tree = np.zeros((T, n_features))
    contrib = gain * w
    for t in range(T):
        split = feat[t] >= 0
        np.add.at(per_tree[t], feat[t][split], contrib[t][split])
    sums = per_tree.sum(axis=1, keepdims=True)
    per_tree = np.divide(per_tree, sums, out=np.zeros_like(per_tree), where=sums > 0)
    avg = per_tree.mean(axis=0)
    s = avg.sum()
    return avg / s if s > 0 else avg

"""Random-forest kernels — level-order tree growth whose memory and work do
not grow with the number of nodes.

Beyond-the-reference capability (the reference ships only PCA — SURVEY.md §2;
the modern RAPIDS Spark-ML line grew RandomForestClassifier/Regressor on
cuML). Every row lies in ONE node of a tree and every node looks at K of the
d features (``featureSubsetStrategy``), so a tree-level REQUIRES n * K
selected bin ids and m * K * B * S histogram cells. The builder
(:func:`grow_forest`, the section "the level builder" below) does that much
and no more in order of magnitude:

  - bin ids are packed four to an int32 word (two past 256 bins), so a row
    is one contiguous run that a row gather moves whole;
  - per level the rows are sorted by node and cut into tiles of one node's
    rows; a tile's K features are SELECTED from the packed words one byte
    plane at a time, by exact bf16 passes against the plane's columns of the
    node's one-hot (no row is unpacked to d digits), and COUNTED by one pass
    of the weights against the one-hot of the selected bins: the histogram is
    ``(trees in flight, nodes, S, K, B)``, never ``nodes * d * B``;
  - the split search, routing (out of the K already selected) and one sort a
    level finish it; no element gather or scatter per row (the chip does 83 M
    of those a second: 53 s a fit at upstream's benchmark shape, PERF.md).

An earlier builder (until PR 34) counted ALL d features of all nodes with a
dense one-hot GEMM into ``(T, M, d * B, S)``: fine at 28 columns, 164 GB a
level at 3000 columns and depth 13. :func:`split_level` keeps that layout for
the pyspark adapter, whose executors ship dense partial histograms.

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


QUANTILE_SAMPLE_ROWS = 262_144  # rows the quantile edges are taken from at most


def quantize_features(
    x: jax.Array, max_bins: int, max_sample_rows: int = QUANTILE_SAMPLE_ROWS
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


BIN_BLOCK_ROWS = 2048  # rows binned at a time: the block's comparisons are one fused pass


@partial(jax.jit, static_argnames=("n_bins",))
def bin_features(x: jax.Array, edges: jax.Array, n_bins: int) -> jax.Array:
    """Map raw features to bin ids, PACKED: ``(n, packed_width(d, n_bins))``
    int32 words of four uint8 ids (two int16 past 256 bins); word ``w`` holds
    features ``w, W + w, 2W + w, ...`` low byte first (:func:`unpack_bins`).

    bin = #{edges e : x > e}, in [0, B-1]. With this convention, "bin <= b"
    is exactly "x <= edges[b]", so raw thresholds for prediction are just the
    winning bin's upper edge.
    """
    n, d = x.shape
    per_word = bins_per_word(n_bins)
    width = packed_width(d, n_bins)
    edges_t = jnp.pad(edges.T, ((0, 0), (0, per_word * width - d)), constant_values=jnp.inf)
    block = min(n, BIN_BLOCK_ROWS)

    def pack(xb):  # (block, d) rows -> (block, W) words
        xb = jnp.pad(xb, ((0, 0), (0, per_word * width - d)))
        ids = jnp.sum(xb[None, :, :] > edges_t[:, None, :], axis=0, dtype=jnp.int32)
        words = ids[:, :width]
        for p in range(1, per_word):
            words = words | (ids[:, p * width : (p + 1) * width] << (p * (32 // per_word)))
        return words

    def step(i, out):
        # the last block is moved back to end at the last row (a block is
        # never past the end, and a row binned twice reads the same)
        at = jnp.minimum(i * block, n - block)
        xb = lax.dynamic_slice_in_dim(x, at, block, axis=0)
        return lax.dynamic_update_slice_in_dim(out, pack(xb), at, axis=0)

    return lax.fori_loop(0, -(-n // block), step, jnp.zeros((n, width), jnp.int32))


@partial(jax.jit, static_argnames=("n_bins", "max_sample_rows"))
def quantize_and_bin(x: jax.Array, n_bins: int, max_sample_rows: int = QUANTILE_SAMPLE_ROWS):
    """``(edges (d, n_bins - 1) float32, packed bin ids)``: the fit's ONE
    preparation program (quantile edges + binning; the sort behind the
    quantiles is its temporary, as large as the sampled rows)."""
    edges = quantize_features(x, n_bins, max_sample_rows).astype(jnp.float32)
    return edges, bin_features(x, edges, n_bins)


def unpack_bins(words, d: int, n_bins: int) -> np.ndarray:
    """The (n, d) bin ids of :func:`bin_features`' words, on the host."""
    words = np.asarray(words).astype(np.uint32)
    per_word = bins_per_word(n_bins)
    bits = 32 // per_word
    parts = [(words >> (p * bits)) & ((1 << bits) - 1) for p in range(per_word)]
    return np.concatenate(parts, axis=1)[:, :d].astype(np.int32)


def _impurity(stats: jax.Array, kind: str, axis: int = -1) -> Tuple[jax.Array, jax.Array]:
    """(impurity, total_weight) from a stats vector along ``axis``.

    Classification stats = per-class weighted counts; regression stats =
    [w, w*y, w*y^2] (weighted variance impurity, as in Spark's Variance).
    """
    if kind in ("gini", "entropy"):
        w = jnp.sum(stats, axis=axis)
        p = stats / jnp.expand_dims(jnp.maximum(w, 1e-12), axis)
        if kind == "gini":
            imp = 1.0 - jnp.sum(p * p, axis=axis)
        else:
            # log2, matching Spark ML's Entropy — keeps minInfoGain
            # thresholds comparable across frameworks.
            imp = -jnp.sum(jnp.where(p > 0, p * jnp.log2(p), 0.0), axis=axis)
        return jnp.where(w > 0, imp, 0.0), w
    if kind == "variance":
        w, wy, wyy = (jnp.take(stats, i, axis=axis) for i in range(3))
        mean = wy / jnp.maximum(w, 1e-12)
        var = wyy / jnp.maximum(w, 1e-12) - mean * mean
        return jnp.where(w > 0, jnp.maximum(var, 0.0), 0.0), w
    raise ValueError(f"unknown impurity {kind!r}")


def _candidate_gains(left, total, *, impurity: str, min_instances: int, axis: int):
    """THE gain arithmetic of a split decision, shared by :func:`split_level`
    (the adapter's dense histograms) and the level builder's search over the
    selected features: ``(gain, w_parent)`` of every candidate from ``left``,
    the stats cumulated along the bin axis (the last axis once the stat
    channels on ``axis`` are reduced), and ``total``, the node's stats with
    ``left``'s rank (size 1 where it has features and bins). A candidate
    without ``min_instances`` of weight on both sides, and the last bin, read
    ``-inf``."""
    right = total - left
    imp_parent, w_parent = _impurity(total, impurity, axis)
    imp_l, w_l = _impurity(left, impurity, axis)
    imp_r, w_r = _impurity(right, impurity, axis)
    gain = imp_parent - (w_l * imp_l + w_r * imp_r) / jnp.maximum(w_parent, 1e-12)
    n_bins = gain.shape[-1]
    min_w = float(min_instances)
    valid = (w_l >= min_w) & (w_r >= min_w) & (jnp.arange(n_bins) < n_bins - 1)
    return jnp.where(valid, gain, -jnp.inf), w_parent


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
    """Split decision for one tree level from its merged DENSE histogram
    (all d features of every node): the pyspark adapter's distributed fit
    calls it on driver-merged executor partials (spark/adapter.py; the
    treeAggregate-then-driver-decide structure of
    RapidsRowMatrix.scala:207-233, applied to trees). The gain arithmetic
    is :func:`_candidate_gains`, which the level builder's search over the
    selected features (:func:`_search_nodes`) shares: both deployments
    decide splits with literally the same math.

    Returns ``(best_f, best_b, best_gain, split_ok, total, w_parent)``
    with shapes (T, M) / (T, M, S) for total.
    """
    T, m_nodes, d, n_bins, _ = hist.shape
    left = jnp.cumsum(hist, axis=3)
    total = left[:, :, 0, -1, :]  # (T, M, S): same for every feature
    gain, w_parent = _candidate_gains(
        left, total[:, :, None, None, :], impurity=impurity,
        min_instances=min_instances, axis=-1,
    )
    w_parent = w_parent[:, :, 0, 0]

    # Per-node random feature subset: exactly feat_subset features, at
    # zero extra histogram cost (all features were counted anyway).
    if feat_subset < d:
        u = jax.random.uniform(jax.random.fold_in(key, level), (T, m_nodes, d))
        kth = lax.top_k(u, feat_subset)[0][..., -1:]
        gain = jnp.where((u >= kth)[:, :, :, None], gain, -jnp.inf)
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


# --- the level builder ---------------------------------------------------
#
# One tree-level, for one tree (the trees of a batch are vmapped over it):
#
#   1. the rows lie sorted by node (``key`` ascending, ``perm`` the row ids in
#      that order; rows whose node became a leaf carry ``_RETIRED`` and sort
#      last). Each node's run of rows is cut into tiles of ``tile_rows`` rows
#      that belong to ONE node: ``ceil(n / tile_rows) + m_pad`` tiles always,
#      so every shape is static whatever the tree looks like.
#   2. per step of ``tiles_per_step`` tiles: the tiles' rows of the packed
#      bin matrix are gathered whole (a row is contiguous: a slice gather, not
#      the scalar loop of an element gather), the node's K chosen features are
#      SELECTED byte plane by byte plane (``_select_bins``: a bf16 pass a
#      plane and digit against the plane's columns of the node's one-hot; bin
#      ids are under 256 a digit, so every pass is exact), and the tile's
#      ``(S, K, B)`` histogram is ``weights^T @ onehot(bins)`` on the MXU,
#      added to its node's.
#   3. the split search (``_search_nodes``) over ``(m_pad, S, K, B)`` cells.
#   4. routing reads the winning feature's bin out of the K selected (kept per
#      tile slot), and one sort by the child's id restores (1).
#
# Nothing here has ``m * d * B`` elements, and none of the row work is times m.

TILE_ROWS = 128  # rows of one node a tile holds: the MXU's contraction width
# tiles a step of a level works on, over all the trees of the batch: more of
# them and a step's one-hots leave the chip's fast memory (13 trees at 32 tiles
# each: 12.9 s a fit's growth; at 2 each: 6.8 s; my chip runs, PR 34)
STEP_TILES = 32
MIN_LEVEL_WIDTH = 256  # levels of up to this many nodes share one padded program
SEARCH_NODES = 256  # nodes whose split search is in flight at once
_RETIRED = np.int32(1 << 30)


def _round_up(value: int, multiple: int) -> int:
    return -(-value // multiple) * multiple


def bins_per_word(n_bins: int) -> int:
    """Bin ids a packed int32 word holds: four uint8, or two int16 past 256
    bins (the storage is that of a uint8 / int16 matrix; words make a row a
    run of 32-bit lanes, which is what a row gather moves)."""
    if n_bins > 1 << 16:
        raise ValueError(f"maxBins must be <= 65536, got {n_bins}")
    return 4 if n_bins <= 256 else 2


def packed_width(d: int, n_bins: int) -> int:
    return -(-d // bins_per_word(n_bins))


def _select_bins(words: jax.Array, feats: jax.Array, n_bins: int, operand_dtype) -> jax.Array:
    """The bin ids ``(C, K, R)`` int32 of features ``feats (C, K)`` in the
    packed rows ``words (C, R, W)``, one byte plane at a time.

    Word ``w`` holds features ``w, W + w, 2W + w, ...`` (:func:`bin_features`),
    so digit ``q`` of byte plane ``p`` is digit ``q`` of features
    ``p W ... p W + W - 1``: each (plane, digit) is one pass against that
    plane's columns of the node's one-hot. Digits are under 256 and exactly
    one plane holds a feature, so every pass and their float32 sum are exact;
    no row is unpacked to ``per_word * W`` digits (a feature past d is never
    in ``feats``).
    """
    per_word = bins_per_word(n_bins)
    n_digits = 4 // per_word
    width = words.shape[-1]
    iota_w = jnp.arange(width, dtype=jnp.int32)
    picked = [0.0] * n_digits
    for p in range(per_word):
        onehot = (feats[:, :, None] == p * width + iota_w).astype(operand_dtype)  # (C, K, W)
        for q in range(n_digits):
            digit = (words >> (8 * (p * n_digits + q))) & 0xFF  # (C, R, W)
            picked[q] = picked[q] + jnp.einsum(
                "crw,ckw->ckr", digit.astype(jnp.float32).astype(operand_dtype), onehot,
                preferred_element_type=jnp.float32,
            )
    return sum(picked[q].astype(jnp.int32) * 256**q for q in range(n_digits))


def level_groups(max_depth: int, min_width: int = MIN_LEVEL_WIDTH) -> list:
    """[(first_level, end_level, m_pad)]: consecutive levels that run one
    program with the node axis padded to ``m_pad`` (``min_width``, then powers
    of four, never over the widest level's 2^(max_depth-1))."""
    groups: list = []
    for level in range(max_depth):
        width = max(int(min_width), 1)
        while width < 2**level:
            width *= 4
        width = min(width, 2 ** (max_depth - 1))
        if groups and groups[-1][2] == width:
            groups[-1] = (groups[-1][0], level + 1, width)
        else:
            groups.append((level, level + 1, width))
    return groups


def step_tiles_a_tree(trees: int) -> int:
    """Tiles of ONE tree a step takes, from the trees in flight alone."""
    return max(1, STEP_TILES // max(1, trees))


def level_tiles(n: int, m_pad: int, tile_rows: int, tiles_per_step: int) -> int:
    """Tiles of a level program: enough for any tree, a whole number of steps."""
    return _round_up(-(-n // tile_rows) + m_pad, tiles_per_step)


def select_passes(n: int, n_bins: int, max_depth: int, trees: int) -> int:
    """Selection products one batch of ``trees`` issues, from shapes alone:
    planes x digits (:func:`_select_bins`) a step, vmapped over the batch's
    trees, times the steps of every level (``grow_forest``'s defaults)."""
    per_word = bins_per_word(n_bins)
    tiles = step_tiles_a_tree(trees)
    steps = sum(
        (end - first) * level_tiles(n, m_pad, TILE_ROWS, tiles) // tiles
        for first, end, m_pad in level_groups(max_depth)
    )
    return per_word * (4 // per_word) * steps


def builder_bytes(
    n: int, d: int, n_bins: int, feat_subset: int, n_stats: int, max_depth: int, *,
    rows_resident: bool = True,
) -> Tuple[int, int, int]:
    """``(resident, per_tree, prepare)`` device bytes of a fit, from shapes
    alone: what ``models/random_forest.py`` hands
    ``membudget.batch_within_budget``.

    ``resident``: the packed bins, and the float32 rows where they are not on
    the device yet. ``prepare``: the quantile sort's temporary (gone before
    growth starts). ``per_tree``: one tree in flight at the widest
    level: its selected histogram ``(m_pad, S, K, B)`` (the loop's and the
    search's copy), the K kept bins a tile slot, the slots' sort operands, and
    one step's gathered rows and one-hots.
    """
    lanes = partial(_round_up, multiple=128)  # the minor axis of a device array
    width = packed_width(d, n_bins)
    k = min(feat_subset, d)
    bins = n * lanes(width) * 4
    rows = 0 if rows_resident else n * d * 4
    sort_tmp = 2 * min(n, QUANTILE_SAMPLE_ROWS) * d * 4
    if max_depth < 1:
        return rows + bins, n * n_stats * 8, sort_tmp
    m_pad = level_groups(max_depth)[-1][2]
    slots = level_tiles(n, m_pad, TILE_ROWS, 1) * TILE_ROWS
    d_packed = lanes(width * bins_per_word(n_bins))
    k8 = _round_up(k, 8)
    hist = m_pad * n_stats * k8 * lanes(n_bins) * 4
    kept = slots * _round_up(k, 32) * (1 if n_bins <= 256 else 4)
    step = STEP_TILES * (  # a lone tree's step; a batch shares as many tiles
        TILE_ROWS * d_packed * (4 + 4 + 2)  # words, a digit, the operand
        + k8 * d_packed * (2 + 1)  # the features' one-hot and its comparison
        + k8 * TILE_ROWS * lanes(n_bins) * (2 + 1)  # the bins' one-hot
        + n_stats * k8 * lanes(n_bins) * 4
    )
    search = 6 * min(SEARCH_NODES, m_pad) * n_stats * k8 * lanes(n_bins) * 4
    per_tree = 2 * hist + 2 * kept + slots * 4 * (6 + 3 * n_stats) + step + search
    return rows + bins, per_tree, sort_tmp


def node_feature_subsets(tree_key: jax.Array, node_ids: jax.Array, d: int, k: int) -> jax.Array:
    """(len(node_ids), k) int32, ascending: node g's feature subset, a
    function of the tree's key and the node's heap id ALONE.

    THE STATED RULE: Floyd's sample of k distinct ids out of d from
    ``t_i = randint(fold_in(tree_key, g), (k,), 0, j_i + 1)``, ``j_i = d - k +
    i``: walking i = 0..k-1, take ``t_i`` unless it is already taken, else
    ``j_i``; then sort. Uniform over the k-subsets, k draws a node (not d).
    """
    if k >= d:
        return jnp.broadcast_to(jnp.arange(d, dtype=jnp.int32), (node_ids.shape[0], d))
    js = jnp.arange(d - k, d, dtype=jnp.int32)

    def one(g):
        ts = jax.random.randint(
            jax.random.fold_in(tree_key, g), (k,), 0, js + 1, dtype=jnp.int32
        )

        def take(i, chosen):
            pick = jnp.where(jnp.any(chosen == ts[i]), js[i], ts[i])
            return chosen.at[i].set(pick)

        chosen = lax.fori_loop(0, k, take, jnp.full((k,), -1, jnp.int32))
        return jnp.sort(chosen)

    return jax.vmap(one)(node_ids)


def _search_nodes(hist, *, impurity, min_instances, min_info_gain):
    """Best split of each node from ``hist (m, S, K, B)``: ``(j, b, gain, ok,
    left (m, S), right (m, S))``. The arithmetic of :func:`split_level`, on
    the K selected features (ties: the lowest position j, then the lowest bin)."""
    m, _, k, n_bins = hist.shape
    left = jnp.cumsum(hist, axis=3)
    total = left[:, :, 0, -1]  # (m, S): the same for every feature
    gain, w_parent = _candidate_gains(
        left, total[:, :, None, None], impurity=impurity, min_instances=min_instances, axis=1
    )
    w_parent = w_parent[:, 0, 0]
    flat = gain.reshape(m, k * n_bins)
    best = jnp.argmax(flat, axis=1)
    best_gain = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
    ok = (best_gain > 0) & (best_gain >= min_info_gain) & (w_parent > 0)
    left_best = jnp.take_along_axis(
        left.reshape(m, -1, k * n_bins), best[:, None, None], axis=2
    )[:, :, 0]
    return (
        (best // n_bins).astype(jnp.int32), (best % n_bins).astype(jnp.int32),
        best_gain, ok, left_best, total - left_best,
    )


def _write_level(arr, vals, at, count):
    """``arr[at : at + count] = vals[:count]`` with ``at`` and ``count``
    traced and ``vals``' length static."""
    width = vals.shape[0]
    cur = lax.dynamic_slice_in_dim(arr, at, width, axis=0)
    mask = (jnp.arange(width) < count).reshape((width,) + (1,) * (vals.ndim - 1))
    return lax.dynamic_update_slice_in_dim(arr, jnp.where(mask, vals, cur), at, axis=0)


def _tiles_of(a: jax.Array, pos0: jax.Array, tile_rows: int) -> jax.Array:
    """``a[..., pos0[t] : pos0[t] + tile_rows]`` for every tile t: slices of a
    sorted per-row array at offsets that no tile boundary aligns, as
    ``(..., n_tiles, R)``; past the end reads zeros.

    Without an element gather and without the loop of single slices that XLA
    makes of a gather of windows (a million device operations a fit): the two
    aligned rows of R that hold a slice are gathered whole, and the offset
    inside them is taken out by ``log2 R`` conditional static shifts.
    """
    n = a.shape[-1]
    rows = -(-n // tile_rows) + 2
    table = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, rows * tile_rows - n)])
    table = table.reshape(a.shape[:-1] + (rows, tile_rows))
    at, shift = pos0 // tile_rows, pos0 % tile_rows
    both = jnp.concatenate(
        [jnp.take(table, at, axis=-2), jnp.take(table, at + 1, axis=-2)], axis=-1
    )  # (..., n_tiles, 2R)
    step = 1
    while step < tile_rows:
        moved = jnp.concatenate([both[..., step:], jnp.zeros_like(both[..., :step])], axis=-1)
        both = jnp.where(((shift // step) % 2 == 1)[:, None], moved, both)
        step *= 2
    return both[..., :tile_rows]


def _grow_tree(
    xb, row_stats, weights, tree_key, *, max_depth, n_bins, n_features, impurity,
    feat_subset, min_instances, min_info_gain, tile_rows, tiles_per_step,
    min_level_width, axis_name, exact_counts, operand_dtype,
):
    """One tree's heap arrays ``(feature, bin, gain, stats)`` (vmapped over
    the trees of a batch by :func:`grow_forest`)."""
    n = xb.shape[0]
    n_stats = row_stats.shape[1]
    k = min(feat_subset, n_features)
    n_nodes = 2 ** (max_depth + 1) - 1
    rows_per_tile = jnp.arange(tile_rows, dtype=jnp.int32)
    iota_b = jnp.arange(n_bins, dtype=jnp.int32)
    iota_k = jnp.arange(k, dtype=jnp.int32)
    keep_dtype = jnp.uint8 if n_bins <= 256 else jnp.int32
    # counts are small integers (one-hot x weights of 256 at most): exact in
    # ONE bf16 pass with float32 accumulation. Real-valued channels (the
    # regressor's, a fractional weightCol) take float32 operands at HIGHEST.
    hist_dtype = operand_dtype if exact_counts else jnp.float32
    hist_prec = lax.Precision.DEFAULT if exact_counts else lax.Precision.HIGHEST

    ws0 = weights[None, :] * row_stats.T  # (S, n): a row's weighted stat channels
    root = jnp.sum(ws0, axis=1)
    if axis_name is not None:
        root = lax.psum(root, axis_name)

    def level_step(level, state, m_pad):
        key, perm, ws, out_f, out_b, out_gain, out_stats = state
        n_tiles = level_tiles(n, m_pad, tile_rows, tiles_per_step)
        m = jnp.left_shift(jnp.int32(1), level)
        offset = m - 1
        # the runs of rows, node by node, and their tiles
        start = jnp.searchsorted(key, jnp.arange(m_pad + 1, dtype=jnp.int32)).astype(jnp.int32)
        count = start[1:] - start[:-1]
        tiles_of = (count + tile_rows - 1) // tile_rows
        tile_end = jnp.cumsum(tiles_of)
        tile = jnp.arange(n_tiles, dtype=jnp.int32)
        tile_node = jnp.searchsorted(tile_end, tile, side="right").astype(jnp.int32)
        tn = jnp.minimum(tile_node, m_pad - 1)  # a spare tile counts nothing, whatever it reads
        pos0 = start[tn] + (tile - (tile_end - tiles_of)[tn]) * tile_rows
        pos0 = jnp.where(tile_node < m_pad, pos0, n)  # a spare tile reads past the end
        n_valid = jnp.clip(start[tn + 1] - pos0, 0, tile_rows)
        valid = rows_per_tile[None, :] < n_valid[:, None]  # (n_tiles, R)
        rid = _tiles_of(perm, pos0, tile_rows)  # (n_tiles, R)
        ws_tiles = jnp.where(valid[None], _tiles_of(ws, pos0, tile_rows), 0.0)  # (S, n_tiles, R)
        feats = node_feature_subsets(
            tree_key, offset + jnp.arange(m_pad, dtype=jnp.int32), n_features, k
        )

        def step(i, carry):
            hist, kept = carry
            at = i * tiles_per_step
            rid_c = lax.dynamic_slice_in_dim(rid, at, tiles_per_step)
            ws_c = lax.dynamic_slice_in_dim(ws_tiles, at, tiles_per_step, axis=1)
            tn_c = lax.dynamic_slice_in_dim(tn, at, tiles_per_step)
            node_c = lax.dynamic_slice_in_dim(tile_node, at, tiles_per_step)
            sel = _select_bins(xb[rid_c], feats[tn_c], n_bins, operand_dtype)  # (C, K, R)
            onehot_b = (sel[..., None] == iota_b).astype(hist_dtype)  # (C, K, R, B)
            tile_hist = jnp.einsum(
                "scr,ckrb->cskb", ws_c.astype(hist_dtype), onehot_b, precision=hist_prec,
                preferred_element_type=jnp.float32,
            )
            hist = hist.at[node_c].add(tile_hist, mode="drop")  # a spare tile's node is m_pad
            kept = lax.dynamic_update_slice_in_dim(kept, sel.astype(keep_dtype), at, axis=0)
            return hist, kept

        hist, kept = lax.fori_loop(
            0, n_tiles // tiles_per_step, step,
            (jnp.zeros((m_pad, n_stats, k, n_bins), jnp.float32),
             jnp.zeros((n_tiles, k, tile_rows), keep_dtype)),
        )
        if axis_name is not None:
            hist = lax.psum(hist, axis_name)

        chunk = min(SEARCH_NODES, m_pad)
        best_j, best_b, best_gain, ok, left, right = jax.tree_util.tree_map(
            lambda a: a.reshape((m_pad,) + a.shape[2:]),
            lax.map(
                partial(_search_nodes, impurity=impurity, min_instances=min_instances,
                        min_info_gain=min_info_gain),
                hist.reshape(m_pad // chunk, chunk, n_stats, k, n_bins),
            ),
        )
        best_f = jnp.take_along_axis(feats, best_j[:, None], axis=1)[:, 0]
        out_f = _write_level(out_f, jnp.where(ok, best_f, -1), offset, m)
        out_b = _write_level(out_b, jnp.where(ok, best_b, 0), offset, m)
        out_gain = _write_level(out_gain, jnp.where(ok, best_gain, 0.0), offset, m)
        children = jnp.where(ok[:, None, None], jnp.stack([left, right], axis=1), 0.0)
        out_stats = _write_level(
            out_stats, children.reshape(2 * m_pad, n_stats), 2 * offset + 1, 2 * m
        )

        # route: the winning feature's bin is among the K selected of the slot
        won = jnp.sum(
            jnp.where(iota_k[None, :, None] == best_j[tn][:, None, None], kept.astype(jnp.int32), 0),
            axis=1,
        )  # (n_tiles, R)
        child = 2 * tn[:, None] + (won > best_b[tn][:, None])
        new_key = jnp.where(valid & ok[tn][:, None], child, _RETIRED)
        # ... and one sort by the child's id puts every row's id and channels
        # back in node order; the n first hold every row that still grows
        slots = (new_key.ravel(), rid.ravel()) + tuple(c.ravel() for c in ws_tiles)
        key, perm, *channels = lax.sort(slots, num_keys=1)
        ws = jnp.stack([c[:n] for c in channels])
        return key[:n], perm[:n], ws, out_f, out_b, out_gain, out_stats

    state = (
        jnp.zeros((n,), jnp.int32),  # every row at the root
        jnp.arange(n, dtype=jnp.int32),
        ws0,
        jnp.full((n_nodes,), -1, jnp.int32),
        jnp.zeros((n_nodes,), jnp.int32),
        jnp.zeros((n_nodes,), jnp.float32),
        jnp.zeros((n_nodes, n_stats), jnp.float32).at[0].set(root),
    )
    for first, end, m_pad in level_groups(max_depth, min_level_width):
        state = lax.fori_loop(first, end, partial(level_step, m_pad=m_pad), state)
    return state[3:]


@partial(
    jax.jit,
    static_argnames=(
        "max_depth", "n_bins", "n_features", "impurity", "feat_subset", "min_instances",
        "min_info_gain", "tile_rows", "tiles_per_step", "min_level_width", "axis_name",
        "exact_counts", "operand_dtype",
    ),
)
def grow_forest(
    x_binned: jax.Array,  # (n, W) int32: pack_bins' words
    row_stats: jax.Array,  # (n, S) float32
    weights: jax.Array,  # (T, n) float32: sample_weights' rows of these trees
    edges: jax.Array,  # (d, n_bins - 1) float32
    key: jax.Array,  # the forest's feature key
    tree_ids: jax.Array,  # (T,) int32: which trees of the forest these are
    *,
    max_depth: int,
    n_bins: int,
    n_features: int,
    impurity: str,
    feat_subset: int,
    min_instances: int = 1,
    min_info_gain: float = 0.0,
    tile_rows: int = TILE_ROWS,
    tiles_per_step: int | None = None,
    min_level_width: int = MIN_LEVEL_WIDTH,
    axis_name: str | None = None,
    exact_counts: bool = True,
    operand_dtype=None,
) -> Forest:
    """Grow a batch of trees level by level; all shapes static, ONE program
    (see the section note above for a level). Tree ``t``'s feature subsets
    come from ``fold_in(key, t)`` and its node's heap id, its weights from
    :func:`sample_weights`' row ``t``: neither the batch, nor a tile size,
    nor the mesh changes a node.

    Distributed mode (``axis_name`` set, under ``shard_map``): rows are
    sharded over the named axis and one ``psum`` a level merges the SELECTED
    histogram ``(T, m_pad, S, K, B)`` (the Spark ``treeAggregate`` of the
    reference, RapidsRowMatrix.scala:207-233, as an XLA collective); split
    selection then runs replicated, so routing needs no further traffic.
    """
    if operand_dtype is None:
        # XLA's CPU backend has no bfloat16 batched dot; a float32 operand at
        # DEFAULT precision is the same single pass on the chip's MXU
        operand_dtype = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    if tiles_per_step is None:
        tiles_per_step = step_tiles_a_tree(weights.shape[0])
    if tile_rows & (tile_rows - 1):
        raise ValueError(f"tile_rows must be a power of two, got {tile_rows}")
    classification = impurity in ("gini", "entropy")
    grow = partial(
        _grow_tree, max_depth=max_depth, n_bins=n_bins, n_features=n_features,
        impurity=impurity, feat_subset=feat_subset, min_instances=min_instances,
        min_info_gain=min_info_gain, tile_rows=tile_rows, tiles_per_step=tiles_per_step,
        min_level_width=min_level_width, axis_name=axis_name,
        exact_counts=exact_counts and classification, operand_dtype=operand_dtype,
    )
    tree_keys = jax.vmap(lambda t: jax.random.fold_in(key, t))(tree_ids)
    feature, best_bin, gain, stats = jax.vmap(grow, in_axes=(None, None, 0, 0))(
        x_binned, row_stats, weights, tree_keys
    )
    split = feature >= 0
    imp, weight = _impurity(stats, impurity)
    return Forest(
        feature,
        jnp.where(split, edges[jnp.maximum(feature, 0), best_bin], 0.0),
        ~split,
        _leaf_prediction(stats, impurity),
        weight,
        gain,
        imp,
    )


def grow_forest_sharded(
    mesh,
    x_binned: jax.Array,
    row_stats: jax.Array,
    weights: jax.Array,
    edges: jax.Array,
    key: jax.Array,
    tree_ids: jax.Array,
    **kwargs,
) -> Forest:
    """Mesh path: rows sharded over the data axis, per-shard partial
    histograms of the selected features merged with one ``psum`` per level
    (see :func:`grow_forest`).

    Rows are padded to a multiple of the data-axis size with zero weight
    (padded rows contribute nothing to any histogram). The returned forest is
    replicated: identical on every device, and identical to one device's.
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

    def local(xb, rs, w, e, k, t):
        return grow_forest(xb, rs, w, e, k, t, axis_name=DATA_AXIS, **kwargs)

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(None, DATA_AXIS), P(), P(), P()),
        out_specs=Forest(P(), P(), P(), P(), P(), P(), P()),
        # psum'd histograms make every split decision replicated; the vma
        # checker cannot see that, so skip the static check (as in ops.knn).
        check_vma=False,
    )
    return fn(x_binned, row_stats, weights, edges, key, tree_ids)


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


@partial(jax.jit, static_argnames=("n_rows", "subsampling_rate", "bootstrap"))
def sample_weights(
    key: jax.Array, tree_ids: jax.Array, n_rows: int, subsampling_rate: float, bootstrap: bool
) -> jax.Array:
    """Per-tree row weights ``(len(tree_ids), n_rows)``: Poisson(rate) with
    replacement (the standard distributed approximation of bootstrap
    resampling), Bernoulli(rate) without. Tree ``t``'s row is drawn from
    ``fold_in(key, t)`` ALONE, so a batch of trees draws what the whole forest
    would.

    Poisson draws clamp at 256 — the bf16-exactness bound of the one-pass
    histogram (ops.trees.grow_forest precision note). A clamp at 256 is
    semantically invisible (P[Poisson(rate <= 1) > 256] ~ 1e-600: no draw
    ever reaches it) but makes the unweighted classification histogram's
    exactness a STATIC fact — one-hot stats x integer weights <= 256 are
    exact bf16 products — so the fit no longer pays a device readback to
    verify it (each readback is a full host round trip that stalls the
    async dispatch stream)."""
    def one(t):
        k = jax.random.fold_in(key, t)
        if bootstrap:
            w = jax.random.poisson(k, subsampling_rate, (n_rows,), dtype=jnp.int32)
            return jnp.minimum(w, 256).astype(jnp.float32)
        return jax.random.bernoulli(k, subsampling_rate, (n_rows,)).astype(jnp.float32)

    return jax.vmap(one)(jnp.asarray(tree_ids, dtype=jnp.int32))


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

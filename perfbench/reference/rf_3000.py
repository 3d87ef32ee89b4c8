"""The plain reference for ``rf_3000``, its controls and faults.

Plain ``jax.numpy`` and numpy float64; imports nothing of the program. A forest
is random and greedy, so, like KMeans's centres, it is judged by what it is:
the returned trees (heap-indexed: node ``g`` has children ``2g + 1`` and
``2g + 2``; a row goes LEFT on ``x[feature] <= threshold``) are held to the
configuration's guarantees, with the randomness recomputed from the seed by
the STATED rule (the configuration's ``guarantees``), restated here:

    k_sample, k_feat = split(key(seed))
    weights of tree t  = min(poisson(fold_in(k_sample, t), 1.0, (n,)), 256)
    subset of node g   = Floyd's sample of K out of d from
                         t_i = randint(fold_in(fold_in(k_feat, t), g), (K,), 0, j_i + 1),
                         j_i = d - K + i: take t_i unless taken, else j_i; sorted

``count_mismatch``  ALL trees, ALL nodes: every row is routed down every tree
    by the returned thresholds, the recomputed bootstrap weights are added up
    per node and class, and compared with the counts the model reports (weight
    times class distribution): integers, cells that differ. Holds the routing,
    the bootstrap, the leaf values, and that one bfloat16 pass over counts is
    exact. Limit 0.
``split_regret``  on a seeded sample of internal nodes (``NODES_A_LEVEL`` of
    every level, so at least one root): the node's histogram over ITS subset
    and the reference's own 127 quantile edges, gini gains in float64; the best
    valid gain less the gain of the returned split (judged by the partition its
    threshold induces on the raw values), over the node's impurity. The worst.
``leaf_regret``  on a seeded sample of the leaves ABOVE the stated depth that
    report an impure distribution: the best valid gain in the leaf's subset,
    over its impurity, which has to be nought (no split with weight on both
    sides). Holds a cut in depth and an early stop. The worst; 0 with no such leaf.
``subset_fault``  sampled internal nodes whose split feature is outside their
    subset, or whose threshold is no edge of that feature (to ``EDGE_RTOL``:
    the rounding of an interpolated float32 quantile). Limit 0.
"""

from __future__ import annotations

import hashlib
import math
from functools import lru_cache

import numpy as np

NUMBERS = ("count_mismatch", "subset_fault", "split_regret", "leaf_regret")
NODES_A_LEVEL = 6
LEAVES = 40
SAMPLE_SEED = 3000_13_128
EDGE_RTOL = 1e-5
EDGE_BLOCK_COLS = 500  # columns whose quantiles are taken at a time (the sort's temporary)
# rows an element gather reads from at a time: its offsets are 32-bit, and the
# cell's 250,000 x 3000 float32 rows are 3 GB (the chip halted on a gather
# from the whole matrix; my chip run, PR 34)
BLOCK_ROWS = 125_000


# --- the stated randomness ------------------------------------------------


def _keys(seed: int):
    import jax

    return jax.random.split(jax.random.key(int(seed)))


def bootstrap_weights(seed: int, trees: int, n: int) -> np.ndarray:
    """(trees, n) int64: tree t's Poisson(1) draw, clamped at 256."""
    import jax
    import jax.numpy as jnp

    k_sample, _ = _keys(seed)
    draw = jax.jit(lambda t: jnp.minimum(
        jax.random.poisson(jax.random.fold_in(k_sample, t), 1.0, (n,), dtype=jnp.int32), 256))
    return np.stack([np.asarray(draw(jnp.int32(t))) for t in range(trees)]).astype(np.int64)


def node_subset(seed: int, tree: int, node: int, d: int, k: int) -> np.ndarray:
    """Node ``node`` of tree ``tree``: its ``k`` features, ascending."""
    import jax
    import jax.numpy as jnp

    if k >= d:
        return np.arange(d)
    _, k_feat = _keys(seed)
    js = np.arange(d - k, d)
    key = jax.random.fold_in(jax.random.fold_in(k_feat, tree), node)
    ts = np.asarray(jax.random.randint(key, (k,), 0, jnp.asarray(js + 1, jnp.int32), dtype=jnp.int32))
    chosen: list = []
    for t, j in zip(ts.tolist(), js.tolist()):
        chosen.append(j if t in chosen else t)
    return np.sort(np.asarray(chosen))


# --- the reference's own edges, routing and gathers -------------------------


def quantile_edges(x, bins: int) -> np.ndarray:
    """(d, bins - 1) float32: the (i + 1) / bins quantiles of every column."""
    import jax
    import jax.numpy as jnp

    qs = jnp.arange(1, bins, dtype=jnp.float32) / bins
    take = jax.jit(lambda cols: jnp.quantile(cols, qs, axis=0).T)
    d = x.shape[1]
    return np.concatenate([np.asarray(take(x[:, lo : min(lo + EDGE_BLOCK_COLS, d)]))
                           for lo in range(0, d, EDGE_BLOCK_COLS)])


@lru_cache(maxsize=None)
def _route_step():
    import jax
    import jax.numpy as jnp

    def step(x, node, feature, threshold, is_leaf):
        at = jnp.maximum(node, 0)
        f = jnp.take_along_axis(feature, at, axis=1)
        thr = jnp.take_along_axis(threshold, at, axis=1)
        leaf = jnp.take_along_axis(is_leaf, at, axis=1)
        value = x[jnp.arange(x.shape[0])[None, :], jnp.maximum(f, 0)]
        return jnp.where((node >= 0) & ~leaf, 2 * node + 1 + (value > thr), -1).astype(jnp.int32)

    return jax.jit(step)


def row_blocks(x) -> list:
    """``[(lo, hi, x[lo:hi])]``: the rows in blocks of their own."""
    n = x.shape[0]
    return [(lo, min(lo + BLOCK_ROWS, n), x[lo : min(lo + BLOCK_ROWS, n)])
            for lo in range(0, n, BLOCK_ROWS)]


def route(blocks: list, feature, threshold, is_leaf) -> list:
    """Per level, the (trees, n) heap id of the node every row is in (-1 once
    it stopped at a leaf above)."""
    import jax.numpy as jnp

    trees, nodes = feature.shape
    depth = int(math.log2(nodes + 1)) - 1
    f, thr, leaf = jnp.asarray(feature), jnp.asarray(threshold), jnp.asarray(is_leaf)
    routed = []
    for lo, hi, rows in blocks:
        node = jnp.zeros((trees, hi - lo), jnp.int32)
        levels = [np.asarray(node)]
        for _ in range(depth):
            node = _route_step()(rows, node, f, thr, leaf)
            levels.append(np.asarray(node))
        routed.append(levels)
    return [np.concatenate([levels[at] for levels in routed], axis=1) for at in range(depth + 1)]


@lru_cache(maxsize=None)
def _take():
    import jax

    return jax.jit(lambda x, rows, cols: x[rows[:, None], cols[None, :]])


def values_of(blocks: list, rows: np.ndarray, cols: np.ndarray) -> np.ndarray:
    """``x[rows][:, cols]`` on the host, float64 (``rows`` ascending); a
    block's row list is padded to a power of four so that a handful of shapes
    serve every node."""
    import jax.numpy as jnp

    cols = jnp.asarray(cols, jnp.int32)
    parts = []
    for lo, hi, block in blocks:
        mine = rows[(rows >= lo) & (rows < hi)] - lo
        if not len(mine):
            continue
        size = 256
        while size < len(mine):
            size *= 4
        padded = np.zeros(size, np.int32)
        padded[: len(mine)] = mine
        parts.append(np.asarray(_take()(block, jnp.asarray(padded), cols))[: len(mine)])
    return np.concatenate(parts).astype(np.float64)


# --- gains ---------------------------------------------------------------------


def gini(counts: np.ndarray) -> tuple:
    """(impurity, weight) of float64 class counts along the last axis."""
    w = counts.sum(axis=-1)
    p = counts / np.maximum(w, 1e-300)[..., None]
    return np.where(w > 0, 1.0 - (p * p).sum(axis=-1), 0.0), w


def split_gain(stats: np.ndarray, left: np.ndarray) -> np.ndarray:
    """Gini gain of sending ``left`` (..., C) of a node's ``stats`` (C,) left;
    -inf where a side holds no weight."""
    imp, w = gini(stats)
    imp_l, w_l = gini(left)
    imp_r, w_r = gini(stats - left)
    gain = imp - (w_l * imp_l + w_r * imp_r) / max(w, 1e-300)
    return np.where((w_l >= 1) & (w_r >= 1), gain, -np.inf)


def best_gain_in_subset(values: np.ndarray, edges: np.ndarray, wy: np.ndarray) -> float:
    """The largest valid gain over the columns of ``values`` (rows, K) at the
    edges ``edges`` (K, B - 1), for per-row class weights ``wy`` (rows, C)."""
    stats = wy.sum(axis=0)
    n_bins = edges.shape[1] + 1
    best = -np.inf
    for j in range(values.shape[1]):
        bins = np.searchsorted(edges[j], values[:, j], side="left")  # #{e : x > e}
        hist = np.stack([np.bincount(bins, weights=wy[:, c], minlength=n_bins)
                         for c in range(wy.shape[1])], axis=1)
        left = hist.cumsum(axis=0)[:-1]  # x <= edge b, b = 0 .. B - 2
        best = max(best, float(split_gain(stats, left).max()))
    return best


# --- reference and comparison -----------------------------------------------------


def reference(pair, config: dict) -> dict:
    """The reference's own edges of the rows; what a fit returned is assessed
    in ``compare`` (answers that are the same to the byte once)."""
    import jax.numpy as jnp

    x, y = (jnp.asarray(a) for a in pair)
    return {"x": x, "y": np.asarray(y).astype(np.int64), "config": config,
            "edges": quantile_edges(x, int(config["max_bins"])), "seen": {}, "weights": {}}


def compare(result: dict, ref: dict) -> dict:
    config = ref["config"]
    bad = dict.fromkeys(NUMBERS, float("inf"))
    feature = np.asarray(result["feature"])
    key = hashlib.sha1(b"".join(np.ascontiguousarray(result[k]).tobytes()
                                for k in sorted(result))).hexdigest()
    if key in ref["seen"]:
        return dict(ref["seen"][key])
    trees, depth = int(config["num_trees"]["run"]), int(config["max_depth"])
    if feature.ndim != 2 or feature.shape[0] != trees:
        return bad
    nodes = feature.shape[1]
    got_depth = int(math.log2(nodes + 1)) - 1
    if 2 ** (got_depth + 1) - 1 != nodes or got_depth > depth:
        return bad
    threshold, is_leaf = np.asarray(result["threshold"]), np.asarray(result["is_leaf"])
    value, weight = np.asarray(result["value"], np.float64), np.asarray(result["weight"], np.float64)
    classes = int(config["num_classes"])
    if value.shape != (trees, nodes, classes) or not np.all(np.isfinite(value)):
        return bad
    x, y, n = ref["x"], ref["y"], ref["x"].shape[0]
    seed = int(np.asarray(result["seed"]))
    if seed not in ref["weights"]:
        ref["weights"][seed] = bootstrap_weights(seed, trees, n)
    w = ref["weights"][seed]

    # count_mismatch: every row down every tree
    blocks = row_blocks(x)  # 3 GB more on the device until the comparison is done
    levels = route(blocks, feature, threshold, is_leaf)
    counts = np.zeros((trees, nodes, classes), np.int64)
    for node in levels:
        for t in range(trees):
            live = node[t] >= 0
            for c in range(classes):
                mine = live & (y == c)
                counts[t, :, c] += np.bincount(node[t][mine], weights=w[t][mine],
                                               minlength=nodes).astype(np.int64)
    reported = value * weight[..., None]
    mismatch = int(np.sum(np.abs(reported - counts) > 0.25))
    # a split node has both its children in the heap, a leaf no feature
    mismatch += int(np.sum((feature >= 0) & is_leaf)) + int(np.sum((feature < 0) & ~is_leaf))

    rng = np.random.default_rng(SAMPLE_SEED)
    d, k = x.shape[1], int(config["features_per_node"])
    first = lambda level: 2**level - 1  # noqa: E731
    level_of = np.floor(np.log2(np.arange(nodes) + 1)).astype(int)

    def node_rows(t, g):
        return np.flatnonzero(levels[level_of[g]][t] == g)

    def class_weights(t, rows):
        return w[t][rows, None] * (y[rows, None] == np.arange(classes)[None, :])

    # split_regret and subset_fault: a sample of the internal nodes of every level
    regret, fault = 0.0, 0
    for level in range(got_depth):
        span = slice(first(level), first(level + 1))
        ts, gs = np.nonzero((feature[:, span] >= 0) & (weight[:, span] > 0))
        for pick in rng.permutation(len(ts))[:NODES_A_LEVEL]:
            t, g = int(ts[pick]), int(gs[pick]) + first(level)
            subset = node_subset(seed, t, g, d, k)
            f, thr = int(feature[t, g]), float(threshold[t, g])
            is_edge = bool(np.isclose(ref["edges"][f], thr, rtol=EDGE_RTOL, atol=0.0).any())
            fault += int(f not in subset or not is_edge)
            rows = node_rows(t, g)
            values = values_of(blocks, rows, np.append(subset, f))
            wy = class_weights(t, rows)
            stats = wy.sum(axis=0)
            best = best_gain_in_subset(values[:, :-1], ref["edges"][subset], wy)
            mine = float(split_gain(stats, wy[values[:, -1] <= np.float64(np.float32(thr))].sum(axis=0)))
            impurity = float(gini(stats)[0])
            if impurity > 0:
                regret = max(regret, (best - mine) / impurity)

    # leaf_regret: leaves above the stated depth that report an impure distribution
    leaf_regret = 0.0
    above = level_of[None, :] < depth
    ts, gs = np.nonzero(is_leaf & above & (weight > 0) & (gini(counts.astype(np.float64))[0] > 0))
    for pick in rng.permutation(len(ts))[:LEAVES]:
        t, g = int(ts[pick]), int(gs[pick])
        subset = node_subset(seed, t, g, d, k)
        rows = node_rows(t, g)
        wy = class_weights(t, rows)
        best = best_gain_in_subset(values_of(blocks, rows, subset), ref["edges"][subset], wy)
        if best > 0:
            leaf_regret = max(leaf_regret, best / float(gini(wy.sum(axis=0))[0]))

    ref["seen"][key] = {"count_mismatch": float(mismatch), "subset_fault": float(fault),
                        "split_regret": regret, "leaf_regret": leaf_regret}
    return dict(ref["seen"][key])


# --- controls and faults -----------------------------------------------------------


def _fit(ctx, pair, **setters):
    from perfbench.drivers import fit_loop

    est = fit_loop.build_estimator(ctx.config)
    for name, value in setters.items():
        getattr(est, "set" + name)(value)
    return fit_loop.read_model(est.fit(pair), ctx.config)


def controls() -> dict:
    """name -> ``control(ctx, pair)``: the program with one stated guarantee
    broken, put in the sound fit's place. Those the configuration lists have to
    come out NOT correct (``perfbench/tests/test_rf_3000.py``; on the chip,
    ``perfbench.control``)."""
    def setting(**setters):
        return lambda ctx, pair: _fit(ctx, pair, **setters)

    def one_level_less(ctx, pair):
        return _fit(ctx, pair, MaxDepth=int(ctx.config["max_depth"]) - 1)

    def half_the_bins(ctx, pair):
        return _fit(ctx, pair, MaxBins=int(ctx.config["max_bins"]) // 2)

    return {
        "shallow": one_level_less,        # "nodes at depth 13 are leaves", and none above that could split
        "coarse_bins": half_the_bins,     # "127 edges a feature": every other candidate is not looked at
        "no_bootstrap": setting(Bootstrap=False),  # "a Poisson(1) weight per row"
        "few_features": setting(FeatureSubsetStrategy="log2"),  # "55 features a node": 12
    }


def faults() -> dict:
    """Planted faults of the timed path, name -> ``fault(ctx, pair)`` that
    returns what a broken fit would hand the comparison."""
    def half_rows(ctx, pair):
        # half of the rows left out, the fit is of the rest
        x, y = pair
        return _fit(ctx, (x[: x.shape[0] // 2], y[: x.shape[0] // 2]))

    def stale_model(ctx, pair):
        # the state left unchanged: the model of other rows handed back
        from perfbench import data

        gen = ctx.config["data"]
        return _fit(ctx, data.generate(gen["generator"], ctx.args.seed + 1,
                                       pair[0].shape[0] // 8, ctx.cols, gen["params"]))

    def altered_feature(ctx, pair):
        # an answer altered where it is produced: one node's feature, a root's
        out = _fit(ctx, pair)
        feature = out["feature"].copy()
        feature[0, 0] = (feature[0, 0] + 1) % pair[0].shape[1]
        out["feature"] = feature
        return out

    return {"half_rows": half_rows, "stale_model": stale_model,
            "altered_feature": altered_feature}

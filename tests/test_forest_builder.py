"""The level builder of ``ops/trees.py`` (``grow_forest``) against a plain
recursive numpy grower under the same stated randomness, and the things that
must not change a node: the tree batch, the tile sizes, the level programs'
widths, a mesh. (Admission and counters: ``tests/test_random_forest.py``.)"""

import numpy as np
import pytest


# --- the level builder (ops/trees.py::grow_forest) ------------------------


def _builder_case(impurity, n=260, d=12, n_bins=8, seed=0):
    """Small seeded rows, binned by the program, and the stat channels."""
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops import trees

    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    x[:, 3] = np.round(x[:, 3])  # a low-cardinality column: empty bins
    signal = x[:, 0] + 0.7 * x[:, 1] * x[:, 2] + 0.3 * rng.normal(size=n)
    if impurity == "variance":
        yc = (signal - signal.mean()).astype(np.float32)
        stats = np.stack([np.ones_like(yc), yc, yc * yc], axis=1)
    else:
        y = np.digitize(signal, [-0.5, 0.6])
        stats = np.eye(3, dtype=np.float32)[y]
    edges, words = trees.quantize_and_bin(jnp.asarray(x), n_bins)
    bins = trees.unpack_bins(words, d, n_bins)
    np.testing.assert_array_equal(  # bin = #{edges e : x > e}
        bins, (x[:, :, None] > np.asarray(edges)[None]).sum(axis=2)
    )
    return x, stats, edges, words, bins


def _grow(words, stats, edges, *, tree_ids, depth, impurity, n_bins, d, k, seed=5,
          bootstrap=True, mesh=None, **kw):
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops import trees

    k_sample, k_feat = jax.random.split(jax.random.key(seed))
    ids = np.asarray(tree_ids, dtype=np.int32)
    w = trees.sample_weights(k_sample, ids, words.shape[0], 1.0, bootstrap)
    args = (words, jnp.asarray(stats), w, edges, k_feat, jnp.asarray(ids))
    kwargs = dict(max_depth=depth, n_bins=n_bins, n_features=d, impurity=impurity,
                  feat_subset=k, **kw)
    if mesh is not None:
        forest = trees.grow_forest_sharded(mesh, *args, **kwargs)
    else:
        forest = trees.grow_forest(*args, **kwargs)
    return jax.tree_util.tree_map(np.asarray, forest), np.asarray(w), k_feat


def _impurity64(stats, kind):
    """(impurity, weight) of float64 stat vectors along the last axis."""
    if kind == "variance":
        w = stats[..., 0]
        safe = np.maximum(w, 1e-12)
        mean = stats[..., 1] / safe
        return np.where(w > 0, np.maximum(stats[..., 2] / safe - mean * mean, 0.0), 0.0), w
    w = stats.sum(axis=-1)
    p = stats / np.maximum(w, 1e-12)[..., None]
    if kind == "gini":
        imp = 1.0 - (p * p).sum(axis=-1)
    else:
        with np.errstate(divide="ignore", invalid="ignore"):
            imp = -np.where(p > 0, p * np.log2(p), 0.0).sum(axis=-1)
    return np.where(w > 0, imp, 0.0), w


def _check_tree_recursively(forest, t, bins, ws, subsets_of, edges, depth, impurity, n_bins):
    """The plain recursive grower, node by node beside the builder's tree:
    the same counts in every node, and in every node the split of largest
    gain over the node's subset (float64 gains; lowest feature, then lowest
    bin, on a tie; a gain within rounding of the best is the best)."""
    checked = {"splits": 0, "leaves": 0}

    def visit(g, rows, level):
        stats = ws[rows].sum(axis=0)
        imp, weight = _impurity64(stats, impurity)
        np.testing.assert_allclose(forest.node_weight[t, g], weight, rtol=1e-5, atol=1e-5)
        if impurity == "variance":
            if weight > 0:
                np.testing.assert_allclose(
                    forest.leaf_value[t, g, 0], stats[1] / weight, rtol=1e-4, atol=1e-4
                )
        else:
            # counts are integers: exactly the reference's
            np.testing.assert_array_equal(
                np.rint(forest.leaf_value[t, g] * forest.node_weight[t, g]), stats
            )
        np.testing.assert_allclose(forest.node_impurity[t, g], imp, rtol=1e-4, atol=1e-5)
        best = None
        if level < depth:
            feats = subsets_of(g)
            hist = np.zeros((len(feats), n_bins, ws.shape[1]))
            for j, f in enumerate(feats):
                np.add.at(hist[j], bins[rows, f], ws[rows])
            left = hist.cumsum(axis=1)
            right = stats - left
            imp_l, w_l = _impurity64(left, impurity)
            imp_r, w_r = _impurity64(right, impurity)
            gain = imp - (w_l * imp_l + w_r * imp_r) / max(weight, 1e-12)
            valid = (w_l >= 1) & (w_r >= 1) & (np.arange(n_bins) < n_bins - 1)
            gain = np.where(valid, gain, -np.inf)
            if weight > 0 and gain.max() > 1e-7:
                best = np.unravel_index(np.argmax(gain), gain.shape)
        if best is None:
            assert forest.is_leaf[t, g] and forest.feature[t, g] == -1
            assert level == depth or weight == 0 or gain.max() <= 1e-6
            checked["leaves"] += 1
            return
        assert not forest.is_leaf[t, g]
        f, thr = int(forest.feature[t, g]), forest.threshold[t, g]
        j = int(np.searchsorted(feats, f))
        assert j < len(feats) and feats[j] == f, f"node {g}: feature {f} outside its subset"
        b = int(np.flatnonzero(np.asarray(edges)[f] == thr)[0]) if (
            np.asarray(edges)[f] == thr).sum() == 1 else None
        if (j, b) != (int(best[0]), int(best[1])):
            # another candidate: it has to induce a partition of the same gain
            go_left = np.asarray(bins[rows, f]) <= (
                b if b is not None else np.searchsorted(np.asarray(edges)[f], thr))
            l_stats = ws[rows][go_left].sum(axis=0)
            i_l, w_l1 = _impurity64(l_stats, impurity)
            i_r, w_r1 = _impurity64(stats - l_stats, impurity)
            mine = imp - (w_l1 * i_l + w_r1 * i_r) / weight
            assert mine >= gain.max() - 1e-5 * max(1.0, abs(gain.max())), (g, mine, gain.max())
        np.testing.assert_allclose(forest.node_gain[t, g], gain.max(), rtol=2e-3, atol=1e-5)
        go_left = bins[rows, f] <= np.searchsorted(np.asarray(edges)[f], thr)
        checked["splits"] += 1
        visit(2 * g + 1, rows[go_left], level + 1)
        visit(2 * g + 2, rows[~go_left], level + 1)

    visit(0, np.arange(bins.shape[0]), 0)
    return checked


class TestLevelBuilder:
    @pytest.mark.parametrize("impurity", ["gini", "entropy", "variance"])
    @pytest.mark.parametrize("depth", [1, 2, 3, 4, 5, 6, 7])
    def test_matches_plain_recursive_grower(self, impurity, depth):
        from spark_rapids_ml_tpu.ops import trees

        n_bins, d, k = 8, 12, 4
        _, stats, edges, words, bins = _builder_case(impurity, seed=depth)
        # two widths of level program (2, then 8, ...) and tiles of 16 rows
        forest, w, k_feat = _grow(
            words, stats, edges, tree_ids=[0, 1, 2], depth=depth, impurity=impurity,
            n_bins=n_bins, d=d, k=k, tile_rows=16, tiles_per_step=4, min_level_width=2,
        )
        import jax

        for t in range(3):
            tree_key = jax.random.fold_in(k_feat, t)
            subsets_of = lambda g, tree_key=tree_key: np.asarray(  # noqa: E731
                trees.node_feature_subsets(tree_key, np.array([g], np.int32), d, k)[0]
            )
            ws = w[t][:, None].astype(np.float64) * stats.astype(np.float64)
            seen = _check_tree_recursively(
                forest, t, bins, ws, subsets_of, edges, depth, impurity, n_bins
            )
            assert seen["splits"] >= 1

    def test_node_subsets_are_k_distinct_and_the_nodes_own(self):
        import jax

        from spark_rapids_ml_tpu.ops import trees

        key = jax.random.key(3)
        ids = np.arange(200, dtype=np.int32)
        sub = np.asarray(trees.node_feature_subsets(key, ids, 30, 6))
        assert sub.shape == (200, 6) and sub.min() >= 0 and sub.max() < 30
        assert all(len(set(row)) == 6 and list(row) == sorted(row) for row in sub)
        assert len({tuple(row) for row in sub}) > 150  # they differ between nodes
        # a node's subset does not depend on which nodes are asked for beside it
        np.testing.assert_array_equal(
            np.asarray(trees.node_feature_subsets(key, ids[17:19], 30, 6)), sub[17:19]
        )
        # uniform: every feature about k/d of the time
        many = np.asarray(trees.node_feature_subsets(key, np.arange(6000, dtype=np.int32), 30, 6))
        share = np.bincount(many.ravel(), minlength=30) / 6000
        assert np.abs(share - 0.2).max() < 0.03
        all_of_them = np.asarray(trees.node_feature_subsets(key, ids[:3], 5, 9))
        np.testing.assert_array_equal(all_of_them, np.tile(np.arange(5), (3, 1)))

    @pytest.mark.parametrize("impurity", ["gini", "variance"])
    def test_forest_is_the_same_whatever_the_batch_and_the_tiles(self, impurity):
        n_bins, d, k, depth = 8, 12, 4, 5
        _, stats, edges, words, _ = _builder_case(impurity, seed=11)
        common = dict(depth=depth, impurity=impurity, n_bins=n_bins, d=d, k=k)
        whole, _, _ = _grow(words, stats, edges, tree_ids=[0, 1, 2, 3, 4], **common)
        parts = [
            _grow(words, stats, edges, tree_ids=ids, tile_rows=tile, tiles_per_step=step,
                  min_level_width=width, **common)[0]
            for ids, tile, step, width in (([0, 1, 2], 8, 3, 2), ([3, 4], 32, 1, 4))
        ]
        for name in whole._fields:
            joined = np.concatenate([getattr(p, name) for p in parts])
            if impurity == "gini" or name in ("feature", "is_leaf", "threshold"):
                np.testing.assert_array_equal(joined, getattr(whole, name), err_msg=name)
            else:  # float sums in another order
                np.testing.assert_allclose(joined, getattr(whole, name), rtol=1e-4, atol=1e-5,
                                           err_msg=name)

    def test_forest_is_the_same_on_a_four_device_mesh(self):
        import jax

        from spark_rapids_ml_tpu.parallel.mesh import make_mesh

        n_bins, d, k, depth = 8, 12, 4, 5
        _, stats, edges, words, _ = _builder_case("gini", n=263, seed=12)  # 263: padded to 264
        common = dict(tree_ids=[0, 1, 2], depth=depth, impurity="gini", n_bins=n_bins, d=d,
                      k=k, tile_rows=16, tiles_per_step=4)
        one, _, _ = _grow(words, stats, edges, **common)
        mesh = make_mesh(devices=jax.devices()[:4])
        four, _, _ = _grow(words, stats, edges, mesh=mesh, **common)
        for name in one._fields:
            np.testing.assert_array_equal(getattr(four, name), getattr(one, name), err_msg=name)

    def test_no_intermediate_grows_with_nodes_times_features(self):
        """The cell's size, traced only (no compile): nothing of M * d * B
        elements exists at any level; the largest intermediate is the
        selected histogram."""
        import jax
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops import trees

        n, d, n_bins, k, depth = 250_000, 3000, 128, 55, 13
        sds = jax.ShapeDtypeStruct
        key = jax.eval_shape(lambda: jax.random.key(0))
        jaxpr = jax.make_jaxpr(
            lambda *a: trees.grow_forest(
                *a, max_depth=depth, n_bins=n_bins, n_features=d, impurity="gini",
                feat_subset=k,
            )
        )(sds((n, trees.packed_width(d, n_bins)), jnp.int32), sds((n, 2), jnp.float32),
          sds((1, n), jnp.float32), sds((d, n_bins - 1), jnp.float32), key,
          sds((1,), jnp.int32))

        largest = [0]

        def walk(jp):
            for eqn in jp.eqns:
                for v in eqn.outvars:
                    size = int(np.prod(v.aval.shape)) if hasattr(v.aval, "shape") else 0
                    largest[0] = max(largest[0], size)
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

        walk(jaxpr.jaxpr)
        m_top = 2 ** (depth - 1)
        selected_hist = m_top * 2 * k * n_bins  # 57.7 M
        assert selected_hist <= largest[0] <= 2 * selected_hist
        assert largest[0] < m_top * d * n_bins / 10  # the dense form's 1.57e9

    def test_level_groups_and_tiles_follow_from_shapes(self):
        from spark_rapids_ml_tpu.ops import trees

        assert trees.level_groups(13) == [(0, 9, 256), (9, 11, 1024), (11, 13, 4096)]
        assert trees.level_groups(5) == [(0, 5, 16)]
        assert trees.level_groups(4, min_width=2) == [(0, 2, 2), (2, 4, 8)]
        assert trees.level_groups(0) == []
        assert trees.level_tiles(250_000, 4096, 128, 32) == 6080
        assert trees.step_tiles_a_tree(13) == 2 and trees.step_tiles_a_tree(1) == 32
        assert trees.step_tiles_a_tree(100) == 1
        assert trees.bins_per_word(128) == 4 and trees.bins_per_word(300) == 2
        assert trees.packed_width(3000, 128) == 750 and trees.packed_width(7, 300) == 4

    def test_more_than_256_bins_pack_two_to_a_word(self):
        n_bins, d, k = 300, 5, 3
        _, stats, edges, words, bins = _builder_case("gini", n=400, d=5, n_bins=n_bins, seed=4)
        assert words.shape == (400, 3) and bins.max() > 256
        forest, w, k_feat = _grow(words, stats, edges, tree_ids=[0], depth=3, impurity="gini",
                                  n_bins=n_bins, d=d, k=k, tile_rows=16, tiles_per_step=4)
        import jax

        from spark_rapids_ml_tpu.ops import trees

        tree_key = jax.random.fold_in(k_feat, 0)
        subsets_of = lambda g: np.asarray(  # noqa: E731
            trees.node_feature_subsets(tree_key, np.array([g], np.int32), d, k)[0])
        ws = w[0][:, None].astype(np.float64) * stats.astype(np.float64)
        _check_tree_recursively(forest, 0, bins, ws, subsets_of, edges, 3, "gini", n_bins)

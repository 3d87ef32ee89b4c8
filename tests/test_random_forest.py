"""RandomForest tests — oracle is handcrafted separable data + scikit-learn.

Beyond-the-reference capability (reference ships only PCA — SURVEY.md §2),
so the test pattern follows the suite's convention for such models: exact
recovery on data with a known tree structure, statistical agreement with a
CPU oracle on synthetic data, determinism, and persistence round-trips.
"""

import numpy as np
import pytest

from spark_rapids_ml_tpu.models.random_forest import (
    RandomForestClassificationModel,
    RandomForestClassifier,
    RandomForestRegressionModel,
    RandomForestRegressor,
    resolve_feature_subset,
)


def _blobs(rng, n_per=100, d=6):
    """Three well-separated gaussian blobs."""
    centers = np.array(
        [[4.0, 0, 0, 0, 0, 0], [0, 4.0, 0, 0, 0, 0], [0, 0, 4.0, 0, 0, 0]]
    )[:, :d]
    xs, ys = [], []
    for c_i, c in enumerate(centers):
        xs.append(rng.normal(size=(n_per, d)) * 0.5 + c)
        ys.append(np.full(n_per, c_i))
    return np.concatenate(xs), np.concatenate(ys).astype(float)


class TestClassifier:
    def test_single_tree_exact_split(self):
        # One feature cleanly separates the classes at x <= ~0.5: a depth-1
        # tree must find that split and classify perfectly.
        rng = np.random.default_rng(0)
        x = np.zeros((200, 3))
        x[:, 0] = np.concatenate([rng.uniform(-1, 0.4, 100), rng.uniform(0.6, 2, 100)])
        x[:, 1] = rng.normal(size=200)
        x[:, 2] = rng.normal(size=200)
        y = np.concatenate([np.zeros(100), np.ones(100)])
        model = (
            RandomForestClassifier()
            .setNumTrees(1)
            .setMaxDepth(1)
            .setBootstrap(False)
            .setSeed(3)
            .fit((x, y))
        )
        preds = model.predict(x)
        assert np.array_equal(preds, y.astype(int))
        feat = np.asarray(model._forest.feature)
        assert feat[0, 0] == 0  # split on the informative feature
        thr = float(np.asarray(model._forest.threshold)[0, 0])
        assert 0.3 <= thr <= 0.7

    def test_blobs_accuracy(self, rng):
        x, y = _blobs(rng)
        model = RandomForestClassifier().setNumTrees(15).setMaxDepth(4).setSeed(1).fit((x, y))
        acc = np.mean(model.predict(x) == y)
        assert acc >= 0.98
        probs = model.predictProbability(x)
        assert probs.shape == (len(y), 3)
        np.testing.assert_allclose(probs.sum(axis=1), 1.0, atol=1e-5)

    def test_matches_sklearn_accuracy(self, rng):
        sklearn = pytest.importorskip("sklearn.ensemble")
        x, y = _blobs(rng, n_per=150)
        x_test, y_test = _blobs(np.random.default_rng(7), n_per=50)
        ours = (
            RandomForestClassifier().setNumTrees(20).setMaxDepth(5).setSeed(2).fit((x, y))
        )
        theirs = sklearn.RandomForestClassifier(
            n_estimators=20, max_depth=5, random_state=2
        ).fit(x, y)
        acc_ours = np.mean(ours.predict(x_test) == y_test)
        acc_theirs = theirs.score(x_test, y_test)
        assert acc_ours >= acc_theirs - 0.05

    def test_determinism(self, rng):
        x, y = _blobs(rng, n_per=40)
        m1 = RandomForestClassifier().setNumTrees(5).setSeed(11).fit((x, y))
        m2 = RandomForestClassifier().setNumTrees(5).setSeed(11).fit((x, y))
        np.testing.assert_array_equal(
            np.asarray(m1._forest.feature), np.asarray(m2._forest.feature)
        )
        np.testing.assert_array_equal(
            np.asarray(m1._forest.threshold), np.asarray(m2._forest.threshold)
        )

    def test_entropy_impurity(self, rng):
        x, y = _blobs(rng, n_per=50)
        model = (
            RandomForestClassifier()
            .setImpurity("entropy")
            .setNumTrees(8)
            .setSeed(4)
            .fit((x, y))
        )
        assert np.mean(model.predict(x) == y) >= 0.95

    def test_feature_importances(self, rng):
        # Only feature 0 is informative: it must dominate the importances.
        x = rng.normal(size=(300, 5))
        y = (x[:, 0] > 0).astype(float)
        model = RandomForestClassifier().setNumTrees(10).setMaxDepth(3).setSeed(5).fit((x, y))
        imp = model.featureImportances
        assert imp.shape == (5,)
        np.testing.assert_allclose(imp.sum(), 1.0, atol=1e-6)
        assert imp[0] > 0.8

    def test_persistence_roundtrip(self, tmp_path, rng):
        x, y = _blobs(rng, n_per=30)
        model = RandomForestClassifier().setNumTrees(4).setMaxDepth(3).setSeed(6).fit((x, y))
        path = str(tmp_path / "rfc")
        model.save(path)
        loaded = RandomForestClassificationModel.load(path)
        assert loaded.numClasses == 3
        assert loaded.numFeatures == x.shape[1]
        np.testing.assert_array_equal(model.predict(x), loaded.predict(x))
        np.testing.assert_allclose(
            model.predictProbability(x), loaded.predictProbability(x), atol=1e-6
        )

    def test_min_instances_per_node(self, rng):
        x, y = _blobs(rng, n_per=30)
        model = (
            RandomForestClassifier()
            .setNumTrees(3)
            .setMaxDepth(6)
            .setMinInstancesPerNode(20)
            .setSeed(8)
            .fit((x, y))
        )
        # With a high floor, trees must stay shallow: few split nodes.
        n_splits = int(np.sum(np.asarray(model._forest.feature) >= 0))
        assert n_splits <= 3 * 7  # far fewer than the 63 possible per tree

    def test_transform_pandas(self, rng):
        pd = pytest.importorskip("pandas")
        x, y = _blobs(rng, n_per=20)
        df = pd.DataFrame(x, columns=[f"f{i}" for i in range(x.shape[1])])
        df["label"] = y
        model = RandomForestClassifier().setNumTrees(3).setSeed(9).fit(df)
        out = model.transform(df)
        assert "prediction" in out.columns
        assert "probability" in out.columns


class TestRegressor:
    def test_piecewise_constant_recovery(self):
        # y is a step function of feature 0; a depth-2 tree nails it.
        rng = np.random.default_rng(1)
        x = rng.uniform(0, 4, size=(400, 2))
        y = np.floor(x[:, 0])  # steps at 1, 2, 3
        model = (
            RandomForestRegressor()
            .setNumTrees(1)
            .setMaxDepth(2)
            .setMaxBins(128)  # bin edges are quantiles; more bins -> edges
            .setBootstrap(False)  # land closer to the true step boundaries
            .setSeed(0)
            .fit((x, y))
        )
        preds = model.predict(x)
        assert np.sqrt(np.mean((preds - y) ** 2)) < 0.15

    def test_matches_sklearn_rmse(self, rng):
        sklearn = pytest.importorskip("sklearn.ensemble")
        x = rng.uniform(-2, 2, size=(500, 4))
        y = np.sin(x[:, 0]) + 0.5 * x[:, 1] ** 2 + 0.1 * rng.normal(size=500)
        # Spark's "auto" means onethird of features per split for regression;
        # sklearn's default is all features — pin "all" for a fair comparison.
        ours = (
            RandomForestRegressor()
            .setNumTrees(20)
            .setMaxDepth(6)
            .setFeatureSubsetStrategy("all")
            .setSeed(3)
            .fit((x, y))
        )
        theirs = sklearn.RandomForestRegressor(
            n_estimators=20, max_depth=6, random_state=3
        ).fit(x, y)
        rmse_ours = np.sqrt(np.mean((ours.predict(x) - y) ** 2))
        rmse_theirs = np.sqrt(np.mean((theirs.predict(x) - y) ** 2))
        assert rmse_ours <= rmse_theirs * 1.5

    def test_subsampling_and_no_bootstrap(self, rng):
        x = rng.normal(size=(200, 3))
        y = x[:, 0] * 2.0
        model = (
            RandomForestRegressor()
            .setNumTrees(10)
            .setSubsamplingRate(0.7)
            .setBootstrap(False)
            .setFeatureSubsetStrategy("all")
            .setSeed(2)
            .fit((x, y))
        )
        rmse = np.sqrt(np.mean((model.predict(x) - y) ** 2))
        assert rmse < 0.6

    def test_large_label_offset(self, rng):
        # Variance impurity must survive labels with |mean| >> std: the raw
        # E[y^2] - mean^2 form in float32 cancels catastrophically; the
        # implementation centers labels first, so structure is preserved.
        x = rng.normal(size=(300, 3))
        y = 2.0 * x[:, 0] + 10_000.0
        model = (
            RandomForestRegressor()
            .setNumTrees(10)
            .setMaxDepth(6)
            .setFeatureSubsetStrategy("all")
            .setSeed(2)
            .fit((x, y))
        )
        rmse = np.sqrt(np.mean((model.predict(x) - y) ** 2))
        assert rmse < 0.6  # same bar as the uncentered equivalent

    def test_persistence_roundtrip(self, tmp_path, rng):
        x = rng.normal(size=(100, 3))
        y = x[:, 0] + x[:, 1]
        model = RandomForestRegressor().setNumTrees(4).setMaxDepth(3).setSeed(1).fit((x, y))
        path = str(tmp_path / "rfr")
        model.save(path)
        loaded = RandomForestRegressionModel.load(path)
        np.testing.assert_allclose(model.predict(x), loaded.predict(x), atol=1e-6)


class TestParams:
    def test_feature_subset_resolution(self):
        assert resolve_feature_subset("auto", 100, 20, True) == 10
        assert resolve_feature_subset("auto", 100, 20, False) == 34  # ceil, like Spark
        assert resolve_feature_subset("auto", 100, 1, True) == 100
        assert resolve_feature_subset("all", 9, 5, True) == 9
        assert resolve_feature_subset("sqrt", 100, 5, False) == 10
        assert resolve_feature_subset("log2", 64, 5, True) == 6
        assert resolve_feature_subset("onethird", 9, 5, True) == 3
        assert resolve_feature_subset("onethird", 4, 5, True) == 2  # ceil(4/3)
        assert resolve_feature_subset("5", 9, 5, True) == 5
        assert resolve_feature_subset("0.5", 10, 5, True) == 5
        # "1.0" is a FRACTION in Spark's grammar (all features), not a count.
        assert resolve_feature_subset("1.0", 10, 5, True) == 10
        with pytest.raises(ValueError):
            resolve_feature_subset("bogus", 10, 5, True)

    def test_param_validation(self):
        with pytest.raises(ValueError):
            RandomForestClassifier().setNumTrees(0)
        with pytest.raises(ValueError):
            RandomForestClassifier().setMaxDepth(20)
        with pytest.raises(ValueError):
            RandomForestClassifier().setSubsamplingRate(0.0)
        with pytest.raises(ValueError):
            RandomForestClassifier().setImpurity("variance")
        with pytest.raises(ValueError):
            RandomForestRegressor().setImpurity("gini")
        with pytest.raises(ValueError):
            RandomForestClassifier().fit((np.zeros((4, 2)), np.array([0.5, 1, 0, 1])))

    def test_defaults_match_spark(self):
        rf = RandomForestClassifier()
        assert rf.getNumTrees() == 20
        assert rf.getMaxDepth() == 5
        assert rf.getMaxBins() == 32
        assert rf.getImpurity() == "gini"
        assert rf.getFeatureSubsetStrategy() == "auto"
        assert rf.getSubsamplingRate() == 1.0
        assert RandomForestRegressor().getImpurity() == "variance"


class TestNumClassesHint:
    """setNumClasses: the Spark label-metadata analogue (fit dispatches
    without a label scan; r5)."""

    def test_hinted_fit_matches_inferred(self, rng):
        from spark_rapids_ml_tpu.classification import RandomForestClassifier

        x = rng.normal(size=(300, 5))
        y = ((x[:, 0] + x[:, 1]) > 0).astype(float)
        inferred = (
            RandomForestClassifier().setNumTrees(6).setMaxDepth(4).setSeed(3)
            .fit((x, y))
        )
        hinted = (
            RandomForestClassifier().setNumTrees(6).setMaxDepth(4).setSeed(3)
            .setNumClasses(2).fit((x, y))
        )
        assert hinted.numClasses == 2
        np.testing.assert_allclose(
            hinted.predictProbability(x), inferred.predictProbability(x),
            atol=1e-6,
        )

    def test_hinted_device_fit_no_readback(self, rng):
        """With the hint (and no weightCol), a device-resident fit must
        dispatch without ANY device->host transfer before the forest
        arrays are touched."""
        import jax
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.classification import RandomForestClassifier

        x = jnp.asarray(rng.normal(size=(200, 4)), dtype=jnp.float32)
        y = (x[:, 0] > 0).astype(jnp.float32)
        est = (
            RandomForestClassifier().setNumTrees(4).setMaxDepth(3).setSeed(0)
            .setNumClasses(2)
        )
        with jax.transfer_guard_device_to_host("disallow"):
            model = est.fit((x, y))
        # Root weight is the tree's bootstrap-draw total (~n, Poisson).
        root_w = float(np.asarray(model._forest.node_weight[0, 0]))
        assert abs(root_w - 200.0) < 5 * np.sqrt(200.0)
        assert model.numClasses == 2

    def test_hint_survives_copy_and_validates(self, rng):
        from spark_rapids_ml_tpu.classification import RandomForestClassifier

        est = RandomForestClassifier().setNumClasses(3)
        assert est.copy().getNumClasses() == 3
        with pytest.raises(ValueError, match="numClasses"):
            RandomForestClassifier().setNumClasses(1)

    def test_bootstrap_weights_clamped_integral(self):
        """The 256 clamp that makes unweighted exactness static: weights
        stay integral and within the bf16-exact product bound."""
        import jax

        from spark_rapids_ml_tpu.ops.trees import sample_weights

        w = np.asarray(sample_weights(jax.random.key(1), np.arange(4), 50_000, 1.0, True))
        assert np.array_equal(w, np.rint(w))
        assert w.max() <= 256.0
        assert w.mean() == pytest.approx(1.0, abs=0.05)
        # a tree's draw is its own: a batch draws what the whole forest would
        one = np.asarray(sample_weights(jax.random.key(1), np.array([2]), 50_000, 1.0, True))
        np.testing.assert_array_equal(one[0], w[2])



class TestForestAdmission:
    def _xy(self, rng, n=300, d=6):
        x = rng.normal(size=(n, d))
        return x, (x[:, 0] > 0).astype(float)

    def test_over_budget_raises_before_any_program(self, rng, monkeypatch):
        from spark_rapids_ml_tpu.core.membudget import FitMemoryError
        from spark_rapids_ml_tpu.models import random_forest

        def never(*a, **k):
            raise AssertionError("a program was built for a fit that cannot fit")

        monkeypatch.setattr(random_forest, "quantize_and_bin", never)
        monkeypatch.setattr(random_forest, "grow_forest", never)
        monkeypatch.setenv("TPUML_FIT_MEM_BUDGET", "200000")
        import jax.numpy as jnp

        x, y = self._xy(rng)
        with pytest.raises(FitMemoryError, match="one tree"):
            RandomForestClassifier().setNumTrees(3).setMaxDepth(6).fit(
                (jnp.asarray(x, jnp.float32), jnp.asarray(y))
            )

    def test_price_of_the_cell(self):
        """At the benchmark's shapes: bins 0.77 GB, a tree in flight about
        0.9 GB, so a free chip holds all 13; the dense form's depth 13 at
        3000 columns (12.6 GB of histogram a tree) is refused."""
        from spark_rapids_ml_tpu.ops.trees import builder_bytes

        resident, per_tree, prepare = builder_bytes(250_000, 3000, 128, 55, 2, 13)
        assert 0.7e9 < resident < 0.8e9
        assert 0.5e9 < per_tree < 1.5e9
        assert 5.9e9 < prepare < 6.1e9
        assert builder_bytes(250_000, 3000, 128, 3000, 2, 13)[1] > 20e9
        host = builder_bytes(250_000, 3000, 128, 55, 2, 13, rows_resident=False)
        assert host[0] - resident == 250_000 * 3000 * 4

    def test_batches_follow_from_the_budget_and_change_nothing(self, rng, monkeypatch):
        from spark_rapids_ml_tpu.ops.trees import builder_bytes
        from spark_rapids_ml_tpu.utils import tracing

        x, y = self._xy(rng)
        est = lambda: RandomForestClassifier().setNumTrees(5).setMaxDepth(4).setSeed(2)  # noqa: E731
        whole = est().fit((x, y))
        resident, per_tree, prepare = builder_bytes(300, 6, 32, 3, 2, 4, rows_resident=False)
        # room for two trees beside the rows and the bins
        monkeypatch.setenv("TPUML_FIT_MEM_BUDGET", str(resident + max(prepare, 2 * per_tree) + 4 * 300 * 8 * 4))
        before = tracing.counters("forest.")
        batched = est().fit((x, y))
        moved = {k: v - before.get(k, 0) for k, v in tracing.counters("forest.").items()}
        assert moved["forest.grow.tree_batches"] == 3  # 2 + 2 + 1
        for name in whole._forest._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(batched._forest, name)), np.asarray(getattr(whole._forest, name))
            )

    def test_counters_equal_their_closed_forms(self, rng):
        from spark_rapids_ml_tpu.utils import tracing

        x, y = self._xy(rng, n=500, d=10)
        before = tracing.counters("forest.")
        RandomForestClassifier().setNumTrees(4).setMaxDepth(3).setMaxBins(16).setSeed(1).fit((x, y))
        moved = {k: v - before.get(k, 0) for k, v in tracing.counters("forest.").items()}
        k = 4  # ceil(sqrt(10))
        assert moved == {
            "forest.bins.bytes": 500 * 3 * 4,  # ten uint8 ids in three words a row
            "forest.grow.tree_batches": 1,
            "forest.grow.tree_levels": 4 * 3,
            "forest.grow.selected_elems": 4 * 3 * 500 * k,
            "forest.grow.hist_cells": 4 * (2**3 - 1) * k * 16 * 2,
            # four byte planes a step; one step of 8 tiles a level (ceil(500 / 128) + 4)
            "forest.grow.select_passes": 4 * 3,
        }

    @pytest.mark.parametrize("n_bins, planes, digits", [(128, 4, 1), (300, 2, 2)])
    def test_select_passes_are_counted_at_dispatch(self, rng, n_bins, planes, digits):
        from spark_rapids_ml_tpu.utils import tracing

        x, y = self._xy(rng, n=2000, d=9)
        # three trees: 10 tiles a step; a level of width 4 has ceil(2000 / 128) + 4 = 20
        # tiles: two steps a level, three levels
        steps = 2 * 3
        for _ in range(2):  # a second fit of the same shape counts as much again
            before = tracing.counters("forest.").get("forest.grow.select_passes", 0)
            est = RandomForestClassifier().setNumTrees(3).setMaxDepth(3).setMaxBins(n_bins)
            est.setSeed(1).fit((x, y))
            moved = tracing.counters("forest.")["forest.grow.select_passes"] - before
            assert moved == planes * digits * steps

"""Device-resident input across EVERY accelerator family.

Round 3 proved the jax.Array fast path for PCA only; these tests pin the
generalized contract for KMeans, Linear/LogisticRegression, RandomForest,
kNN/ANN, DBSCAN, and UMAP:

  1. a device array fed to the public estimator fits WITHOUT the
     ``as_matrix`` host-float64 round trip (guarded two ways: a
     ``jax.transfer_guard_device_to_host`` context for the strict
     families, and an ``as_matrix``-rejects-device-arrays tripwire for
     all of them);
  2. the fitted model matches the host-input fit;
  3. fitted state stays on device until read (lazy host conversion), and
     pickling materializes host float64 — never live device buffers;
  4. device queries to model predict/transform/kneighbors return device
     arrays (no host pull the caller didn't ask for).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import spark_rapids_ml_tpu.core.data as core_data
from spark_rapids_ml_tpu.classification import (
    LogisticRegression,
    RandomForestClassifier,
)
from spark_rapids_ml_tpu.clustering import DBSCAN, KMeans
from spark_rapids_ml_tpu.manifold import UMAP
from spark_rapids_ml_tpu.neighbors import ApproximateNearestNeighbors, NearestNeighbors
from spark_rapids_ml_tpu.regression import LinearRegression, RandomForestRegressor


@pytest.fixture(scope="module")
def blobs():
    rng = np.random.default_rng(42)
    centers = rng.normal(scale=8.0, size=(4, 12))
    x = np.concatenate(
        [rng.normal(loc=c, scale=0.6, size=(200, 12)) for c in centers]
    ).astype(np.float32)
    y = np.repeat(np.arange(4), 200).astype(np.float32)
    perm = rng.permutation(x.shape[0])
    return x[perm], y[perm]


@pytest.fixture(autouse=True)
def no_device_as_matrix(monkeypatch):
    """Tripwire: the estimator paths must never densify a jax.Array
    through as_matrix (the r3 choke point, core/data.py)."""
    orig = core_data.as_matrix

    def guarded(data, dtype=None):
        assert not core_data.is_device_array(data), (
            "as_matrix called with a device array — host round trip"
        )
        return orig(data, dtype=dtype)

    monkeypatch.setattr(core_data, "as_matrix", guarded)
    yield


class TestKMeansDevice:
    def test_fit_no_device_to_host_transfer(self, blobs):
        """THE regression test for device residence: the whole fit under a
        disallow-device-to-host guard — not one byte may come back."""
        x, _ = blobs
        xd = jnp.asarray(x)
        jax.block_until_ready(xd)
        with jax.transfer_guard_device_to_host("disallow"):
            model = KMeans().setK(4).setMaxIter(8).fit(xd)
            jax.block_until_ready(model._centers_raw)
        assert isinstance(model._centers_raw, jax.Array)

    def test_matches_host_fit(self, blobs):
        x, _ = blobs
        dev = KMeans().setK(4).setSeed(3).fit(jnp.asarray(x))
        host = KMeans().setK(4).setSeed(3).fit(x.astype(np.float64))
        assert np.allclose(
            np.sort(dev.clusterCenters(), axis=0),
            np.sort(host.clusterCenters(), axis=0),
            atol=1e-3,
        )
        assert dev.trainingCost == pytest.approx(host.trainingCost, rel=1e-4)

    def test_model_lazy_and_pickles_host(self, blobs):
        cloudpickle = pytest.importorskip("cloudpickle")

        x, _ = blobs
        model = KMeans().setK(3).fit(jnp.asarray(x))
        assert isinstance(model._centers_raw, jax.Array)
        assert model._centers_np is None  # no host conversion yet
        dup = cloudpickle.loads(cloudpickle.dumps(model))
        assert isinstance(dup._centers_raw, np.ndarray)
        assert np.allclose(dup.clusterCenters(), model.clusterCenters())
        assert dup.trainingCost == pytest.approx(model.trainingCost)

    def test_device_predict_returns_device(self, blobs):
        x, _ = blobs
        xd = jnp.asarray(x)
        model = KMeans().setK(3).fit(xd)
        labels = model.predict(xd)
        assert isinstance(labels, jax.Array)
        assert labels.shape == (x.shape[0],)
        host_labels = model.predict(x.astype(np.float64))
        assert np.array_equal(np.asarray(labels), host_labels)

    def test_mesh_device_input_pads_with_mask(self, blobs):
        from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS
        from jax.sharding import Mesh

        x, _ = blobs
        n_dev = len(jax.devices())
        if n_dev < 2:
            pytest.skip("needs a multi-device mesh")
        mesh = Mesh(np.array(jax.devices()), (DATA_AXIS,))
        # Deliberately indivisible row count: the funnel pads ON DEVICE
        # with a zero mask instead of raising (all consumers mask-aware).
        xd = jnp.asarray(x[: (x.shape[0] // n_dev) * n_dev + 1])
        model = KMeans(mesh=mesh).setK(4).setSeed(3).fit(xd)
        host = KMeans().setK(4).setSeed(3).fit(np.asarray(xd, dtype=np.float64))
        assert np.allclose(
            np.sort(model.clusterCenters(), axis=0),
            np.sort(host.clusterCenters(), axis=0),
            atol=1e-2,
        )


class TestLinearRegressionDevice:
    def _xy(self, rng=None):
        rng = rng or np.random.default_rng(7)
        x = rng.normal(size=(600, 10)).astype(np.float32)
        coef = rng.normal(size=10)
        y = (x @ coef + 0.5).astype(np.float32)
        return x, y, coef

    def test_fit_no_device_to_host_transfer(self):
        x, y, _ = self._xy()
        xd, yd = jnp.asarray(x), jnp.asarray(y)
        jax.block_until_ready((xd, yd))
        with jax.transfer_guard_device_to_host("disallow"):
            model = LinearRegression().fit((xd, yd))
            jax.block_until_ready(model._coef_raw)
        assert isinstance(model._coef_raw, jax.Array)

    def test_matches_host_fit_and_truth(self):
        x, y, coef = self._xy()
        dev = LinearRegression().fit((jnp.asarray(x), jnp.asarray(y)))
        host = LinearRegression().fit((x.astype(np.float64), y.astype(np.float64)))
        assert np.allclose(dev.coefficients, host.coefficients, atol=1e-3)
        assert dev.intercept == pytest.approx(host.intercept, abs=1e-3)
        assert np.allclose(dev.coefficients, coef, atol=1e-2)

    def test_device_predict_returns_device(self):
        x, y, _ = self._xy()
        xd = jnp.asarray(x)
        model = LinearRegression().fit((xd, jnp.asarray(y)))
        pred = model.predict(xd)
        assert isinstance(pred, jax.Array)
        assert np.allclose(np.asarray(pred), model.predict(x.astype(np.float64)), atol=1e-4)

    def test_pickle_materializes_host(self):
        cloudpickle = pytest.importorskip("cloudpickle")

        x, y, _ = self._xy()
        model = LinearRegression().fit((jnp.asarray(x), jnp.asarray(y)))
        dup = cloudpickle.loads(cloudpickle.dumps(model))
        assert isinstance(dup._coef_raw, np.ndarray)
        assert np.allclose(dup.coefficients, model.coefficients)

    @pytest.mark.parametrize("device_y", [False, True])
    def test_mismatched_xy_lengths_raise(self, device_y):
        # Regression (r4 review): prepare_labels used to zero-pad a short
        # y silently — phantom rows trained into the model.
        x, y, _ = self._xy()
        y_short = jnp.asarray(y[:300]) if device_y else y[:300]
        with pytest.raises(ValueError, match="entries"):
            LinearRegression().fit((jnp.asarray(x), y_short))
        with pytest.raises(ValueError, match="entries"):
            LogisticRegression().fit(
                (jnp.asarray(x), (jnp.asarray(y[:300]) > 0).astype(jnp.float32))
            )

    def test_dd_rejected_for_device_input(self):
        x, y, _ = self._xy()
        with pytest.raises(ValueError, match="dd"):
            LinearRegression().setPrecision("dd").fit(
                (jnp.asarray(x), jnp.asarray(y))
            )

    def test_elastic_net_device_input(self):
        x, y, _ = self._xy()
        dev = (
            LinearRegression()
            .setRegParam(0.1)
            .setElasticNetParam(0.5)
            .fit((jnp.asarray(x), jnp.asarray(y)))
        )
        host = (
            LinearRegression()
            .setRegParam(0.1)
            .setElasticNetParam(0.5)
            .fit((x.astype(np.float64), y.astype(np.float64)))
        )
        assert np.allclose(dev.coefficients, host.coefficients, atol=1e-3)


class TestLogisticRegressionDevice:
    def _xy(self, classes=2):
        rng = np.random.default_rng(11)
        x = rng.normal(size=(800, 8)).astype(np.float32)
        w = rng.normal(size=(8, classes))
        y = np.argmax(x @ w + rng.normal(scale=0.1, size=(800, classes)), axis=1)
        return x, y.astype(np.float32)

    @pytest.mark.parametrize("classes", [2, 3])
    def test_matches_host_fit(self, classes):
        # regParam > 0 keeps the optimum bounded (the blobs are separable,
        # so the unregularized optimum is at infinity and run-to-run
        # comparison of raw weights is meaningless).
        x, y = self._xy(classes)
        dev = (
            LogisticRegression()
            .setRegParam(0.05)
            .fit((jnp.asarray(x), jnp.asarray(y)))
        )
        host = (
            LogisticRegression()
            .setRegParam(0.05)
            .fit((x.astype(np.float64), y.astype(np.float64)))
        )
        assert dev.numClasses == host.numClasses == max(classes, 2)
        assert np.allclose(dev.weights, host.weights, atol=5e-3)
        pred_d = dev.predict(x.astype(np.float64))
        pred_h = host.predict(x.astype(np.float64))
        assert np.mean(pred_d == pred_h) > 0.995

    def test_fractional_device_labels_raise(self):
        x, y = self._xy()
        y = y.copy()
        y[3] = 0.5
        with pytest.raises(ValueError, match="integers"):
            LogisticRegression().fit((jnp.asarray(x), jnp.asarray(y)))

    def test_device_predict_returns_device(self):
        x, y = self._xy()
        xd = jnp.asarray(x)
        model = LogisticRegression().fit((xd, jnp.asarray(y)))
        labels = model.predict(xd)
        probs = model.predictProbability(xd)
        assert isinstance(labels, jax.Array) and isinstance(probs, jax.Array)
        assert isinstance(model._w_raw, jax.Array)  # lazy fitted state

    def test_pickle_materializes_host(self):
        cloudpickle = pytest.importorskip("cloudpickle")

        x, y = self._xy()
        model = LogisticRegression().fit((jnp.asarray(x), jnp.asarray(y)))
        dup = cloudpickle.loads(cloudpickle.dumps(model))
        assert isinstance(dup._w_raw, np.ndarray)
        assert np.allclose(dup.weights, model.weights)


class TestRandomForestDevice:
    def test_classifier_matches_host_fit(self, blobs):
        x, y = blobs
        dev = (
            RandomForestClassifier()
            .setNumTrees(5)
            .setMaxDepth(4)
            .fit((jnp.asarray(x), jnp.asarray(y)))
        )
        host = (
            RandomForestClassifier()
            .setNumTrees(5)
            .setMaxDepth(4)
            .fit((x.astype(np.float64), y.astype(np.float64)))
        )
        xq = x.astype(np.float64)
        assert np.array_equal(dev.predict(xq), host.predict(xq))

    def test_classifier_device_predict_returns_device(self, blobs):
        x, y = blobs
        xd = jnp.asarray(x)
        model = (
            RandomForestClassifier().setNumTrees(4).setMaxDepth(3).fit((xd, jnp.asarray(y)))
        )
        probs = model.predictProbability(xd)
        assert isinstance(probs, jax.Array)

    def test_regressor_matches_host_fit(self):
        rng = np.random.default_rng(5)
        x = rng.normal(size=(500, 6)).astype(np.float32)
        y = (np.sin(x[:, 0]) + x[:, 1] ** 2).astype(np.float32)
        dev = (
            RandomForestRegressor()
            .setNumTrees(5)
            .setMaxDepth(4)
            .fit((jnp.asarray(x), jnp.asarray(y)))
        )
        host = (
            RandomForestRegressor()
            .setNumTrees(5)
            .setMaxDepth(4)
            .fit((x.astype(np.float64), y.astype(np.float64)))
        )
        xq = x.astype(np.float64)
        assert np.allclose(dev.predict(xq), host.predict(xq), atol=1e-5)


class TestNeighborsDevice:
    def _items_queries(self):
        rng = np.random.default_rng(9)
        return (
            rng.normal(size=(500, 16)).astype(np.float32),
            rng.normal(size=(40, 16)).astype(np.float32),
        )

    def test_knn_device_end_to_end(self):
        items, q = self._items_queries()
        items_d, q_d = jnp.asarray(items), jnp.asarray(q)
        model = NearestNeighbors().setK(5).fit(items_d)
        assert isinstance(model._items_raw, jax.Array)
        d, idx = model.kneighbors(q_d)
        assert isinstance(d, jax.Array) and isinstance(idx, jax.Array)
        host_model = NearestNeighbors().setK(5).fit(items.astype(np.float64))
        d_h, idx_h = host_model.kneighbors(q.astype(np.float64))
        assert np.array_equal(np.asarray(idx), idx_h)
        assert np.allclose(np.asarray(d), d_h, atol=1e-4)

    def test_knn_no_device_to_host_transfer(self):
        items, q = self._items_queries()
        items_d, q_d = jnp.asarray(items), jnp.asarray(q)
        jax.block_until_ready((items_d, q_d))
        with jax.transfer_guard_device_to_host("disallow"):
            model = NearestNeighbors().setK(5).fit(items_d)
            d, idx = model.kneighbors(q_d)
            jax.block_until_ready((d, idx))

    @pytest.mark.parametrize("algo", ["brute", "brute_approx"])
    def test_ann_brute_device_end_to_end(self, algo):
        items, q = self._items_queries()
        model = (
            ApproximateNearestNeighbors()
            .setK(5)
            .setAlgorithm(algo)
            .fit(jnp.asarray(items))
        )
        d, idx = model.kneighbors(jnp.asarray(q))
        assert isinstance(d, jax.Array) and isinstance(idx, jax.Array)
        host = (
            ApproximateNearestNeighbors()
            .setK(5)
            .setAlgorithm(algo)
            .fit(items.astype(np.float64))
        )
        d_h, idx_h = host.kneighbors(q.astype(np.float64))
        assert np.array_equal(np.asarray(idx), idx_h)

    def test_ann_ivfflat_device_items(self):
        # IVF list packing is host-side by design (one pull at build);
        # device queries still come back as device arrays.
        items, q = self._items_queries()
        model = (
            ApproximateNearestNeighbors()
            .setK(5)
            .setAlgorithm("ivfflat")
            .setAlgoParams({"nlist": 8, "nprobe": 8})
            .fit(jnp.asarray(items))
        )
        d, idx = model.kneighbors(jnp.asarray(q))
        assert isinstance(d, jax.Array) and isinstance(idx, jax.Array)

    def test_model_pickles_host(self):
        cloudpickle = pytest.importorskip("cloudpickle")

        items, _ = self._items_queries()
        model = NearestNeighbors().setK(3).fit(jnp.asarray(items))
        dup = cloudpickle.loads(cloudpickle.dumps(model))
        assert isinstance(dup._items_raw, np.ndarray)


class TestDBSCANDevice:
    def test_fit_matches_host(self, blobs):
        x, _ = blobs
        dev = DBSCAN().setEps(1.5).setMinSamples(5).fit(jnp.asarray(x))
        host = DBSCAN().setEps(1.5).setMinSamples(5).fit(x.astype(np.float64))
        assert np.array_equal(dev.labels_, host.labels_)
        assert isinstance(dev._fitted_raw, jax.Array)  # rows stay resident

    def test_pickle_materializes_host(self, blobs):
        cloudpickle = pytest.importorskip("cloudpickle")

        x, _ = blobs
        model = DBSCAN().setEps(1.5).setMinSamples(5).fit(jnp.asarray(x))
        dup = cloudpickle.loads(cloudpickle.dumps(model))
        assert isinstance(dup._fitted_raw, np.ndarray)
        assert np.array_equal(dup.labels_, model.labels_)


class TestUMAPDevice:
    def test_fit_matches_host(self, blobs):
        x, _ = blobs
        x = x[:300]
        dev = UMAP().setNNeighbors(10).setSeed(2).fit(jnp.asarray(x))
        host = UMAP().setNNeighbors(10).setSeed(2).fit(x.astype(np.float64))
        assert isinstance(dev._emb_raw, jax.Array)  # stays resident
        assert dev.embedding.shape == host.embedding.shape
        # Same seed + same graph => same layout (float32 both ways).
        assert np.allclose(dev.embedding, host.embedding, atol=1e-2)

    def test_device_transform_returns_device(self, blobs):
        x, _ = blobs
        xd = jnp.asarray(x[:300])
        model = UMAP().setNNeighbors(10).fit(xd)
        emb = model.transform(jnp.asarray(x[300:340]))
        assert isinstance(emb, jax.Array)
        assert emb.shape == (40, 2)

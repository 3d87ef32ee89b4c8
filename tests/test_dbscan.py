"""DBSCAN vs the sklearn oracle (sklearn.cluster.DBSCAN, exact algorithm).

Cluster structure of core points must match sklearn exactly up to label
permutation; border points may differ on ties (documented in
ops/dbscan.py), so datasets here keep clusters separated by > eps.
"""

import numpy as np
import pytest
from sklearn.cluster import DBSCAN as SkDBSCAN

from spark_rapids_ml_tpu.core.data import DataFrame
from spark_rapids_ml_tpu.models.dbscan import DBSCAN, DBSCANModel
from spark_rapids_ml_tpu.ops.dbscan import (
    core_point_mask,
    dbscan_labels,
    relabel_consecutive,
)


@pytest.fixture
def rng():
    return np.random.default_rng(7)


def blobs(rng, centers, n_per=60, scale=0.08):
    pts = np.concatenate(
        [rng.normal(c, scale, size=(n_per, len(c))) for c in centers]
    ).astype(np.float32)
    perm = rng.permutation(len(pts))
    return pts[perm]


def same_partition(a, b):
    """Labels agree as set partitions (incl. noise = -1 matching exactly)."""
    assert a.shape == b.shape
    assert np.array_equal(a == -1, b == -1)
    mapping = {}
    for x, y in zip(a, b):
        if x == -1:
            continue
        if x in mapping:
            assert mapping[x] == y
        else:
            assert y not in mapping.values()
            mapping[x] = y


class TestOps:
    def test_core_mask_matches_sklearn(self, rng):
        x = blobs(rng, [[0, 0], [3, 3], [6, 0]])
        sk = SkDBSCAN(eps=0.3, min_samples=8).fit(x)
        sk_core = np.zeros(len(x), bool)
        sk_core[sk.core_sample_indices_] = True
        core = np.asarray(core_point_mask(x, 0.3, 8))
        np.testing.assert_array_equal(core, sk_core)

    def test_labels_match_sklearn(self, rng):
        x = blobs(rng, [[0, 0], [3, 3], [6, 0]])
        sk = SkDBSCAN(eps=0.3, min_samples=8).fit(x)
        labels, _ = dbscan_labels(x, 0.3, 8)
        ours = relabel_consecutive(np.asarray(labels))
        same_partition(ours, sk.labels_)

    def test_noise_points(self, rng):
        x = blobs(rng, [[0, 0], [5, 5]], n_per=50)
        outliers = rng.uniform(10, 20, size=(10, 2)).astype(np.float32)
        x = np.concatenate([x, outliers])
        sk = SkDBSCAN(eps=0.3, min_samples=8).fit(x)
        labels, _ = dbscan_labels(x, 0.3, 8)
        same_partition(relabel_consecutive(np.asarray(labels)), sk.labels_)
        assert np.sum(np.asarray(labels) == -1) >= 10

    def test_all_noise(self, rng):
        x = rng.uniform(0, 100, size=(40, 3)).astype(np.float32)
        labels, core = dbscan_labels(x, 0.01, 3)
        assert np.all(np.asarray(labels) == -1)
        assert not np.any(np.asarray(core))

    def test_single_cluster(self, rng):
        x = rng.normal(0, 0.05, size=(100, 4)).astype(np.float32)
        labels, core = dbscan_labels(x, 0.5, 5)
        assert np.all(np.asarray(labels) == np.asarray(labels)[0])
        assert np.all(np.asarray(core))

    def test_blocked_matches_unblocked(self, rng):
        x = blobs(rng, [[0, 0], [4, 4]], n_per=70)
        l1, _ = dbscan_labels(x, 0.3, 5, block_q=32, block_i=64)
        l2, _ = dbscan_labels(x, 0.3, 5)
        same_partition(
            relabel_consecutive(np.asarray(l1)), relabel_consecutive(np.asarray(l2))
        )

    def test_chain_cluster_long_diameter(self, rng):
        # A long chain: worst case for naive propagation; pointer-jumping
        # must still converge and agree with sklearn.
        t = np.linspace(0, 10, 200)
        x = np.stack([t, np.zeros_like(t)], axis=1).astype(np.float32)
        x += rng.normal(0, 0.005, x.shape).astype(np.float32)
        sk = SkDBSCAN(eps=0.12, min_samples=3).fit(x)
        labels, _ = dbscan_labels(x, 0.12, 3)
        same_partition(relabel_consecutive(np.asarray(labels)), sk.labels_)

    def test_chain_sweep_count_logarithmic(self):
        # Adversarial topology: a 4096-point chain has
        # cluster diameter ~n, which the old one-jump-per-sweep diffusion
        # resolved in O(n) expensive eps sweeps. With full path
        # compression between sweeps the EXPENSIVE sweep count is O(log n)
        # — for a pure chain the min label reaches every point's neighbor
        # list after one sweep and compression collapses the chain, so the
        # bound here is a small constant, far under log2(n) = 12.
        n = 4096
        x = np.stack(
            [np.arange(n) * 0.5, np.zeros(n)], axis=1
        ).astype(np.float32)
        labels, core, sweeps = dbscan_labels(
            x, 0.6, 2, return_sweeps=True, block_q=512, block_i=1024
        )
        assert np.all(np.asarray(core))
        assert np.all(np.asarray(labels) == 0)  # one cluster, rep = row 0
        assert int(sweeps) <= 6, int(sweeps)

    def test_two_chains_parity_with_sklearn(self):
        # Two parallel chains separated by > eps: compression must not
        # merge distinct components.
        n = 512
        t = np.arange(n) * 0.5
        a = np.stack([t, np.zeros(n)], axis=1)
        b = np.stack([t, np.full(n, 10.0)], axis=1)
        x = np.concatenate([a, b]).astype(np.float32)
        sk = SkDBSCAN(eps=0.6, min_samples=2).fit(x)
        labels, _ = dbscan_labels(x, 0.6, 2)
        same_partition(relabel_consecutive(np.asarray(labels)), sk.labels_)


class TestEstimator:
    def test_fit_transform(self, rng):
        x = blobs(rng, [[0, 0], [3, 3]])
        model = DBSCAN().setEps(0.3).setMinSamples(8).fit(x)
        sk = SkDBSCAN(eps=0.3, min_samples=8).fit(x)
        same_partition(model.labels_, sk.labels_)
        pred = model.transform(x)
        np.testing.assert_array_equal(pred, model.labels_)

    def test_out_of_sample(self, rng):
        x = blobs(rng, [[0, 0], [5, 5]])
        model = DBSCAN().setEps(0.3).setMinSamples(8).fit(x)
        lab_near0 = model.labels_[np.argmin(np.linalg.norm(x, axis=1))]
        q = np.array([[0.05, 0.0], [50.0, 50.0]], dtype=np.float32)
        pred = model.transform(q)
        assert pred[0] == lab_near0
        assert pred[1] == -1

    def test_dataframe_shim(self, rng):
        x = blobs(rng, [[0, 0], [3, 3]], n_per=30)
        df = DataFrame({"features": list(x)})
        model = DBSCAN().setEps(0.3).setMinSamples(5).fit(df)
        out = model.transform(df)
        assert "prediction" in out.columns
        np.testing.assert_array_equal(np.asarray(out.select("prediction")), model.labels_)

    def test_param_validation(self):
        with pytest.raises(ValueError):
            DBSCAN().setEps(-1.0)
        with pytest.raises(ValueError):
            DBSCAN().setMinSamples(0)
        with pytest.raises(ValueError):
            DBSCAN().setMetric("manhattan")

    def test_defaults(self):
        est = DBSCAN()
        assert est.getEps() == 0.5
        assert est.getMinSamples() == 5
        assert est.getMetric() == "euclidean"

    def test_read_write(self, tmp_path, rng):
        x = blobs(rng, [[0, 0], [3, 3]], n_per=30)
        model = DBSCAN().setEps(0.3).setMinSamples(5).fit(x)
        path = str(tmp_path / "dbscan_model")
        model.save(path)
        loaded = DBSCANModel.load(path)
        np.testing.assert_array_equal(loaded.labels_, model.labels_)
        np.testing.assert_array_equal(loaded.core_mask_, model.core_mask_)
        np.testing.assert_allclose(loaded.fitted, model.fitted)
        assert loaded.getEps() == 0.3
        assert loaded.getMinSamples() == 5
        # loaded model predicts out-of-sample identically
        q = np.array([[0.0, 0.0]], dtype=np.float32)
        np.testing.assert_array_equal(loaded.transform(q), model.transform(q))

    def test_copy(self, rng):
        x = blobs(rng, [[0, 0]], n_per=30)
        model = DBSCAN().setEps(0.3).fit(x)
        c = model.copy()
        assert c.uid == model.uid
        np.testing.assert_array_equal(c.labels_, model.labels_)

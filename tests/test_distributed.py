"""Distributed/mesh tests — the multi-chip coverage the reference lacks
(SURVEY.md §4 implication: add a multi-partition -> multi-chip integration
test). Runs on the 8-device virtual CPU mesh from conftest."""

import jax
import numpy as np
import pytest

from spark_rapids_ml_tpu.feature import PCA
from spark_rapids_ml_tpu.parallel.distributed_cov import (
    distributed_covariance_shard_map,
    distributed_mean_and_covariance,
)
from spark_rapids_ml_tpu.parallel.mesh import make_mesh, shard_rows

from conftest import numpy_pca_oracle


@pytest.fixture(scope="module")
def mesh_8x1():
    return make_mesh((8, 1))


@pytest.fixture(scope="module")
def mesh_4x2():
    return make_mesh((4, 2))


def test_eight_devices_available():
    assert len(jax.devices()) == 8


class TestShardRows:
    def test_padding_and_mask(self, rng, mesh_8x1):
        x = rng.normal(size=(13, 4))  # 13 % 8 != 0
        xs, mask, n = shard_rows(x, mesh_8x1)
        assert n == 13
        assert xs.shape == (16, 4)
        assert float(np.asarray(mask).sum()) == 13.0

    def test_data_only_mesh(self, rng):
        # A 1-axis (pure-DP) mesh must work end to end: shard_rows,
        # shard_rows_process_local, and the PCA mesh fit all used to
        # KeyError/ValueError on mesh.shape['model'].
        from jax.sharding import Mesh

        from spark_rapids_ml_tpu.parallel.distributed import (
            shard_rows_process_local,
        )
        from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS

        mesh = Mesh(np.array(jax.devices()), (DATA_AXIS,))
        x = rng.normal(size=(13, 4))
        xs, mask, n = shard_rows(x, mesh)
        assert n == 13 and xs.shape == (16, 4)
        xs2, mask2, n2, d2 = shard_rows_process_local([x], mesh)
        assert n2 == 13 and d2 == 4
        model = PCA(mesh=mesh).setK(2).fit(x)
        oracle = PCA().setK(2).fit(x)
        from spark_rapids_ml_tpu.utils.testing import assert_components_close

        assert_components_close(model.pc, oracle.pc, 1e-8)


class TestDistributedCovariance:
    def test_gspmd_matches_numpy(self, rng, mesh_8x1):
        x = rng.normal(size=(200, 12))
        xs, mask, _ = shard_rows(x, mesh_8x1)
        mean, cov = distributed_mean_and_covariance(xs, mask, mesh_8x1)
        np.testing.assert_allclose(mean, x.mean(axis=0), atol=1e-10)
        np.testing.assert_allclose(cov, np.cov(x, rowvar=False), atol=1e-10)

    def test_gspmd_2d_mesh(self, rng, mesh_4x2):
        """Rows AND features sharded (dp x mp)."""
        x = rng.normal(size=(100, 10))
        xs, mask, _ = shard_rows(x, mesh_4x2)
        mean, cov = distributed_mean_and_covariance(xs, mask, mesh_4x2)
        np.testing.assert_allclose(mean, x.mean(axis=0), atol=1e-10)
        np.testing.assert_allclose(cov, np.cov(x, rowvar=False), atol=1e-10)

    def test_shard_map_explicit_collectives(self, rng, mesh_4x2):
        """Hand-written psum/all_gather path agrees with numpy."""
        x = rng.normal(size=(64, 8))
        xs, mask, _ = shard_rows(x, mesh_4x2)
        mean, cov = distributed_covariance_shard_map(xs, mask, mesh_4x2)
        np.testing.assert_allclose(np.asarray(mean), x.mean(axis=0), atol=1e-10)
        np.testing.assert_allclose(np.asarray(cov), np.cov(x, rowvar=False), atol=1e-10)

    def test_padded_rows_do_not_pollute(self, rng, mesh_8x1):
        x = rng.normal(size=(19, 5))  # heavy padding: 19 -> 24
        xs, mask, _ = shard_rows(x, mesh_8x1)
        _, cov = distributed_mean_and_covariance(xs, mask, mesh_8x1)
        np.testing.assert_allclose(cov, np.cov(x, rowvar=False), atol=1e-10)


class TestDistributedPCA:
    def test_mesh_fit_matches_oracle(self, rng, mesh_8x1):
        x = rng.normal(size=(300, 16))
        expected_pc, expected_var = numpy_pca_oracle(x, 5)
        model = PCA(mesh=mesh_8x1).setK(5).fit(x)
        np.testing.assert_allclose(np.abs(model.pc), np.abs(expected_pc), atol=1e-6)
        np.testing.assert_allclose(model.explainedVariance, expected_var, atol=1e-6)

    def test_mesh_fit_matches_single_device_fit(self, rng, mesh_4x2):
        x = rng.normal(size=(120, 9))
        m_mesh = PCA(mesh=mesh_4x2).setK(4).fit(x)
        m_single = PCA().setK(4).fit(x)
        np.testing.assert_allclose(np.abs(m_mesh.pc), np.abs(m_single.pc), atol=1e-6)


class TestDistributedRandomForest:
    """Rows sharded over the data axis; per-level histograms psum over the
    mesh. Classification counts are small integers (exact in fp32), so the
    sharded fit must produce the IDENTICAL forest to the single-device fit."""

    def test_sharded_classifier_identical(self, rng, mesh_8x1):
        from spark_rapids_ml_tpu.classification import RandomForestClassifier

        x = rng.normal(size=(203, 6))  # deliberately not divisible by 8
        y = (x[:, 0] + 0.5 * x[:, 1] > 0).astype(float)
        kw = dict(numTrees=5, maxDepth=4, seed=3)
        m_single = RandomForestClassifier()._set(**kw).fit((x, y))
        m_mesh = RandomForestClassifier(mesh=mesh_8x1)._set(**kw).fit((x, y))
        np.testing.assert_array_equal(
            np.asarray(m_single._forest.feature), np.asarray(m_mesh._forest.feature)
        )
        np.testing.assert_allclose(
            np.asarray(m_single._forest.threshold),
            np.asarray(m_mesh._forest.threshold),
            atol=1e-6,
        )
        np.testing.assert_array_equal(m_single.predict(x), m_mesh.predict(x))

    def test_sharded_regressor_quality(self, rng, mesh_4x2):
        from spark_rapids_ml_tpu.regression import RandomForestRegressor

        x = rng.normal(size=(240, 4))
        y = 2.0 * x[:, 0] - x[:, 2]
        model = (
            RandomForestRegressor(mesh=mesh_4x2)
            .setNumTrees(8)
            .setMaxDepth(6)
            .setFeatureSubsetStrategy("all")
            .setSeed(1)
            .fit((x, y))
        )
        rmse = np.sqrt(np.mean((model.predict(x) - y) ** 2))
        assert rmse < 0.6


class TestDistributedUMAP:
    def test_sharded_knn_graph_matches(self, rng, mesh_8x1):
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.models.umap import _knn_excluding_self

        x = jnp.asarray(rng.normal(size=(101, 6)), dtype=jnp.float32)
        d_s, i_s = _knn_excluding_self(x, 8, "euclidean", mesh_8x1)
        d_u, i_u = _knn_excluding_self(x, 8, "euclidean", None)
        np.testing.assert_array_equal(np.asarray(i_s), np.asarray(i_u))
        np.testing.assert_allclose(np.asarray(d_s), np.asarray(d_u), atol=1e-5)

    def test_mesh_umap_fit(self, rng, mesh_8x1):
        from spark_rapids_ml_tpu.manifold import UMAP

        x = np.concatenate(
            [rng.normal(size=(40, 8)) + off for off in (0.0, 10.0)]
        )
        model = UMAP(mesh=mesh_8x1).setNNeighbors(8).setNEpochs(60).setSeed(0).fit(x)
        emb = model.embedding
        assert emb.shape == (80, 2)
        labels = np.repeat([0, 1], 40)
        c0, c1 = emb[labels == 0].mean(0), emb[labels == 1].mean(0)
        spread = np.mean(np.linalg.norm(emb[labels == 0] - c0, axis=1))
        assert np.linalg.norm(c0 - c1) > 2 * spread


class TestDistributedKnnMetrics:
    def test_mesh_cosine_matches_single(self, rng, mesh_8x1):
        from spark_rapids_ml_tpu.neighbors import NearestNeighbors

        items = rng.normal(size=(150, 8))
        q = rng.normal(size=(11, 8))
        m_mesh = NearestNeighbors().setK(5).setMetric("cosine").fit(items)
        m_mesh.setMesh(mesh_8x1)
        m_single = NearestNeighbors().setK(5).setMetric("cosine").fit(items)
        d_m, i_m = m_mesh.kneighbors(q)
        d_s, i_s = m_single.kneighbors(q)
        np.testing.assert_array_equal(i_m, i_s)
        np.testing.assert_allclose(d_m, d_s, atol=1e-6)


class TestDistributedDBSCAN:
    def test_sharded_matches_single(self, rng, mesh_8x1):
        from spark_rapids_ml_tpu.clustering import DBSCAN

        # Three blobs + scattered noise; n not divisible by 8.
        x = np.concatenate(
            [rng.normal(size=(45, 3)) * 0.2 + c for c in ([0, 0, 0], [3, 3, 0], [0, 3, 3])]
            + [rng.uniform(-2, 5, size=(10, 3))]
        )
        m_single = DBSCAN().setEps(0.7).setMinSamples(4).fit(x)
        m_mesh = DBSCAN(mesh=mesh_8x1).setEps(0.7).setMinSamples(4).fit(x)
        np.testing.assert_array_equal(m_single.labels_, m_mesh.labels_)
        np.testing.assert_array_equal(m_single.core_mask_, m_mesh.core_mask_)
        assert len(set(m_single.labels_[m_single.labels_ >= 0])) == 3


class TestDistributedANN:
    def test_sharded_search_matches_single(self, rng, mesh_8x1):
        from spark_rapids_ml_tpu.neighbors import ApproximateNearestNeighbors

        items = rng.normal(size=(300, 10))
        queries = rng.normal(size=(21, 10))  # deliberately not divisible by 8
        m = (
            ApproximateNearestNeighbors()
            .setAlgorithm("ivfflat")
            .setAlgoParams({"nlist": 8, "nprobe": 8})
            .setK(5)
            .setSeed(0)
            .fit(items)
        )
        d_single, i_single = m.kneighbors(queries)
        m.setMesh(mesh_8x1)
        d_mesh, i_mesh = m.kneighbors(queries)
        np.testing.assert_array_equal(i_single, i_mesh)
        np.testing.assert_allclose(d_single, d_mesh, atol=1e-6)

    def test_sharded_ivfpq_with_refine(self, rng, mesh_8x1):
        from spark_rapids_ml_tpu.neighbors import ApproximateNearestNeighbors

        items = rng.normal(size=(240, 8))
        queries = rng.normal(size=(13, 8))
        kwargs = dict(
            algorithm="ivfpq",
            algoParams={"nlist": 6, "nprobe": 6, "M": 4, "n_bits": 6,
                        "refine_ratio": 4},
            k=5, seed=1,
        )
        m = ApproximateNearestNeighbors()._set(**kwargs).fit(items)
        d_single, i_single = m.kneighbors(queries)
        m.setMesh(mesh_8x1)
        d_mesh, i_mesh = m.kneighbors(queries)
        np.testing.assert_array_equal(i_single, i_mesh)
        np.testing.assert_allclose(d_single, d_mesh, atol=1e-6)

    def test_sharded_brute_matches_single(self, rng, mesh_8x1):
        from spark_rapids_ml_tpu.neighbors import ApproximateNearestNeighbors

        items = rng.normal(size=(150, 6))
        queries = rng.normal(size=(9, 6))
        m = ApproximateNearestNeighbors().setAlgorithm("brute").setK(4).fit(items)
        d_single, i_single = m.kneighbors(queries)
        m.setMesh(mesh_8x1)
        d_mesh, i_mesh = m.kneighbors(queries)
        np.testing.assert_array_equal(i_single, i_mesh)
        np.testing.assert_allclose(d_single, d_mesh, atol=1e-6)

    def test_estimator_mesh_propagates(self, rng, mesh_8x1):
        from spark_rapids_ml_tpu.neighbors import ApproximateNearestNeighbors

        items = rng.normal(size=(100, 5))
        m = (
            ApproximateNearestNeighbors(mesh=mesh_8x1)
            .setAlgorithm("ivfflat")
            .setAlgoParams({"nlist": 4, "nprobe": 4})
            .setK(3)
            .fit(items)
        )
        assert m.mesh is mesh_8x1
        d, i = m.kneighbors(rng.normal(size=(7, 5)))
        assert d.shape == (7, 3)


class TestDistributedIndexBuild:
    """The ANN index BUILD is mesh-sharded now, not just the search:
    coarse quantizer + PQ codebook Lloyds run over sharded rows with
    psum-merged stats."""

    def test_ivf_build_parity(self, rng, mesh_8x1):
        from spark_rapids_ml_tpu.ops.ann import build_ivf_index, ivf_search
        import jax.numpy as jnp

        items = rng.normal(size=(512, 16)).astype(np.float32)
        idx_s = build_ivf_index(items, n_lists=8, seed=0, mesh=mesh_8x1)
        idx_u = build_ivf_index(items, n_lists=8, seed=0)
        # Same seeded init + deterministic Lloyd: centroids agree to fp
        # reduction-order tolerance. NOTE this parity holds because the
        # shapes here divide the mesh evenly — row/feature padding changes
        # the array length the seeded k-means++ draws its Gumbel noise
        # over, legitimately diverging the init (both builds stay correct;
        # only the exact-equality comparison would break).
        np.testing.assert_allclose(
            np.asarray(idx_s.centroids), np.asarray(idx_u.centroids), atol=1e-4
        )
        # Search through both indexes returns overwhelmingly the same
        # neighbors (boundary items may flip lists at fp tolerance).
        q = jnp.asarray(items[:64])
        _, i_s = ivf_search(idx_s, q, k=5, n_probe=8)
        _, i_u = ivf_search(idx_u, q, k=5, n_probe=8)
        overlap = np.mean(
            [
                len(set(a) & set(b)) / 5.0
                for a, b in zip(np.asarray(i_s), np.asarray(i_u))
            ]
        )
        assert overlap > 0.95, overlap

    def test_ivfpq_build_parity(self, rng, mesh_8x1):
        from spark_rapids_ml_tpu.ops.ann import build_ivfpq_index, ivfpq_search
        import jax.numpy as jnp

        items = rng.normal(size=(512, 16)).astype(np.float32)
        idx_s = build_ivfpq_index(items, n_lists=4, m_subspaces=4, seed=0, mesh=mesh_8x1)
        idx_u = build_ivfpq_index(items, n_lists=4, m_subspaces=4, seed=0)
        np.testing.assert_allclose(
            np.asarray(idx_s.centroids), np.asarray(idx_u.centroids), atol=1e-4
        )
        assert idx_s.codebooks.shape == idx_u.codebooks.shape
        assert idx_s.codes.dtype == idx_u.codes.dtype
        # Both indexes must retrieve true neighbors with similar quality.
        from spark_rapids_ml_tpu.ops.knn import knn as _  # noqa: F401

        q = jnp.asarray(items[:32])
        d2 = ((items[:32, None, :] - items[None]) ** 2).sum(-1)
        true_nn = np.argsort(d2, axis=1)[:, :5]
        for idx in (idx_s, idx_u):
            _, i_got = ivfpq_search(idx, q, k=5, n_probe=4)
            recall = np.mean(
                [
                    len(set(a) & set(b)) / 5.0
                    for a, b in zip(np.asarray(i_got), true_nn)
                ]
            )
            assert recall > 0.6, recall

    def test_model_level_sharded_build(self, rng, mesh_8x1):
        from spark_rapids_ml_tpu.neighbors import ApproximateNearestNeighbors

        items = rng.normal(size=(256, 8))
        m = (
            ApproximateNearestNeighbors(mesh=mesh_8x1)
            .setAlgorithm("ivfpq")
            .setAlgoParams({"nlist": 4, "nprobe": 4, "M": 2})
            .setK(3)
            .fit(items)
        )
        d, i = m.kneighbors(items[:10])
        assert i.shape == (10, 3)
        assert np.all(i[:, 0] == np.arange(10))  # self is nearest


class TestDistributedUMAPOptimize:
    def test_sharded_epochs_separate_clusters(self, rng, mesh_8x1):
        """The mesh fit shards the SGD epochs (edges over the data axis,
        one delta psum per epoch), not only the kNN stage; cluster
        separation quality must match the single-device optimizer."""
        import jax
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops.umap import (
            find_ab_params,
            fuzzy_simplicial_set,
            optimize_layout,
            optimize_layout_sharded,
        )
        from spark_rapids_ml_tpu.models.umap import _knn_excluding_self

        x = jnp.asarray(
            np.concatenate(
                [rng.normal(size=(48, 6)) + off for off in (0.0, 12.0)]
            ),
            dtype=jnp.float32,
        )
        dists, idx = _knn_excluding_self(x, 8, "euclidean", None)
        graph = fuzzy_simplicial_set(idx, dists)
        a, b = find_ab_params(1.0, 0.1)
        emb0 = 10.0 * jax.random.uniform(
            jax.random.key(0), (96, 2), minval=-1.0, maxval=1.0
        ).astype(jnp.float32)

        def separation(emb):
            labels = np.repeat([0, 1], 48)
            c0, c1 = emb[labels == 0].mean(0), emb[labels == 1].mean(0)
            spread = np.mean(np.linalg.norm(emb[labels == 0] - c0, axis=1)) + 1e-9
            return np.linalg.norm(c0 - c1) / spread

        kw = dict(n_epochs=80, neg_rate=5, learning_rate=1.0, repulsion=1.0, a=a, b=b)
        emb_s = np.asarray(
            optimize_layout_sharded(mesh_8x1, emb0, graph, jax.random.key(1), **kw)
        )
        emb_u = np.asarray(optimize_layout(emb0, graph, jax.random.key(1), **kw))
        assert separation(emb_s) > 2.0, separation(emb_s)
        # 1.8 (not 2.0): the r4 structured-head epoch changes only the
        # float reduction ORDER of the gradient sums — same math, a
        # slightly different SGD trajectory on this 96-point toy; the
        # clusters must still clearly separate.
        assert separation(emb_u) > 1.8, separation(emb_u)

    def test_sharded_pooled_epoch_matches_unsharded(self, rng, mesh_8x1):
        """Pooled mode draws the shared pool from the replicated key, so
        the sharded epoch computes the SAME update as the single-device
        one (only psum reduction order differs) — checked over one epoch,
        before float drift can amplify through the SGD trajectory."""
        import jax
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.models.umap import _knn_excluding_self
        from spark_rapids_ml_tpu.ops.umap import (
            fuzzy_simplicial_set,
            optimize_layout,
            optimize_layout_sharded,
        )

        x = jnp.asarray(rng.normal(size=(96, 6)), dtype=jnp.float32)
        d, i = _knn_excluding_self(x, 8, "euclidean")
        graph = fuzzy_simplicial_set(i, d)
        emb0 = jnp.asarray(rng.normal(size=(96, 2)), dtype=jnp.float32)
        kw = dict(n_epochs=1, neg_rate=5, neg_pool=64, a=1.577, b=0.895)
        e_s = np.asarray(
            optimize_layout_sharded(mesh_8x1, emb0, graph, jax.random.key(3), **kw)
        )
        e_u = np.asarray(optimize_layout(emb0, graph, jax.random.key(3), **kw))
        np.testing.assert_allclose(e_s, e_u, atol=1e-5)


class TestStreamedMeshCovariance:
    """Streaming + mesh — the north-star loop: blocks stream in, each is
    row-sharded over the data axis, the Gram accumulates replicated with
    one psum per block (BASELINE config 5, now a real code path rather
    than a projection)."""

    def test_streamed_mesh_pca_matches_materialized(self, rng, mesh_8x1):
        from spark_rapids_ml_tpu.utils.testing import assert_components_close

        x = rng.normal(size=(5_003, 8)) * np.linspace(1, 2, 8) + 50.0
        gen = (x[i : i + 1024] for i in range(0, x.shape[0], 1024))
        m_stream = PCA(mesh=mesh_8x1).setK(3).fit(gen)
        m_mat = PCA().setK(3).fit(x)
        assert_components_close(m_stream.pc, m_mat.pc, 1e-8)
        np.testing.assert_allclose(
            m_stream.explainedVariance, m_mat.explainedVariance, atol=1e-10
        )

    def test_streamed_mesh_covariance_oracle(self, rng, mesh_8x1):
        from spark_rapids_ml_tpu.ops.covariance import (
            streaming_mean_and_covariance_mesh,
        )

        x = rng.normal(size=(3_000, 6)) + 1e3
        gen = (x[i : i + 500] for i in range(0, 3_000, 500))
        mean, cov, n = streaming_mean_and_covariance_mesh(gen, mesh_8x1)
        assert n == 3_000
        np.testing.assert_allclose(mean, x.mean(axis=0), rtol=1e-12)
        np.testing.assert_allclose(cov, np.cov(x, rowvar=False), atol=1e-6)

    def test_reader_streamed_mesh(self, rng, mesh_8x1, tmp_path):
        from spark_rapids_ml_tpu import native

        if not native.available():
            pytest.skip("native library unavailable")
        x = rng.normal(size=(2_048, 6)).astype(np.float64)
        path = str(tmp_path / "m.npy")
        np.save(path, x)
        reader = native.NpyBlockReader(path, block_rows=300)
        try:
            model = PCA(mesh=mesh_8x1).setK(2).fit(reader)
        finally:
            reader.close()
        oracle = PCA().setK(2).fit(x)
        from spark_rapids_ml_tpu.utils.testing import assert_components_close

        assert_components_close(model.pc, oracle.pc, 1e-8)

"""NearestNeighbors suite. Oracle: numpy/scipy exact distances + argsort."""

import numpy as np
import pytest
from scipy.spatial.distance import cdist

from spark_rapids_ml_tpu.core.data import DataFrame
from spark_rapids_ml_tpu.neighbors import NearestNeighbors, NearestNeighborsModel
from spark_rapids_ml_tpu.ops.knn import knn, knn_sharded
from spark_rapids_ml_tpu.parallel.mesh import make_mesh


def numpy_knn(q, x, k, metric="euclidean"):
    d = cdist(q, x, metric="cosine" if metric == "cosine" else "euclidean")
    if metric == "sqeuclidean":
        d = d * d
    idx = np.argsort(d, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(d, idx, axis=1), idx


class TestOps:
    def test_exact_vs_numpy(self, rng):
        q = rng.normal(size=(30, 8))
        x = rng.normal(size=(500, 8))
        d, idx = knn(q, x, k=7)
        d_ref, idx_ref = numpy_knn(q, x, 7)
        np.testing.assert_array_equal(idx, idx_ref)
        np.testing.assert_allclose(d, d_ref, atol=1e-10)

    def test_approx_topk_matches_exact_on_cpu(self, rng):
        # lax.approx_min_k is exact on the CPU backend, so the approx path
        # must reproduce the exact kernel bit-for-bit here; on TPU it is
        # the hardware partial-reduce (recall ~0.995 in an earlier round).
        q = rng.normal(size=(20, 8))
        x = rng.normal(size=(700, 8))
        d_ex, i_ex = knn(q, x, k=6)
        d_ap, i_ap = knn(q, x, k=6, approx=True)
        np.testing.assert_array_equal(np.asarray(i_ap), np.asarray(i_ex))
        np.testing.assert_allclose(np.asarray(d_ap), np.asarray(d_ex), atol=1e-10)

    def test_approx_blocked_masked(self, rng):
        q = rng.normal(size=(8, 4))
        x = rng.normal(size=(300, 4))
        import jax.numpy as jnp

        mask = jnp.asarray((np.arange(300) < 250).astype(np.float64))
        d, idx = knn(q, x, k=5, item_mask=mask, block_items=64, approx=True)
        assert np.all(np.asarray(idx) < 250)  # masked items never surface

    def test_blocked_matches_unblocked(self, rng):
        q = rng.normal(size=(10, 4))
        x = rng.normal(size=(1000, 4))
        d1, i1 = knn(q, x, k=9, block_items=64)
        d2, i2 = knn(q, x, k=9, block_items=100000)
        np.testing.assert_array_equal(i1, i2)
        np.testing.assert_allclose(d1, d2, atol=1e-12)

    def test_metrics(self, rng):
        q = rng.normal(size=(5, 6))
        x = rng.normal(size=(50, 6))
        for metric in ("euclidean", "sqeuclidean", "cosine"):
            d, idx = knn(q, x, k=3, metric=metric)
            d_ref, idx_ref = numpy_knn(q, x, 3, metric)
            np.testing.assert_array_equal(idx, idx_ref)
            np.testing.assert_allclose(d, d_ref, atol=1e-9)

    def test_item_mask_excludes_padding(self, rng):
        q = rng.normal(size=(4, 3))
        x = rng.normal(size=(20, 3))
        x_pad = np.vstack([x, np.zeros((5, 3))])
        mask = np.concatenate([np.ones(20), np.zeros(5)])
        import jax.numpy as jnp

        d, idx = knn(jnp.asarray(q), jnp.asarray(x_pad), k=5, item_mask=jnp.asarray(mask))
        _, idx_ref = numpy_knn(q, x, 5)
        np.testing.assert_array_equal(idx, idx_ref)
        assert (np.asarray(idx) < 20).all()

    def test_self_query_returns_self_first(self, rng):
        x = rng.normal(size=(40, 5))
        d, idx = knn(x, x, k=1)
        np.testing.assert_array_equal(np.asarray(idx)[:, 0], np.arange(40))
        np.testing.assert_allclose(d, 0.0, atol=1e-6)

    def test_bad_k(self, rng):
        x = rng.normal(size=(10, 2))
        with pytest.raises(ValueError):
            knn(x, x, k=11)
        with pytest.raises(ValueError):
            knn(x, x, k=0)

    def test_sharded_matches_single(self, rng):
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops.knn import shard_items

        mesh = make_mesh((4, 2))
        q = rng.normal(size=(12, 6)).astype(np.float64)
        x = rng.normal(size=(203, 6)).astype(np.float64)  # not divisible
        xs, mask = shard_items(x, mesh)
        d2, idx = knn_sharded(jnp.asarray(q), xs, mask, mesh, k=5)
        d_ref, idx_ref = numpy_knn(q, x, 5)
        np.testing.assert_allclose(np.sqrt(np.asarray(d2)), d_ref, atol=1e-8)
        # shard_items pads only at the end, preserving row order: global
        # indices are directly comparable to the unsharded oracle.
        np.testing.assert_array_equal(np.asarray(idx), idx_ref)

    def test_sharded_approx_matches_exact_on_cpu(self, rng):
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops.knn import shard_items

        mesh = make_mesh((8, 1))
        q = rng.normal(size=(9, 5)).astype(np.float64)
        x = rng.normal(size=(170, 5)).astype(np.float64)
        xs, mask = shard_items(x, mesh)
        d_ex, i_ex = knn_sharded(jnp.asarray(q), xs, mask, mesh, k=4)
        d_ap, i_ap = knn_sharded(jnp.asarray(q), xs, mask, mesh, k=4, approx=True)
        np.testing.assert_array_equal(np.asarray(i_ap), np.asarray(i_ex))
        np.testing.assert_allclose(np.asarray(d_ap), np.asarray(d_ex), atol=1e-10)


class TestEstimator:
    def test_fit_kneighbors(self, rng):
        items = rng.normal(size=(300, 10))
        queries = rng.normal(size=(20, 10))
        model = NearestNeighbors().setK(6).fit(items)
        d, idx = model.kneighbors(queries)
        d_ref, idx_ref = numpy_knn(queries, items, 6)
        np.testing.assert_array_equal(idx, idx_ref)
        np.testing.assert_allclose(d, d_ref, atol=1e-9)

    def test_k_override(self, rng):
        items = rng.normal(size=(50, 4))
        model = NearestNeighbors().setK(3).fit(items)
        d, idx = model.kneighbors(items[:5], k=10)
        assert d.shape == (5, 10)

    def test_id_mapping(self, rng):
        items = rng.normal(size=(40, 3))
        ids = np.array([f"row{i}" for i in range(40)])
        df = DataFrame({"features": list(items), "rid": list(ids)})
        model = NearestNeighbors().setK(2).setIdCol("rid").fit(df)
        d, out_ids = model.kneighbors_ids(items[:3])
        _, idx_ref = numpy_knn(items[:3], items, 2)
        np.testing.assert_array_equal(out_ids, ids[idx_ref])

    def test_dataframe_transform(self, rng):
        items = rng.normal(size=(30, 4))
        df = DataFrame({"features": list(items)})
        model = NearestNeighbors().setK(3).fit(df)
        out = model.transform(df)
        assert "knn_indices" in out.columns and "knn_distances" in out.columns

    def test_errors(self, rng):
        items = rng.normal(size=(10, 2))
        with pytest.raises(ValueError):
            NearestNeighbors().setMetric("manhattan")
        with pytest.raises(ValueError):
            NearestNeighbors().setK(11).fit(items)
        model = NearestNeighbors().setK(3).fit(items)
        with pytest.raises(ValueError):
            model.kneighbors(items, k=0)
        # idCol set but not extractable must raise, not silently fall back
        # to positional indices.
        with pytest.raises(ValueError):
            NearestNeighbors().setIdCol("rid").fit(items)

    def test_pandas_fit_and_query(self, rng):
        import pandas as pd

        items = rng.normal(size=(40, 3))
        df = pd.DataFrame(
            {"features": list(items), "rid": [f"r{i}" for i in range(40)]}
        )
        model = NearestNeighbors().setK(2).setIdCol("rid").fit(df)
        d, ids = model.kneighbors_ids(df)
        _, idx_ref = numpy_knn(items, items, 2)
        np.testing.assert_array_equal(ids, np.asarray(df["rid"])[idx_ref])
        out = model.transform(df)
        assert "knn_indices" in out.columns

    def test_persistence_roundtrip(self, rng, tmp_path):
        items = rng.normal(size=(25, 5))
        ids = np.arange(100, 125)
        df = DataFrame({"features": list(items), "rid": list(ids)})
        model = NearestNeighbors().setK(4).setIdCol("rid").fit(df)
        path = str(tmp_path / "nn")
        model.write.save(path)
        loaded = NearestNeighborsModel.load(path)
        np.testing.assert_allclose(loaded.items, model.items)
        np.testing.assert_array_equal(loaded.ids, model.ids)
        assert loaded.getK() == 4
        d1, i1 = model.kneighbors(items[:4])
        d2, i2 = loaded.kneighbors(items[:4])
        np.testing.assert_array_equal(i1, i2)

    def test_mesh_model_matches_single(self, rng):
        mesh = make_mesh((8, 1))
        items = rng.normal(size=(101, 7))
        queries = rng.normal(size=(9, 7))
        single = NearestNeighbors().setK(4).fit(items)
        dist = NearestNeighbors(mesh=mesh).setK(4).fit(items)
        d1, _ = single.kneighbors(queries)
        d2, _ = dist.kneighbors(queries)
        np.testing.assert_allclose(np.sort(d1, axis=1), np.sort(d2, axis=1), atol=1e-8)

"""UMAP tests — oracle is structural (trustworthiness + cluster geometry).

Beyond-the-reference capability (reference ships only PCA — SURVEY.md §2).
UMAP has no exact numeric oracle (stochastic optimization), so the suite
checks the properties every correct implementation must deliver: local
structure preservation (sklearn's trustworthiness), cluster separation on
well-separated blobs, determinism for a fixed seed, and persistence.
"""

import numpy as np
import pytest

from spark_rapids_ml_tpu.manifold import UMAP, UMAPModel
from spark_rapids_ml_tpu.ops.umap import find_ab_params, fuzzy_simplicial_set, smooth_knn_dist


def _three_blobs(rng, n_per=60, d=10, sep=12.0):
    centers = np.zeros((3, d))
    centers[0, 0] = sep
    centers[1, 1] = sep
    centers[2, 2] = sep
    x = np.concatenate(
        [rng.normal(size=(n_per, d)) + c for c in centers]
    )
    labels = np.repeat(np.arange(3), n_per)
    return x, labels


def _separation_ratio(emb, labels):
    """min inter-centroid distance / mean intra-cluster spread."""
    cents = np.stack([emb[labels == c].mean(axis=0) for c in np.unique(labels)])
    inter = np.inf
    for i in range(len(cents)):
        for j in range(i + 1, len(cents)):
            inter = min(inter, np.linalg.norm(cents[i] - cents[j]))
    intra = np.mean(
        [
            np.linalg.norm(emb[labels == c] - cents[ci], axis=1).mean()
            for ci, c in enumerate(np.unique(labels))
        ]
    )
    return inter / max(intra, 1e-12)


class TestOps:
    def test_smooth_knn_solves_target(self, rng):
        import jax.numpy as jnp

        d = jnp.asarray(np.abs(rng.normal(size=(50, 10))) + 0.1, dtype=jnp.float32)
        sigmas, rhos = smooth_knn_dist(d, 10.0)
        # The defining equation: sum exp(-(d - rho)/sigma) == log2(k).
        lhs = np.sum(
            np.exp(-np.maximum(np.asarray(d) - np.asarray(rhos)[:, None], 0)
                   / np.asarray(sigmas)[:, None]),
            axis=1,
        )
        np.testing.assert_allclose(lhs, np.log2(10.0), rtol=1e-3)
        assert np.all(np.asarray(rhos) > 0)

    def test_fuzzy_graph_symmetric_weights(self, rng):
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.models.umap import _knn_excluding_self

        x = jnp.asarray(rng.normal(size=(40, 5)), dtype=jnp.float32)
        dists, idx = _knn_excluding_self(x, 8, "euclidean")
        g = fuzzy_simplicial_set(idx, dists)
        w = np.asarray(g.weight)
        assert w.shape == (40, 8)
        assert np.all(w >= 0) and np.all(w <= 1.0 + 1e-6)
        # Reconstruct the dense symmetrized matrix: must be symmetric.
        dense = np.zeros((40, 40))
        src = np.repeat(np.arange(40), 8)
        dense[src, np.asarray(g.indices).ravel()] += w.ravel()
        dense = dense + dense.T
        np.testing.assert_allclose(dense, dense.T, atol=1e-6)

    def test_smooth_knn_large_scale(self, rng):
        import jax.numpy as jnp

        # Distances at O(1e5): the sigma bracket must expand past any fixed
        # cap or memberships collapse to zero.
        d = jnp.asarray(
            (np.abs(rng.normal(size=(20, 8))) + 1.0) * 1e5, dtype=jnp.float32
        )
        sigmas, rhos = smooth_knn_dist(d, 8.0)
        lhs = np.sum(
            np.exp(-np.maximum(np.asarray(d) - np.asarray(rhos)[:, None], 0)
                   / np.asarray(sigmas)[:, None]),
            axis=1,
        )
        np.testing.assert_allclose(lhs, np.log2(8.0), rtol=1e-3)

    def test_find_ab_params(self):
        a, b = find_ab_params(1.0, 0.1)
        # Known umap-learn values for the default (spread=1, min_dist=0.1).
        assert abs(a - 1.577) < 0.05
        assert abs(b - 0.895) < 0.05

    def test_knn_excluding_self(self, rng):
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.models.umap import _knn_excluding_self

        x = jnp.asarray(rng.normal(size=(30, 4)), dtype=jnp.float32)
        dists, idx = _knn_excluding_self(x, 5, "euclidean")
        rows = np.arange(30)[:, None]
        assert not np.any(np.asarray(idx) == rows)
        assert np.all(np.asarray(dists) > 0)


class TestBuildAlgo:
    def test_brute_approx_build_matches_exact_on_cpu(self, rng):
        # approx_min_k is exact on the CPU backend, so the approximate
        # graph build must give the identical embedding here; on TPU it
        # trades ~0.5% neighbor recall for the hardware top-k.
        x = rng.normal(size=(120, 6)).astype(np.float32)
        e1 = np.asarray(UMAP().setNEpochs(20).setSeed(1).fit(x).transform(x))
        e2 = np.asarray(
            UMAP().setNEpochs(20).setSeed(1).setBuildAlgo("brute_approx")
            .fit(x).transform(x)
        )
        np.testing.assert_allclose(e1, e2, atol=1e-5)

    def test_invalid_build_algo_rejected(self):
        with pytest.raises(ValueError, match="buildAlgo"):
            UMAP().setBuildAlgo("nn_descent")


class TestUMAP:
    def test_blobs_separate(self, rng):
        x, labels = _three_blobs(rng)
        model = UMAP().setNNeighbors(10).setNEpochs(150).setSeed(0).fit(x)
        emb = model.embedding
        assert emb.shape == (180, 2)
        assert np.all(np.isfinite(emb))
        assert _separation_ratio(emb, labels) > 2.0

    def test_trustworthiness(self, rng):
        manifold = pytest.importorskip("sklearn.manifold")
        x, _ = _three_blobs(rng, n_per=50)
        model = UMAP().setNNeighbors(10).setNEpochs(150).setSeed(1).fit(x)
        t = manifold.trustworthiness(x, model.embedding, n_neighbors=10)
        assert t > 0.85

    def test_determinism(self, rng):
        x, _ = _three_blobs(rng, n_per=30)
        e1 = UMAP().setNEpochs(50).setSeed(7).fit(x).embedding
        e2 = UMAP().setNEpochs(50).setSeed(7).fit(x).embedding
        np.testing.assert_allclose(e1, e2, atol=1e-6)

    def test_random_init_and_cosine(self, rng):
        x, labels = _three_blobs(rng, n_per=40)
        model = (
            UMAP()
            .setInit("random")
            .setMetric("cosine")
            .setNNeighbors(8)
            .setNEpochs(150)
            .setSeed(3)
            .fit(x)
        )
        assert _separation_ratio(model.embedding, labels) > 1.5

    def test_transform_new_points(self, rng):
        x, labels = _three_blobs(rng, n_per=50)
        model = UMAP().setNNeighbors(10).setNEpochs(150).setSeed(2).fit(x)
        # New points from blob 0 must land nearest blob 0's centroid.
        x_new = rng.normal(size=(20, x.shape[1]))
        x_new[:, 0] += 12.0
        emb_new = model.transform(x_new)
        assert emb_new.shape == (20, 2)
        cents = np.stack(
            [model.embedding[labels == c].mean(axis=0) for c in range(3)]
        )
        d = np.linalg.norm(emb_new[:, None, :] - cents[None, :, :], axis=2)
        assert np.mean(np.argmin(d, axis=1) == 0) >= 0.9

    def test_persistence_roundtrip(self, tmp_path, rng):
        x, _ = _three_blobs(rng, n_per=20)
        model = UMAP().setNEpochs(30).setSeed(4).fit(x)
        path = str(tmp_path / "umap")
        model.save(path)
        loaded = UMAPModel.load(path)
        np.testing.assert_allclose(model.embedding, loaded.embedding, atol=1e-12)
        np.testing.assert_allclose(
            model.transform(x[:5]), loaded.transform(x[:5]), atol=1e-6
        )

    def test_dataframe_shim(self, rng):
        from spark_rapids_ml_tpu.core.data import DataFrame

        x, _ = _three_blobs(rng, n_per=15)
        df = DataFrame({"features": list(x)})
        model = UMAP().setNEpochs(20).setSeed(5).fit(df)
        out = model.transform(df)
        assert "embedding" in out.columns
        assert len(out.select("embedding")) == len(x)

    def test_param_validation(self):
        with pytest.raises(ValueError):
            UMAP().setNNeighbors(1)
        with pytest.raises(ValueError):
            UMAP().setMetric("mahalanobis")
        with pytest.raises(ValueError):
            UMAP().setInit("pca")
        with pytest.raises(ValueError):
            UMAP().fit(np.zeros((2, 3)))

    def test_defaults(self):
        u = UMAP()
        assert u.getNNeighbors() == 15
        assert u.getNComponents() == 2
        assert u.getMinDist() == 0.1
        assert u.getInit() == "spectral"
        assert u._auto_epochs(5_000) == 500
        assert u._auto_epochs(50_000) == 200


class TestPooledNegatives:
    """The r5 epoch-shared negative pool (dense GEMM repulsion) must be an
    equivalent estimator to per-edge sampling: same embedding QUALITY, not
    the same stochastic trajectory (different RNG usage by design)."""

    def test_pooled_quality_matches_per_edge(self, rng):
        manifold = pytest.importorskip("sklearn.manifold")
        x, labels = _three_blobs(rng, n_per=50)
        pooled = UMAP().setNNeighbors(10).setNEpochs(150).setSeed(1).fit(x)
        per_edge = (
            UMAP().setNNeighbors(10).setNEpochs(150).setSeed(1)
            .setNegativePoolSize(0).fit(x)
        )
        t_pool = manifold.trustworthiness(x, pooled.embedding, n_neighbors=10)
        t_edge = manifold.trustworthiness(x, per_edge.embedding, n_neighbors=10)
        # Neighborhood preservation parity: pooled within 0.03 of per-edge
        # (both must clear the absolute bar the suite holds UMAP to).
        assert t_pool > 0.85, t_pool
        assert t_pool > t_edge - 0.03, (t_pool, t_edge)
        assert _separation_ratio(pooled.embedding, labels) > 2.0

    def test_pool_smaller_and_larger_than_n(self, rng):
        # Pool size is independent of n: oversampling (s > n) and heavy
        # subsampling both stay finite and separate the blobs.
        x, labels = _three_blobs(rng, n_per=30)  # n = 90
        for s in (32, 512):
            emb = (
                UMAP().setNNeighbors(8).setNEpochs(120).setSeed(2)
                .setNegativePoolSize(s).fit(x).embedding
            )
            assert np.all(np.isfinite(emb))
            assert _separation_ratio(emb, labels) > 1.5, s

    def test_per_edge_path_deterministic(self, rng):
        x, _ = _three_blobs(rng, n_per=20)
        kw = dict()
        e1 = (
            UMAP().setNEpochs(40).setSeed(9).setNegativePoolSize(0)
            .fit(x).embedding
        )
        e2 = (
            UMAP().setNEpochs(40).setSeed(9).setNegativePoolSize(0)
            .fit(x).embedding
        )
        np.testing.assert_allclose(e1, e2, atol=1e-6)

    def test_pool_param_validation(self):
        with pytest.raises(ValueError, match="negativePoolSize"):
            UMAP().setNegativePoolSize(-1)

    def test_transform_uses_pool(self, rng):
        # Transform-mode pooled repulsion draws from the FROZEN training
        # layout; new points must still land near their blob.
        x, labels = _three_blobs(rng, n_per=40)
        model = UMAP().setNNeighbors(10).setNEpochs(120).setSeed(3).fit(x)
        x_new = rng.normal(size=(15, x.shape[1]))
        x_new[:, 1] += 12.0  # blob 1
        emb_new = model.transform(x_new)
        cents = np.stack(
            [model.embedding[labels == c].mean(axis=0) for c in range(3)]
        )
        d = np.linalg.norm(emb_new[:, None, :] - cents[None, :, :], axis=2)
        assert np.mean(np.argmin(d, axis=1) == 1) >= 0.9


class TestResume:
    def test_init_embedding_resumes_optimization(self, rng):
        """An interrupted fit's embedding seeds a continuation that reaches
        the same separation quality as one long fit."""
        from spark_rapids_ml_tpu.manifold import UMAP

        x = np.concatenate(
            [rng.normal(size=(40, 6)) + off for off in (0.0, 12.0)]
        )
        def separation(emb):
            labels = np.repeat([0, 1], 40)
            c0, c1 = emb[labels == 0].mean(0), emb[labels == 1].mean(0)
            spread = np.mean(np.linalg.norm(emb[labels == 0] - c0, axis=1)) + 1e-9
            return np.linalg.norm(c0 - c1) / spread

        short = UMAP().setNNeighbors(8).setNEpochs(10).setSeed(0).fit(x)
        resumed = (
            UMAP()
            .setNNeighbors(8)
            .setNEpochs(150)
            .setSeed(0)
            .setInitEmbedding(short.embedding)
            .fit(x)
        )
        # Continuation genuinely improves on the interrupted layout and
        # reaches a well-separated embedding.
        assert separation(resumed.embedding) > max(2.0, separation(short.embedding))

    def test_shape_validation(self, rng):
        from spark_rapids_ml_tpu.manifold import UMAP

        x = rng.normal(size=(30, 5))
        with pytest.raises(ValueError, match="shape"):
            UMAP().setNNeighbors(5).setInitEmbedding(np.zeros((10, 2))).fit(x)


class TestTailScatterPallas:
    """Bucketed tail scatter-add kernel: the per-epoch
    XLA scatter replaced by a static tail-sort + dense per-tile
    accumulation. Interpret mode on CPU; the compiled kernel runs on the
    chip in ``chip_smoke.py`` (its speed there is not measured)."""

    @pytest.mark.parametrize(
        "n,k,dim",
        [(600, 8, 2), (257, 5, 3), (1024, 15, 2), (130, 3, 10)],
    )
    def test_tail_accumulate_matches_scatter(self, rng, n, k, dim):
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops.pallas.umap import (
            build_tail_plan,
            plan_feasible,
            tail_accumulate,
        )

        assert plan_feasible(n, k, dim)
        indices = rng.integers(0, n, size=(n, k))
        g = rng.normal(size=(n * k, dim)).astype(np.float32)
        plan, cfg = build_tail_plan(indices, n, dim)
        out = np.asarray(
            tail_accumulate(jnp.asarray(g), plan, cfg, interpret=True)
        )
        expected = np.zeros((n, dim), dtype=np.float64)
        np.add.at(expected, indices.reshape(-1), g.astype(np.float64))
        # In-tile accumulation order differs from the scatter order:
        # float tolerance, not bitwise (PARITY.md TPUML_UMAP_SCATTER).
        np.testing.assert_allclose(out, expected, atol=1e-4, rtol=1e-5)

    def test_plan_infeasible_wide_embedding(self):
        from spark_rapids_ml_tpu.ops.pallas.umap import plan_feasible

        assert not plan_feasible(1000, 15, 129)  # dim > one sublane tile
        assert not plan_feasible(0, 15, 2)  # empty edge stream

    def test_backend_one_epoch_matches_xla(self, rng, monkeypatch):
        """One SGD epoch: before chaotic divergence compounds, the two
        scatter implementations must agree tightly (measured 4.8e-7 at
        one epoch; 20 epochs diverge to O(1) — hence the structural
        oracle below, not a numeric one)."""
        x, _ = _three_blobs(rng, n_per=50)

        def fit(mode):
            monkeypatch.setenv("TPUML_UMAP_SCATTER", mode)
            return (
                UMAP().setNNeighbors(8).setNEpochs(1).setSeed(5).fit(x).embedding
            )

        np.testing.assert_allclose(fit("pallas"), fit("xla"), atol=1e-5)

    @pytest.mark.slow
    def test_backend_trustworthiness_at_scale(self, rng, monkeypatch):
        """Multi-epoch runs diverge numerically (per-epoch epsilon is
        amplified by the SGD's chaotic dynamics), so at scale the oracle
        is structural: both backends must embed equally trustworthily."""
        manifold = pytest.importorskip("sklearn.manifold")
        x = rng.normal(size=(50_000, 64)).astype(np.float32)
        x[:25_000, 0] += 8.0  # two far sheets: real structure to preserve

        def fit(mode):
            monkeypatch.setenv("TPUML_UMAP_SCATTER", mode)
            est = (
                UMAP()
                .setNNeighbors(10)
                .setNEpochs(10)
                .setBuildAlgo("brute_approx")
                .setInit("random")
                .setSeed(5)
            )
            return est.fit(x).embedding

        # Trustworthiness on a fixed subsample (the full 50k pairwise
        # matrix would need ~10 GB); same rows for both backends.
        sub = rng.choice(50_000, size=2_000, replace=False)
        t_pallas = manifold.trustworthiness(
            x[sub], fit("pallas")[sub], n_neighbors=10
        )
        t_xla = manifold.trustworthiness(x[sub], fit("xla")[sub], n_neighbors=10)
        assert abs(t_pallas - t_xla) < 0.05


def _wide_blobs(n=2000, d=64, seed=11):
    """The benchmark's law at a CPU size: 3 centres uniform in (-10, 10),
    unit noise (perfbench/data.py::blobs, sklearn's make_blobs)."""
    rng = np.random.default_rng(seed)
    mu = rng.uniform(-10, 10, size=(3, d))
    return (mu[rng.integers(0, 3, size=n)] + rng.normal(size=(n, d))).astype(np.float32)


class TestFittedGraph:
    """The directed kNN graph a fit keeps, its precision, its stages."""

    def test_graph_is_the_exact_neighbours_and_distances(self):
        x = _wide_blobs()
        model = UMAP().setNNeighbors(15).setNEpochs(30).setSeed(3).fit(x)
        idx, dist = model.knnIndices, model.knnDists
        assert idx.shape == dist.shape == (2000, 15)
        # the plain reference: float64 differences, the row itself left out
        x64 = x.astype(np.float64)
        d2 = np.sum((x64[:, None, :] - x64[None, :, :]) ** 2, axis=2)
        np.fill_diagonal(d2, np.inf)
        want = np.sqrt(np.sort(d2, axis=1)[:, :15])
        got = np.sqrt(np.take_along_axis(d2, idx.astype(np.int64), axis=1))
        # every returned neighbour is as near as the reference's j-th (ties
        # within float32's rounding of a squared distance may swap places)
        assert np.max((got - want) / want) < 1e-5
        assert np.mean(np.sort(idx, axis=1) == np.sort(np.argsort(d2, axis=1)[:, :15], axis=1)) > 0.99
        np.testing.assert_allclose(dist, got, rtol=2e-5)
        assert np.all(np.diff(dist, axis=1) >= 0)
        assert not np.any(idx == np.arange(2000)[:, None])

    def test_precision_reaches_the_knn_product(self, monkeypatch):
        import spark_rapids_ml_tpu.models.umap as umap_mod

        seen = []
        real = umap_mod.knn

        def spy(*args, **kwargs):
            seen.append(kwargs.get("precision"))
            return real(*args, **kwargs)

        monkeypatch.setattr(umap_mod, "knn", spy)
        x = _wide_blobs(n=600)
        exact = UMAP().setNEpochs(5).fit(x)
        assert UMAP().getPrecision() == "highest" and seen == ["highest"]
        one_pass = UMAP().setPrecision("bf16").setNEpochs(5).fit(x)
        assert seen == ["highest", "bf16"]
        # one bf16 pass rounds the product: other distances, other neighbours
        assert np.max(np.abs(one_pass.knnDists - exact.knnDists) / exact.knnDists) > 1e-4
        with pytest.raises(ValueError):
            UMAP().setPrecision("half")

    @pytest.mark.parametrize("scatter", ["xla", "pallas"])
    def test_stages_and_counters(self, monkeypatch, scatter):
        from spark_rapids_ml_tpu.utils import tracing

        monkeypatch.setenv("TPUML_UMAP_SCATTER", scatter)
        x = _wide_blobs(n=1200, d=32)
        before = tracing.counters()
        UMAP().setNNeighbors(15).setNEpochs(30).fit(x)
        got = {k: v - before.get(k, 0) for k, v in tracing.counters().items()}
        plan = scatter == "pallas"
        assert got["fit.stage.knn.calls"] == got["fit.stage.layout.calls"] == 1
        assert got.get("fit.stage.plan.calls", 0) == int(plan)
        assert got.get("umap.layout.pallas", 0) == int(plan)
        assert got["umap.layout.epochs"] == 30
        assert got["knn.blocks"] == 1  # 1,200 items fit one block
        assert got["fit.stage.knn.ns"] > 0 and got["fit.stage.layout.ns"] > 0

    def test_knn_blocks_count_the_scan(self):
        from spark_rapids_ml_tpu.ops.knn import _auto_block_items, count_knn_blocks
        from spark_rapids_ml_tpu.utils import tracing

        before = tracing.counter_value("knn.blocks")
        count_knn_blocks(250_000, 250_000)
        assert _auto_block_items(250_000, 250_000) == 2147
        assert tracing.counter_value("knn.blocks") - before == 117

    def test_graph_is_not_saved(self, tmp_path):
        import os

        x = _wide_blobs(n=300, d=16)
        model = UMAP().setNEpochs(10).setSeed(4).fit(x)
        assert model.knnIndices is not None
        path = str(tmp_path / "umap")
        model.save(path)
        loaded = UMAPModel.load(path)
        assert loaded.knnIndices is None and loaded.knnDists is None
        np.testing.assert_allclose(model.embedding, loaded.embedding, atol=1e-12)
        from spark_rapids_ml_tpu.core.persistence import load_data

        assert set(load_data(path)) == {"embedding", "trainData"}
        # the same files as a model that never had a graph
        bare = str(tmp_path / "bare")
        UMAPModel(model.uid, model.embedding, model.trainData, model.a, model.b).save(bare)
        assert sorted(os.listdir(path)) == sorted(os.listdir(bare))
        copied = model.copy()
        np.testing.assert_array_equal(copied.knnIndices, model.knnIndices)


def test_layout_products_are_full_precision():
    """The negative pool's two products (``y @ pool.T``, ``c @ pool``) ask
    for ``HIGHEST``: at a chip's default one bf16 pass the squared distances
    cancel to nought for near pairs and the clipped repulsion flings rows
    (a CPU computes float32 whatever is asked, so only the request is
    checkable here)."""
    import re

    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.umap import FuzzyGraph, optimize_layout

    n, k = 64, 5
    graph = FuzzyGraph(jnp.zeros((n, k), jnp.int32), jnp.ones((n, k), jnp.float32),
                       jnp.ones(n, jnp.float32), jnp.zeros(n, jnp.float32))
    text = optimize_layout.lower(
        jnp.zeros((n, 2), jnp.float32), graph, jax.random.key(0), n_epochs=2, neg_pool=16
    ).as_text()
    dots = re.findall(r"dot_general.*", text)
    assert len(dots) == 2, dots
    assert all("precision = [HIGHEST, HIGHEST]" in d for d in dots), dots

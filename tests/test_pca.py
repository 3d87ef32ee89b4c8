"""PCA suite — mirrors the reference's 7 tests (PCASuite.scala, SURVEY.md §4)
plus the distributed/mesh tests the reference lacks.

Oracle pattern kept: CPU fp64 ground truth, absTol 1e-5, sign-invariant
comparison where the eigensolver's sign convention may differ
(PCASuite.scala:71,106,136-143).
"""

import numpy as np
import pytest

from spark_rapids_ml_tpu.core.data import DataFrame, Vectors
from spark_rapids_ml_tpu.feature import PCA, PCAModel

from conftest import numpy_pca_oracle

ABS_TOL = 1e-5


def _fit_df(rows, **params):
    df = DataFrame({"features": rows})
    pca = PCA().setK(params.pop("k", 3)).setInputCol("features").setOutputCol("pca_features")
    for name, value in params.items():
        pca.set(pca.getParam(name), value)
    return pca, pca.fit(df), df


class TestParams:
    """Test 1: params smoke check (PCASuite.scala:33-39)."""

    def test_default_params(self):
        pca = PCA()
        assert pca.getMeanCentering() is True
        assert pca.getUseGemm() is True
        assert pca.getUseCuSolverSVD() is True
        assert pca.getGpuId() == -1
        assert not pca.isSet(pca.k)

    def test_param_surface(self):
        pca = PCA()
        for name in ("k", "inputCol", "outputCol", "meanCentering", "useGemm", "useCuSolverSVD", "gpuId"):
            assert pca.hasParam(name), name
        assert "number of principal components" in pca.explainParam("k")

    def test_setters_chain_and_validate(self):
        pca = PCA().setK(2).setMeanCentering(False).setUseGemm(False).setGpuId(0)
        assert pca.getK() == 2
        assert pca.getMeanCentering() is False
        with pytest.raises((TypeError, ValueError)):
            PCA().setK(0)
        with pytest.raises(TypeError):
            PCA().setMeanCentering("yes")

    def test_copy(self):
        pca = PCA().setK(4)
        clone = pca.copy()
        assert clone.getK() == 4
        assert clone.uid != pca.uid or clone is not pca


class TestPCAPaths:
    """Tests 2-4: spr path, gemm path, accelerated-SVD path vs oracle."""

    @staticmethod
    def _check_vs_oracle(model, x, k):
        """Compare against the CPU oracle. With 3 centered rows the
        covariance has rank 2, so components beyond the rank live in an
        arbitrary null-space basis (any tiny covariance perturbation picks a
        different one — the reference suite only dodges this because its spr
        path and oracle share bit-identical covariance code). Informative
        components must match at absTol 1e-5; null-space components are
        checked structurally: unit norm, orthogonal to the rest, and zero
        variance (B·v = 0 for centered B)."""
        expected_pc, expected_var = numpy_pca_oracle(x, k)
        rank = np.linalg.matrix_rank(np.cov(x, rowvar=False))
        r = min(rank, k)
        np.testing.assert_allclose(model.pc[:, :r], expected_pc[:, :r], atol=ABS_TOL)
        np.testing.assert_allclose(model.explainedVariance, expected_var, atol=ABS_TOL)
        b = x - x.mean(axis=0)
        for j in range(r, k):
            v = model.pc[:, j]
            assert abs(np.linalg.norm(v) - 1.0) < ABS_TOL
            np.testing.assert_allclose(b @ v, 0.0, atol=ABS_TOL)
        np.testing.assert_allclose(model.pc.T @ model.pc, np.eye(k), atol=ABS_TOL)

    def test_pca_using_spr(self, reference_rows):
        """useGemm=False packed path + host SVD (PCASuite.scala:41-74)."""
        x = np.stack([r.toArray() for r in reference_rows])
        _, model, df = _fit_df(reference_rows, k=3, useGemm=False, useCuSolverSVD=False)
        self._check_vs_oracle(model, x, 3)
        out = model.transform(df).select("pca_features")
        expected_pc, _ = numpy_pca_oracle(x, 3)
        rank = 2
        np.testing.assert_allclose(
            np.stack(out)[:, :rank], (x @ expected_pc)[:, :rank], atol=ABS_TOL
        )

    def test_pca_using_gemm(self, reference_rows):
        """useGemm=True covariance, host SVD (PCASuite.scala:76-109)."""
        x = np.stack([r.toArray() for r in reference_rows])
        _, model, _ = _fit_df(reference_rows, k=3, useGemm=True, useCuSolverSVD=False)
        self._check_vs_oracle(model, x, 3)

    def test_pca_using_accel_svd(self, rng):
        """100x100 uniform random, XLA eigensolver, sign-invariant |.|
        comparison (PCASuite.scala:111-153)."""
        x = rng.uniform(size=(100, 100))
        expected_pc, expected_var = numpy_pca_oracle(x, 10)
        _, model, _ = _fit_df(list(x), k=10, useGemm=True, useCuSolverSVD=True)
        np.testing.assert_allclose(np.abs(model.pc), np.abs(expected_pc), atol=1e-4)
        np.testing.assert_allclose(model.explainedVariance, expected_var, atol=ABS_TOL)

    def test_gemm_and_spr_agree(self, rng):
        x = rng.normal(size=(50, 8))
        _, m_gemm, _ = _fit_df(list(x), k=5, useGemm=True, useCuSolverSVD=False)
        _, m_spr, _ = _fit_df(list(x), k=5, useGemm=False, useCuSolverSVD=False)
        np.testing.assert_allclose(m_gemm.pc, m_spr.pc, atol=ABS_TOL)

    def test_mean_centering_false(self, rng):
        x = rng.normal(size=(30, 6)) + 5.0
        _, model, _ = _fit_df(list(x), k=3, meanCentering=False, useCuSolverSVD=False)
        # Oracle without centering: eig of X^T X / (n-1)
        cov = x.T @ x / (x.shape[0] - 1)
        w, v = np.linalg.eigh(cov)
        v = v[:, ::-1]
        idx = np.argmax(np.abs(v), axis=0)
        v = v * np.where(v[idx, np.arange(v.shape[1])] < 0, -1.0, 1.0)
        np.testing.assert_allclose(model.pc, v[:, :3], atol=ABS_TOL)


class TestDenseSparseEquivalence:
    """Test 5: dense/sparse input variants give identical results
    (PCASuite.scala:155-190)."""

    def test_variants_identical(self, rng):
        x = rng.normal(size=(20, 5))
        x[x < 0] = 0.0  # make it sparse-ish
        import scipy.sparse as sp

        variants = [
            list(x),  # dense rows
            x,  # one dense block
            [Vectors.dense(row) for row in x],  # DenseVector rows
            [
                Vectors.sparse(5, np.nonzero(row)[0], row[np.nonzero(row)[0]])
                for row in x
            ],  # SparseVector rows
            sp.csr_matrix(x),  # scipy CSR
        ]
        results = []
        for rows in variants:
            _, model, _ = _fit_df(rows, k=3, useCuSolverSVD=False)
            results.append((model.pc, model.explainedVariance))
        for pc, var in results[1:]:
            np.testing.assert_allclose(pc, results[0][0], atol=1e-12)
            np.testing.assert_allclose(var, results[0][1], atol=1e-12)


class TestReadWrite:
    """Tests 6-7: estimator and model read/write round-trips
    (PCASuite.scala:192-206)."""

    def test_estimator_read_write(self, tmp_path):
        path = str(tmp_path / "pca")
        pca = PCA().setK(3).setInputCol("features").setOutputCol("out").setMeanCentering(False)
        pca.save(path)
        loaded = PCA.load(path)
        assert loaded.uid == pca.uid
        assert loaded.getK() == 3
        assert loaded.getInputCol() == "features"
        assert loaded.getOutputCol() == "out"
        assert loaded.getMeanCentering() is False
        assert loaded.getUseGemm() is True  # default survives round-trip

    def test_model_read_write(self, tmp_path, rng):
        path = str(tmp_path / "pca_model")
        x = rng.normal(size=(30, 6))
        _, model, _ = _fit_df(list(x), k=4, useCuSolverSVD=False)
        model.write.overwrite().save(path)
        loaded = PCAModel.load(path)
        assert loaded.uid == model.uid
        np.testing.assert_allclose(loaded.pc, model.pc, atol=0)
        np.testing.assert_allclose(loaded.explainedVariance, model.explainedVariance, atol=0)
        assert loaded.getInputCol() == "features"
        # loaded model transforms identically
        out_a = model.transform(x)
        out_b = loaded.transform(x)
        np.testing.assert_allclose(out_a, out_b, atol=0)

    def test_model_overwrite_guard(self, tmp_path, rng):
        path = str(tmp_path / "m")
        x = rng.normal(size=(10, 4))
        _, model, _ = _fit_df(list(x), k=2, useCuSolverSVD=False)
        model.save(path)
        with pytest.raises(FileExistsError):
            model.save(path)

    def test_parquet_schema_matches_spark_udt(self, tmp_path, rng):
        """The data file uses Spark's MatrixUDT/VectorUDT struct layout."""
        pytest.importorskip("pyarrow")
        import pyarrow.parquet as pq

        path = str(tmp_path / "m")
        x = rng.normal(size=(10, 4))
        _, model, _ = _fit_df(list(x), k=2, useCuSolverSVD=False)
        model.save(path)
        table = pq.read_table(f"{path}/data/part-00000.parquet")
        pc = table.column("pc")[0].as_py()
        assert pc["type"] == 1 and pc["numRows"] == 4 and pc["numCols"] == 2
        ev = table.column("explainedVariance")[0].as_py()
        assert ev["type"] == 1 and ev["size"] == 2


class TestTransform:
    def test_transform_dataframe_shim(self, rng):
        x = rng.normal(size=(12, 5))
        pca, model, df = _fit_df(list(x), k=2, useCuSolverSVD=False)
        out = model.transform(df)
        assert "pca_features" in out.columns
        assert len(out.select("pca_features")) == 12
        assert out.select("pca_features")[0].shape == (2,)

    def test_transform_pandas(self, rng):
        import pandas as pd

        x = rng.normal(size=(12, 5))
        df = pd.DataFrame({"features": list(x)})
        model = PCA().setK(2).setInputCol("features").setOutputCol("out").fit(df)
        out = model.transform(df)
        assert "out" in out.columns
        np.testing.assert_allclose(np.stack(out["out"]), x @ model.pc, atol=1e-6)

    def test_transform_partitioned_matches_single(self, rng):
        x = rng.normal(size=(40, 7))
        _, model, _ = _fit_df(list(x), k=3, useCuSolverSVD=False)
        whole = model.transform(x)
        parts = model.transform([x[:15], x[15:]])
        np.testing.assert_allclose(whole, parts, atol=1e-10)


class TestRandomizedSolver:
    """Randomized (sketch) PCA must agree with the covariance path on the
    dominant subspace and the explained-variance ratios."""

    def test_matches_covariance_path(self, rng):
        from spark_rapids_ml_tpu.feature import PCA

        # Strong spectral decay so the sketch captures the subspace exactly.
        n, d, k = 500, 60, 5
        basis, _ = np.linalg.qr(rng.normal(size=(d, d)))
        scales = np.concatenate([[20, 15, 10, 6, 4], np.full(d - 5, 0.3)])
        x = rng.normal(size=(n, d)) @ (basis * scales).T

        full = PCA().setK(k).setSolver("covariance").fit(x)
        rand = PCA().setK(k).setSolver("randomized").fit(x)
        # Component-wise agreement up to sign (both sign-flip, so exact).
        for j in range(k):
            dot = abs(np.dot(full.pc[:, j], rand.pc[:, j]))
            assert dot > 0.999, (j, dot)
        np.testing.assert_allclose(
            rand.explainedVariance, full.explainedVariance, rtol=1e-3
        )

    def test_auto_routes_wide_features(self, rng):
        from spark_rapids_ml_tpu.feature import PCA

        # d >= the auto threshold: fit must succeed quickly without the
        # (d, d) eigh (n tiny, so the covariance would be rank-deficient
        # anyway — the sketch handles that via the CQR ridge).
        n, d = 300, 4096
        x = rng.normal(size=(n, d))
        model = PCA().setK(3).fit(x)
        assert model.pc.shape == (d, 3)
        assert np.all(np.isfinite(model.pc))
        assert float(np.sum(model.explainedVariance)) <= 1.0 + 1e-6

    def test_determinism(self, rng):
        from spark_rapids_ml_tpu.feature import PCA

        x = rng.normal(size=(200, 40))
        a = PCA().setK(4).setSolver("randomized").fit(x)
        b = PCA().setK(4).setSolver("randomized").fit(x)
        np.testing.assert_array_equal(a.pc, b.pc)

    def test_uncentered_variant(self, rng):
        from spark_rapids_ml_tpu.feature import PCA

        x = rng.normal(size=(300, 30)) + 5.0  # large mean
        cov = PCA().setK(3).setSolver("covariance").setMeanCentering(False).fit(x)
        rnd = PCA().setK(3).setSolver("randomized").setMeanCentering(False).fit(x)
        # Without centering the mean direction dominates; both paths must
        # agree on it.
        dot = abs(np.dot(cov.pc[:, 0], rnd.pc[:, 0]))
        assert dot > 0.999

    def test_solver_validation(self):
        from spark_rapids_ml_tpu.feature import PCA

        with pytest.raises(ValueError):
            PCA().setSolver("lanczos")

    def test_k_exceeds_rank_raises(self, rng):
        from spark_rapids_ml_tpu.feature import PCA

        x = rng.normal(size=(8, 50))
        with pytest.raises(ValueError, match="k must be in"):
            PCA().setK(10).setSolver("randomized").fit(x)

    def test_large_offset_total_variance(self, rng):
        from spark_rapids_ml_tpu.feature import PCA

        # Means ~1e4, std ~1: the ratio denominator must come from the
        # centered trace, not E[x^2] - mean^2 (fp32 cancellation).
        x = rng.normal(size=(300, 20)) + 1e4
        full = PCA().setK(3).setSolver("covariance").fit(x)
        rand = PCA().setK(3).setSolver("randomized").fit(x)
        # Flat spectra make the sketched singular values a slight
        # underestimate (a few %, and the exact margin moves with the
        # backend's RNG/GEMM version); the cancellation bug this guards
        # against produced order-of-magnitude-wrong or negative ratios.
        np.testing.assert_allclose(
            rand.explainedVariance, full.explainedVariance, rtol=8e-2
        )
        assert np.all(rand.explainedVariance > 0)
        assert float(np.sum(rand.explainedVariance)) <= 1.0

    def test_mesh_randomized_is_a_real_path(self, rng):
        # Round 3: the mesh restriction is gone — the sketch shards like
        # the covariance (full coverage in tests/test_wide_features.py).
        from spark_rapids_ml_tpu.feature import PCA
        from spark_rapids_ml_tpu.parallel.mesh import make_mesh

        x = rng.normal(size=(256, 8)) * np.linspace(1, 3, 8)
        model = PCA(mesh=make_mesh((8, 1))).setK(2).setSolver("randomized").fit(x)
        assert model.pc.shape == (8, 2)


class TestTopkEigenSolver:
    """eigenSolver="topk": subspace iteration + Rayleigh-Ritz in place of
    the full O(d^3) eigh — for decaying spectra (PCA's regime) it must
    reproduce the exact solver's components and EXACT explained ratios."""

    def _decaying(self, rng, n=4000, d=128):
        # Strong spectral decay: a few dominant directions + noise floor.
        scales = np.concatenate([np.array([30.0, 20.0, 12.0, 8.0]), np.ones(d - 4)])
        return rng.normal(size=(n, d)) * scales

    def test_matches_full_solver(self, rng):
        from spark_rapids_ml_tpu.utils.testing import assert_components_close

        x = self._decaying(rng)
        m_full = PCA().setK(4).fit(x)
        m_topk = PCA().setK(4).setEigenSolver("topk").fit(x)
        assert_components_close(m_topk.pc, m_full.pc, 1e-5)
        # Explained ratios are trace-normalized: exact, not subspace-relative.
        np.testing.assert_allclose(
            m_topk.explainedVariance, m_full.explainedVariance, atol=1e-7
        )

    def test_ops_level_vs_numpy(self, rng):
        from spark_rapids_ml_tpu.ops.eigh import eigh_topk

        import jax.numpy as jnp

        x = self._decaying(rng, n=2000, d=64)
        cov = np.cov(x, rowvar=False)
        w, v = eigh_topk(jnp.asarray(cov), 3)
        w_ref, v_ref = np.linalg.eigh(cov)
        np.testing.assert_allclose(np.asarray(w), w_ref[::-1][:3], rtol=1e-8)
        from spark_rapids_ml_tpu.utils.testing import assert_components_close

        ref = v_ref[:, ::-1][:, :3]
        signs = np.sign(ref[np.argmax(np.abs(ref), axis=0), np.arange(3)])
        assert_components_close(np.asarray(v), ref * signs, 1e-6)

    def test_topk_with_mesh(self, rng):
        from spark_rapids_ml_tpu.parallel.mesh import make_mesh
        from spark_rapids_ml_tpu.utils.testing import assert_components_close

        x = self._decaying(rng, n=1000, d=32)
        m_mesh = PCA(mesh=make_mesh()).setK(3).setEigenSolver("topk").fit(x)
        m_full = PCA().setK(3).fit(x)
        assert_components_close(m_mesh.pc, m_full.pc, 1e-5)

    def test_invalid_rejected(self):
        with pytest.raises(ValueError, match="eigenSolver"):
            PCA().setEigenSolver("lanczos")

    def test_eigen_iters_knob_improves_weak_decay(self, rng):
        """Moderate eigengap: more iterations must tighten agreement with
        the exact solver (the knob exists for exactly this case)."""
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops.eigh import eigh_topk

        d, k = 96, 4
        # Weak decay: top-k scales 1.6..1.2 over a 1.0 noise floor.
        scales = np.concatenate([np.linspace(1.6, 1.2, k), np.ones(d - k)])
        x = rng.normal(size=(20_000, d)) * scales
        cov = jnp.asarray(np.cov(x, rowvar=False))
        w_ref = np.linalg.eigvalsh(np.asarray(cov))[::-1][:k]

        def err(iters):
            w, _ = eigh_topk(cov, k, iters=iters)
            return float(np.max(np.abs(np.asarray(w) - w_ref)))

        assert err(40) < err(2)
        assert err(40) < 1e-6

    def test_eigen_iters_validation(self):
        with pytest.raises(ValueError, match="eigenIters"):
            PCA().setEigenIters(0)

    def test_topk_with_dd_precision(self, rng):
        """Explicit topk + dd is honored at fp64 (ARPACK), not silently
        downgraded to the full host eigh (r2 review)."""
        from spark_rapids_ml_tpu.utils.testing import assert_components_close

        x = self._decaying(rng, n=3000, d=64)
        m = PCA().setK(3).setPrecision("dd").setEigenSolver("topk").fit(x)
        m_ref = PCA().setK(3).setPrecision("dd").fit(x)
        assert_components_close(m.pc, m_ref.pc, 1e-6)
        np.testing.assert_allclose(
            m.explainedVariance, m_ref.explainedVariance, atol=1e-9
        )



class TestHostPartitionsOnePass:
    """Host partitions on the GEMM route: column means and the centred Gram
    come from ONE pass (each partition placed once, its Gram centred on its
    own means, Chan's merge between them). ``pc`` and ``explainedVariance``
    against ``numpy.cov`` + ``numpy.linalg.eigh`` in float64, for both
    covariance kernels, with the rows shuffled and with the partitions
    sorted (block means many spreads apart, columns 1e3 off zero), under
    the tests' x64 and in the chip's float32."""

    K = 3

    @staticmethod
    def partitions(rng, order: str, parts: int = 8, rows: int = 256, cols: int = 12):
        basis = np.linalg.qr(rng.standard_normal((cols, cols)))[0]
        scales = np.array([6.0, 4.0, 2.5] + [1.0] * (cols - 3))
        x = (rng.standard_normal((parts * rows, cols)) * scales) @ basis.T
        x += 1e3 * rng.standard_normal(cols)
        if order == "sorted":  # each partition ten spreads further along one axis
            x += np.repeat(np.arange(parts) * 10.0, rows)[:, None] * basis[:, 3]
        return np.split(x.astype(np.float32), parts)

    @staticmethod
    def oracle(parts, k: int):
        w, v = np.linalg.eigh(np.cov(np.concatenate(parts).astype(np.float64), rowvar=False))
        w, v = w[::-1], v[:, ::-1]
        return v[:, :k], w[:k] / w.sum()

    @pytest.mark.parametrize("order", ["shuffled", "sorted"])
    @pytest.mark.parametrize("backend", ["xla", "pallas"])
    @pytest.mark.parametrize("x64", [True, False], ids=["x64", "chip_dtypes"])
    def test_matches_the_float64_oracle(self, rng, request, x64, backend, order):
        if not x64:
            request.getfixturevalue("chip_dtypes")
        parts = self.partitions(rng, order)
        model = PCA().setK(self.K).setCovarianceBackend(backend).fit(parts)
        want_pc, want_ev = self.oracle(parts, self.K)
        pc = np.asarray(model.pc, dtype=np.float64)
        pc = pc * np.sign(np.sum(pc * want_pc, axis=0))  # sign-aligned
        # float32: 1e-7 to 7e-7 and 3e-6 to 9e-5 on three seeds, as on the
        # route that made two passes (the components' gap is the subspace
        # iteration's stopping residual, not the covariance)
        ev_tol, pc_tol = (1e-9, 1e-7) if x64 else (2e-6, 3e-4)
        np.testing.assert_allclose(model.explainedVariance, want_ev, rtol=ev_tol)
        np.testing.assert_allclose(pc, want_pc, atol=pc_tol)

    def test_sorted_and_shuffled_partitions_of_one_matrix_fit_one_model(self, rng, chip_dtypes):
        parts = self.partitions(rng, "sorted")
        x = np.concatenate(parts)
        shuffled = np.split(x[rng.permutation(x.shape[0])], len(parts))
        a, b = (PCA().setK(self.K).fit(p) for p in (parts, shuffled))
        np.testing.assert_allclose(a.explainedVariance, b.explainedVariance, rtol=2e-6)
        np.testing.assert_allclose(np.abs(a.pc), np.abs(b.pc), atol=3e-4)

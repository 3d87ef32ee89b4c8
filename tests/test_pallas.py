"""Pallas fused covariance kernel tests (interpreter mode on CPU; the same
kernel compiles for TPU via pallas_call with interpret=False)."""

import numpy as np
import pytest

import jax.numpy as jnp

from spark_rapids_ml_tpu.ops.pallas.covariance import centered_gram_pallas


class TestCenteredGramPallas:
    def test_matches_numpy(self, rng):
        x = rng.normal(size=(300, 200)).astype(np.float32)
        mean = x.mean(0)
        ref = (x - mean).T @ (x - mean)
        out = np.asarray(
            centered_gram_pallas(jnp.asarray(x), jnp.asarray(mean), block_rows=128, interpret=True)
        )
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=1e-3)

    def test_row_and_lane_padding(self, rng):
        """n not a tile multiple AND d not a 128 multiple."""
        x = rng.normal(size=(77, 50)).astype(np.float32)
        mean = x.mean(0)
        ref = (x - mean).T @ (x - mean)
        out = np.asarray(
            centered_gram_pallas(jnp.asarray(x), jnp.asarray(mean), block_rows=32, interpret=True)
        )
        assert out.shape == (50, 50)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=1e-3)

    def test_single_block(self, rng):
        x = rng.normal(size=(16, 128)).astype(np.float32)
        mean = np.zeros(128, dtype=np.float32)
        out = np.asarray(
            centered_gram_pallas(jnp.asarray(x), jnp.asarray(mean), block_rows=64, interpret=True)
        )
        np.testing.assert_allclose(out, x.T @ x, rtol=2e-5, atol=1e-3)

    def test_empty_rows(self):
        out = centered_gram_pallas(
            jnp.zeros((0, 8), dtype=jnp.float32), jnp.zeros(8, dtype=jnp.float32), interpret=True
        )
        np.testing.assert_allclose(np.asarray(out), np.zeros((8, 8)))


class TestPallasBackendSelection:
    """The kernel is a selectable covariance backend,
    not dead code: PCA(covarianceBackend='pallas') must produce the same
    model as the default XLA fusion."""

    def test_pca_backend_matches_xla(self, rng):
        from spark_rapids_ml_tpu.feature import PCA
        from spark_rapids_ml_tpu.utils.testing import assert_components_close

        x = rng.normal(size=(600, 20)) * np.linspace(1, 2, 20)
        m_xla = PCA().setK(3).fit(x)
        m_pal = PCA().setK(3).setCovarianceBackend("pallas").fit(x)
        assert_components_close(m_pal.pc, m_xla.pc, 1e-8)
        np.testing.assert_allclose(
            m_pal.explainedVariance, m_xla.explainedVariance, atol=1e-10
        )

    def test_rowmatrix_backend(self, rng):
        from spark_rapids_ml_tpu.linalg.row_matrix import RowMatrix

        x = rng.normal(size=(300, 12)) + 7.0
        cov_xla = np.asarray(RowMatrix([x]).compute_covariance())
        cov_pal = np.asarray(RowMatrix([x], backend="pallas").compute_covariance())
        np.testing.assert_allclose(cov_pal, cov_xla, atol=1e-9)

    def test_invalid_combinations(self, rng):
        from spark_rapids_ml_tpu.feature import PCA
        from spark_rapids_ml_tpu.linalg.row_matrix import RowMatrix
        from spark_rapids_ml_tpu.parallel.mesh import make_mesh

        x = rng.normal(size=(50, 4))
        with pytest.raises(ValueError, match="backend"):
            RowMatrix([x], backend="triton")
        with pytest.raises(ValueError, match="covarianceBackend"):
            PCA().setCovarianceBackend("triton")
        with pytest.raises(ValueError, match="pallas"):
            PCA(mesh=make_mesh()).setK(2).setCovarianceBackend("pallas").fit(x)
        with pytest.raises(ValueError, match="pallas"):
            PCA().setK(2).setCovarianceBackend("pallas").fit(iter([x]))
        with pytest.raises(ValueError, match="dd"):
            RowMatrix([x], backend="pallas", precision="dd")
        with pytest.raises(ValueError, match="pallas"):
            PCA().setK(2).setSolver("randomized").setCovarianceBackend("pallas")\
                .fit(rng.normal(size=(50, 4)))
        with pytest.raises(ValueError, match="pallas"):
            RowMatrix([x], backend="pallas", use_gemm=False)

    def test_auto_precision_yields_to_pallas(self, rng, monkeypatch):
        """auto precision must not route fp64 input to dd under the
        explicit pallas (fp32-kernel) choice — it falls back to highest
        (r2 review: the combination crashed on real TPUs). Simulated by
        forcing the no-x64 resolution the real chip would produce."""
        import spark_rapids_ml_tpu.linalg.row_matrix as rm_mod
        from spark_rapids_ml_tpu.linalg.row_matrix import RowMatrix
        from spark_rapids_ml_tpu.ops.linalg import resolve_precision

        monkeypatch.setattr(
            rm_mod,
            "resolve_precision",
            lambda req, input_dtype=None: resolve_precision(
                req, input_dtype=input_dtype, x64_enabled=False, platform="tpu"
            ),
        )
        x = rng.normal(size=(60, 4))  # float64 input on a "no-x64 platform"
        assert (
            RowMatrix([x], precision="auto", input_dtype=np.float64).precision
            == "dd"
        )  # the monkeypatched resolution does produce dd...
        rm = RowMatrix(
            [x], backend="pallas", precision="auto", input_dtype=np.float64
        )
        assert rm.precision == "highest"  # ...but pallas downgrades it
        with pytest.raises(ValueError, match="dd"):
            RowMatrix([x], backend="pallas", precision="dd")

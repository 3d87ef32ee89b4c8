"""Constant-memory streaming fits: PCA over one-shot block generators,
reader objects, and iterator factories (the reference's streamed
``mapPartitions`` contract, RapidsRowMatrix.scala:170 — here one pass of
shifted accumulation, one block resident at a time)."""

import subprocess
import sys

import numpy as np
import pytest

from spark_rapids_ml_tpu import native
from spark_rapids_ml_tpu.core.data import is_streaming_source, iter_stream_blocks
from spark_rapids_ml_tpu.feature import PCA
from spark_rapids_ml_tpu.linalg.row_matrix import RowMatrix
from spark_rapids_ml_tpu.ops.covariance import streaming_mean_and_covariance


from spark_rapids_ml_tpu.utils.testing import assert_components_close as _pc_close


class TestStreamingSourceDetection:
    def test_detection(self, rng):
        x = rng.normal(size=(10, 3))
        gen = (b for b in [x])
        assert is_streaming_source(gen)
        assert is_streaming_source(lambda: iter([x]))
        assert not is_streaming_source(x)
        assert not is_streaming_source([x, x])
        assert not is_streaming_source("nope")

    def test_callable_requiring_args_is_not_a_factory(self, rng):
        # A callable that NEEDS arguments is not a zero-arg
        # iterator factory — classifying it as one routes it into
        # multi-pass paths that die with an opaque TypeError.
        from spark_rapids_ml_tpu.core.data import is_reiterable_stream

        needs_arg = lambda path: iter([])  # noqa: E731
        assert not is_streaming_source(needs_arg)
        assert not is_reiterable_stream(needs_arg)
        # Defaults-only callables remain factories.
        with_default = lambda n=2: iter([rng.normal(size=(n, 3))])  # noqa: E731
        assert is_streaming_source(with_default)
        assert is_reiterable_stream(with_default)

    def test_iter_stream_blocks_factory_fresh(self, rng):
        x = rng.normal(size=(4, 2))
        factory = lambda: iter([x, x])  # noqa: E731
        assert len(list(iter_stream_blocks(factory))) == 2
        assert len(list(iter_stream_blocks(factory))) == 2  # re-iterable


class TestStreamingCovariance:
    def test_one_pass_matches_oracle(self, rng):
        x = rng.normal(size=(8_000, 6)) * np.linspace(1, 3, 6) + 100.0
        gen = (x[i : i + 1000] for i in range(0, 8_000, 1000))
        mean, cov, n = streaming_mean_and_covariance(gen)
        assert n == 8_000
        np.testing.assert_allclose(mean, x.mean(axis=0), rtol=1e-9)
        np.testing.assert_allclose(cov, np.cov(x, rowvar=False), atol=1e-6)

    def test_uncentered(self, rng):
        x = rng.normal(size=(500, 4))
        _, m2, _ = streaming_mean_and_covariance(iter([x]), center=False)
        np.testing.assert_allclose(m2, x.T @ x / 499, atol=1e-8)

    def test_empty_raises(self):
        with pytest.raises(ValueError, match="at least 2 rows"):
            streaming_mean_and_covariance(iter([]))


class TestStreamingPCA:
    def test_generator_fit_matches_materialized(self, rng):
        x = rng.normal(size=(6_000, 8)) * np.linspace(1, 2, 8)
        blocks = [x[i : i + 1024] for i in range(0, 6_000, 1024)]
        m_mat = PCA().setK(3).fit(x)
        m_gen = PCA().setK(3).fit(iter(blocks))
        _pc_close(m_gen.pc, m_mat.pc, 1e-6)
        np.testing.assert_allclose(
            m_gen.explainedVariance, m_mat.explainedVariance, atol=1e-8
        )

    def test_factory_fit(self, rng):
        x = rng.normal(size=(2_000, 5))
        factory = lambda: (x[i : i + 500] for i in range(0, 2_000, 500))  # noqa: E731
        model = PCA().setK(2).fit(factory)
        oracle = PCA().setK(2).fit(x)
        _pc_close(model.pc, oracle.pc, 1e-6)

    def test_streaming_dd_ill_conditioned(self, rng):
        d = 6
        x = 1e4 * (1 + np.arange(d)) + np.linspace(1, 2, d) * rng.normal(
            size=(8_000, d)
        )
        gen = (x[i : i + 1024] for i in range(0, 8_000, 1024))
        model = PCA().setK(2).setPrecision("dd").fit(gen)
        cov = np.cov(x, rowvar=False)
        w, v = np.linalg.eigh(cov)
        v = v[:, ::-1]
        _pc_close(model.pc, v[:, :2], 1e-5)

    def test_k_validated_after_stream(self, rng):
        x = rng.normal(size=(100, 3))
        with pytest.raises(ValueError, match="k must be in"):
            PCA().setK(7).fit(iter([x]))

    def test_randomized_solver_rejects_one_shot_stream(self, rng):
        # Re-iterable streams are a real sketch path now
        # (tests/test_wide_features.py); only one-shot generators — which
        # a multi-pass algorithm cannot re-read — are refused.
        with pytest.raises(ValueError, match="one-shot"):
            PCA().setK(2).setSolver("randomized").fit(iter([np.ones((4, 3))]))

    def test_mesh_stream_fit(self, rng):
        """Streaming + mesh is a REAL path now (the north-star loop):
        blocks shard over the data axis with one psum per block."""
        from spark_rapids_ml_tpu.parallel.mesh import make_mesh

        x = rng.normal(size=(640, 4)) + 5.0
        model = PCA(mesh=make_mesh()).setK(2).fit(iter([x[:300], x[300:]]))
        oracle = PCA().setK(2).fit(x)
        _pc_close(model.pc, oracle.pc, 1e-8)

    def test_rowmatrix_shape_unknown_before_pass(self, rng):
        rm = RowMatrix(iter([rng.normal(size=(10, 3))]))
        with pytest.raises(RuntimeError, match="unknown until"):
            _ = rm.num_cols
        rm.compute_covariance()
        assert rm.num_cols == 3 and rm.num_rows == 10


class TestReaderFit:
    @pytest.mark.skipif(
        not native.available(), reason="native library unavailable"
    )
    def test_pca_fit_reader_object(self, rng, tmp_path):
        x = rng.normal(size=(4_096, 6)) * np.linspace(1, 2, 6) + 10.0
        path = str(tmp_path / "data.npy")
        np.save(path, x)
        reader = native.NpyBlockReader(path, block_rows=512)
        try:
            model = PCA().setK(2).fit(reader)
        finally:
            reader.close()
        oracle = PCA().setK(2).fit(x)
        _pc_close(model.pc, oracle.pc, 1e-6)

    @pytest.mark.skipif(
        not native.available(), reason="native library unavailable"
    )
    def test_linreg_fit_reader_blocks(self, rng, tmp_path):
        x = rng.normal(size=(3_000, 4))
        y = x @ np.arange(1.0, 5.0) + 2.0
        path = str(tmp_path / "xdata.npy")
        np.save(path, x)
        from spark_rapids_ml_tpu.regression import LinearRegression

        reader = native.NpyBlockReader(path, block_rows=700)
        try:
            model = LinearRegression().fit((reader.iter_blocks(), y))
        finally:
            reader.close()
        np.testing.assert_allclose(model.coefficients, np.arange(1.0, 5.0), atol=1e-6)
        assert model.intercept == pytest.approx(2.0, abs=1e-6)


class TestConstantMemory:
    @pytest.mark.skipif(
        not native.available(), reason="native library unavailable"
    )
    def test_peak_rss_bounded_below_file_size(self, tmp_path):
        """Fit a file much larger than one block; peak RSS growth over the
        post-import baseline must stay far below the file size — the
        constant-memory contract."""
        n, d = 400_000, 64  # 400k x 64 f64 = ~205 MB
        path = str(tmp_path / "big.npy")
        rng = np.random.default_rng(0)
        # Write in chunks to keep THIS process honest too.
        header = np.lib.format.header_data_from_array_1_0(
            np.empty((0, d), dtype=np.float64)
        )
        header["shape"] = (n, d)
        with open(path, "wb") as f:
            np.lib.format.write_array_header_1_0(f, header)
            for i in range(0, n, 50_000):
                f.write(rng.normal(size=(50_000, d)).tobytes())
        from pathlib import Path

        repo_root = str(Path(__file__).resolve().parents[1])
        script = f"""
import resource, sys
sys.path.insert(0, {repr(repo_root)})
import numpy as np
from spark_rapids_ml_tpu import native
from spark_rapids_ml_tpu.feature import PCA
import jax
jax.config.update("jax_platforms", "cpu")
base = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
reader = native.NpyBlockReader({repr(path)}, block_rows=8192)
model = PCA().setK(4).fit(reader)
peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
assert model.pc.shape == ({d}, 4)
print("GROWTH_KB", peak - base)
"""
        import os

        env = dict(os.environ, JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, "-c", script],
            capture_output=True,
            text=True,
            env=env,
            cwd=repo_root,
            timeout=300,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        growth_kb = int(out.stdout.split("GROWTH_KB")[1].strip())
        # File is ~205 MB; one 8192-row block is ~4 MB. Without the
        # reader's MADV_DONTNEED page release the whole mapping accretes
        # (~330 MB measured); with it, growth is XLA arenas + a few blocks.
        # The bound is loose for run-to-run reclaim variance but decisively
        # below both the no-release behavior and the file size.
        assert growth_kb < 160_000, f"peak RSS grew {growth_kb} KB"


class TestStreamingTransform:
    def test_generator_in_generator_out(self, rng):
        """transform on a streaming source yields projected blocks lazily
        — the symmetric counterpart of the streaming fit."""
        import types

        x = rng.normal(size=(3_000, 6)) * np.linspace(1, 2, 6)
        model = PCA().setK(2).fit(x)
        gen = (x[i : i + 512] for i in range(0, 3_000, 512))
        out = model.transform(gen)
        assert isinstance(out, types.GeneratorType)
        blocks = list(out)
        assert sum(b.shape[0] for b in blocks) == 3_000
        np.testing.assert_allclose(
            np.concatenate(blocks), model.transform(x), atol=1e-9
        )

    @pytest.mark.skipif(
        not native.available(), reason="native library unavailable"
    )
    def test_reader_transform(self, rng, tmp_path):
        x = rng.normal(size=(2_048, 5))
        path = str(tmp_path / "t.npy")
        np.save(path, x)
        model = PCA().setK(2).fit(x)
        reader = native.NpyBlockReader(path, block_rows=300)
        try:
            blocks = list(model.transform(reader))
        finally:
            reader.close()
        np.testing.assert_allclose(
            np.concatenate(blocks), model.transform(x), atol=1e-9
        )

    def test_empty_blocks_skipped(self, rng):
        """Empty partitions (densifying to (0, 0)) must not kill the
        stream — fit or transform (r2 review)."""
        x = rng.normal(size=(900, 4))
        model = PCA().setK(2).fit(iter([x[:400], [], x[400:]]))
        oracle = PCA().setK(2).fit(x)
        _pc_close(model.pc, oracle.pc, 1e-8)
        blocks = list(model.transform(iter([x[:400], [], x[400:]])))
        np.testing.assert_allclose(
            np.concatenate(blocks), model.transform(x), atol=1e-9
        )


class TestStreamingPackedPath:
    @pytest.mark.skipif(
        not native.available(), reason="native library unavailable"
    )
    def test_use_gemm_false_streams_into_native_accumulator(self, rng):
        """useGemm=False on a streaming source routes through the native
        fp64 Kahan accumulator block by block — the streamed twin of the
        materialized packed path."""
        x = rng.normal(size=(4_000, 6)) * np.linspace(1, 2, 6) + 1e3
        gen = (x[i : i + 700] for i in range(0, 4_000, 700))
        rm = RowMatrix(gen, use_gemm=False)
        cov = np.asarray(rm.compute_covariance())
        np.testing.assert_allclose(cov, np.cov(x, rowvar=False), atol=1e-8)
        assert rm.num_rows == 4_000 and rm.num_cols == 6

    @pytest.mark.skipif(
        not native.available(), reason="native library unavailable"
    )
    def test_pca_usegemm_false_reader(self, rng, tmp_path):
        x = rng.normal(size=(2_048, 5)) + 50.0
        path = str(tmp_path / "pk.npy")
        np.save(path, x)
        reader = native.NpyBlockReader(path, block_rows=300)
        try:
            model = PCA().setK(2).setUseGemm(False).fit(reader)
        finally:
            reader.close()
        oracle = PCA().setK(2).fit(x)
        _pc_close(model.pc, oracle.pc, 1e-8)

    @pytest.mark.skipif(
        not native.available(), reason="native library unavailable"
    )
    def test_native_cov_not_downcast(self, rng):
        """The native accumulator's fp64 covariance must reach the
        eigensolve UNCAST — on no-x64 platforms a device-dtype cast would
        round it to f32, wasting the Kahan accumulation (the f32 device
        dtype is forced via the ctor's dtype argument)."""
        import jax.numpy as jnp

        x = rng.normal(size=(2_000, 5)) + 1e3
        rm = RowMatrix([x], use_gemm=False, dtype=jnp.float32)
        cov = rm.compute_covariance()
        assert isinstance(cov, np.ndarray) and cov.dtype == np.float64
        np.testing.assert_allclose(cov, np.cov(x, rowvar=False), atol=1e-8)

"""Wide-feature regime: the randomized sketch now covers
mesh-sharded and re-iterable streaming inputs, so d >= 4096 has a story
that never materializes a (d, d) covariance on one device — beating the
reference's 65535 packed cap (RapidsRowMatrix.scala:66-68) AND its GEMM
path's one-device covariance requirement."""

import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from spark_rapids_ml_tpu.feature import PCA
from spark_rapids_ml_tpu.parallel.mesh import make_mesh
from spark_rapids_ml_tpu.utils.testing import assert_components_close

REPO = str(Path(__file__).resolve().parents[1])


def _decaying(rng, n, d, rank=8):
    """Low-rank + noise data with a spectrum the sketch resolves."""
    u = rng.normal(size=(n, rank))
    v = rng.normal(size=(rank, d))
    scales = np.exp(-np.arange(rank) / 2.0)[None, :]
    return (u * scales) @ v + 0.05 * rng.normal(size=(n, d))


def _oracle(x, k):
    xc = x - x.mean(axis=0)
    cov = xc.T @ xc / (x.shape[0] - 1)
    w, v = np.linalg.eigh(cov)
    w, v = w[::-1], v[:, ::-1]
    return v[:, :k], (w / w.sum())[:k]


class TestRandomizedStreaming:
    def test_factory_matches_oracle(self, rng):
        x = _decaying(rng, 2500, 300)
        blocks = [x[i : i + 512] for i in range(0, 2500, 512)]
        model = PCA().setK(4).setSolver("randomized").fit(lambda: iter(blocks))
        pc_o, ev_o = _oracle(x, 4)
        assert_components_close(model.pc, pc_o, 1e-4)
        np.testing.assert_allclose(model.explainedVariance, ev_o, atol=1e-5)

    def test_matches_materialized_sketch_quality(self, rng):
        # Streamed and materialized sketches see the same data; both must
        # land on the oracle (they use different but equivalent algebra).
        x = _decaying(rng, 1500, 200)
        m_stream = (
            PCA().setK(3).setSolver("randomized").fit(lambda: iter([x]))
        )
        m_mat = PCA().setK(3).setSolver("randomized").fit(x)
        pc_o, _ = _oracle(x, 3)
        assert_components_close(m_stream.pc, pc_o, 1e-4)
        assert_components_close(m_mat.pc, pc_o, 1e-4)

    def test_uncentered_stream_matches_materialized_ratios(self, rng):
        # center=False: Ritz values are RAW second moments — the streamed
        # denominator must be the raw trace, not the centered one (r3
        # review: offset data inflated ratios ~25x).
        x = rng.normal(size=(400, 30)) + 5.0
        m_stream = (
            PCA()
            .setK(3)
            .setSolver("randomized")
            .setMeanCentering(False)
            .fit(lambda: iter([x[:250], x[250:]]))
        )
        m_mat = (
            PCA().setK(3).setSolver("randomized").setMeanCentering(False).fit(x)
        )
        # Dominant ratio tight; the near-degenerate tail (~0.002) carries
        # sketch-approximation noise in BOTH solvers — absolute tolerance.
        np.testing.assert_allclose(
            m_stream.explainedVariance, m_mat.explainedVariance, atol=1e-4
        )
        assert m_stream.explainedVariance[0] == pytest.approx(
            m_mat.explainedVariance[0], rel=1e-6
        )
        assert m_stream.explainedVariance[0] <= 1.0 + 1e-6

    def test_ragged_blocks_reuse_compiled_buckets(self, rng):
        # Ragged block heights pad to power-of-two buckets with MEAN rows
        # (which center to zero) — results stay exact.
        x = _decaying(rng, 1000, 120)
        ragged = [x[:333], x[333:700], x[700:999], x[999:]]
        model = PCA().setK(3).setSolver("randomized").fit(lambda: iter(ragged))
        pc_o, _ = _oracle(x, 3)
        assert_components_close(model.pc, pc_o, 1e-4)

    def test_streaming_with_mesh_rejected_loudly(self, rng):
        x = rng.normal(size=(100, 8))
        with pytest.raises(ValueError, match="single-device"):
            PCA(mesh=make_mesh()).setK(2).setSolver("randomized").fit(
                lambda: iter([x])
            )

    def test_one_shot_generator_rejected(self, rng):
        x = rng.normal(size=(100, 8))
        gen = (b for b in [x])
        with pytest.raises(ValueError, match="one-shot"):
            PCA().setK(2).setSolver("randomized").fit(gen)

    def test_one_shot_generator_stays_on_covariance_path_at_any_width(
        self, rng, monkeypatch
    ):
        monkeypatch.setattr(PCA, "_RANDOMIZED_AUTO_DIM", 16)
        x = rng.normal(size=(200, 32))
        gen = (b for b in [x[:100], x[100:]])
        model = PCA().setK(2).fit(gen)  # auto: must NOT try to re-read
        pc_o, _ = _oracle(x, 2)
        assert_components_close(model.pc, pc_o, 1e-6)

    def test_auto_routes_wide_reiterable_stream_to_sketch(
        self, rng, monkeypatch
    ):
        import spark_rapids_ml_tpu.ops.randomized as R

        called = {}
        orig = R.randomized_pca_streaming

        def spy(*a, **kw):
            called["yes"] = True
            return orig(*a, **kw)

        monkeypatch.setattr(R, "randomized_pca_streaming", spy)
        monkeypatch.setattr(PCA, "_RANDOMIZED_AUTO_DIM", 64)
        x = _decaying(rng, 1200, 128)
        blocks = [x[i : i + 256] for i in range(0, 1200, 256)]
        model = PCA().setK(3).fit(lambda: iter(blocks))
        assert called.get("yes"), "auto did not route to the streaming sketch"
        pc_o, _ = _oracle(x, 3)
        assert_components_close(model.pc, pc_o, 1e-4)


class TestRandomizedMesh:
    def test_mesh_matches_oracle(self, rng):
        x = _decaying(rng, 1100, 160)  # 1100 pads to the 8-device data axis
        parts = [x[:400], x[400:]]
        model = (
            PCA(mesh=make_mesh()).setK(4).setSolver("randomized").fit(parts)
        )
        pc_o, ev_o = _oracle(x, 4)
        assert_components_close(model.pc, pc_o, 1e-4)
        np.testing.assert_allclose(model.explainedVariance, ev_o, atol=1e-5)

    def test_auto_routes_wide_mesh_to_sketch(self, rng, monkeypatch):
        import spark_rapids_ml_tpu.ops.randomized as R

        called = {}
        orig = R.randomized_pca

        def spy(*a, **kw):
            called["yes"] = True
            return orig(*a, **kw)

        monkeypatch.setattr(R, "randomized_pca", spy)
        monkeypatch.setattr(PCA, "_RANDOMIZED_AUTO_DIM", 64)
        x = _decaying(rng, 900, 96)
        model = PCA(mesh=make_mesh()).setK(3).fit(x)
        assert called.get("yes"), "auto did not route the mesh fit to the sketch"
        pc_o, _ = _oracle(x, 3)
        assert_components_close(model.pc, pc_o, 1e-4)

    def test_model_axis_mesh_divisible_works(self, rng):
        # Features divisible by the model axis: the sketch GEMMs contract
        # over the sharded feature dim (GSPMD inserts the psum) — no
        # padding, no (d, d), correct results.
        x = _decaying(rng, 800, 64)
        model = (
            PCA(mesh=make_mesh((4, 2))).setK(3).setSolver("randomized").fit(x)
        )
        pc_o, _ = _oracle(x, 3)
        assert_components_close(model.pc, pc_o, 1e-4)

    def test_auto_2d_mesh_indivisible_width_falls_back(self, rng, monkeypatch):
        # auto must pick a WORKING path: wide d that the model axis would
        # pad keeps the mesh covariance instead of crashing in the sketch.
        monkeypatch.setattr(PCA, "_RANDOMIZED_AUTO_DIM", 64)
        x = rng.normal(size=(160, 65)) * np.linspace(1, 2, 65)
        model = PCA(mesh=make_mesh((4, 2))).setK(3).fit(x)
        pc_o, _ = _oracle(x, 3)
        assert_components_close(model.pc, pc_o, 1e-6)

    def test_model_axis_padding_rejected(self, rng):
        x = rng.normal(size=(160, 31))  # 31 pads on a model axis of 2
        with pytest.raises(ValueError, match="model axis"):
            PCA(mesh=make_mesh((4, 2))).setK(2).setSolver("randomized").fit(x)


class TestWideBoundedMemory:
    @pytest.mark.slow  # ~21 s; runs full-file in CI's Streamed-fit memory bounds step
    def test_16kx8192_streamed_sketch_bounded_rss(self):
        """A 16384 x 8192 fit (1.0 GB as f64 — the matrix is NEVER
        materialized: blocks are computed on demand) at bounded RSS, with
        an orthonormal result. The two former ValueErrors
        (randomized+streaming, randomized+mesh) are gone; this drives the
        streaming one at a width where the (d, d) covariance (512 MB)
        plus the eigh workspace would dwarf the sketch state (d*l ~ 1 MB).
        """
        script = f"""
import resource, sys
sys.path.insert(0, {REPO!r})
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
from spark_rapids_ml_tpu.feature import PCA

n, d, bs = 16384, 8192, 2048
def blocks():
    for i in range(n // bs):
        rng = np.random.default_rng(100 + i)  # per-block, recomputable
        yield rng.normal(size=(bs, d))

base = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
model = PCA().setK(4).setSolver("randomized").fit(blocks)
peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
pc = model.pc
assert pc.shape == (d, 4), pc.shape
g = pc.T @ pc
assert np.abs(g - np.eye(4)).max() < 1e-4, g
print("GROWTH_KB", peak - base)
"""
        import os

        env = dict(os.environ, JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, "-c", script],
            capture_output=True,
            text=True,
            env=env,
            cwd=REPO,
            timeout=560,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        growth_kb = int(out.stdout.split("GROWTH_KB")[1].strip())
        # Full matrix is ~1.05 GB f64 (+ an f32 device copy would be
        # another 512 MB); sketch state is O(d*l + one block). Bound is
        # loose for XLA CPU arenas but decisively below materialization.
        assert growth_kb < 600_000, f"peak RSS grew {growth_kb} KB"

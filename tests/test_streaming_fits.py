"""Re-iterable streaming fits for the ITERATIVE families.

LinearRegression and PCA already stream (single-pass moments / sketch);
these tests pin the new multi-pass streaming paths: KMeans (one data pass
per Lloyd iteration) and LogisticRegression (one data pass per L-BFGS
evaluation), both at O(block + model) memory over the same re-iterable
block contract the streamed PCA sketch uses (iterator factory or
``NpyBlockReader``-style ``.iter_blocks()``).
"""

import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from spark_rapids_ml_tpu.classification import LogisticRegression
from spark_rapids_ml_tpu.clustering import KMeans

REPO = str(Path(__file__).resolve().parents[1])


def _blob_block(seed, n, d=8, k=4):
    rng = np.random.default_rng(seed)
    centers = np.arange(k)[:, None] * 10.0 + np.zeros((k, d))
    labels = rng.integers(0, k, size=n)
    return (centers[labels] + rng.normal(scale=0.5, size=(n, d))).astype(
        np.float64
    ), labels


class TestKMeansStreaming:
    def test_matches_materialized_fit(self):
        blocks = [_blob_block(s, 500)[0] for s in range(4)]

        def factory():
            return iter(blocks)

        streamed = KMeans().setK(4).setSeed(1).fit(factory)
        dense = KMeans().setK(4).setSeed(1).fit(np.concatenate(blocks))
        c_s = np.sort(streamed.clusterCenters(), axis=0)
        c_d = np.sort(dense.clusterCenters(), axis=0)
        assert np.allclose(c_s, c_d, atol=0.2)
        assert streamed.trainingCost == pytest.approx(
            dense.trainingCost, rel=0.02
        )
        assert streamed.numIter >= 1

    def test_one_shot_generator_rejected(self):
        gen = (b for b in [_blob_block(0, 100)[0]])
        with pytest.raises(ValueError, match="RE-ITERABLE"):
            KMeans().setK(2).fit(gen)

    def test_k_exceeds_rows_raises(self):
        def factory():
            return iter([_blob_block(0, 5)[0]])

        with pytest.raises(ValueError, match="exceeds"):
            KMeans().setK(7).fit(factory)

    def test_cosine_streaming(self):
        blocks = [_blob_block(s, 300)[0] + 5.0 for s in range(2)]

        def factory():
            return iter(blocks)

        streamed = (
            KMeans().setK(3).setSeed(2).setDistanceMeasure("cosine").fit(factory)
        )
        dense = (
            KMeans()
            .setK(3)
            .setSeed(2)
            .setDistanceMeasure("cosine")
            .fit(np.concatenate(blocks))
        )
        assert streamed.trainingCost == pytest.approx(dense.trainingCost, rel=0.05)

    def test_warm_start_streaming(self):
        blocks = [_blob_block(s, 400)[0] for s in range(2)]

        def factory():
            return iter(blocks)

        first = KMeans().setK(4).setSeed(0).fit(factory)
        resumed = KMeans().setK(4).setInitialModel(first).setMaxIter(3).fit(factory)
        assert resumed.trainingCost <= first.trainingCost * 1.01


class TestLogisticStreaming:
    def _pairs(self, n_blocks=4, n=400, classes=2, d=6, seed=0):
        rng = np.random.default_rng(seed)
        w = rng.normal(size=(d, classes))
        xs, ys = [], []
        for _ in range(n_blocks):
            x = rng.normal(size=(n, d))
            y = np.argmax(x @ w + rng.normal(scale=0.2, size=(n, classes)), axis=1)
            xs.append(x)
            ys.append(y.astype(np.float64))
        return xs, np.concatenate(ys)

    @pytest.mark.parametrize("classes", [2, 3])
    def test_matches_materialized_fit(self, classes):
        xs, y = self._pairs(classes=classes)

        def factory():
            return iter(xs)

        streamed = (
            LogisticRegression().setRegParam(0.05).fit((factory, y))
        )
        dense = (
            LogisticRegression()
            .setRegParam(0.05)
            .fit((np.concatenate(xs), y))
        )
        assert streamed.numClasses == dense.numClasses
        assert np.allclose(streamed.weights, dense.weights, atol=5e-3)
        assert np.allclose(streamed.intercepts, dense.intercepts, atol=5e-3)

    def test_one_shot_generator_rejected(self):
        xs, y = self._pairs()
        gen = (b for b in xs)
        with pytest.raises(ValueError, match="RE-ITERABLE"):
            LogisticRegression().fit((gen, y))

    def test_fractional_labels_raise(self):
        xs, y = self._pairs()
        y = y.copy()
        y[0] = 0.5

        def factory():
            return iter(xs)

        with pytest.raises(ValueError, match="integers"):
            LogisticRegression().fit((factory, y))

    def test_streaming_elastic_net_rejected(self):
        xs, y = self._pairs()

        def factory():
            return iter(xs)

        with pytest.raises(ValueError, match="elastic"):
            LogisticRegression().setRegParam(0.1).setElasticNetParam(0.5).fit(
                (factory, y)
            )

    def test_no_intercept_no_standardization(self):
        xs, y = self._pairs()

        def factory():
            return iter(xs)

        streamed = (
            LogisticRegression()
            .setFitIntercept(False)
            .setStandardization(False)
            .setRegParam(0.05)
            .fit((factory, y))
        )
        dense = (
            LogisticRegression()
            .setFitIntercept(False)
            .setStandardization(False)
            .setRegParam(0.05)
            .fit((np.concatenate(xs), y))
        )
        assert np.allclose(streamed.weights, dense.weights, atol=5e-3)
        assert np.all(streamed.intercepts == 0.0)


class TestStreamingBoundedMemory:
    """The r3 wide-features pattern: fit in a subprocess, assert RSS growth
    stays far below the materialized dataset size."""

    def _run(self, script):
        import os

        env = dict(os.environ, JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, "-c", script], capture_output=True, env=env
        )
        assert out.returncode == 0, out.stderr.decode()[-3000:]
        growth_kb = int(out.stdout.decode().strip().splitlines()[-1].split()[-1])
        return growth_kb

    @pytest.mark.slow  # ~11 s; runs full-file in CI's Streamed-fit memory bounds step
    def test_kmeans_streaming_bounded_rss(self):
        # 48 x 32768 x 64 f64 = 0.75 GB if materialized; blocks are
        # recomputed on demand so RSS growth must stay a small multiple
        # of one block (16 MB) + compile workspace.
        script = f"""
import resource, sys
sys.path.insert(0, {REPO!r})
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
from spark_rapids_ml_tpu.clustering import KMeans

n_blocks, bs, d = 48, 32768, 64
def blocks():
    for i in range(n_blocks):
        rng = np.random.default_rng(200 + i)
        yield rng.normal(size=(bs, d)) + (i % 4) * 8.0

base = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
model = KMeans().setK(4).setMaxIter(3).fit(blocks)
peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
assert model.clusterCenters().shape == (4, d)
print("GROWTH_KB", peak - base)
"""
        growth_kb = self._run(script)
        assert growth_kb < 400_000, (
            f"RSS grew {growth_kb} kB (dataset is 0.75 GB)"
        )

    @pytest.mark.slow  # ~31 s; runs full-file in CI's Streamed-fit memory bounds step
    def test_logreg_streaming_bounded_rss(self):
        # 48 x 32768 x 64 f64 = 0.75 GB if materialized; the L-BFGS path
        # re-streams every block per evaluation, so iteration count is
        # the wall-clock knob — 8 is past convergence on this separable
        # data and keeps the RSS property (growth << dataset) intact.
        script = f"""
import resource, sys
sys.path.insert(0, {REPO!r})
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
from spark_rapids_ml_tpu.classification import LogisticRegression

n_blocks, bs, d = 48, 32768, 64
rng_w = np.random.default_rng(0)
w = rng_w.normal(size=(d,))
def blocks():
    for i in range(n_blocks):
        rng = np.random.default_rng(300 + i)
        yield rng.normal(size=(bs, d))
def labels():
    out = []
    for i in range(n_blocks):
        rng = np.random.default_rng(300 + i)
        x = rng.normal(size=(bs, d))
        out.append((x @ w > 0).astype(float))
    return np.concatenate(out)

y = labels()
base = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
model = LogisticRegression().setRegParam(0.01).setMaxIter(8).fit((blocks, y))
peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
assert model.weights.shape == (d, 1)
acc = model.evaluate((np.asarray(next(blocks())), y[:bs]))["accuracy"]
assert acc > 0.9, acc
print("GROWTH_KB", peak - base)
"""
        growth_kb = self._run(script)
        assert growth_kb < 400_000, (
            f"RSS grew {growth_kb} kB (dataset is 0.75 GB)"
        )

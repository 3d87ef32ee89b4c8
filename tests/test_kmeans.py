"""KMeans suite. Oracle: exact Lloyd in numpy from the same init (the
framework's own init is deterministic given a seed), plus recovery of
well-separated synthetic clusters — the test strategy the reference family
uses for its kmeans (cuML/RAFT): cluster-recovery + cost monotonicity."""

import numpy as np
import pytest

from spark_rapids_ml_tpu.clustering import KMeans, KMeansModel
from spark_rapids_ml_tpu.core.data import DataFrame
from spark_rapids_ml_tpu.parallel.mesh import make_mesh


def make_blobs(rng, n=300, d=8, k=4, sep=10.0):
    centers = rng.normal(size=(k, d)) * sep
    labels = rng.integers(0, k, size=n)
    x = centers[labels] + rng.normal(size=(n, d))
    return x, centers, labels


def numpy_lloyd(x, init, max_iter=20, tol=1e-4):
    centers = init.copy()
    for _ in range(max_iter):
        d2 = ((x[:, None, :] - centers[None, :, :]) ** 2).sum(-1)
        labels = d2.argmin(1)
        new = np.stack(
            [x[labels == j].mean(0) if (labels == j).any() else centers[j] for j in range(len(centers))]
        )
        moved = ((new - centers) ** 2).sum(1).max()
        centers = new
        if moved <= tol * tol:
            break
    d2 = ((x[:, None, :] - centers[None, :, :]) ** 2).sum(-1)
    return centers, d2.min(1).sum()


class TestKMeansFit:
    def test_recovers_separated_blobs(self, rng):
        x, true_centers, _ = make_blobs(rng)
        model = KMeans().setK(4).setSeed(1).fit(x)
        got = model.clusterCenters()
        # each true center has a fitted center within ~noise distance
        for c in true_centers:
            assert np.min(np.linalg.norm(got - c, axis=1)) < 1.0
        assert model.numIter >= 1
        assert np.isfinite(model.trainingCost)

    def test_matches_numpy_lloyd_from_same_init(self, rng):
        """Seeded framework init fed to a numpy Lloyd oracle must converge to
        the same centers (exact algorithm equivalence, not just quality)."""
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops.kmeans import kmeans_plusplus_init, lloyd

        x, _, _ = make_blobs(rng, n=200, d=5, k=3)
        import jax

        key = jax.random.key(7)
        mask = jnp.ones(200, dtype=x.dtype)
        init = np.asarray(kmeans_plusplus_init(jnp.asarray(x), mask, key, 3))
        ours, cost, _ = lloyd(jnp.asarray(x), mask, jnp.asarray(init), max_iter=50, tol=1e-6)
        theirs, ref_cost = numpy_lloyd(x, init, max_iter=50, tol=1e-6)
        np.testing.assert_allclose(np.asarray(ours), theirs, atol=1e-6)
        np.testing.assert_allclose(float(cost), ref_cost, rtol=1e-8)

    def test_cost_decreases_vs_init(self, rng):
        import jax
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops.kmeans import kmeans_plusplus_init, lloyd, lloyd_step
        from spark_rapids_ml_tpu.ops.linalg import _dot_precision

        x, _, _ = make_blobs(rng, n=150, d=4, k=5, sep=2.0)
        xs = jnp.asarray(x)
        mask = jnp.ones(150, dtype=x.dtype)
        init = kmeans_plusplus_init(xs, mask, jax.random.key(0), 5)
        _, init_cost = lloyd_step(xs, mask, init, jnp.sum(xs * xs, 1), _dot_precision("highest"))
        _, final_cost, _ = lloyd(xs, mask, init, max_iter=30)
        assert float(final_cost) <= float(init_cost) + 1e-9

    def test_random_init_mode(self, rng):
        x, _, _ = make_blobs(rng)
        model = KMeans().setK(4).setInitMode("random").setSeed(3).fit(x)
        assert model.clusterCenters().shape == (4, 8)

    def test_cosine_distance(self, rng):
        # two directions, different magnitudes: cosine must cluster by angle
        a = np.array([1.0, 0.0]) * rng.uniform(0.5, 5.0, size=(50, 1))
        b = np.array([0.0, 1.0]) * rng.uniform(0.5, 5.0, size=(50, 1))
        x = np.concatenate([a, b])
        model = KMeans().setK(2).setDistanceMeasure("cosine").setSeed(0).fit(x)
        pred = model.predict(x)
        assert len(set(pred[:50])) == 1
        assert len(set(pred[50:])) == 1
        assert pred[0] != pred[50]

    def test_k_exceeds_rows(self, rng):
        with pytest.raises(ValueError):
            KMeans().setK(10).fit(rng.normal(size=(5, 3)))

    def test_invalid_params(self):
        with pytest.raises(ValueError):
            KMeans().setInitMode("zzz")
        with pytest.raises(ValueError):
            KMeans().setDistanceMeasure("manhattan")
        with pytest.raises((TypeError, ValueError)):
            KMeans().setK(1)  # k must be > 1


class TestKMeansModel:
    def test_transform_dataframe(self, rng):
        x, _, _ = make_blobs(rng, n=100)
        df = DataFrame({"features": list(x)})
        model = KMeans().setK(4).setSeed(0).fit(df)
        out = model.transform(df)
        assert "prediction" in out.columns
        labels = out.select("prediction")
        assert len(labels) == 100
        assert all(0 <= l < 4 for l in labels)

    def test_predict_consistent_with_centers(self, rng):
        x, _, _ = make_blobs(rng, n=80)
        model = KMeans().setK(4).setSeed(0).fit(x)
        pred = model.predict(x)
        d2 = ((x[:, None, :] - model.clusterCenters()[None]) ** 2).sum(-1)
        np.testing.assert_array_equal(pred, d2.argmin(1))

    def test_compute_cost(self, rng):
        x, _, _ = make_blobs(rng, n=80)
        model = KMeans().setK(4).setSeed(0).fit(x)
        d2 = ((x[:, None, :] - model.clusterCenters()[None]) ** 2).sum(-1)
        np.testing.assert_allclose(model.computeCost(x), d2.min(1).sum(), rtol=1e-6)

    def test_read_write(self, tmp_path, rng):
        x, _, _ = make_blobs(rng, n=60)
        model = KMeans().setK(3).setSeed(0).setPredictionCol("cluster").fit(x)
        path = str(tmp_path / "km")
        model.save(path)
        loaded = KMeansModel.load(path)
        np.testing.assert_allclose(loaded.clusterCenters(), model.clusterCenters())
        assert loaded.getPredictionCol() == "cluster"
        assert loaded.trainingCost == pytest.approx(model.trainingCost)
        np.testing.assert_array_equal(loaded.predict(x), model.predict(x))


class TestDistributed:
    def test_mesh_fit_matches_local(self, rng):
        x, true_centers, _ = make_blobs(rng, n=256, d=6, k=3)
        mesh = make_mesh((8, 1))
        m_mesh = KMeans(mesh=mesh).setK(3).setSeed(5).fit(x)
        m_local = KMeans().setK(3).setSeed(5).fit(x)
        # same seed but different row layouts may pick different inits; check
        # cluster QUALITY parity instead of exact centers
        assert m_mesh.computeCost(x) <= m_local.computeCost(x) * 1.05 + 1e-6
        for c in true_centers:
            assert np.min(np.linalg.norm(m_mesh.clusterCenters() - c, axis=1)) < 1.0

    def test_mesh_padding_rows_ignored(self, rng):
        x, true_centers, _ = make_blobs(rng, n=251, d=6, k=3)  # 251 % 8 != 0
        mesh = make_mesh((8, 1))
        model = KMeans(mesh=mesh).setK(3).setSeed(5).fit(x)
        for c in true_centers:
            assert np.min(np.linalg.norm(model.clusterCenters() - c, axis=1)) < 1.0


class TestReviewRegressions:
    def test_2d_mesh_feature_padding_sliced(self, rng):
        """d=7 on a (4,2) mesh pads features to 8; centers must come back (k,7)."""
        x, true_centers, _ = make_blobs(rng, n=128, d=7, k=3)
        mesh = make_mesh((4, 2))
        model = KMeans(mesh=mesh).setK(3).setSeed(1).fit(x)
        assert model.clusterCenters().shape == (3, 7)
        pred = model.predict(x)  # must not shape-mismatch
        assert pred.shape == (128,)

    def test_cosine_training_consistent_with_predict(self, rng):
        """Training assignments/cost must agree with the fitted model's own
        predict/computeCost (centers renormalized every Lloyd iteration)."""
        x = rng.normal(size=(200, 5)) + 2.0
        model = KMeans().setK(3).setDistanceMeasure("cosine").setSeed(0).fit(x)
        # centers are unit-norm
        np.testing.assert_allclose(
            np.linalg.norm(model.clusterCenters(), axis=1), 1.0, atol=1e-5
        )
        # trainingCost equals recomputed cosine cost on the training data
        assert model.computeCost(x) == pytest.approx(model.trainingCost, rel=1e-5)

    def test_model_persistence_is_per_cluster_rows(self, tmp_path, rng):
        """Spark KMeansModel on-disk layout: k rows of (clusterIdx, VectorUDT)."""
        pytest.importorskip("pyarrow")
        import pyarrow.parquet as pq

        x, _, _ = make_blobs(rng, n=60, k=3)
        model = KMeans().setK(3).setSeed(0).fit(x)
        path = str(tmp_path / "km_rows")
        model.save(path)
        table = pq.read_table(f"{path}/data/part-00000.parquet")
        assert table.num_rows == 3
        assert set(table.column_names) == {"clusterIdx", "clusterCenter"}
        row0 = table.to_pylist()[0]
        assert row0["clusterCenter"]["type"] == 1  # dense VectorUDT struct


class TestWarmStart:
    """setInitialModel: resume/refine from an existing model's centers —
    the recovery path for interrupted long fits (mllib setInitialModel /
    cuML init-array semantics)."""

    def test_resume_converges_from_checkpoint(self, rng):
        from spark_rapids_ml_tpu.clustering import KMeans

        centers_true = np.array([[0.0, 0.0], [8.0, 8.0], [0.0, 8.0]])
        x = np.concatenate(
            [c + rng.normal(scale=0.4, size=(60, 2)) for c in centers_true]
        )
        # "Interrupted" fit: only 1 Lloyd iteration.
        partial = KMeans().setK(3).setSeed(0).setMaxIter(1).fit((x,))
        # Resume from its centers; a converged result must match a full fit.
        resumed = (
            KMeans().setK(3).setMaxIter(50).setInitialModel(partial).fit((x,))
        )
        full = KMeans().setK(3).setSeed(0).setMaxIter(50).fit((x,))
        assert resumed.trainingCost == pytest.approx(full.trainingCost, rel=1e-6)

    def test_shape_validation(self, rng):
        from spark_rapids_ml_tpu.clustering import KMeans

        x = rng.normal(size=(30, 4))
        with pytest.raises(ValueError, match="centers but k"):
            KMeans().setK(3).setInitialModel(np.zeros((2, 4))).fit((x,))
        with pytest.raises(ValueError, match="features"):
            KMeans().setK(2).setInitialModel(np.zeros((2, 3))).fit((x,))

    def test_copy_preserves_warm_start(self):
        from spark_rapids_ml_tpu.clustering import KMeans

        est = KMeans().setK(2).setInitialModel(np.zeros((2, 3)))
        assert est.copy({})._initial_centers.shape == (2, 3)

    def test_setter_raise_leaves_estimator_clean(self):
        from spark_rapids_ml_tpu.clustering import KMeans

        est = KMeans().setK(3)
        with pytest.raises(ValueError):
            est.setInitialModel(np.zeros(3))
        assert est._initial_centers is None  # no corrupted state


def _old_update(labels, k, mb, xb, dot):
    """The centre update as it was before the three-pass form, written
    out: what every mode but ``f32`` / ``highest`` on float32 rows must
    still return bit for bit."""
    import jax
    import jax.numpy as jnp

    one_hot = jax.nn.one_hot(labels, k, dtype=xb.dtype) * mb[:, None]
    return dot(one_hot.T, xb), jnp.sum(one_hot, axis=0)


def _wide_rows(rng, n, d):
    """float32 rows whose exponents spread over e^+-8, both signs."""
    return (rng.normal(size=(n, d)) * np.exp(rng.uniform(-8, 8, size=(n, d)))).astype(np.float32)


def _step_parts(x, mb, centers, dot):
    """(labels, sums, counts, cost) of one Lloyd step's accumulation."""
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.kmeans import _assign_and_accumulate, _sq_dists

    x2 = jnp.sum(x * x, axis=1)
    labels = jnp.argmin(_sq_dists(x, centers, x2, dot), axis=1)
    return (labels, *_assign_and_accumulate(x, mb, x2, centers, centers.shape[0], dot))


def numpy_weighted_lloyd(x, w, init, iters):
    """float64 Lloyd with per-row weights, a fixed number of iterations."""
    x, w, centers = x.astype(np.float64), w.astype(np.float64), init.astype(np.float64)
    for _ in range(iters):
        labels = ((x[:, None, :] - centers[None]) ** 2).sum(-1).argmin(1)
        sums = np.zeros_like(centers)
        np.add.at(sums, labels, x * w[:, None])
        counts = np.bincount(labels, weights=w, minlength=len(centers))
        centers = np.where(counts[:, None] > 0, sums / np.maximum(counts, 1.0)[:, None], centers)
    cost = (((x[:, None, :] - centers[None]) ** 2).sum(-1).min(1) * w).sum()
    return centers, cost


class TestUpdateSplit3:
    """The centre update of float32 rows at ``f32`` / ``highest``: three
    single-pass bf16 products on an exact three-piece split of the rows
    (a one-hot operand is exact in ONE bf16 piece), every other dtype and
    mode as before. A CPU computes every dot exactly, so what these hold
    is the arithmetic and the choice; that the chip's compiler keeps the
    split's roundings is ``chip_smoke.py``'s to show."""

    @pytest.mark.parametrize("kind", ["log_uniform", "short_mantissa", "beside_powers_of_two"])
    def test_split_rebuilds_float32_bit_for_bit(self, rng, kind):
        """Magnitudes 1e-30 to 1e30, both signs, zeros. (Under 2^-103 the
        ``lo`` piece would be subnormal, which a TPU flushes to zero.)"""
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops.precision import split3_bf16

        m = 32_768
        x = (rng.choice([-1.0, 1.0], size=m) * 10.0 ** rng.uniform(-30, 30, size=m)).astype(np.float32)
        if kind == "short_mantissa":  # values that need one or two pieces only
            x = x.view(np.uint32)
            x &= rng.choice(np.array([0xFFFF0000, 0xFFFFFF00, 0xFF800000], np.uint32), size=m)
            x = x.view(np.float32)
        elif kind == "beside_powers_of_two":  # roundings that carry into the exponent
            x = np.nextafter(np.exp2(np.round(np.log2(np.abs(x)))).astype(np.float32),
                             np.where(rng.random(m) < 0.5, 0, np.inf).astype(np.float32)) * np.sign(x)
        x[::97] = 0.0
        pieces = split3_bf16(jnp.asarray(x))
        assert all(p.dtype == jnp.bfloat16 for p in pieces)
        hi, mid, lo = (np.asarray(p).astype(np.float32) for p in pieces)
        # Each piece is its own bfloat16 rounding: one MXU pass is exact.
        for p in (hi, mid, lo):
            np.testing.assert_array_equal(
                np.asarray(jnp.asarray(p).astype(jnp.bfloat16).astype(jnp.float32)), p
            )
        back = (hi + mid) + lo
        np.testing.assert_array_equal(back.view(np.uint32), x.view(np.uint32))
        assert np.all(np.abs(mid) <= np.abs(hi) * 2.0**-8) and np.all(np.abs(lo) <= np.abs(hi) * 2.0**-16)

    def test_three_pass_sums_match_float64_segment_sum(self, rng):
        import jax
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops.kmeans import _onehot_sums_split3
        from spark_rapids_ml_tpu.ops.precision import make_dot

        n, d, k = 20_000, 64, 50
        x = _wide_rows(rng, n, d)
        labels = rng.integers(0, k, size=n).astype(np.int32)
        mb = jnp.ones(n, jnp.float32)
        # Jitted, as every driver runs it (op by op the CPU's bf16 dot sums
        # in another order and reads 4.7e-7 on these heavy-tailed rows).
        sums, counts = jax.jit(_onehot_sums_split3, static_argnums=1)(
            jnp.asarray(labels), k, mb, jnp.asarray(x)
        )
        assert sums.dtype == jnp.float32 and counts.dtype == jnp.float32
        exact = np.zeros((k, d))
        np.add.at(exact, labels, x.astype(np.float64))
        top = np.abs(exact).max()
        assert np.abs(np.asarray(sums) - exact).max() <= 3e-7 * top
        old, old_counts = jax.jit(_old_update, static_argnums=(1, 4))(
            jnp.asarray(labels), k, mb, jnp.asarray(x), make_dot("highest")
        )
        assert np.abs(np.asarray(old) - exact).max() <= 3e-7 * top
        assert np.abs(np.asarray(sums) - np.asarray(old)).max() <= 3e-7 * top
        np.testing.assert_array_equal(np.asarray(counts), np.asarray(old_counts))
        np.testing.assert_array_equal(np.asarray(counts), np.bincount(labels, minlength=k))

    def test_masked_rows_add_to_no_sum_and_no_count(self, rng):
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops.precision import make_dot

        x, _, _ = make_blobs(rng, n=400, d=8, k=4)
        x = x.astype(np.float32)
        centers = jnp.asarray(x[:4])
        dot = make_dot("highest")
        keep = np.ones(400, np.float32)
        keep[300:] = 0.0
        junk = x.copy()
        junk[300:] = 1e6 * rng.normal(size=(100, 8))  # finite: a masked row is padding, never NaN
        _, sums, counts, cost = _step_parts(jnp.asarray(junk), jnp.asarray(keep), centers, dot)
        _, sums_ref, counts_ref, cost_ref = _step_parts(
            jnp.asarray(x[:300]), jnp.ones(300, jnp.float32), centers, dot
        )
        np.testing.assert_array_equal(np.asarray(counts), np.asarray(counts_ref))
        assert float(jnp.sum(counts)) == 300.0
        np.testing.assert_allclose(np.asarray(sums), np.asarray(sums_ref), rtol=0, atol=3e-7 * float(jnp.max(jnp.abs(sums_ref))))
        assert float(cost) == pytest.approx(float(cost_ref), rel=1e-6)

    def test_weighted_lloyd_matches_float64_numpy(self, rng):
        """Fractional weights ride the mask (core/ingest.py::_combine_weights):
        they are multiplied into the rows BEFORE the split, the one-hot
        stays 0/1 on the mask's support, counts are sums of weights."""
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops.kmeans import lloyd
        from spark_rapids_ml_tpu.utils.tracing import clear_counters, counters

        x, _, _ = make_blobs(rng, n=600, d=6, k=4, sep=6.0)
        x = x.astype(np.float32)
        w = rng.uniform(0.25, 3.0, size=600).astype(np.float32)
        w[::50] = 0.0  # and some rows masked out altogether
        init = x[rng.choice(600, 4, replace=False)]
        clear_counters("kmeans.update")
        centers, cost, n_iter = lloyd(
            jnp.asarray(x), jnp.asarray(w), jnp.asarray(init), max_iter=7, tol=0.0
        )
        assert counters("kmeans.update") == {"kmeans.update.split3": 2}  # loop body + final cost
        assert centers.dtype == jnp.float32 and 2 <= int(n_iter) <= 7  # a fixed point stops it early
        want, want_cost = numpy_weighted_lloyd(x, w, init, 7)
        np.testing.assert_allclose(np.asarray(centers), want, atol=2e-5)
        assert float(cost) == pytest.approx(want_cost, rel=1e-5)

    def test_weightcol_fit_of_float32_rows_takes_the_split(self, rng):
        """Through the estimator, as the chip sees it (x64 off: a
        DataFrame's rows are float32 there)."""
        import jax

        from spark_rapids_ml_tpu.utils.tracing import clear_counters, counters

        x, _, _ = make_blobs(rng, n=300, d=5, k=3, sep=8.0)
        w = rng.uniform(0.5, 2.0, size=300)
        init = x[rng.choice(300, 3, replace=False)]
        df = DataFrame({"features": list(x.astype(np.float32)), "w": list(w)})
        clear_counters("kmeans.update")
        with jax.enable_x64(False):
            model = (
                KMeans().setK(3).setWeightCol("w").setInitialModel(init)
                .setMaxIter(6).setTol(0.0).fit(df)
            )
            got = model.clusterCenters()
        seen = counters("kmeans.update")
        assert seen.get("kmeans.update.split3", 0) >= 1 and "kmeans.update.matmul" not in seen
        want, _ = numpy_weighted_lloyd(x.astype(np.float32), w, init, 6)
        np.testing.assert_allclose(got, want, atol=2e-5)

    @pytest.mark.parametrize(
        "dtype,mode",
        [
            ("float64", "highest"),
            ("float64", "f32"),
            ("float32", "high"),
            ("float32", "bf16x3"),
            ("float32", "bf16"),
            ("float32", "default"),
        ],
    )
    def test_other_dtypes_and_modes_keep_the_old_update_bit_for_bit(self, rng, dtype, mode):
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops.precision import make_dot
        from spark_rapids_ml_tpu.utils.tracing import clear_counters, counters

        x = _wide_rows(rng, 2000, 32).astype(dtype)
        mb = rng.uniform(0.5, 2.0, size=2000).astype(dtype)
        mb[::7] = 0.0
        centers = jnp.asarray(x[:20])
        dot = make_dot(mode)
        clear_counters("kmeans.update")
        labels, sums, counts, _ = _step_parts(jnp.asarray(x), jnp.asarray(mb), centers, dot)
        assert counters("kmeans.update") == {"kmeans.update.matmul": 1}
        want, want_counts = _old_update(labels, 20, jnp.asarray(mb), jnp.asarray(x), dot)
        assert sums.dtype == want.dtype == jnp.dtype(dtype)
        np.testing.assert_array_equal(np.asarray(sums), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(counts), np.asarray(want_counts))

    def test_registered_test_mode_keeps_the_old_update(self, rng):
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops import precision
        from spark_rapids_ml_tpu.utils.tracing import clear_counters, counters

        calls = []

        def counting_dot(a, b):
            calls.append(a.shape)
            return jnp.matmul(a, b, precision="highest")

        precision.register_test_mode("counting", counting_dot)
        try:
            x = _wide_rows(rng, 500, 16)
            clear_counters("kmeans.update")
            _step_parts(jnp.asarray(x), jnp.ones(500, jnp.float32), jnp.asarray(x[:5]), precision.make_dot("counting"))
        finally:
            precision.clear_test_modes()
        assert counters("kmeans.update") == {"kmeans.update.matmul": 1}
        assert (5, 500) in calls  # the update went through the mode's own dot

    @pytest.mark.parametrize(
        "spelling,full",
        [
            ("f32", True), ("highest", True), ("enum", True),
            ("high", False), ("default", False), ("bf16x3", False), ("bf16", False),
        ],
    )
    def test_what_counts_as_the_full_precision_matmul(self, spelling, full):
        import jax

        from spark_rapids_ml_tpu.ops.precision import as_dot, is_highest_matmul

        dot = as_dot(jax.lax.Precision.HIGHEST if spelling == "enum" else spelling)
        assert is_highest_matmul(dot) is full

    def test_monolithic_and_segmented_drivers_stay_bit_identical(self, rng):
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops.kmeans import lloyd, lloyd_resumable
        from spark_rapids_ml_tpu.robustness.checkpoint import EphemeralSegmenter
        from spark_rapids_ml_tpu.utils.tracing import clear_counters, counters

        x, _, _ = make_blobs(rng, n=500, d=7, k=5, sep=3.0)
        x = jnp.asarray(x.astype(np.float32))
        w = jnp.asarray(rng.uniform(0.5, 2.0, size=500).astype(np.float32))
        init = x[:5]
        clear_counters("kmeans.update")
        mono = lloyd(x, w, init, max_iter=9, tol=0.0)
        seg = lloyd_resumable(x, w, init, EphemeralSegmenter(2), max_iter=9, tol=0.0)
        assert "kmeans.update.matmul" not in counters("kmeans.update")
        for a, b in zip(mono, seg):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert int(mono[2]) >= 3  # several segments of two iterations

    def test_blocked_scan_and_streaming_block_share_the_update(self, rng):
        import jax
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops.kmeans import block_suff_stats, lloyd_step
        from spark_rapids_ml_tpu.ops.precision import make_dot
        from spark_rapids_ml_tpu.utils.tracing import clear_counters, counters

        x = jnp.asarray(_wide_rows(rng, 1024, 24))
        centers = x[:8]
        ones = jnp.ones(1024, jnp.float32)
        dot = make_dot("highest")
        clear_counters("kmeans.update")
        sums, counts, cost = block_suff_stats(x, centers)  # the streaming driver's block program
        assert counters("kmeans.update") == {"kmeans.update.split3": 1}
        x2 = jnp.sum(x * x, axis=1)
        step = jax.jit(lloyd_step, static_argnames=("dot", "block_rows"))
        whole, _ = step(x, ones, centers, x2, dot=dot)
        blocked, _ = step(x, ones, centers, x2, dot=dot, block_rows=256)
        assert counters("kmeans.update") == {"kmeans.update.split3": 3}
        from_stats = jnp.where(counts[:, None] > 0, sums / jnp.maximum(counts, 1.0)[:, None], centers)
        tol = 3e-7 * float(jnp.max(jnp.abs(whole)))
        np.testing.assert_allclose(np.asarray(from_stats), np.asarray(whole), rtol=0, atol=tol)
        np.testing.assert_allclose(np.asarray(blocked), np.asarray(whole), rtol=0, atol=tol)

    def test_counters_count_traced_programs_not_calls(self, rng):
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops.kmeans import lloyd
        from spark_rapids_ml_tpu.utils.tracing import clear_counters, counters

        x = _wide_rows(rng, 333, 9)  # a shape no other test compiles
        args32 = (jnp.asarray(x), jnp.ones(333, jnp.float32), jnp.asarray(x[:3]))
        args64 = tuple(a.astype(jnp.float64) for a in args32)
        clear_counters("kmeans.update")
        lloyd(*args32, max_iter=2)
        first = counters("kmeans.update")
        lloyd(*args32, max_iter=2)  # same program: nothing is traced again
        assert counters("kmeans.update") == first == {"kmeans.update.split3": 2}
        lloyd(*args64, max_iter=2)
        assert counters("kmeans.update") == {"kmeans.update.split3": 2, "kmeans.update.matmul": 2}

    def test_row_sharded_fit_takes_the_split_and_matches_local(self, rng):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from spark_rapids_ml_tpu.ops.kmeans import lloyd

        x, _, _ = make_blobs(rng, n=512, d=6, k=4, sep=6.0)
        x = x.astype(np.float32)
        mask = np.ones(512, np.float32)
        init = x[rng.choice(512, 4, replace=False)]
        mesh = make_mesh((8, 1))
        rows = NamedSharding(mesh, P(mesh.axis_names[0]))
        xs = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P(mesh.axis_names[0], None)))
        ms = jax.device_put(jnp.asarray(mask), rows)
        local = lloyd(jnp.asarray(x), jnp.asarray(mask), jnp.asarray(init), max_iter=5, tol=0.0)
        sharded = lloyd(xs, ms, jnp.asarray(init), max_iter=5, tol=0.0, data_shards=8)
        np.testing.assert_allclose(np.asarray(sharded[0]), np.asarray(local[0]), atol=2e-5)
        assert float(sharded[1]) == pytest.approx(float(local[1]), rel=1e-5)

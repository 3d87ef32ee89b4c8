"""Device-side evaluator kernels must agree with the host evaluators —
the scale path (jax-array or >=1M-row tuples) vs the validation-fold path
(the AUC sort no longer collects to host)."""

import numpy as np
import pytest

import jax.numpy as jnp

from spark_rapids_ml_tpu.evaluation import (
    BinaryClassificationEvaluator,
    MulticlassClassificationEvaluator,
    RegressionEvaluator,
)
from spark_rapids_ml_tpu.ops.metrics import (
    binary_auc_device,
    confusion_matrix_device,
    multiclass_metrics_device,
    regression_metrics_device,
)


class TestRegressionDevice:
    def test_matches_host(self, rng):
        y = rng.normal(size=5000) * 3 + 1
        p = y + 0.3 * rng.normal(size=5000)
        for m in ("rmse", "mse", "mae", "r2"):
            ev = RegressionEvaluator().setMetricName(m)
            host = ev.evaluate((y, p))
            dev = ev.evaluate((jnp.asarray(y), jnp.asarray(p)))  # device route
            assert dev == pytest.approx(host, rel=1e-9)
        rmse, mse, mae, r2 = regression_metrics_device(jnp.asarray(y), jnp.asarray(p))
        assert float(rmse) == pytest.approx(np.sqrt(np.mean((y - p) ** 2)))


class TestMulticlassDevice:
    def test_matches_host(self, rng):
        y = rng.integers(0, 4, 3000).astype(float)
        p = np.where(rng.uniform(size=3000) < 0.7, y, rng.integers(0, 4, 3000)).astype(float)
        for m in ("accuracy", "f1", "weightedPrecision", "weightedRecall"):
            ev = MulticlassClassificationEvaluator().setMetricName(m)
            host = ev.evaluate((y, p))
            dev = ev.evaluate((jnp.asarray(y), jnp.asarray(p)))
            assert dev == pytest.approx(host, rel=1e-9), m

    def test_confusion_matrix(self, rng):
        y = rng.integers(0, 3, 500)
        p = rng.integers(0, 3, 500)
        cm = np.asarray(confusion_matrix_device(jnp.asarray(y), jnp.asarray(p), 3))
        for a in range(3):
            for b in range(3):
                assert cm[a, b] == np.sum((y == a) & (p == b))

    def test_single_class_predictions(self):
        """All predictions one class: precision of empty classes is 0."""
        y = jnp.asarray([0, 1, 2, 1])
        p = jnp.asarray([1, 1, 1, 1])
        out = multiclass_metrics_device(y, p, 3)
        assert out["accuracy"] == pytest.approx(0.5)
        assert 0.0 <= out["weightedPrecision"] <= 1.0


class TestBinaryAUCDevice:
    def test_matches_host(self, rng):
        y = rng.integers(0, 2, 4000).astype(float)
        s = y * 0.8 + rng.normal(size=4000)
        for m in ("areaUnderROC", "areaUnderPR"):
            ev = BinaryClassificationEvaluator().setMetricName(m)
            host = ev.evaluate((y, s))
            dev = ev.evaluate((jnp.asarray(y), jnp.asarray(s)))
            assert dev == pytest.approx(host, rel=1e-6), m

    def test_ties_match_host(self, rng):
        """Heavy score ties: the tie-grouped curve must agree exactly."""
        y = rng.integers(0, 2, 1000).astype(float)
        s = np.round(y * 0.5 + rng.normal(size=1000), 1)  # many ties
        for m in ("areaUnderROC", "areaUnderPR"):
            ev = BinaryClassificationEvaluator().setMetricName(m)
            host = ev.evaluate((y, s))
            dev = float(binary_auc_device(jnp.asarray(y), jnp.asarray(s), metric=m))
            assert dev == pytest.approx(host, rel=1e-6), m

    def test_degenerate_single_class(self):
        y = jnp.zeros(50)
        s = jnp.linspace(0, 1, 50)
        assert float(binary_auc_device(y, s)) == 0.0

    def test_perfect_separation(self):
        y = jnp.asarray([0.0] * 50 + [1.0] * 50)
        s = jnp.concatenate([jnp.linspace(0, 0.4, 50), jnp.linspace(0.6, 1.0, 50)])
        assert float(binary_auc_device(y, s)) == pytest.approx(1.0)


class TestPrecisionRouting:
    def test_host_f64_not_demoted_without_x64(self, rng, monkeypatch):
        """A big host float64 tuple must stay on the exact host path when
        the device would compute it at f32 (r2 review: 8% rmse error on
        large-offset targets)."""
        import spark_rapids_ml_tpu.evaluation as ev_mod

        monkeypatch.setattr(ev_mod, "_DEVICE_THRESHOLD", 100)
        y = rng.normal(size=1_000) + 1e6
        p = y + 0.01 * rng.normal(size=1_000)

        import jax

        # Simulate the no-x64 platform decision without flipping the
        # global flag mid-suite: patch the config object the router reads.
        class _Cfg:
            jax_enable_x64 = False

        real_config = jax.config
        monkeypatch.setattr(ev_mod, "_device_pair", ev_mod._device_pair)
        # Directly check the routing decision instead.
        monkeypatch.setattr(jax, "config", _Cfg)
        try:
            routed = ev_mod._device_pair((y, p))
        finally:
            monkeypatch.setattr(jax, "config", real_config)
        assert routed is None  # stays host-side: exact f64

        # f32 host input of the same size IS routed (no precision loss).
        monkeypatch.setattr(jax, "config", _Cfg)
        try:
            routed32 = ev_mod._device_pair(
                (y.astype(np.float32), p.astype(np.float32))
            )
        finally:
            monkeypatch.setattr(jax, "config", real_config)
        assert routed32 is not None

    def test_multiclass_fallback_keeps_original_columns(self, rng, monkeypatch):
        """Labels failing the bincount gate must evaluate from the ORIGINAL
        columns, not a device round-trip (r2 review)."""
        import spark_rapids_ml_tpu.evaluation as ev_mod

        monkeypatch.setattr(ev_mod, "_DEVICE_THRESHOLD", 100)
        # Sparse large IDs: gate rejects; host np.unique handles exactly.
        y = rng.choice([7.0, 123456.0], size=500)
        p = np.where(rng.uniform(size=500) < 0.8, y, 7.0)
        ev = MulticlassClassificationEvaluator().setMetricName("accuracy")
        assert ev.evaluate((y, p)) == pytest.approx(np.mean(y == p))


class TestAUCSortAttack:
    """The sort-attack rewrite has two
    code paths: the packed-uint64 single sort (f32 scores under x64) and
    the variadic key+label sort (everything else). Both must reproduce
    the host tie-grouped curve; the packed path must survive the exact
    hazards that killed the pack32 candidate (tie splitting, -0.0)."""

    def _host(self, y, s, m):
        ev = BinaryClassificationEvaluator().setMetricName(m)
        return ev.evaluate((y.astype(np.float64), s.astype(np.float64)))

    @pytest.mark.parametrize("metric", ["areaUnderROC", "areaUnderPR"])
    @pytest.mark.parametrize("dtype", [np.float32, np.float64])
    def test_ties_10k_both_branches(self, rng, metric, dtype):
        """f32 under x64 dispatches the packed sort, f64 the variadic
        sort — same 10k heavy-ties fixture, same host oracle."""
        y = rng.integers(0, 2, 10_000).astype(np.float64)
        s = np.round(y * 0.5 + rng.normal(size=10_000), 1).astype(dtype)
        dev = float(
            binary_auc_device(jnp.asarray(y), jnp.asarray(s), metric=metric)
        )
        assert dev == pytest.approx(self._host(y, s, metric), rel=1e-6)

    def test_branches_agree(self, rng):
        """The two dispatch branches compute one definition: f32 scores
        (packed) vs their f64 copy (variadic) on ties-free data."""
        y = rng.integers(0, 2, 10_000).astype(np.float64)
        s32 = (y * 0.3 + rng.normal(size=10_000)).astype(np.float32)
        for m in ("areaUnderROC", "areaUnderPR"):
            a32 = float(binary_auc_device(jnp.asarray(y), jnp.asarray(s32), metric=m))
            a64 = float(
                binary_auc_device(
                    jnp.asarray(y), jnp.asarray(s32.astype(np.float64)), metric=m
                )
            )
            assert a32 == pytest.approx(a64, rel=1e-6), m

    def test_negative_zero_one_tie_group(self):
        """-0.0 and +0.0 compare equal but have different bit patterns:
        the packed path must canonicalize before the bit transform or the
        zeros split into two tie groups (the bug XLA's `s + 0.0` folding
        would resurrect — see the kernel comment)."""
        y = np.array([1.0, 0.0, 1.0, 0.0])
        s_zero = np.array([-0.0, 0.0, 0.5, -0.25], dtype=np.float32)
        s_tied = np.array([0.0, 0.0, 0.5, -0.25], dtype=np.float32)
        for m in ("areaUnderROC", "areaUnderPR"):
            a_zero = float(binary_auc_device(jnp.asarray(y), jnp.asarray(s_zero), metric=m))
            a_tied = float(binary_auc_device(jnp.asarray(y), jnp.asarray(s_tied), metric=m))
            assert a_zero == a_tied, m
            # rel 1e-6: the packed path divides in the score's f32 dtype.
            assert a_zero == pytest.approx(self._host(y, s_zero, m), rel=1e-6), m

    def test_adjacent_floats_stay_distinct(self):
        """The pack32 candidate collapsed adjacent f32 scores with even
        keys (label stole the LSB) — the exactness probe that rejected
        it. The shipped pack64 keeps all 32 key bits: a one-ULP score gap
        must still separate the curve points."""
        lo = np.float32(0.5)
        hi = np.nextafter(lo, np.float32(1.0), dtype=np.float32)
        y = np.array([0.0, 1.0])
        s = np.array([lo, hi], dtype=np.float32)
        assert float(binary_auc_device(jnp.asarray(y), jnp.asarray(s))) == 1.0

    @pytest.mark.parametrize("n", [1, 2, 17, 1000])
    def test_sizes_vs_host(self, rng, n):
        y = rng.integers(0, 2, n).astype(np.float64)
        s = rng.normal(size=n).astype(np.float32)
        for m in ("areaUnderROC", "areaUnderPR"):
            dev = float(binary_auc_device(jnp.asarray(y), jnp.asarray(s), metric=m))
            if np.all(y == y[0]):  # degenerate: device defines 0.0
                assert dev == 0.0
            else:
                assert dev == pytest.approx(self._host(y, s, m), rel=1e-6), m

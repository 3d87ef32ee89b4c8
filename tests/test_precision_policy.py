"""Mixed-precision MXU policy layer contracts (ISSUE 17).

The load-bearing promises, each pinned here:

  - the default policy is TODAY'S numerics bit-for-bit: ``f32`` is the
    same primitive sequence as ``lax.Precision.HIGHEST``, and every op's
    default-precision output is unchanged;
  - ``bf16x3`` (3-pass compensated GEMM, arXiv:2112.09017) stays within
    its documented GEMM-level bound (``REL_TOL``) on every hot-path op
    family, and plain ``bf16`` within its own, on this backend — the
    hi/lo splits are bf16-representable so the parity bars are
    backend-portable, which is what makes them CPU-CI-testable;
  - the packed KMeans kernel's unused-slot sentinel and the compensated
    split are bf16-safe: finite sentinels survive the hi/lo split
    (``split_hi_lo(inf)`` manufactures NaN — the hazard the finite
    ``_UNUSED_SCORE`` guards against), pinned at config17's exact
    geometry;
  - policy layering is explicit > per-family env > global env >
    committed autotune decision > family default, and the autotuner is
    the ONLY path that can change numerics without an operator setting
    a knob — so with ``TPUML_AUTOTUNE`` off, resolution is pure and
    allocation-light, adds zero compiles, and fits are bit-identical;
  - the autotuner gate NEVER commits a parity-violating mode: a seeded
    fast-but-wrong GEMM is recorded ``rejected`` with reason
    ``parity`` and the incumbent stands;
  - segmented/checkpoint-resumable fits under a fixed non-default
    policy remain bit-identical to the monolithic fit.
"""

import logging
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_ml_tpu.observability import autotune, costs
from spark_rapids_ml_tpu.ops import precision as prec
from spark_rapids_ml_tpu.ops.precision import (
    FAMILIES,
    PASSES,
    REL_TOL,
    as_dot,
    active_mode,
    active_modes,
    make_dot,
    pallas_precision,
    pdot,
    register_test_mode,
    resolve_policy,
    roofline_peak_scale,
    split_hi_lo,
    tune_precision,
    validate_mode,
)
from spark_rapids_ml_tpu.utils.tracing import clear_counters, counter_value


@pytest.fixture(autouse=True)
def _clean_registry():
    prec.reset_for_tests()
    yield
    prec.reset_for_tests()


@pytest.fixture
def tuner(monkeypatch, tmp_path):
    """Armed tuner over a tmp-file store (mirrors test_autotune.py)."""
    monkeypatch.setenv("TPUML_AUTOTUNE", "on")
    monkeypatch.setenv("TPUML_TUNE_STORE", str(tmp_path / "tune.json"))
    clear_counters("autotune.")
    costs.reset_for_tests()
    autotune.reset_for_tests()
    t = autotune.active()
    assert t is not None
    yield t
    autotune.configure(enable=False)
    costs.configure(enable=False)


@pytest.fixture
def slower_candidates(monkeypatch):
    """The probe's real results under FIXED walls, every candidate three
    times the f32 reference: the gate's logic is under test, not the
    load of a machine whose other workers share the clock."""
    def fixed(a, b, mode, repeats=3):
        out = np.asarray(prec._probe_gemm(a, b, mode))
        return out, (1e-3 if mode == "f32" else 3e-3)

    monkeypatch.setattr(prec, "_time_probe", fixed)


@pytest.fixture
def off(monkeypatch):
    monkeypatch.delenv("TPUML_AUTOTUNE", raising=False)
    monkeypatch.delenv("TPUML_PRECISION", raising=False)
    clear_counters("autotune.")
    costs.reset_for_tests()
    autotune.reset_for_tests()
    assert autotune.active() is None
    yield


def _rel_err(got, ref):
    ref = np.asarray(ref, dtype=np.float64)
    got = np.asarray(got, dtype=np.float64)
    scale = np.max(np.abs(ref)) or 1.0
    return float(np.max(np.abs(got - ref))) / scale


# ---------------------------------------------------------------------------
# vocabulary and the dot chokepoint
# ---------------------------------------------------------------------------


class TestVocabulary:
    def test_modes_and_legacy_validate(self):
        for m in ("f32", "bf16x3", "bf16", "highest", "high", "default"):
            assert validate_mode(m) == m

    def test_unknown_mode_rejected(self):
        with pytest.raises(ValueError, match="precision mode"):
            validate_mode("fp8")

    def test_registered_test_mode_extends_vocabulary(self):
        register_test_mode("unittest_mode", jnp.matmul, rel_tol=1.0)
        assert validate_mode("unittest_mode") == "unittest_mode"
        prec.clear_test_modes()
        with pytest.raises(ValueError):
            validate_mode("unittest_mode")

    def test_pass_counts(self):
        # The roofline scaling hangs off these: f32 = 6 bf16 passes on
        # the MXU, compensated = 3, plain bf16 = 1.
        assert PASSES["f32"] == PASSES["highest"] == 6
        assert PASSES["bf16x3"] == PASSES["high"] == 3
        assert PASSES["bf16"] == PASSES["default"] == 1

    def test_pallas_mapping(self):
        # The pallas kernels' "high" emulation IS the 3-pass split.
        assert pallas_precision("f32") == "highest"
        assert pallas_precision("bf16x3") == "high"
        assert pallas_precision("bf16") == "default"
        assert pallas_precision("highest") == "highest"  # legacy passthrough

    def test_as_dot_coerces_every_historical_spelling(self, rng):
        a = jnp.asarray(rng.normal(size=(8, 5)).astype(np.float32))
        b = jnp.asarray(rng.normal(size=(5, 7)).astype(np.float32))
        ref = jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
        for spelling in ("highest", "f32", jax.lax.Precision.HIGHEST,
                         make_dot("f32")):
            np.testing.assert_array_equal(
                np.asarray(as_dot(spelling)(a, b)), np.asarray(ref)
            )


class TestSplitHiLo:
    def test_exact_decomposition(self, rng):
        a = jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32) * 100)
        hi, lo = split_hi_lo(a)
        np.testing.assert_array_equal(np.asarray(hi + lo), np.asarray(a))
        # hi is exactly the bf16 rounding (round-trip identity) and lo is
        # the residual carrying the next mantissa bits — at most half a
        # bf16 ulp of each element (<= 2^-8 |a| elementwise).
        np.testing.assert_array_equal(
            np.asarray(hi), np.asarray(hi.astype(jnp.bfloat16).astype(jnp.float32))
        )
        assert bool(jnp.all(jnp.abs(lo) <= 2.0 ** -8 * jnp.abs(a)))

    def test_inf_manufactures_nan(self):
        # The documented hazard: hi(inf)=inf, lo = inf - inf = NaN. This
        # is WHY compensated-path sentinels must stay finite.
        _, lo = split_hi_lo(jnp.asarray([jnp.inf], dtype=jnp.float32))
        assert np.isnan(np.asarray(lo))[0]

    def test_sentinel_and_clamp_constants_are_bf16_exact(self):
        from spark_rapids_ml_tpu.ops.pallas.kmeans import _UNUSED_SCORE

        for c in (_UNUSED_SCORE, 4.0):
            v = jnp.asarray(c, dtype=jnp.float32)
            assert np.isfinite(float(v))
            assert float(v.astype(jnp.bfloat16).astype(jnp.float32)) == float(v)
            hi, lo = split_hi_lo(v)
            assert float(hi) == float(v) and float(lo) == 0.0


# ---------------------------------------------------------------------------
# parity: per-family GEMM-level bounds, f32 bit identity
# ---------------------------------------------------------------------------


class TestDotParity:
    def test_f32_is_highest_bit_for_bit(self, rng):
        a = jnp.asarray(rng.normal(size=(96, 48)).astype(np.float32))
        b = jnp.asarray(rng.normal(size=(48, 33)).astype(np.float32))
        ref = jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
        np.testing.assert_array_equal(
            np.asarray(pdot(a, b, "f32")), np.asarray(ref)
        )

    @pytest.mark.parametrize("mode", ["bf16x3", "bf16"])
    def test_raw_gemm_within_documented_bound(self, rng, mode):
        a = jnp.asarray(rng.normal(size=(256, 128)).astype(np.float32))
        b = jnp.asarray(rng.normal(size=(128, 64)).astype(np.float32))
        ref = pdot(a, b, "f32")
        assert _rel_err(pdot(a, b, mode), ref) <= REL_TOL[mode]

    @pytest.mark.parametrize("mode", ["bf16x3", "bf16"])
    def test_covariance_family(self, rng, mode):
        from spark_rapids_ml_tpu.ops.covariance import centered_gram

        x = jnp.asarray(rng.normal(size=(400, 32)).astype(np.float32))
        mean = jnp.mean(x, axis=0)
        ref = centered_gram(x, mean, precision="f32")
        assert _rel_err(centered_gram(x, mean, precision=mode), ref) <= REL_TOL[mode]

    @pytest.mark.parametrize("mode", ["bf16x3", "bf16"])
    def test_linear_family(self, rng, mode):
        from spark_rapids_ml_tpu.ops.linear import normal_eq_stats, predict_linear

        x = jnp.asarray(rng.normal(size=(300, 16)).astype(np.float32))
        y = jnp.asarray(rng.normal(size=(300,)).astype(np.float32))
        ref = normal_eq_stats(x, y, None, precision="f32")
        got = normal_eq_stats(x, y, None, precision=mode)
        assert _rel_err(got.a, ref.a) <= REL_TOL[mode]  # Xc^T Xc
        assert _rel_err(got.b, ref.b) <= REL_TOL[mode]  # Xc^T yc
        coef = jnp.asarray(rng.normal(size=(16,)).astype(np.float32))
        pref = predict_linear(x, coef, 0.5, precision="f32")
        assert _rel_err(predict_linear(x, coef, 0.5, precision=mode), pref) <= REL_TOL[mode]

    @pytest.mark.parametrize("mode", ["bf16x3", "bf16"])
    def test_logistic_family_forward(self, rng, mode):
        from spark_rapids_ml_tpu.ops.logistic import predict_logistic

        x = jnp.asarray(rng.normal(size=(200, 24)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(24, 4)).astype(np.float32))
        b = jnp.asarray(rng.normal(size=(4,)).astype(np.float32))
        _, _, ref = predict_logistic(x, w, b, 4, precision="f32")
        _, _, raw = predict_logistic(x, w, b, 4, precision=mode)
        assert _rel_err(raw, ref) <= REL_TOL[mode]

    @pytest.mark.parametrize("mode", ["bf16x3", "bf16"])
    def test_kmeans_family_assignment(self, rng, mode):
        from spark_rapids_ml_tpu.ops.kmeans import assign_clusters

        # Well-separated clusters: the assignment itself must be
        # mode-invariant, and the distances within the GEMM bound.
        k, d = 8, 16
        centers = jnp.asarray((rng.normal(size=(k, d)) * 10).astype(np.float32))
        x = jnp.asarray(
            (np.repeat(np.asarray(centers), 50, axis=0)
             + rng.normal(size=(k * 50, d)).astype(np.float32) * 0.1)
        )
        lref, dref = assign_clusters(x, centers, precision="f32")
        lgot, dgot = assign_clusters(x, centers, precision=mode)
        np.testing.assert_array_equal(np.asarray(lgot), np.asarray(lref))
        # Distances go through x2 - 2 x·c + c2 with cancellation; allow
        # the bound on the GEMM term (scale = max |x·c|).
        scale = float(np.max(np.abs(np.asarray(x) @ np.asarray(centers).T)))
        assert float(np.max(np.abs(np.asarray(dgot - dref)))) / scale <= 2 * REL_TOL[mode]

    def test_pca_family_randomized_sketch(self, rng):
        from spark_rapids_ml_tpu.ops.randomized import randomized_pca

        x = jnp.asarray(
            (rng.normal(size=(200, 24)) * np.linspace(1, 4, 24)).astype(np.float32)
        )
        key = jax.random.PRNGKey(0)
        ref = randomized_pca(x, 3, key, precision="f32")
        got = randomized_pca(x, 3, key, precision="bf16x3")
        # Subspace agreement (eigvectors sign-free); the power iterations
        # amplify GEMM error, so the bar is looser than the raw bound.
        for a, b in zip(np.asarray(got[0]).T, np.asarray(ref[0]).T):
            assert abs(float(np.dot(a, b))) > 1 - 1e-4


class TestPackedKernelConfig17:
    """Satellite 2: the 128-lane packed kernel at config17's exact shape
    pair (d=16, k=16) must stay NaN-free and reference-exact under the
    compensated mapping — the finite ``_UNUSED_SCORE`` sentinel is what
    makes the bf16 hi/lo split safe in the unused lane-group slots."""

    @pytest.mark.parametrize("mode", ["f32", "bf16x3", "bf16"])
    def test_packed_stats_finite_and_match_unpacked(self, mode):
        from spark_rapids_ml_tpu.ops.pallas.kmeans import (
            assign_stats_fused,
            assign_stats_packed,
            pad_transposed,
        )

        n, d, k = 777, 16, 16  # config17 geometry (D17=16, K17=16)
        rng = np.random.default_rng(17)
        x = jnp.asarray(
            (rng.normal(size=(n, d)) + rng.integers(0, k, n)[:, None]).astype(
                np.float32
            )
        )
        centers = jnp.asarray(rng.normal(size=(k, d)).astype(np.float32))
        xt, _ = pad_transposed(x, block_n=256)
        cpad = jnp.pad(centers, ((0, 0), (0, xt.shape[0] - d)))
        sums, counts, cost, c2 = assign_stats_packed(
            xt, cpad, block_n=256, precision=mode, interpret=True
        )
        # The finite _UNUSED_SCORE sentinel keeps every output finite
        # even when the hi/lo split runs over the unused lane-group
        # slots — an inf sentinel would manufacture NaN there.
        for arr in (sums, counts, cost, c2):
            assert np.all(np.isfinite(np.asarray(arr)))
        # Unpacked fused reference at the SAME mode: identical
        # assignments, accumulation-order epsilon on the sums.
        sf, cf, costf, c2f = assign_stats_fused(
            xt, cpad, block_n=256, precision=mode, interpret=True
        )
        np.testing.assert_array_equal(np.asarray(counts), np.asarray(cf))
        np.testing.assert_allclose(sums, sf, rtol=1e-5, atol=1e-4)
        assert float(cost) == pytest.approx(float(costf), rel=1e-5)
        np.testing.assert_allclose(c2, c2f, rtol=1e-6)

    def test_packed_bf16x3_tracks_f32_stats(self):
        """Cross-mode sanity at the same geometry: the compensated stats
        stay close to the f32 stats (assignment flips aside, the bound
        is the GEMM tolerance amortized over the accumulation)."""
        from spark_rapids_ml_tpu.ops.pallas.kmeans import (
            assign_stats_packed,
            pad_transposed,
        )

        n, d, k = 777, 16, 16
        rng = np.random.default_rng(18)
        centers = jnp.asarray((rng.normal(size=(k, d)) * 8).astype(np.float32))
        x = jnp.asarray(
            np.repeat(np.asarray(centers), n // k + 1, axis=0)[:n]
            + rng.normal(size=(n, d)).astype(np.float32) * 0.05
        )
        xt, _ = pad_transposed(x, block_n=256)
        cpad = jnp.pad(centers, ((0, 0), (0, xt.shape[0] - d)))
        ref = assign_stats_packed(xt, cpad, block_n=256, precision="f32",
                                  interpret=True)
        got = assign_stats_packed(xt, cpad, block_n=256, precision="bf16x3",
                                  interpret=True)
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(ref[1]))
        np.testing.assert_allclose(got[0], ref[0], rtol=1e-3, atol=1e-2)


# ---------------------------------------------------------------------------
# policy resolution layering
# ---------------------------------------------------------------------------


class TestResolvePolicy:
    def test_default_when_nothing_set(self, off):
        assert resolve_policy("kmeans") == "highest"
        assert resolve_policy("covariance", default="auto") == "auto"

    def test_explicit_beats_env(self, off, monkeypatch):
        monkeypatch.setenv("TPUML_PRECISION_KMEANS", "bf16")
        assert resolve_policy("kmeans", "f32") == "f32"

    def test_family_env_beats_global_env(self, off, monkeypatch):
        monkeypatch.setenv("TPUML_PRECISION", "bf16")
        monkeypatch.setenv("TPUML_PRECISION_KMEANS", "bf16x3")
        assert resolve_policy("kmeans") == "bf16x3"
        assert resolve_policy("logistic") == "bf16"

    def test_dd_passes_through_untouched(self, off, monkeypatch):
        monkeypatch.setenv("TPUML_PRECISION", "bf16")
        assert resolve_policy("linear", "dd") == "dd"

    def test_invalid_env_value_raises(self, off, monkeypatch):
        from spark_rapids_ml_tpu.utils.envknobs import EnvKnobError

        monkeypatch.setenv("TPUML_PRECISION", "fp8")
        with pytest.raises(EnvKnobError):
            resolve_policy("kmeans")

    def test_unknown_family_rejected(self, off):
        with pytest.raises(ValueError, match="family"):
            resolve_policy("umap")

    def test_resolution_feeds_roofline_registry(self, off, monkeypatch):
        monkeypatch.setenv("TPUML_PRECISION_KMEANS", "bf16x3")
        resolve_policy("kmeans")
        assert active_mode("kmeans") == "bf16x3"
        # Ledger program families carry dotted suffixes.
        assert active_mode("kmeans.lloyd") == "bf16x3"
        assert roofline_peak_scale("kmeans.lloyd") == 2.0
        assert roofline_peak_scale("never.resolved") == 1.0
        resolve_policy("serving", "bf16")
        assert roofline_peak_scale("serving") == 6.0
        assert active_modes()["serving"] == "bf16"
        # Forward-pass ledger families run under the SERVING policy,
        # not the fit family their prefix suggests.
        assert active_mode("kmeans.predict") == "bf16"
        assert active_mode("pca.transform") == "bf16"
        assert roofline_peak_scale("kmeans.predict") == 6.0

    def test_families_registry_is_closed(self):
        assert set(FAMILIES) == {
            "covariance", "pca", "kmeans", "logistic", "linear", "serving"
        }


# ---------------------------------------------------------------------------
# off mode: bit identity, zero compiles, zero allocation
# ---------------------------------------------------------------------------


class TestOffBitIdentity:
    def test_kmeans_default_fit_is_f32_fit(self, off, rng):
        from spark_rapids_ml_tpu.clustering import KMeans

        x = (rng.normal(size=(240, 5)) + rng.integers(0, 3, 240)[:, None]).astype(
            np.float32
        )
        m_default = KMeans().setK(3).setSeed(7).fit(x)
        m_f32 = KMeans().setK(3).setSeed(7).setPrecision("f32").fit(x)
        np.testing.assert_array_equal(
            m_default.clusterCenters(), m_f32.clusterCenters()
        )
        assert float(m_default.trainingCost) == float(m_f32.trainingCost)

    def test_resolution_adds_zero_compiles_and_stays_allocation_light(
        self, off, rng, caplog
    ):
        a = jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32))
        b = jnp.asarray(rng.normal(size=(32, 16)).astype(np.float32))

        @jax.jit
        def kern(a, b):
            return make_dot(resolve_policy("serving"))(a, b)

        first = np.asarray(kern(a, b))  # compile outside the window
        jax.config.update("jax_log_compiles", True)
        try:
            with caplog.at_level(logging.WARNING, logger="jax._src.dispatch"):
                second = np.asarray(kern(a, b))
        finally:
            jax.config.update("jax_log_compiles", False)
        assert [
            r for r in caplog.records if "XLA compilation" in r.getMessage()
        ] == []
        np.testing.assert_array_equal(first, second)
        assert counter_value("autotune.commit") == 0
        # Off-mode resolution itself is allocation-light: no tuner, no
        # probes, no store IO.
        n = 200
        tracemalloc.start()
        base, _ = tracemalloc.get_traced_memory()
        for _ in range(n):
            resolve_policy("serving")
        _, peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        assert peak - base < n * 4096


# ---------------------------------------------------------------------------
# the autotuner gate
# ---------------------------------------------------------------------------


class TestAutotunerGate:
    def test_off_tuner_never_probes(self, off):
        assert tune_precision("kmeans") is None

    def test_cpu_probe_keeps_f32_and_memoizes(self, tuner, slower_candidates, monkeypatch):
        """Where the compensated mode probes SLOWER than native f32 (a
        CPU: three real f32 GEMMs for one), the gate must keep the f32
        incumbent — this is the mechanism that makes default-mode CI
        runs bit-identical. The decision memoizes: the second resolution
        never re-probes."""
        mode = tune_precision("kmeans", tuner=tuner)
        assert mode == "f32"
        decision = tuner.store.get("precision_mode", "kmeans")
        assert decision["value"] == "f32"

        def boom(*a, **k):  # pragma: no cover - must not run
            raise AssertionError("re-probed a memoized decision")

        monkeypatch.setattr(prec, "_time_probe", boom)
        assert tune_precision("kmeans", tuner=tuner) == "f32"

    def test_slower_candidates_keep_f32_in_every_family(self, tuner, slower_candidates):
        """Every family through the gate against one live store (what
        the retired precision sweep asserted with the tuner armed): each
        keeps the f32 incumbent and records its candidates rejected as
        regressions, `serving` both of its two."""
        decisions = {fam: tune_precision(fam, tuner=tuner) for fam in FAMILIES}
        assert decisions == dict.fromkeys(FAMILIES, "f32")
        for fam in FAMILIES:
            rejected = tuner.store.get("precision_mode", fam)["rejected"]
            assert [r["reason"] for r in rejected] == ["regression"] * len(rejected)
            want = ["bf16x3", "bf16"] if fam == "serving" else ["bf16x3"]
            assert [r["value"] for r in rejected] == want

    def test_gate_rejects_seeded_parity_violating_mode(self, tuner):
        """A fast-but-wrong GEMM (plain bf16 math sold with a 1e-7
        parity bar) must be recorded rejected with reason ``parity`` and
        never displace the incumbent."""
        register_test_mode(
            "seeded_wrong_17",
            lambda a, b: jnp.matmul(
                a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                preferred_element_type=jnp.float32,
            ),
            rel_tol=1e-7,
        )
        before = counter_value("autotune.revert")
        mode = tune_precision(
            "covariance", tuner=tuner, candidates=("seeded_wrong_17",)
        )
        assert mode == "f32"  # incumbent stands
        decision = tuner.store.get("precision_mode", "covariance")
        assert decision["value"] == "f32"
        rejected = decision.get("rejected", [])
        assert any(
            r["value"] == "seeded_wrong_17" and r["reason"] == "parity"
            for r in rejected
        )
        assert counter_value("autotune.revert") > before

    def test_record_trial_ok_false_contract(self, tuner):
        """ok=False records the rejection (reason preserved), bumps the
        revert counter, and returns False — even with an empty store."""
        before = counter_value("autotune.revert")
        committed = tuner.record_trial(
            "precision_mode", "unit", "bf16", 1e-9, ok=False, reason="parity"
        )
        assert committed is False
        entry = tuner.store.get("precision_mode", "unit")
        assert entry["value"] is None  # placeholder, nothing committed
        assert entry["rejected"][0]["reason"] == "parity"
        assert counter_value("autotune.revert") == before + 1

    def test_resolve_policy_consults_committed_decision(self, tuner, slower_candidates):
        """With the tuner armed and no explicit/env setting, resolution
        goes through the gate and lands on the committed mode."""
        assert resolve_policy("logistic") == "f32"
        assert tuner.store.get("precision_mode", "logistic")["value"] == "f32"


# ---------------------------------------------------------------------------
# segmented / resumable bit identity under a fixed policy
# ---------------------------------------------------------------------------


class TestSegmentedBitIdentity:
    def test_lloyd_resumable_matches_monolithic_under_bf16x3(self, off, tmp_path, rng):
        from spark_rapids_ml_tpu.ops.kmeans import lloyd, lloyd_resumable, random_init
        from spark_rapids_ml_tpu.robustness.checkpoint import FitCheckpointer

        x = jnp.asarray(
            (rng.normal(size=(300, 6)) + rng.integers(0, 4, 300)[:, None]).astype(
                np.float32
            )
        )
        mask = jnp.ones(300, dtype=jnp.float32)
        init = random_init(x, mask, jax.random.PRNGKey(0), 4)
        c_ref, cost_ref, it_ref = lloyd(
            x, mask, init, max_iter=8, precision="bf16x3"
        )
        ck = FitCheckpointer(
            str(tmp_path / "run"), uid="u", param_hash="p", data_fp="d", every=2
        )
        c_seg, cost_seg, it_seg = lloyd_resumable(
            x, mask, init, ck, max_iter=8, precision="bf16x3"
        )
        np.testing.assert_array_equal(np.asarray(c_seg), np.asarray(c_ref))
        assert float(cost_seg) == float(cost_ref)
        assert int(it_seg) == int(it_ref)

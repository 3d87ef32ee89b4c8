"""Unit tests for the ops layer — the XLA replacements for the reference's
JNI kernels (rapidsml_jni.cu), each checked against a numpy oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_ml_tpu.ops import (
    cal_svd,
    covariance,
    eigh_descending,
    gemm_project,
    gemm_syrk,
    mean_and_covariance,
    sign_flip,
    spr,
    triu_to_full,
)
from spark_rapids_ml_tpu.ops.covariance import (
    centered_gram,
    comoment_resident,
    centered_gram_packed,
    comoment_add_block,
    comoment_init,
    comoment_merge,
    welford_add_block,
    welford_init,
    welford_merge,
)


class TestGemm:
    def test_syrk(self, rng):
        b = rng.normal(size=(50, 8))
        np.testing.assert_allclose(gemm_syrk(b), b.T @ b, atol=1e-10)

    def test_project(self, rng):
        a = rng.normal(size=(8, 50))
        b = rng.normal(size=(8, 3))
        np.testing.assert_allclose(gemm_project(a, b), a.T @ b, atol=1e-10)


class TestPacked:
    def test_spr_matches_blas_layout(self, rng):
        """Packed upper, column-major — cublasDspr/Spark BLAS.spr layout."""
        n = 5
        x = rng.normal(size=(n,))
        packed = np.zeros(n * (n + 1) // 2)
        result = np.asarray(spr(x, packed))
        outer = np.outer(x, x)
        expected = np.concatenate([outer[: j + 1, j] for j in range(n)])
        np.testing.assert_allclose(result, expected, atol=1e-12)

    def test_triu_to_full_roundtrip(self, rng):
        a = rng.normal(size=(6, 6))
        sym = a + a.T
        packed = np.concatenate([sym[: j + 1, j] for j in range(6)])
        np.testing.assert_allclose(triu_to_full(packed), sym, atol=1e-12)

    def test_triu_to_full_rejects_bad_length(self):
        with pytest.raises(ValueError):
            triu_to_full(np.zeros(7))


def comoment_of(blocks, dtype=jnp.float64, **kwargs):
    state = comoment_init(blocks[0].shape[1], dtype=dtype)
    for blk in blocks:
        state = comoment_add_block(state, jnp.asarray(blk, dtype=dtype), **kwargs)
    return state


def rel_gap(got, want) -> float:
    """Widest entry gap as a share of the oracle's largest entry."""
    want = np.asarray(want, dtype=np.float64)
    return float(np.abs(np.asarray(got, dtype=np.float64) - want).max() / np.abs(want).max())


class TestComoment:
    """The matrix-moment ``welford_*``: (count, mean, mean_lo, M) against a
    float64 numpy oracle, ``M`` the Gram centred on the rows' means."""

    def test_unequal_blocks_match_the_two_pass_oracle(self, rng):
        x = rng.normal(size=(500, 8)) * 3 + 7
        count, mean, lo, m = comoment_of(np.array_split(x, [3, 50, 51, 333]))
        assert int(count) == 500
        np.testing.assert_allclose(mean + lo, x.mean(axis=0), atol=1e-12)
        np.testing.assert_allclose(m / (500 - 1), np.cov(x, rowvar=False), atol=1e-11)

    def test_an_empty_block_returns_the_state_unchanged(self, rng):
        x = rng.normal(size=(60, 5))
        with_empty = comoment_of([x[:20], x[:0], x[20:]])
        without = comoment_of([x[:20], x[20:]])
        for got, want in zip(with_empty, without):
            assert np.array_equal(np.asarray(got), np.asarray(want))
        first = comoment_add_block(comoment_init(5), jnp.asarray(x[:0]))
        assert int(first[0]) == 0 and not np.asarray(first[3]).any()

    def test_a_one_row_block_has_no_gram_of_its_own(self, rng):
        x = rng.normal(size=(41, 6)) + 100.0
        one = comoment_of([x[:1]])
        assert int(one[0]) == 1 and not np.asarray(one[3]).any()
        np.testing.assert_allclose(one[1] + one[2], x[0], atol=1e-12)
        rows = comoment_of([x[i : i + 1] for i in range(41)])  # Welford's own form
        np.testing.assert_allclose(rows[3] / 40, np.cov(x, rowvar=False), atol=1e-11)

    def test_merge_is_associative_and_agrees_with_add_block(self, rng):
        x = rng.normal(size=(120, 4)) * 2 - 5
        a, b, c = (comoment_of([blk]) for blk in (x[:30], x[30:31], x[31:]))
        left = comoment_merge(comoment_merge(a, b), c)
        right = comoment_merge(a, comoment_merge(b, c))
        scan = comoment_of([x[:30], x[30:31], x[31:]])
        for state in (left, right, scan):
            assert int(state[0]) == 120
            np.testing.assert_allclose(state[1] + state[2], x.mean(axis=0), atol=1e-12)
            np.testing.assert_allclose(state[3] / 119, np.cov(x, rowvar=False), atol=1e-11)
        empty = comoment_init(4)
        for got, want in zip(comoment_merge(empty, a), a):
            np.testing.assert_allclose(got, want, atol=0)
        assert int(comoment_merge(empty, empty)[0]) == 0

    def test_the_pallas_kernel_takes_the_block_mean_the_same_way(self, rng):
        x = rng.normal(size=(300, 16)) + 3
        blocks = np.array_split(x, [100, 230])
        xla = comoment_of(blocks)
        pallas = comoment_of(blocks, backend="pallas", interpret=True)
        np.testing.assert_allclose(pallas[3], xla[3], rtol=1e-12, atol=1e-10)

    # What the route that made two passes (means by ``welford_*``, then
    # ``centered_gram`` on the finished means) reads against the float64
    # covariance of SHUFFLED rows whose columns lie 1e3 off zero: under
    # 1e-14 in float64, 2e-7 to 5e-7 in float32 (one pass with the means
    # in ONE piece reads 2e-6 to 2e-5 there). One pass is held to it
    # on sorted partitions, where a merge has the most to add: block means
    # ten spreads apart (the between-block term carries the covariance) and
    # half a spread apart (the means' rounding is as large as it gets
    # against what it is multiplied with).
    PRESENT_ROUTE_TOL = {"float64": 1e-12, "float32": 1e-6}

    @staticmethod
    def partitions(rng, order: str, parts: int = 40, rows: int = 500, cols: int = 24):
        x = rng.standard_normal((parts * rows, cols)) + 1e3 * rng.standard_normal(cols)
        if order != "shuffled":
            step = {"sorted_far": 10.0, "sorted_near": 0.5}[order]
            x += np.repeat(np.arange(parts) * step, rows)[:, None] * rng.standard_normal(cols)
        x = x.astype(np.float32)  # the same values at either compute dtype
        return np.split(x, parts), np.cov(x.astype(np.float64), rowvar=False)

    @pytest.mark.parametrize("order", ["shuffled", "sorted_far", "sorted_near"])
    @pytest.mark.parametrize("dtype", ["float64", "float32"])
    def test_sorted_partitions_read_as_the_two_pass_route_does(self, rng, dtype, order):
        blocks, want = self.partitions(rng, order)
        n = sum(blk.shape[0] for blk in blocks)
        with jax.enable_x64(dtype == "float64"):
            state = comoment_of(blocks, dtype=jnp.dtype(dtype))
            assert state[3].dtype == jnp.dtype(dtype)
            one_pass = np.asarray(state[3], dtype=np.float64) / (n - 1)
            mean = np.asarray(state[1], np.float64) + np.asarray(state[2], np.float64)
            placed = [jnp.asarray(blk, dtype=jnp.dtype(dtype)) for blk in blocks]
            means = welford_init(blocks[0].shape[1], dtype=jnp.dtype(dtype))
            for blk in placed:
                means = welford_add_block(means, blk)
            two_pass = sum(
                np.asarray(centered_gram(blk, means[1]), dtype=np.float64) for blk in placed
            ) / (n - 1)
        tol = self.PRESENT_ROUTE_TOL[dtype]
        assert rel_gap(two_pass, want) < tol  # the tolerance IS the old route's
        assert rel_gap(one_pass, want) < tol
        exact = np.concatenate(blocks).astype(np.float64).mean(axis=0)
        # carried in two pieces the means are good to a rounding of the
        # SPREAD, not of the 1e3 they lie off zero
        assert np.abs(mean - exact).max() < (1e-10 if dtype == "float64" else 1e-5)


class TestEigh:
    def test_sign_flip(self):
        u = np.array([[0.9, -0.2], [-0.1, -0.8]])
        flipped = np.asarray(sign_flip(u))
        # col 0: max-|.| elem is 0.9 (positive) -> unchanged
        np.testing.assert_allclose(flipped[:, 0], u[:, 0])
        # col 1: max-|.| elem is -0.8 (negative) -> negated
        np.testing.assert_allclose(flipped[:, 1], -u[:, 1])

    def test_sign_flip_idempotent(self, rng):
        u = rng.normal(size=(10, 10))
        once = np.asarray(sign_flip(u))
        twice = np.asarray(sign_flip(once))
        np.testing.assert_allclose(once, twice)

    def test_eigh_descending(self, rng):
        a = rng.normal(size=(12, 12))
        sym = a @ a.T
        w, v = eigh_descending(sym)
        w, v = np.asarray(w), np.asarray(v)
        assert np.all(np.diff(w) <= 1e-9)  # descending
        np.testing.assert_allclose(sym @ v, v * w, atol=1e-8)

    def test_cal_svd_psd(self, rng):
        """Full calSVD contract: U orthonormal, s = sqrt(eigvals) descending."""
        a = rng.normal(size=(15, 15))
        cov = a @ a.T / 15
        u, s = cal_svd(cov)
        u, s = np.asarray(u), np.asarray(s)
        expected_s = np.sqrt(np.sort(np.linalg.eigvalsh(cov))[::-1])
        np.testing.assert_allclose(s, expected_s, atol=1e-8)
        np.testing.assert_allclose(u.T @ u, np.eye(15), atol=1e-8)

    def test_cal_svd_clamps_negative_eigs(self):
        """Near-singular PSD input must not produce NaN singular values."""
        cov = np.outer([1.0, 1.0], [1.0, 1.0])  # rank-1, eigvals {2, 0±eps}
        _, s = cal_svd(cov)
        assert not np.any(np.isnan(np.asarray(s)))


class TestCovariance:
    def test_mean_and_covariance(self, rng):
        x = rng.normal(size=(100, 10))
        mean, cov = mean_and_covariance(x)
        np.testing.assert_allclose(mean, x.mean(axis=0), atol=1e-10)
        np.testing.assert_allclose(cov, np.cov(x, rowvar=False), atol=1e-10)

    def test_covariance_normalization_is_n_minus_1(self, rng):
        """Both paths normalize by (n-1) — the reference GEMM path's
        1/sqrt(numCols-1) mis-scaling (RapidsRowMatrix.scala:169) is fixed."""
        x = rng.normal(size=(40, 6))
        np.testing.assert_allclose(covariance(x), np.cov(x, rowvar=False), atol=1e-10)

    @pytest.mark.parametrize("with_label", [False, True], ids=["rows", "rows+label"])
    @pytest.mark.parametrize(
        "n", [20_000, 23_457, 640], ids=["two-blocks", "remainder", "one-short-block"]
    )
    def test_resident_blocks_match_float64_two_pass(self, rng, n, with_label):
        """The blocked co-moment sum over resident rows (blocks of 10,000,
        the short last block a static remainder) against a float64 two-pass
        covariance, on float32 columns a thousand spreads off zero (the
        case ``comoment_init``'s docstring names), with and without the
        label riding along as one more column."""
        d = 5
        x = (rng.normal(size=(n, d)) * np.arange(1, d + 1) + 1000.0).astype(np.float32)
        y = (x @ rng.normal(size=d) + rng.normal(size=n)).astype(np.float32)
        cols = np.column_stack([x, y]) if with_label else x
        count, mean, mean_lo, m = comoment_resident(
            jnp.asarray(x), jnp.asarray(y) if with_label else None
        )
        exact = cols.astype(np.float64)
        centred = exact - exact.mean(axis=0)
        want = centred.T @ centred
        scale = np.sqrt(np.outer(np.diag(want), np.diag(want)))
        assert float(count) == n
        assert np.asarray(m).dtype == np.float32
        assert np.max(np.abs(np.asarray(m, np.float64) - want) / scale) < 1e-6
        got_mean = np.asarray(mean, np.float64) + np.asarray(mean_lo, np.float64)
        np.testing.assert_allclose(got_mean, exact.mean(axis=0), rtol=1e-7)

    def test_resident_blocks_weighted_and_uncentred(self, rng):
        """Per-row weights (``weightCol``; nought for padding) enter the
        step as the block's weights; ``center=False`` sums the raw second
        moment in the same blocks."""
        n, d = 12_345, 4
        x = rng.normal(size=(n, d)) + 3.0
        w = np.where(np.arange(n) < n - 345, rng.uniform(0.1, 2.0, size=n), 0.0)
        count, mean, mean_lo, m = comoment_resident(jnp.asarray(x), weights=jnp.asarray(w))
        mu = (w[:, None] * x).sum(axis=0) / w.sum()
        np.testing.assert_allclose(float(count), w.sum(), rtol=1e-12)
        np.testing.assert_allclose(np.asarray(mean + mean_lo), mu, rtol=1e-12)
        np.testing.assert_allclose(
            np.asarray(m), ((x - mu) * w[:, None]).T @ (x - mu), rtol=1e-10
        )
        raw = comoment_resident(jnp.asarray(x), center=False)
        np.testing.assert_allclose(np.asarray(raw[3]), x.T @ x, rtol=1e-12)
        assert not np.any(np.asarray(raw[1]))

    def test_packed_matches_dense(self, rng):
        x = rng.normal(size=(30, 5))
        mean = x.mean(axis=0)
        full = np.asarray(centered_gram(x, mean))
        packed = np.asarray(centered_gram_packed(x, mean))
        expected = np.concatenate([full[: j + 1, j] for j in range(5)])
        np.testing.assert_allclose(packed, expected, atol=1e-10)

    def test_welford_streaming_mean(self, rng):
        x = rng.normal(size=(500, 8)) * 3 + 7
        state = welford_init(8)
        for blk in np.array_split(x, 7):
            state = welford_add_block(state, blk)
        count, mean, m2 = state
        assert int(count) == 500
        np.testing.assert_allclose(mean, x.mean(axis=0), atol=1e-10)
        np.testing.assert_allclose(m2 / (500 - 1), x.var(axis=0, ddof=1), atol=1e-9)

    def test_welford_merge_associative(self, rng):
        x = rng.normal(size=(100, 4))
        a = welford_add_block(welford_init(4), x[:30])
        b = welford_add_block(welford_init(4), x[30:])
        merged = welford_merge(a, b)
        np.testing.assert_allclose(merged[1], x.mean(axis=0), atol=1e-10)

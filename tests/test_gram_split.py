"""The blocked Gram's four-pass form (PR 37): ``ops/covariance.py``'s
``centered_gram`` takes ``b^T b`` of float32 rows under the full-precision
policy as four single-pass bfloat16 products on an exact split, and every
other case as the plain product it was, bit for bit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_ml_tpu.ops import precision
from spark_rapids_ml_tpu.ops.covariance import (
    GRAM_BLOCK_ROWS,
    centered_gram,
    comoment_resident,
    comoment_sharded,
)
from spark_rapids_ml_tpu.utils.tracing import clear_counters, counters


def _rows(rng, n, d, off=1000.0):
    """float32 columns whose means lie ``off`` spreads from zero, each of
    its own spread."""
    spread = rng.uniform(0.5, 4.0, size=d)
    return (rng.normal(size=(n, d)) * spread + off * rng.normal(size=d)).astype(np.float32)


def _fresh_counters():
    # the Gram is a jit of its own: a trace cached by an earlier test bumps nothing
    jax.clear_caches()
    clear_counters("gram.")


@pytest.mark.parametrize("with_label", [False, True], ids=["rows", "rows+label"])
@pytest.mark.parametrize("n", [23_457, 640], ids=["remainder", "one-short-block"])
def test_resident_four_pass_gram_matches_float64(rng, n, with_label):
    """Through ``comoment_resident`` (blocks of 10,000, a short last block)
    at a width that is no multiple of 128, against a float64 two-pass Gram,
    within the tolerance the suite holds ``HIGHEST`` to there
    (``test_ops.py::test_resident_blocks_match_float64_two_pass``)."""
    d = 130
    x = _rows(rng, n, d)
    y = (x[:, :3].sum(axis=1) + rng.normal(size=n)).astype(np.float32)
    _fresh_counters()
    _, mean, mean_lo, m = comoment_resident(
        jnp.asarray(x), jnp.asarray(y) if with_label else None
    )
    assert counters("gram.") == {"gram.split4": 1 if n < 10_000 else 2}
    exact = (np.column_stack([x, y]) if with_label else x).astype(np.float64)
    centred = exact - exact.mean(axis=0)
    want = centred.T @ centred
    scale = np.sqrt(np.outer(np.diag(want), np.diag(want)))
    got = np.asarray(m, np.float64)
    assert np.asarray(m).dtype == np.float32
    assert np.max(np.abs(got - want) / scale) < 1e-6
    np.testing.assert_allclose(
        np.asarray(mean, np.float64) + np.asarray(mean_lo, np.float64),
        exact.mean(axis=0),
        rtol=1e-7,
    )


@pytest.mark.parametrize("d", [130, 256])
def test_four_pass_gram_is_symmetric_and_as_close_as_highest(rng, d):
    """One block: the four-pass result is symmetric to a rounding (``S +
    S^T`` exactly; ``h^T h`` and ``m^T m`` as the backend's GEMM sums them,
    exactly on the chip: PERF.md section 6, PR 37), and its gap to the
    float64 Gram of the same float32 ``b`` is of ``HIGHEST``'s order."""
    x = _rows(rng, 4_000, d)
    mean = jnp.asarray(x.mean(axis=0, dtype=np.float64).astype(np.float32))
    got = np.asarray(centered_gram(jnp.asarray(x), mean), np.float64)
    b = np.asarray(jnp.asarray(x) - mean, np.float64)
    want = b.T @ b
    plain = np.asarray(precision.make_dot("highest")((b.T).astype(np.float32), b.astype(np.float32)))
    scale = np.abs(want).max()
    assert np.abs(got - got.T).max() <= 4 * np.finfo(np.float32).eps * np.abs(got).max()
    assert np.abs(got - want).max() / scale < max(4 * np.abs(plain - want).max() / scale, 1e-6)


@pytest.mark.parametrize(
    "mode", ["high", "bf16x3", "bf16", "default", "float64", "test_mode"]
)
def test_every_other_case_is_the_plain_product_bit_for_bit(rng, mode):
    """Where the four-pass form does not engage, ``centered_gram`` is
    ``make_dot(precision)(b.T, b)`` to the bit, and counts ``gram.matmul``."""
    x = _rows(rng, 2_000, 70, off=3.0)
    dtype = jnp.float64 if mode == "float64" else jnp.float32
    spelled = "highest" if mode == "float64" else mode
    if mode == "test_mode":
        precision.register_test_mode("test_mode", lambda a, b: jnp.matmul(a, b) * 1.0)
    try:
        xs = jnp.asarray(x, dtype=dtype)
        mean = jnp.mean(xs, axis=0)
        _fresh_counters()
        got = centered_gram(xs, mean, precision=spelled)
        assert counters("gram.") == {"gram.matmul": 1}

        @jax.jit
        def plain(x, m):
            b = x - m
            return precision.make_dot(spelled)(b.T, b)

        want = plain(xs, mean)
    finally:
        precision.clear_test_modes()
    assert got.dtype == want.dtype == dtype
    assert np.array_equal(np.asarray(got), np.asarray(want))


def _mesh4():
    from jax.sharding import Mesh

    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS

    return Mesh(np.array(jax.devices()[:4]), (DATA_AXIS,))


def _on_mesh(x, mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS

    return jax.device_put(x, NamedSharding(mesh, P(DATA_AXIS, None)))


def _float64_moments(x):
    x = np.asarray(x, dtype=np.float64)
    mean = x.mean(axis=0)
    c = x - mean
    return len(x), mean, c.T @ c


@pytest.mark.parametrize("center", [True, False])
def test_rows_sharded_over_a_mesh_sum_in_blocks_a_chip(rng, center):
    """``comoment_sharded`` on 4 devices, 2.5 blocks a chip, float32 columns
    far from zero: the merged state against a float64 sum of all the rows,
    and as close to it as one device's blocked sum."""
    n = 4 * (2 * GRAM_BLOCK_ROWS + GRAM_BLOCK_ROWS // 2)
    x32 = _rows(rng, n, 6, off=50.0)
    mesh = _mesh4()
    count, mean, lo, m = comoment_sharded(_on_mesh(jnp.asarray(x32), mesh), mesh, center=center)
    want_n, want_mean, want_m = _float64_moments(x32)
    if not center:
        want_m = x32.astype(np.float64).T @ x32.astype(np.float64)
        want_mean = np.zeros_like(want_mean)
    scale = np.sqrt(np.outer(np.diag(want_m), np.diag(want_m)))
    assert float(count) == want_n
    np.testing.assert_allclose(np.asarray(mean) + np.asarray(lo), want_mean, rtol=1e-7, atol=1e-7)
    err = np.max(np.abs(np.asarray(m, dtype=np.float64) - want_m) / scale)
    one = comoment_resident(jnp.asarray(x32), center=center)[3]
    one_err = np.max(np.abs(np.asarray(one, dtype=np.float64) - want_m) / scale)
    assert err < 1e-6 and err < 4 * one_err + 1e-9, (err, one_err)


def test_the_mesh_pca_fit_reads_float64(rng):
    """``PCA(mesh=...)`` on device rows sharded over 4 devices: the fused fit
    on the per-chip blocked co-moments against float64, and a model fitted
    on one chip's share of the rows (the four-chip cell's ``one_chip_rows``
    fault) is told apart by its explained variance."""
    from spark_rapids_ml_tpu.feature import PCA

    n, d = 4 * 12_000, 10
    spread = np.linspace(3.0, 0.5, d)
    x32 = (rng.normal(size=(n, d)) * spread + 40.0).astype(np.float32)
    mesh = _mesh4()
    model = PCA(mesh=mesh).setK(3).fit(_on_mesh(jnp.asarray(x32), mesh))
    _, _, m = _float64_moments(x32)
    w = np.linalg.eigvalsh(m)[::-1]
    want = w[:3] / w.sum()
    got = np.asarray(model.explainedVariance)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    share = PCA().setK(3).fit(jnp.asarray(x32[: n // 4]))
    assert np.max(np.abs(np.asarray(share.explainedVariance) - want) / want) > 1e-4


@pytest.mark.parametrize(
    "setting,seen",
    [(None, "gram.split4"), ("f32", "gram.split4"), ("high", "gram.matmul")],
)
def test_the_public_device_fit_counts_the_form_it_traced(rng, setting, seen):
    """``PCA().fit`` on resident float32 rows (the fused one-program fit):
    the default policy and ``f32`` trace the four-pass Gram, ``high`` the
    plain product; ``gram.split4`` / ``gram.matmul`` say which."""
    from spark_rapids_ml_tpu.feature import PCA

    x = jnp.asarray(_rows(rng, 3_000, 24, off=5.0))
    est = PCA().setK(3)
    if setting is not None:
        est = est.setPrecision(setting)
    _fresh_counters()
    est.fit(x)
    got = counters("gram.")
    assert got.get(seen, 0) >= 1
    assert set(got) <= {seen, "gram.blocks", "gram.rows"}

"""Fused pallas Lloyd kernel: assignment + update stats
with zero (n, k) HBM temporaries, exact parity with the masked XLA
formulation (padding corrected in closed form). On CPU these run the
pallas interpreter; ``chip_smoke.py`` runs the compiled kernels on the chip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_ml_tpu.clustering import KMeans
from spark_rapids_ml_tpu.ops.kmeans import lloyd, random_init
from spark_rapids_ml_tpu.ops.pallas.kmeans import (
    assign_stats_fused,
    auto_block_n,
    lloyd_fused,
    pad_transposed,
)


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(3)
    n, d, k = 1100, 16, 6
    x = (rng.normal(size=(n, d)) + rng.integers(0, k, n)[:, None] * 4).astype(
        np.float32
    )
    return x, k


class TestFusedOps:
    @pytest.mark.parametrize("precision", ["highest", "high", "default"])
    def test_lloyd_parity(self, data, precision):
        x, k = data
        xj = jnp.asarray(x)
        mask = jnp.ones(x.shape[0], jnp.float32)
        init = random_init(xj, mask, jax.random.key(1), k)
        xt, n_true = pad_transposed(xj, block_n=256)
        cf, costf, itf = lloyd_fused(
            xt, n_true, init, max_iter=8, tol=0.0, block_n=256,
            precision=precision, interpret=True,
        )
        cr, costr, itr = lloyd(xj, mask, init, max_iter=8, tol=0.0)
        assert np.abs(np.asarray(cf)[:, : x.shape[1]] - np.asarray(cr)).max() < 1e-4
        assert float(costf) == pytest.approx(float(costr), rel=1e-4)

    def test_odd_width_and_ragged_rows(self):
        rng = np.random.default_rng(5)
        n, d, k = 530, 13, 5  # d not a sublane multiple, n not a block multiple
        x = rng.normal(size=(n, d)).astype(np.float32)
        xj = jnp.asarray(x)
        mask = jnp.ones(n, jnp.float32)
        init = random_init(xj, mask, jax.random.key(2), k)
        xt, n_true = pad_transposed(xj, block_n=128)
        cf, costf, _ = lloyd_fused(
            xt, n_true, init, max_iter=6, tol=0.0, block_n=128, interpret=True
        )
        cr, costr, _ = lloyd(xj, mask, init, max_iter=6, tol=0.0)
        assert np.abs(np.asarray(cf)[:, :d] - np.asarray(cr)).max() < 1e-4
        assert float(costf) == pytest.approx(float(costr), rel=1e-5)

    def test_stats_padding_correction_exact(self, data):
        """Raw kernel stats include the zero-pad rows; the closed-form
        correction must remove exactly their count and cost."""
        x, k = data
        xj = jnp.asarray(x)
        init = random_init(xj, jnp.ones(x.shape[0], jnp.float32), jax.random.key(1), k)
        xt, n_true = pad_transposed(xj, block_n=256)
        s, c, cost, _ = assign_stats_fused(xt, init, block_n=256, interpret=True)
        pad_rows = xt.shape[1] - n_true
        assert float(jnp.sum(c)) == pytest.approx(n_true + pad_rows)

    def test_assign_clusters_blocked_parity(self):
        # The row-blocked assignment (used by the IVF coarse quantizer at
        # shapes whose full (n, k) distance matrix would blow HBM) must
        # match the unblocked op exactly, ragged final block included.
        from spark_rapids_ml_tpu.ops.kmeans import (
            assign_clusters,
            assign_clusters_blocked,
        )

        rng = np.random.default_rng(9)
        x = jnp.asarray(rng.normal(size=(1001, 12)).astype(np.float32))
        c = jnp.asarray(rng.normal(size=(17, 12)).astype(np.float32))
        l_b, d_b = assign_clusters_blocked(x, c, block_rows=128)
        l_u, d_u = assign_clusters(x, c)
        assert np.array_equal(np.asarray(l_b), np.asarray(l_u))
        assert np.allclose(np.asarray(d_b), np.asarray(d_u), atol=1e-6)

    def test_auto_block_n_respects_vmem(self):
        bn_small = auto_block_n(16, 100)
        assert 4096 <= bn_small <= 8192 and bn_small % 128 == 0
        bn = auto_block_n(1024, 100)
        assert 128 <= bn < 8192 and bn % 128 == 0
        # Very wide d x large k: no feasible block — auto must decline.
        assert auto_block_n(16384, 100) is None

    def test_cosine_parity(self):
        rng = np.random.default_rng(7)
        from spark_rapids_ml_tpu.ops.kmeans import normalize_rows

        x = normalize_rows(jnp.asarray(rng.normal(size=(400, 16)).astype(np.float32)))
        mask = jnp.ones(400, jnp.float32)
        init = random_init(x, mask, jax.random.key(0), 4)
        xt, n_true = pad_transposed(x, block_n=128)
        cf, costf, _ = lloyd_fused(
            xt, n_true, init, max_iter=6, tol=0.0, block_n=128, cosine=True,
            interpret=True,
        )
        cr, costr, _ = lloyd(x, mask, init, max_iter=6, tol=0.0, cosine=True)
        assert np.abs(np.asarray(cf)[:, :16] - np.asarray(cr)).max() < 1e-4


class TestFusedEstimator:
    def test_explicit_fused_backend_matches_xla(self, data):
        x, k = data
        fused = (
            KMeans().setK(k).setSeed(3).setBackend("fused").setMaxIter(10).fit(x)
        )
        xla = KMeans().setK(k).setSeed(3).setBackend("xla").setMaxIter(10).fit(x)
        assert np.allclose(
            np.sort(fused.clusterCenters(), axis=0),
            np.sort(xla.clusterCenters(), axis=0),
            atol=1e-3,
        )
        assert fused.trainingCost == pytest.approx(xla.trainingCost, rel=1e-4)

    def test_fused_rejects_mesh_and_weights(self, data):
        from jax.sharding import Mesh
        from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS

        x, k = data
        mesh = Mesh(np.array(jax.devices()), (DATA_AXIS,))
        with pytest.raises(ValueError, match="mesh"):
            KMeans(mesh=mesh).setK(k).setBackend("fused").fit(x)

    def test_auto_stays_xla_off_tpu(self, data):
        x, k = data
        est = KMeans().setK(k)
        # On the CPU test platform auto must never pick the interpreter.
        assert est._resolve_backend(None, 10**9) == "xla"

    def test_precision_param_validates(self):
        # "bf16"/"bf16x3"/"f32" are valid policy modes (ops/precision.py);
        # only genuinely unknown names must raise.
        with pytest.raises(ValueError, match="precision"):
            KMeans().setPrecision("fp8")
        with pytest.raises(ValueError, match="backend"):
            KMeans().setBackend("cuda")


class TestPackedOps:
    """Lane-packed assignment kernel: P row groups share
    one 128-lane contraction at small d and k. Raw-stats parity with the
    unpacked fused kernel must hold at every packable geometry (its
    speed on a chip is not measured)."""

    @pytest.mark.parametrize(
        "n,d,k",
        [(1100, 8, 4), (1100, 16, 7), (777, 16, 16), (1100, 64, 33), (513, 64, 4)],
    )
    def test_assign_stats_parity(self, n, d, k):
        from spark_rapids_ml_tpu.ops.pallas.kmeans import (
            assign_stats_packed,
            packed_feasible,
        )

        assert packed_feasible(d, k)
        rng = np.random.default_rng(n + d + k)
        x = jnp.asarray(
            (rng.normal(size=(n, d)) + rng.integers(0, k, n)[:, None]).astype(
                np.float32
            )
        )
        centers = jnp.asarray(rng.normal(size=(k, d)).astype(np.float32))
        xt, n_true = pad_transposed(x, block_n=256)
        d_pad = xt.shape[0]
        cpad = jnp.pad(centers, ((0, 0), (0, d_pad - d)))
        sf, cf, costf, c2f = assign_stats_fused(
            xt, cpad, block_n=256, interpret=True
        )
        sp, cp, costp, c2p = assign_stats_packed(
            xt, cpad, block_n=256, interpret=True
        )
        # Identical assignments (counts are integers), accumulation-order
        # epsilon on the float sums.
        np.testing.assert_array_equal(np.asarray(cf), np.asarray(cp))
        np.testing.assert_allclose(sp, sf, rtol=1e-5, atol=1e-4)
        assert float(costp) == pytest.approx(float(costf), rel=1e-5)
        np.testing.assert_allclose(c2p, c2f, rtol=1e-6)

    def test_feasibility_boundaries(self):
        from spark_rapids_ml_tpu.ops.pallas.kmeans import packed_feasible

        assert packed_feasible(8, 16)
        assert packed_feasible(16, 16)
        assert packed_feasible(64, 64)
        assert not packed_feasible(128, 4)  # lane tile already well used
        assert not packed_feasible(16, 32)  # scores overflow the group slot
        assert not packed_feasible(64, 65)
        assert not packed_feasible(65, 4)  # d_pad 72 > 64

    def test_lloyd_packed_matches_unpacked(self, data):
        """End-to-end Lloyd on both kernels: same assignments each pass,
        centers agree to accumulation tolerance."""
        x, k = data
        xj = jnp.asarray(x)
        mask = jnp.ones(x.shape[0], jnp.float32)
        init = random_init(xj, mask, jax.random.key(2), k)
        xt, n_true = pad_transposed(xj, block_n=256)

        def run(packed):
            return lloyd_fused(
                xt, n_true, init, max_iter=5, tol=0.0, block_n=256,
                interpret=True, packed=packed,
            )

        cu, costu, itu = run(False)
        cp, costp, itp = run(True)
        assert int(itu) == int(itp)
        np.testing.assert_allclose(cp, cu, rtol=1e-4, atol=1e-4)
        assert float(costp) == pytest.approx(float(costu), rel=1e-5)

    def test_estimator_fused_backend_packs_small_d(self, data, monkeypatch):
        """The model layer routes packable shapes onto the packed kernel;
        the fit must match the XLA backend regardless."""
        import spark_rapids_ml_tpu.ops.pallas.kmeans as pk

        x, k = data  # d=16, k=6: packable
        calls = {"packed": 0}
        real = pk.assign_stats_packed

        def spy(*a, **kw):
            calls["packed"] += 1
            return real(*a, **kw)

        monkeypatch.setattr(pk, "assign_stats_packed", spy)

        def fit(backend):
            est = (
                KMeans()
                .setK(k)
                .setMaxIter(5)
                .setTol(0.0)
                .setInitMode("random")
                .setSeed(0)
                .setBackend(backend)
            )
            return est.fit(jnp.asarray(x))

        m_fused = fit("fused")
        assert calls["packed"] > 0  # the packed kernel actually ran
        m_xla = fit("xla")
        np.testing.assert_allclose(
            np.sort(m_fused.clusterCenters(), axis=0),
            np.sort(m_xla.clusterCenters(), axis=0),
            rtol=1e-4, atol=1e-4,
        )

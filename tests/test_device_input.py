"""Device-resident input path: a jax.Array fed to the
public estimator runs the whole fit as one XLA program with no host
round-trip, and the model converts to host float64 lazily. Also covers the
self-selecting eigensolver (ops.eigh.eigh_auto)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_ml_tpu.feature import PCA
from spark_rapids_ml_tpu.ops.eigh import eigh_auto, eigh_descending_host


def _oracle(xh, k):
    xc = xh.astype(np.float64) - xh.mean(0, dtype=np.float64)
    cov = xc.T @ xc / (xh.shape[0] - 1)
    w, v = np.linalg.eigh(cov)
    w, v = w[::-1], v[:, ::-1]
    return v[:, :k], (w / w.sum())[:k]


@pytest.fixture(scope="module")
def decaying():
    rng = np.random.default_rng(7)
    d = 48
    scales = np.exp(-np.arange(d) / 6.0)
    return (rng.standard_normal((1500, d)) * scales).astype(np.float32)


class TestDeviceInputFit:
    def test_matches_oracle_sign_invariant(self, decaying):
        x = jnp.asarray(decaying)
        model = PCA().setK(5).fit(x)
        pc_o, ev_o = _oracle(decaying, 5)
        assert np.abs(np.abs(model.pc) - np.abs(pc_o)).max() < 1e-4
        assert np.abs(model.explainedVariance - ev_o).max() < 1e-5

    def test_matches_host_partition_path(self, decaying):
        x = jnp.asarray(decaying)
        dev = PCA().setK(4).fit(x)
        host = PCA().setK(4).fit(decaying.astype(np.float64))
        assert np.abs(np.abs(dev.pc) - np.abs(host.pc)).max() < 1e-4

    def test_model_stays_on_device_until_read(self, decaying):
        model = PCA().setK(3).fit(jnp.asarray(decaying))
        assert isinstance(model._pc_raw, jax.Array)
        assert model._pc_np is None  # no host conversion yet
        pc = model.pc
        assert pc.dtype == np.float64 and pc.shape == (decaying.shape[1], 3)
        assert model.pc is pc  # cached, converted once

    def test_device_transform_returns_device_array(self, decaying):
        x = jnp.asarray(decaying)
        model = PCA().setK(3).fit(x)
        proj = model.transform(x)
        assert isinstance(proj, jax.Array)
        assert proj.shape == (decaying.shape[0], 3)
        # Matches the host projection contract X @ pc.
        expect = decaying.astype(np.float64) @ model.pc
        assert np.abs(np.asarray(proj, dtype=np.float64) - expect).max() < 1e-3

    def test_copy_preserves_lazy_state(self, decaying):
        model = PCA().setK(3).fit(jnp.asarray(decaying))
        dup = model.copy()
        assert np.allclose(dup.pc, model.pc)

    def test_randomized_solver_accepts_device_input(self, decaying):
        x = jnp.asarray(decaying)
        model = PCA().setK(3).setSolver("randomized").fit(x)
        pc_o, _ = _oracle(decaying, 3)
        assert np.abs(np.abs(model.pc) - np.abs(pc_o)).max() < 1e-3

    def test_randomized_solver_device_input_honors_mesh(self, decaying):
        # A device array + explicit mesh must reshard onto the
        # mesh (never silently compute single-device), matching the
        # covariance path's _device_array_on_mesh stance.
        from jax.sharding import Mesh
        from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS

        n_dev = len(jax.devices())
        mesh = Mesh(np.array(jax.devices()), (DATA_AXIS,))
        n = (decaying.shape[0] // n_dev) * n_dev
        xh = decaying[:n]
        model = (
            PCA(mesh=mesh).setK(3).setSolver("randomized").fit(jnp.asarray(xh))
        )
        pc_o, _ = _oracle(xh, 3)
        assert np.abs(np.abs(model.pc) - np.abs(pc_o)).max() < 1e-3

    def test_randomized_solver_device_input_mesh_indivisible_raises(
        self, decaying
    ):
        from jax.sharding import Mesh
        from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS

        n_dev = len(jax.devices())
        if n_dev < 2:
            pytest.skip("needs a multi-device mesh")
        mesh = Mesh(np.array(jax.devices()), (DATA_AXIS,))
        n = (decaying.shape[0] // n_dev) * n_dev + 1
        with pytest.raises(ValueError, match="divisible"):
            PCA(mesh=mesh).setK(2).setSolver("randomized").fit(
                jnp.asarray(decaying[:n])
            )

    def test_randomized_solver_host_partitions_on_1axis_mesh(self, decaying):
        # The error path above recommends "pass host partitions" — that
        # route must WORK on the same data-only mesh (it used to KeyError
        # on mesh.shape['model'] inside shard_rows_from_partitions).
        from jax.sharding import Mesh
        from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS

        mesh = Mesh(np.array(jax.devices()), (DATA_AXIS,))
        model = (
            PCA(mesh=mesh).setK(3).setSolver("randomized").fit(decaying)
        )
        pc_o, _ = _oracle(decaying, 3)
        assert np.abs(np.abs(model.pc) - np.abs(pc_o)).max() < 1e-3

    def test_device_fitted_model_pickles_host_state(self, decaying):
        # Pickling a device-fitted model (Spark broadcast,
        # cloudpickle closure) must ship host float64, not live device
        # buffers.
        cloudpickle = pytest.importorskip("cloudpickle")

        model = PCA().setK(3).fit(jnp.asarray(decaying))
        state = model.__getstate__()
        assert isinstance(state["_pc_raw"], np.ndarray)
        assert isinstance(state["_ev_raw"], np.ndarray)
        assert state["_pc_dev_cache"] == {}
        dup = cloudpickle.loads(cloudpickle.dumps(model))
        assert np.allclose(dup.pc, model.pc)
        assert np.allclose(dup.explainedVariance, model.explainedVariance)

    def test_dd_precision_rejected(self, decaying):
        with pytest.raises(ValueError, match="dd"):
            PCA().setK(3).setPrecision("dd").fit(jnp.asarray(decaying))

    def test_packed_path_rejected(self, decaying):
        with pytest.raises(ValueError, match="useGemm"):
            PCA().setK(3).setUseGemm(False).fit(jnp.asarray(decaying))

    def test_1d_array_rejected(self):
        with pytest.raises(ValueError, match="2-D"):
            PCA().setK(1).fit(jnp.ones((8,), dtype=jnp.float32))

    def test_zero_variance_input_yields_zero_ev_not_nan(self):
        model = PCA().setK(2).fit(jnp.ones((10, 4), dtype=jnp.float32))
        assert np.all(model.explainedVariance == 0)
        assert np.all(np.isfinite(model.pc))

    def test_mesh_device_input_runs_sharded_and_matches_oracle(self, decaying):
        from jax.sharding import Mesh
        from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS

        n_dev = len(jax.devices())
        mesh = Mesh(np.array(jax.devices()), (DATA_AXIS,))
        n = (decaying.shape[0] // n_dev) * n_dev
        xh = decaying[:n]
        model = PCA(mesh=mesh).setK(4).fit(jnp.asarray(xh))
        pc_o, ev_o = _oracle(xh, 4)
        assert np.abs(np.abs(model.pc) - np.abs(pc_o)).max() < 1e-4
        assert np.abs(model.explainedVariance - ev_o).max() < 1e-5

    def test_mesh_device_input_indivisible_rows_raises(self, decaying):
        from jax.sharding import Mesh
        from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS

        n_dev = len(jax.devices())
        if n_dev < 2:
            pytest.skip("needs a multi-device mesh")
        mesh = Mesh(np.array(jax.devices()), (DATA_AXIS,))
        n = (decaying.shape[0] // n_dev) * n_dev + 1
        with pytest.raises(ValueError, match="divisible"):
            PCA(mesh=mesh).setK(2).fit(jnp.asarray(decaying[:n]))

    def test_pallas_backend_device_input(self, decaying):
        model = PCA().setK(3).setCovarianceBackend("pallas").fit(
            jnp.asarray(decaying)
        )
        pc_o, _ = _oracle(decaying, 3)
        assert np.abs(np.abs(model.pc) - np.abs(pc_o)).max() < 1e-3

    def test_host_svd_optout_still_works(self, decaying):
        # useCuSolverSVD=False falls back to the generic path: device
        # covariance + host LAPACK SVD (the breeze branch).
        model = PCA().setK(3).setUseCuSolverSVD(False).fit(jnp.asarray(decaying))
        pc_o, _ = _oracle(decaying, 3)
        assert np.abs(np.abs(model.pc) - np.abs(pc_o)).max() < 1e-4


class TestEighAuto:
    def test_decaying_spectrum_accepted_not_promoted(self):
        d = 96
        w_true = 0.5 ** np.arange(d)
        rng = np.random.default_rng(3)
        q, _ = np.linalg.qr(rng.standard_normal((d, d)))
        a = (q * w_true) @ q.T
        w, v, promoted = eigh_auto(jnp.asarray(a, dtype=jnp.float32), 4)
        assert not bool(promoted)
        w_o, v_o = eigh_descending_host(a)
        assert np.abs(np.asarray(w) - w_o[:4]).max() < 1e-5
        assert np.abs(np.abs(np.asarray(v)) - np.abs(v_o[:, :4])).max() < 1e-3

    def test_slow_spectrum_promotes_to_full(self):
        # lambda_i = 0.99^i: the subspace-iteration convergence ratio
        # (lambda_{l+1}/lambda_k) is ~0.91 — neither stagnates within the
        # iteration budget nor passes the residual check, so the solver
        # must promote itself to the full eigh and return exact pairs.
        d = 100
        w_true = 0.99 ** np.arange(d)
        rng = np.random.default_rng(4)
        q, _ = np.linalg.qr(rng.standard_normal((d, d)))
        a = (q * w_true) @ q.T
        w, v, promoted = eigh_auto(jnp.asarray(a, dtype=jnp.float32), 4, max_iters=6)
        w_o, v_o = eigh_descending_host(a)
        assert bool(promoted)
        assert np.abs(np.asarray(w) - w_o[:4]).max() < 1e-4

    def test_mp_noise_spectrum_keeps_cluster_guarantees(self):
        # d/n = 64/4000 Marchenko-Pastur noise: whichever branch the
        # runtime check picks, the promises hold — orthonormal basis,
        # eigenvalues within cluster_tol relative of the truth, captured
        # variance within 2*cluster_tol of the optimal top-6 sum.
        rng = np.random.default_rng(5)
        x = rng.standard_normal((4000, 64)).astype(np.float32)
        a = x.T @ x / 4000.0
        w, v, promoted = eigh_auto(jnp.asarray(a), 6)
        w = np.asarray(w, dtype=np.float64)
        v = np.asarray(v, dtype=np.float64)
        assert np.abs(v.T @ v - np.eye(6)).max() < 1e-4
        w_o, _ = eigh_descending_host(a)
        assert np.abs(w - w_o[:6]).max() <= 0.05 * w_o[0]
        assert w.sum() > (1 - 0.1) * w_o[:6].sum()

    def test_tight_degenerate_cluster_accepted(self):
        # Eigenvalues within a 2% band: below cluster_tol=5%, so the
        # solver accepts without promoting — every exact solver's vectors
        # are equally arbitrary inside such a cluster; the promised
        # deliverables are orthonormality, per-eigenvalue accuracy to
        # cluster_tol relative, and near-optimal captured variance.
        rng = np.random.default_rng(8)
        d = 128
        w_true = 1.0 + 0.02 * rng.random(d)
        q, _ = np.linalg.qr(rng.standard_normal((d, d)))
        a = ((q * w_true) @ q.T).astype(np.float32)
        w, v, promoted = eigh_auto(jnp.asarray(a), 8)
        assert not bool(promoted)
        v = np.asarray(v, dtype=np.float64)
        assert np.abs(v.T @ v - np.eye(8)).max() < 1e-4
        w_sorted = np.sort(w_true)[::-1]
        assert np.abs(np.asarray(w) - w_sorted[:8]).max() < 0.05 * w_sorted[0]
        assert np.asarray(w).sum() > (1 - 0.1) * w_sorted[:8].sum()

    def test_adversarial_spectrum_sweep_accept_guarantees(self):
        # The acceptance rule's promises, validated across adversarial
        # spectra (geometric ratios through the slow regime, steps,
        # clusters, flat): whenever eigh_auto ACCEPTS (no promotion),
        # (1) eigenvalues are within cluster_tol relative of the truth,
        # (2) captured variance >= (1 - 2*cluster_tol) * optimal,
        # (3) the basis is orthonormal. Promoted cases are exact by
        # construction (full eigh).
        rng = np.random.default_rng(11)
        d, k, tol = 96, 6, 0.05
        spectra = [
            0.3 ** np.arange(d),
            0.7 ** np.arange(d),
            0.9 ** np.arange(d),
            0.97 ** np.arange(d),
            0.995 ** np.arange(d),
            np.ones(d),
            np.concatenate([np.full(3, 10.0), np.ones(d - 3)]),
            np.concatenate([np.full(k, 2.0), np.full(d - k, 1.9)]),
            np.concatenate([np.full(2, 5.0), np.full(8, 4.9), np.ones(d - 10)]),
            1.0 + 0.5 * rng.random(d),
        ]
        for idx, w_true in enumerate(spectra):
            w_true = np.sort(w_true)[::-1]
            q, _ = np.linalg.qr(rng.standard_normal((d, d)))
            a = ((q * w_true) @ q.T).astype(np.float32)
            w, v, promoted = eigh_auto(jnp.asarray(a), k)
            w, v = np.asarray(w, dtype=np.float64), np.asarray(v, dtype=np.float64)
            label = f"spectrum #{idx} promoted={bool(promoted)}"
            assert np.abs(v.T @ v - np.eye(k)).max() < 1e-3, label
            assert np.abs(w - w_true[:k]).max() <= tol * w_true[0] + 1e-4, label
            assert w.sum() >= (1 - 2 * tol) * w_true[:k].sum() - 1e-4, label

    def test_k_equals_d_runs_full(self):
        rng = np.random.default_rng(6)
        x = rng.standard_normal((200, 8)).astype(np.float32)
        a = x.T @ x / 200.0
        w, v, promoted = eigh_auto(jnp.asarray(a), 8)
        assert bool(promoted)
        w_o, _ = eigh_descending_host(a)
        assert np.abs(np.asarray(w) - w_o).max() < 1e-4

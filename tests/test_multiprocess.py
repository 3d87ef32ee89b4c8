"""Multi-process distributed execution: N OS processes, each owning a
slice of the data and a share of the (virtual CPU) devices, brought up via
jax.distributed and fitting through the ordinary estimator API — the
executor-per-chip deployment shape (the
reference's per-partition compute + cross-process reduce,
RapidsRowMatrix.scala:170-201)."""

import os
import socket
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from spark_rapids_ml_tpu.parallel.mesh import make_mesh, shard_rows, shard_rows_from_partitions

REPO = Path(__file__).resolve().parents[1]


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _concat_oracle(x, mesh):
    """Independent concat-then-pad placement oracle (shard_rows itself is
    now a wrapper over the partition version, so the oracle is built from
    raw numpy here)."""
    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

    n, d = x.shape
    dp, mp = mesh.shape[DATA_AXIS], mesh.shape[MODEL_AXIS]
    xp = np.pad(x, ((0, (-n) % dp), (0, (-d) % mp)))
    mask = np.zeros(xp.shape[0], dtype=x.dtype)
    mask[:n] = 1.0
    return xp, mask


class TestShardRowsFromPartitions:
    """The no-host-concat placement must be indistinguishable from a
    concat-then-shard placement."""

    def test_matches_concat_oracle(self, rng):
        x = rng.normal(size=(1003, 12))
        parts = [x[:100], x[100:700], x[700:]]
        mesh = make_mesh()
        xs, mask, n = shard_rows_from_partitions(parts, mesh)
        exp_x, exp_mask = _concat_oracle(x, mesh)
        assert n == 1003
        np.testing.assert_array_equal(np.asarray(xs), exp_x)
        np.testing.assert_array_equal(np.asarray(mask), exp_mask)

    def test_2d_mesh_with_feature_padding(self, rng):
        x = rng.normal(size=(65, 7))  # d=7 pads to 8 on a model axis of 2
        parts = [x[:30], x[30:]]
        mesh = make_mesh((4, 2))
        xs, mask, _ = shard_rows_from_partitions(parts, mesh)
        exp_x, exp_mask = _concat_oracle(x, mesh)
        np.testing.assert_array_equal(np.asarray(xs), exp_x)
        np.testing.assert_array_equal(np.asarray(mask), exp_mask)

    def test_wrapper_shard_rows_identical(self, rng):
        x = rng.normal(size=(37, 5))
        mesh = make_mesh()
        xs, mask, n = shard_rows(x, mesh)
        exp_x, exp_mask = _concat_oracle(x, mesh)
        assert n == 37
        np.testing.assert_array_equal(np.asarray(xs), exp_x)
        np.testing.assert_array_equal(np.asarray(mask), exp_mask)

    def test_mesh_pca_fit_unchanged(self, rng):
        from spark_rapids_ml_tpu.feature import PCA
        from spark_rapids_ml_tpu.utils.testing import assert_components_close

        x = rng.normal(size=(500, 6)) * np.linspace(1, 2, 6)
        parts = [x[:200], x[200:]]
        m_mesh = PCA(mesh=make_mesh()).setK(2).fit(parts)
        m_single = PCA().setK(2).fit(x)
        assert_components_close(m_mesh.pc, m_single.pc, 1e-9)


class TestMultiProcess:
    def _run(self, n_proc, extra_env=None):
        port = _free_port()
        procs = []
        for pid in range(n_proc):
            env = {
                **os.environ,
                "JAX_PLATFORMS": "cpu",
                "JAX_ENABLE_X64": "1",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
                "TPUML_COORDINATOR": f"127.0.0.1:{port}",
                "TPUML_NUM_PROCESSES": str(n_proc),
                "TPUML_PROCESS_ID": str(pid),
                **(extra_env or {}),  # extra_env wins (e.g. x64 off)
            }
            procs.append(
                subprocess.Popen(
                    [sys.executable, str(REPO / "tests" / "multiproc_pca_worker.py")],
                    stdout=subprocess.PIPE,
                    stderr=subprocess.PIPE,
                    text=True,
                    env=env,
                    cwd=str(REPO),
                )
            )
        outs = [p.communicate(timeout=300) for p in procs]
        for pid, (p, (out, err)) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"proc {pid} failed:\n{err[-3000:]}"
            assert f"OK process {pid}/{n_proc}" in out, out

    # The heaviest gang spawns (3-8 python+jax bring-ups each, fully
    # serialized on a single-core host) are slow-marked: tier-1 keeps the
    # 2/3-process streaming + empty-executor + x64-off cases plus the
    # real 2-process gang fit in tests/test_gang_fit.py, and the CI
    # "Multi-process fits" step runs this whole file unmarked.
    @pytest.mark.slow
    def test_4_process_distributed_pca(self):
        """4 OS processes x 2 virtual CPU devices = an 8-way data-parallel
        fit through PCA(mesh=...).fit(local_blocks), checked against the
        full-dataset oracle in every process."""
        self._run(4)

    @pytest.mark.slow
    def test_4x2_data_model_mesh(self):
        """A 4-process x 2-device fit on a (4, 2)
        data x model mesh — features sharded across each process's own
        devices, rows across processes — must match the oracle in every
        process. d=13 does NOT divide the model axis, so the zero-pad +
        strip path is genuinely exercised."""
        self._run(
            4,
            extra_env={"TPUML_TEST_MESH_SHAPE": "4,2", "TPUML_TEST_D": "13"},
        )

    @pytest.mark.slow
    def test_streaming_psum_merge(self):
        """Streamed multi-process fit with the device-collective moment
        merge (merge='auto' routes non-dd + mesh to the psum backend)."""
        self._run(
            3,
            extra_env={
                "TPUML_TEST_STREAMING": "1",
                "TPUML_TEST_MESH_SHAPE": "6,1",
            },
        )

    @pytest.mark.slow  # ~9 s spawn; runs full-file in CI's Multi-process step
    def test_empty_executor_does_not_strand_peers(self):
        """One process holds zero local rows; the fit must still complete
        on every process with the identical oracle-checked model (the
        asymmetric-failure/deadlock case)."""
        self._run(3, extra_env={"TPUML_TEST_EMPTY_LAST": "1"})

    @pytest.mark.slow  # ~8 s spawn; runs full-file in CI's Multi-process step
    def test_streaming_executors(self):
        """Each process STREAMS its local rows (one-shot block generator):
        per-process shifted scans merge through one allgather of the
        O(d^2) moments — the full executor deployment loop, checked
        against the full-dataset oracle in every process."""
        self._run(3, extra_env={"TPUML_TEST_STREAMING": "1"})

    @pytest.mark.slow  # ~8 s spawn; runs full-file in CI's Multi-process step
    def test_streaming_with_empty_executor(self):
        self._run(
            3,
            extra_env={
                "TPUML_TEST_STREAMING": "1",
                "TPUML_TEST_EMPTY_LAST": "1",
            },
        )

    @pytest.mark.slow
    def test_worker_death_fails_fast_on_survivors_no_hang(self):
        """Fault path: one executor hard-dies mid-stream
        (os._exit inside its block generator, before the merge
        collective). Survivors must FAIL FAST within the tightened
        heartbeat window — no hang, no wrong model. jax's coordination
        service propagates the peer death as a fatal distributed-runtime
        error ('task died' / 'stopped sending heartbeats') that
        terminates the surviving processes; a Python-level raise (rc 3)
        is also accepted if the collective errors before the fail-fast
        shutdown lands. The recovery recipe (relaunch-and-refit, the
        Spark barrier-task retry analogue) is documented in
        docs/PARITY.md §5."""
        import time

        port = _free_port()
        n_proc = 3
        procs = []
        for pid in range(n_proc):
            env = {
                **os.environ,
                "JAX_PLATFORMS": "cpu",
                "JAX_ENABLE_X64": "1",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
                "TPUML_COORDINATOR": f"127.0.0.1:{port}",
                "TPUML_NUM_PROCESSES": str(n_proc),
                "TPUML_PROCESS_ID": str(pid),
                "TPUML_TEST_FAULT_VICTIM": "2",
                "TPUML_HEARTBEAT_TIMEOUT": "10",
            }
            procs.append(
                subprocess.Popen(
                    [sys.executable, str(REPO / "tests" / "multiproc_pca_worker.py")],
                    stdout=subprocess.PIPE,
                    stderr=subprocess.PIPE,
                    text=True,
                    env=env,
                    cwd=str(REPO),
                )
            )
        t0 = time.monotonic()
        # Bounded wait: detection rides the 10 s heartbeat — a hang past
        # 120 s is the failure mode this test exists to rule out. The
        # finally-kill keeps a genuine hang from leaking three spinning
        # jax workers onto this 1-CPU box.
        try:
            outs = [p.communicate(timeout=120) for p in procs]
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
        elapsed = time.monotonic() - t0
        assert procs[2].returncode == 42, outs[2][1][-500:]  # victim died
        for pid in (0, 1):
            rc = procs[pid].returncode
            out, err = outs[pid]
            assert rc not in (0, 42), f"survivor {pid} rc={rc}\n{err[-2000:]}"
            clear_error = (
                "SURVIVOR_RAISED" in out  # collective raised first
                or "task died" in err  # fail-fast shutdown
                or "unhealthy" in err
                or "stopped sending heartbeats" in err
            )
            assert clear_error, f"survivor {pid} died without a clear error:\n{err[-2000:]}"
        assert elapsed < 110, f"survivors took {elapsed:.0f}s — effectively a hang"

    @pytest.mark.slow
    def test_8_process_north_star_8x1(self):
        """The EXACT north-star software topology — 8
        processes, one (virtual) device each, streamed per-executor
        blocks, psum moment merge on an (8, 1) mesh. The BASELINE config
        5 ×8 projection's software preconditions (bring-up, wire format,
        collective schedule at 8 members) all execute here; only the
        chips are virtual."""
        self._run(
            8,
            extra_env={
                "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
                "TPUML_TEST_STREAMING": "1",
                "TPUML_TEST_MESH_SHAPE": "8,1",
            },
        )

    @pytest.mark.slow
    def test_8_device_north_star_4x2_streamed(self):
        """The same 8 mesh members on a (4, 2) data x model mesh — rows
        over 4 executor groups, features over 2 — STREAMED, with d=13
        exercising the model-axis zero-pad + strip path. Runs as 4
        processes x 2 devices: the placement layer requires the model
        axis to divide each process's local device count (a process's
        addressable shards must span whole mesh rows —
        parallel/distributed.shard_rows_process_local), so a
        model-sharded deployment pairs chips within an executor, it does
        not split one chip's features across executors."""
        self._run(
            4,
            extra_env={
                "TPUML_TEST_STREAMING": "1",
                "TPUML_TEST_MESH_SHAPE": "4,2",
                "TPUML_TEST_D": "13",
            },
        )

    @pytest.mark.slow  # ~5 s spawn; runs full-file in CI's Multi-process step
    def test_streaming_without_x64(self):
        """The real-TPU configuration: fp32 compute, and the fp64 moment
        payload crosses the allgather as a double-float (hi, lo) pair —
        the wire must not silently squash it (r2 review)."""
        self._run(
            2,
            extra_env={
                "TPUML_TEST_STREAMING": "1",
                "TPUML_TEST_NO_X64": "1",
                "JAX_ENABLE_X64": "0",
            },
        )


class TestProcessLocalStreamingCovariance:
    """Direct unit coverage of the per-executor streaming merge (the
    process_count()==1 degenerate case exercises the same code: local
    scan, hi/lo or fp64 wire, ShiftedMoments merge)."""

    def test_matches_oracle_highest(self, rng):
        from spark_rapids_ml_tpu.parallel.distributed import (
            streaming_covariance_process_local,
        )

        x = rng.normal(size=(4_000, 6)) * np.linspace(1, 2, 6) + 1e3
        gen = (x[i : i + 512] for i in range(0, 4_000, 512))
        mean, cov, n = streaming_covariance_process_local(gen)
        assert n == 4_000
        np.testing.assert_allclose(mean, x.mean(axis=0), rtol=1e-12)
        np.testing.assert_allclose(cov, np.cov(x, rowvar=False), atol=1e-6)

    def test_matches_oracle_dd(self, rng):
        from spark_rapids_ml_tpu.parallel.distributed import (
            streaming_covariance_process_local,
        )

        x = 1e4 * (1 + np.arange(5)) + np.linspace(1, 2, 5) * rng.normal(
            size=(4_000, 5)
        )
        gen = (x[i : i + 700] for i in range(0, 4_000, 700))
        _, cov, _ = streaming_covariance_process_local(gen, precision="dd")
        assert np.max(np.abs(cov - np.cov(x, rowvar=False))) < 1e-5

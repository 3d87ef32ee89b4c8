"""Worker for the multi-process distributed PCA integration test.

Launched N times by tests/test_multiprocess.py with TPUML_COORDINATOR /
TPUML_NUM_PROCESSES / TPUML_PROCESS_ID in the environment — the same
contract a Spark/SLURM/GKE launcher would use in production (one process
per chip). Each worker loads only ITS slice of the dataset, fits through
the ordinary library API with a global mesh, and checks the fitted model
against the full-dataset numpy oracle.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

# The inherited env may select an accelerator; pin the CPU via config (it
# wins over the env) before the distributed runtime comes up.
import jax

jax.config.update("jax_platforms", "cpu")
# Cross-process collectives on the CPU backend need an explicit transport
# on older jaxlibs (the default "none" raises "Multiprocess computations
# aren't implemented on the CPU backend").
try:
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
except Exception:  # newer jax: gloo is the default, the knob may be gone
    pass
# Default x64 for tight oracle tolerances; TPUML_TEST_NO_X64 exercises the
# real-TPU configuration (fp32 compute, double-float moment wire format).
_x64 = os.environ.get("TPUML_TEST_NO_X64") != "1"
jax.config.update("jax_enable_x64", _x64)

from spark_rapids_ml_tpu.parallel import distributed as dist
from spark_rapids_ml_tpu.utils.envknobs import env_int

dist.initialize()  # from TPUML_* env

from spark_rapids_ml_tpu.feature import PCA


def main() -> None:
    pid = jax.process_index()
    n_proc = jax.process_count()
    assert n_proc == env_int("TPUML_NUM_PROCESSES"), n_proc

    # Deterministic global dataset; every worker derives the same one and
    # takes a DIFFERENT (deliberately uneven) slice as its local data.
    rng = np.random.default_rng(0)
    n = int(os.environ.get("TPUML_TEST_ROWS", "1003"))
    d = int(os.environ.get("TPUML_TEST_D", "12"))
    x = rng.normal(size=(n, d)) * np.linspace(1.0, 2.0, d) + 100.0
    if os.environ.get("TPUML_TEST_EMPTY_LAST") == "1" and n_proc > 1:
        # Deployment reality: one executor may hold no rows; the fit must
        # neither crash it nor strand its peers in a collective.
        bounds = np.linspace(0, n, n_proc).astype(int).tolist() + [n]
    else:
        bounds = np.linspace(0, n, n_proc + 1).astype(int)
    local = x[bounds[pid] : bounds[pid + 1]]

    shape_env = os.environ.get("TPUML_TEST_MESH_SHAPE")
    shape = tuple(int(v) for v in shape_env.split(",")) if shape_env else None
    mesh = dist.global_mesh(shape)
    victim = os.environ.get("TPUML_TEST_FAULT_VICTIM")
    if victim is not None and int(victim) == pid:
        # Fault injection: this executor dies mid-stream (after two
        # blocks, before the merge collective) — the hard-kill an OOM
        # or preemption delivers, with no cleanup.
        def dying_blocks():
            for i, start in enumerate(range(0, local.shape[0], 97)):
                if i == 2:
                    os._exit(42)
                yield local[start : start + 97]

        PCA(mesh=mesh).setK(3).fit(dying_blocks())
        raise AssertionError("victim must have exited")  # pragma: no cover
    if victim is not None:
        # Survivor of the fault-injection run: the fit must RAISE a
        # distributed-runtime error within the (tightened) heartbeat
        # window — not hang, not return a wrong model.
        import time

        blocks = (local[i : i + 97] for i in range(0, local.shape[0], 97))
        t0 = time.monotonic()
        try:
            PCA(mesh=mesh).setK(3).fit(blocks)
        except Exception as e:  # noqa: BLE001 - the assertion IS the raise
            elapsed = time.monotonic() - t0
            print(
                f"SURVIVOR_RAISED {type(e).__name__} after {elapsed:.1f}s: "
                f"{(str(e).splitlines() or [''])[0][:200]}"
            )
            sys.exit(3)
        print("SURVIVOR_COMPLETED_UNEXPECTEDLY")
        sys.exit(4)
    import time

    t0 = time.monotonic()
    if os.environ.get("TPUML_TEST_STREAMING") == "1":
        # Stream the local rows as a one-shot generator of small blocks —
        # per-process constant-memory scan + cross-process moment merge.
        blocks = (local[i : i + 97] for i in range(0, local.shape[0], 97))
        model = PCA(mesh=mesh).setK(3).fit(blocks)
    else:
        model = PCA(mesh=mesh).setK(3).fit([local] if local.shape[0] else [])
    # Fit wall (post-bringup, incl. compile + collectives), for a
    # weak-scaling reading of the worker logs.
    print(f"FIT_WALL {time.monotonic() - t0:.3f}")

    from spark_rapids_ml_tpu.utils.testing import assert_components_close

    cov = np.cov(x, rowvar=False)
    w, v = np.linalg.eigh(cov)
    w, v = w[::-1], v[:, ::-1]
    tol = 1e-6 if _x64 else 1e-3  # fp32 compute floor on +100-offset data
    assert_components_close(model.pc, v[:, :3], tol)
    np.testing.assert_allclose(
        model.explainedVariance, (w / w.sum())[:3], atol=tol
    )
    print(f"OK process {pid}/{n_proc}")


if __name__ == "__main__":
    main()

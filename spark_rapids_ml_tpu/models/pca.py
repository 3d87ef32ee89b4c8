"""PCA estimator/model — the user-facing L1/L2 layer.

Reference: ``com.nvidia.spark.ml.feature.PCA`` (PCA.scala:27, a thin rename)
over ``RapidsPCA`` / ``RapidsPCAModel`` (RapidsPCA.scala). Param surface kept
name-for-name (RapidsPCA.scala:30-106): ``k``, ``inputCol``, ``outputCol``,
``meanCentering`` (default True, :36-37), ``useGemm`` (default True, :47-49),
``useCuSolverSVD`` (default True, :58-59 — here it routes to the XLA
eigensolver; name retained for drop-in compatibility), ``gpuId`` (default −1,
:70-71 — here the TPU chip ordinal).

Differences by design (SURVEY.md §7 "beyond-parity"):
  - ``transform`` is the *batched accelerated* projection (one AᵀB GEMM per
    partition) — the path the reference disabled as too slow
    (RapidsPCA.scala:172-185). A per-row host path is kept for tiny inputs.
  - both covariance paths normalize by (numRows − 1) (quirk §7.5 fixed).
"""

from __future__ import annotations

from typing import Any, Optional

import numpy as np

from spark_rapids_ml_tpu.core.data import (
    DataFrame,
    as_matrix,
    as_partitions,
    extract_column,
    num_features,
)
from spark_rapids_ml_tpu.core.estimator import Estimator, HasInputCol, HasOutputCol, Model
from spark_rapids_ml_tpu.core.lazy_state import LazyHostState
from spark_rapids_ml_tpu.core.params import Param, gt, toBoolean, toInt, toString
from spark_rapids_ml_tpu.core.persistence import (
    MLReadable,
    get_and_set_params,
    load_data,
    load_metadata,
    save_data,
    save_metadata,
)
from spark_rapids_ml_tpu.core.serving import (
    note_device_cache,
    serve_rows,
    serve_stream,
)
from spark_rapids_ml_tpu.linalg.row_matrix import RowMatrix
from spark_rapids_ml_tpu.ops.linalg import project_rows
from spark_rapids_ml_tpu.utils.tracing import TraceColor, TraceRange


def _project_kernel(x, pc, *, precision: str = "highest"):
    """Serving kernel: rows onto the principal subspace. The cast follows
    the device-transform convention (components follow the batch dtype)
    and fuses into the projection GEMM."""
    return project_rows(x, pc.astype(x.dtype), precision=precision)


class _PCAParams(HasInputCol, HasOutputCol):
    """RapidsPCAParams equivalent (RapidsPCA.scala:30-75)."""

    k = Param("_", "k", "number of principal components", lambda v: gt(0)(toInt(v)))
    meanCentering = Param("_", "meanCentering", "whether to center data before covariance", toBoolean)
    useGemm = Param("_", "useGemm", "use dense fused GEMM covariance (else packed spr layout)", toBoolean)
    useCuSolverSVD = Param(
        "_", "useCuSolverSVD", "use the accelerated (XLA) eigensolver instead of host SVD", toBoolean
    )
    gpuId = Param("_", "gpuId", "accelerator chip ordinal; -1 = runtime-assigned", toInt)
    solver = Param(
        "_", "solver", "auto | covariance | randomized (wide-feature sketch)", toString
    )
    precision = Param(
        "_",
        "precision",
        "auto | default | high | highest | dd (double-float fp64 emulation)",
        toString,
    )
    covarianceBackend = Param(
        "_",
        "covarianceBackend",
        "xla (fused, default) | pallas (VMEM-resident streaming kernel)",
        toString,
    )
    eigenSolver = Param(
        "_",
        "eigenSolver",
        "auto (self-selecting, default) | full (exact eigh) | "
        "topk (subspace iteration, k << d)",
        toString,
    )
    eigenIters = Param(
        "_",
        "eigenIters",
        "subspace iterations for eigenSolver='topk' (raise for slowly "
        "decaying spectra: subspace error ~ (lambda_{k+1}/lambda_k)^iters)",
        toInt,
    )

    def __init__(self, uid: Optional[str] = None):
        super().__init__(uid)
        self._setDefault(
            meanCentering=True, useGemm=True, useCuSolverSVD=True, gpuId=-1,
            solver="auto", precision="auto", covarianceBackend="xla",
            eigenSolver="auto", eigenIters=8,
        )

    def getK(self) -> int:
        return self.getOrDefault(self.k)

    def getMeanCentering(self) -> bool:
        return self.getOrDefault(self.meanCentering)

    def getUseGemm(self) -> bool:
        return self.getOrDefault(self.useGemm)

    def getUseCuSolverSVD(self) -> bool:
        return self.getOrDefault(self.useCuSolverSVD)

    def getGpuId(self) -> int:
        return self.getOrDefault(self.gpuId)

    def getSolver(self) -> str:
        return self.getOrDefault(self.solver)

    def getPrecision(self) -> str:
        return self.getOrDefault(self.precision)

    def getCovarianceBackend(self) -> str:
        return self.getOrDefault(self.covarianceBackend)

    def getEigenSolver(self) -> str:
        return self.getOrDefault(self.eigenSolver)

    def getEigenIters(self) -> int:
        return self.getOrDefault(self.eigenIters)


class PCA(_PCAParams, Estimator, MLReadable):
    """PCA estimator. ``PCA().setK(3).setInputCol("features").fit(df)``."""

    # Consumes device arrays in place, so tuning loops may feed
    # device-resident fold slices (tuning._device_fold_prep).
    _device_foldable = True

    def __init__(self, uid: Optional[str] = None, mesh=None):
        super().__init__(uid)
        self.mesh = mesh

    # chainable setters (RapidsPCA.scala:80-106)
    def setK(self, value: int) -> "PCA":
        self.set(self.k, value)
        return self

    def setMeanCentering(self, value: bool) -> "PCA":
        self.set(self.meanCentering, value)
        return self

    def setUseGemm(self, value: bool) -> "PCA":
        self.set(self.useGemm, value)
        return self

    def setUseCuSolverSVD(self, value: bool) -> "PCA":
        self.set(self.useCuSolverSVD, value)
        return self

    def setGpuId(self, value: int) -> "PCA":
        self.set(self.gpuId, value)
        return self

    def setMesh(self, mesh) -> "PCA":
        self.mesh = mesh
        return self

    def setSolver(self, value: str) -> "PCA":
        if value not in ("auto", "covariance", "randomized"):
            raise ValueError(
                f"solver must be auto/covariance/randomized, got {value!r}"
            )
        self.set(self.solver, value)
        return self

    def setPrecision(self, value: str) -> "PCA":
        """Matmul precision for the covariance path. ``"dd"`` emulates fp64
        with double-float MXU GEMMs (ops.doubledouble) — the reference's
        ``double[]`` numerics bar (JniRAPIDSML.java:64-69) on fp32-only
        hardware; ``"auto"`` selects it when fitting float64 input without
        x64 support."""
        from spark_rapids_ml_tpu.ops.linalg import validate_precision

        self.set(self.precision, validate_precision(value))
        return self

    def setEigenSolver(self, value: str) -> "PCA":
        """``"auto"`` (default) is self-selecting: subspace iteration that
        stops when its captured-variance objective stagnates and promotes
        itself to the full eigensolver when it runs out of iterations
        unconverged (ops.eigh.eigh_auto — the runtime check that replaces
        a static solver choice). ``"topk"`` forces subspace iteration +
        Rayleigh-Ritz (O(d^2 k) MXU matmuls instead of the full O(d^3)
        eigensolve): the right explicit choice when k << d and the
        spectrum decays; explained-variance ratios stay exact
        (trace-normalized). Convergence depends on the eigengap: subspace
        error shrinks like (lambda_{k+1}/lambda_k)^iters, so raise
        ``eigenIters`` (default 8) for slowly decaying spectra. ``"full"``
        is the reference-parity exact eigh (calSVD's eigDC,
        rapidsml_jni.cu:302-356)."""
        if value not in ("auto", "full", "topk"):
            raise ValueError(f"eigenSolver must be auto|full|topk, got {value!r}")
        self.set(self.eigenSolver, value)
        return self

    def setEigenIters(self, value: int) -> "PCA":
        """Iteration budget for the subspace eigensolvers. ``"topk"`` runs
        exactly this many; ``"auto"`` treats it as a CAP on an
        early-exiting loop and enforces a quality floor of
        ``ops.eigh.AUTO_MIN_ITERS`` (12) — below that the accept/promote
        check cannot separate converged from degenerate."""
        if value < 1:
            raise ValueError(f"eigenIters must be >= 1, got {value}")
        self.set(self.eigenIters, value)
        return self

    def setCovarianceBackend(self, value: str) -> "PCA":
        """Kernel backend for the covariance GEMM: "xla" (whole-array
        fusion, the default) or "pallas" (centering + accumulation fused
        in VMEM, for when row-blocking is required). The default predates
        the chip; neither is measured against the other on it (ROADMAP.md
        Design 6, Design 14)."""
        if value not in ("xla", "pallas"):
            raise ValueError(f"covarianceBackend must be xla|pallas, got {value!r}")
        self.set(self.covarianceBackend, value)
        return self

    # Above this many features, "auto" switches to the randomized sketch:
    # the (d, d) covariance + full eigh grow as d^2 / d^3 while the sketch
    # stays O(n d l) with l = k + oversample.
    _RANDOMIZED_AUTO_DIM = 4096

    def _fit(self, dataset: Any) -> "PCAModel":
        """RapidsPCA.fit (RapidsPCA.scala:111-125)."""
        from spark_rapids_ml_tpu.core import membudget
        from spark_rapids_ml_tpu.core.data import is_streaming_source

        rows = extract_column(dataset, self.getInputCol())
        # Budgeted admission (core/membudget.py): an over-budget host
        # input re-enters this fit as a first-class block reader — the
        # SAME streaming moments/sketch path an explicit reader takes,
        # bit-identical by construction — and a device OOM mid-fit
        # reclaims caches and takes the same exit.
        can_stream = self.getCovarianceBackend() != "pallas"
        guard = membudget.fit_memory_guard(
            "pca", rows, can_stream=can_stream,
            why_cannot_stream="covarianceBackend='pallas' needs the "
                              "materialized single-device path",
            mesh=self.mesh, ledger_families=("pca",),
        )
        if guard.degrade:
            return membudget.run_streaming_with_recovery(
                "pca", self._fit, guard.matrix
            )
        fallback = (
            (lambda: membudget.run_streaming_with_recovery(
                "pca", self._fit, membudget.host_matrix(rows)))
            if can_stream and self.mesh is None
            and not is_streaming_source(rows) else None
        )
        return membudget.run_fit_with_oom_recovery(
            "pca", lambda: self._fit_in_memory(rows, dataset), fallback
        )

    def _fit_in_memory(self, rows: Any, dataset: Any) -> "PCAModel":
        """Solver routing + fit for an ADMITTED input: in-memory host or
        device data, or any streaming source (which the admission gate
        waves through untouched)."""
        from spark_rapids_ml_tpu.core.data import infer_input_dtype, is_streaming_source

        import jax

        from spark_rapids_ml_tpu.core.data import is_reiterable_stream

        solver = self.getSolver()
        streaming = is_streaming_source(rows)
        if solver == "randomized" and streaming and not is_reiterable_stream(rows):
            raise ValueError(
                "the randomized solver makes multiple passes; a one-shot "
                "generator cannot be re-read — pass an iterator factory "
                "(zero-arg callable) or a block reader (iter_blocks), or "
                "use solver='covariance' (one-pass)"
            )
        if solver == "randomized" and streaming and self.mesh is not None:
            # An explicit mesh must never be silently dropped: the
            # streaming sketch is single-device.
            raise ValueError(
                "the streaming randomized solver is single-device; unset "
                "the mesh, materialize the input (mesh-sharded sketch), or "
                "use solver='covariance' (streamed mesh covariance)"
            )
        if solver == "randomized" and jax.process_count() > 1:
            raise ValueError(
                "the randomized solver has no multi-process path; use "
                "solver='covariance' (per-executor streaming + moment merge)"
            )
        if solver == "randomized" and self.getPrecision() == "dd":
            raise ValueError(
                "the randomized solver has no dd path; use "
                "solver='covariance' with precision='dd'"
            )
        if self.getCovarianceBackend() == "pallas" and (
            self.mesh is not None
            or streaming
            or not self.getUseGemm()
            or solver == "randomized"
        ):
            raise ValueError(
                "covarianceBackend='pallas' applies to the single-device "
                "materialized GEMM covariance path (no mesh, no streaming "
                "source, useGemm=True, solver != 'randomized')"
            )
        # Resolve "auto" against the RAW input dtype (before densification
        # makes the route's dtype of it) so only genuinely-fp64 sources
        # route to dd — RowMatrix.resolve is the single home of this policy.
        requested_prec = self.getPrecision()
        # Probe the container extract_column did NOT already coerce: for a
        # pandas frame with no inputCol, extract_column densified to
        # float64, so the probe must look at the original frame.
        probe_source = rows
        if requested_prec == "auto" and self.getInputCol() is None:
            try:
                import pandas as pd

                if isinstance(dataset, pd.DataFrame):
                    probe_source = dataset
            except ImportError:  # pragma: no cover
                pass
        input_dtype = (
            infer_input_dtype(probe_source) if requested_prec == "auto" else None
        )
        # Mixed-precision policy layering (ops/precision.py): explicit
        # setPrecision > TPUML_PRECISION[_PCA] knobs > committed autotune
        # decision > the param default. fp64 input keeps its pre-policy
        # "auto" dd routing — the tuner never displaces fp64 emulation.
        from spark_rapids_ml_tpu.ops.precision import resolve_policy

        explicit = self.getPrecision() if self.isSet(self.precision) else None
        wants_f64 = input_dtype is not None and np.dtype(input_dtype) == np.float64
        if explicit is None and wants_f64:
            explicit = "auto"
        requested_prec = resolve_policy("pca", explicit, default=requested_prec)
        resolved_prec = RowMatrix.resolve(
            requested_prec,
            mesh=self.mesh,
            # Only "auto" needs the raw-dtype probe.
            input_dtype=input_dtype,
            backend=self.getCovarianceBackend(),
        )
        # 'auto' peeks at the first partition/block only — the covariance
        # path streams partitions, so routing must not force a densify.
        # An auto-resolved dd forces the covariance path (the sketch is
        # fp32-only), same as explicit precision='dd'. Wide-feature auto
        # routing covers materialized, mesh-sharded, and RE-ITERABLE
        # streaming inputs (one-shot generators cannot be multi-passed —
        # they keep the one-pass covariance path at any width).
        if solver == "randomized":
            return self._fit_randomized(rows)
        if (
            solver == "auto"
            and jax.process_count() == 1
            and resolved_prec != "dd"
            and self.getCovarianceBackend() != "pallas"  # explicit kernel choice
        ):
            from spark_rapids_ml_tpu.core.data import peek_stream_width

            if streaming:
                # mesh + stream keeps the streamed mesh covariance (the
                # streaming sketch is single-device — see the explicit-
                # solver guard above).
                wide = (
                    self.mesh is None
                    and is_reiterable_stream(rows)
                    and peek_stream_width(rows) >= self._RANDOMIZED_AUTO_DIM
                )
            else:
                wide = num_features(rows) >= self._RANDOMIZED_AUTO_DIM
                if wide and self.mesh is not None:
                    # auto must pick a WORKING path: the sketch does not
                    # shard the model axis, so a 2-D mesh whose model
                    # axis would pad the features keeps the mesh
                    # covariance (explicit solver='randomized' raises
                    # loudly instead).
                    from spark_rapids_ml_tpu.parallel.mesh import model_axis_size

                    mp = model_axis_size(self.mesh)
                    wide = num_features(rows) % mp == 0
            if wide:
                return self._fit_randomized(rows)
        mat = RowMatrix(
            rows,
            mean_centering=self.getMeanCentering(),
            use_gemm=self.getUseGemm(),
            use_accel_svd=self.getUseCuSolverSVD(),
            device_id=self.getGpuId(),
            mesh=self.mesh,
            precision=resolved_prec,
            backend=self.getCovarianceBackend(),
            eigen_solver=self.getEigenSolver(),
            eigen_iters=self.getEigenIters(),
        )
        pc, explained = mat.compute_principal_components_and_explained_variance(self.getK())
        # Device-resident fits return device arrays; PCAModel converts to
        # host float64 LAZILY, so a device-input fit never pays a host
        # transfer the caller didn't ask for (the fit stays fully async
        # until someone reads the model).
        model = PCAModel(self.uid, pc, explained)
        return self._copyValues(model)

    def _sketch_precision(self) -> str:
        """Policy mode for the randomized-sketch GEMMs (ops/precision.py).
        The sketch is fp32-only, so 'auto' resolves 'highest' here
        (explicit 'dd' was rejected before routing)."""
        from spark_rapids_ml_tpu.ops.precision import resolve_policy

        requested = self.getPrecision() if self.isSet(self.precision) else None
        mode = resolve_policy("pca", requested, default="highest")
        return "highest" if mode in ("auto", "dd") else mode

    def _fit_randomized(self, rows) -> "PCAModel":
        """Wide-feature path: subspace sketch, no (d, d) covariance.

        Covers every input mode: device arrays in place;
        host data on one chip; host partitions over a MESH (row-sharded
        with a padding mask — the sketch GEMMs shard like the covariance,
        one psum per rmatmul, no (d, d) on any device); and re-iterable
        block streams at O(d·l + block) memory (randomized_pca_streaming).
        """
        import jax
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.core.data import (
            is_device_array,
            is_streaming_source,
        )
        from spark_rapids_ml_tpu.ops.randomized import (
            randomized_pca,
            randomized_pca_streaming,
        )

        k = self.getK()
        prec = self._sketch_precision()
        if is_streaming_source(rows):
            from spark_rapids_ml_tpu.core.data import iter_stream_blocks

            gpu_id = self.getGpuId()
            comps, ratio, _, _ = randomized_pca_streaming(
                lambda: iter_stream_blocks(rows),
                k,
                jax.random.key(0),
                center=self.getMeanCentering(),
                precision=prec,
                device=jax.local_devices()[gpu_id] if gpu_id >= 0 else None,
            )
            return self._copyValues(PCAModel(self.uid, comps, ratio))
        mask = None
        n_true = None
        if is_device_array(rows):
            # Already resident: sketch in place, stay async (lazy model).
            n, d = rows.shape
            if not 1 <= k <= min(n, d):
                raise ValueError(f"k must be in [1, {min(n, d)}], got {k}")
            x = rows
            if self.mesh is not None:
                # An explicit mesh must never be silently dropped (the
                # stance of RowMatrix._device_array_on_mesh): shard onto
                # the mesh so the sketch GEMMs run under GSPMD. Same
                # constraint as the host-partitions branch below: the
                # sketch cannot PAD the model axis, so features must
                # divide it exactly (mp=1 always does).
                from spark_rapids_ml_tpu.parallel.mesh import (
                    device_array_rows_on_mesh,
                    model_axis_size,
                )

                mp = model_axis_size(self.mesh)
                if d % mp != 0:
                    raise ValueError(
                        "the randomized solver does not shard the model "
                        f"axis (features {d} would pad to a multiple of "
                        f"{mp}); use a (dp, 1) mesh or solver='covariance'"
                    )
                x = device_array_rows_on_mesh(
                    x, self.mesh, shard_features=mp > 1
                )
        elif self.mesh is not None:
            from spark_rapids_ml_tpu.parallel.mesh import (
                shard_rows_from_partitions,
            )

            parts = as_partitions(rows)
            dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
            if jax.process_count() > 1:
                # Gang deploy mode: these partitions are one member's LOCAL
                # rows — assemble the global sketch input through the
                # process-local funnel (same masked-padding semantics).
                from spark_rapids_ml_tpu.parallel.distributed import (
                    shard_rows_process_local,
                )

                x, mask, n_true, d = shard_rows_process_local(
                    parts, self.mesh, dtype=np.dtype(dtype)
                )
            else:
                x, mask, n_true = shard_rows_from_partitions(
                    parts, self.mesh, dtype=np.dtype(dtype)
                )
                d = parts[0].shape[1]
            if not 1 <= k <= min(n_true, d):
                raise ValueError(f"k must be in [1, {min(n_true, d)}], got {k}")
            if x.shape[1] != d:
                raise ValueError(
                    "the randomized solver does not shard the model axis "
                    f"(features {d} pad to {x.shape[1]}); use a (dp, 1) "
                    "mesh or solver='covariance'"
                )
        else:
            x_host = as_matrix(rows)
            n, d = x_host.shape
            if not 1 <= k <= min(n, d):
                raise ValueError(f"k must be in [1, {min(n, d)}], got {k}")
            dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
            # Honor the chip-ordinal param the way the covariance path does
            # (RowMatrix._device); the sketch SEED stays fixed so the fitted
            # model never depends on placement.
            gpu_id = self.getGpuId()
            device = (
                jax.local_devices()[gpu_id]
                if gpu_id >= 0
                else jax.local_devices()[0]
            )
            # Guarded placement: the whole-dataset upload goes through the
            # ingest.device_put chokepoint (fault point, OOM retry + cache
            # reclaim) instead of a bare device_put.
            from spark_rapids_ml_tpu.core.ingest import place_array

            x = place_array(x_host, dtype=dtype, device=device)
        comps, ratio, _ = randomized_pca(
            x,
            k,
            jax.random.key(0),
            center=self.getMeanCentering(),
            mask=mask,
            n_true=n_true,
            precision=prec,
        )
        # Gang fits can hand back sharded results; the model's lazy host
        # pulls need them fully replicated (no-op otherwise).
        from spark_rapids_ml_tpu.parallel.distributed import replicate_for_host

        comps, ratio = replicate_for_host(self.mesh, comps, ratio)
        model = PCAModel(self.uid, comps, ratio)
        return self._copyValues(model)

class PCAModel(_PCAParams, Model, LazyHostState):
    """Fitted PCA model: principal components (d, k) + explained variance (k,).

    Reference: RapidsPCAModel (RapidsPCA.scala:146-205).
    """

    def __init__(
        self,
        uid: Optional[str] = None,
        pc: Optional[np.ndarray] = None,
        explainedVariance: Optional[np.ndarray] = None,
    ):
        super().__init__(uid)
        # Raw fitted state may be host numpy OR a jax.Array from a
        # device-resident fit; the public `pc`/`explainedVariance` host
        # float64 views convert lazily (and cache) so a device fit stays
        # async until the model is actually read. Pickling materializes
        # host state (core/lazy_state.LazyHostState).
        self._pc_raw = pc
        self._ev_raw = explainedVariance
        self._pc_np: Optional[np.ndarray] = None
        self._ev_np: Optional[np.ndarray] = None
        self._pc_dev_cache: dict = {}

    _lazy_host_fields = {
        "_pc_raw": ("_pc_np", np.float64),
        "_ev_raw": ("_ev_np", np.float64),
    }
    _pickle_clear = ("_pc_dev_cache",)
    _pickle_clear_values = {"_pc_dev_cache": {}}

    @property
    def pc(self) -> Optional[np.ndarray]:
        """Principal components (d, k) as host float64 (Spark's
        DenseMatrix surface, RapidsPCA.scala:146-150)."""
        return self._lazy_host_view("_pc_raw")

    @property
    def explainedVariance(self) -> Optional[np.ndarray]:
        """Explained-variance ratios (k,) as host float64."""
        return self._lazy_host_view("_ev_raw")

    def setInputCol(self, value: str) -> "PCAModel":
        self.set(self.inputCol, value)
        return self

    def setOutputCol(self, value: str) -> "PCAModel":
        self.set(self.outputCol, value)
        return self

    def copy(self, extra=None) -> "PCAModel":
        """Model.copy preserves fitted state (Spark's Model.copy contract)."""
        that = PCAModel(self.uid, self._pc_raw, self._ev_raw)
        return self._copyValues(that, extra)

    def transform(self, dataset: Any) -> Any:
        """Project rows onto the principal subspace: out = X · pc.

        The accelerated batched path (AᵀB GEMM per partition) — live here,
        disabled in the reference (RapidsPCA.scala:172-185). Returns the same
        container family as the input: DataFrame shim -> DataFrame with
        outputCol appended; array-like -> (n, k) ndarray.
        """
        if self._pc_raw is None:
            raise RuntimeError("model has no principal components")
        rows = extract_column(dataset, self.getInputCol())
        from spark_rapids_ml_tpu.core.data import (
            is_device_array,
            is_streaming_source,
            iter_stream_blocks,
        )

        if is_device_array(rows):
            # Device-resident projection through the serving program cache:
            # one AOT MXU matmul per (bucket, dtype), result stays on device
            # (the symmetric counterpart of the device-resident fit; the
            # batched path the reference disabled, RapidsPCA.scala:172-185).
            with TraceRange("device transform", TraceColor.GREEN):
                return serve_rows(
                    _project_kernel,
                    rows,
                    (self._pc_device(rows.dtype),),
                    static={"precision": self._serving_precision()},
                    name="pca.transform",
                )

        pc_dev = self._pc_device(self._serving_dtype())
        if is_streaming_source(rows):
            # Streaming in, streaming out: project block by block at
            # constant memory (the symmetric counterpart of streaming fit),
            # double-buffered — block k+1's H2D overlaps block k's GEMM.
            from spark_rapids_ml_tpu.core.data import _block_to_dense

            def dense_blocks():
                for blk in iter_stream_blocks(rows):
                    part = _block_to_dense(blk)
                    if part.shape[0] == 0:
                        # Empty partitions densify to (0, 0) — skip
                        # rather than matmul a widthless block.
                        continue
                    yield part

            with TraceRange("stream transform", TraceColor.GREEN):
                return serve_stream(
                    _project_kernel,
                    dense_blocks(),
                    (pc_dev,),
                    static={"precision": self._serving_precision()},
                    name="pca.transform",
                    dtype=pc_dev.dtype,
                )
        parts = as_partitions(rows)
        with TraceRange("batch transform", TraceColor.GREEN):
            outs = list(
                serve_stream(
                    _project_kernel,
                    parts,
                    (pc_dev,),
                    static={"precision": self._serving_precision()},
                    name="pca.transform",
                    dtype=pc_dev.dtype,
                )
            )
        if not outs:
            # All partitions empty: keep the (0, k) ndarray contract.
            projected = np.zeros((0, self.pc.shape[1]), dtype=self.pc.dtype)
        else:
            projected = np.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]
        if isinstance(dataset, DataFrame):
            return dataset.withColumn(self.getOutputCol(), list(projected))
        try:
            import pandas as pd

            if isinstance(dataset, pd.DataFrame):
                out_df = dataset.copy()
                out_df[self.getOutputCol()] = list(projected)
                return out_df
        except ImportError:  # pragma: no cover
            pass
        return projected

    def _pc_device(self, dtype):
        """Components as a device array at ``dtype``, cached — repeated
        device transforms must not pay a host->device copy per call."""
        import jax.numpy as jnp

        key = str(dtype)
        if key not in self._pc_dev_cache:
            self._pc_dev_cache[key] = jnp.asarray(self._pc_raw).astype(dtype)
            note_device_cache(self)
        return self._pc_dev_cache[key]

    def _serving_dtype(self):
        """Compute dtype for host-batch serving: the components' own dtype,
        canonicalized (f64 under x64, f32 otherwise) — one program set per
        model, however the batch dtypes wander."""
        import jax

        return jax.dtypes.canonicalize_dtype(self.pc.dtype)

    def _serving_precision(self) -> str:
        """The serving-family policy mode (ops/precision.py): an explicit
        estimator ``setPrecision`` survives into the model and wins
        (non-GEMM modes like 'auto'/'dd' serve at 'highest'); otherwise
        the TPUML_PRECISION[_SERVING] knobs and committed autotune
        decisions apply. Part of the static dict, hence of the
        AOT/program cache key."""
        from spark_rapids_ml_tpu.ops.precision import resolve_policy

        requested = self.getPrecision() if self.isSet(self.precision) else None
        if requested in ("auto", "dd"):
            requested = "highest"
        return resolve_policy("serving", requested)

    def serving_signature(self):
        """The online-serving contract: the projection kernel, the
        device-resident components at the serving dtype, and the (n, k)
        projected output spec."""
        import jax

        from spark_rapids_ml_tpu.serving.signature import ServingSignature

        if self._pc_raw is None:
            raise RuntimeError("model has no principal components")
        pc = self._pc_device(self._serving_dtype())
        d, k = int(pc.shape[0]), int(pc.shape[1])
        return ServingSignature(
            kernel=_project_kernel,
            weights=(pc,),
            static={"precision": self._serving_precision()},
            name="pca.transform",
            n_features=d,
            output_spec=lambda n, dtype: (
                jax.ShapeDtypeStruct((n, k), dtype),
            ),
        )

    # --- persistence (RapidsPCA.scala:207-255) ---

    def _save_impl(self, path: str) -> None:
        save_metadata(self, path, class_name="com.nvidia.spark.ml.feature.PCAModel")
        save_data(
            path,
            {
                "pc": ("matrix", self.pc),
                "explainedVariance": ("vector", self.explainedVariance),
            },
        )

    @classmethod
    def _load_impl(cls, path: str) -> "PCAModel":
        metadata = load_metadata(path, expected_class="PCAModel")
        data = load_data(path)
        model = cls(metadata["uid"], data["pc"], data["explainedVariance"])
        get_and_set_params(model, metadata)
        return model

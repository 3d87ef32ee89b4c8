"""Distributed row matrix — the ``RapidsRowMatrix`` equivalent (L3).

Reference: RapidsRowMatrix.scala — rows as RDD[Vector] partitions, covariance
via either per-partition JNI GEMM + Spark reduce (:168-201) or packed
spr/treeAggregate (:202-251), then principal components via driver-side
cuSolver or breeze SVD (:75-125).

Here partitions are dense host blocks (core.data.as_partitions) and covariance
runs per-partition on the accelerator with host-side partial summation (the
Spark-reduce analogue, so the structure generalizes to one-chip-per-executor
deployments), or — when a mesh is supplied — as ONE jitted sharded computation
whose covariance sum rides ICI collectives (parallel.distributed_cov), the
TPU-native fast path SURVEY.md §2 anticipates.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_ml_tpu.core.data import (
    is_device_array,
    is_streaming_source,
    iter_stream_blocks,
)
from spark_rapids_ml_tpu.core.ingest import PlacementWindow, dense_partitions
from spark_rapids_ml_tpu.ops.covariance import (
    centered_gram,
    centered_gram_packed,
    comoment_add_block,
    comoment_init,
    comoment_resident,
    count_resident_blocks,
    streaming_mean_and_covariance,
    welford_add_block,
    welford_init,
)
from spark_rapids_ml_tpu.ops.eigh import (
    auto_max_iters,
    eigh_auto,
    eigh_descending,
    eigh_descending_host,
    eigh_topk,
    eigh_topk_host,
    sign_flip,
)
from spark_rapids_ml_tpu.ops.linalg import resolve_precision, triu_to_full
from spark_rapids_ml_tpu.parallel.distributed_cov import distributed_mean_and_covariance
from spark_rapids_ml_tpu.parallel.mesh import shard_rows_from_partitions
from spark_rapids_ml_tpu.utils.tracing import (
    StageRange,
    TraceColor,
    TraceRange,
    bump_counter,
)


from functools import partial as _partial


@_partial(
    jax.jit,
    static_argnames=("k", "center", "precision", "eigen_solver", "eigen_iters", "blocked"),
)
def _pca_fit_device(x, k, center, precision, eigen_solver, eigen_iters, blocked=True):
    """The whole PCA fit as ONE XLA program on a device-resident array:
    column means and the centred Gram summed in blocks of rows
    (``ops/covariance.py::comoment_resident``), the eigensolve and the
    explained variance — nothing leaves the device, nothing re-traces
    across calls (module-level jit keyed on shape + the static config).
    Measured in ``pca_3000.device_rows`` (PERF.md section 5); the
    reference's equivalent spans four JNI calls with host copies between
    each (RapidsRowMatrix.scala:149-257, rapidsml_jni.cu:159-356).

    ``blocked=False`` is the mesh fit's form until the four-chip cell
    (ROADMAP.md Reach 1): rows sharded over the data axis are summed in one
    contraction a chip, XLA inserting the ``psum`` (a scan over a sharded
    axis would gather every block).
    """
    n, d = x.shape
    if blocked:
        _, mean, _, gram = comoment_resident(x, precision=precision, center=center)
    else:
        mean = jnp.mean(x, axis=0) if center else jnp.zeros((d,), dtype=x.dtype)
        gram = centered_gram(x, mean, precision=precision)
    cov = gram / (n - 1)
    # What rounding can leave of constant columns: their means are not exact
    # (a block's mean is a rounded sum), so their Gram is nought only to
    # epsilon squared of the means' size, of either sign.
    floor = jnp.finfo(x.dtype).eps ** 2 * jnp.sum(mean * mean)

    def ratio(w, total):
        # Zero-variance input (constant rows) must yield zeros, not NaN —
        # the `total > 0` guard every host path applies, at rounding's floor.
        some = total > floor
        return jnp.where(some, w / jnp.where(some, total, 1), 0.0)

    if eigen_solver == "auto" and k < d:
        w, v, _ = eigh_auto(cov, k, max_iters=auto_max_iters(eigen_iters))
        w = jnp.maximum(w, 0)
        return v, ratio(w, jnp.trace(cov))
    if eigen_solver == "topk" and k < d:
        w, v = eigh_topk(cov, k, iters=eigen_iters)
        w = jnp.maximum(w, 0)
        return v, ratio(w, jnp.trace(cov))
    w, v = eigh_descending(cov)
    w = jnp.maximum(w, 0)
    return v[:, :k], ratio(w, jnp.sum(w))[:k]


class RowMatrix:
    """A row-partitioned matrix with accelerated covariance/PCA.

    Parameters mirror the reference ctor (RapidsRowMatrix.scala:30-45):
    ``mean_centering`` (:36), ``use_gemm`` (:47 — dense fused GEMM vs packed
    spr-layout aggregation), ``use_accel_svd`` (:58 — XLA eigh vs host numpy,
    the cuSolver/breeze switch), ``device_id`` (:70 — chip ordinal, −1 = let
    the runtime pick, replacing TaskContext GPU discovery :171-175).
    """

    def __init__(
        self,
        rows,
        mean_centering: bool = True,
        use_gemm: bool = True,
        use_accel_svd: bool = True,
        device_id: int = -1,
        mesh=None,
        precision: str = "highest",
        dtype=None,
        input_dtype=None,
        backend: str = "xla",
        eigen_solver: str = "full",
        eigen_iters: int = 8,
    ):
        # Resolved before the rows are looked at: the dtype the host
        # partitions are made in follows the route (below).
        self.precision = self.resolve(
            precision, mesh=mesh, input_dtype=input_dtype, backend=backend
        )
        self._dtype = dtype
        # Streaming sources (block iterators / readers / iterator
        # factories) are never materialized: the covariance runs as a
        # one-pass shifted accumulation at constant memory — the
        # reference's streamed mapPartitions contract
        # (RapidsRowMatrix.scala:170). jax.Array input is the
        # device-resident mode: the whole fit runs as ONE XLA program on
        # the array in place — no host round-trip, no float64 coercion
        # (the input path the reference cannot express: every JNI call
        # copies host arrays, rapidsml_jni.cu:112,179).
        self._device_x = None
        self._num_rows: Optional[int] = None
        self._num_cols: Optional[int] = None
        if is_device_array(rows):
            if rows.ndim != 2:
                raise ValueError(
                    f"device-array input must be 2-D (n, d), got shape {rows.shape}"
                )
            self.partitions: Optional[List[np.ndarray]] = None
            self._stream = None
            self._device_x = rows
            self._num_rows = int(rows.shape[0])
            self._num_cols = int(rows.shape[1])
        elif is_streaming_source(rows):
            self.partitions = None
            self._stream = rows
        else:
            # Host partitions are densified in the dtype their route
            # reads: float64 for the routes that compute on host float64
            # (dd's shift is an exact host-float64 subtract; the packed
            # route's native accumulator takes float64 and its no-native
            # fallback may route to dd), the compute dtype for every route
            # that places them on the device (per-partition GEMM, pallas,
            # mesh) — float32 on a chip without x64, so a float32 source
            # is not widened here and narrowed again at every placement.
            # A block already dense in that dtype IS the caller's buffer,
            # and the device then reads it directly. Every host route of a
            # fit ends with a host read of the eigensolve's result, which
            # depends on every placed partition, so no transfer is still
            # in flight when fit returns the buffers to the caller.
            host_f64 = self.precision == "dd" or not use_gemm
            self.partitions = dense_partitions(
                rows, dtype=np.float64 if host_f64 else np.dtype(self.dtype)
            )
            self._stream = None
        self.mean_centering = mean_centering
        self.use_gemm = use_gemm
        self.use_accel_svd = use_accel_svd
        self.device_id = device_id
        self.mesh = mesh
        if self.precision == "dd" and self._device_x is not None:
            raise ValueError(
                "precision='dd' is the host-streaming fp64 emulation; a "
                "device-resident jax.Array is already in its compute dtype "
                "— pass host partitions (or enable x64) for dd semantics"
            )
        if not use_gemm and self._device_x is not None:
            raise ValueError(
                "useGemm=False (the packed spr-layout path) consumes host "
                "partitions; device-resident input runs the fused GEMM "
                "covariance (useGemm=True)"
            )
        if self.precision == "dd" and mesh is not None:
            # dd composes with a mesh ONLY as the per-executor streaming
            # merge (each process runs the dd scan on its local blocks;
            # parallel.distributed.streaming_covariance_process_local) —
            # the GSPMD sharded-gram paths are f32 programs.
            if not (self.partitions is None and jax.process_count() > 1):
                raise ValueError(
                    "precision='dd' with a mesh requires the multi-process "
                    "streaming deployment (per-executor dd scans + moment "
                    "merge); single-process mesh fits use "
                    "precision='highest'"
                )
        # Covariance kernel backend for the GEMM path. The default "xla"
        # (whole-array fusion) over "pallas" (fused streaming: the
        # centered tile and accumulator stay in VMEM) and the XLA
        # scan-blocked path predates the chip and is not measured on it:
        # ROADMAP.md Design 6 / Design 14 (the cell pca_3000.host_parts
        # runs the default; no cell runs the kernel).
        if backend == "pallas":
            # The explicit kernel choice must never be silently dropped:
            # only the materialized single-device GEMM route consults it.
            if mesh is not None:
                raise ValueError("backend='pallas' has no mesh path; use 'xla'")
            if self.partitions is None and self._device_x is None:
                raise ValueError(
                    "backend='pallas' has no streaming path; use 'xla'"
                )
            if not use_gemm:
                raise ValueError(
                    "backend='pallas' applies to the GEMM path (useGemm=True)"
                )
        self.backend = backend
        if eigen_solver not in ("auto", "full", "topk"):
            raise ValueError(
                f"eigen_solver must be 'auto', 'full' or 'topk', got {eigen_solver!r}"
            )
        self.eigen_solver = eigen_solver
        if eigen_iters < 1:
            raise ValueError(f"eigen_iters must be >= 1, got {eigen_iters}")
        self.eigen_iters = int(eigen_iters)

    @staticmethod
    def resolve(precision: str, mesh=None, input_dtype=None, backend: str = "xla") -> str:
        """THE home of precision-request resolution (PCA calls this too —
        keep the policy in one place). ``input_dtype`` is the dtype of the
        RAW user container, probed by the caller before the rows are
        densified (core.data.infer_input_dtype). Without it, "auto" must
        not trust partitions[0].dtype (the dtype the resolved route reads,
        not the source's: the compute dtype, or float64 on the dd and
        packed routes) — it resolves to "highest" rather than silently
        routing every fit through the slow dd emulation. With a mesh,
        "auto" defers to the mesh covariance path (dd has no mesh route).
        Under ``backend="pallas"`` (an fp32-kernel choice), auto-resolved
        dd yields to "highest"; explicit dd is an error.
        """
        if backend not in ("xla", "pallas"):
            raise ValueError(f"backend must be 'xla' or 'pallas', got {backend!r}")
        if precision == "auto" and mesh is not None:
            return "highest"
        resolved = resolve_precision(precision, input_dtype=input_dtype)
        if backend == "pallas" and resolved == "dd":
            if precision == "dd":
                raise ValueError(
                    "precision='dd' has its own kernels; use backend='xla'"
                )
            return "highest"
        return resolved

    # --- shape (lazy, like numRows/numCols via count()/first(), :48-57) ---

    @property
    def num_rows(self) -> int:
        if self._num_rows is None:
            if self.partitions is None:
                raise RuntimeError(
                    "streaming input: shape is unknown until a fit pass runs"
                )
            self._num_rows = sum(p.shape[0] for p in self.partitions)
        return self._num_rows

    @property
    def num_cols(self) -> int:
        # A fit pass may have recorded the authoritative (global) width —
        # streaming sources discover it then, and multi-process fits must
        # not report a zero-row process's local width.
        if self._num_cols is not None:
            return self._num_cols
        if self.partitions is None:
            raise RuntimeError(
                "streaming input: shape is unknown until a fit pass runs"
            )
        return self.partitions[0].shape[1]

    @property
    def dtype(self):
        if self._dtype is not None:
            return self._dtype
        if self._device_x is not None:
            # Device-resident input computes in ITS dtype — no coercion.
            return self._device_x.dtype
        return jnp.float64 if jax.config.jax_enable_x64 else jnp.float32

    def _device(self):
        # local_devices, not devices: under a multi-process gang the global
        # list includes peers' non-addressable chips, and device_put to one
        # of those raises. Identical in single-process runs.
        devices = jax.local_devices()
        if self.device_id >= 0:
            return devices[self.device_id]
        return devices[0]

    # --- column stats (Statistics.colStats analogue, :156) ---

    def column_means(self) -> jnp.ndarray:
        """Column means in a pass of their own. Of the host routes only the
        packed route's no-native fallback still calls it; the GEMM route
        takes its means in the pass that makes the Gram
        (:meth:`_covariance_gemm`)."""
        if self._device_x is not None:
            with TraceRange("mean center", TraceColor.ORANGE):
                return jnp.mean(self._device_x, axis=0)
        if self.partitions is None:
            raise RuntimeError(
                "streaming input: column means are computed inside the "
                "one-pass covariance; use compute_covariance()"
            )
        with TraceRange("mean center", TraceColor.ORANGE):
            with StageRange("solve"):
                state = welford_init(self.num_cols, dtype=self.dtype)
            window = PlacementWindow()
            for part in self.partitions:
                blk = self._place_partition(part, jnp.asarray, window)
                with StageRange("solve"):
                    state = welford_add_block(state, blk)
            return state[1]

    def _place_partition(self, part: np.ndarray, put, window: PlacementWindow):
        """One host partition on the device in the compute dtype: the
        ``convert`` stage (the host conversion ``jnp.asarray(part,
        dtype=...)`` makes inside itself, taken out of it) and the
        ``place`` stage round ``put``, the route's placement call, made
        through the pass's ``window`` (one a pass: it bounds the placements
        in flight, ``core/ingest.py::PlacementWindow``). The
        GEMM and pallas routes hold their partitions in the compute dtype
        already (``__init__``), so ``convert`` hands the partition on as
        it is, and they come here once a partition; only the packed
        route's no-native fallback, whose partitions are float64, still
        narrows here without x64, in each of its two passes."""
        with StageRange("convert"):
            host = np.asarray(part, dtype=self.dtype)
        return window.place(host, put)

    # --- covariance (computeCovariance, :149-257) ---

    def compute_covariance(self) -> jnp.ndarray:
        if self._device_x is not None:
            return self._covariance_device()
        if self.partitions is None:
            return self._covariance_streaming()
        if not (self.mesh is not None and jax.process_count() > 1):
            # Multi-process fits validate the GLOBAL row count inside the
            # mesh path (after the counts allgather): a local pre-check
            # would kill a low-row executor while its peers deadlock in
            # the collective waiting for it.
            n = self.num_rows
            if n < 2:
                raise ValueError(f"need at least 2 rows, got {n}")
        with TraceRange("compute cov", TraceColor.RED):
            if self.mesh is not None:
                return self._covariance_mesh()[1]  # honors mean_centering
            if not self.use_gemm:
                # The explicitly requested packed path outranks auto-dd:
                # with the native runtime it is TRUE fp64 (never less
                # accurate than dd); its no-native fallback routes dd
                # itself when dd precision was resolved.
                return self._covariance_packed()
            if self.precision == "dd":
                return self._covariance_dd()
            return self._covariance_gemm()

    def _covariance_device(self) -> jnp.ndarray:
        """Covariance of a device-resident array — one fused XLA program,
        no host round-trip (the standalone-covariance sibling of
        :func:`_pca_fit_device`)."""
        x = self._device_array_on_mesh()
        n = self.num_rows
        if n < 2:
            raise ValueError(f"need at least 2 rows, got {n}")
        with TraceRange("compute cov", TraceColor.RED):
            if self.backend != "pallas" and self.mesh is None:
                count_resident_blocks(n)
                state = comoment_resident(
                    x, precision=self.precision, center=self.mean_centering
                )
                return state[3] / (n - 1)
            mean = (
                jnp.mean(x, axis=0)
                if self.mean_centering
                else jnp.zeros((self.num_cols,), dtype=x.dtype)
            )
            if self.backend == "pallas":
                from spark_rapids_ml_tpu.ops.pallas.covariance import (
                    centered_gram_pallas,
                )

                interpret = jax.default_backend() != "tpu"
                return centered_gram_pallas(x, mean, interpret=interpret) / (n - 1)
            # rows sharded over a mesh: one contraction a chip and XLA's
            # psum, until the four-chip cell (ROADMAP.md Reach 1)
            return centered_gram(x, mean, precision=self.precision) / (n - 1)

    def _device_array_on_mesh(self):
        """The device input honoring a configured mesh: with a mesh set,
        the array is placed row-sharded over the data axis (an explicit
        mesh choice must never be silently dropped — the same stance as
        the pallas guard above), so the fused program runs under GSPMD
        with its covariance psum riding ICI. Without a mesh the array
        computes wherever it lives."""
        x = self._device_x
        if self.mesh is None:
            return x
        from spark_rapids_ml_tpu.parallel.mesh import device_array_rows_on_mesh

        return device_array_rows_on_mesh(x, self.mesh)

    def _covariance_gemm(self) -> jnp.ndarray:
        """One pass over the host partitions, each placed once (:168-201
        needs the finished means first and makes two). Centred: every
        partition goes into a (count, means, Gram) state on the device by
        ``comoment_add_block``, its Gram centred on its own means.
        Uncentred: the partitions' raw Grams, added up."""
        device = self._device()
        use_pallas = self.backend == "pallas"
        # The interpreter covers non-TPU platforms (CI's CPU mesh).
        interpret = use_pallas and jax.default_backend() != "tpu"
        if use_pallas and not interpret and np.dtype(self.dtype) == np.float64:
            # Mosaic has no f64 MXU dot — fail clearly instead of at
            # kernel compile (reachable only with x64 forced on TPU).
            raise ValueError(
                "backend='pallas' compiles f32 kernels; disable x64 or "
                "pass dtype=jnp.float32 (or use backend='xla')"
            )
        put = _partial(jax.device_put, device=device)
        window = PlacementWindow()
        if self.mean_centering:
            bump_counter("rowmatrix.cov.one_pass")
            with StageRange("solve", TraceColor.GREEN):
                state = comoment_init(self.num_cols, dtype=self.dtype)
            for part in self.partitions:
                blk = self._place_partition(part, put, window)
                with StageRange("solve", TraceColor.GREEN):
                    state = comoment_add_block(
                        state,
                        blk,
                        precision=self.precision,
                        backend=self.backend,
                        interpret=interpret,
                    )
            acc = state[3]
        else:
            if use_pallas:
                from spark_rapids_ml_tpu.ops.pallas.covariance import (
                    centered_gram_pallas,
                )
            acc = None
            mean = jnp.zeros(self.num_cols, dtype=self.dtype)
            for part in self.partitions:
                blk = self._place_partition(part, put, window)
                with StageRange("solve", TraceColor.GREEN):
                    if use_pallas:
                        gram = centered_gram_pallas(blk, mean, interpret=interpret)
                    else:
                        gram = centered_gram(blk, mean, precision=self.precision)
                    acc = gram if acc is None else acc + gram
        with StageRange("solve", TraceColor.GREEN):
            return acc / (self.num_rows - 1)

    @staticmethod
    def _native_spr_covariance(blocks, center: bool):
        """Stream dense host blocks through the native fp64 Kahan
        accumulator; returns ``(cov fp64 UNCAST, n_rows)``. ONE home for
        the cap/accumulate/finalize sequence shared by the materialized
        packed path and its streaming twin — the uncast return is the
        contract that keeps the fp64 accuracy through the eigensolve on
        no-x64 platforms."""
        from spark_rapids_ml_tpu import native

        acc = None
        for b in blocks:
            if b.shape[0] == 0:
                continue
            if acc is None:
                if b.shape[1] > 65535:
                    raise ValueError(
                        f"packed path caps features at 65535, got {b.shape[1]}"
                    )
                acc = native.SprAccumulator(b.shape[1])
            acc.add_block(b)
        if acc is None:
            raise ValueError("need at least 2 rows to compute a covariance, got 0")
        cov, _ = acc.finalize(center=center)
        return cov, int(acc.n_rows)

    def _covariance_packed(self) -> jnp.ndarray:
        """Packed-upper aggregation path (spr/treeAggregate, :202-251).

        Keeps the reference's n ≤ 65535 wire-format constraint (:66-68).
        When the native host runtime is present, this runs as a true-fp64
        Kahan-compensated streaming accumulation in C++ (the reference's
        all-``double[]`` numerics bar, independent of jax_enable_x64);
        otherwise it falls back to jitted packed Gram accumulation. Both
        compute their own column means in a single pass — no separate
        Welford sweep.
        """
        n_cols = self.num_cols
        if n_cols > 65535:
            raise ValueError(f"packed path caps features at 65535, got {n_cols}")
        from spark_rapids_ml_tpu import native

        if native.available():
            cov, _ = self._native_spr_covariance(
                iter(self.partitions), self.mean_centering
            )
            return cov
        if self.precision == "dd":
            # No native runtime: the packed layout is a compatibility shim
            # here; dd precision still needs the dd kernels.
            return self._covariance_dd()
        mean = (
            self.column_means()
            if self.mean_centering
            else jnp.zeros(n_cols, dtype=self.dtype)
        )
        acc = None
        window = PlacementWindow()
        for part in self.partitions:
            blk = self._place_partition(part, jnp.asarray, window)
            with StageRange("solve"):
                packed = centered_gram_packed(blk, mean)
                acc = packed if acc is None else acc + packed
        with StageRange("solve"):
            full = triu_to_full(acc)
            return full / (self.num_rows - 1)

    def _covariance_streaming(self) -> jnp.ndarray:
        """Constant-memory covariance over a streaming block source: one
        pass, one block resident at a time (shifted accumulation). Records
        the shape discovered during the pass. With a mesh, each block is
        row-sharded over the data axis and the Gram accumulates replicated
        on device (one psum per block over ICI) — the streamed
        deployment loop (no cell yet: ROADMAP.md Reach 5)."""
        blocks = iter_stream_blocks(self._stream)
        if self.mesh is not None:
            if jax.process_count() > 1:
                # Executor model: each process streams ITS local blocks on
                # its own chip; one allgather merges the O(d^2) moments —
                # the reference's partition-local compute + cross-process
                # reduce, at constant memory per executor.
                from spark_rapids_ml_tpu.parallel.distributed import (
                    streaming_covariance_process_local,
                )

                with TraceRange("compute cov (stream, multiproc)", TraceColor.RED):
                    # merge="auto": non-dd moments merge as a psum riding
                    # ICI (the mesh is the fabric); dd stays on the exact
                    # fp64 host allgather.
                    _, cov, n = streaming_covariance_process_local(
                        blocks,
                        center=self.mean_centering,
                        dtype=self.dtype,
                        precision=self.precision,
                        mesh=self.mesh,
                    )
                if self.precision == "dd":
                    # Keep the exact-fp64 host covariance — a device-dtype
                    # cast (f32 without x64) would destroy the accuracy
                    # this combination exists to provide.
                    self._num_rows = int(n)
                    self._num_cols = int(cov.shape[0])
                    return cov
            else:
                from spark_rapids_ml_tpu.ops.covariance import (
                    streaming_mean_and_covariance_mesh,
                )

                with TraceRange("compute cov (stream, mesh)", TraceColor.RED):
                    _, cov, n = streaming_mean_and_covariance_mesh(
                        blocks,
                        self.mesh,
                        center=self.mean_centering,
                        dtype=self.dtype,
                        precision=self.precision,
                    )
            self._num_rows = int(n)
            self._num_cols = int(cov.shape[0])
            return jnp.asarray(cov, dtype=self.dtype)
        if not self.use_gemm:
            # Packed-path semantics for streams: the native fp64 Kahan
            # accumulator (tpuml_host.cpp) consumes blocks one at a time —
            # true fp64 at constant memory, the streamed twin of the
            # materialized spr path (RapidsRowMatrix.scala:202-251).
            from spark_rapids_ml_tpu import native

            if native.available():
                with TraceRange("compute cov (stream, native spr)", TraceColor.RED):
                    from spark_rapids_ml_tpu.core.data import _block_to_dense

                    cov, n = self._native_spr_covariance(
                        (_block_to_dense(blk) for blk in blocks),
                        self.mean_centering,
                    )
                self._num_rows = n
                self._num_cols = int(cov.shape[0])
                return cov
            # No native runtime: fall through to the jitted streaming path.
        with TraceRange("compute cov (stream)", TraceColor.RED):
            if self.precision == "dd":
                from spark_rapids_ml_tpu.ops.doubledouble import (
                    covariance_dd_blocks,
                )

                _, cov, n = covariance_dd_blocks(
                    blocks, center=self.mean_centering
                )
                self._num_rows = int(n)
                self._num_cols = int(cov.shape[0])
                # Keep the exact-fp64 host array: casting to the device
                # dtype (fp32 on no-x64 platforms) before the host
                # eigensolve would throw away the dd accuracy.
                return cov
            _, cov, n = streaming_mean_and_covariance(
                blocks,
                center=self.mean_centering,
                dtype=self.dtype,
                precision=self.precision,
            )
        self._num_rows = int(n)
        self._num_cols = int(cov.shape[0])
        return jnp.asarray(cov, dtype=self.dtype)

    def _covariance_dd(self) -> np.ndarray:
        """Double-float fp64-emulated covariance (ops.doubledouble): the
        reference's ``double[]`` numerics (JniRAPIDSML.java:64-69) on fp32
        hardware. ONE streaming pass over the partitions (shifted
        accumulation); fp64 host accumulation of per-block
        extended-precision Gram partials."""
        from spark_rapids_ml_tpu.ops.doubledouble import covariance_dd_blocks

        with TraceRange("dd gemm", TraceColor.GREEN):
            _, cov, _ = covariance_dd_blocks(
                self.partitions, center=self.mean_centering
            )
        return cov

    def _covariance_mesh(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Whole-fit-as-one-XLA-program path over a device mesh.

        Placement is per-shard (shard_rows_from_partitions): the host never
        materializes the concatenated dataset, only one device shard at a
        time. In a multi-process deployment (one process per chip,
        parallel.distributed.initialize), each process contributes its
        LOCAL partitions and the global array is assembled across
        processes — the reference's executor-local partitions + cross-
        process reduce (RapidsRowMatrix.scala:170-201)."""
        import jax as _jax

        if _jax.process_count() > 1:
            from spark_rapids_ml_tpu.parallel.distributed import (
                shard_rows_process_local,
            )

            xs, mask, n_global, d = shard_rows_process_local(
                self.partitions, self.mesh, dtype=np.dtype(self.dtype)
            )
            # Shape facts must be GLOBAL after a distributed placement (a
            # process may hold zero local rows), and the <2 check happens
            # here — consistently on every process, after the allgather.
            # ``d`` is the TRUE width (2-D meshes zero-pad features to the
            # model axis; the padded columns are stripped below).
            self._num_rows = int(n_global)
            self._num_cols = d
            if n_global < 2:
                raise ValueError(f"need at least 2 rows, got {n_global}")
        else:
            d = self.num_cols
            xs, mask, _ = shard_rows_from_partitions(
                self.partitions, self.mesh, dtype=np.dtype(self.dtype)
            )
        mean, cov = distributed_mean_and_covariance(
            xs, mask, self.mesh, precision=self.precision, center=self.mean_centering
        )
        # Strip model-axis feature padding (padded columns are exactly zero).
        return mean[:d], cov[:d, :d]

    # --- PCA (computePrincipalComponentsAndExplainedVariance, :75-125) ---

    def compute_principal_components_and_explained_variance(
        self, k: int
    ) -> Tuple[np.ndarray, np.ndarray]:
        # Validate k before the expensive pass when the shape is known
        # up front. Streaming sources learn d only during the pass, and a
        # multi-process fit only learns the GLOBAL width from the
        # placement allgather (a zero-row executor has no local width).
        if self._device_x is not None and self.use_accel_svd and self.backend != "pallas":
            # Device-resident fused fit: one XLA program end to end.
            n, n_cols = self.num_rows, self.num_cols
            if n < 2:
                raise ValueError(f"need at least 2 rows, got {n}")
            if not 1 <= k <= n_cols:
                raise ValueError(f"k must be in [1, {n_cols}], got {k}")
            blocked = self.mesh is None
            if blocked:
                count_resident_blocks(n)
            with TraceRange("fused device fit", TraceColor.RED), StageRange("solve"):
                u, explained = _pca_fit_device(
                    self._device_array_on_mesh(),
                    k,
                    center=self.mean_centering,
                    precision=self.precision,
                    eigen_solver=self.eigen_solver,
                    eigen_iters=self.eigen_iters,
                    blocked=blocked,
                )
            return u, explained  # device arrays — the caller decides on host
        shape_known = (
            self.partitions is not None or self._device_x is not None
        ) and not (self.mesh is not None and jax.process_count() > 1)
        if shape_known:
            n_cols = self.num_cols
            if not 1 <= k <= n_cols:
                raise ValueError(f"k must be in [1, {n_cols}], got {k}")
        cov = self.compute_covariance()
        n_cols = self.num_cols
        if not shape_known and not 1 <= k <= n_cols:
            raise ValueError(f"k must be in [1, {n_cols}], got {k}")
        # Host-exact fp64 covariances (dd emulation, or the native Kahan
        # accumulator's packed/streamed paths): a device eigensolve would
        # round them to fp32 on a no-x64 platform — host LAPACK/ARPACK
        # keeps the fp64 accuracy end to end (d x d only, off the critical
        # data path). With x64 on, the device solve is equally exact and
        # keeps useCuSolverSVD semantics.
        host_f64_cov = isinstance(cov, np.ndarray) and cov.dtype == np.float64 and not (
            jax.config.jax_enable_x64
        )
        if self.precision == "dd" or host_f64_cov:
            # An explicit topk request is honored at fp64 via ARPACK
            # rather than silently ignored ("auto" stays with the exact
            # host solve: the fp64 path exists for accuracy, not speed).
            if self.eigen_solver == "topk" and k < n_cols:
                with TraceRange("host fp64 topk", TraceColor.BLUE), StageRange("solve"):
                    w_k, u_k = eigh_topk_host(np.asarray(cov), k)
                    w_k = np.clip(w_k, 0, None)
                    total = float(np.trace(np.asarray(cov)))
                    explained = w_k / total if total > 0 else w_k
                    return u_k, explained
            with TraceRange("host fp64 SVD", TraceColor.BLUE), StageRange("solve"):
                w, u = eigh_descending_host(np.asarray(cov))
        elif self.eigen_solver == "topk" and k < n_cols:
            # Subspace iteration + Rayleigh-Ritz: O(d^2 k) MXU matmuls
            # instead of the full O(d^3) eigensolve — exact explained-
            # variance RATIOS come from the trace, so nothing is lost.
            with TraceRange("topk eigh", TraceColor.BLUE), StageRange("solve"):
                w_k, u_k = eigh_topk(jnp.asarray(cov), k, iters=self.eigen_iters)
                w_k = np.clip(np.asarray(w_k), 0, None)
                total = float(np.trace(np.asarray(cov)))
                explained = w_k / total if total > 0 else w_k
                return np.asarray(u_k), explained
        elif self.eigen_solver == "auto" and k < n_cols and self.use_accel_svd:
            # Self-selecting: subspace iteration that promotes itself to
            # the full eigensolver when the spectrum defeats it (eigh_auto).
            with TraceRange("auto eigh", TraceColor.BLUE), StageRange("solve"):
                w_k, u_k, _ = eigh_auto(
                    jnp.asarray(cov), k, max_iters=auto_max_iters(self.eigen_iters)
                )
                w_k = np.clip(np.asarray(w_k), 0, None)
                total = float(np.trace(np.asarray(cov)))
                explained = w_k / total if total > 0 else w_k
                return np.asarray(u_k), explained
        elif self.use_accel_svd:
            with TraceRange("xla SVD", TraceColor.BLUE), StageRange("solve"):
                w, u = eigh_descending(cov)
                u, w = np.asarray(u), np.asarray(w)
        else:
            with TraceRange("cpu SVD", TraceColor.BLUE), StageRange("solve"):
                # Host LAPACK SVD — the breeze brzSvd analogue (:110-123).
                # For symmetric PSD cov the singular values ARE eigenvalues.
                u, w, _ = np.linalg.svd(np.asarray(cov, dtype=np.float64))
                u = np.asarray(sign_flip(u))
        # Explained variance ratio is eigenvalue-proportional: λ_i / Σλ. The
        # reference normalizes sqrt-eigenvalues (RapidsRowMatrix.scala:101-102
        # via calSVD's seqRoot) — a quirk not copied; the mllib oracle uses λ.
        w = np.clip(w, 0, None)
        total = w.sum()
        explained = w / total if total > 0 else w
        if k < n_cols:
            return u[:, :k], explained[:k]
        return u, explained

"""KMeans estimator/model — Spark ML surface, XLA compute.

Param surface mirrors ``org.apache.spark.ml.clustering.KMeans``:
``k``, ``initMode`` ("k-means||" or "random"), ``maxIter``, ``tol``,
``seed``, ``distanceMeasure`` ("euclidean" | "cosine"), ``featuresCol``,
``predictionCol``. This is a beyond-the-reference capability (its cell:
kmeans_3000_k1000.device_rows); the reference repo ships only PCA, so the oracle for tests is
scipy/numpy Lloyd rather than a reference file.

"k-means||" routes to on-device k-means++ (the sequential D^2 sampler is
exact; Spark's parallel variant is an approximation of it designed for
multi-pass RDD scans that a jitted fori_loop doesn't need).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_ml_tpu.core.data import (
    DataFrame,
    extract_features,
    extract_weights,
    is_device_array,
    is_streaming_source,
)
from spark_rapids_ml_tpu.core.estimator import Estimator, Model
from spark_rapids_ml_tpu.core.ingest import matrix_like, prepare_rows
from spark_rapids_ml_tpu.core.lazy_state import LazyHostState
from spark_rapids_ml_tpu.core.params import Param, Params, gt, toFloat, toInt, toString
from spark_rapids_ml_tpu.core.persistence import (
    MLReadable,
    get_and_set_params,
    load_metadata,
    load_rows,
    save_metadata,
    save_rows,
)
from spark_rapids_ml_tpu.ops.kmeans import (
    assign_clusters,
    kmeans_plusplus_init,
    lloyd,
    lloyd_resumable,
    normalize_rows,
    random_init,
)
from spark_rapids_ml_tpu.core.serving import (
    note_device_cache,
    serve_blocks,
    serve_rows,
    stream_block_rows,
)
from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS
from spark_rapids_ml_tpu.utils.tracing import TraceColor, TraceRange


def _assign_kernel(x, centers, *, cosine: bool, precision: str = "highest"):
    """Serving kernel: nearest-center labels. Centers follow the batch
    dtype (the model-side cast fuses into the distance GEMM); zero padding
    rows normalize to NaN under cosine but assignments are row-wise, so
    they never reach a real row's label. ``precision`` is the resolved
    serving-family policy mode (ops/precision.py) — part of the static
    dict, so it keys the AOT program cache."""
    centers = centers.astype(x.dtype)
    if cosine:
        x = normalize_rows(x)
        centers = normalize_rows(centers)
    labels, _ = assign_clusters(x, centers, precision=precision)
    return labels


class _KMeansParams(Params):
    k = Param("_", "k", "number of clusters", lambda v: gt(1)(toInt(v)))
    initMode = Param("_", "initMode", "initialization: k-means|| or random", toString)
    maxIter = Param("_", "maxIter", "maximum Lloyd iterations", toInt)
    tol = Param("_", "tol", "center-movement convergence tolerance", toFloat)
    seed = Param("_", "seed", "random seed", toInt)
    distanceMeasure = Param("_", "distanceMeasure", "euclidean or cosine", toString)
    featuresCol = Param("_", "featuresCol", "features column name", toString)
    predictionCol = Param("_", "predictionCol", "prediction column name", toString)
    weightCol = Param("_", "weightCol", "per-row weight column name", toString)
    precision = Param(
        "_", "precision",
        "matmul precision for the Lloyd GEMMs: highest/f32 (6 bf16 passes, "
        "the reference-parity default) | high (3-pass f32-grade) | bf16x3 "
        "(3-pass compensated split, ops/precision.py, max rel err <= 2e-4) "
        "| default/bf16 (1 bf16 pass — bf16-rounded distances flip only "
        "Voronoi-boundary assignments; measured cost delta ~1e-4 relative "
        "at 20Mx16 k=100). Unset, the TPUML_PRECISION[_KMEANS] knobs and "
        "committed autotune decisions apply (resolve_policy layering).",
        toString,
    )
    backend = Param(
        "_", "backend",
        "Lloyd kernel: auto | fused (pallas assignment+stats, zero (n,k) "
        "HBM temporaries) | xla (whole-array fusion)",
        toString,
    )

    def __init__(self, uid: Optional[str] = None):
        super().__init__(uid)
        self._setDefault(
            k=2,
            initMode="k-means||",
            maxIter=20,
            tol=1e-4,
            seed=0,
            distanceMeasure="euclidean",
            featuresCol="features",
            predictionCol="prediction",
            precision="highest",
            backend="auto",
        )

    def getK(self) -> int:
        return self.getOrDefault(self.k)

    def getInitMode(self) -> str:
        return self.getOrDefault(self.initMode)

    def getMaxIter(self) -> int:
        return self.getOrDefault(self.maxIter)

    def getTol(self) -> float:
        return self.getOrDefault(self.tol)

    def getSeed(self) -> int:
        return self.getOrDefault(self.seed)

    def getDistanceMeasure(self) -> str:
        return self.getOrDefault(self.distanceMeasure)

    def getFeaturesCol(self) -> str:
        return self.getOrDefault(self.featuresCol)

    def getPredictionCol(self) -> str:
        return self.getOrDefault(self.predictionCol)

    def getWeightCol(self) -> Optional[str]:
        return (
            self.getOrDefault(self.weightCol)
            if self.isDefined(self.weightCol)
            else None
        )

    def getPrecision(self) -> str:
        return self.getOrDefault(self.precision)

    def getBackend(self) -> str:
        return self.getOrDefault(self.backend)


class KMeans(_KMeansParams, Estimator, MLReadable):
    """``KMeans().setK(8).fit(x)`` — Lloyd on the MXU."""

    # Consumes device arrays in place (prepare_rows), so tuning loops may
    # feed device-resident fold slices (tuning._device_fold_prep).
    _device_foldable = True

    def __init__(self, uid: Optional[str] = None, mesh=None):
        super().__init__(uid)
        self.mesh = mesh

    def setK(self, value: int) -> "KMeans":
        self.set(self.k, value)
        return self

    def setInitMode(self, value: str) -> "KMeans":
        if value not in ("k-means||", "random"):
            raise ValueError(f"initMode must be 'k-means||' or 'random', got {value!r}")
        self.set(self.initMode, value)
        return self

    def setMaxIter(self, value: int) -> "KMeans":
        self.set(self.maxIter, value)
        return self

    def setTol(self, value: float) -> "KMeans":
        self.set(self.tol, value)
        return self

    def setSeed(self, value: int) -> "KMeans":
        self.set(self.seed, value)
        return self

    def setDistanceMeasure(self, value: str) -> "KMeans":
        if value not in ("euclidean", "cosine"):
            raise ValueError(f"distanceMeasure must be 'euclidean' or 'cosine', got {value!r}")
        self.set(self.distanceMeasure, value)
        return self

    def setFeaturesCol(self, value: str) -> "KMeans":
        self.set(self.featuresCol, value)
        return self

    def setPredictionCol(self, value: str) -> "KMeans":
        self.set(self.predictionCol, value)
        return self

    def setWeightCol(self, value: str) -> "KMeans":
        self.set(self.weightCol, value)
        return self

    def setMesh(self, mesh) -> "KMeans":
        self.mesh = mesh
        return self

    def setPrecision(self, value: str) -> "KMeans":
        from spark_rapids_ml_tpu.ops.precision import validate_mode

        self.set(self.precision, validate_mode(value))
        return self

    def setBackend(self, value: str) -> "KMeans":
        """``"fused"`` computes in float32 (the pallas kernel's dtype) —
        an explicit request downcasts float64 input; ``"auto"`` never
        does (f64 fits keep the XLA path)."""
        if value not in ("auto", "fused", "xla"):
            raise ValueError(f"backend must be auto/fused/xla, got {value!r}")
        self.set(self.backend, value)
        return self

    def setInitialModel(self, value) -> "KMeans":
        """Warm start: begin Lloyd from an existing model's centers (or a
        raw (k, d) array) instead of k-means++/random seeding — the
        resume-after-interruption / refine-a-checkpoint path (mllib's
        ``setInitialModel``, cuML's init array). ``k`` must match."""
        centers = value.clusterCenters() if hasattr(value, "clusterCenters") else value
        centers = np.asarray(centers, dtype=np.float64)
        if centers.ndim != 2:
            # Validate BEFORE assigning: a raising setter must not leave
            # the estimator holding a malformed warm start.
            raise ValueError("initial model/centers must be a (k, d) matrix")
        self._initial_centers = centers
        return self

    _initial_centers = None
    _copy_attrs = ("_initial_centers",)  # survives Params.copy (tuning grids)

    def _fit(self, dataset: Any) -> "KMeansModel":
        rows = _extract_features(dataset, self.getFeaturesCol())
        w_host = extract_weights(dataset, self.getWeightCol())
        if is_streaming_source(rows):
            return self._fit_streaming(rows)
        from spark_rapids_ml_tpu.core import membudget

        # Budgeted admission (core/membudget.py): an over-budget host
        # input reroutes to the SAME _fit_streaming an explicit streaming
        # source takes — bit-identical by construction — and a device OOM
        # mid-fit reclaims caches and takes the same exit.
        can_stream = w_host is None and self.getBackend() != "fused"
        guard = membudget.fit_memory_guard(
            "kmeans", rows, can_stream=can_stream,
            why_cannot_stream="the streaming KMeans path supports neither "
                              "weightCol nor backend='fused'",
            mesh=self.mesh, ledger_families=("kmeans",),
        )
        if guard.degrade:
            return membudget.run_streaming_with_recovery(
                "kmeans", self._fit_streaming, guard.matrix
            )
        fallback = (
            (lambda: membudget.run_streaming_with_recovery(
                "kmeans", self._fit_streaming, membudget.host_matrix(rows)))
            if can_stream and self.mesh is None else None
        )
        return membudget.run_fit_with_oom_recovery(
            "kmeans", lambda: self._fit_in_memory(rows, w_host), fallback
        )

    def _train_precision(self) -> str:
        """Resolve the fit-time GEMM policy (ops/precision.py): an
        explicit ``setPrecision`` wins, then the TPUML_PRECISION[_KMEANS]
        knobs, then a committed autotune decision; otherwise the param's
        default ('highest') stands — bit-identical to the pre-policy
        behavior."""
        from spark_rapids_ml_tpu.ops.precision import resolve_policy

        requested = self.getPrecision() if self.isSet(self.precision) else None
        return resolve_policy("kmeans", requested, default=self.getPrecision())

    def _fit_in_memory(self, rows: Any, w_host) -> "KMeansModel":
        k = self.getK()
        cosine = self.getDistanceMeasure() == "cosine"
        precision = self._train_precision()
        key = jax.random.key(self.getSeed())

        with TraceRange("kmeans fit", TraceColor.CYAN):
            # One funnel for every residence: a jax.Array fits IN PLACE (no
            # host round trip), host data places once.
            xs, mask, n, d = prepare_rows(rows, mesh=self.mesh, weights=w_host)
            if k > n:
                raise ValueError(f"k={k} exceeds number of rows {n}")
            dtype = xs.dtype
            if cosine:
                # Zero out padding via the mask's SUPPORT, not its value —
                # fractional weights must not rescale the unit vectors.
                xs = normalize_rows(xs) * (mask > 0).astype(dtype)[:, None]
            if self._initial_centers is not None:
                if self._initial_centers.shape[0] != k:
                    raise ValueError(
                        f"initial model has {self._initial_centers.shape[0]} "
                        f"centers but k={k}"
                    )
                if self._initial_centers.shape[1] != d:
                    raise ValueError(
                        f"initial centers have {self._initial_centers.shape[1]} "
                        f"features but the data has {d}"
                    )
                init = jnp.asarray(
                    np.pad(
                        self._initial_centers,
                        ((0, 0), (0, xs.shape[1] - d)),
                    ),
                    dtype=dtype,
                )
                if cosine:
                    init = normalize_rows(init)
            elif self.getInitMode() == "random":
                # No mesh padding and no weights => every row real: the
                # seeding can use the hardware approximate top-k.
                init = random_init(
                    xs, mask, key, k,
                    assume_unmasked=self.mesh is None and w_host is None,
                )
            else:
                init = kmeans_plusplus_init(xs, mask, key, k)
            # Preemption tolerance (robustness/checkpoint.py): with the
            # TPUML_CHECKPOINT_* knobs set, Lloyd runs segmented with
            # async snapshots and resumes mid-solve from the latest valid
            # checkpoint — except under an EXPLICIT backend='fused'
            # request, whose pallas kernel has no externalized state.
            ckpt = (
                self._fit_checkpointer("kmeans.lloyd", data=(xs, mask, init))
                if self.getBackend() != "fused"
                else None
            )
            if ckpt is not None:
                shards = self.mesh.shape[DATA_AXIS] if self.mesh is not None else 1
                centers, cost, n_iter = lloyd_resumable(
                    xs, mask, init, ckpt,
                    max_iter=self.getMaxIter(), tol=self.getTol(),
                    cosine=cosine, data_shards=shards,
                    precision=precision, mesh=self.mesh,
                )
                from spark_rapids_ml_tpu.parallel.distributed import (
                    replicate_for_host,
                )

                centers = replicate_for_host(self.mesh, centers)
                model = KMeansModel(
                    self.uid, centers[:, :d], trainingCost=cost, numIter=n_iter
                )
                return self._copyValues(model)
            backend = self._resolve_backend(
                w_host, int(xs.shape[0]) * k, d=int(xs.shape[1]), k=k,
                dtype=xs.dtype,
            )
            if backend == "fused":
                # Pallas fused assignment+stats: the (n, k) distance and
                # one-hot temporaries never touch HBM.
                # Requires a uniform mask (no weightCol) and one device —
                # _resolve_backend guarantees both.
                from spark_rapids_ml_tpu.ops.pallas.kmeans import (
                    auto_block_n,
                    lloyd_fused,
                    packed_feasible,
                    pad_transposed,
                )

                # Lane packing: small d x small k shares one MXU tile
                # across P row blocks; it wants its own block alignment.
                packed = packed_feasible(int(xs.shape[1]), k)
                bn = auto_block_n(int(xs.shape[1]), k, packed=packed)
                xt, _ = pad_transposed(xs.astype(jnp.float32), block_n=bn)
                centers, cost, n_iter = lloyd_fused(
                    xt,
                    int(xs.shape[0]),
                    init.astype(jnp.float32),
                    max_iter=self.getMaxIter(),
                    tol=self.getTol(),
                    block_n=bn,
                    precision=precision,
                    cosine=cosine,
                    # Explicit backend='fused' off-TPU runs the pallas
                    # interpreter (tests); auto never routes here off-TPU.
                    interpret=jax.default_backend() != "tpu",
                    packed=packed,
                )
            else:
                shards = self.mesh.shape[DATA_AXIS] if self.mesh is not None else 1
                centers, cost, n_iter = lloyd(
                    xs, mask, init, max_iter=self.getMaxIter(), tol=self.getTol(),
                    cosine=cosine, data_shards=shards,
                    precision=precision,
                )

        # Gang fits can hand back sharded results; host reads (the model's
        # lazy float64 pulls) need them fully replicated on every member.
        from spark_rapids_ml_tpu.parallel.distributed import replicate_for_host

        centers = replicate_for_host(self.mesh, centers)
        # Strip model-axis feature padding (device slice, stays async);
        # host float64 conversion happens lazily inside KMeansModel.
        model = KMeansModel(
            self.uid,
            centers[:, :d],
            trainingCost=cost,
            numIter=n_iter,
        )
        return self._copyValues(model)

    # Fused-kernel auto threshold: below this n*k the whole fit is
    # sub-millisecond either way and the extra transposed copy + pallas
    # compile isn't worth it.
    _FUSED_AUTO_WORK = 1 << 22

    def _resolve_backend(
        self, w_host, work: int, d: int = 1, k: int = 2, dtype=None
    ) -> str:
        """Pick the Lloyd kernel. "fused" needs a uniform row weight (the
        kernel streams no mask — padding is corrected in closed form) and
        a single-device layout; explicit requests that can't be honored
        raise rather than silently fall back. "auto" takes fused for
        eligible large fits and keeps the XLA path for small ones (no
        extra transposed copy/compile): a route that predates the chip
        and is not measured on it. The benchmark's KMeans cell lies on
        the XLA side (d=3000 is not fused_feasible); the narrow cell of
        ROADMAP.md Reach 10 decides it (Design 14)."""
        from spark_rapids_ml_tpu.ops.pallas.kmeans import fused_feasible

        requested = self.getBackend()
        blockers = []
        if self.mesh is not None:
            blockers.append("a mesh")
        if w_host is not None:
            blockers.append("weightCol")
        if not fused_feasible(d, k):
            blockers.append(f"d={d} x k={k} (VMEM residents exceed budget)")
        if requested == "fused":
            if blockers:
                raise ValueError(
                    "backend='fused' does not support " + ", ".join(blockers)
                )
            # An EXPLICIT fused request accepts the kernel's documented
            # f32 compute (the setter docs say so) even for f64 input.
            return "fused"
        if dtype is not None and np.dtype(dtype) == np.float64:
            # auto must not silently downcast x64 input to the f32 kernel
            # — precision='highest' on f64 means the f64 XLA path.
            blockers.append("float64 input")
        if requested == "xla" or blockers:
            return "xla"
        # auto: the pallas kernel is TPU-compiled; other platforms would
        # run the (slow) interpreter, so they keep the XLA path.
        if jax.default_backend() != "tpu":
            return "xla"
        return "fused" if work >= self._FUSED_AUTO_WORK else "xla"

    # Seeding-sample reservoir size for streaming fits: big enough that
    # k-means++ on the sample seeds like k-means++ on the data, bounded so
    # the sample never dominates memory.
    _STREAM_SAMPLE_CAP = 4096

    def _fit_streaming(self, rows) -> "KMeansModel":
        """Re-iterable block sources (iterator factory / NpyBlockReader):
        one full data pass per Lloyd iteration at O(block + k*d) memory —
        the multi-pass twin of the streamed PCA sketch.
        Seeding runs k-means++ (or random) on a one-pass uniform reservoir.
        """
        from spark_rapids_ml_tpu.core.data import (
            is_reiterable_stream,
            iter_stream_blocks,
        )
        from spark_rapids_ml_tpu.core.ingest import default_dtype
        from spark_rapids_ml_tpu.ops.kmeans import (
            lloyd_streaming,
            reservoir_sample_rows,
        )

        if not is_reiterable_stream(rows):
            raise ValueError(
                "KMeans is multi-pass: a streaming fit needs a RE-ITERABLE "
                "source (a zero-arg iterator factory or a block reader with "
                ".iter_blocks()), not a one-shot generator"
            )
        if self.mesh is not None:
            raise ValueError(
                "streaming KMeans is single-device; pass host partitions "
                "for a mesh fit"
            )
        k = self.getK()
        cosine = self.getDistanceMeasure() == "cosine"
        dtype = np.dtype(default_dtype())
        with TraceRange("kmeans stream fit", TraceColor.CYAN):
            if self._initial_centers is not None:
                # Warm start: no sampling pass — validate the feature
                # width against ONE peeked block (the in-memory path's
                # clear error, not an opaque matmul shape failure) and
                # trust k from the supplied centers.
                from spark_rapids_ml_tpu.core.data import peek_stream_width

                if self._initial_centers.shape[0] != k:
                    raise ValueError(
                        f"initial model has {self._initial_centers.shape[0]} "
                        f"centers but k={k}"
                    )
                width = peek_stream_width(rows)
                if self._initial_centers.shape[1] != width:
                    raise ValueError(
                        f"initial centers have {self._initial_centers.shape[1]} "
                        f"features but the data has {width}"
                    )
                init = jnp.asarray(self._initial_centers, dtype=dtype)
                if cosine:
                    init = normalize_rows(init)
            else:
                cap = max(self._STREAM_SAMPLE_CAP, 4 * k)
                sample, n_seen = reservoir_sample_rows(
                    iter_stream_blocks(rows), cap, self.getSeed(), dtype=dtype
                )
                if k > n_seen:
                    raise ValueError(f"k={k} exceeds number of rows {n_seen}")
                xs = jnp.asarray(sample)
                if cosine:
                    xs = normalize_rows(xs)
                mask = jnp.ones(xs.shape[0], dtype=xs.dtype)
                key = jax.random.key(self.getSeed())
                if self.getInitMode() == "random":
                    init = random_init(xs, mask, key, k)
                else:
                    init = kmeans_plusplus_init(xs, mask, key, k)
            centers, cost, n_iter = lloyd_streaming(
                lambda: iter_stream_blocks(rows),
                init,
                max_iter=self.getMaxIter(),
                tol=self.getTol(),
                precision=self._train_precision(),
                cosine=cosine,
                dtype=dtype,
            )
        model = KMeansModel(
            self.uid, centers, trainingCost=cost, numIter=n_iter
        )
        return self._copyValues(model)


# Shared extraction convention; re-exported name kept for back-compat.
_extract_features = extract_features


class KMeansModel(_KMeansParams, Model, LazyHostState):
    """Fitted model: ``clusterCenters()`` (k, d), prediction via transform.

    Fitted state may be host numpy OR live jax.Arrays from a device-
    resident fit; host float64 views convert lazily and pickling
    materializes host state (core/lazy_state.LazyHostState)."""

    _lazy_host_fields = {"_centers_raw": ("_centers_np", np.float64)}
    _pickle_clear = ("_centers_dev",)

    def __init__(
        self,
        uid: Optional[str] = None,
        clusterCenters: Optional[np.ndarray] = None,
        trainingCost: float = float("nan"),
        numIter: int = 0,
    ):
        super().__init__(uid)
        self._centers_raw = clusterCenters
        self._centers_np: Optional[np.ndarray] = None
        self._centers_dev = None
        self._cost_raw = trainingCost
        self._iter_raw = numIter

    def __getstate__(self):
        state = super().__getstate__()
        state["_cost_raw"] = self.trainingCost
        state["_iter_raw"] = self.numIter
        return state

    @property
    def _centers(self) -> Optional[np.ndarray]:
        return self._lazy_host_view("_centers_raw")

    @property
    def trainingCost(self) -> float:
        if not isinstance(self._cost_raw, float):
            self._cost_raw = float(self._cost_raw)
        return self._cost_raw

    @property
    def numIter(self) -> int:
        if not isinstance(self._iter_raw, int):
            self._iter_raw = int(self._iter_raw)
        return self._iter_raw

    def clusterCenters(self) -> np.ndarray:
        return self._centers

    def _centers_device(self, dtype):
        """Centers as a device array for device-side prediction; free when
        the fit was device-resident (the raw state IS the device array)."""
        raw = self._centers_raw
        if is_device_array(raw) and raw.dtype == dtype:
            return raw
        return jnp.asarray(
            raw if is_device_array(raw) else self._centers, dtype=dtype
        )

    def setFeaturesCol(self, value: str) -> "KMeansModel":
        self.set(self.featuresCol, value)
        return self

    def setPredictionCol(self, value: str) -> "KMeansModel":
        self.set(self.predictionCol, value)
        return self

    def _serving_precision(self) -> str:
        """The serving-family policy mode (ops/precision.py). An explicit
        ``setPrecision`` on the estimator survives into the model via
        param copy and wins; otherwise the TPUML_PRECISION[_SERVING]
        knobs and committed autotune decisions apply. Part of the
        serving static dict, hence of the AOT/program cache key."""
        from spark_rapids_ml_tpu.ops.precision import resolve_policy

        requested = self.getPrecision() if self.isSet(self.precision) else None
        return resolve_policy("serving", requested)

    def predict(self, x) -> np.ndarray:
        if self._centers_raw is None:
            raise RuntimeError("model has no cluster centers")
        x = matrix_like(x)
        static = {
            "cosine": self.getDistanceMeasure() == "cosine",
            "precision": self._serving_precision(),
        }
        # Large HOST batches stream block by block (double-buffered: the
        # H2D of block k+1 overlaps the assignment GEMM of block k —
        # the PCA transform's discipline) instead of paying one
        # serialized whole-matrix transfer.
        if not is_device_array(x):
            xh = np.asarray(x)
            if xh.ndim == 2 and xh.shape[0] > stream_block_rows():
                return serve_blocks(
                    _assign_kernel,
                    xh,
                    (self._centers_serving(),),
                    static=static,
                    name="kmeans.predict",
                )
        # Device queries get device labels (no host pull the caller didn't
        # ask for); host queries keep the numpy contract. Both run through
        # the shape-bucketed serving program cache.
        return serve_rows(
            _assign_kernel,
            x,
            (self._centers_serving(),),
            static=static,
            name="kmeans.predict",
        )

    def _centers_serving(self):
        """Centers as ONE device-resident array reused by every predict —
        the kernel's in-program cast to the batch dtype makes a single
        copy serve all batch dtypes."""
        raw = self._centers_raw
        if is_device_array(raw):
            return raw
        if self._centers_dev is None:
            self._centers_dev = jnp.asarray(self._centers)
            note_device_cache(self)
        return self._centers_dev

    def serving_signature(self):
        """The online-serving contract (serving/signature.py): the same
        assignment kernel ``predict`` routes through the program cache,
        the device-resident centers, and the label output spec the
        admission controller prices requests with."""
        from spark_rapids_ml_tpu.serving.signature import ServingSignature

        if self._centers_raw is None:
            raise RuntimeError("model has no cluster centers")
        centers = self._centers_serving()
        return ServingSignature(
            kernel=_assign_kernel,
            weights=(centers,),
            static={
                "cosine": self.getDistanceMeasure() == "cosine",
                "precision": self._serving_precision(),
            },
            name="kmeans.predict",
            n_features=int(centers.shape[1]),
            output_spec=lambda n, dtype: (
                jax.ShapeDtypeStruct((n,), np.int32),
            ),
        )

    def transform(self, dataset: Any) -> Any:
        rows = _extract_features(dataset, self.getFeaturesCol())
        labels = self.predict(rows)
        if isinstance(dataset, DataFrame):
            return dataset.withColumn(self.getPredictionCol(), list(labels))
        try:
            import pandas as pd

            if isinstance(dataset, pd.DataFrame):
                out = dataset.copy()
                out[self.getPredictionCol()] = labels
                return out
        except ImportError:  # pragma: no cover
            pass
        return labels

    def copy(self, extra=None) -> "KMeansModel":
        """Model.copy preserves fitted state (Spark's Model.copy contract)."""
        that = KMeansModel(self.uid, self._centers_raw, self._cost_raw, self._iter_raw)
        return self._copyValues(that, extra)

    def computeCost(self, x) -> float:
        """Sum of squared distances to nearest center (Spark's computeCost)."""
        xj = matrix_like(x)
        if not is_device_array(xj):
            xj = jnp.asarray(xj)
        centers = self._centers_device(xj.dtype)
        if self.getDistanceMeasure() == "cosine":
            xj = normalize_rows(xj)
            centers = normalize_rows(centers)
        _, d2 = assign_clusters(xj, centers)
        return float(jnp.sum(d2))

    # --- persistence: Spark KMeansModel layout — one ClusterData row per
    # cluster: (clusterIdx: int, clusterCenter: VectorUDT) ---

    def _save_impl(self, path: str) -> None:
        save_metadata(
            self,
            path,
            class_name="org.apache.spark.ml.clustering.KMeansModel",
            extra_metadata={"trainingCost": self.trainingCost, "numIter": self.numIter},
        )
        save_rows(
            path,
            {
                "clusterIdx": ("scalar", list(range(len(self._centers)))),
                "clusterCenter": ("vector", [c for c in self._centers]),
            },
        )

    @classmethod
    def _load_impl(cls, path: str) -> "KMeansModel":
        metadata = load_metadata(path, expected_class="KMeansModel")
        rows = load_rows(path)
        order = np.argsort(np.asarray(rows["clusterIdx"]))
        centers = np.stack([rows["clusterCenter"][i] for i in order])
        model = cls(
            metadata["uid"],
            centers,
            trainingCost=metadata.get("trainingCost", float("nan")),
            numIter=metadata.get("numIter", 0),
        )
        get_and_set_params(model, metadata)
        return model

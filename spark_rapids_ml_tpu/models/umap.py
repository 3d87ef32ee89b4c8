"""UMAP estimator/model — Spark ML surface, XLA compute.

Beyond-the-reference capability (the reference ships only PCA — SURVEY.md
§2; the modern RAPIDS Spark-ML line grew UMAP on cuML). Param surface
follows the cuML-backed Spark estimator's knobs with this package's Spark
ML naming convention: ``nNeighbors``, ``nComponents``, ``minDist``,
``spread``, ``nEpochs`` (0 = auto), ``learningRate``, ``init``
("spectral" | "random"), ``negativeSampleRate``, ``repulsionStrength``,
``metric`` ("euclidean" | "cosine"), ``seed``, ``featuresCol``,
``outputCol``.

Pipeline: exact kNN graph on the MXU (:mod:`ops.knn`), vectorized
smooth-kNN bisection + fuzzy symmetrization, spectral or random init, then
synchronous-epoch SGD layout optimization — one jitted program per stage
(:mod:`ops.umap`). ``transform`` places new points by membership-weighted
interpolation of their training neighbors' coordinates, then refines with
attraction-only epochs against the FIXED training embedding (cuML's
transform semantics, batch-parallel).

DELIBERATE DIVERGENCE (docs/PARITY.md "Known deviations"): the default
``negativePoolSize=256`` draws each epoch's repulsion negatives from one
shared 256-point pool instead of the reference's fresh per-edge negative
samples — the pooled scheme keeps the SGD epoch a single dense jitted
program (no per-edge gather storms on the MXU). Embedding geometry is
equivalent in practice but not sample-for-sample identical to
umap-learn/cuML; ``setNegativePoolSize(0)`` restores the reference
per-edge sampling scheme exactly."""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_ml_tpu.core.data import (
    DataFrame,
    extract_features,
    is_device_array,
)
from spark_rapids_ml_tpu.core.ingest import matrix_like
from spark_rapids_ml_tpu.core.lazy_state import LazyHostState
from spark_rapids_ml_tpu.core.estimator import Estimator, Model
from spark_rapids_ml_tpu.core.params import Param, Params, toFloat, toInt, toString
from spark_rapids_ml_tpu.core.persistence import (
    MLReadable,
    get_and_set_params,
    load_data,
    load_metadata,
    save_data,
    save_metadata,
)
from spark_rapids_ml_tpu.ops.knn import knn
from spark_rapids_ml_tpu.ops.umap import (
    FuzzyGraph,
    find_ab_params,
    fuzzy_simplicial_set,
    optimize_layout,
    smooth_knn_dist,
    spectral_init,
)
from spark_rapids_ml_tpu.utils.envknobs import env_choice
from spark_rapids_ml_tpu.utils.tracing import TraceColor, TraceRange

_SPECTRAL_CAP = 8192  # dense-Laplacian eigh above this would dominate fit time


class _UMAPParams(Params):
    nNeighbors = Param("_", "nNeighbors", "local neighborhood size", toInt)
    nComponents = Param("_", "nComponents", "embedding dimension", toInt)
    metric = Param("_", "metric", "distance metric", toString)
    nEpochs = Param("_", "nEpochs", "optimization epochs (0 = auto)", toInt)
    learningRate = Param("_", "learningRate", "initial SGD step", toFloat)
    init = Param("_", "init", "spectral or random", toString)
    minDist = Param("_", "minDist", "minimum embedded distance", toFloat)
    spread = Param("_", "spread", "embedded scale", toFloat)
    negativeSampleRate = Param("_", "negativeSampleRate", "negatives per edge", toInt)
    negativePoolSize = Param(
        "_", "negativePoolSize",
        "shared negative pool per epoch (0 = per-edge sampling)", toInt,
    )
    repulsionStrength = Param("_", "repulsionStrength", "repulsion weight", toFloat)
    seed = Param("_", "seed", "random seed", toInt)
    featuresCol = Param("_", "featuresCol", "features column name", toString)
    outputCol = Param("_", "outputCol", "embedding column name", toString)
    buildAlgo = Param(
        "_", "buildAlgo",
        "kNN graph build: brute (exact) | brute_approx (hardware top-k)",
        toString,
    )

    def __init__(self, uid: Optional[str] = None):
        super().__init__(uid)
        self._setDefault(
            nNeighbors=15,
            nComponents=2,
            metric="euclidean",
            nEpochs=0,
            learningRate=1.0,
            init="spectral",
            minDist=0.1,
            spread=1.0,
            negativeSampleRate=5,
            negativePoolSize=256,
            repulsionStrength=1.0,
            seed=0,
            featuresCol="features",
            outputCol="embedding",
            buildAlgo="brute",
        )

    def getBuildAlgo(self) -> str:
        return self.getOrDefault(self.buildAlgo)

    def getNNeighbors(self) -> int:
        return self.getOrDefault(self.nNeighbors)

    def getNComponents(self) -> int:
        return self.getOrDefault(self.nComponents)

    def getMetric(self) -> str:
        return self.getOrDefault(self.metric)

    def getNEpochs(self) -> int:
        return self.getOrDefault(self.nEpochs)

    def getLearningRate(self) -> float:
        return self.getOrDefault(self.learningRate)

    def getInit(self) -> str:
        return self.getOrDefault(self.init)

    def getMinDist(self) -> float:
        return self.getOrDefault(self.minDist)

    def getSpread(self) -> float:
        return self.getOrDefault(self.spread)

    def getNegativeSampleRate(self) -> int:
        return self.getOrDefault(self.negativeSampleRate)

    def getNegativePoolSize(self) -> int:
        return self.getOrDefault(self.negativePoolSize)

    def getRepulsionStrength(self) -> float:
        return self.getOrDefault(self.repulsionStrength)

    def getSeed(self) -> int:
        return self.getOrDefault(self.seed)

    def getFeaturesCol(self) -> str:
        return self.getOrDefault(self.featuresCol)

    def getOutputCol(self) -> str:
        return self.getOrDefault(self.outputCol)

    def _chain(self, param, value):
        self.set(param, value)
        return self

    def setNNeighbors(self, v: int):
        if v < 2:
            raise ValueError(f"nNeighbors must be >= 2, got {v}")
        return self._chain(self.nNeighbors, v)

    def setNComponents(self, v: int):
        if v < 1:
            raise ValueError(f"nComponents must be >= 1, got {v}")
        return self._chain(self.nComponents, v)

    def setMetric(self, v: str):
        if v not in ("euclidean", "cosine"):
            raise ValueError(f"metric must be euclidean or cosine, got {v!r}")
        return self._chain(self.metric, v)

    def setNEpochs(self, v: int):
        return self._chain(self.nEpochs, v)

    def setLearningRate(self, v: float):
        return self._chain(self.learningRate, v)

    def setInit(self, v: str):
        if v not in ("spectral", "random"):
            raise ValueError(f"init must be spectral or random, got {v!r}")
        return self._chain(self.init, v)

    def setMinDist(self, v: float):
        return self._chain(self.minDist, v)

    def setSpread(self, v: float):
        return self._chain(self.spread, v)

    def setNegativeSampleRate(self, v: int):
        return self._chain(self.negativeSampleRate, v)

    def setNegativePoolSize(self, v: int):
        """Per-epoch shared negative pool size (r5 default path): repulsion
        is scored against one pool of ``v`` uniform draws with dense
        (n, v) distance GEMMs instead of E * negativeSampleRate random
        gathers — an importance-weighted equivalent estimator
        (:func:`ops.umap.optimize_layout`). ``0`` restores exact per-edge
        sampling (the umap-learn/cuML scheme, gather-bound on TPU)."""
        if v < 0:
            raise ValueError(f"negativePoolSize must be >= 0, got {v}")
        return self._chain(self.negativePoolSize, v)

    def setRepulsionStrength(self, v: float):
        return self._chain(self.repulsionStrength, v)

    def setSeed(self, v: int):
        return self._chain(self.seed, v)

    def setFeaturesCol(self, v: str):
        return self._chain(self.featuresCol, v)

    def setOutputCol(self, v: str):
        return self._chain(self.outputCol, v)

    def setBuildAlgo(self, v: str):
        """``"brute_approx"`` builds the kNN graph with the hardware
        approximate top-k (its gain on the brute search is not measured
        on the chip); UMAP's fuzzy graph is
        robust to it, and cuML's spark UMAP likewise defaults to an
        approximate builder (nn_descent) at scale. ``"brute"`` (default)
        keeps the exact graph."""
        if v not in ("brute", "brute_approx"):
            raise ValueError(f"buildAlgo must be brute|brute_approx, got {v!r}")
        return self._chain(self.buildAlgo, v)

    def _auto_epochs(self, n: int) -> int:
        epochs = self.getNEpochs()
        if epochs > 0:
            return epochs
        return 500 if n <= 10_000 else 200


def _knn_excluding_self(x: jax.Array, k: int, metric: str, mesh=None,
                        x_host=None, approx: bool = False):
    """kNN of x against itself with the self-match column removed.

    ``x_host``: the host copy of ``x`` when the caller still has it — the
    sharded index upload then skips a device->host round trip.
    ``approx``: hardware approximate per-block top-k for the graph build
    (``buildAlgo="brute_approx"`` — UMAP's fuzzy graph tolerates ~0.995
    neighbor recall by design; cuML's spark UMAP likewise builds with
    nn_descent, an approximate method).
    """
    if mesh is not None:
        from spark_rapids_ml_tpu.ops.knn import knn_sharded, shard_items

        host = x_host if x_host is not None else np.asarray(x)
        items, item_mask = shard_items(host, mesh, metric=metric)
        d, idx = knn_sharded(
            x, items.astype(x.dtype), item_mask.astype(x.dtype), mesh, k + 1,
            metric=metric, approx=approx,
        )
    else:
        d, idx = knn(x, x, k + 1, metric=metric, approx=approx)
    # The self column is wherever idx == row (ties can displace it from 0);
    # mask it out then take the first k of the rest.
    rows = jnp.arange(x.shape[0])[:, None]
    is_self = idx == rows
    # Push self to the end by distance +inf, re-sort the small k+1 window.
    d = jnp.where(is_self, jnp.inf, d)
    order = jnp.argsort(d, axis=1)
    d = jnp.take_along_axis(d, order, axis=1)[:, :k]
    idx = jnp.take_along_axis(idx, order, axis=1)[:, :k]
    return d, idx


class UMAP(_UMAPParams, Estimator, MLReadable):
    """``UMAP().setNNeighbors(15).setNComponents(2).fit(x)``.

    With a mesh, BOTH heavy stages are distributed: the kNN graph build —
    the O(n^2 d) stage — shards items over the data axis (local top-k +
    all-gathered candidate merge over ICI, :func:`ops.knn.knn_sharded`),
    and the layout SGD shards its edges over the same axis with one
    (n, dim) delta psum per epoch
    (:func:`ops.umap.optimize_layout_sharded`).
    """

    def __init__(self, uid: Optional[str] = None, mesh=None):
        super().__init__(uid)
        self.mesh = mesh

    def setMesh(self, mesh) -> "UMAP":
        self.mesh = mesh
        return self

    _init_embedding = None
    _copy_attrs = ("_init_embedding",)  # survives Params.copy (tuning grids)

    def setInitEmbedding(self, value) -> "UMAP":
        """Warm start / resume: begin the epoch SGD from an existing (n,
        nComponents) layout — a previous model's ``embedding`` — instead
        of spectral/random init. Lets an interrupted optimization continue
        (run more epochs from the checkpointed layout) or refine a coarse
        fit; cuML/umap-learn's ``init=array`` semantics."""
        arr = np.asarray(value, dtype=np.float32)
        if arr.ndim != 2:
            raise ValueError("init embedding must be an (n, nComponents) matrix")
        self._init_embedding = arr
        return self

    def _fit(self, dataset: Any) -> "UMAPModel":
        from spark_rapids_ml_tpu.core.membudget import fit_memory_guard

        rows = extract_features(dataset, self.getFeaturesCol())
        # Budgeted admission (core/membudget.py): UMAP's kNN graph and
        # epoch SGD need the whole matrix resident — no streaming rung —
        # so an over-budget input raises the structured FitMemoryError
        # up front instead of dying inside device_put.
        fit_memory_guard(
            "umap", rows, can_stream=False,
            why_cannot_stream="UMAP has no streaming fit (the kNN graph "
                              "and epoch SGD need the full matrix resident)",
            mesh=self.mesh, dtype=np.float32, ledger_families=("umap",),
        )
        # Device arrays are consumed in place — no host round trip
        #; the mesh index upload still wants a host copy,
        # which matrix_like keeps for host sources.
        device_in = is_device_array(rows)
        x_in = matrix_like(rows)
        n = int(x_in.shape[0])
        k = min(self.getNNeighbors(), n - 1)
        if n < 3:
            raise ValueError(f"UMAP needs at least 3 rows, got {n}")
        dim = self.getNComponents()
        a, b = find_ab_params(self.getSpread(), self.getMinDist())
        key = jax.random.key(self.getSeed())
        k_init, k_opt = jax.random.split(key)

        with TraceRange("umap fit", TraceColor.PURPLE):
            # Guarded placement: the one whole-dataset upload goes through
            # the ingest.device_put chokepoint (fault point, OOM retry +
            # cache reclaim) instead of a bare jnp.asarray.
            from spark_rapids_ml_tpu.core.ingest import place_array

            x = place_array(x_in, dtype=jnp.float32)
            dists, idx = _knn_excluding_self(
                x, k, self.getMetric(), self.mesh,
                x_host=None if device_in else x_in,
                approx=self.getBuildAlgo() == "brute_approx",
            )
            graph = fuzzy_simplicial_set(idx, dists)
            # Tail-scatter backend: the edge list is static
            # per fit, so 'pallas' sorts it by tail ONCE here and the epoch
            # SGD accumulates tail gradients densely per tile instead of
            # XLA's per-element scatter. 'auto' engages it on the TPU
            # backend; elsewhere (and under a mesh, whose sharded epoch
            # keeps its own scatter) the XLA path stands.
            tail_plan = tail_cfg = None
            tail_interpret = False
            scatter_mode = env_choice(
                "TPUML_UMAP_SCATTER", ("auto", "pallas", "xla"), "auto"
            )
            on_tpu = jax.default_backend() == "tpu"
            want_pallas = scatter_mode == "pallas" or (
                scatter_mode == "auto" and on_tpu
            )
            if want_pallas and self.mesh is None:
                from spark_rapids_ml_tpu.ops.pallas.umap import (
                    build_tail_plan,
                    plan_feasible,
                )

                if plan_feasible(n, k, dim):
                    tail_plan, tail_cfg = build_tail_plan(
                        np.asarray(idx), n, dim
                    )
                    tail_interpret = not on_tpu
            if self._init_embedding is not None:
                if self._init_embedding.shape != (n, dim):
                    raise ValueError(
                        f"init embedding shape {self._init_embedding.shape} != "
                        f"({n}, {dim})"
                    )
                emb0 = jnp.asarray(self._init_embedding)
            elif self.getInit() == "spectral" and n <= _SPECTRAL_CAP:
                emb0 = spectral_init(graph, n, dim, k_init)
            else:
                emb0 = 10.0 * jax.random.uniform(
                    k_init, (n, dim), minval=-1.0, maxval=1.0
                )
            if self.mesh is not None:
                # Mesh fit: the epoch SGD shards its edges over the data
                # axis too (one delta psum per epoch) — both heavy stages
                # (kNN graph AND layout optimization) are distributed.
                import functools

                from spark_rapids_ml_tpu.ops.umap import optimize_layout_sharded

                optimizer = functools.partial(optimize_layout_sharded, self.mesh)
            else:
                optimizer = optimize_layout
            # Preemption tolerance is OPT-IN for UMAP (TPUML_CHECKPOINT_UMAP=1
            # on top of the global knobs): only the epoch SGD checkpoints —
            # the kNN graph and the init recompute deterministically on
            # resume. Single-device fits only (the sharded epoch program
            # keeps its state inside shard_map).
            ckpt = None
            if self.mesh is None:
                from spark_rapids_ml_tpu.robustness.checkpoint import umap_opt_in

                if umap_opt_in():
                    ckpt = self._fit_checkpointer("umap.layout", data=(x, emb0))
            if ckpt is not None:
                from spark_rapids_ml_tpu.ops.umap import optimize_layout_resumable

                emb = optimize_layout_resumable(
                    emb0.astype(jnp.float32),
                    graph,
                    k_opt,
                    ckpt,
                    n_epochs=self._auto_epochs(n),
                    neg_rate=self.getNegativeSampleRate(),
                    neg_pool=self.getNegativePoolSize(),
                    learning_rate=self.getLearningRate(),
                    repulsion=self.getRepulsionStrength(),
                    a=a,
                    b=b,
                    tail_plan=tail_plan,
                    tail_cfg=tail_cfg,
                    tail_interpret=tail_interpret,
                )
            else:
                tail_kw = {}
                if self.mesh is None:
                    tail_kw = dict(
                        tail_plan=tail_plan, tail_cfg=tail_cfg,
                        tail_interpret=tail_interpret,
                    )
                emb = optimizer(
                    emb0.astype(jnp.float32),
                    graph,
                    k_opt,
                    n_epochs=self._auto_epochs(n),
                    neg_rate=self.getNegativeSampleRate(),
                    neg_pool=self.getNegativePoolSize(),
                    learning_rate=self.getLearningRate(),
                    repulsion=self.getRepulsionStrength(),
                    a=a,
                    b=b,
                    **tail_kw,
                )

        # Device fits keep embedding + train rows resident; the model's
        # host float64 views convert lazily (the PCAModel contract).
        model = UMAPModel(
            self.uid,
            embedding=emb if device_in else np.asarray(emb, dtype=np.float64),
            trainData=x_in if device_in else np.asarray(x_in, dtype=np.float64),
            a=a,
            b=b,
        )
        return self._copyValues(model)


class UMAPModel(_UMAPParams, Model, LazyHostState):
    """Fitted model: ``embedding`` (n, dim); transform embeds NEW points
    against the frozen training layout."""

    def __init__(
        self,
        uid: Optional[str] = None,
        embedding: Optional[np.ndarray] = None,
        trainData: Optional[np.ndarray] = None,
        a: float = 1.577,
        b: float = 0.895,
    ):
        super().__init__(uid)
        # Fitted state keeps its residence (device-fit state stays on
        # device); host float64 views convert lazily and pickling
        # materializes host state (core/lazy_state.LazyHostState).
        self._emb_raw = embedding
        self._train_raw = trainData
        self._emb_np: Optional[np.ndarray] = None
        self._train_np: Optional[np.ndarray] = None
        self.a = a
        self.b = b

    _lazy_host_fields = {
        "_emb_raw": ("_emb_np", np.float64),
        "_train_raw": ("_train_np", np.float64),
    }

    @property
    def embedding(self) -> Optional[np.ndarray]:
        return self._lazy_host_view("_emb_raw")

    @property
    def trainData(self) -> Optional[np.ndarray]:
        return self._lazy_host_view("_train_raw")

    def copy(self, extra=None) -> "UMAPModel":
        that = UMAPModel(self.uid, self._emb_raw, self._train_raw, self.a, self.b)
        return self._copyValues(that, extra)

    def transform(self, dataset: Any) -> Any:
        rows = extract_features(dataset, self.getFeaturesCol())
        x = matrix_like(rows)
        emb = self._embed_new(x)
        if isinstance(dataset, DataFrame):
            return dataset.withColumn(self.getOutputCol(), [e for e in emb])
        try:
            import pandas as pd

            if isinstance(dataset, pd.DataFrame):
                out = dataset.copy()
                out[self.getOutputCol()] = list(emb)
                return out
        except ImportError:  # pragma: no cover
            pass
        return emb

    def _embed_new(self, x_in) -> np.ndarray:
        device_in = is_device_array(x_in)
        n_train = self._train_raw.shape[0]
        k = min(self.getNNeighbors(), n_train)
        x = (
            x_in.astype(jnp.float32)
            if device_in
            else jnp.asarray(x_in, dtype=jnp.float32)
        )
        train = (
            self._train_raw.astype(jnp.float32)
            if is_device_array(self._train_raw)
            else jnp.asarray(self.trainData, dtype=jnp.float32)
        )
        train_emb = (
            self._emb_raw.astype(jnp.float32)
            if is_device_array(self._emb_raw)
            else jnp.asarray(self.embedding, dtype=jnp.float32)
        )

        with TraceRange("umap transform", TraceColor.PURPLE):
            dists, idx = knn(x, train, k, metric=self.getMetric())
            sigmas, rhos = smooth_knn_dist(dists, float(k))
            w = jnp.exp(
                -jnp.maximum(dists - rhos[:, None], 0.0) / sigmas[:, None]
            )
            w = w / jnp.maximum(jnp.sum(w, axis=1, keepdims=True), 1e-12)
            init = jnp.einsum("qk,qkd->qd", w, train_emb[idx])
            graph = FuzzyGraph(idx.astype(jnp.int32), w.astype(jnp.float32), sigmas, rhos)
            epochs = max(1, self._auto_epochs(n_train) // 3)
            emb = optimize_layout(
                init,
                graph,
                jax.random.key(self.getSeed() + 1),
                n_epochs=epochs,
                neg_rate=self.getNegativeSampleRate(),
                neg_pool=self.getNegativePoolSize(),
                learning_rate=self.getLearningRate(),
                repulsion=self.getRepulsionStrength(),
                a=self.a,
                b=self.b,
                move_other=False,
                target=train_emb,
            )
        # Device queries get a device embedding back; host queries keep
        # the numpy float64 contract.
        return emb if device_in else np.asarray(emb, dtype=np.float64)

    def _save_impl(self, path: str) -> None:
        save_metadata(
            self,
            path,
            class_name="com.nvidia.rapids.ml.UMAPModel",
            extra_metadata={"a": self.a, "b": self.b},
        )
        save_data(
            path,
            {
                "embedding": ("matrix", self.embedding),
                "trainData": ("matrix", self.trainData),
            },
        )

    @classmethod
    def _load_impl(cls, path: str) -> "UMAPModel":
        metadata = load_metadata(path, expected_class="UMAPModel")
        data = load_data(path)
        model = cls(
            metadata["uid"],
            embedding=np.asarray(data["embedding"]),
            trainData=np.asarray(data["trainData"]),
            a=metadata.get("a", 1.577),
            b=metadata.get("b", 0.895),
        )
        get_and_set_params(model, metadata)
        return model

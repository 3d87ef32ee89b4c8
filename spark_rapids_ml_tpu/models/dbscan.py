"""DBSCAN estimator/model — Spark ML surface, XLA compute.

Beyond-the-reference capability (the reference ships only PCA — SURVEY.md
§2; the modern RAPIDS Spark-ML line grew DBSCAN on cuML). Param surface
mirrors the cuML/spark-rapids-ml estimator: ``eps`` (default 0.5),
``minSamples`` (default 5, a.k.a. cuML ``min_samples``), ``metric``
("euclidean"), ``featuresCol``, ``predictionCol``.

DBSCAN is transductive: ``fit`` clusters the training rows and the model
carries their labels. ``transform`` on the *fitted* rows returns those
labels; on new rows it assigns each point to the cluster of its nearest
core point within eps (else noise, -1) — an out-of-sample extension the
cuML line does not offer.

TPU-first notes: see ``ops/dbscan.py`` — no adjacency lists, no BFS; the
epsilon graph lives implicitly in blocked distance GEMMs and clusters come
from min-label diffusion with pointer-jumping inside one jitted program.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import numpy as np

from spark_rapids_ml_tpu.core.data import (
    DataFrame,
    extract_features,
    is_device_array,
)
from spark_rapids_ml_tpu.core.ingest import matrix_like
from spark_rapids_ml_tpu.core.estimator import Estimator, Model
from spark_rapids_ml_tpu.core.params import Param, Params, gt, toFloat, toInt, toString
from spark_rapids_ml_tpu.core.persistence import (
    MLReadable,
    get_and_set_params,
    load_metadata,
    load_rows,
    save_metadata,
    save_rows,
)
from spark_rapids_ml_tpu.ops.dbscan import (
    dbscan_labels,
    dbscan_labels_sharded,
    relabel_consecutive,
)
from spark_rapids_ml_tpu.ops.knn import knn_sq_euclidean
from spark_rapids_ml_tpu.utils.tracing import TraceColor, TraceRange


def _dtype():
    """float64 under jax_enable_x64, float32 otherwise — the package-wide
    dtype convention (matches KMeans/NearestNeighbors); the eps test is
    cancellation-sensitive, so use the widest available float."""
    return np.float64 if jax.config.jax_enable_x64 else np.float32


class _DBSCANParams(Params):
    eps = Param("_", "eps", "neighborhood radius", lambda v: gt(0.0)(toFloat(v)))
    minSamples = Param(
        "_", "minSamples", "min points (incl. self) within eps for a core point",
        lambda v: gt(0)(toInt(v)),
    )
    metric = Param("_", "metric", "distance metric (euclidean)", toString)
    featuresCol = Param("_", "featuresCol", "features column name", toString)
    predictionCol = Param("_", "predictionCol", "prediction column name", toString)

    def __init__(self, uid: Optional[str] = None):
        super().__init__(uid)
        self._setDefault(
            eps=0.5,
            minSamples=5,
            metric="euclidean",
            featuresCol="features",
            predictionCol="prediction",
        )

    def getEps(self) -> float:
        return self.getOrDefault(self.eps)

    def getMinSamples(self) -> int:
        return self.getOrDefault(self.minSamples)

    def getMetric(self) -> str:
        return self.getOrDefault(self.metric)

    def getFeaturesCol(self) -> str:
        return self.getOrDefault(self.featuresCol)

    def getPredictionCol(self) -> str:
        return self.getOrDefault(self.predictionCol)


class DBSCAN(_DBSCANParams, Estimator, MLReadable):
    """``DBSCAN().setEps(0.3).setMinSamples(10).fit(x)``.

    With a mesh, the epsilon sweeps shard query rows over the data axis and
    the label-diffusion rounds all-gather the (tiny) label vector over ICI
    (:func:`ops.dbscan.dbscan_labels_sharded`)."""

    def __init__(self, uid: Optional[str] = None, mesh=None):
        super().__init__(uid)
        self.mesh = mesh

    def setEps(self, value: float) -> "DBSCAN":
        self.set(self.eps, value)
        return self

    def setMinSamples(self, value: int) -> "DBSCAN":
        self.set(self.minSamples, value)
        return self

    def setMetric(self, value: str) -> "DBSCAN":
        if value != "euclidean":
            raise ValueError(f"only 'euclidean' is supported, got {value!r}")
        self.set(self.metric, value)
        return self

    def setFeaturesCol(self, value: str) -> "DBSCAN":
        self.set(self.featuresCol, value)
        return self

    def setPredictionCol(self, value: str) -> "DBSCAN":
        self.set(self.predictionCol, value)
        return self

    def setMesh(self, mesh) -> "DBSCAN":
        self.mesh = mesh
        return self

    def fit(self, dataset: Any) -> "DBSCANModel":
        from spark_rapids_ml_tpu.core.serving import configure_compile_cache

        configure_compile_cache()
        # Device arrays are consumed in place — no host round trip
        #; host input densifies straight to compute dtype.
        x = matrix_like(extract_features(dataset, self.getFeaturesCol()), dtype=_dtype())
        with TraceRange("dbscan fit", TraceColor.RED):
            if self.mesh is not None:
                labels, core = dbscan_labels_sharded(
                    self.mesh, x, self.getEps(), self.getMinSamples()
                )
            else:
                labels, core = dbscan_labels(x, self.getEps(), self.getMinSamples())
        labels = relabel_consecutive(np.asarray(labels))
        model = DBSCANModel(
            self.uid,
            fitted=x,
            labels=labels,
            core_mask=np.asarray(core),
        )
        return self._copyValues(model)


class DBSCANModel(_DBSCANParams, Model):
    """Fitted DBSCAN: training rows, their labels, and the core mask."""

    def __init__(
        self,
        uid: Optional[str] = None,
        fitted: Optional[np.ndarray] = None,
        labels: Optional[np.ndarray] = None,
        core_mask: Optional[np.ndarray] = None,
    ):
        super().__init__(uid)
        # Training rows keep their residence (device-fit rows stay on
        # device); the host view converts lazily via `fitted`.
        self._fitted_raw = (
            fitted
            if fitted is None or is_device_array(fitted)
            else np.asarray(fitted, dtype=_dtype())
        )
        self._fitted_np: Optional[np.ndarray] = None
        self.labels_ = None if labels is None else np.asarray(labels, dtype=np.int32)
        self.core_mask_ = None if core_mask is None else np.asarray(core_mask, dtype=bool)

    def __getstate__(self):
        """Pickle host state, never live device buffers."""
        state = dict(self.__dict__)
        state["_fitted_raw"] = self.fitted
        state["_fitted_np"] = state["_fitted_raw"]
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)

    @property
    def fitted(self) -> Optional[np.ndarray]:
        if self._fitted_np is None and self._fitted_raw is not None:
            self._fitted_np = np.asarray(self._fitted_raw, dtype=_dtype())
        return self._fitted_np

    @fitted.setter
    def fitted(self, value) -> None:
        # Stored AS-IS (no dtype cast): callers that swap in a specific
        # storage dtype (the f32-emulation contract test) must see exactly
        # what they assigned.
        self._fitted_raw = value
        self._fitted_np = None if is_device_array(value) else value

    @property
    def core_sample_indices_(self) -> np.ndarray:
        """Indices of core points (cuML calc_core_sample_indices equivalent)."""
        return np.flatnonzero(self.core_mask_)

    def copy(self, extra=None) -> "DBSCANModel":
        that = DBSCANModel(self.uid, self._fitted_raw, self.labels_, self.core_mask_)
        return self._copyValues(that, extra)

    def _predict_new(self, x) -> np.ndarray:
        """Out-of-sample: cluster of the nearest core point within eps."""
        import jax.numpy as jnp

        core_idx = self.core_sample_indices_
        if core_idx.size == 0:
            return np.full(x.shape[0], -1, dtype=np.int32)
        if is_device_array(self._fitted_raw):
            cores = self._fitted_raw[jnp.asarray(core_idx)]
        else:
            # Host-fitted model: gather the (few) core rows on host and
            # upload only those — not the full training matrix.
            cores = jnp.asarray(self.fitted[core_idx])
        xq = x if is_device_array(x) else jnp.asarray(x.astype(_dtype(), copy=False))
        d, i = knn_sq_euclidean(xq.astype(cores.dtype), cores, k=1)
        d = np.asarray(d)[:, 0]
        i = np.asarray(i)[:, 0]
        out = self.labels_[core_idx[i]]
        return np.where(d <= self.getEps() ** 2, out, -1).astype(np.int32)

    def transform(self, dataset: Any) -> Any:
        import jax.numpy as jnp

        x = matrix_like(extract_features(dataset, self.getFeaturesCol()), dtype=_dtype())
        fitted = self._fitted_raw
        same = fitted is not None and tuple(x.shape) == tuple(fitted.shape)
        if same and x is not fitted:
            if is_device_array(x) or is_device_array(fitted):
                same = bool(jnp.array_equal(jnp.asarray(x), jnp.asarray(fitted)))
            else:
                same = np.array_equal(x, fitted)
        if same:
            pred = self.labels_
        else:
            with TraceRange("dbscan transform", TraceColor.GREEN):
                pred = self._predict_new(x)
        if isinstance(dataset, DataFrame):
            return dataset.withColumn(self.getPredictionCol(), list(np.asarray(pred)))
        try:
            import pandas as pd

            if isinstance(dataset, pd.DataFrame):
                out = dataset.copy()
                out[self.getPredictionCol()] = list(np.asarray(pred))
                return out
        except ImportError:  # pragma: no cover
            pass
        return np.asarray(pred)

    # --- persistence ---

    def _save_impl(self, path: str) -> None:
        save_metadata(self, path, class_name="com.nvidia.spark.ml.clustering.DBSCANModel")
        save_rows(
            path,
            {
                "row": ("vector", [r for r in self.fitted.astype(np.float64)]),
                "label": ("scalar", [int(v) for v in self.labels_]),
                "core": ("scalar", [bool(v) for v in self.core_mask_]),
            },
        )

    @classmethod
    def _load_impl(cls, path: str) -> "DBSCANModel":
        metadata = load_metadata(path, expected_class="DBSCANModel")
        rows = load_rows(path)
        model = cls(
            metadata["uid"],
            fitted=np.stack(rows["row"]).astype(_dtype()),
            labels=np.asarray(rows["label"], dtype=np.int32),
            core_mask=np.asarray(rows["core"], dtype=bool),
        )
        get_and_set_params(model, metadata)
        return model

"""NearestNeighbors estimator/model — exact brute-force kNN on the MXU.

Beyond-the-reference capability (the reference ships only PCA — SURVEY.md
§2; the modern RAPIDS Spark-ML line exposes cuML brute-force
NearestNeighbors with this param surface: ``k``, ``inputCol``, ``idCol``).
``fit`` indexes the item set; ``kneighbors(queries)`` returns (distances,
indices) — plus caller ids when ``idCol`` is set, mirroring the
item-id/query-id join the Spark version emits.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

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
from spark_rapids_ml_tpu.core.params import Param, Params, gt, toInt, toString
from spark_rapids_ml_tpu.core.persistence import (
    MLReadable,
    get_and_set_params,
    load_rows,
    load_metadata,
    save_metadata,
    save_rows,
)
from spark_rapids_ml_tpu.ops.knn import knn, knn_sharded, shard_items
from spark_rapids_ml_tpu.utils.tracing import TraceColor, TraceRange


# Shared extraction convention lives in core.data; keep the old local name.
_extract_features = extract_features


class _NearestNeighborsParams(Params):
    k = Param("_", "k", "number of neighbors", lambda v: gt(0)(toInt(v)))
    inputCol = Param("_", "inputCol", "features column name", toString)
    idCol = Param("_", "idCol", "optional row-id column name", toString)
    metric = Param("_", "metric", "euclidean, sqeuclidean, or cosine", toString)

    def __init__(self, uid: Optional[str] = None):
        super().__init__(uid)
        self._setDefault(k=5, inputCol="features", metric="euclidean")

    def getK(self) -> int:
        return self.getOrDefault(self.k)

    def getInputCol(self) -> str:
        return self.getOrDefault(self.inputCol)

    def getIdCol(self) -> Optional[str]:
        return self.getOrDefault(self.idCol) if self.isDefined(self.idCol) else None

    def getMetric(self) -> str:
        return self.getOrDefault(self.metric)


class NearestNeighbors(_NearestNeighborsParams, Estimator, MLReadable):
    """``NearestNeighbors().setK(8).fit(items).kneighbors(queries)``."""

    def __init__(self, uid: Optional[str] = None, mesh=None):
        super().__init__(uid)
        self.mesh = mesh

    def setK(self, value: int) -> "NearestNeighbors":
        self.set(self.k, value)
        return self

    def setInputCol(self, value: str) -> "NearestNeighbors":
        self.set(self.inputCol, value)
        return self

    def setIdCol(self, value: str) -> "NearestNeighbors":
        self.set(self.idCol, value)
        return self

    def setMetric(self, value: str) -> "NearestNeighbors":
        if value not in ("euclidean", "sqeuclidean", "cosine"):
            raise ValueError(
                f"metric must be euclidean/sqeuclidean/cosine, got {value!r}"
            )
        self.set(self.metric, value)
        return self

    def setMesh(self, mesh) -> "NearestNeighbors":
        self.mesh = mesh
        return self

    def fit(self, dataset: Any) -> "NearestNeighborsModel":
        """Index the item set (brute force: store + pre-shard). Device
        arrays are indexed in place — no host round trip.

        A RE-ITERABLE streaming source (iterator factory / block reader)
        becomes a STREAMED index: items never materialize on device or
        host — each ``kneighbors`` call streams the blocks through the
        running top-k merge (``ops.knn.knn_host_streamed``), so item
        capacity is bounded by the source, not HBM."""
        from spark_rapids_ml_tpu.core.serving import configure_compile_cache

        configure_compile_cache()
        from spark_rapids_ml_tpu.core.data import (
            is_reiterable_stream,
            is_streaming_source,
        )

        if is_streaming_source(dataset):
            if not is_reiterable_stream(dataset):
                raise ValueError(
                    "a streamed kNN index needs a RE-ITERABLE source (a "
                    "zero-arg iterator factory or a block reader with "
                    ".iter_blocks()), not a one-shot generator"
                )
            if self.mesh is not None:
                raise ValueError(
                    "streamed indexes are single-device; use host "
                    "partitions + a mesh for the sharded index"
                )
            model = NearestNeighborsModel(
                self.uid, None, None, items_stream=dataset
            )
            return self._copyValues(model)
        id_col = self.getIdCol()
        items = matrix_like(_extract_features(dataset, self.getInputCol(), drop=id_col))
        ids = None
        if id_col is not None:
            # idCol set but not extractable => raise rather than silently
            # returning positional indices from kneighbors_ids later.
            if isinstance(dataset, DataFrame):
                if id_col not in dataset.columns:
                    raise ValueError(
                        f"idCol={id_col!r} set, but the dataset has no such column"
                    )
                ids = np.asarray(dataset.select(id_col))
            else:
                try:
                    import pandas as pd
                except ImportError:  # pragma: no cover
                    pd = None
                if pd is not None and isinstance(dataset, pd.DataFrame) and id_col in dataset.columns:
                    ids = dataset[id_col].to_numpy()
                else:
                    raise ValueError(
                        f"idCol={id_col!r} set, but the dataset has no such column"
                    )
        if self.getK() > items.shape[0]:
            raise ValueError(f"k={self.getK()} exceeds item count {items.shape[0]}")
        model = NearestNeighborsModel(self.uid, items, ids, mesh=self.mesh)
        return self._copyValues(model)


class NearestNeighborsModel(_NearestNeighborsParams, Model, LazyHostState):
    """Indexed item set; ``kneighbors`` runs the blocked distance GEMM."""

    def __init__(
        self,
        uid: Optional[str] = None,
        items: Optional[np.ndarray] = None,
        ids: Optional[np.ndarray] = None,
        mesh=None,
        items_stream=None,
    ):
        super().__init__(uid)
        # Device-fitted items stay resident; the host view (`items`)
        # converts lazily.
        self._items_raw = (
            items if items is None or is_device_array(items) else np.asarray(items)
        )
        self._items_np: Optional[np.ndarray] = None
        self.ids = None if ids is None else np.asarray(ids)
        self.mesh = mesh
        self._sharded = None  # lazily cached (items_sharded, mask_sharded)
        self._items_stream = items_stream  # re-iterable beyond-HBM index

    # Host views convert lazily; pickling materializes host state and
    # drops the sharded-index device cache (core/lazy_state.LazyHostState).
    _lazy_host_fields = {"_items_raw": ("_items_np", None)}
    _pickle_clear = ("_sharded",)

    def __getstate__(self):
        # Same contract as _save_impl: a streamed-index model
        # must not pickle — cloudpickling (Spark broadcast, UDF closures)
        # would either ship the whole item set the streamed mode exists to
        # avoid, or fail opaquely on an unpicklable reader.
        if self._items_stream is not None:
            raise ValueError(
                "a streamed-index model does not pickle (its items live "
                "in the external source); broadcast/persist the source "
                "instead"
            )
        return super().__getstate__()

    @property
    def items(self) -> Optional[np.ndarray]:
        return self._lazy_host_view("_items_raw")

    def setMesh(self, mesh) -> "NearestNeighborsModel":
        self.mesh = mesh
        self._sharded = None
        return self

    def kneighbors(self, queries: Any, k: Optional[int] = None) -> Tuple[np.ndarray, np.ndarray]:
        """(distances (nq, k), indices (nq, k)). Indices are row positions in
        the fitted item set; use ``kneighbors_ids`` for idCol-mapped output.
        Device queries against device-fitted items stay entirely on device
        (device results back); host queries keep the numpy contract."""
        if self._items_stream is not None:
            return self._kneighbors_streamed(queries, k)
        if self._items_raw is None:
            raise RuntimeError("model has no indexed items")
        n_items = int(self._items_raw.shape[0])
        k = self.getK() if k is None else k
        if not 1 <= k <= n_items:
            raise ValueError(f"k must be in [1, {n_items}], got {k}")
        q_in = matrix_like(
            _extract_features(queries, self.getInputCol(), drop=self.getIdCol())
        )
        device_q = is_device_array(q_in)
        dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
        qj = q_in.astype(dtype) if device_q else jnp.asarray(q_in, dtype=dtype)
        with TraceRange("knn", TraceColor.PURPLE):
            if self.mesh is not None:
                metric = self.getMetric()
                if self._sharded is None or self._sharded[2] != metric:
                    # One upload of the index (cosine rows pre-normalized by
                    # shard_items), reused across query batches (fit's
                    # "store + pre-shard" promise). Keyed by metric:
                    # re-normalization is baked into the upload.
                    xs, mask = shard_items(
                        self.items.astype(np.dtype(dtype)), self.mesh,
                        metric=metric,
                    )
                    self._sharded = (xs, mask, metric)
                xs, mask, _ = self._sharded
                d, idx = knn_sharded(qj, xs, mask, self.mesh, k=k, metric=metric)
            else:
                items_dev = (
                    self._items_raw.astype(dtype)
                    if is_device_array(self._items_raw)
                    else jnp.asarray(self.items, dtype=dtype)
                )
                d, idx = knn(qj, items_dev, k=k, metric=self.getMetric())
        if device_q:
            return d, idx
        return np.asarray(d), np.asarray(idx)

    def _kneighbors_streamed(self, queries: Any, k: Optional[int]):
        """Beyond-HBM search: one pass over the streamed item blocks with
        a running top-k merge. k validates against the streamed count."""
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.core.data import iter_stream_blocks
        from spark_rapids_ml_tpu.ops.knn import knn_host_streamed

        k = self.getK() if k is None else k
        q_in = matrix_like(
            _extract_features(queries, self.getInputCol(), drop=self.getIdCol())
        )
        device_q = is_device_array(q_in)
        dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
        qj = q_in.astype(dtype) if device_q else jnp.asarray(q_in, dtype=dtype)
        with TraceRange("knn streamed", TraceColor.PURPLE):
            d, idx = knn_host_streamed(
                qj,
                iter_stream_blocks(self._items_stream),
                k=k,
                metric=self.getMetric(),
            )
        if device_q:
            return d, idx
        return np.asarray(d), np.asarray(idx)

    def kneighbors_ids(self, queries: Any, k: Optional[int] = None):
        """(distances, ids) with indices mapped through the fitted idCol."""
        d, idx = self.kneighbors(queries, k)
        if self.ids is None:
            return d, idx
        return d, self.ids[idx]

    def transform(self, dataset: Any) -> Any:
        """Append neighbor indices + distances columns (DataFrame input)."""
        d, idx = self.kneighbors(dataset)
        if isinstance(dataset, DataFrame):
            out = dataset.withColumn("knn_indices", list(idx))
            return out.withColumn("knn_distances", list(d))
        try:
            import pandas as pd

            if isinstance(dataset, pd.DataFrame):
                out = dataset.copy()
                out["knn_indices"] = list(idx)
                out["knn_distances"] = list(d)
                return out
        except ImportError:  # pragma: no cover
            pass
        return d, idx

    def _save_impl(self, path: str) -> None:
        if self._items_stream is not None:
            raise ValueError(
                "a streamed-index model does not persist (its items live "
                "in the external source); persist the source instead"
            )
        save_metadata(
            self,
            path,
            class_name="com.nvidia.rapids.ml.NearestNeighborsModel",
            extra_metadata={"hasIds": self.ids is not None},
        )
        cols = {"item": ("vector", [r for r in self.items])}
        if self.ids is not None:
            cols["id"] = ("scalar", self.ids.tolist())
        save_rows(path, cols)

    @classmethod
    def _load_impl(cls, path: str) -> "NearestNeighborsModel":
        metadata = load_metadata(path, expected_class="NearestNeighborsModel")
        rows = load_rows(path)
        items = np.stack(rows["item"])
        ids = np.asarray(rows["id"]) if metadata.get("hasIds") else None
        model = cls(metadata["uid"], items, ids)
        get_and_set_params(model, metadata)
        return model

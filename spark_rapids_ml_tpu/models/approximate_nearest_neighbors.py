"""ApproximateNearestNeighbors estimator/model — IVF-Flat on the MXU.

Beyond-the-reference capability (the reference ships only PCA — SURVEY.md
§2; the modern RAPIDS Spark-ML line exposes cuML ApproximateNearestNeighbors
with this param surface: ``k``, ``algorithm`` (default "ivfflat"),
``algoParams`` (e.g. ``{"nlist": 50, "nprobe": 20}``), ``metric``,
``inputCol``, ``idCol``). Algorithms: ``ivfflat`` / ``ivfpq`` (kernels in
``ops/ann.py`` — see its docstring for the dense-tensor redesign of cuML's
inverted lists), ``brute`` (exact, delegates to ``ops/knn.py``), and
``brute_approx`` (dense MXU scoring + the TPU-native hardware approximate
top-k, ``lax.approx_min_k``). The TPU-first design: TPU gathers are
scalarized while dense GEMMs ride the systolic array, so ``brute_approx``
is the expected winner over the inverted lists at resident scales. That
crossover predates the chip and is not measured on it (ROADMAP.md Reach 9
is its cell; Design 14). Under a mesh,
``brute_approx`` runs the hardware per-shard top-k with an exact
cross-shard merge (``ops/knn.knn_sharded(approx=True)``).

BEYOND single-chip HBM a re-iterable block source fits a STREAMED brute
index (``ops/knn.knn_host_streamed`` — running top-k merge, capacity
bounded by the source). The compressed resident alternative (``ivfpq`` —
the only structure whose residency shrinks relative to raw items) is
gather-bound on TPU; where streaming overtakes it is not measured on the
chip (ROADMAP.md Reach 5, Design 14). The TPU-native beyond-HBM recipe
is taken to be streaming (or sharding items across
chips/executors — ``knn_sharded`` / the adapter's
``setIndexMode("sharded")``); ``ivfpq``/``ivfflat`` remain for API
parity with the cuML lineage, not as the scale path.

Metrics: ``euclidean`` / ``sqeuclidean`` natively; ``cosine`` by
L2-normalizing items and queries, under which cosine distance equals half
the squared euclidean distance.

Persistence stores the raw items (+ ids); the IVF index is rebuilt on load
from the persisted ``seed`` — the quantizer is deterministic given (items,
n_lists, seed), so a reloaded model probes identical lists.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_ml_tpu.core.data import (
    DataFrame,
    extract_features,
    is_device_array,
)
from spark_rapids_ml_tpu.core.estimator import Estimator, Model
from spark_rapids_ml_tpu.core.ingest import matrix_like
from spark_rapids_ml_tpu.core.lazy_state import LazyHostState
from spark_rapids_ml_tpu.core.params import Param, Params, gt, toInt, toString
from spark_rapids_ml_tpu.core.persistence import (
    MLReadable,
    get_and_set_params,
    load_metadata,
    load_rows,
    save_metadata,
    save_rows,
)
from spark_rapids_ml_tpu.ops.ann import (
    IVFIndex,
    IVFPQIndex,
    ann_search_sharded,
    build_ivf_index,
    build_ivfpq_index,
    dispatch_search,
)
from spark_rapids_ml_tpu.ops.knn import knn, knn_sharded, shard_items
from spark_rapids_ml_tpu.utils.tracing import TraceColor, TraceRange

_ALGORITHMS = ("ivfflat", "ivfpq", "brute", "brute_approx")


@partial(jax.jit, static_argnames=("k", "block_q"))
def _refine_exact(q, items, cand_idx, k, block_q: int = 1024):
    """Re-rank PQ candidates with exact squared distances.

    Queries stream in ``block_q`` chunks (same memory discipline as the
    searches — an unblocked (nq, k', d) gather would OOM large batches).
    ``cand_idx`` (nq, k') may contain -1 fill slots; those stay at +inf.
    Returns ascending (d2 (nq, k), idx (nq, k))."""
    nq = q.shape[0]
    n_blocks = -(-nq // block_q)
    pad = n_blocks * block_q - nq
    qp = jnp.pad(q, ((0, pad), (0, 0)))
    cp = jnp.pad(cand_idx, ((0, pad), (0, 0)), constant_values=-1)

    def one_block(args):
        qb, cb = args
        gathered = items[jnp.maximum(cb, 0)]  # (Bq, k', d)
        diff = qb[:, None, :] - gathered
        d2 = jnp.sum(diff * diff, axis=2)
        d2 = jnp.where(cb >= 0, d2, jnp.inf)
        neg_top, pos = jax.lax.top_k(-d2, k)
        return -neg_top, jnp.take_along_axis(cb, pos, axis=1)

    d2, idx = jax.lax.map(
        one_block,
        (qp.reshape(n_blocks, block_q, -1), cp.reshape(n_blocks, block_q, -1)),
    )
    return d2.reshape(-1, k)[:nq], idx.reshape(-1, k)[:nq]
_METRICS = ("euclidean", "sqeuclidean", "cosine")


def _dtype():
    return np.float64 if jax.config.jax_enable_x64 else np.float32


def _normalize(x: np.ndarray) -> np.ndarray:
    norms = np.linalg.norm(x, axis=1, keepdims=True)
    return x / np.maximum(norms, 1e-30)


class _ANNParams(Params):
    k = Param("_", "k", "number of neighbors", lambda v: gt(0)(toInt(v)))
    algorithm = Param(
        "_", "algorithm", "ivfflat | ivfpq | brute | brute_approx", toString
    )
    algoParams = Param(
        "_", "algoParams", "algorithm tuning dict, e.g. {'nlist': 50, 'nprobe': 20}",
        lambda v: dict(v) if v is not None else {},
    )
    metric = Param("_", "metric", "euclidean, sqeuclidean, or cosine", toString)
    inputCol = Param("_", "inputCol", "features column name", toString)
    idCol = Param("_", "idCol", "optional row-id column name", toString)
    seed = Param("_", "seed", "quantizer random seed", toInt)

    def __init__(self, uid: Optional[str] = None):
        super().__init__(uid)
        self._setDefault(
            k=5, algorithm="ivfflat", algoParams={}, metric="euclidean",
            inputCol="features", seed=0,
        )

    def getK(self) -> int:
        return self.getOrDefault(self.k)

    def getAlgorithm(self) -> str:
        return self.getOrDefault(self.algorithm)

    def getAlgoParams(self) -> Dict[str, Any]:
        return self.getOrDefault(self.algoParams)

    def getMetric(self) -> str:
        return self.getOrDefault(self.metric)

    def getInputCol(self) -> str:
        return self.getOrDefault(self.inputCol)

    def getIdCol(self) -> Optional[str]:
        return self.getOrDefault(self.idCol) if self.isDefined(self.idCol) else None

    def getSeed(self) -> int:
        return self.getOrDefault(self.seed)


class ApproximateNearestNeighbors(_ANNParams, Estimator, MLReadable):
    """``ApproximateNearestNeighbors().setK(8).setAlgoParams({"nlist": 64,
    "nprobe": 8}).fit(items).kneighbors(queries)``."""

    def __init__(self, uid: Optional[str] = None, mesh=None):
        super().__init__(uid)
        self.mesh = mesh

    def setMesh(self, mesh) -> "ApproximateNearestNeighbors":
        self.mesh = mesh
        return self

    def setK(self, value: int) -> "ApproximateNearestNeighbors":
        self.set(self.k, value)
        return self

    def setAlgorithm(self, value: str) -> "ApproximateNearestNeighbors":
        if value not in _ALGORITHMS:
            raise ValueError(f"algorithm must be one of {_ALGORITHMS}, got {value!r}")
        self.set(self.algorithm, value)
        return self

    def setAlgoParams(self, value: Dict[str, Any]) -> "ApproximateNearestNeighbors":
        known = {
            "nlist", "nprobe", "kmeans_iters", "M", "n_bits", "pq_iters",
            "refine_ratio",
        }
        unknown = set(value) - known
        if unknown:
            raise ValueError(f"unknown algoParams {sorted(unknown)}; known: {sorted(known)}")
        self.set(self.algoParams, value)
        return self

    def setMetric(self, value: str) -> "ApproximateNearestNeighbors":
        if value not in _METRICS:
            raise ValueError(f"metric must be one of {_METRICS}, got {value!r}")
        self.set(self.metric, value)
        return self

    def setInputCol(self, value: str) -> "ApproximateNearestNeighbors":
        self.set(self.inputCol, value)
        return self

    def setIdCol(self, value: str) -> "ApproximateNearestNeighbors":
        self.set(self.idCol, value)
        return self

    def setSeed(self, value: int) -> "ApproximateNearestNeighbors":
        self.set(self.seed, value)
        return self

    def fit(self, dataset: Any) -> "ApproximateNearestNeighborsModel":
        """Device arrays are indexed in place for the brute paths — no
        host round trip. IVF builds still pull the items
        to host ONCE (transiently) for the inverted-list packing, which
        is host-side by design (ops/ann.build_ivf_index).

        A RE-ITERABLE streaming source becomes a STREAMED brute index
        (``brute``/``brute_approx`` only): items never materialize — each
        search streams blocks through the running top-k merge, so item
        capacity is bounded by the source, not HBM.
        Inverted lists need the resident (compressed) index; the
        streaming-vs-ivfpq crossover is not measured on the chip."""
        from spark_rapids_ml_tpu.core.serving import configure_compile_cache

        configure_compile_cache()
        from spark_rapids_ml_tpu.core.data import (
            is_reiterable_stream,
            is_streaming_source,
        )

        if is_streaming_source(dataset):
            if not is_reiterable_stream(dataset):
                raise ValueError(
                    "a streamed ANN index needs a RE-ITERABLE source (a "
                    "zero-arg iterator factory or a block reader with "
                    ".iter_blocks()), not a one-shot generator"
                )
            if self.getAlgorithm() not in ("brute", "brute_approx"):
                raise ValueError(
                    "streamed indexes support brute/brute_approx only — "
                    "inverted lists are resident structures (use ivfpq "
                    "for compressed residency)"
                )
            if self.mesh is not None:
                raise ValueError(
                    "streamed indexes are single-device; use host "
                    "partitions + a mesh for the sharded index"
                )
            model = ApproximateNearestNeighborsModel(
                self.uid, None, None, items_stream=dataset
            )
            return self._copyValues(model)
        id_col = self.getIdCol()
        items = matrix_like(extract_features(dataset, self.getInputCol(), drop=id_col))
        ids = None
        if id_col is not None:
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
                if (
                    pd is not None
                    and isinstance(dataset, pd.DataFrame)
                    and id_col in dataset.columns
                ):
                    ids = dataset[id_col].to_numpy()
                else:
                    raise ValueError(
                        f"idCol={id_col!r} set, but the dataset has no such column"
                    )
        if self.getK() > items.shape[0]:
            raise ValueError(f"k={self.getK()} exceeds item count {items.shape[0]}")
        model = ApproximateNearestNeighborsModel(
            self.uid, items, ids, mesh=self.mesh
        )
        model = self._copyValues(model)
        if model.getAlgorithm() in ("ivfflat", "ivfpq"):
            with TraceRange("ann build index", TraceColor.YELLOW):
                model._build_index()
        return model


class ApproximateNearestNeighborsModel(_ANNParams, Model, LazyHostState):
    """Indexed item set; ``kneighbors`` probes the IVF lists.

    With a mesh, queries shard over the data axis against the replicated
    index (:func:`ops.ann.ann_search_sharded`)."""

    def __init__(
        self,
        uid: Optional[str] = None,
        items: Optional[np.ndarray] = None,
        ids: Optional[np.ndarray] = None,
        mesh=None,
        items_stream=None,
    ):
        super().__init__(uid)
        self.mesh = mesh
        self._items_stream = items_stream  # re-iterable beyond-HBM index
        # Device-fitted items stay resident; the host view (`items`)
        # converts lazily.
        self._items_raw = (
            items if items is None or is_device_array(items) else np.asarray(items)
        )
        self._items_np: Optional[np.ndarray] = None
        self.ids = None if ids is None else np.asarray(ids)
        self._index: Optional[IVFIndex | IVFPQIndex] = None
        self._items_dev = None  # cached device copy of _search_items()
        self._sharded_brute = None  # cached (items_sharded, mask) for brute+mesh

    # Host views convert lazily; pickling materializes host state and
    # drops the device-side caches (index, sharded copies — rebuilt
    # lazily after load). core/lazy_state.LazyHostState.
    _lazy_host_fields = {"_items_raw": ("_items_np", None)}
    _pickle_clear = ("_items_dev", "_sharded_brute", "_index")

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

    def setMesh(self, mesh) -> "ApproximateNearestNeighborsModel":
        self.mesh = mesh
        self._sharded_brute = None
        return self

    def _effective_nlist(self) -> int:
        n = self.items.shape[0]
        nlist = self.getAlgoParams().get("nlist")
        if nlist is None:
            # cuML-style default: ~sqrt(n) lists, at least 1.
            nlist = max(1, int(np.sqrt(n)))
        return min(int(nlist), n)

    def _effective_nprobe(self, n_lists: int) -> int:
        nprobe = self.getAlgoParams().get("nprobe")
        if nprobe is None:
            nprobe = max(1, n_lists // 8)
        return min(int(nprobe), n_lists)

    def _search_items(self) -> np.ndarray:
        # IVF list packing is host-side by design (ops/ann.py); a device-
        # fitted model pays this pull ONCE at build time as a transient —
        # not through the `items` property, which would retain a second
        # permanent host copy of a matrix already resident in HBM.
        raw = self._items_raw
        host = np.asarray(raw) if is_device_array(raw) else self.items
        items = host.astype(_dtype(), copy=False)
        return _normalize(items) if self.getMetric() == "cosine" else items

    def _search_items_device(self):
        """Device copy of the (normalized) items, computed once — repeated
        kneighbors calls must not redo the O(n*d) host normalize+transfer.
        Device-fitted items normalize on device (no host round trip)."""
        if self._items_dev is None:
            raw = self._items_raw
            if is_device_array(raw):
                it = raw.astype(_dtype())
                if self.getMetric() == "cosine":
                    it = it / jnp.maximum(
                        jnp.linalg.norm(it, axis=1, keepdims=True), 1e-30
                    )
                self._items_dev = it
            else:
                self._items_dev = jnp.asarray(self._search_items())
        return self._items_dev

    def _effective_m(self, d: int) -> int:
        m = self.getAlgoParams().get("M")
        if m is not None:
            # An EXPLICIT M must divide d — silently retuning a user's
            # compression setting would contradict build_ivfpq_index, which
            # raises for the same input.
            return int(m)
        # cuML-style auto default: ~d/4-dim subspaces, nudged to divide d.
        m = max(1, d // 4)
        while m > 1 and d % m != 0:
            m -= 1
        return m

    def _build_index(self) -> None:
        # With a mesh, the BUILD is distributed too: the coarse quantizer
        # and PQ codebook Lloyds shard their rows over the data axis
        # (previously only the search side was sharded).
        params = self.getAlgoParams()
        if self.getAlgorithm() == "ivfpq":
            self._index = build_ivfpq_index(
                self._search_items(),
                n_lists=self._effective_nlist(),
                m_subspaces=self._effective_m(self.items.shape[1]),
                n_bits=int(params.get("n_bits", 8)),
                seed=self.getSeed(),
                kmeans_iters=int(params.get("kmeans_iters", 10)),
                pq_iters=int(params.get("pq_iters", 10)),
                mesh=self.mesh,
            )
        else:
            self._index = build_ivf_index(
                self._search_items(),
                n_lists=self._effective_nlist(),
                seed=self.getSeed(),
                kmeans_iters=int(params.get("kmeans_iters", 10)),
                mesh=self.mesh,
            )

    def kneighbors(
        self, queries: Any, k: Optional[int] = None
    ) -> Tuple[np.ndarray, np.ndarray]:
        """(distances (nq, k), indices (nq, k)) under the configured metric.

        Unfilled slots when the probed lists hold fewer than k real
        candidates are (inf, -1); raise nprobe/nlist to avoid them.
        """
        if self._items_stream is not None:
            return self._kneighbors_streamed(queries, k)
        if self._items_raw is None:
            raise RuntimeError("model has no indexed items")
        n_items = int(self._items_raw.shape[0])
        k = self.getK() if k is None else k
        if not 1 <= k <= n_items:
            raise ValueError(f"k must be in [1, {n_items}], got {k}")
        metric = self.getMetric()
        q_in = matrix_like(
            extract_features(queries, self.getInputCol(), drop=self.getIdCol())
        )
        device_q = is_device_array(q_in)
        if device_q:
            # Device queries stay resident: normalize on device, results
            # return as device arrays.
            q = q_in.astype(_dtype())
            if metric == "cosine":
                q = q / jnp.maximum(
                    jnp.linalg.norm(q, axis=1, keepdims=True), 1e-30
                )
        else:
            q = np.asarray(q_in).astype(_dtype(), copy=False)
            if metric == "cosine":
                q = _normalize(q)

        with TraceRange("ann search", TraceColor.PURPLE):
            if self.getAlgorithm() in ("brute", "brute_approx"):
                # knn's sqeuclidean output matches ivf_search's; the shared
                # metric post-processing below then applies to both paths.
                if self.mesh is not None:
                    # Items shard over the mesh (memory / device count),
                    # exactly as NearestNeighborsModel does.
                    if self._sharded_brute is None:
                        self._sharded_brute = shard_items(
                            self._search_items(), self.mesh
                        )
                    xs, mask = self._sharded_brute
                    d2_j, idx_j = knn_sharded(
                        jnp.asarray(q, dtype=xs.dtype), xs, mask, self.mesh,
                        k=k,
                        approx=self.getAlgorithm() == "brute_approx",
                    )
                else:
                    d2_j, idx_j = knn(
                        jnp.asarray(q), self._search_items_device(), k=k,
                        metric="sqeuclidean",
                        approx=self.getAlgorithm() == "brute_approx",
                    )
            else:
                if self._index is None:
                    self._build_index()
                n_probe = self._effective_nprobe(self._index.n_lists)

                def _fetch(k_fetch: int):
                    if self.mesh is not None:
                        # Queries shard over the mesh against the
                        # replicated index; results are per-query, so no
                        # cross-device merge is needed.
                        return ann_search_sharded(
                            self.mesh, self._index, jnp.asarray(q),
                            k=k_fetch, n_probe=n_probe,
                        )
                    return dispatch_search(self._index)(
                        self._index, jnp.asarray(q), k=k_fetch, n_probe=n_probe
                    )

                if isinstance(self._index, IVFPQIndex):
                    # Refine (FAISS IndexRefineFlat / cuML refine_ratio):
                    # over-fetch candidates under the quantized metric, then
                    # re-rank that shortlist with exact distances — recovers
                    # most of the recall PQ noise costs, at k*ratio exact
                    # distance computations per query.
                    ratio = int(self.getAlgoParams().get("refine_ratio", 1))
                    k_fetch = min(max(k * max(ratio, 1), k), n_items)
                    d2_j, idx_j = _fetch(k_fetch)
                    if k_fetch > k:
                        d2_j, idx_j = _refine_exact(
                            jnp.asarray(q),
                            self._search_items_device(),
                            idx_j,
                            k,
                        )
                else:
                    d2_j, idx_j = _fetch(k)

        if device_q:
            # Device in, device out — metric post-processing on device.
            if metric == "euclidean":
                return jnp.sqrt(d2_j), idx_j
            if metric == "cosine":
                return d2_j / 2.0, idx_j
            return d2_j, idx_j
        d2, idx = np.asarray(d2_j), np.asarray(idx_j)
        if metric == "euclidean":
            return np.sqrt(d2), idx
        if metric == "cosine":
            return d2 / 2.0, idx
        return d2, idx

    def _kneighbors_streamed(self, queries: Any, k: Optional[int]):
        """Beyond-HBM search: one pass over the streamed item blocks with
        a running (approximate) top-k merge."""
        from spark_rapids_ml_tpu.core.data import iter_stream_blocks
        from spark_rapids_ml_tpu.ops.knn import knn_host_streamed

        k = self.getK() if k is None else k
        metric = self.getMetric()
        q_in = matrix_like(
            extract_features(queries, self.getInputCol(), drop=self.getIdCol())
        )
        device_q = is_device_array(q_in)
        qj = (
            q_in.astype(_dtype())
            if device_q
            else jnp.asarray(np.asarray(q_in).astype(_dtype(), copy=False))
        )
        with TraceRange("ann streamed search", TraceColor.PURPLE):
            d, idx = knn_host_streamed(
                qj,
                iter_stream_blocks(self._items_stream),
                k=k,
                metric="sqeuclidean" if metric != "cosine" else "cosine",
                approx=self.getAlgorithm() == "brute_approx",
            )
            if metric == "euclidean":
                d = jnp.sqrt(d)
        if device_q:
            return d, idx
        return np.asarray(d), np.asarray(idx)

    def kneighbors_ids(self, queries: Any, k: Optional[int] = None):
        """(distances, ids) mapped through the fitted idCol; -1 slots stay -1."""
        d, idx = self.kneighbors(queries, k)
        if self.ids is None:
            return d, idx
        mapped = np.where(idx >= 0, self.ids[np.clip(idx, 0, None)], -1)
        return d, mapped

    def transform(self, dataset: Any) -> Any:
        """Append neighbor indices + distances columns (DataFrame input)."""
        d, idx = self.kneighbors(dataset)
        if isinstance(dataset, DataFrame):
            out = dataset.withColumn("ann_indices", list(idx))
            return out.withColumn("ann_distances", list(d))
        try:
            import pandas as pd

            if isinstance(dataset, pd.DataFrame):
                out = dataset.copy()
                out["ann_indices"] = list(idx)
                out["ann_distances"] = list(d)
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
            class_name="com.nvidia.rapids.ml.ApproximateNearestNeighborsModel",
            extra_metadata={"hasIds": self.ids is not None},
        )
        cols = {"item": ("vector", [r for r in self.items])}
        if self.ids is not None:
            cols["id"] = ("scalar", self.ids.tolist())
        save_rows(path, cols)

    @classmethod
    def _load_impl(cls, path: str) -> "ApproximateNearestNeighborsModel":
        metadata = load_metadata(path, expected_class="ApproximateNearestNeighborsModel")
        rows = load_rows(path)
        items = np.stack(rows["item"])
        ids = np.asarray(rows["id"]) if metadata.get("hasIds") else None
        model = cls(metadata["uid"], items, ids)
        get_and_set_params(model, metadata)
        # The index is rebuilt lazily on first kneighbors; deterministic
        # given (items, nlist, seed), so probing matches the saved model.
        return model

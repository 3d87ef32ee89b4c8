"""Input data handling: vectors, partitions, and a minimal DataFrame shim.

The reference consumes a Spark DataFrame with a Vector column and immediately
lowers it to ``RDD[Vector]`` (reference RapidsPCA.scala:114-116); rows may be
dense or sparse and both must produce identical results (PCASuite.scala:155-190,
the dense/sparse equivalence test). Partitions are the unit of data parallelism
(RapidsRowMatrix.scala:170).

Here the native representations are:
  - ``numpy.ndarray`` (n, d)            — a single dense partition
  - ``scipy.sparse`` matrix             — sparse rows, densified per block
  - ``pandas.DataFrame`` + input column — column of array-likes / SparseVector
  - ``list`` of any of the above        — explicit partitions (the RDD analogue)
  - ``DataFrame`` shim below            — named columns over the same storage

Everything funnels through :func:`as_partitions`, which yields dense row-major
float blocks — the same contract as the reference's per-partition
"concat rows -> row-major DenseMatrix B" step (RapidsRowMatrix.scala:183-189),
but vectorized instead of per-row JVM loops.
"""

from __future__ import annotations

from typing import Any, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from spark_rapids_ml_tpu.utils.envknobs import env_int

try:  # scipy is available in the image; gate anyway for safety
    import scipy.sparse as _sp
except ImportError:  # pragma: no cover
    _sp = None

#: Default rows per block for the streaming-fit readers below (matches the
#: serving stream block: one block resident on device at a time).
DEFAULT_FIT_BLOCK_ROWS = 65536

FIT_BLOCK_ROWS_ENV = "TPUML_FIT_BLOCK_ROWS"


def fit_block_rows(
    family: Optional[str] = None,
    *,
    width: Optional[int] = None,
    itemsize: int = 4,
) -> int:
    """Rows per block for the fit-path block readers (``TPUML_FIT_BLOCK_ROWS``):
    the block size auto-degraded streaming fits start from, and the default
    batch size :class:`ArrowBlockReader` reads parquet at.

    An explicitly set env knob always wins. Otherwise, when the
    ledger-driven autotuner is on (``TPUML_AUTOTUNE=on``), the DEFAULT is
    replaced by the tuner's recommendation for ``family`` — the largest
    block fitting measured HBM headroom, or a committed tune-store
    decision — sized with ``width``/``itemsize`` when the caller knows
    the matrix shape. Off (the default) is today's value bit-for-bit."""
    import os as _os

    if _os.environ.get(FIT_BLOCK_ROWS_ENV) is not None:
        return env_int(FIT_BLOCK_ROWS_ENV, DEFAULT_FIT_BLOCK_ROWS, minimum=1)
    from spark_rapids_ml_tpu.observability import autotune as _autotune

    tuner = _autotune.active()
    if tuner is None:
        return DEFAULT_FIT_BLOCK_ROWS
    return tuner.recommend_block_rows(
        family or "fit",
        default=DEFAULT_FIT_BLOCK_ROWS,
        width=width,
        itemsize=itemsize,
    )


class SparseVector:
    """Spark-ML-style sparse vector: (size, indices, values)."""

    __slots__ = ("size", "indices", "values")

    def __init__(self, size: int, indices: Sequence[int], values: Sequence[float]):
        self.size = int(size)
        self.indices = np.asarray(indices, dtype=np.int32)
        self.values = np.asarray(values, dtype=np.float64)
        if self.indices.shape != self.values.shape:
            raise ValueError("indices and values must have the same length")

    def toArray(self) -> np.ndarray:
        out = np.zeros(self.size, dtype=np.float64)
        out[self.indices] = self.values
        return out

    def __len__(self) -> int:
        return self.size

    def __repr__(self) -> str:
        return f"SparseVector({self.size}, {self.indices.tolist()}, {self.values.tolist()})"


class DenseVector:
    """Spark-ML-style dense vector (thin ndarray wrapper for API parity)."""

    __slots__ = ("values",)

    def __init__(self, values: Sequence[float]):
        self.values = np.asarray(values, dtype=np.float64)

    def toArray(self) -> np.ndarray:
        return self.values

    def __len__(self) -> int:
        return self.values.shape[0]

    def __repr__(self) -> str:
        return f"DenseVector({self.values.tolist()})"


def Vectors_dense(*values) -> DenseVector:
    if len(values) == 1 and isinstance(values[0], (list, tuple, np.ndarray)):
        return DenseVector(values[0])
    return DenseVector(values)


def Vectors_sparse(size: int, indices, values) -> SparseVector:
    return SparseVector(size, indices, values)


class Vectors:
    """Namespace matching org.apache.spark.ml.linalg.Vectors factory methods."""

    dense = staticmethod(Vectors_dense)
    sparse = staticmethod(Vectors_sparse)


def _row_to_array(row: Any) -> np.ndarray:
    if isinstance(row, (SparseVector, DenseVector)):
        return row.toArray()
    if _sp is not None and _sp.issparse(row):
        return np.asarray(row.todense()).ravel()
    return np.asarray(row, dtype=np.float64).ravel()


def is_device_array(data: Any) -> bool:
    """True for ``jax.Array`` inputs — the device-resident fast path: the
    estimators consume the array in place (no host round-trip, no float64
    coercion, whole fit as one XLA program). numpy arrays are NOT device
    arrays — they take the partition path. This is the input mode the
    reference cannot express (every JNI call copies host arrays,
    rapidsml_jni.cu:112,179), and the one the benchmark's
    ``device_rows`` cells hand to ``fit`` (PERF.md section 4).
    """
    try:
        import jax
    except ImportError:  # pragma: no cover
        return False
    return isinstance(data, jax.Array)


def infer_input_dtype(data: Any):
    """Best-effort dtype of the USER's raw feature container, inspected
    BEFORE the densification pipeline (``as_partitions``/``as_matrix``)
    coerces everything to float64.

    Drives ``precision="auto"`` routing: only genuinely-fp64 sources should
    pay for fp64 emulation on fp32 hardware. Python floats and the Vectors
    types report float64 (they ARE double, matching Spark's all-``double``
    vectors); numpy / scipy / pandas containers report their own floating
    dtype; integer/bool containers and opaque iterators report None (not
    double data — undeterminable or never worth emulation).
    """
    if isinstance(data, np.ndarray):
        return data.dtype if np.issubdtype(data.dtype, np.floating) else None
    if is_device_array(data):
        dt = np.dtype(data.dtype)
        return dt if np.issubdtype(dt, np.floating) else None
    if _sp is not None and _sp.issparse(data):
        return data.dtype if np.issubdtype(data.dtype, np.floating) else None
    if isinstance(data, (SparseVector, DenseVector)):
        return np.float64
    if isinstance(data, float):
        return np.float64
    if callable(getattr(data, "iter_blocks", None)) and hasattr(data, "dtype"):
        # Block-reader objects (e.g. native.NpyBlockReader) know their dtype.
        try:
            dt = np.dtype(data.dtype)
        except TypeError:
            return None
        return dt if np.issubdtype(dt, np.floating) else None
    try:
        import pandas as pd

        def _np_dtype(d):
            # Extension dtypes (Float64Dtype, Categorical, ...) are not
            # numpy dtypes; most float-like ones expose numpy_dtype.
            try:
                return np.dtype(d)
            except TypeError:
                return getattr(d, "numpy_dtype", None)

        if isinstance(data, (pd.DataFrame, pd.Series)):
            if isinstance(data, pd.Series):
                first = data.iloc[0] if len(data) else None
                if first is not None and not np.isscalar(first):
                    return infer_input_dtype(first)
                dts = [data.dtype]
            else:
                dts = list(data.dtypes)
            mapped = [_np_dtype(d) for d in dts]
            if any(d == np.float64 for d in mapped if d is not None):
                return np.float64
            if any(d == np.float32 for d in mapped if d is not None):
                return np.float32
            return None
    except ImportError:  # pragma: no cover
        pass
    if isinstance(data, (list, tuple)):
        return infer_input_dtype(data[0]) if len(data) else None
    return None


def _block_to_dense(block: Any, dtype=None) -> np.ndarray:
    """Convert one partition-like object to a dense (rows, d) float array.

    ``dtype=None`` keeps the historical contract (float64, the reference's
    ``double[]`` surface); passing a dtype avoids the intermediate float64
    copy for float32 sources (stop coercing f32 host
    sources to f64 on their way to an f32 device)."""
    dt = np.float64 if dtype is None else np.dtype(dtype)
    if isinstance(block, np.ndarray):
        if block.ndim == 1:
            return block[None, :].astype(dt, copy=False)
        return np.ascontiguousarray(block, dtype=dt)
    if _sp is not None and _sp.issparse(block):
        return np.asarray(block.todense(), dtype=dt)
    if isinstance(block, (SparseVector, DenseVector)):
        return _row_to_array(block)[None, :].astype(dt, copy=False)
    # iterable of rows
    rows = [_row_to_array(r) for r in block]
    if not rows:
        return np.zeros((0, 0), dtype=dt)
    return np.stack(rows).astype(dt, copy=False)


class DataFrame:
    """Minimal named-column frame so estimator code reads like Spark ML.

    Columns are stored as-is (list/array of rows, or partition lists). A
    pyspark adapter with the same surface lives in
    :mod:`spark_rapids_ml_tpu.spark` (gated on pyspark availability).
    """

    def __init__(self, columns: Optional[dict] = None):
        self._columns: dict = dict(columns or {})

    @classmethod
    def from_rows(cls, rows: Iterable[Tuple], schema: Sequence[str]) -> "DataFrame":
        cols: dict = {name: [] for name in schema}
        for row in rows:
            for name, value in zip(schema, row):
                cols[name].append(value)
        return cls(cols)

    @property
    def columns(self) -> List[str]:
        return list(self._columns)

    def select(self, name: str):
        if name not in self._columns:
            raise KeyError(f"no column {name!r}; have {self.columns}")
        return self._columns[name]

    def withColumn(self, name: str, values) -> "DataFrame":
        cols = dict(self._columns)
        cols[name] = values
        return DataFrame(cols)

    def count(self) -> int:
        first = next(iter(self._columns.values()))
        return len(first)

    def collect(self) -> List[tuple]:
        names = self.columns
        return list(zip(*(self._columns[n] for n in names)))


def extract_column(dataset: Any, input_col: Optional[str]) -> Any:
    """Pull the raw vector column out of whatever ``dataset`` is."""
    if isinstance(dataset, DataFrame):
        if input_col is None:
            raise ValueError("inputCol must be set for DataFrame input")
        return dataset.select(input_col)
    try:
        import pandas as pd

        if isinstance(dataset, pd.DataFrame):
            if input_col is not None and input_col in dataset.columns:
                return dataset[input_col].tolist()
            if input_col is not None:
                raise KeyError(f"no column {input_col!r} in pandas DataFrame")
            # No input column: treat the frame itself as the feature matrix
            # (iterating a DataFrame would yield column labels, not rows).
            return dataset.to_numpy(dtype=np.float64)
    except ImportError:  # pragma: no cover
        pass
    return dataset


def extract_features(dataset: Any, col: str, drop: Optional[str] = None) -> Any:
    """Feature extraction shared by the estimators (the single home of the
    dispatch convention — keep models importing this rather than forking it):
    DataFrame shim selects ``col``; pandas uses ``col`` if present, else
    treats the frame (minus the optional ``drop`` column, e.g. a row-id)
    as a bare feature matrix; arrays/lists pass through."""
    if isinstance(dataset, DataFrame):
        return dataset.select(col)
    try:
        import pandas as pd

        if isinstance(dataset, pd.DataFrame):
            if col in dataset.columns:
                return extract_column(dataset, col)
            keep = [c for c in dataset.columns if c != drop]
            return dataset[keep].to_numpy(dtype=np.float64)
    except ImportError:  # pragma: no cover
        pass
    return dataset


def as_partitions(
    data: Any, num_partitions: Optional[int] = None, dtype=None
) -> List[np.ndarray]:
    """Normalize input into a list of dense (rows_i, d) float partitions
    (float64 by default; pass ``dtype`` to place narrower sources without
    an intermediate widening copy).

    ``list``/``tuple`` of 2-D blocks is treated as pre-partitioned (the RDD
    analogue); anything else becomes one partition, optionally re-split into
    ``num_partitions`` roughly equal row blocks.
    """
    parts = [_block_to_dense(b, dtype=dtype) for b in partition_blocks(data)]
    d = parts[0].shape[1]
    for p in parts:
        if p.shape[1] != d:
            raise ValueError(f"inconsistent feature dims: {p.shape[1]} vs {d}")
    if num_partitions is not None and len(parts) == 1 and num_partitions > 1:
        parts = [np.ascontiguousarray(b) for b in np.array_split(parts[0], num_partitions)]
    return parts


def partition_blocks(data: Any) -> Sequence[Any]:
    """The blocks :func:`as_partitions` densifies one by one: the items of
    a pre-partitioned ``list``/``tuple`` of 2-D blocks, else ``data``
    itself as the only one."""
    if isinstance(data, (list, tuple)) and data and _is_block(data[0]):
        return data
    return [data]


def _is_block(obj: Any) -> bool:
    if isinstance(obj, np.ndarray) and obj.ndim == 2:
        return True
    if _sp is not None and _sp.issparse(obj):
        return True
    return False


def is_streaming_source(data: Any) -> bool:
    """True for inputs that stream blocks instead of materializing: a block
    iterator/generator (one-shot), a block-reader object exposing
    ``iter_blocks`` (re-iterable, e.g. ``native.NpyBlockReader``), or a
    zero-arg callable returning a block iterator (an iterator factory).
    These fit at constant memory — one block resident at a time — via the
    estimators' one-pass shifted accumulation paths."""
    from collections.abc import Iterator

    if isinstance(data, Iterator):
        return True
    if callable(getattr(data, "iter_blocks", None)):
        return True
    if callable(data) and not isinstance(data, type):
        return _is_zero_arg_callable(data)
    return False


def _is_zero_arg_callable(fn: Any) -> bool:
    """True when ``fn()`` is callable without arguments — the iterator-
    factory contract. A callable that REQUIRES arguments is not a stream
    factory; classifying it as one would die later inside the multi-pass
    paths with an opaque TypeError, so probe the signature up front
    (builtins without introspectable signatures pass through as factories)."""
    import inspect

    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):  # no introspectable signature
        return True
    for p in sig.parameters.values():
        if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD, p.KEYWORD_ONLY):
            if p.default is p.empty:
                return False
    return True


def is_reiterable_stream(data: Any) -> bool:
    """True for streaming sources that can be iterated MORE THAN ONCE — a
    block-reader object (``iter_blocks``) or an iterator factory (zero-arg
    callable). One-shot generators are streaming but not re-iterable:
    multi-pass algorithms (the randomized sketch) need these."""
    if callable(getattr(data, "iter_blocks", None)):
        return True
    from collections.abc import Iterator

    return (
        callable(data)
        and not isinstance(data, (type, Iterator))
        and _is_zero_arg_callable(data)
    )


def peek_stream_width(data: Any) -> int:
    """Feature width of a RE-ITERABLE streaming source by reading one
    block from a FRESH iterator (cheap routing probe; never call on a
    one-shot generator — it would consume data)."""
    for blk in iter_stream_blocks(data):
        b = _block_to_dense(blk)
        if b.shape[0] > 0:
            return int(b.shape[1])
    raise ValueError("streaming source yielded no rows")


def iter_stream_blocks(data: Any):
    """Normalize a streaming source (see :func:`is_streaming_source`) to a
    fresh iterator of raw blocks."""
    from collections.abc import Iterator

    if isinstance(data, Iterator):
        return data
    if callable(getattr(data, "iter_blocks", None)):
        return data.iter_blocks()
    if callable(data):
        return iter(data())
    raise TypeError(f"not a streaming block source: {type(data).__name__}")


def as_matrix(data: Any, dtype=None) -> np.ndarray:
    """Normalize input into one dense (n, d) float matrix (float64 by
    default — the reference's ``double[]`` contract)."""
    parts = as_partitions(data, dtype=dtype)
    if len(parts) == 1:
        return parts[0]
    return np.concatenate(parts, axis=0)


def extract_weights(dataset: Any, weight_col: Optional[str]) -> Optional[np.ndarray]:
    """Optional per-row weight column (Spark's ``weightCol``).

    Returns None when no weight column is configured. Named-column
    containers only — a bare (X, y) tuple has no columns to resolve the
    name against, so configuring weightCol with one is an error rather
    than a silent ignore. Weights must be non-negative and not all zero.
    """
    if weight_col is None:
        return None
    w = None
    if isinstance(dataset, DataFrame):
        w = np.asarray(dataset.select(weight_col), dtype=np.float64)
    else:
        try:
            import pandas as pd

            if isinstance(dataset, pd.DataFrame):
                if weight_col not in dataset.columns:
                    raise KeyError(f"no column {weight_col!r} in pandas DataFrame")
                w = dataset[weight_col].to_numpy(dtype=np.float64)
        except ImportError:  # pragma: no cover
            pass
    if w is None:
        raise TypeError(
            f"weightCol={weight_col!r} requires a dataset with named columns "
            f"(DataFrame shim or pandas), got {type(dataset).__name__}"
        )
    w = w.ravel()
    # `not all(w >= 0)` (unlike `any(w < 0)`) also rejects NaN, which would
    # otherwise poison every weighted sum downstream.
    if not np.all(w >= 0):
        raise ValueError("weights must be non-negative and non-NaN")
    if not np.any(w > 0):
        raise ValueError("at least one weight must be positive")
    return w


def num_features(data: Any) -> int:
    """Feature count by PEEKING at the first partition/row only — never
    densifies the dataset (used for cheap routing decisions)."""
    if isinstance(data, np.ndarray) or is_device_array(data):
        return int(data.shape[1] if data.ndim == 2 else data.shape[0])
    if _sp is not None and _sp.issparse(data):
        return data.shape[1]
    if isinstance(data, (list, tuple)) and data:
        first = data[0]
        if _is_block(first):
            return first.shape[1]
        return len(_row_to_array(first))
    return as_partitions(data)[0].shape[1]


def host_rows_shape(data: Any) -> Optional[Tuple[int, int]]:
    """(n_rows, n_features) of a HOST input without densifying it — the
    cheap probe the fit memory gate prices from. Returns None when the
    shape cannot be known without materializing (then admission waves the
    input through rather than paying the copy it exists to avoid)."""
    if is_device_array(data):
        return None  # already resident on device; nothing left to admit
    if isinstance(data, np.ndarray):
        if data.ndim == 2:
            return (int(data.shape[0]), int(data.shape[1]))
        if data.ndim == 1:
            return (1, int(data.shape[0]))
        return None
    if _sp is not None and _sp.issparse(data):
        return (int(data.shape[0]), int(data.shape[1]))
    if isinstance(data, (SparseVector, DenseVector)):
        return (1, len(data.toArray()))
    if isinstance(data, (list, tuple)) and data:
        first = data[0]
        if _is_block(first):
            if any(not _is_block(p) for p in data):
                return None
            return (
                int(sum(p.shape[0] for p in data)),
                int(first.shape[1]),
            )
        try:
            return (len(data), len(_row_to_array(first)))
        except (TypeError, ValueError):
            return None
    return None


class HostArrayBlockReader:
    """Re-iterable block view over ONE host matrix — the degradation shim.

    When fit admission finds a host input over the device-memory budget,
    wrapping it in this reader re-enters the estimators' EXISTING
    streaming paths unchanged: blocks are row slices (numpy views, no
    copy), so the only memory cost is the one block resident on device at
    a time. Satisfies the streaming-source protocol
    (:func:`is_streaming_source` / :func:`is_reiterable_stream`) and
    exposes ``dtype`` for :func:`infer_input_dtype` precision probes.
    """

    def __init__(self, x: Any, block_rows: Optional[int] = None):
        self._x = np.asarray(x)
        if self._x.ndim != 2:
            raise ValueError(
                f"HostArrayBlockReader needs a 2-D matrix, got {self._x.ndim}-D"
            )
        self.block_rows = (
            int(block_rows)
            if block_rows
            else fit_block_rows(
                "fit.host_matrix",
                width=int(self._x.shape[1]),
                itemsize=int(self._x.dtype.itemsize),
            )
        )
        if self.block_rows < 1:
            raise ValueError("block_rows must be >= 1")

    @property
    def dtype(self):
        return self._x.dtype

    @property
    def shape(self) -> Tuple[int, int]:
        return (int(self._x.shape[0]), int(self._x.shape[1]))

    def iter_blocks(self) -> Iterable[np.ndarray]:
        for i in range(0, self._x.shape[0], self.block_rows):
            yield self._x[i : i + self.block_rows]


class ArrowBlockReader:
    """Re-iterable block reader over an on-disk parquet dataset — the
    first-class beyond-HBM fit input.

    Wraps ``pyarrow.dataset`` so a directory of parquet files (or a single
    file) feeds the streaming fit paths directly: ``fit(ArrowBlockReader(
    path))`` trains without ever materializing the dataset in host or
    device memory. Feature ``columns`` default to every column except
    ``exclude`` (pass the label column there); a single list-typed column
    (the Spark-style packed vector column) expands to its width. Labels
    ride along via :meth:`read_column`, which DOES materialize one column
    — labels are O(n), the 1/d-sized exception to the streaming rule.
    """

    def __init__(
        self,
        source: Any,
        columns: Optional[Sequence[str]] = None,
        *,
        block_rows: Optional[int] = None,
        dtype: Any = None,
        exclude: Sequence[str] = (),
    ):
        import pyarrow.dataset as pads

        self._ds = (
            source
            if isinstance(source, pads.Dataset)
            else pads.dataset(source, format="parquet")
        )
        schema = self._ds.schema
        if columns is None:
            columns = [c for c in schema.names if c not in set(exclude)]
        else:
            missing = [c for c in columns if c not in schema.names]
            if missing:
                raise KeyError(f"no such column(s) in dataset: {missing}")
        if not columns:
            raise ValueError("ArrowBlockReader needs at least one feature column")
        self.columns = list(columns)
        if dtype is not None:
            self._dtype = np.dtype(dtype)
        else:
            # Narrow only when EVERY feature column is float32; mixed or
            # wider schemas keep the float64 reference surface (and the
            # precision auto-resolution that hangs off the input dtype).
            import pyarrow as pa

            feats = [schema.field(c).type for c in self.columns]

            def _leaf(t):
                return t.value_type if pa.types.is_list(t) or pa.types.is_fixed_size_list(t) else t

            all_f32 = all(_leaf(t) == pa.float32() for t in feats)
            self._dtype = np.dtype(np.float32 if all_f32 else np.float64)
        # Width for tuned sizing: column count is a lower bound (a packed
        # vector column is wider) — good enough for the headroom estimate.
        self.block_rows = (
            int(block_rows)
            if block_rows
            else fit_block_rows(
                "fit.arrow",
                width=len(self.columns),
                itemsize=int(self._dtype.itemsize),
            )
        )

    @property
    def dtype(self):
        return self._dtype

    def num_rows(self) -> int:
        return int(self._ds.count_rows())

    def _column_to_numpy(self, chunk) -> np.ndarray:
        import pyarrow as pa

        t = chunk.type
        if pa.types.is_list(t) or pa.types.is_fixed_size_list(t):
            # Packed vector column: (rows, width) from the flat values.
            # flatten() (not .values) — a sliced batch shares the parent
            # buffer and .values would return the WHOLE column again.
            flat = np.asarray(chunk.flatten())
            if pa.types.is_list(t):
                widths = np.asarray(chunk.value_lengths())
                if widths.size and not np.all(widths == widths[0]):
                    raise ValueError("ragged list column cannot form a matrix")
                width = int(widths[0]) if widths.size else 0
            else:
                width = t.list_size
            return flat.reshape(-1, width)
        return np.asarray(chunk.to_numpy(zero_copy_only=False)).reshape(-1, 1)

    def iter_blocks(self) -> Iterable[np.ndarray]:
        for batch in self._ds.to_batches(
            columns=self.columns, batch_size=self.block_rows
        ):
            if batch.num_rows == 0:
                continue
            cols = [
                self._column_to_numpy(batch.column(i))
                for i in range(batch.num_columns)
            ]
            block = cols[0] if len(cols) == 1 else np.concatenate(cols, axis=1)
            yield np.ascontiguousarray(block, dtype=self._dtype)

    def read_column(self, name: str, dtype: Any = np.float64) -> np.ndarray:
        """One full column as a host array (label extraction)."""
        if name not in self._ds.schema.names:
            raise KeyError(f"no such column in dataset: {name!r}")
        tbl = self._ds.to_table(columns=[name])
        return np.asarray(tbl.column(0).to_numpy(zero_copy_only=False), dtype=dtype)

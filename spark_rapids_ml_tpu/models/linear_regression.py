"""LinearRegression estimator/model — Spark ML surface, normal-equation solver.

Param surface mirrors ``org.apache.spark.ml.regression.LinearRegression``:
``featuresCol``, ``labelCol``, ``predictionCol``, ``fitIntercept``,
``regParam``, ``elasticNetParam`` (0 -> Ridge via the exact normal-equation
solve; > 0 -> Lasso/elastic net via FISTA on the same sufficient
statistics — solver="normal" rejects it, as in Spark), ``maxIter`` and
``tol`` (the proximal loop's; the exact solve has no iteration),
``standardization``, ``solver`` ("normal" | "auto"). Beyond-the-reference
capability.

Objective, as Spark states it: minimise ``1/(2n) ||y - X b - b0||^2 +
regParam (alpha sum_j w1_j |b_j| + (1 - alpha)/2 sum_j w2_j b_j^2)``,
``w1 = sigma_j``, ``w2 = sigma_j^2`` under ``standardization`` (1
otherwise), the intercept ``b0 = mean(y) - mean(x)^T b`` unpenalised.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_ml_tpu.core.data import (
    DataFrame,
    as_matrix,
    extract_weights,
    is_device_array,
)
from spark_rapids_ml_tpu.core.estimator import Estimator, Model
from spark_rapids_ml_tpu.core.ingest import matrix_like, prepare_labels, prepare_rows
from spark_rapids_ml_tpu.core.lazy_state import LazyHostState
from spark_rapids_ml_tpu.core.params import Param, Params, toBoolean, toFloat, toInt, toString
from spark_rapids_ml_tpu.core.persistence import (
    MLReadable,
    get_and_set_params,
    load_data,
    load_metadata,
    save_data,
    save_metadata,
)
from spark_rapids_ml_tpu.ops.covariance import count_resident_blocks
from spark_rapids_ml_tpu.ops.linear import (
    FISTA_POWER_ITERS,
    Moments,
    moments_from_raw,
    normal_eq_stats,
    normal_eq_stats_streaming,
    predict_linear,
    raw_moments,
    regression_metrics,
    solve_elastic_net,
    solve_elastic_net_resumable,
    solve_normal,
    solve_normal_host,
)
from spark_rapids_ml_tpu.core.serving import note_device_cache, serve_rows
from spark_rapids_ml_tpu.utils.tracing import (
    StageRange,
    TraceColor,
    TraceRange,
    bump_counter,
)


def _predict_kernel(x, coef, intercept, *, precision: str = "highest"):
    """Serving kernel: X·coef + b. Coefficients follow the batch dtype
    (the model-side convention; the cast fuses into the GEMM).
    ``precision`` is the resolved serving-family policy mode
    (ops/precision.py) — static, so it keys the AOT program cache."""
    return predict_linear(
        x, coef.astype(x.dtype), intercept.astype(x.dtype),
        precision=precision,
    )


class _LinearRegressionParams(Params):
    featuresCol = Param("_", "featuresCol", "features column name", toString)
    labelCol = Param("_", "labelCol", "label column name", toString)
    predictionCol = Param("_", "predictionCol", "prediction column name", toString)
    fitIntercept = Param("_", "fitIntercept", "whether to fit an intercept", toBoolean)
    regParam = Param("_", "regParam", "L2 regularization strength", toFloat)
    elasticNetParam = Param("_", "elasticNetParam", "L1/L2 mixing (0 = pure L2)", toFloat)
    maxIter = Param("_", "maxIter", "maximum proximal (FISTA) iterations", toInt)
    tol = Param(
        "_", "tol", "convergence tolerance of the proximal iterations", toFloat
    )
    standardization = Param(
        "_", "standardization", "penalize standardized coefficients", toBoolean
    )
    solver = Param("_", "solver", "normal or auto", toString)
    weightCol = Param("_", "weightCol", "per-row weight column name", toString)
    precision = Param(
        "_",
        "precision",
        "auto | default | high | highest | dd (double-float fp64 emulation)",
        toString,
    )

    def __init__(self, uid: Optional[str] = None):
        super().__init__(uid)
        self._setDefault(
            featuresCol="features",
            labelCol="label",
            predictionCol="prediction",
            fitIntercept=True,
            regParam=0.0,
            elasticNetParam=0.0,
            maxIter=100,
            tol=1e-6,
            standardization=True,
            solver="auto",
            precision="auto",
        )

    def getFeaturesCol(self) -> str:
        return self.getOrDefault(self.featuresCol)

    def getLabelCol(self) -> str:
        return self.getOrDefault(self.labelCol)

    def getPredictionCol(self) -> str:
        return self.getOrDefault(self.predictionCol)

    def getFitIntercept(self) -> bool:
        return self.getOrDefault(self.fitIntercept)

    def getRegParam(self) -> float:
        return self.getOrDefault(self.regParam)

    def getElasticNetParam(self) -> float:
        return self.getOrDefault(self.elasticNetParam)

    def getMaxIter(self) -> int:
        return self.getOrDefault(self.maxIter)

    def getTol(self) -> float:
        return self.getOrDefault(self.tol)

    def getStandardization(self) -> bool:
        return self.getOrDefault(self.standardization)

    def getSolver(self) -> str:
        return self.getOrDefault(self.solver)

    def getWeightCol(self) -> Optional[str]:
        return (
            self.getOrDefault(self.weightCol)
            if self.isDefined(self.weightCol)
            else None
        )

    def getPrecision(self) -> str:
        return self.getOrDefault(self.precision)


class LinearRegression(_LinearRegressionParams, Estimator, MLReadable):
    """OLS / Ridge via the normal-equation GEMM path.

    ``LinearRegression().setRegParam(0.1).fit((X, y))`` — input is
    ``(X, y)``, a DataFrame shim / pandas frame with features+label columns.
    """

    # Consumes device (X, y) pairs in place, so tuning loops may feed
    # device-resident fold slices (tuning._device_fold_prep).
    _device_foldable = True

    def __init__(self, uid: Optional[str] = None, mesh=None):
        super().__init__(uid)
        self.mesh = mesh

    def setFeaturesCol(self, value: str) -> "LinearRegression":
        self.set(self.featuresCol, value)
        return self

    def setLabelCol(self, value: str) -> "LinearRegression":
        self.set(self.labelCol, value)
        return self

    def setPredictionCol(self, value: str) -> "LinearRegression":
        self.set(self.predictionCol, value)
        return self

    def setFitIntercept(self, value: bool) -> "LinearRegression":
        self.set(self.fitIntercept, value)
        return self

    def setRegParam(self, value: float) -> "LinearRegression":
        if value < 0:
            raise ValueError(f"regParam must be >= 0, got {value}")
        self.set(self.regParam, value)
        return self

    def setElasticNetParam(self, value: float) -> "LinearRegression":
        if not 0.0 <= value <= 1.0:
            raise ValueError(f"elasticNetParam must be in [0, 1], got {value}")
        self.set(self.elasticNetParam, value)
        return self

    def setMaxIter(self, value: int) -> "LinearRegression":
        """Proximal (FISTA) iterations at most; the exact normal-equation
        solve (``elasticNetParam`` 0 or ``regParam`` 0) has none."""
        if value < 0:
            raise ValueError(f"maxIter must be >= 0, got {value}")
        self.set(self.maxIter, value)
        return self

    def setTol(self, value: float) -> "LinearRegression":
        """The proximal loop stops once the widest change of a coefficient
        is at most ``tol`` times the widest coefficient (1 at least). A
        ``tol`` under the compute dtype's epsilon is read as "run
        ``maxIter`` iterations" (``ops/linear.py::_fista_loop``)."""
        if value < 0:
            raise ValueError(f"tol must be >= 0, got {value}")
        self.set(self.tol, value)
        return self

    def setStandardization(self, value: bool) -> "LinearRegression":
        self.set(self.standardization, value)
        return self

    def setSolver(self, value: str) -> "LinearRegression":
        if value not in ("normal", "auto"):
            raise ValueError(f"solver must be 'normal' or 'auto', got {value!r}")
        self.set(self.solver, value)
        return self

    def setWeightCol(self, value: str) -> "LinearRegression":
        self.set(self.weightCol, value)
        return self

    def setPrecision(self, value: str) -> "LinearRegression":
        """Matmul precision for the sufficient-statistics GEMMs. ``"dd"``
        emulates fp64 via double-float MXU GEMMs (ops.doubledouble) and
        solves the normal equations in host fp64 — the reference's
        ``double[]`` numerics (JniRAPIDSML.java:64-69) on fp32-only
        hardware; ``"auto"`` selects it for float64 input without x64."""
        from spark_rapids_ml_tpu.ops.linalg import validate_precision

        self.set(self.precision, validate_precision(value))
        return self

    def setMesh(self, mesh) -> "LinearRegression":
        self.mesh = mesh
        return self

    _initial_coef = None  # (d,) FISTA warm start, original space
    _copy_attrs = ("_initial_coef",)

    def setInitialModel(self, value) -> "LinearRegression":
        """Warm start the FISTA solve from an existing model's
        coefficients (or a raw ``(d,)`` array) — the incremental-refit
        seed (lifecycle/partial_fit.py). Applies to the elastic-net
        path; the exact normal-equation solve has no iteration to seed
        and rejects it at fit time."""
        coef = value.coefficients if hasattr(value, "coefficients") else value
        coef = np.asarray(coef, dtype=np.float64)
        if coef.ndim != 1:
            raise ValueError("initial model/coefficients must be a (d,) vector")
        self._initial_coef = coef
        return self

    def _uses_fista(self) -> bool:
        """True when the fit routes to the proximal (FISTA) solver rather
        than the exact normal-equation solve (see _solve_from_stats)."""
        return self.getElasticNetParam() > 0.0 and self.getRegParam() > 0.0

    def _raw_features_dtype(self, dataset):
        """Dtype of the raw user feature container, probed before any
        float64 coercion (core.data.infer_input_dtype) — the gate for
        precision='auto' dd routing."""
        from spark_rapids_ml_tpu.core.data import infer_input_dtype

        if isinstance(dataset, tuple) and len(dataset) == 2:
            return infer_input_dtype(dataset[0])
        if isinstance(dataset, DataFrame):
            return infer_input_dtype(dataset.select(self.getFeaturesCol()))
        try:
            import pandas as pd

            if isinstance(dataset, pd.DataFrame):
                fc = self.getFeaturesCol()
                if fc in dataset.columns:
                    return infer_input_dtype(dataset[fc])
                return infer_input_dtype(
                    dataset.drop(columns=[self.getLabelCol()], errors="ignore")
                )
        except ImportError:  # pragma: no cover
            pass
        return infer_input_dtype(dataset)

    def _resolved_precision(self, dataset) -> str:
        """Resolve the precision request to a concrete mode for this fit.
        Resolution policy lives in :meth:`RowMatrix.resolve` (the single
        home); this adds only the estimator-specific dd blockers: explicit
        ``precision='dd'`` raises on combinations that have no dd route
        (mesh, weightCol, FISTA); ``'auto'`` quietly falls back to
        ``'highest'`` for those."""
        from spark_rapids_ml_tpu.linalg.row_matrix import RowMatrix
        from spark_rapids_ml_tpu.ops.precision import resolve_policy

        requested = self.getPrecision()
        # Only "auto" needs the dtype probe; explicit values pass through.
        input_dtype = (
            self._raw_features_dtype(dataset) if requested == "auto" else None
        )
        # Mixed-precision policy layering (ops/precision.py): explicit
        # setPrecision > TPUML_PRECISION[_LINEAR] knobs > committed
        # autotune decision > the param default. fp64 input keeps its
        # pre-policy "auto" dd routing — the tuner never displaces fp64
        # emulation.
        explicit = self.getPrecision() if self.isSet(self.precision) else None
        wants_f64 = input_dtype is not None and np.dtype(input_dtype) == np.float64
        if explicit is None and wants_f64:
            explicit = "auto"
        requested = resolve_policy("linear", explicit, default=requested)
        resolved = RowMatrix.resolve(
            requested, mesh=self.mesh, input_dtype=input_dtype
        )
        if resolved != "dd":
            return resolved
        blockers = []
        if self.mesh is not None:
            blockers.append("a mesh (dd is single-device)")
        if self.getWeightCol() is not None:
            blockers.append("weightCol")
        if self._uses_fista():
            blockers.append("elastic net (FISTA)")
        if blockers:
            if requested == "dd":
                raise ValueError(
                    "precision='dd' does not support " + ", ".join(blockers)
                )
            return "highest"
        return "dd"

    def _fit_dd(self, block_pairs) -> "LinearRegressionModel":
        """Extended-precision fit: dd GEMM moments + host fp64 solve."""
        from spark_rapids_ml_tpu.ops.doubledouble import normal_eq_stats_dd

        with TraceRange("linreg dd fit", TraceColor.DARK_GREEN):
            xtx, xty, x_sum, y_sum, _, count = normal_eq_stats_dd(block_pairs)
            coef, intercept = solve_normal_host(
                xtx,
                xty,
                x_sum,
                y_sum,
                count,
                reg_param=self.getRegParam(),
                fit_intercept=self.getFitIntercept(),
                standardization=self.getStandardization(),
            )
        model = LinearRegressionModel(
            self.uid, np.asarray(coef, dtype=np.float64), float(intercept)
        )
        return self._copyValues(model)

    def _fit(self, dataset: Any) -> "LinearRegressionModel":
        if self.getElasticNetParam() > 0.0 and self.getSolver() == "normal":
            # Spark's normal solver rejects L1 the same way; validate before
            # any data movement or GEMM work.
            raise ValueError(
                "solver='normal' supports only L2 (elasticNetParam must "
                "be 0); use solver='auto' for elastic net"
            )
        streaming = None
        if self.mesh is None and self.getWeightCol() is None:
            streaming = _streaming_blocks(dataset)
        if streaming is not None:
            # Blocks (list or generator of (rows_i, d) arrays) accumulate
            # their sufficient statistics one block at a time — every solver
            # below consumes only the O(d^2) moments, so device memory is
            # bounded by one block (pairs with native.NpyBlockReader).
            # Precision resolution probes the dataset container, never the
            # stream, so the generator passes through unconsumed.
            prec = self._resolved_precision(dataset)
            if prec == "dd":
                return self._fit_dd(streaming)
            dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
            with TraceRange("linreg fit", TraceColor.DARK_GREEN):
                stats = normal_eq_stats_streaming(
                    streaming, dtype=dtype, precision=prec
                )
                solved = self._solve_from_stats(
                    moments_from_raw(*stats), stats[0].shape[0]
                )
            return self._copyValues(LinearRegressionModel(self.uid, *solved))

        x_in, y_in = _extract_xy(dataset, self.getFeaturesCol(), self.getLabelCol())
        w_host = extract_weights(dataset, self.getWeightCol())
        prec = self._resolved_precision(dataset)
        from spark_rapids_ml_tpu.core import membudget

        # Budgeted admission (core/membudget.py): an over-budget host
        # input reroutes through a block reader into the SAME streaming
        # sufficient-statistics branch above — bit-identical by
        # construction — and a device OOM mid-fit reclaims caches and
        # takes the same exit.
        can_stream = w_host is None
        guard = membudget.fit_memory_guard(
            "linear", x_in, can_stream=can_stream,
            why_cannot_stream="the streaming path does not support weightCol",
            mesh=self.mesh, ledger_families=("linear", "linreg"),
        )
        if guard.degrade:
            return membudget.run_streaming_with_recovery(
                "linear", lambda r: self._fit((r, y_in)), guard.matrix
            )
        fallback = (
            (lambda: membudget.run_streaming_with_recovery(
                "linear", lambda r: self._fit((r, y_in)),
                membudget.host_matrix(x_in)))
            if can_stream and self.mesh is None else None
        )
        return membudget.run_fit_with_oom_recovery(
            "linear", lambda: self._fit_in_memory(x_in, y_in, w_host, prec),
            fallback,
        )

    def _fit_in_memory(self, x_in, y_in, w_host, prec) -> "LinearRegressionModel":
        if prec == "dd":
            if is_device_array(x_in):
                # Same stance as PCA: dd operands split on HOST fp64 — a
                # device array has no fp64 bits left to split.
                raise ValueError(
                    "precision='dd' does not support device-array input "
                    "(the hi/lo split consumes the host fp64 source)"
                )
            return self._fit_dd([(x_in, y_in)])

        with TraceRange("linreg fit", TraceColor.DARK_GREEN):
            # One funnel for every residence: device arrays fit in place
            #, host data places once, dtype-preserving.
            xs, mask, n, d = prepare_rows(x_in, mesh=self.mesh, weights=w_host)
            ys = prepare_labels(
                y_in, int(xs.shape[0]), n_true=n, mesh=self.mesh, dtype=xs.dtype
            )
            # The stage times the dispatches (the blocked sum, then the
            # solve): nothing here waits for the device.
            with StageRange("solve"):
                with TraceRange("linreg moments", TraceColor.GREEN):
                    if self.mesh is None:
                        # every real row at weight 1: no per-row weight
                        if w_host is None:
                            mask = None
                        count_resident_blocks(int(xs.shape[0]))
                        moments = normal_eq_stats(xs, ys, mask, precision=prec)
                    else:
                        # rows sharded over a mesh: one contraction a chip
                        # and XLA's psum, until the four-chip cell
                        # (ROADMAP.md Reach 1)
                        moments = moments_from_raw(
                            *raw_moments(xs, ys, mask, precision=prec)
                        )
                # Gang deploy mode: the solve below reads the O(d²)
                # statistics on the host — replicate them so every member
                # solves the identical whole-dataset normal equations
                # (no-op otherwise).
                from spark_rapids_ml_tpu.parallel.distributed import (
                    replicate_for_host,
                )

                moments = Moments(*replicate_for_host(self.mesh, *moments))
                solved = self._solve_from_stats(moments, d)

        # Solve outputs stay device-resident; the model's host float64
        # views convert lazily (the PCAModel contract).
        return self._copyValues(LinearRegressionModel(self.uid, *solved))

    def _solve_from_stats(self, moments, d: int) -> tuple:
        """Dispatch the solver on the accumulated sufficient statistics —
        the one home of the exact-vs-proximal routing (shared by the
        in-memory, mesh, and streaming fit paths). Returns the model's
        arguments after its uid: (coefficients, intercept) from the exact
        solve, and (numIter, finalObjective, finalGradient) after them
        from the proximal one."""
        moments = moments.narrowed(d)
        init_coef = self._initial_coef
        if init_coef is not None and init_coef.shape[0] != d:
            raise ValueError(
                f"initial model has {init_coef.shape[0]} coefficients, "
                f"data has {d} features"
            )
        solver_args = dict(
            reg_param=self.getRegParam(),
            fit_intercept=self.getFitIntercept(),
            standardization=self.getStandardization(),
        )
        if not self._uses_fista():
            if init_coef is not None:
                raise ValueError(
                    "setInitialModel warm start applies to the elastic-net "
                    "(FISTA) path (elasticNetParam > 0 and regParam > 0); "
                    "the exact normal-equation solve has no iteration to seed"
                )
            # Zero effective penalty: the exact (Cholesky) solve, not a
            # fixed-step proximal approximation of the same objective.
            return solve_normal(moments, **solver_args)
        # L1/elastic net: FISTA on the same sufficient statistics — one
        # blocked pass over the data, then O(d^2) proximal iterations
        # (Spark reaches this case via OWL-QN over the data). With the
        # TPUML_CHECKPOINT_* knobs set the proximal loop runs segmented
        # with async snapshots and resumes mid-solve
        # (robustness/checkpoint.py); the iterative loop — not the one
        # stats pass — is what preemption loses.
        solver_args.update(
            elastic_net_param=self.getElasticNetParam(),
            max_iter=self.getMaxIter(),
            tol=self.getTol(),
            init_coef=init_coef,
        )
        ckpt = self._fit_checkpointer("linreg.fista", data=tuple(moments))
        bump_counter("linreg.fista.power_iters", FISTA_POWER_ITERS)
        with TraceRange("linreg prox", TraceColor.PURPLE):
            if ckpt is not None:
                return tuple(
                    solve_elastic_net_resumable(
                        moments, checkpointer=ckpt, mesh=self.mesh, **solver_args
                    )
                )
            return tuple(solve_elastic_net(moments, **solver_args))


def _streaming_blocks(dataset):
    """Detect the streaming input form: ``(X, y)`` where X is a list of 2-D
    blocks (dense or scipy-sparse) or any iterator of them (e.g.
    ``NpyBlockReader.iter_blocks()``). Returns an iterator of
    (X_block, y_block) pairs, or None when the input is not block-shaped.

    A single y array is sliced along the block boundaries and must match
    the total row count exactly; a list of per-block label arrays must have
    one entry per block — both mismatches raise instead of silently
    truncating.
    """
    from spark_rapids_ml_tpu.core.data import (
        _block_to_dense,
        _is_block,
        is_streaming_source,
        iter_stream_blocks,
    )

    if not (isinstance(dataset, tuple) and len(dataset) == 2):
        return None
    x, y = dataset
    if isinstance(x, (list, tuple)) and x and _is_block(x[0]):
        blocks = iter(x)
    elif is_streaming_source(x):
        blocks = iter_stream_blocks(x)
    else:
        return None

    def pairs():
        if isinstance(y, (list, tuple)):
            sentinel = object()
            from itertools import zip_longest

            for xb, yb in zip_longest(blocks, y, fillvalue=sentinel):
                if xb is sentinel or yb is sentinel:
                    raise ValueError(
                        "streaming fit: X blocks and per-block label lists "
                        "have different lengths"
                    )
                yield _block_to_dense(xb), yb
            return
        y_arr = np.asarray(y).ravel()
        start = 0
        for xb in blocks:
            xb = _block_to_dense(xb)
            yb = y_arr[start : start + xb.shape[0]]
            # Check the slice HERE, not downstream: the double-buffered
            # accumulator prepares pair k+1 before consuming pair k, so a
            # short tail must fail when it is produced to fail at all.
            if yb.shape[0] != xb.shape[0]:
                raise ValueError(
                    f"block rows mismatch: X block has {xb.shape[0]} rows "
                    f"but only {yb.shape[0]} labels remain"
                )
            yield xb, yb
            start += xb.shape[0]
        if start != y_arr.shape[0]:
            raise ValueError(
                f"streaming fit: blocks supplied {start} rows but y has "
                f"{y_arr.shape[0]}"
            )

    return pairs()


def _extract_xy(dataset: Any, features_col: str, label_col: str):
    """Accepts (X, y) tuples, DataFrame shim, or pandas with named columns."""
    if isinstance(dataset, tuple) and len(dataset) == 2:
        x, y = dataset
        if is_device_array(x):
            # Device-resident X: consumed in place by the prepare_rows
            # funnel. y keeps its device residence when it has one;
            # host-side y (list/ndarray) still normalizes to float64 —
            # downstream code relies on ndarray semantics (.size, math).
            if is_device_array(y):
                return x, y
            return x, np.asarray(y, dtype=np.float64).ravel()
        return as_matrix(x), np.asarray(y, dtype=np.float64).ravel()
    if isinstance(dataset, DataFrame):
        x = as_matrix(dataset.select(features_col))
        y = np.asarray(dataset.select(label_col), dtype=np.float64).ravel()
        return x, y
    try:
        import pandas as pd

        if isinstance(dataset, pd.DataFrame):
            if features_col in dataset.columns:
                x = as_matrix(dataset[features_col].tolist())
            else:
                x = dataset.drop(columns=[label_col]).to_numpy(dtype=np.float64)
            y = dataset[label_col].to_numpy(dtype=np.float64)
            return x, y
    except ImportError:  # pragma: no cover
        pass
    raise TypeError(
        "dataset must be (X, y), a DataFrame with features/label columns, or a pandas DataFrame"
    )


class LinearRegressionModel(_LinearRegressionParams, Model, LazyHostState):
    """Fitted model: ``coefficients`` (d,), ``intercept``.

    Fitted state may be host numpy OR live jax.Arrays from a device-
    resident fit; host float64 views convert lazily and pickling
    materializes host state (core/lazy_state.LazyHostState)."""

    _lazy_host_fields = {
        "_coef_raw": ("_coef_np", np.float64),
        "_grad_raw": ("_grad_np", np.float64),
    }
    _pickle_clear = ("_coef_dev",)

    def __init__(
        self,
        uid: Optional[str] = None,
        coefficients: Optional[np.ndarray] = None,
        intercept: float = 0.0,
        numIter: Optional[int] = None,
        finalObjective: Optional[float] = None,
        finalGradient: Optional[np.ndarray] = None,
    ):
        super().__init__(uid)
        self._coef_raw = coefficients
        self._coef_np: Optional[np.ndarray] = None
        self._coef_dev = None
        # (intercept, numIter, finalObjective): host numbers, or a
        # device-resident fit's scalars until the host first reads one
        self._scalars_raw = (intercept, numIter, finalObjective)
        self._grad_raw = finalGradient
        self._grad_np: Optional[np.ndarray] = None

    def __getstate__(self):
        self._scalars()  # device scalars never pickle
        return super().__getstate__()

    @property
    def coefficients(self) -> Optional[np.ndarray]:
        return self._lazy_host_view("_coef_raw")

    def _scalars(self) -> tuple:
        """(intercept, numIter, finalObjective) on the host. A
        device-resident fit's scalars cross together on the first read of
        any of them (the fit itself never waits for them); that read is
        also where a proximal fit's ``linreg.fista.iters`` counter moves,
        here and in the fit's report."""
        raw = self._scalars_raw
        if any(is_device_array(v) for v in raw):
            intercept, n_iter, objective = jax.device_get(raw)
            self._scalars_raw = (
                float(intercept),
                None if n_iter is None else int(n_iter),
                None if objective is None else float(objective),
            )
            if is_device_array(raw[1]):  # this process's proximal fit, first read
                bump_counter("linreg.fista.iters", int(n_iter))
                if self._fit_report is not None:
                    self._fit_report.counters["linreg.fista.iters"] = int(n_iter)
        return self._scalars_raw

    @property
    def intercept(self) -> float:
        return float(self._scalars()[0])

    @property
    def numIter(self) -> Optional[int]:
        """Proximal (FISTA) iterations the fit ran: ``maxIter`` exactly
        under a ``tol`` below the dtype's epsilon. None after the exact
        normal-equation solve and for a model no fit of this process made."""
        return self._scalars()[1]

    @property
    def finalObjective(self) -> Optional[float]:
        """Spark's objective (module docstring) at the returned
        coefficients and intercept, from the fit's moments: the last entry
        of Spark's ``objectiveHistory``. None off the proximal path."""
        return self._scalars()[2]

    @property
    def finalGradient(self) -> Optional[np.ndarray]:
        """(d,) gradient of the objective's smooth part (the least-squares
        term and the L2 penalty) with respect to the returned coefficients,
        ``(Xc^T Xc b - Xc^T yc) / n + regParam (1 - alpha) w2 b`` from the
        fit's moments: what the next proximal step would start from, so a
        check of the moments' arithmetic against an independent gradient at
        the same point. None off the proximal path."""
        return self._lazy_host_view("_grad_raw")

    def copy(self, extra=None) -> "LinearRegressionModel":
        """Model.copy preserves fitted state (Spark's Model.copy contract)."""
        that = LinearRegressionModel(
            self.uid, self._coef_raw, *self._scalars_raw, self._grad_raw
        )
        return self._copyValues(that, extra)

    def predict(self, x) -> np.ndarray:
        if self._coef_raw is None:
            raise RuntimeError("model has no coefficients")
        # Device queries get device predictions; host queries keep numpy.
        # Both run through the shape-bucketed serving program cache.
        return serve_rows(
            _predict_kernel,
            matrix_like(x),
            self._coef_serving(),
            static={"precision": self._serving_precision()},
            name="linreg.predict",
        )

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

    def _coef_serving(self):
        """(coefficients, intercept) as ONE device-resident pair reused by
        every predict call."""
        if self._coef_dev is None:
            coef = (
                self._coef_raw
                if is_device_array(self._coef_raw)
                else jnp.asarray(self.coefficients)
            )
            self._coef_dev = (coef, jnp.asarray(self._scalars_raw[0]))
            note_device_cache(self)
        return self._coef_dev

    def serving_signature(self):
        """The online-serving contract: the X·coef + b kernel, the
        device-resident (coefficients, intercept) pair, and the (n,)
        prediction output spec."""
        import jax

        from spark_rapids_ml_tpu.serving.signature import ServingSignature

        if self._coef_raw is None:
            raise RuntimeError("model has no coefficients")
        coef, intercept = self._coef_serving()
        return ServingSignature(
            kernel=_predict_kernel,
            weights=(coef, intercept),
            static={"precision": self._serving_precision()},
            name="linreg.predict",
            n_features=int(coef.shape[0]),
            output_spec=lambda n, dtype: (
                jax.ShapeDtypeStruct((n,), dtype),
            ),
        )

    def transform(self, dataset: Any) -> Any:
        if isinstance(dataset, tuple):
            x = dataset[0]
        else:
            x = dataset
        if isinstance(dataset, DataFrame):
            pred = self.predict(dataset.select(self.getFeaturesCol()))
            return dataset.withColumn(self.getPredictionCol(), list(pred))
        try:
            import pandas as pd

            if isinstance(dataset, pd.DataFrame):
                if self.getFeaturesCol() in dataset.columns:
                    pred = self.predict(dataset[self.getFeaturesCol()].tolist())
                else:
                    cols = [c for c in dataset.columns if c != self.getLabelCol()]
                    pred = self.predict(dataset[cols].to_numpy(dtype=np.float64))
                out = dataset.copy()
                out[self.getPredictionCol()] = pred
                return out
        except ImportError:  # pragma: no cover
            pass
        return self.predict(x)

    def evaluate(self, dataset: Any) -> dict:
        """RegressionSummary analogue: mse/rmse/mae/r2 on a labeled dataset."""
        x, y = _extract_xy(dataset, self.getFeaturesCol(), self.getLabelCol())
        pred = self.predict(x)
        mask = jnp.ones(len(y), dtype=pred.dtype)
        mse, rmse, mae, r2 = regression_metrics(jnp.asarray(y, dtype=pred.dtype), jnp.asarray(pred), mask)
        return {
            "meanSquaredError": float(mse),
            "rootMeanSquaredError": float(rmse),
            "meanAbsoluteError": float(mae),
            "r2": float(r2),
        }

    def _save_impl(self, path: str) -> None:
        save_metadata(
            self, path, class_name="org.apache.spark.ml.regression.LinearRegressionModel"
        )
        save_data(
            path,
            {
                "coefficients": ("vector", self.coefficients),
                "intercept": ("scalar", float(self.intercept)),
            },
        )

    @classmethod
    def _load_impl(cls, path: str) -> "LinearRegressionModel":
        metadata = load_metadata(path, expected_class="LinearRegressionModel")
        data = load_data(path)
        model = cls(metadata["uid"], data["coefficients"], float(data["intercept"]))
        get_and_set_params(model, metadata)
        return model

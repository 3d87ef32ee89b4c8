"""LogisticRegression estimator/model — Spark ML surface, L-BFGS on the MXU.

Param surface mirrors ``org.apache.spark.ml.classification.LogisticRegression``:
``featuresCol``, ``labelCol``, ``predictionCol``, ``probabilityCol``,
``rawPredictionCol``, ``maxIter``, ``regParam``, ``elasticNetParam`` (0 ->
L2 via jitted L-BFGS; > 0 -> L1/elastic net via jitted FISTA, Spark's
OWL-QN analogue), ``tol``, ``fitIntercept``, ``standardization``,
``family`` ("auto" | "binomial" | "multinomial"), ``threshold``.
Beyond-the-reference capability (the reference ships only PCA — SURVEY.md
§2); the whole optimization is one jitted program (ops.logistic),
mesh-shardable.

Model attributes follow Spark: binomial exposes ``coefficients`` (d,) and
``intercept``; multinomial exposes ``coefficientMatrix`` (numClasses, d) and
``interceptVector`` (numClasses,).
"""

from __future__ import annotations

from typing import Any, Optional

import jax.numpy as jnp
import numpy as np

from spark_rapids_ml_tpu.core.data import (
    DataFrame,
    as_matrix,
    extract_weights,
    is_device_array,
    is_streaming_source,
)
from spark_rapids_ml_tpu.core.estimator import Estimator, Model
from spark_rapids_ml_tpu.core.ingest import (
    matrix_like,
    prepare_labels,
    prepare_rows,
    validate_int_labels,
)
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
from spark_rapids_ml_tpu.models.linear_regression import _extract_xy
from spark_rapids_ml_tpu.ops.logistic import (
    classification_metrics,
    fit_logistic,
    fit_logistic_elastic_net,
    fit_logistic_resumable,
    predict_logistic,
)
from spark_rapids_ml_tpu.core.serving import (
    note_device_cache,
    serve_blocks,
    serve_rows,
    stream_block_rows,
)
from spark_rapids_ml_tpu.utils.envknobs import env_choice
from spark_rapids_ml_tpu.utils.tracing import (
    StageRange,
    TraceColor,
    TraceRange,
    bump_counter,
)


def _logistic_fused_knob() -> bool:
    """TPUML_LOGISTIC_FUSED, read in the model layer (outside jit) and
    plumbed into the FISTA and streaming solvers as a static arg (the
    L-BFGS fit has one formulation)."""
    return env_choice("TPUML_LOGISTIC_FUSED", ("0", "1"), "1") == "1"


def _forward_kernel(
    x, w, b, *, n_classes: int, threshold: float, precision: str = "highest"
):
    """Serving kernel: one forward pass -> (labels, probs, raw logits).
    The batch follows the weights' dtype (the fitted precision is the
    numerics contract; the cast fuses into the logits GEMM).
    ``precision`` is the resolved serving-family policy mode
    (ops/precision.py) — static, so it keys the AOT program cache."""
    labels, probs, raw = predict_logistic(
        x.astype(w.dtype), w, b, n_classes=n_classes, precision=precision
    )
    if w.shape[1] == 1 and threshold != 0.5:
        labels = (probs[:, 1] > threshold).astype(jnp.int32)
    return labels, probs, raw


def _select_labels(outs):
    """Transform-contract selection for the fuser: a pipeline ending in a
    classifier yields LABELS (``transform`` on a plain array returns
    ``predict``'s labels); probabilities and raw margins are downstream-
    dead, so selecting in-program lets XLA eliminate their writes."""
    labels, _probs, _raw = outs
    return labels


class _LogisticRegressionParams(Params):
    featuresCol = Param("_", "featuresCol", "features column name", toString)
    labelCol = Param("_", "labelCol", "label column name", toString)
    predictionCol = Param("_", "predictionCol", "prediction column name", toString)
    probabilityCol = Param("_", "probabilityCol", "class probabilities column", toString)
    rawPredictionCol = Param("_", "rawPredictionCol", "raw logits column", toString)
    maxIter = Param("_", "maxIter", "maximum L-BFGS iterations", toInt)
    regParam = Param("_", "regParam", "L2 regularization strength", toFloat)
    elasticNetParam = Param("_", "elasticNetParam", "L1/L2 mixing (0 = pure L2)", toFloat)
    tol = Param("_", "tol", "gradient-norm convergence tolerance", toFloat)
    fitIntercept = Param("_", "fitIntercept", "whether to fit an intercept", toBoolean)
    standardization = Param(
        "_", "standardization", "optimize in standardized feature space", toBoolean
    )
    family = Param("_", "family", "auto, binomial, or multinomial", toString)
    threshold = Param("_", "threshold", "binary decision threshold", toFloat)
    weightCol = Param("_", "weightCol", "per-row weight column name", toString)
    precision = Param(
        "_", "precision",
        "matmul precision for the X-sweep GEMMs (ops/precision.py): "
        "highest/f32 (reference-parity default) | high | bf16x3 (3-pass "
        "compensated split, max rel err <= 2e-4) | default/bf16 (1-pass). "
        "Unset, the TPUML_PRECISION[_LOGISTIC] knobs and committed "
        "autotune decisions apply (resolve_policy layering).",
        toString,
    )

    def __init__(self, uid: Optional[str] = None):
        super().__init__(uid)
        self._setDefault(
            featuresCol="features",
            labelCol="label",
            predictionCol="prediction",
            probabilityCol="probability",
            rawPredictionCol="rawPrediction",
            maxIter=100,
            regParam=0.0,
            elasticNetParam=0.0,
            tol=1e-6,
            fitIntercept=True,
            standardization=True,
            family="auto",
            threshold=0.5,
            precision="highest",
        )

    def getFeaturesCol(self) -> str:
        return self.getOrDefault(self.featuresCol)

    def getLabelCol(self) -> str:
        return self.getOrDefault(self.labelCol)

    def getPredictionCol(self) -> str:
        return self.getOrDefault(self.predictionCol)

    def getProbabilityCol(self) -> str:
        return self.getOrDefault(self.probabilityCol)

    def getRawPredictionCol(self) -> str:
        return self.getOrDefault(self.rawPredictionCol)

    def getMaxIter(self) -> int:
        return self.getOrDefault(self.maxIter)

    def getRegParam(self) -> float:
        return self.getOrDefault(self.regParam)

    def getElasticNetParam(self) -> float:
        return self.getOrDefault(self.elasticNetParam)

    def getTol(self) -> float:
        return self.getOrDefault(self.tol)

    def getFitIntercept(self) -> bool:
        return self.getOrDefault(self.fitIntercept)

    def getStandardization(self) -> bool:
        return self.getOrDefault(self.standardization)

    def getFamily(self) -> str:
        return self.getOrDefault(self.family)

    def getThreshold(self) -> float:
        return self.getOrDefault(self.threshold)

    def getPrecision(self) -> str:
        return self.getOrDefault(self.precision)

    def getWeightCol(self):
        return (
            self.getOrDefault(self.weightCol)
            if self.isDefined(self.weightCol)
            else None
        )


class LogisticRegression(_LogisticRegressionParams, Estimator, MLReadable):
    """``LogisticRegression().setRegParam(0.1).fit((X, y))``."""

    # Consumes device (X, y) pairs in place, so tuning loops may feed
    # device-resident fold slices (tuning._device_fold_prep).
    _device_foldable = True

    def __init__(self, uid: Optional[str] = None, mesh=None):
        super().__init__(uid)
        self.mesh = mesh

    def setFeaturesCol(self, value: str) -> "LogisticRegression":
        self.set(self.featuresCol, value)
        return self

    def setLabelCol(self, value: str) -> "LogisticRegression":
        self.set(self.labelCol, value)
        return self

    def setPredictionCol(self, value: str) -> "LogisticRegression":
        self.set(self.predictionCol, value)
        return self

    def setProbabilityCol(self, value: str) -> "LogisticRegression":
        self.set(self.probabilityCol, value)
        return self

    def setRawPredictionCol(self, value: str) -> "LogisticRegression":
        self.set(self.rawPredictionCol, value)
        return self

    def setMaxIter(self, value: int) -> "LogisticRegression":
        self.set(self.maxIter, value)
        return self

    def setRegParam(self, value: float) -> "LogisticRegression":
        if value < 0:
            raise ValueError(f"regParam must be >= 0, got {value}")
        self.set(self.regParam, value)
        return self

    def setElasticNetParam(self, value: float) -> "LogisticRegression":
        if not 0.0 <= value <= 1.0:
            raise ValueError(f"elasticNetParam must be in [0, 1], got {value}")
        self.set(self.elasticNetParam, value)
        return self

    def setTol(self, value: float) -> "LogisticRegression":
        self.set(self.tol, value)
        return self

    def setFitIntercept(self, value: bool) -> "LogisticRegression":
        self.set(self.fitIntercept, value)
        return self

    def setStandardization(self, value: bool) -> "LogisticRegression":
        self.set(self.standardization, value)
        return self

    def setFamily(self, value: str) -> "LogisticRegression":
        if value not in ("auto", "binomial", "multinomial"):
            raise ValueError(f"family must be auto/binomial/multinomial, got {value!r}")
        self.set(self.family, value)
        return self

    def setThreshold(self, value: float) -> "LogisticRegression":
        self.set(self.threshold, value)
        return self

    def setPrecision(self, value: str) -> "LogisticRegression":
        from spark_rapids_ml_tpu.ops.precision import validate_mode

        self.set(self.precision, validate_mode(value))
        return self

    def setWeightCol(self, value: str) -> "LogisticRegression":
        self.set(self.weightCol, value)
        return self

    def setMesh(self, mesh) -> "LogisticRegression":
        self.mesh = mesh
        return self

    _initial_weights = None  # (weights (d, c), intercepts (c,)) warm start
    _copy_attrs = ("_initial_weights",)

    def setInitialModel(self, value) -> "LogisticRegression":
        """Warm start the L-BFGS solve from an existing model's solution —
        resume an interrupted fit, or seed a regularization-path sweep
        (each grid cell starts from the previous optimum). Applies to the
        L-BFGS (L2 / unregularized) path."""
        w = np.asarray(value.weights, dtype=np.float64)
        b = np.asarray(value.intercepts, dtype=np.float64)
        if w.ndim != 2 or b.ndim != 1 or w.shape[1] != b.shape[0]:
            raise ValueError("initial model must carry (d, c) weights and (c,) intercepts")
        self._initial_weights = (w, b)
        return self

    def _fit(self, dataset: Any) -> "LogisticRegressionModel":
        if (
            isinstance(dataset, tuple)
            and len(dataset) == 2
            and is_streaming_source(dataset[0])
        ):
            return self._fit_streaming(dataset)
        x_in, y_in = _extract_xy(dataset, self.getFeaturesCol(), self.getLabelCol())
        w_host = extract_weights(dataset, self.getWeightCol())
        from spark_rapids_ml_tpu.core import membudget

        # Budgeted admission (core/membudget.py): an over-budget host
        # input reroutes to the SAME (reader, y) streaming fit an explicit
        # streaming source takes — bit-identical by construction — and a
        # device OOM mid-fit reclaims caches and takes the same exit.
        can_stream = (
            w_host is None
            and not (self.getElasticNetParam() > 0.0 and self.getRegParam() > 0.0)
            and self._initial_weights is None
        )
        guard = membudget.fit_memory_guard(
            "logistic", x_in, can_stream=can_stream,
            why_cannot_stream="the streaming path supports neither "
                              "weightCol, elastic net, nor warm starts",
            mesh=self.mesh, ledger_families=("logistic",),
        )
        if guard.degrade:
            return membudget.run_streaming_with_recovery(
                "logistic", lambda r: self._fit((r, y_in)), guard.matrix
            )
        fallback = (
            (lambda: membudget.run_streaming_with_recovery(
                "logistic", lambda r: self._fit((r, y_in)),
                membudget.host_matrix(x_in)))
            if can_stream and self.mesh is None else None
        )
        return membudget.run_fit_with_oom_recovery(
            "logistic", lambda: self._fit_in_memory(x_in, y_in, w_host), fallback
        )

    def _train_precision(self) -> str:
        """Resolve the fit-time GEMM policy (ops/precision.py): explicit
        ``setPrecision`` wins, then TPUML_PRECISION[_LOGISTIC], then a
        committed autotune decision; the default stays 'highest'."""
        from spark_rapids_ml_tpu.ops.precision import resolve_policy

        requested = self.getPrecision() if self.isSet(self.precision) else None
        return resolve_policy(
            "logistic", requested, default=self.getPrecision()
        )

    def _fit_in_memory(self, x_in, y_in, w_host) -> "LogisticRegressionModel":
        # Device labels validate on device (two scalar readbacks — the
        # class count defines shapes, so a sync is inherent; what never
        # happens is an O(n) pull of the label vector).
        y_int, n_classes = validate_int_labels(y_in)
        import jax

        if self.mesh is not None and jax.process_count() > 1:
            # Gang deploy mode: each member counted classes from its LOCAL
            # labels, but n_classes is a trace-time shape — members must
            # agree globally or they trace different programs and deadlock
            # in the first collective.
            from spark_rapids_ml_tpu.parallel.distributed import (
                allgather_host_max,
            )

            n_classes = allgather_host_max(n_classes)
        family = self.getFamily()
        if family == "auto":
            family = "binomial" if n_classes <= 2 else "multinomial"
        if family == "binomial" and n_classes > 2:
            raise ValueError(f"binomial family with {n_classes} labels")
        n_classes = max(n_classes, 2)

        with TraceRange("logreg fit", TraceColor.YELLOW):
            # One funnel for every residence: device arrays fit in place
            #, host data places once, dtype-preserving.
            xs, mask, n, d = prepare_rows(x_in, mesh=self.mesh, weights=w_host)
            dtype = xs.dtype
            ys = prepare_labels(
                y_int, int(xs.shape[0]), n_true=n, mesh=self.mesh, dtype=jnp.int32
            )
            use_multinomial = family == "multinomial"
            precision = self._train_precision()
            enet = self.getElasticNetParam()
            # regParam == 0 means zero effective penalty whatever enet says:
            # use the L-BFGS path (faster, and it applies the multinomial
            # identifiability pivot the proximal path has no need for).
            init_w = init_b = None
            if self._initial_weights is not None:
                w0, b0 = self._initial_weights
                c_expect = n_classes if (use_multinomial or n_classes > 2) else 1
                if w0.shape != (d, c_expect):
                    raise ValueError(
                        f"initial model weights {w0.shape} != expected "
                        f"({d}, {c_expect})"
                    )
                # Pad to any model-axis feature padding the mesh added.
                pad_d = xs.shape[1] - w0.shape[0]
                init_w = jnp.asarray(
                    np.pad(w0, ((0, pad_d), (0, 0))), dtype=dtype
                )
                init_b = jnp.asarray(b0, dtype=dtype)
            solver_args = dict(
                n_classes=n_classes,
                reg_param=self.getRegParam(),
                fit_intercept=self.getFitIntercept(),
                standardization=self.getStandardization(),
                max_iter=self.getMaxIter(),
                tol=self.getTol(),
                multinomial=use_multinomial,
                precision=precision,
            )
            if enet == 0.0 or self.getRegParam() == 0.0:
                # Preemption tolerance: the TPUML_CHECKPOINT_* knobs route
                # the L-BFGS solve through the segmented driver (async
                # snapshots, mid-solve resume, bit-identical results).
                ckpt = self._fit_checkpointer("logistic.lbfgs", data=(xs, ys, mask))
                fit_fn = fit_logistic
                solver_args.update(init_w=init_w, init_b=init_b)
                if ckpt is not None:
                    fit_fn = fit_logistic_resumable
                    solver_args.update(checkpointer=ckpt, mesh=self.mesh)
            else:
                if self._initial_weights is not None:
                    raise ValueError(
                        "setInitialModel warm start applies to the L-BFGS "
                        "path (elasticNetParam 0 or regParam 0)"
                    )
                # L1/elastic net: FISTA (Spark reaches this via OWL-QN).
                # maxIter caps proximal iterations exactly as it caps
                # OWL-QN iterations in Spark — users of the slower-
                # converging proximal steps raise maxIter, preserving the
                # totalIterations <= maxIter invariant.
                fit_fn = fit_logistic_elastic_net
                # Knob read OUTSIDE jit; it rides in as a static arg.
                solver_args.update(
                    elastic_net_param=enet, fused=_logistic_fused_knob()
                )
            # The stage times the dispatch: a jitted solve returns at once,
            # a resumable one blocks between its segments.
            with StageRange("solve"):
                result = fit_fn(xs, ys, mask, **solver_args)
        # Gang fits can hand back sharded results; replicate them so every
        # member's host reads see identical values.
        from spark_rapids_ml_tpu.parallel.distributed import replicate_for_host

        weights, intercepts = replicate_for_host(
            self.mesh, result.weights, result.intercepts
        )
        grad = None
        if result.grad is not None:  # the L-BFGS path: (d + 1, c), as the model keeps it
            gw, gb = result.grad
            grad = replicate_for_host(self.mesh, jnp.concatenate([gw[:d], gb[None, :]]))
        # Strip model-axis feature padding (device slice, stays async);
        # host float64 conversion happens lazily inside the model.
        model = LogisticRegressionModel(
            self.uid,
            weights[:d],
            intercepts,
            numClasses=n_classes,
            numIter=result.n_iter,
            xPasses=result.x_passes,
            linesearchTrials=result.linesearch_trials,
            finalObjective=result.loss,
            finalGradient=grad,
        )
        return self._copyValues(model)

    def _fit_streaming(self, dataset) -> "LogisticRegressionModel":
        """Re-iterable (X_stream, y) sources: multi-pass L-BFGS at
        O(block + d*c) memory — one stats pass (moments + label scan),
        then one data pass per objective evaluation
        (:func:`ops.logistic.fit_logistic_streaming`)."""
        from spark_rapids_ml_tpu.core.data import is_reiterable_stream
        from spark_rapids_ml_tpu.models.linear_regression import _streaming_blocks
        from spark_rapids_ml_tpu.ops.logistic import (
            fit_logistic_streaming,
            streaming_label_feature_stats,
        )

        if not is_reiterable_stream(dataset[0]):
            raise ValueError(
                "LogisticRegression is multi-pass: a streaming fit needs a "
                "RE-ITERABLE source (a zero-arg iterator factory or a block "
                "reader with .iter_blocks()), not a one-shot generator"
            )
        if self.mesh is not None:
            raise ValueError(
                "streaming LogisticRegression is single-device; pass host "
                "partitions for a mesh fit"
            )
        if self.getWeightCol() is not None:
            raise TypeError(
                "weightCol requires a dataset with named columns; streaming "
                "block sources carry no columns"
            )
        if self.getElasticNetParam() > 0.0 and self.getRegParam() > 0.0:
            raise ValueError(
                "streaming elastic net is not supported (FISTA needs the "
                "in-memory design); use elasticNetParam=0 or materialize"
            )
        if self._initial_weights is not None:
            raise ValueError(
                "setInitialModel warm start is not supported for streaming "
                "fits yet"
            )

        n, mean, sigma, y_max, y_int_ok = streaming_label_feature_stats(
            _streaming_blocks(dataset)
        )
        if not y_int_ok:
            raise ValueError("labels must be integers in [0, numClasses)")
        n_classes = y_max + 1
        family = self.getFamily()
        if family == "auto":
            family = "binomial" if n_classes <= 2 else "multinomial"
        if family == "binomial" and n_classes > 2:
            raise ValueError(f"binomial family with {n_classes} labels")
        n_classes = max(n_classes, 2)

        with TraceRange("logreg stream fit", TraceColor.YELLOW):
            result = fit_logistic_streaming(
                lambda: _streaming_blocks(dataset),
                n_classes,
                n=n,
                mean=mean,
                sigma=sigma,
                reg_param=self.getRegParam(),
                fit_intercept=self.getFitIntercept(),
                standardization=self.getStandardization(),
                max_iter=self.getMaxIter(),
                tol=self.getTol(),
                multinomial=family == "multinomial",
                fused=_logistic_fused_knob(),
                precision=self._train_precision(),
            )
        model = LogisticRegressionModel(
            self.uid,
            np.asarray(result.weights, dtype=np.float64),
            np.asarray(result.intercepts, dtype=np.float64),
            numClasses=n_classes,
            numIter=int(result.n_iter),
            finalObjective=float(result.loss),
        )
        return self._copyValues(model)


class LogisticRegressionModel(_LogisticRegressionParams, Model, LazyHostState):
    """Fitted model. ``weights``: (d, 1) binomial sigmoid column or (d, c)
    softmax matrix; ``intercepts``: (1,) or (c,).

    Fitted state may be host numpy OR live jax.Arrays from a device-
    resident fit; host float64 views convert lazily and pickling
    materializes host state (core/lazy_state.LazyHostState)."""

    _lazy_host_fields = {
        "_w_raw": ("_w_np", np.float64),
        "_b_raw": ("_b_np", np.float64),
        "_grad_raw": ("_grad_np", np.float64),
    }
    _pickle_clear = ("_wb_dev",)

    def __init__(
        self,
        uid: Optional[str] = None,
        weights: Optional[np.ndarray] = None,
        intercepts: Optional[np.ndarray] = None,
        numClasses: int = 2,
        numIter: int = 0,
        xPasses: Optional[int] = None,
        linesearchTrials: Optional[int] = None,
        finalObjective: Optional[float] = None,
        finalGradient: Optional[np.ndarray] = None,
    ):
        super().__init__(uid)
        self._w_raw = weights
        self._b_raw = intercepts
        self._w_np: Optional[np.ndarray] = None
        self._b_np: Optional[np.ndarray] = None
        self._wb_dev = None
        self.numClasses = numClasses
        # (numIter, xPasses, linesearchTrials, finalObjective): host numbers,
        # or a device-resident fit's scalars until the host first reads one
        self._solver_raw = (numIter, xPasses, linesearchTrials, finalObjective)
        self._grad_raw = finalGradient
        self._grad_np: Optional[np.ndarray] = None

    def __getstate__(self):
        self._solver_counts()  # device scalars never pickle
        return super().__getstate__()

    @property
    def weights(self) -> Optional[np.ndarray]:
        return self._lazy_host_view("_w_raw")

    @property
    def intercepts(self) -> Optional[np.ndarray]:
        return self._lazy_host_view("_b_raw")

    def _solver_counts(self) -> tuple:
        """(numIter, xPasses, linesearchTrials, finalObjective) on the host.
        A device-resident fit's four scalars cross together on the first
        read of any of them (the fit itself never waits for them); that
        read is also where an L-BFGS fit's ``logreg.lbfgs.*`` counters
        move, here and in the fit's report."""
        if not isinstance(self._solver_raw[0], int):
            import jax

            n_iter, passes, trials, objective = jax.device_get(self._solver_raw)
            n_iter, passes, trials = (
                None if v is None else int(v) for v in (n_iter, passes, trials)
            )
            objective = None if objective is None else float(objective)
            self._solver_raw = (n_iter, passes, trials, objective)
            if passes is not None:
                moved = {
                    "logreg.lbfgs.iters": n_iter,
                    "logreg.lbfgs.x_passes": passes,
                    "logreg.lbfgs.linesearch_trials": trials,
                }
                for name, amount in moved.items():
                    bump_counter(name, amount)
                if self._fit_report is not None:
                    self._fit_report.counters.update(moved)
        return self._solver_raw

    @property
    def numIter(self) -> int:
        """Optimizer iterations the fit ran (line-search trials not counted)."""
        return self._solver_counts()[0]

    @property
    def xPasses(self) -> Optional[int]:
        """Passes over the rows the L-BFGS fit made: a function of
        ``numIter`` and the params alone. None off the L-BFGS path."""
        return self._solver_counts()[1]

    @property
    def linesearchTrials(self) -> Optional[int]:
        """Trial steps the fit's line searches took, none of which read
        the rows. None off the L-BFGS path."""
        return self._solver_counts()[2]

    @property
    def finalObjective(self) -> Optional[float]:
        """The objective (scaled loss + penalty, in the space the optimizer
        worked in) at the returned coefficients, as the optimizer last saw
        it: the last entry of Spark's ``objectiveHistory``. None for a
        model that no fit of this process made."""
        return self._solver_counts()[3]

    @property
    def finalGradient(self) -> Optional[np.ndarray]:
        """The objective's gradient at the returned point as the L-BFGS
        fit's last iteration computed it, in the space the optimizer worked
        in (as ``finalObjective``; with ``standardization`` false, with
        respect to the returned coefficients): (d + 1, c), the coefficients'
        rows then the intercepts' (zeros without an intercept). What the
        optimizer stopped on, so a check of the fit's arithmetic against an
        independent gradient at the same point. None off the L-BFGS path
        and for a model no fit of this process made."""
        return self._lazy_host_view("_grad_raw")

    def setFeaturesCol(self, value: str) -> "LogisticRegressionModel":
        self.set(self.featuresCol, value)
        return self

    def setPredictionCol(self, value: str) -> "LogisticRegressionModel":
        self.set(self.predictionCol, value)
        return self

    def setProbabilityCol(self, value: str) -> "LogisticRegressionModel":
        self.set(self.probabilityCol, value)
        return self

    def setRawPredictionCol(self, value: str) -> "LogisticRegressionModel":
        self.set(self.rawPredictionCol, value)
        return self

    def setThreshold(self, value: float) -> "LogisticRegressionModel":
        self.set(self.threshold, value)
        return self

    def copy(self, extra=None) -> "LogisticRegressionModel":
        # the counts resolved first: the fit's counters move once, on the
        # model that fit returned, not again on every copy's first read
        that = LogisticRegressionModel(
            self.uid, self._w_raw, self._b_raw, self.numClasses,
            *self._solver_counts(), self._grad_raw,
        )
        return self._copyValues(that, extra)

    # --- Spark-style accessors ---

    @property
    def coefficients(self) -> np.ndarray:
        """Binomial coefficient vector (d,). Raises for multinomial (Spark
        throws the same way)."""
        if self.weights.shape[1] != 1:
            raise AttributeError("multinomial model: use coefficientMatrix")
        return self.weights[:, 0]

    @property
    def intercept(self) -> float:
        if self.intercepts.shape[0] != 1:
            raise AttributeError("multinomial model: use interceptVector")
        return float(self.intercepts[0])

    @property
    def coefficientMatrix(self) -> np.ndarray:
        """Spark's orientation: (1, d) for binomial, (numClasses, d) for
        multinomial."""
        return self.weights.T

    @property
    def interceptVector(self) -> np.ndarray:
        return self.intercepts.copy()

    def predict(self, x) -> np.ndarray:
        labels, _, _ = self._predict_all(x)
        return labels

    def predictProbability(self, x) -> np.ndarray:
        _, probs, _ = self._predict_all(x)
        return probs

    def predictRaw(self, x) -> np.ndarray:
        """Raw margins (Spark's rawPrediction): [-z, z] for binomial,
        the logits for multinomial — NOT probabilities."""
        _, _, raw = self._predict_all(x)
        return raw

    def _predict_all(self, x):
        """One forward pass through the shape-bucketed serving program
        cache; binomial labels honor the threshold param (applied INSIDE
        the program so a threshold change is a new program, not a per-call
        epilogue). Device queries keep everything on device; host queries
        keep the numpy contract. Large host batches stream block by
        block through the double-buffered path (H2D of block k+1
        overlaps the forward GEMM of block k)."""
        w, b = self._wb_serving()
        static = {
            "n_classes": self.numClasses,
            "threshold": float(self.getThreshold()),
            "precision": self._serving_precision(),
        }
        x = matrix_like(x)
        if not is_device_array(x):
            xh = np.asarray(x)
            if xh.ndim == 2 and xh.shape[0] > stream_block_rows():
                return serve_blocks(
                    _forward_kernel,
                    xh,
                    (w, b),
                    static=static,
                    name="logreg.predict",
                )
        return serve_rows(
            _forward_kernel,
            x,
            (w, b),
            static=static,
            name="logreg.predict",
        )

    def _serving_precision(self) -> str:
        """The serving-family policy mode (ops/precision.py): an explicit
        estimator ``setPrecision`` survives into the model and wins;
        otherwise the TPUML_PRECISION[_SERVING] knobs and committed
        autotune decisions apply. Part of the static dict, hence of the
        AOT/program cache key."""
        from spark_rapids_ml_tpu.ops.precision import resolve_policy

        requested = self.getPrecision() if self.isSet(self.precision) else None
        return resolve_policy("serving", requested)

    def _wb_serving(self):
        """Weights/intercepts as ONE device-resident pair reused across
        predict calls (device-resident fits already hold them there)."""
        if self._wb_dev is None:
            w = self._w_raw if is_device_array(self._w_raw) else jnp.asarray(self.weights)
            b = self._b_raw if is_device_array(self._b_raw) else jnp.asarray(self.intercepts)
            self._wb_dev = (w, b.astype(w.dtype))
            note_device_cache(self)
        return self._wb_dev

    def serving_signature(self):
        """The online-serving contract: the forward kernel, the
        device-resident (weights, intercepts) pair, and the
        (labels, probabilities, raw margins) output specs."""
        import jax

        from spark_rapids_ml_tpu.serving.signature import ServingSignature

        if self._w_raw is None:
            raise RuntimeError("model has no weights")
        w, b = self._wb_serving()
        n_out = max(2, self.numClasses)
        return ServingSignature(
            kernel=_forward_kernel,
            weights=(w, b),
            static={
                "n_classes": self.numClasses,
                "threshold": float(self.getThreshold()),
                "precision": self._serving_precision(),
            },
            name="logreg.predict",
            n_features=int(w.shape[0]),
            output_spec=lambda n, dtype: (
                jax.ShapeDtypeStruct((n,), np.int32),
                jax.ShapeDtypeStruct((n, n_out), w.dtype),
                jax.ShapeDtypeStruct((n, n_out), w.dtype),
            ),
            select=_select_labels,
        )

    def transform(self, dataset: Any) -> Any:
        if isinstance(dataset, DataFrame):
            x = as_matrix(dataset.select(self.getFeaturesCol()))
            labels, probs, raw = self._predict_all(x)
            out = dataset.withColumn(self.getRawPredictionCol(), list(raw))
            out = out.withColumn(self.getProbabilityCol(), list(probs))
            return out.withColumn(self.getPredictionCol(), list(labels))
        try:
            import pandas as pd

            if isinstance(dataset, pd.DataFrame):
                if self.getFeaturesCol() in dataset.columns:
                    x = as_matrix(dataset[self.getFeaturesCol()].tolist())
                else:
                    cols = [c for c in dataset.columns if c != self.getLabelCol()]
                    x = dataset[cols].to_numpy(dtype=np.float64)
                labels, probs, raw = self._predict_all(x)
                out = dataset.copy()
                out[self.getRawPredictionCol()] = list(raw)
                out[self.getProbabilityCol()] = list(probs)
                out[self.getPredictionCol()] = labels
                return out
        except ImportError:  # pragma: no cover
            pass
        return self.predict(dataset)

    def evaluate(self, dataset: Any) -> dict:
        """Summary metrics: accuracy / error rate on a labeled dataset."""
        x, y = _extract_xy(dataset, self.getFeaturesCol(), self.getLabelCol())
        pred = self.predict(x)
        mask = jnp.ones(len(y))
        acc, err = classification_metrics(
            jnp.asarray(y.astype(np.int32)), jnp.asarray(pred.astype(np.int32)), mask
        )
        return {"accuracy": float(acc), "errorRate": float(err)}

    def _save_impl(self, path: str) -> None:
        save_metadata(
            self,
            path,
            class_name="org.apache.spark.ml.classification.LogisticRegressionModel",
            extra_metadata={"numClasses": self.numClasses, "numIter": self.numIter},
        )
        # Spark LogisticRegressionModel's exact data row (its Data case
        # class): numClasses, numFeatures, interceptVector,
        # coefficientMatrix ((1, d) binomial / (C, d) multinomial),
        # isMultinomial — byte-compatible with upstream readers
        # (the SURVEY §3.4 discipline).
        save_data(
            path,
            {
                "numClasses": ("scalar", int(self.numClasses)),
                "numFeatures": ("scalar", int(self.weights.shape[0])),
                "interceptVector": ("vector", self.intercepts),
                "coefficientMatrix": ("matrix", self.coefficientMatrix),
                "isMultinomial": ("scalar", bool(self.intercepts.shape[0] > 1)),
            },
        )

    @classmethod
    def _load_impl(cls, path: str) -> "LogisticRegressionModel":
        metadata = load_metadata(path, expected_class="LogisticRegressionModel")
        data = load_data(path)
        if "coefficientMatrix" in data:
            weights = np.asarray(data["coefficientMatrix"]).T  # (d, 1|C)
            intercepts = np.asarray(data["interceptVector"])
            n_classes = int(data.get("numClasses", metadata.get("numClasses", 2)))
        else:  # directories written before the Spark-schema alignment (r5)
            weights = data["weights"]
            intercepts = data["intercepts"]
            n_classes = metadata.get("numClasses", 2)
        model = cls(
            metadata["uid"],
            weights,
            intercepts,
            numClasses=n_classes,
            numIter=metadata.get("numIter", 0),
        )
        get_and_set_params(model, metadata)
        return model

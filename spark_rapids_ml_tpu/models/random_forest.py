"""RandomForestClassifier / RandomForestRegressor — Spark ML surface, XLA compute.

Param surface mirrors ``org.apache.spark.ml.classification.RandomForestClassifier``
and ``...regression.RandomForestRegressor``: ``numTrees``, ``maxDepth``,
``maxBins``, ``minInstancesPerNode``, ``minInfoGain``, ``subsamplingRate``,
``featureSubsetStrategy``, ``impurity``, ``bootstrap``, ``seed``, plus the
usual column params. Beyond-the-reference capability (the reference repo
ships only PCA — SURVEY.md §2; the modern RAPIDS Spark-ML line accelerates
random forests via cuML), so the test oracle is scikit-learn / handcrafted
separable data rather than a reference file.

The trees of a batch grow together, level by level: a level counts only each
node's chosen features, so neither memory nor work grows with the number of
nodes — see :mod:`spark_rapids_ml_tpu.ops.trees` for the builder.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_ml_tpu.core.data import (
    DataFrame,
    extract_features,
    extract_weights,
    is_device_array,
)
from spark_rapids_ml_tpu.core.estimator import Estimator, Model
from spark_rapids_ml_tpu.core.ingest import matrix_like, validate_int_labels
from spark_rapids_ml_tpu.core.params import Param, Params, toBoolean, toFloat, toInt, toString
from spark_rapids_ml_tpu.core.persistence import (
    MLReadable,
    get_and_set_params,
    load_metadata,
    load_rows,
    save_metadata,
)
from spark_rapids_ml_tpu.models.linear_regression import _extract_xy
from spark_rapids_ml_tpu.ops.trees import (
    Forest,
    builder_bytes,
    feature_importances,
    forest_predict_proba,
    forest_predict_reg,
    grow_forest,
    grow_forest_sharded,
    quantize_and_bin,
    sample_weights,
)
from spark_rapids_ml_tpu.core.serving import note_device_cache, serve_rows
from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS
from spark_rapids_ml_tpu.utils.tracing import StageRange, TraceColor, TraceRange, bump_counter


def _proba_kernel(x, forest, *, depth: int):
    """Serving kernel: (n, C) mean leaf class distributions. Trees route
    in float32 (the forests' training dtype)."""
    return forest_predict_proba(x.astype(jnp.float32), forest, depth)


def _reg_kernel(x, forest, *, depth: int):
    """Serving kernel: (n,) mean leaf values."""
    return forest_predict_reg(x.astype(jnp.float32), forest, depth)


def _forest_device(model):
    """The model's forest as ONE device-resident pytree reused by every
    predict call (host pickles drop it; it rebuilds lazily)."""
    if model._forest_dev is None:
        model._forest_dev = jax.tree_util.tree_map(jnp.asarray, model._forest)
        note_device_cache(model)
    return model._forest_dev


def _select_argmax(outs):
    """Transform-contract selection for the fuser: the classifier's
    ``transform`` on a plain array yields argmax labels, not the class
    distribution — selecting in-program lets XLA drop the probability
    writes when a fused pipeline ends in a forest classifier."""
    probs = outs[0] if isinstance(outs, tuple) else outs
    return jnp.argmax(probs, axis=1)


def _forest_signature(model, kernel, name, output_spec, select=None):
    """Shared ``serving_signature()`` body for the two forest models."""
    from spark_rapids_ml_tpu.serving.signature import ServingSignature

    if model._forest is None:
        raise RuntimeError("model has no fitted forest")
    return ServingSignature(
        kernel=kernel,
        weights=(_forest_device(model),),
        static={"depth": _forest_depth(model._forest)},
        name=name,
        n_features=int(model.numFeatures),
        output_spec=output_spec,
        select=select,
    )


def resolve_feature_subset(strategy: str, d: int, n_trees: int, classification: bool) -> int:
    """Spark's featureSubsetStrategy -> number of features per split."""
    s = strategy.lower()
    if s == "auto":
        if n_trees == 1:
            return d
        return (
            max(1, int(math.ceil(math.sqrt(d))))
            if classification
            else max(1, int(math.ceil(d / 3.0)))
        )
    if s == "all":
        return d
    if s == "sqrt":
        return max(1, int(math.ceil(math.sqrt(d))))
    if s == "log2":
        return max(1, int(math.ceil(math.log2(max(d, 2)))))
    if s == "onethird":
        return max(1, int(math.ceil(d / 3.0)))
    # Spark's grammar: an all-digits string is an absolute count in [1, d];
    # anything with a decimal point is a fraction in (0, 1] of the features
    # (so "1.0" means ALL features, not one).
    try:
        count = int(strategy)
    except ValueError:
        count = None
    if count is not None:
        if count < 1:
            raise ValueError(
                f"featureSubsetStrategy integer must be >= 1, got {strategy!r}"
            )
        return min(d, count)
    try:
        v = float(strategy)
    except ValueError:
        raise ValueError(f"unknown featureSubsetStrategy {strategy!r}")
    if 0 < v <= 1:
        return max(1, int(math.ceil(v * d)))
    raise ValueError(f"unknown featureSubsetStrategy {strategy!r}")


class _RandomForestParams(Params):
    numTrees = Param("_", "numTrees", "number of trees", toInt)
    maxDepth = Param("_", "maxDepth", "maximum tree depth", toInt)
    maxBins = Param("_", "maxBins", "max histogram bins per feature", toInt)
    minInstancesPerNode = Param(
        "_", "minInstancesPerNode", "min instances each child must have", toInt
    )
    minInfoGain = Param("_", "minInfoGain", "min info gain for a split", toFloat)
    subsamplingRate = Param("_", "subsamplingRate", "row sampling rate per tree", toFloat)
    featureSubsetStrategy = Param(
        "_", "featureSubsetStrategy", "features considered per split", toString
    )
    impurity = Param("_", "impurity", "split criterion", toString)
    bootstrap = Param("_", "bootstrap", "sample with replacement", toBoolean)
    seed = Param("_", "seed", "random seed", toInt)
    featuresCol = Param("_", "featuresCol", "features column name", toString)
    labelCol = Param("_", "labelCol", "label column name", toString)
    predictionCol = Param("_", "predictionCol", "prediction column name", toString)
    weightCol = Param("_", "weightCol", "per-row weight column name", toString)

    def __init__(self, uid: Optional[str] = None):
        super().__init__(uid)
        self._setDefault(
            numTrees=20,
            maxDepth=5,
            maxBins=32,
            minInstancesPerNode=1,
            minInfoGain=0.0,
            subsamplingRate=1.0,
            featureSubsetStrategy="auto",
            bootstrap=True,
            seed=0,
            featuresCol="features",
            labelCol="label",
            predictionCol="prediction",
        )

    def getNumTrees(self) -> int:
        return self.getOrDefault(self.numTrees)

    def getMaxDepth(self) -> int:
        return self.getOrDefault(self.maxDepth)

    def getMaxBins(self) -> int:
        return self.getOrDefault(self.maxBins)

    def getMinInstancesPerNode(self) -> int:
        return self.getOrDefault(self.minInstancesPerNode)

    def getMinInfoGain(self) -> float:
        return self.getOrDefault(self.minInfoGain)

    def getSubsamplingRate(self) -> float:
        return self.getOrDefault(self.subsamplingRate)

    def getFeatureSubsetStrategy(self) -> str:
        return self.getOrDefault(self.featureSubsetStrategy)

    def getImpurity(self) -> str:
        return self.getOrDefault(self.impurity)

    def getBootstrap(self) -> bool:
        return self.getOrDefault(self.bootstrap)

    def getSeed(self) -> int:
        return self.getOrDefault(self.seed)

    def getFeaturesCol(self) -> str:
        return self.getOrDefault(self.featuresCol)

    def getLabelCol(self) -> str:
        return self.getOrDefault(self.labelCol)

    def getPredictionCol(self) -> str:
        return self.getOrDefault(self.predictionCol)

    def getWeightCol(self) -> Optional[str]:
        return (
            self.getOrDefault(self.weightCol)
            if self.isDefined(self.weightCol)
            else None
        )

    # Chainable setters shared by estimators and models.
    def _chain(self, param, value):
        self.set(param, value)
        return self

    def setNumTrees(self, v: int):
        if v < 1:
            raise ValueError(f"numTrees must be >= 1, got {v}")
        return self._chain(self.numTrees, v)

    def setMaxDepth(self, v: int):
        if not 0 <= v <= 14:
            # the heap-indexed Forest holds 2^(maxDepth+1) - 1 node slots a
            # tree and the builder's widest level 2^(maxDepth-1) selected
            # histograms of (K, maxBins, stats): both double with a level
            raise ValueError(
                f"maxDepth must be in [0, 14], got {v}: a tree is a static heap of "
                "2^(maxDepth+1) - 1 node slots, and the widest level's selected "
                "histograms (2^(maxDepth-1) x features a node x maxBins) are held at once"
            )
        return self._chain(self.maxDepth, v)

    def setMaxBins(self, v: int):
        if v < 2:
            raise ValueError(f"maxBins must be >= 2, got {v}")
        return self._chain(self.maxBins, v)

    def setMinInstancesPerNode(self, v: int):
        if v < 1:
            raise ValueError(f"minInstancesPerNode must be >= 1, got {v}")
        return self._chain(self.minInstancesPerNode, v)

    def setMinInfoGain(self, v: float):
        return self._chain(self.minInfoGain, v)

    def setSubsamplingRate(self, v: float):
        if not 0 < v <= 1:
            raise ValueError(f"subsamplingRate must be in (0, 1], got {v}")
        return self._chain(self.subsamplingRate, v)

    def setFeatureSubsetStrategy(self, v: str):
        return self._chain(self.featureSubsetStrategy, v)

    def setBootstrap(self, v: bool):
        return self._chain(self.bootstrap, v)

    def setSeed(self, v: int):
        return self._chain(self.seed, v)

    def setFeaturesCol(self, v: str):
        return self._chain(self.featuresCol, v)

    def setLabelCol(self, v: str):
        return self._chain(self.labelCol, v)

    def setPredictionCol(self, v: str):
        return self._chain(self.predictionCol, v)

    def setWeightCol(self, v: str):
        return self._chain(self.weightCol, v)


class _ForestArrays:
    """The fitted forest as heap-indexed host arrays (node ``g`` of a tree
    has children ``2g + 1`` and ``2g + 2``): the model's public result, read
    once from the device and kept."""

    _forest: Optional[Forest]

    def _host_forest(self) -> Forest:
        if not isinstance(self._forest.feature, np.ndarray):
            self._forest = Forest(*jax.device_get(tuple(self._forest)))
        return self._forest

    @property
    def nodeFeature(self) -> np.ndarray:
        """(numTrees, nodes) split feature, -1 at a leaf."""
        return self._host_forest().feature

    @property
    def nodeThreshold(self) -> np.ndarray:
        """(numTrees, nodes): a row goes LEFT on ``x[feature] <= threshold``."""
        return self._host_forest().threshold

    @property
    def nodeIsLeaf(self) -> np.ndarray:
        return self._host_forest().is_leaf

    @property
    def nodeValue(self) -> np.ndarray:
        """(numTrees, nodes, S): class distribution, or [mean]."""
        return self._host_forest().leaf_value

    @property
    def nodeWeight(self) -> np.ndarray:
        """(numTrees, nodes) bootstrap-weighted rows that reached the node."""
        return self._host_forest().node_weight

    @property
    def nodeGain(self) -> np.ndarray:
        return self._host_forest().node_gain


@jax.jit
def _exactness_device(rs, w):
    """Fused device-side bf16-exactness predicate: ONE scalar readback
    (integrality of stats AND weights, and the max product bound) — a
    device-resident fit must not pull the (n, S) one-hot to host, and
    even the host path should pay one sync, not two (each readback is a
    full host round trip that stalls the async dispatch stream)."""
    rs = rs.astype(jnp.float32)
    return (
        jnp.all(rs == jnp.rint(rs))
        & jnp.all(w == jnp.rint(w))
        & (jnp.max(jnp.abs(rs)) * jnp.max(w) <= 256.0)
    )


@jax.jit
def _weight_exact_and_max(w):
    """[weights_all_integer, max_weight] as one device array — one pull."""
    return jnp.stack(
        [jnp.all(w == jnp.rint(w)).astype(jnp.float32), jnp.max(w)]
    )


def _hist_exact_in_bf16(row_stats, sample_w) -> bool:
    """True when every histogram operand survives bf16 rounding. The
    one-pass DEFAULT-precision histogram feeds ``sample_weight * stat``
    to the MXU as bf16 (fp32 accumulation), so exactness needs the
    *product* — integer and <= 256 — not just the raw stats: an integer
    weightCol of 129 drawn 3 times by the bootstrap contributes 387,
    which bf16 rounds. Bootstrap draws are integral today
    (Poisson/Bernoulli), but the guard verifies that rather than assume
    it."""
    if is_device_array(row_stats):
        if row_stats.size == 0:
            return False
        return bool(_exactness_device(row_stats, jnp.asarray(sample_w)))
    rs = np.asarray(row_stats, dtype=np.float32)
    if rs.size == 0 or not np.array_equal(rs, np.rint(rs)):
        return False
    w_stats = np.asarray(_weight_exact_and_max(jnp.asarray(sample_w)))
    if not w_stats[0]:
        return False
    return float(np.abs(rs).max()) * float(w_stats[1]) <= 256.0


def _fit_forest(params: _RandomForestParams, x: np.ndarray, row_stats: np.ndarray,
                impurity: str, classification: bool, mesh=None,
                stats_integral: bool = False) -> Forest:
    """Shared fit: bin, sample, grow. Returns the Forest arrays.

    Two kinds of program, neither read back inside the fit: ``rf bin``
    (quantile edges + packed bin ids, once) and ``rf grow`` (one batch of
    trees, all levels: :func:`ops.trees.grow_forest`). How many trees a batch
    holds follows from the shapes and the device's free memory
    (``membudget.batch_within_budget`` on :func:`ops.trees.builder_bytes`);
    what cannot fit even one tree raises ``FitMemoryError`` before any
    compile. A tree is a function of (rows, params, seed, its index) alone,
    so the batches change no node.

    With a mesh, rows are data-sharded and the per-level SELECTED histograms
    merge over ICI (:func:`grow_forest_sharded`); binning and weight sampling
    stay replicated (seed-deterministic)."""
    from spark_rapids_ml_tpu.core.ingest import place_array
    from spark_rapids_ml_tpu.core.membudget import batch_within_budget, fit_memory_guard

    n, d = x.shape
    # Budgeted admission (core/membudget.py): forest growth has no
    # streaming rung — the binned matrix must be resident — so an
    # over-budget input raises the structured FitMemoryError up front
    # instead of dying inside device_put. row_stats rides along as the
    # sidecar allocation priced on top of the matrix.
    fit_memory_guard(
        "random_forest", x, can_stream=False,
        why_cannot_stream="RandomForest has no streaming fit (histogram "
                          "growth needs the binned matrix resident)",
        mesh=mesh, dtype=np.float32, ledger_families=("rf",),
        extra_bytes=(
            0 if is_device_array(row_stats)
            else np.asarray(row_stats).size * 4
        ),
    )
    n_trees, max_depth = params.getNumTrees(), params.getMaxDepth()
    n_bins = min(params.getMaxBins(), max(2, n))
    m = resolve_feature_subset(
        params.getFeatureSubsetStrategy(), d, n_trees, classification
    )
    n_stats = row_stats.shape[1]
    shards = 1 if mesh is None else int(mesh.shape[DATA_AXIS])
    resident, per_tree, prepare = builder_bytes(
        -(-n // shards), d, n_bins, m, n_stats, max_depth,
        rows_resident=is_device_array(x),
    )
    batch_within_budget("random_forest", resident, prepare, 1, "binning pass")
    batch = batch_within_budget("random_forest", resident, per_tree, n_trees, "tree")
    n_batches = -(-n_trees // batch)
    batch = -(-n_trees // n_batches)  # as even as they come

    key = jax.random.key(params.getSeed())
    k_sample, k_feat = jax.random.split(key)
    sampling = (params.getSubsamplingRate(), params.getBootstrap())
    # Guarded placement: the whole-dataset uploads go through the
    # ingest.device_put chokepoint (fault point, OOM retry + cache
    # reclaim) instead of bare jnp.asarray calls.
    xj = place_array(x, dtype=jnp.float32)
    rs = place_array(row_stats, dtype=jnp.float32)
    # stats_integral: the caller GUARANTEES exact-integer stats (a plain
    # one-hot, no weightCol) — with the 256-clamped bootstrap weights the
    # bf16 exactness is then a static fact and the device-readback
    # predicate (one host round trip per fit) is skipped entirely.
    exact = classification and (
        stats_integral
        or _hist_exact_in_bf16(
            row_stats, sample_weights(k_sample, np.arange(n_trees), n, *sampling)
        )
    )
    kwargs = dict(
        max_depth=max_depth,
        n_bins=n_bins,
        n_features=d,
        impurity=impurity,
        feat_subset=m,
        min_instances=params.getMinInstancesPerNode(),
        min_info_gain=params.getMinInfoGain(),
        exact_counts=exact,
    )
    # The stage times the dispatch: no program of the fit is waited for.
    with StageRange("solve"):
        with TraceRange("rf bin", TraceColor.BLUE):
            edges, xb = quantize_and_bin(xj, n_bins)
        bump_counter("forest.bins.bytes", int(xb.size) * 4)
        grow = grow_forest if mesh is None else partial(grow_forest_sharded, mesh)
        grown = []
        for first in range(0, n_trees, batch):
            ids = np.arange(first, min(first + batch, n_trees), dtype=np.int32)
            with TraceRange("rf grow", TraceColor.RED):
                w = sample_weights(k_sample, ids, n, *sampling)
                grown.append(grow(xb, rs, w, edges, k_feat, ids, **kwargs))
        forest = grown[0] if len(grown) == 1 else Forest(*map(jnp.concatenate, zip(*grown)))
    # what the fit dispatched, from its shapes (nothing is read back for them)
    bump_counter("forest.grow.tree_batches", len(grown))
    bump_counter("forest.grow.tree_levels", n_trees * max_depth)
    bump_counter("forest.grow.selected_elems", n_trees * max_depth * n * m)
    bump_counter("forest.grow.hist_cells", n_trees * (2**max_depth - 1) * m * n_bins * n_stats)
    return forest


class RandomForestClassifier(_RandomForestParams, Estimator, MLReadable):
    """``RandomForestClassifier().setNumTrees(20).fit((X, y))``."""

    # Consumes device (X, y) pairs in place, so tuning loops may feed
    # device-resident fold slices (tuning._device_fold_prep).
    _device_foldable = True

    probabilityCol = Param("_", "probabilityCol", "probability column name", toString)
    rawPredictionCol = Param(
        "_", "rawPredictionCol", "raw prediction column name", toString
    )

    def __init__(self, uid: Optional[str] = None, mesh=None):
        super().__init__(uid)
        self.mesh = mesh
        self._setDefault(
            impurity="gini",
            probabilityCol="probability",
            rawPredictionCol="rawPrediction",
        )

    # Fit-time hint, not a Param (the fitted model's ``numClasses`` is a
    # plain attribute of the same name); survives Params.copy like mesh.
    _declared_num_classes = 0
    _copy_attrs = ("_declared_num_classes",)

    def setMesh(self, mesh) -> "RandomForestClassifier":
        self.mesh = mesh
        return self

    def getProbabilityCol(self) -> str:
        return self.getOrDefault(self.probabilityCol)

    def getRawPredictionCol(self) -> str:
        return self.getOrDefault(self.rawPredictionCol)

    def getNumClasses(self) -> int:
        return self._declared_num_classes

    def setNumClasses(self, v: int):
        """Declare the class count up front — the analogue of Spark ML's
        label-column METADATA (a NominalAttribute's numValues), which
        Spark's RandomForestClassifier trusts WITHOUT rescanning the
        labels. With the hint, a device-resident fit dispatches with no
        label readback at all (inferring the count forces one sync, a
        full host round trip); like Spark metadata, a
        wrong declaration is the caller's contract violation. 0 restores
        inference."""
        if v != 0 and v < 2:
            raise ValueError(f"numClasses must be 0 (infer) or >= 2, got {v}")
        self._declared_num_classes = int(v)
        return self

    def setProbabilityCol(self, v: str):
        return self._chain(self.probabilityCol, v)

    def setRawPredictionCol(self, v: str):
        return self._chain(self.rawPredictionCol, v)

    def setImpurity(self, v: str):
        if v not in ("gini", "entropy"):
            raise ValueError(f"impurity must be gini or entropy, got {v!r}")
        return self._chain(self.impurity, v)

    def _fit(self, dataset: Any) -> "RandomForestClassificationModel":
        x, y = _extract_xy(dataset, self.getFeaturesCol(), self.getLabelCol())
        declared = self.getNumClasses()
        if declared:
            if is_device_array(y):
                # Trusted label-metadata path (see setNumClasses): no
                # readback — inferring min/max is the sync the hint
                # exists to avoid.
                y_int = y.ravel().astype(jnp.int32)
            else:
                # Host labels cost nothing to validate, and skipping it
                # let a negative label wrap silently into the LAST class
                # column of the one-hot scatter below.
                y_int, _ = validate_int_labels(y)
            n_classes = declared
        else:
            y_int, n_classes = validate_int_labels(y)
            n_classes = max(n_classes, 2)
        w = extract_weights(dataset, self.getWeightCol())
        if is_device_array(y_int):
            # Device labels one-hot on device — no O(n) pull.
            row_stats = jax.nn.one_hot(y_int, n_classes, dtype=jnp.float32)
            if w is not None:
                row_stats = row_stats * jnp.asarray(w, dtype=jnp.float32)[:, None]
        else:
            row_stats = np.zeros((y_int.shape[0], n_classes), dtype=np.float32)
            row_stats[np.arange(y_int.shape[0]), y_int] = 1.0  # one-hot counts
            if w is not None:
                # Per-row weights multiply into the stat channels: histogram
                # contributions become weight * count, composing with the
                # per-tree bootstrap weights untouched.
                row_stats *= w[:, None].astype(np.float32)
        with TraceRange("rf-classifier fit", TraceColor.GREEN):
            forest = _fit_forest(
                self, x, row_stats, self.getImpurity(), True, self.mesh,
                stats_integral=w is None,
            )
        model = RandomForestClassificationModel(
            self.uid, forest, numFeatures=x.shape[1], numClasses=n_classes
        )
        return self._copyValues(model)


class RandomForestClassificationModel(_ForestArrays, _RandomForestParams, Model):
    probabilityCol = RandomForestClassifier.probabilityCol
    rawPredictionCol = RandomForestClassifier.rawPredictionCol

    def __init__(
        self,
        uid: Optional[str] = None,
        forest: Optional[Forest] = None,
        numFeatures: int = 0,
        numClasses: int = 0,
    ):
        super().__init__(uid)
        self._setDefault(
            impurity="gini",
            probabilityCol="probability",
            rawPredictionCol="rawPrediction",
        )
        self._forest = forest
        self._forest_dev = None
        self.numFeatures = numFeatures
        self.numClasses = numClasses

    def __getstate__(self):
        # Broadcast/pickle ships host forest arrays, never live device
        # buffers; the serving copy rebuilds lazily after load.
        state = dict(self.__dict__)
        state["_forest_dev"] = None
        return state

    def getProbabilityCol(self) -> str:
        return self.getOrDefault(self.probabilityCol)

    @property
    def featureImportances(self) -> np.ndarray:
        return feature_importances(self._forest, self.numFeatures)

    @property
    def totalNumNodes(self) -> int:
        leaf = np.asarray(self._forest.is_leaf)
        feat = np.asarray(self._forest.feature)
        # Reachable nodes: splits plus leaves that carry weight.
        w = np.asarray(self._forest.node_weight)
        return int(np.sum((feat >= 0) | (leaf & (w > 0))))

    def predictProbability(self, x) -> np.ndarray:
        # Shape-bucketed serving path: one AOT tree-routing program per
        # row bucket, forest resident on device across calls.
        return serve_rows(
            _proba_kernel,
            matrix_like(x),
            (_forest_device(self),),
            static={"depth": _forest_depth(self._forest)},
            name="rf.predictProbability",
        )

    def predict(self, x) -> np.ndarray:
        probs = self.predictProbability(x)
        if is_device_array(probs):
            return jnp.argmax(probs, axis=1)
        return np.argmax(probs, axis=1)

    def predictRaw(self, x) -> np.ndarray:
        """Spark RF rawPrediction: unnormalized per-class vote mass (mean
        leaf distribution scaled by the tree count)."""
        return self.predictProbability(x) * self._forest.feature.shape[0]

    def serving_signature(self):
        """The online-serving contract: the tree-routing probability
        kernel, the device-resident forest pytree, and the (n, C)
        class-distribution output spec (float32, the forests' dtype)."""
        n_classes = int(self.numClasses)
        return _forest_signature(
            self,
            _proba_kernel,
            "rf.predictProbability",
            lambda n, dtype: (
                jax.ShapeDtypeStruct((n, n_classes), np.float32),
            ),
            select=_select_argmax,
        )

    def transform(self, dataset: Any) -> Any:
        rows = extract_features(dataset, self.getFeaturesCol(), drop=self.getLabelCol())
        probs = self.predictProbability(rows)
        preds = np.argmax(probs, axis=1)
        # rawPrediction mirrors Spark RF: unnormalized per-class vote mass
        # (mean probability scaled by the tree count).
        raws = probs * len(np.asarray(self._forest.feature))
        if isinstance(dataset, DataFrame):
            out = dataset.withColumn(self.getPredictionCol(), list(preds.astype(float)))
            out = out.withColumn(self.getProbabilityCol(), [p for p in probs])
            return out.withColumn(self.getOrDefault(self.rawPredictionCol), [r for r in raws])
        try:
            import pandas as pd

            if isinstance(dataset, pd.DataFrame):
                out = dataset.copy()
                out[self.getPredictionCol()] = preds.astype(float)
                out[self.getProbabilityCol()] = list(probs)
                out[self.getOrDefault(self.rawPredictionCol)] = list(raws)
                return out
        except ImportError:  # pragma: no cover
            pass
        return preds

    def _save_impl(self, path: str) -> None:
        _save_forest_model(
            self,
            path,
            "org.apache.spark.ml.classification.RandomForestClassificationModel",
            {"numFeatures": self.numFeatures, "numClasses": self.numClasses},
        )

    @classmethod
    def _load_impl(cls, path: str) -> "RandomForestClassificationModel":
        metadata, forest = _load_forest_model(path, "RandomForestClassificationModel")
        model = cls(
            metadata["uid"],
            forest,
            numFeatures=metadata.get("numFeatures", 0),
            numClasses=metadata.get("numClasses", 0),
        )
        get_and_set_params(model, metadata)
        return model


class RandomForestRegressor(_RandomForestParams, Estimator, MLReadable):
    """``RandomForestRegressor().setNumTrees(20).fit((X, y))``."""

    # Consumes device (X, y) pairs in place, so tuning loops may feed
    # device-resident fold slices (tuning._device_fold_prep).
    _device_foldable = True

    def __init__(self, uid: Optional[str] = None, mesh=None):
        super().__init__(uid)
        self.mesh = mesh
        self._setDefault(impurity="variance")

    def setMesh(self, mesh) -> "RandomForestRegressor":
        self.mesh = mesh
        return self

    def setImpurity(self, v: str):
        if v != "variance":
            raise ValueError(f"regression impurity must be variance, got {v!r}")
        return self._chain(self.impurity, v)

    def _fit(self, dataset: Any) -> "RandomForestRegressionModel":
        x, y = _extract_xy(dataset, self.getFeaturesCol(), self.getLabelCol())
        # Stats channels [1, y, y^2] -> weighted variance impurity. Labels
        # are centered first: the E[y^2] - mean^2 form in float32 would lose
        # the variance signal to cancellation when |mean(y)| >> std(y);
        # variance gains are shift-invariant, so centering changes nothing
        # but the conditioning. The mean is added back to the leaf values.
        w = extract_weights(dataset, self.getWeightCol())
        if is_device_array(y):
            # Device targets stay resident: mean/center/stack on device
            # (one scalar readback for the leaf-shift constant).
            yj = y.ravel().astype(jnp.float32)
            wj = None if w is None else jnp.asarray(w, dtype=jnp.float32)
            y_mean = float(
                jnp.average(yj, weights=wj) if wj is not None else jnp.mean(yj)
            )
            yc = yj - y_mean
            row_stats = jnp.stack([jnp.ones_like(yc), yc, yc * yc], axis=1)
            if wj is not None:
                row_stats = row_stats * wj[:, None]
        else:
            y_mean = (
                float(np.average(y, weights=w))
                if w is not None
                else (float(np.mean(y)) if y.size else 0.0)
            )
            yc = y - y_mean
            row_stats = np.stack([np.ones_like(yc), yc, yc * yc], axis=1)
            if w is not None:
                row_stats *= w[:, None]
        with TraceRange("rf-regressor fit", TraceColor.GREEN):
            forest = _fit_forest(self, x, row_stats, "variance", False, self.mesh)
        forest = forest._replace(leaf_value=forest.leaf_value + y_mean)
        model = RandomForestRegressionModel(self.uid, forest, numFeatures=x.shape[1])
        return self._copyValues(model)


class RandomForestRegressionModel(_ForestArrays, _RandomForestParams, Model):
    def __init__(
        self,
        uid: Optional[str] = None,
        forest: Optional[Forest] = None,
        numFeatures: int = 0,
    ):
        super().__init__(uid)
        self._setDefault(impurity="variance")
        self._forest = forest
        self._forest_dev = None
        self.numFeatures = numFeatures

    def __getstate__(self):
        state = dict(self.__dict__)
        state["_forest_dev"] = None
        return state

    @property
    def featureImportances(self) -> np.ndarray:
        return feature_importances(self._forest, self.numFeatures)

    def predict(self, x) -> np.ndarray:
        return serve_rows(
            _reg_kernel,
            matrix_like(x),
            (_forest_device(self),),
            static={"depth": _forest_depth(self._forest)},
            name="rf.predict",
        )

    def serving_signature(self):
        """The online-serving contract: the tree-routing regression
        kernel, the device-resident forest, and the (n,) mean-leaf-value
        output spec (float32, the forests' dtype)."""
        return _forest_signature(
            self,
            _reg_kernel,
            "rf.predict",
            lambda n, dtype: (jax.ShapeDtypeStruct((n,), np.float32),),
        )

    def transform(self, dataset: Any) -> Any:
        rows = extract_features(dataset, self.getFeaturesCol(), drop=self.getLabelCol())
        preds = self.predict(rows)
        if isinstance(dataset, DataFrame):
            return dataset.withColumn(self.getPredictionCol(), list(preds))
        try:
            import pandas as pd

            if isinstance(dataset, pd.DataFrame):
                out = dataset.copy()
                out[self.getPredictionCol()] = preds
                return out
        except ImportError:  # pragma: no cover
            pass
        return preds

    def _save_impl(self, path: str) -> None:
        _save_forest_model(
            self,
            path,
            "org.apache.spark.ml.regression.RandomForestRegressionModel",
            {"numFeatures": self.numFeatures},
        )

    @classmethod
    def _load_impl(cls, path: str) -> "RandomForestRegressionModel":
        metadata, forest = _load_forest_model(path, "RandomForestRegressionModel")
        model = cls(metadata["uid"], forest, numFeatures=metadata.get("numFeatures", 0))
        get_and_set_params(model, metadata)
        return model


def _forest_depth(forest: Forest) -> int:
    """Recover max_depth from the heap size: N = 2^(D+1) - 1."""
    n_nodes = forest.feature.shape[1]
    return int(math.log2(n_nodes + 1)) - 1


def _spark_nodedata_type():
    """Arrow schema of Spark's ``(treeID, nodeData)`` rows — the exact
    DecisionTreeModelReadWrite.NodeData struct (Spark 3.x, incl. the 3.0+
    ``rawCount`` field), so directories written here load in upstream
    Spark and vice versa (SURVEY §3.4 discipline applied to forests)."""
    import pyarrow as pa

    split_t = pa.struct(
        [
            ("featureIndex", pa.int32()),
            ("leftCategoriesOrThreshold", pa.list_(pa.float64())),
            ("numCategories", pa.int32()),
        ]
    )
    node_t = pa.struct(
        [
            ("id", pa.int32()),
            ("prediction", pa.float64()),
            ("impurity", pa.float64()),
            ("impurityStats", pa.list_(pa.float64())),
            ("rawCount", pa.int64()),
            ("gain", pa.float64()),
            ("leftChild", pa.int32()),
            ("rightChild", pa.int32()),
            ("split", split_t),
        ]
    )
    return node_t


def _tree_to_nodedata(f: Forest, t: int, classification: bool) -> list:
    """One tree's heap arrays -> Spark NodeData dicts in PREORDER ids
    (root 0, left subtree next — EnsembleModelReadWrite's numbering).

    Classification ``impurityStats`` are the per-class weighted counts
    (leaf distribution x node weight); regression stats are Spark's
    Variance triplet [count, sum, sumSq] with sumSq reconstructed EXACTLY
    from the stored node impurity (var = sumSq/w - mean^2). Leaves carry
    Spark's sentinels: gain -1, children -1, split (-1, [], -1).

    APPROXIMATION (docs/PARITY.md "Known deviations"): Spark's
    ``rawCount`` is the UNWEIGHTED instance count at the node; the heap
    arrays keep only the weighted node weight, so ``rawCount`` is
    written as ``round(node_weight)``. With no ``weightCol`` (weights
    all 1.0) the two are identical; under fractional row weights the
    stored rawCount is the rounded weighted count, not the row count.
    Predictions are unaffected (nothing reads rawCount back); only the
    persisted field's meaning deviates.
    """
    feature = np.asarray(f.feature[t])
    thr = np.asarray(f.threshold[t], dtype=np.float64)
    leaf = np.asarray(f.is_leaf[t])
    lv = np.asarray(f.leaf_value[t], dtype=np.float64)
    w = np.asarray(f.node_weight[t], dtype=np.float64)
    gain = np.asarray(f.node_gain[t], dtype=np.float64)
    imp = np.asarray(f.node_impurity[t], dtype=np.float64)
    rows: list = []

    def walk(g: int) -> int:
        my = len(rows)
        rows.append(None)
        is_split = (not leaf[g]) and feature[g] >= 0
        if classification:
            stats = (lv[g] * w[g]).tolist()
            pred = float(np.argmax(lv[g]))
        else:
            mean = float(lv[g, 0])
            stats = [w[g], mean * w[g], (imp[g] + mean * mean) * w[g]]
            pred = mean
        node = {
            "id": my,
            "prediction": pred,
            "impurity": float(imp[g]),
            "impurityStats": stats,
            "rawCount": int(round(w[g])),
            "gain": float(gain[g]) if is_split else -1.0,
            "leftChild": -1,
            "rightChild": -1,
            "split": {
                "featureIndex": int(feature[g]) if is_split else -1,
                "leftCategoriesOrThreshold": [float(thr[g])] if is_split else [],
                "numCategories": -1,
            },
        }
        rows[my] = node
        if is_split:
            node["leftChild"] = walk(2 * g + 1)
            node["rightChild"] = walk(2 * g + 2)
        return my

    walk(0)
    return rows


def _save_forest_model(model, path: str, class_name: str, extra: dict) -> None:
    """Spark EnsembleModelReadWrite layout: ``metadata/`` (with
    numFeatures/numClasses/numTrees), ``treesMetadata/`` (one row per tree:
    treeID, per-tree metadata JSON, weight), and ``data/`` as
    ``(treeID, nodeData struct)`` rows in Spark's exact NodeData schema —
    a forest saved here loads in upstream Spark ML and a Spark-written
    forest directory loads here."""
    import json as _json
    import os as _os

    from spark_rapids_ml_tpu.core.persistence import _HAS_ARROW

    f = model._forest
    T = int(np.asarray(f.feature).shape[0])
    classification = "Classification" in class_name
    extra = dict(extra)
    extra.setdefault("numTrees", T)
    save_metadata(model, path, class_name=class_name, extra_metadata=extra)

    if not _HAS_ARROW:  # pragma: no cover - arrow is in every test image
        _np_dir = _os.path.join(path, "data")
        _os.makedirs(_np_dir, exist_ok=True)
        np.savez(
            _os.path.join(_np_dir, "part-00000.npz"),
            **{k: np.asarray(getattr(f, k)) for k in Forest._fields},
        )
        return

    import pyarrow as pa
    import pyarrow.parquet as pq

    node_t = _spark_nodedata_type()
    tree_ids, nodes = [], []
    for t in range(T):
        for nd in _tree_to_nodedata(f, t, classification):
            tree_ids.append(t)
            nodes.append(nd)
    data_dir = _os.path.join(path, "data")
    _os.makedirs(data_dir, exist_ok=True)
    table = pa.Table.from_arrays(
        [
            pa.array(tree_ids, type=pa.int32()),
            pa.array(nodes, type=node_t),
        ],
        schema=pa.schema([("treeID", pa.int32()), ("nodeData", node_t)]),
    )
    pq.write_table(table, _os.path.join(data_dir, "part-00000.parquet"))
    open(_os.path.join(data_dir, "_SUCCESS"), "w").close()

    # treesMetadata: per-tree DefaultParamsWriter metadata + tree weight
    # (all 1.0 — uniform-vote forests, as Spark RF writes).
    tm_dir = _os.path.join(path, "treesMetadata")
    _os.makedirs(tm_dir, exist_ok=True)
    tm = pa.Table.from_arrays(
        [
            pa.array(list(range(T)), type=pa.int32()),
            pa.array(
                [
                    _json.dumps(
                        {
                            "class": (
                                "org.apache.spark.ml.classification."
                                "DecisionTreeClassificationModel"
                                if classification
                                else "org.apache.spark.ml.regression."
                                "DecisionTreeRegressionModel"
                            ),
                            "uid": f"dtc_{model.uid}_{t}",
                            "paramMap": {},
                        }
                    )
                    for t in range(T)
                ],
                type=pa.string(),
            ),
            pa.array([1.0] * T, type=pa.float64()),
        ],
        schema=pa.schema(
            [
                ("treeID", pa.int32()),
                ("metadata", pa.string()),
                ("weights", pa.float64()),
            ]
        ),
    )
    pq.write_table(tm, _os.path.join(tm_dir, "part-00000.parquet"))
    open(_os.path.join(tm_dir, "_SUCCESS"), "w").close()


def _forest_from_nodedata(per_tree: list, classification: bool) -> Forest:
    """Spark ``(treeID, nodeData)`` rows -> heap-indexed Forest arrays.

    Node ids are arbitrary (pointers are explicit in leftChild/rightChild);
    the walk from each tree's root re-derives heap slots. The heap depth is
    the deepest tree's depth (static-shape arrays, as grow_forest builds).
    """

    def node_depth(nodes, nid):
        nd = nodes[nid]
        if nd["leftChild"] < 0:
            return 0
        return 1 + max(
            node_depth(nodes, nd["leftChild"]),
            node_depth(nodes, nd["rightChild"]),
        )

    roots = []
    for nodes in per_tree:
        child_ids = set()
        for nd in nodes.values():
            if nd["leftChild"] >= 0:
                child_ids.add(nd["leftChild"])
                child_ids.add(nd["rightChild"])
        roots.append(next(i for i in nodes if i not in child_ids))

    depth = max(node_depth(nodes, r) for nodes, r in zip(per_tree, roots))
    if depth > 20:
        raise ValueError(f"forest depth {depth} exceeds the supported 20")
    T = len(per_tree)
    N = 2 ** (depth + 1) - 1
    s_out = (
        max(len(nd["impurityStats"]) for nodes in per_tree for nd in nodes.values())
        if classification
        else 1
    )

    feature = np.full((T, N), -1, dtype=np.int32)
    threshold = np.zeros((T, N), dtype=np.float32)
    is_leaf = np.zeros((T, N), dtype=bool)
    leaf_value = np.zeros((T, N, s_out), dtype=np.float32)
    node_weight = np.zeros((T, N), dtype=np.float32)
    node_gain = np.zeros((T, N), dtype=np.float32)
    node_imp = np.zeros((T, N), dtype=np.float32)

    def place(t, nodes, nid, g):
        nd = nodes[nid]
        stats = np.asarray(nd["impurityStats"], dtype=np.float64)
        if classification:
            wsum = float(stats.sum())
            node_weight[t, g] = wsum
            leaf_value[t, g, : stats.size] = (
                stats / wsum if wsum > 0 else 1.0 / stats.size
            )
        else:
            node_weight[t, g] = float(stats[0]) if stats.size else 0.0
            leaf_value[t, g, 0] = nd["prediction"]
        node_imp[t, g] = nd["impurity"]
        if nd["leftChild"] >= 0:
            feature[t, g] = nd["split"]["featureIndex"]
            threshold[t, g] = nd["split"]["leftCategoriesOrThreshold"][0]
            node_gain[t, g] = max(float(nd["gain"]), 0.0)
            place(t, nodes, nd["leftChild"], 2 * g + 1)
            place(t, nodes, nd["rightChild"], 2 * g + 2)
        else:
            is_leaf[t, g] = True

    for t, (nodes, r) in enumerate(zip(per_tree, roots)):
        place(t, nodes, r, 0)

    return Forest(
        jnp.asarray(feature),
        jnp.asarray(threshold),
        jnp.asarray(is_leaf),
        jnp.asarray(leaf_value),
        jnp.asarray(node_weight),
        jnp.asarray(node_gain),
        jnp.asarray(node_imp),
    )


def _load_forest_model(path: str, expected_class: str):
    metadata = load_metadata(path, expected_class=expected_class)
    rows = load_rows(path)
    classification = "Classification" in expected_class
    if "nodeData" in rows:
        by_tree: dict = {}
        for tid, nd in zip(rows["treeID"], rows["nodeData"]):
            by_tree.setdefault(int(tid), {})[int(nd["id"])] = nd
        per_tree = [by_tree[t] for t in sorted(by_tree)]
        return metadata, _forest_from_nodedata(per_tree, classification)
    if "nodeID" in rows:
        # Directories written before the r5 Spark-schema alignment: the
        # flattened (treeID, nodeID, per-field scalar columns) layout.
        # node_impurity was not stored then; it backfills as 0 (only the
        # Spark-format WRITER consumes it, and a legacy model re-saved
        # through it records impurity 0 rather than failing).
        tree_id = np.asarray(rows["treeID"])
        node_id = np.asarray(rows["nodeID"])
        T = int(tree_id.max()) + 1
        N = int(node_id.max()) + 1
        order = np.argsort(tree_id * N + node_id)

        def grid(name, dtype):
            return np.asarray(rows[name])[order].reshape(T, N).astype(dtype)

        leaf_value = np.stack(
            [rows["leafValue"][i] for i in order]
        ).reshape(T, N, -1)
        forest = Forest(
            jnp.asarray(grid("feature", np.int32)),
            jnp.asarray(grid("threshold", np.float32)),
            jnp.asarray(grid("isLeaf", bool)),
            jnp.asarray(leaf_value.astype(np.float32)),
            jnp.asarray(grid("nodeWeight", np.float32)),
            jnp.asarray(grid("nodeGain", np.float32)),
            jnp.zeros((T, N), dtype=jnp.float32),
        )
        return metadata, forest
    # npz fallback written by arrow-less environments: raw heap arrays.
    forest = Forest(*(jnp.asarray(np.asarray(rows[k])) for k in Forest._fields))
    return metadata, forest

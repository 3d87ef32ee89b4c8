"""Pipeline fusion contract suite (`spark_rapids_ml_tpu/pipeline_fusion/`).

The claims under test:

- FUSED == STAGED, bitwise, for every fusable 2-/3-stage chain: the
  composite program and the stage-at-a-time loop are the same math.
- An unfusable chain degrades LOUDLY (one structured
  ``FusionFallbackWarning``) and CORRECTLY (staged results).
- The fused program's ledgered bytes are STRICTLY below the staged sum
  (each stage's transform-contract selection runs inside the program, so
  dead stage outputs are never materialized) — the whole point.
- A fused pipeline is a first-class servable: it registers, warms,
  round-trips by path alone, and hot-swaps version-atomically under
  threaded load.
- ``Pipeline.fit`` / CrossValidator / TrainValidationSplit run pipelines
  on device-resident data with no host hop, and fit the same models.
"""

import os
import threading
from contextlib import contextmanager

import numpy as np
import pytest

from spark_rapids_ml_tpu.classification import (
    LogisticRegression,
    RandomForestClassifier,
)
from spark_rapids_ml_tpu.clustering import KMeans
from spark_rapids_ml_tpu.evaluation import MulticlassClassificationEvaluator
from spark_rapids_ml_tpu.feature import PCA
from spark_rapids_ml_tpu.pipeline import Pipeline, PipelineModel
from spark_rapids_ml_tpu.pipeline_fusion import (
    CompositeSignature,
    FusionFallbackWarning,
    fuse_pipeline_stages,
)
from spark_rapids_ml_tpu.regression import (
    LinearRegression,
    RandomForestRegressor,
)
from spark_rapids_ml_tpu.serving.server import ServingRuntime
from spark_rapids_ml_tpu.tuning import (
    CrossValidator,
    ParamGridBuilder,
    TrainValidationSplit,
    _device_fold_prep,
)
from spark_rapids_ml_tpu.utils.envknobs import env_str

D = 12  # input feature width shared by the chain fixtures


@contextmanager
def fusion_off():
    """Force the staged path (the in-test reference for parity checks)."""
    prev = env_str("TPUML_PIPELINE_FUSION")
    os.environ["TPUML_PIPELINE_FUSION"] = "off"
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop("TPUML_PIPELINE_FUSION", None)
        else:
            os.environ["TPUML_PIPELINE_FUSION"] = prev


@pytest.fixture
def data(rng):
    x = rng.normal(size=(96, D)).astype(np.float64)
    y = (x[:, 0] + x[:, 1] - x[:, 2] > 0).astype(np.int64)
    return x, y


CHAINS = {
    "pca-kmeans": lambda: [PCA().setK(4), KMeans().setK(3).setSeed(7)],
    "pca-logistic": lambda: [PCA().setK(4), LogisticRegression().setMaxIter(25)],
    "pca-linreg": lambda: [PCA().setK(4), LinearRegression()],
    "pca-rf-classifier": lambda: [
        PCA().setK(4),
        RandomForestClassifier().setNumTrees(5).setMaxDepth(4).setSeed(3),
    ],
    "pca-rf-regressor": lambda: [
        PCA().setK(4),
        RandomForestRegressor().setNumTrees(5).setMaxDepth(4).setSeed(3),
    ],
    "pca-pca-kmeans": lambda: [
        PCA().setK(6),
        PCA().setK(3),
        KMeans().setK(3).setSeed(7),
    ],
}


class TestFusedParity:
    """Fused transform == staged transform, bitwise, per fusable chain."""

    @pytest.mark.parametrize("chain", sorted(CHAINS), ids=sorted(CHAINS))
    def test_chain_parity(self, chain, data):
        x, y = data
        model = Pipeline(stages=CHAINS[chain]()).fit((x, y))
        fused = np.asarray(model.transform(x))
        with fusion_off():
            staged = np.asarray(model.transform(x))
        np.testing.assert_array_equal(fused, staged)
        assert fused.shape[0] == x.shape[0]

    def test_fused_path_engages(self, data):
        from spark_rapids_ml_tpu.utils.tracing import counter_value

        x, y = data
        model = Pipeline(stages=CHAINS["pca-logistic"]()).fit((x, y))
        before = counter_value("pipeline.fusion.fused")
        model.transform(x)
        assert counter_value("pipeline.fusion.fused") == before + 1

    def test_device_array_in_device_array_out(self, data):
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.core.data import is_device_array

        x, y = data
        model = Pipeline(stages=CHAINS["pca-logistic"]()).fit((x, y))
        xd = jnp.asarray(x)
        out = model.transform(xd)
        assert is_device_array(out)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(model.transform(x)))

    def test_serving_signature_is_composite(self, data):
        x, y = data
        model = Pipeline(stages=CHAINS["pca-logistic"]()).fit((x, y))
        sig = model.serving_signature()
        assert isinstance(sig, CompositeSignature)
        assert sig.n_features == D
        assert sig.stage_names == ("pca.transform", "logreg.predict")
        assert sig.name == "fused:pca.transform+logreg.predict"
        # Every stage's static config is part of the composite program
        # key, stage-prefixed.
        assert any(k.startswith("s0_") for k in sig.static)
        assert any(k.startswith("s1_") for k in sig.static)

    def test_composite_kernel_identity_is_stable(self, data):
        """Two signature builds share ONE kernel object — the AOT program
        cache keys on function identity; a fresh closure per call would
        recompile every serve."""
        x, y = data
        model = Pipeline(stages=CHAINS["pca-logistic"]()).fit((x, y))
        assert model.serving_signature().kernel is model.serving_signature().kernel


class TestFallback:
    """Unfusable chains degrade loudly and correctly."""

    class _Opaque:
        """A transformer with no serving_signature()."""

        uid = "opaque-stage"

        def transform(self, x):
            return np.asarray(x) * 1.0

    def test_non_signature_stage_warns_and_matches_staged(self, data):
        x, y = data
        pca = PCA().setK(4).fit(x)
        model = PipelineModel("pm-opaque", [pca, self._Opaque()])
        with pytest.warns(FusionFallbackWarning) as rec:
            out = np.asarray(model.transform(x))
        w = rec[0].message
        assert w.pipeline == "pm-opaque"
        assert w.stage == 1
        assert "serving_signature" in w.reason
        np.testing.assert_array_equal(
            out, self._Opaque().transform(pca.transform(x))
        )

    def test_width_mismatch_warns(self, data):
        x, y = data
        pca = PCA().setK(3).fit(x)  # emits width 3
        lr = LogisticRegression().setMaxIter(5).fit((x[:, :5], y))  # wants 5
        with pytest.warns(FusionFallbackWarning) as rec:
            assert fuse_pipeline_stages([pca, lr], pipeline="pm-width") is None
        assert "width" in rec[0].message.reason
        assert rec[0].message.stage == 0

    def test_strict_signature_raises(self, data):
        x, _ = data
        pca = PCA().setK(4).fit(x)
        model = PipelineModel("pm-strict", [pca, self._Opaque()])
        with pytest.raises(TypeError, match="not fusable"):
            model.serving_signature()

    def test_off_knob_never_fuses(self, data, monkeypatch):
        from spark_rapids_ml_tpu.utils.tracing import counter_value

        x, y = data
        model = Pipeline(stages=CHAINS["pca-kmeans"]()).fit((x, y))
        monkeypatch.setenv("TPUML_PIPELINE_FUSION", "off")
        before = counter_value("pipeline.fusion.fused")
        model.transform(x)
        assert counter_value("pipeline.fusion.fused") == before

    def test_dataframe_keeps_column_contract(self, rng):
        """DataFrames NEVER take the fused path: each stage appends its
        output column (the Spark contract)."""
        from spark_rapids_ml_tpu.core.data import DataFrame

        x = rng.normal(size=(40, D))
        df = DataFrame({"features": list(x)})
        model = Pipeline(
            stages=[
                PCA().setK(3).setInputCol("features").setOutputCol("pca"),
                KMeans().setK(3).setFeaturesCol("pca").setSeed(0),
            ]
        ).fit(df)
        out = model.transform(df)
        assert "pca" in out.columns and "prediction" in out.columns


class TestLedgerProof:
    """The acceptance criterion: fused bytes STRICTLY below staged sum,
    with bit parity, in the same test."""

    def test_fused_bytes_strictly_below_staged_sum(self, data):
        from spark_rapids_ml_tpu.core.serving import clear_program_cache
        from spark_rapids_ml_tpu.observability import costs

        x, y = data
        model = Pipeline(
            stages=[PCA().setK(7), LogisticRegression().setMaxIter(25)]
        ).fit((x, y))
        ledger = costs.configure(enable=True)
        try:
            clear_program_cache()
            with fusion_off():
                staged = np.asarray(model.transform(x))
            fused = np.asarray(model.transform(x))
            np.testing.assert_array_equal(fused, staged)

            doc = ledger.snapshot()
            fused_bytes = staged_bytes = 0
            for e in doc["entries"]:
                fam = e.get("family") or ""
                b = int(e.get("bytes_accessed") or 0)
                if fam.startswith("fused:"):
                    fused_bytes += b
                elif fam in ("pca.transform", "logreg.predict"):
                    staged_bytes += b
            assert fused_bytes > 0 and staged_bytes > 0
            # The logistic forward kernel materializes (labels, probs,
            # raw); the pipeline contract exposes labels only. In the
            # composite the selection happens in-program, so the unused
            # outputs are dead code to XLA: strictly fewer bytes than
            # the staged stages' total.
            assert fused_bytes < staged_bytes
        finally:
            costs.reset_for_tests()


class TestServingIntegration:
    """A fused pipeline is one versioned servable."""

    def test_register_warm_submit(self, data):
        x, y = data
        model = Pipeline(stages=CHAINS["pca-logistic"]()).fit((x, y))
        rt = ServingRuntime()
        try:
            mv = rt.register("pipe", model, alias="prod", warm_buckets=(8, 32))
            assert isinstance(mv.signature, CompositeSignature)
            out = rt.submit("pipe@prod", x[:20]).result(timeout=60)
            np.testing.assert_array_equal(
                np.asarray(out), np.asarray(model.transform(x[:20]))
            )
        finally:
            rt.close()

    def test_registry_load_by_path_alone(self, data, tmp_path):
        """satellite: ModelRegistry.load with model_cls omitted resolves
        the class from the persisted metadata — a saved PipelineModel
        round-trips into the registry by path alone."""
        x, y = data
        model = Pipeline(stages=CHAINS["pca-logistic"]()).fit((x, y))
        path = str(tmp_path / "fused_pipe")
        model.save(path)
        rt = ServingRuntime()
        try:
            mv = rt.load("pipe", path, alias="prod", warm_buckets=(8,))
            assert isinstance(mv.model, PipelineModel)
            out = rt.submit("pipe@prod", x[:16]).result(timeout=60)
            np.testing.assert_array_equal(
                np.asarray(out), np.asarray(model.transform(x[:16]))
            )
        finally:
            rt.close()

    def test_hot_swap_fused_pipeline_version_pure(self, data):
        """Swap prod from fused v1 to fused v2 under threaded load: every
        answer is bitwise v1's or v2's, the freshness table shows both
        versions serving with v2 strictly after v1 first appears."""
        from tools.tpuml_loadgen import FreshnessTable

        x, y = data
        m1 = Pipeline(stages=[PCA().setK(4), KMeans().setK(3).setSeed(7)]).fit((x, y))
        m2 = Pipeline(stages=[PCA().setK(5), KMeans().setK(4).setSeed(11)]).fit((x, y))
        exp1 = np.asarray(m1.transform(x))
        exp2 = np.asarray(m2.transform(x))

        rt = ServingRuntime(max_batch=16, max_delay_ms=2.0)
        fresh = FreshnessTable()
        collected = []
        lock = threading.Lock()
        try:
            v1 = rt.register("pipe", m1, alias="prod")

            def worker(tid):
                local = []
                for j in range(20):
                    i = (tid * 20 + j) % x.shape[0]
                    fut = rt.submit("pipe@prod", x[i])
                    out = np.asarray(fut.result(timeout=60))
                    fresh.note(fut)
                    local.append((i, out))
                with lock:
                    collected.extend(local)

            threads = [
                threading.Thread(target=worker, args=(t,)) for t in range(4)
            ]
            for t in threads:
                t.start()
            v2 = rt.register("pipe", m2)
            rt.set_alias("pipe", "prod", v2.version)
            for t in threads:
                t.join()
        finally:
            rt.close()

        for i, out in collected:
            ok = np.array_equal(out, exp1[i : i + 1]) or np.array_equal(
                out, exp2[i : i + 1]
            )
            assert ok, f"row {i} matches neither pipeline version"
        report = {r["version"]: r for r in fresh.report()}
        assert v2.version in report, "swap target never served"
        if v1.version in report:  # v1 may drain before any completion lands
            assert (
                report[v1.version]["first_seen_s"]
                <= report[v2.version]["first_seen_s"]
            )


class TestFitFusion:
    """Fit-side fusion: device-resident datasets through whole pipelines."""

    def test_fit_device_ingest_matches_host_fit(self, data, monkeypatch):
        x, y = data
        pipe = Pipeline(stages=[PCA().setK(4), LogisticRegression().setMaxIter(25)])
        fused_model = pipe.fit((x, y))
        monkeypatch.setenv("TPUML_PIPELINE_FUSION_FIT", "off")
        host_model = pipe.fit((x, y))
        with fusion_off():
            np.testing.assert_array_equal(
                np.asarray(fused_model.transform(x)),
                np.asarray(host_model.transform(x)),
            )

    def test_pipeline_is_device_foldable(self, data):
        x, y = data
        pipe = Pipeline(stages=[PCA().setK(3), LogisticRegression()])
        assert pipe._device_foldable
        prep = _device_fold_prep((x, y), pipe)
        assert prep is not None
        xs, ys = prep.slice(np.arange(16))
        from spark_rapids_ml_tpu.core.data import is_device_array

        assert is_device_array(xs) and is_device_array(ys)

    def test_opaque_stage_disables_device_folds(self, data):
        x, y = data
        pipe = Pipeline(stages=[TestFallback._Opaque(), LogisticRegression()])
        assert not pipe._device_foldable
        assert _device_fold_prep((x, y), pipe) is None

    def test_cv_over_pipeline_with_inner_grid(self, data):
        """CrossValidator tunes params of INNER pipeline stages on
        device-resident folds; Pipeline.copy routes each grid entry to
        the stage that owns it."""
        x, y = data
        pca = PCA().setK(4)
        lr = LogisticRegression().setMaxIter(20)
        pipe = Pipeline(stages=[pca, lr])
        grid = (
            ParamGridBuilder()
            .addGrid(pca.k, [3, 4])
            .addGrid(lr.regParam, [0.0, 0.1])
            .build()
        )
        cvm = (
            CrossValidator()
            .setEstimator(pipe)
            .setEstimatorParamMaps(grid)
            .setEvaluator(MulticlassClassificationEvaluator())
            .setNumFolds(3)
            .fit((x, y))
        )
        assert len(cvm.avgMetrics) == 4
        assert all(np.isfinite(m) for m in cvm.avgMetrics)
        best = cvm.bestModel
        assert isinstance(best, PipelineModel)
        assert best.stages[0].getK() in (3, 4)
        preds = np.asarray(best.transform(x))
        assert (preds == y).mean() > 0.6

    def test_tvs_over_pipeline_with_inner_grid(self, data):
        x, y = data
        pca = PCA().setK(4)
        pipe = Pipeline(stages=[pca, LogisticRegression().setMaxIter(20)])
        grid = ParamGridBuilder().addGrid(pca.k, [2, 4]).build()
        tvm = (
            TrainValidationSplit()
            .setEstimator(pipe)
            .setEstimatorParamMaps(grid)
            .setEvaluator(MulticlassClassificationEvaluator())
            .setTrainRatio(0.75)
            .fit((x, y))
        )
        assert len(tvm.validationMetrics) == 2
        assert isinstance(tvm.bestModel, PipelineModel)

    def test_pipeline_copy_routes_inner_extra(self):
        pca = PCA().setK(4)
        lr = LogisticRegression().setMaxIter(20)
        pipe = Pipeline(stages=[pca, lr])
        clone = pipe.copy({pca.k: 2, lr.regParam: 0.5})
        assert clone.stages[0].getK() == 2
        assert clone.stages[1].getRegParam() == 0.5
        # Originals untouched; stage objects are copies, not aliases.
        assert pca.getK() == 4 and lr.getRegParam() == 0.0
        assert clone.stages[0] is not pca

    def test_pipeline_model_copy_keeps_stages(self, data):
        x, y = data
        model = Pipeline(stages=CHAINS["pca-kmeans"]()).fit((x, y))
        clone = model.copy()
        assert len(clone.stages) == 2
        np.testing.assert_array_equal(
            np.asarray(clone.transform(x)), np.asarray(model.transform(x))
        )


class TestFuserUnit:
    def test_fuse_empty_chain_warns_none(self):
        with pytest.warns(FusionFallbackWarning):
            assert fuse_pipeline_stages([], pipeline="empty") is None

    def test_static_prefix_roundtrip(self):
        from spark_rapids_ml_tpu.pipeline_fusion.fuser import _demux_static

        per = _demux_static(
            {"s0_precision": "f32", "s1_n_classes": 3, "s1_threshold": 0.5},
            2,
        )
        assert per == [{"precision": "f32"}, {"n_classes": 3, "threshold": 0.5}]

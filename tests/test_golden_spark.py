"""Golden-file cross-compat: model directories in the EXACT shape upstream
Spark writes them must load through this framework, and directories this
framework writes must carry the exact structural schema Spark reads.

No pyspark/JVM exists in this image, so the golden directories are
byte-constructed here from Spark's documented on-disk contract
(DefaultParamsWriter metadata JSON + snappy parquet with Spark's
row-metadata key and MatrixUDT/VectorUDT structs — RapidsPCA.scala:218-254,
SURVEY §3.4 "must keep this exact on-disk format"): Spark-style part file
names, sparkVersion stamps, JVM class names, and nullable struct fields.
"""

import json
import os

import numpy as np
import pytest

pa = pytest.importorskip("pyarrow")
import pyarrow.parquet as pq  # noqa: E402

from spark_rapids_ml_tpu.classification import (  # noqa: E402
    LogisticRegressionModel,
    RandomForestClassificationModel,
    RandomForestClassifier,
)
from spark_rapids_ml_tpu.clustering import KMeansModel  # noqa: E402
from spark_rapids_ml_tpu.feature import PCA, PCAModel  # noqa: E402
from spark_rapids_ml_tpu.regression import (  # noqa: E402
    LinearRegressionModel,
    RandomForestRegressionModel,
    RandomForestRegressor,
)

# Spark's MatrixUDT / VectorUDT arrow-side schemas, nullable like Spark's.
_SPARK_MATRIX = pa.struct(
    [
        ("type", pa.int8()),
        ("numRows", pa.int32()),
        ("numCols", pa.int32()),
        ("colPtrs", pa.list_(pa.int32())),
        ("rowIndices", pa.list_(pa.int32())),
        ("values", pa.list_(pa.float64())),
        ("isTransposed", pa.bool_()),
    ]
)
_SPARK_VECTOR = pa.struct(
    [
        ("type", pa.int8()),
        ("size", pa.int32()),
        ("indices", pa.list_(pa.int32())),
        ("values", pa.list_(pa.float64())),
    ]
)


def _write_spark_metadata(path, class_name, uid, param_map, default_map=None):
    """DefaultParamsWriter.saveMetadata byte shape: single JSON line in
    metadata/part-00000 + empty _SUCCESS."""
    meta_dir = os.path.join(path, "metadata")
    os.makedirs(meta_dir)
    payload = {
        "class": class_name,
        "timestamp": 1714456800000,
        "sparkVersion": "3.5.1",
        "uid": uid,
        "paramMap": param_map,
        "defaultParamMap": default_map or {},
    }
    with open(os.path.join(meta_dir, "part-00000"), "w") as f:
        f.write(json.dumps(payload) + "\n")
    open(os.path.join(meta_dir, "_SUCCESS"), "w").close()


def _write_spark_parquet(path, schema, rows, spark_schema_json, parts=1):
    """Spark executor part-file shape: snappy parquet named
    part-0000N-<uuid>-c000.snappy.parquet with Spark's row-metadata keys.

    ``parts > 1`` splits ``rows`` round-robin across that many part
    files — the multi-task layout a genuine distributed write produces
    (a part may come out EMPTY, exactly like a Spark task that owned no
    rows)."""
    data_dir = os.path.join(path, "data")
    os.makedirs(data_dir)
    chunks = [rows[i::parts] for i in range(parts)]
    for n, chunk in enumerate(chunks):
        arrays = [
            pa.array([r[name] for r in chunk], type=schema.field(name).type)
            for name in schema.names
        ]
        table = pa.Table.from_arrays(arrays, schema=schema).replace_schema_metadata(
            {
                "org.apache.spark.version": "3.5.1",
                "org.apache.spark.sql.parquet.row.metadata": spark_schema_json,
            }
        )
        pq.write_table(
            table,
            os.path.join(
                data_dir,
                f"part-{n:05d}-2fc4f2c3-0d5e-4a52-9b3e-77a312345678"
                "-c000.snappy.parquet",
            ),
            compression="snappy",
        )
    open(os.path.join(data_dir, "_SUCCESS"), "w").close()


def _matrix_struct(m):
    m = np.asarray(m, dtype=np.float64)
    return {
        "type": 1,
        "numRows": m.shape[0],
        "numCols": m.shape[1],
        "colPtrs": None,
        "rowIndices": None,
        "values": m.ravel(order="F").tolist(),
        "isTransposed": False,
    }


def _vector_struct(v):
    return {
        "type": 1,
        "size": len(v),
        "indices": None,
        "values": np.asarray(v, dtype=np.float64).tolist(),
    }


class TestLoadSparkWrittenModels:
    def test_pca_model(self, tmp_path, rng):
        pc = rng.normal(size=(5, 2))
        ev = np.array([0.7, 0.2])
        path = str(tmp_path / "spark_pca")
        os.makedirs(path)
        _write_spark_metadata(
            path,
            "org.apache.spark.ml.feature.PCAModel",
            "PCAModel_4b1c2d3e4f50",
            {"k": 2, "inputCol": "features", "outputCol": "pca"},
        )
        schema = pa.schema([("pc", _SPARK_MATRIX), ("explainedVariance", _SPARK_VECTOR)])
        _write_spark_parquet(
            path,
            schema,
            [{"pc": _matrix_struct(pc), "explainedVariance": _vector_struct(ev)}],
            '{"type":"struct","fields":[{"name":"pc","type":{"type":"udt",'
            '"class":"org.apache.spark.ml.linalg.MatrixUDT"},"nullable":true,'
            '"metadata":{}},{"name":"explainedVariance","type":{"type":"udt",'
            '"class":"org.apache.spark.ml.linalg.VectorUDT"},"nullable":true,'
            '"metadata":{}}]}',
        )

        model = PCAModel.load(path)
        np.testing.assert_allclose(model.pc, pc)
        np.testing.assert_allclose(model.explainedVariance, ev)
        assert model.getK() == 2
        assert model.getInputCol() == "features"
        # And it transforms.
        out = model.transform(rng.normal(size=(10, 5)))
        assert out.shape == (10, 2)

    def test_pca_model_is_transposed_layout(self, tmp_path, rng):
        """Spark may store matrices row-major (isTransposed=True)."""
        pc = rng.normal(size=(4, 2))
        path = str(tmp_path / "spark_pca_t")
        os.makedirs(path)
        _write_spark_metadata(
            path, "org.apache.spark.ml.feature.PCAModel", "PCAModel_x", {"k": 2}
        )
        struct = _matrix_struct(pc)
        struct["values"] = pc.ravel(order="C").tolist()
        struct["isTransposed"] = True
        schema = pa.schema([("pc", _SPARK_MATRIX), ("explainedVariance", _SPARK_VECTOR)])
        _write_spark_parquet(
            path,
            schema,
            [{"pc": struct, "explainedVariance": _vector_struct([0.9, 0.1])}],
            "{}",
        )
        model = PCAModel.load(path)
        np.testing.assert_allclose(model.pc, pc)

    def test_kmeans_model(self, tmp_path, rng):
        centers = rng.normal(size=(3, 4))
        path = str(tmp_path / "spark_kmeans")
        os.makedirs(path)
        _write_spark_metadata(
            path,
            "org.apache.spark.ml.clustering.KMeansModel",
            "KMeansModel_abc",
            {"k": 3, "featuresCol": "features", "predictionCol": "prediction"},
        )
        schema = pa.schema(
            [("clusterIdx", pa.int32()), ("clusterCenter", _SPARK_VECTOR)]
        )
        _write_spark_parquet(
            path,
            schema,
            [
                {"clusterIdx": i, "clusterCenter": _vector_struct(c)}
                for i, c in enumerate(centers)
            ],
            "{}",
        )
        model = KMeansModel.load(path)
        np.testing.assert_allclose(model.clusterCenters(), centers)

    def test_linear_regression_model(self, tmp_path, rng):
        coef = rng.normal(size=6)
        path = str(tmp_path / "spark_lr")
        os.makedirs(path)
        _write_spark_metadata(
            path,
            "org.apache.spark.ml.regression.LinearRegressionModel",
            "LinearRegressionModel_q",
            {"featuresCol": "features", "labelCol": "label"},
        )
        schema = pa.schema(
            [("intercept", pa.float64()), ("coefficients", _SPARK_VECTOR)]
        )
        _write_spark_parquet(
            path,
            schema,
            [{"intercept": 2.5, "coefficients": _vector_struct(coef)}],
            "{}",
        )
        model = LinearRegressionModel.load(path)
        np.testing.assert_allclose(model.coefficients, coef)
        assert model.intercept == pytest.approx(2.5)

    def test_sparse_vector_struct(self, tmp_path):
        """Spark VectorUDT type=0 is sparse; loaders must densify it."""
        path = str(tmp_path / "spark_lr_sparse")
        os.makedirs(path)
        _write_spark_metadata(
            path,
            "org.apache.spark.ml.regression.LinearRegressionModel",
            "LinearRegressionModel_s",
            {},
        )
        schema = pa.schema(
            [("intercept", pa.float64()), ("coefficients", _SPARK_VECTOR)]
        )
        sparse = {"type": 0, "size": 5, "indices": [1, 3], "values": [2.0, -1.0]}
        _write_spark_parquet(
            path, schema, [{"intercept": 0.0, "coefficients": sparse}], "{}"
        )
        model = LinearRegressionModel.load(path)
        np.testing.assert_allclose(model.coefficients, [0.0, 2.0, 0.0, -1.0, 0.0])


def _node(nid, pred, imp, stats, raw, gain=-1.0, left=-1, right=-1,
          feat=-1, thr=None):
    """Spark NodeData dict (leaf by default; pass children for a split)."""
    return {
        "id": nid,
        "prediction": float(pred),
        "impurity": float(imp),
        "impurityStats": [float(s) for s in stats],
        "rawCount": int(raw),
        "gain": float(gain),
        "leftChild": left,
        "rightChild": right,
        "split": {
            "featureIndex": feat,
            "leftCategoriesOrThreshold": [] if thr is None else [float(thr)],
            "numCategories": -1,
        },
    }


def _nodedata_schema():
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
    return pa.schema([("treeID", pa.int32()), ("nodeData", node_t)])


class TestLoadSparkWrittenForests:
    """Spark's EnsembleModelReadWrite on-disk shape (treeID + NodeData
    struct rows, preorder ids, explicit child pointers, leaf sentinels)
    must load into the heap-array Forest and predict correctly
    (the RF families joined the golden suite in r5)."""

    def test_rf_classifier_golden(self, tmp_path, rng):
        path = str(tmp_path / "spark_rfc")
        os.makedirs(path)
        _write_spark_metadata(
            path,
            "org.apache.spark.ml.classification.RandomForestClassificationModel",
            "RandomForestClassificationModel_g",
            {"numTrees": 2, "featuresCol": "features"},
        )
        # Tree 0: split on feature 0 at 0.5 -> class-count leaves;
        # tree 1: a single root leaf (50/50).
        rows = [
            (0, _node(0, 1.0, 0.495, [9, 11], 20, gain=0.3, left=1, right=2,
                      feat=0, thr=0.5)),
            (0, _node(1, 0.0, 0.32, [8, 2], 10)),
            (0, _node(2, 1.0, 0.18, [1, 9], 10)),
            (1, _node(0, 0.0, 0.5, [5, 5], 10)),
        ]
        schema = _nodedata_schema()
        _write_spark_parquet(
            path,
            schema,
            [{"treeID": t, "nodeData": nd} for t, nd in rows],
            "{}",
        )
        model = RandomForestClassificationModel.load(path)
        probs = model.predictProbability(
            np.array([[0.0, 0.0], [1.0, 0.0]], dtype=np.float64)
        )
        # Mean of tree leaf distributions: ((.8,.2)+(.5,.5))/2, ((.1,.9)+(.5,.5))/2
        np.testing.assert_allclose(probs, [[0.65, 0.35], [0.3, 0.7]], atol=1e-6)
        preds = np.asarray(
            model.predict(np.array([[0.0, 0.0], [1.0, 0.0]], dtype=np.float64))
        )
        np.testing.assert_array_equal(preds, [0, 1])
        assert model.totalNumNodes == 4

    def test_rf_regressor_golden(self, tmp_path):
        path = str(tmp_path / "spark_rfr")
        os.makedirs(path)
        _write_spark_metadata(
            path,
            "org.apache.spark.ml.regression.RandomForestRegressionModel",
            "RandomForestRegressionModel_g",
            {"numTrees": 1},
        )
        # Variance stats [count, sum, sumSq]; prediction = mean.
        rows = [
            (0, _node(0, 0.8, 2.1, [10, 8, 30.0], 10, gain=1.5, left=1,
                      right=2, feat=1, thr=0.0)),
            (0, _node(1, -1.0, 0.1, [4, -4.0, 4.4], 4)),
            (0, _node(2, 2.0, 0.1, [6, 12.0, 24.6], 6)),
        ]
        _write_spark_parquet(
            path,
            _nodedata_schema(),
            [{"treeID": t, "nodeData": nd} for t, nd in rows],
            "{}",
        )
        model = RandomForestRegressionModel.load(path)
        pred = model.predict(np.array([[0.0, -1.0], [0.0, 1.0]], dtype=np.float64))
        np.testing.assert_allclose(pred, [-1.0, 2.0], atol=1e-6)

    def test_rf_classifier_multipart_golden(self, tmp_path):
        """A genuine Spark-written model dir has one part file PER WRITE
        TASK; NodeData split across two parts (tree 1 entirely in
        part-00001) must load every tree — the pre-r6 reader took only
        ``parquets[0]`` and silently dropped the rest of the forest
        (ROADMAP 5a)."""
        rows = [
            (0, _node(0, 1.0, 0.495, [9, 11], 20, gain=0.3, left=1, right=2,
                      feat=0, thr=0.5)),
            (0, _node(1, 0.0, 0.32, [8, 2], 10)),
            (0, _node(2, 1.0, 0.18, [1, 9], 10)),
            (1, _node(0, 0.0, 0.5, [5, 5], 10)),
        ]
        expected = {}
        for parts in (1, 2):
            path = str(tmp_path / f"spark_rfc_p{parts}")
            os.makedirs(path)
            _write_spark_metadata(
                path,
                "org.apache.spark.ml.classification."
                "RandomForestClassificationModel",
                "RandomForestClassificationModel_mp",
                {"numTrees": 2, "featuresCol": "features"},
            )
            # Round-robin with parts=2 puts tree 0's nodes in part-00000
            # and tree 1's single root in part-00001.
            ordered = [rows[0], rows[3], rows[1], rows[2]]
            _write_spark_parquet(
                path,
                _nodedata_schema(),
                [{"treeID": t, "nodeData": nd} for t, nd in ordered],
                "{}",
                parts=parts,
            )
            model = RandomForestClassificationModel.load(path)
            assert model.totalNumNodes == 4, f"parts={parts} lost nodes"
            expected[parts] = np.asarray(
                model.predictProbability(
                    np.array([[0.0, 0.0], [1.0, 0.0]], dtype=np.float64)
                )
            )
        # The split layout decodes to the identical forest.
        np.testing.assert_allclose(expected[2], expected[1])
        np.testing.assert_allclose(expected[2], [[0.65, 0.35], [0.3, 0.7]],
                                   atol=1e-6)

    def test_single_row_model_with_empty_leading_part(self, tmp_path):
        """Spark tasks that owned no rows still write a part file; the
        model row may therefore live in part-00001 behind an EMPTY
        part-00000. load_data must read past the empty part."""
        path = str(tmp_path / "spark_lr_empty_part")
        os.makedirs(path)
        _write_spark_metadata(
            path,
            "org.apache.spark.ml.regression.LinearRegressionModel",
            "LinearRegressionModel_ep",
            {},
        )
        schema = pa.schema(
            [("intercept", pa.float64()), ("coefficients", _SPARK_VECTOR)]
        )
        row = {"intercept": 1.5, "coefficients": _vector_struct([2.0, -1.0])}
        _write_spark_parquet(path, schema, [], "{}")  # empty part-00000
        data_dir = os.path.join(path, "data")
        arrays = [
            pa.array([row[name]], type=schema.field(name).type)
            for name in schema.names
        ]
        pq.write_table(
            pa.Table.from_arrays(arrays, schema=schema),
            os.path.join(data_dir, "part-00001-aaaa-c000.snappy.parquet"),
            compression="snappy",
        )
        model = LinearRegressionModel.load(path)
        assert model.intercept == 1.5
        np.testing.assert_allclose(model.coefficients, [2.0, -1.0])

    def test_legacy_flattened_forest_layout_loads(self, tmp_path):
        """Pre-r5 model directories (the flattened treeID/nodeID scalar
        columns) must still load (code-review r5: the Spark-schema
        rewrite must not strand existing checkpoints)."""
        from spark_rapids_ml_tpu.core.persistence import save_metadata, save_rows

        path = str(tmp_path / "legacy_rf")
        shell = RandomForestClassificationModel()
        save_metadata(
            shell,
            path,
            class_name=(
                "org.apache.spark.ml.classification."
                "RandomForestClassificationModel"
            ),
            extra_metadata={"numFeatures": 1, "numClasses": 2},
        )
        # One depth-1 tree: root splits feature 0 at 0.5.
        save_rows(
            path,
            {
                "treeID": ("scalar", [0, 0, 0]),
                "nodeID": ("scalar", [0, 1, 2]),
                "feature": ("scalar", [0, -1, -1]),
                "threshold": ("scalar", [0.5, 0.0, 0.0]),
                "isLeaf": ("scalar", [False, True, True]),
                "leafValue": ("vector", [[0.5, 0.5], [0.8, 0.2], [0.1, 0.9]]),
                "nodeWeight": ("scalar", [20.0, 10.0, 10.0]),
                "nodeGain": ("scalar", [0.3, 0.0, 0.0]),
            },
        )
        model = RandomForestClassificationModel.load(path)
        probs = model.predictProbability(np.array([[0.0], [1.0]]))
        np.testing.assert_allclose(probs, [[0.8, 0.2], [0.1, 0.9]], atol=1e-6)

    def test_logistic_regression_golden(self, tmp_path, rng):
        coef = rng.normal(size=4)
        path = str(tmp_path / "spark_logreg")
        os.makedirs(path)
        _write_spark_metadata(
            path,
            "org.apache.spark.ml.classification.LogisticRegressionModel",
            "LogisticRegressionModel_g",
            {"featuresCol": "features", "threshold": 0.5},
        )
        schema = pa.schema(
            [
                ("numClasses", pa.int32()),
                ("numFeatures", pa.int32()),
                ("interceptVector", _SPARK_VECTOR),
                ("coefficientMatrix", _SPARK_MATRIX),
                ("isMultinomial", pa.bool_()),
            ]
        )
        _write_spark_parquet(
            path,
            schema,
            [
                {
                    "numClasses": 2,
                    "numFeatures": 4,
                    "interceptVector": _vector_struct([0.25]),
                    "coefficientMatrix": _matrix_struct(coef[None, :]),
                    "isMultinomial": False,
                }
            ],
            "{}",
        )
        model = LogisticRegressionModel.load(path)
        np.testing.assert_allclose(model.coefficients, coef)
        assert model.intercept == pytest.approx(0.25)
        x = rng.normal(size=(5, 4))
        expect = 1.0 / (1.0 + np.exp(-(x @ coef + 0.25)))
        np.testing.assert_allclose(
            model.predictProbability(x)[:, 1], expect, atol=1e-6
        )

    def test_logistic_regression_multinomial_golden(self, tmp_path, rng):
        cm = rng.normal(size=(3, 4))  # (numClasses, d), Spark orientation
        iv = rng.normal(size=3)
        path = str(tmp_path / "spark_logreg_mn")
        os.makedirs(path)
        _write_spark_metadata(
            path,
            "org.apache.spark.ml.classification.LogisticRegressionModel",
            "LogisticRegressionModel_mn",
            {},
        )
        schema = pa.schema(
            [
                ("numClasses", pa.int32()),
                ("numFeatures", pa.int32()),
                ("interceptVector", _SPARK_VECTOR),
                ("coefficientMatrix", _SPARK_MATRIX),
                ("isMultinomial", pa.bool_()),
            ]
        )
        _write_spark_parquet(
            path,
            schema,
            [
                {
                    "numClasses": 3,
                    "numFeatures": 4,
                    "interceptVector": _vector_struct(iv),
                    "coefficientMatrix": _matrix_struct(cm),
                    "isMultinomial": True,
                }
            ],
            "{}",
        )
        model = LogisticRegressionModel.load(path)
        np.testing.assert_allclose(model.coefficientMatrix, cm)
        np.testing.assert_allclose(model.interceptVector, iv)
        x = rng.normal(size=(6, 4))
        z = x @ cm.T + iv
        expect = np.exp(z) / np.exp(z).sum(axis=1, keepdims=True)
        np.testing.assert_allclose(model.predictProbability(x), expect, atol=1e-6)


class TestCompositeGoldenLayouts:
    """Upstream Spark's COMPOSITE writers (Pipeline.SharedReadWrite,
    CrossValidatorModel) record no python class paths: ``stageUids``
    lives inside ``paramMap``, stage type information exists only as
    each nested directory's own JVM metadata class, and the winning
    model sits bare under ``bestModel/``. Directories byte-constructed
    in that exact shape must load here (ROADMAP item 5c)."""

    def _golden_pca_stage(self, path, pc, ev, uid="PCAModel_stage0"):
        os.makedirs(path)
        _write_spark_metadata(
            path, "org.apache.spark.ml.feature.PCAModel", uid, {"k": pc.shape[1]}
        )
        schema = pa.schema(
            [("pc", _SPARK_MATRIX), ("explainedVariance", _SPARK_VECTOR)]
        )
        _write_spark_parquet(
            path,
            schema,
            [{"pc": _matrix_struct(pc), "explainedVariance": _vector_struct(ev)}],
            "{}",
        )

    def _golden_linreg_stage(self, path, coef, intercept, uid="LinearRegressionModel_stage1"):
        os.makedirs(path)
        _write_spark_metadata(
            path,
            "org.apache.spark.ml.regression.LinearRegressionModel",
            uid,
            {},
        )
        schema = pa.schema(
            [("intercept", pa.float64()), ("coefficients", _SPARK_VECTOR)]
        )
        _write_spark_parquet(
            path,
            schema,
            [{"intercept": float(intercept), "coefficients": _vector_struct(coef)}],
            "{}",
        )

    def test_pipeline_model_golden(self, tmp_path, rng):
        """A Spark-written PipelineModel dir — paramMap.stageUids, no
        stageClasses, JVM class names in the stage metadata — loads and
        transforms end to end."""
        from spark_rapids_ml_tpu.pipeline import PipelineModel

        pc = rng.normal(size=(5, 2))
        ev = np.array([0.7, 0.2])
        coef = rng.normal(size=2)
        path = str(tmp_path / "spark_pipeline")
        os.makedirs(path)
        uids = ["PCAModel_stage0", "LinearRegressionModel_stage1"]
        # Spark's SharedReadWrite: stageUids INSIDE paramMap, nothing else.
        _write_spark_metadata(
            path,
            "org.apache.spark.ml.PipelineModel",
            "PipelineModel_golden",
            {"stageUids": uids},
        )
        self._golden_pca_stage(
            os.path.join(path, "stages", f"0_{uids[0]}"), pc, ev, uid=uids[0]
        )
        self._golden_linreg_stage(
            os.path.join(path, "stages", f"1_{uids[1]}"), coef, 1.5, uid=uids[1]
        )

        model = PipelineModel.load(path)
        assert len(model.stages) == 2
        x = rng.normal(size=(8, 5))
        out = np.asarray(model.transform(x))
        # PCA projection then the linear head, exactly as Spark composes.
        np.testing.assert_allclose(out, x @ pc @ coef + 1.5, atol=1e-6)

    def test_pipeline_model_roundtrip_ours(self, tmp_path, rng):
        """Our own writer's layout keeps loading too (stageClasses path),
        and the written metadata carries the stage bookkeeping Spark's
        reader keys on."""
        from spark_rapids_ml_tpu.pipeline import PipelineModel
        from spark_rapids_ml_tpu.regression import LinearRegression

        x = rng.normal(size=(60, 5))
        pca_model = PCA().setK(3).fit(x)
        y = np.asarray(pca_model.transform(x)) @ rng.normal(size=3) + 2.0
        lr_model = LinearRegression().fit((np.asarray(pca_model.transform(x)), y))
        model = PipelineModel(None, [pca_model, lr_model])
        path = str(tmp_path / "ours_pipeline")
        model.write.overwrite().save(path)
        with open(os.path.join(path, "metadata", "part-00000")) as f:
            meta = json.loads(f.readline())
        assert meta["stageUids"] == [s.uid for s in model.stages]
        assert len(meta["stageClasses"]) == 2

        loaded = PipelineModel.load(path)
        np.testing.assert_allclose(
            np.asarray(loaded.transform(x)), np.asarray(model.transform(x)),
            atol=1e-6,
        )

    def test_cross_validator_model_golden(self, tmp_path, rng):
        """A Spark-written CrossValidatorModel dir — avgMetrics in the
        metadata, the winner bare under bestModel/ with only its JVM
        class — loads with metrics intact and a servable bestModel."""
        from spark_rapids_ml_tpu.tuning import CrossValidatorModel

        coef = rng.normal(size=4)
        path = str(tmp_path / "spark_cv")
        os.makedirs(path)
        _write_spark_metadata(
            path,
            "org.apache.spark.ml.tuning.CrossValidatorModel",
            "CrossValidatorModel_golden",
            {"numFolds": 3},
        )
        # avgMetrics land top-level (Spark's extraMetadata), not in paramMap.
        meta_file = os.path.join(path, "metadata", "part-00000")
        with open(meta_file) as f:
            meta = json.loads(f.readline())
        meta["avgMetrics"] = [0.81, 0.93, 0.77]
        meta["bestIndex"] = 1
        with open(meta_file, "w") as f:
            f.write(json.dumps(meta) + "\n")

        best = os.path.join(path, "bestModel")
        os.makedirs(best)
        _write_spark_metadata(
            best,
            "org.apache.spark.ml.classification.LogisticRegressionModel",
            "LogisticRegressionModel_best",
            {"threshold": 0.5},
        )
        schema = pa.schema(
            [
                ("numClasses", pa.int32()),
                ("numFeatures", pa.int32()),
                ("interceptVector", _SPARK_VECTOR),
                ("coefficientMatrix", _SPARK_MATRIX),
                ("isMultinomial", pa.bool_()),
            ]
        )
        _write_spark_parquet(
            best,
            schema,
            [
                {
                    "numClasses": 2,
                    "numFeatures": 4,
                    "interceptVector": _vector_struct([0.25]),
                    "coefficientMatrix": _matrix_struct(coef[None, :]),
                    "isMultinomial": False,
                }
            ],
            "{}",
        )

        model = CrossValidatorModel.load(path)
        assert model.avgMetrics == [0.81, 0.93, 0.77]
        assert model.bestIndex == 1
        np.testing.assert_allclose(model.bestModel.coefficients, coef)
        x = rng.normal(size=(6, 4))
        expect = 1.0 / (1.0 + np.exp(-(x @ coef + 0.25)))
        np.testing.assert_allclose(
            model.bestModel.predictProbability(x)[:, 1], expect, atol=1e-6
        )

    def test_cross_validator_model_roundtrip_ours(self, tmp_path, rng):
        """write -> load through our own layout: metrics, bestIndex, and
        bit-equal bestModel predictions survive."""
        from spark_rapids_ml_tpu.classification import LogisticRegression
        from spark_rapids_ml_tpu.tuning import CrossValidatorModel

        x = rng.normal(size=(80, 3))
        y = (x[:, 0] > 0).astype(float)
        best = LogisticRegression().setMaxIter(40).fit((x, y))
        model = CrossValidatorModel(
            None, best, avgMetrics=[0.5, 0.9], bestIndex=1
        )
        path = str(tmp_path / "ours_cv")
        model.write.overwrite().save(path)
        loaded = CrossValidatorModel.load(path)
        assert loaded.avgMetrics == [0.5, 0.9]
        assert loaded.bestIndex == 1
        np.testing.assert_allclose(
            loaded.bestModel.predictProbability(x),
            best.predictProbability(x),
            atol=1e-8,
        )


class TestWrittenFormatIsSparkShaped:
    """The reverse direction: what this framework writes must be exactly
    the structural schema Spark's readers parse."""

    def test_pca_written_schema(self, tmp_path, rng):
        x = rng.normal(size=(50, 4))
        model = PCA().setK(2).fit(x)
        path = str(tmp_path / "ours")
        model.write.overwrite().save(path)

        # metadata: single-line JSON with DefaultParamsReader's keys.
        with open(os.path.join(path, "metadata", "part-00000")) as f:
            lines = f.read().splitlines()
        assert len(lines) == 1
        meta = json.loads(lines[0])
        for key in ("class", "timestamp", "sparkVersion", "uid", "paramMap", "defaultParamMap"):
            assert key in meta, key
        assert meta["class"].endswith("PCAModel")
        assert os.path.exists(os.path.join(path, "metadata", "_SUCCESS"))

        # data: parquet whose struct fields match MatrixUDT/VectorUDT
        # name-for-name, type-for-type.
        files = [
            f
            for f in os.listdir(os.path.join(path, "data"))
            if f.endswith(".parquet")
        ]
        assert files
        table = pq.read_table(os.path.join(path, "data", files[0]))
        assert table.num_rows == 1
        assert table.schema.field("pc").type == _SPARK_MATRIX
        assert table.schema.field("explainedVariance").type == _SPARK_VECTOR
        assert os.path.exists(os.path.join(path, "data", "_SUCCESS"))

    def test_rf_written_schema_and_roundtrip(self, tmp_path, rng):
        """Forests persist in Spark's EnsembleModelReadWrite shape:
        (treeID, nodeData struct) rows + treesMetadata, and round-trip to
        identical predictions."""
        x = rng.normal(size=(150, 5))
        y = ((x[:, 0] + x[:, 2]) > 0).astype(float)
        model = (
            RandomForestClassifier().setNumTrees(4).setMaxDepth(3).setSeed(1)
            .fit((x, y))
        )
        path = str(tmp_path / "ours_rfc")
        model.write.overwrite().save(path)

        files = [
            f for f in os.listdir(os.path.join(path, "data"))
            if f.endswith(".parquet")
        ]
        table = pq.read_table(os.path.join(path, "data", files[0]))
        assert table.schema.equals(_nodedata_schema()), table.schema
        # Leaf sentinels and preorder roots, as Spark writes them.
        first = table.to_pylist()[0]
        assert first["nodeData"]["id"] == 0
        leaves = [
            r["nodeData"] for r in table.to_pylist()
            if r["nodeData"]["leftChild"] < 0
        ]
        assert leaves and all(nd["gain"] == -1.0 for nd in leaves)
        assert all(nd["split"]["featureIndex"] == -1 for nd in leaves)
        # treesMetadata: one row per tree with uniform weights.
        tm_files = [
            f for f in os.listdir(os.path.join(path, "treesMetadata"))
            if f.endswith(".parquet")
        ]
        tm = pq.read_table(os.path.join(path, "treesMetadata", tm_files[0]))
        assert tm.column_names == ["treeID", "metadata", "weights"]
        assert tm.num_rows == 4

        loaded = RandomForestClassificationModel.load(path)
        np.testing.assert_allclose(
            loaded.predictProbability(x), model.predictProbability(x), atol=1e-6
        )
        np.testing.assert_allclose(
            loaded.featureImportances, model.featureImportances, atol=1e-6
        )

    def test_rf_regressor_roundtrip_exact(self, tmp_path, rng):
        """Regression round trip: the variance-triplet encoding must be
        lossless (sumSq reconstructed from the stored node impurity)."""
        x = rng.normal(size=(120, 4))
        y = 2.0 * x[:, 0] - x[:, 3] + 0.1 * rng.normal(size=120) + 5.0
        model = (
            RandomForestRegressor().setNumTrees(3).setMaxDepth(3).setSeed(2)
            .fit((x, y))
        )
        path = str(tmp_path / "ours_rfr")
        model.write.overwrite().save(path)
        loaded = RandomForestRegressionModel.load(path)
        np.testing.assert_allclose(loaded.predict(x), model.predict(x), atol=1e-5)
        f0, f1 = model._forest, loaded._forest
        np.testing.assert_allclose(
            np.asarray(f1.node_impurity), np.asarray(f0.node_impurity), atol=1e-4
        )
        np.testing.assert_allclose(
            np.asarray(f1.node_weight), np.asarray(f0.node_weight), atol=1e-5
        )

    def test_logreg_written_schema(self, tmp_path, rng):
        x = rng.normal(size=(100, 3))
        y = (x[:, 0] > 0).astype(float)
        from spark_rapids_ml_tpu.classification import LogisticRegression

        model = LogisticRegression().setMaxIter(30).fit((x, y))
        path = str(tmp_path / "ours_lr")
        model.write.overwrite().save(path)
        files = [
            f for f in os.listdir(os.path.join(path, "data"))
            if f.endswith(".parquet")
        ]
        table = pq.read_table(os.path.join(path, "data", files[0]))
        assert table.schema.field("coefficientMatrix").type == _SPARK_MATRIX
        assert table.schema.field("interceptVector").type == _SPARK_VECTOR
        row = table.to_pylist()[0]
        assert row["numClasses"] == 2
        assert row["numFeatures"] == 3
        assert row["isMultinomial"] is False
        loaded = LogisticRegressionModel.load(path)
        np.testing.assert_allclose(
            loaded.predictProbability(x), model.predictProbability(x), atol=1e-8
        )

    def test_roundtrip_through_spark_shape(self, tmp_path, rng):
        """Write with our writer, re-read the raw structs as a Spark reader
        would (column-major values + struct fields), and compare."""
        x = rng.normal(size=(60, 5)) * np.linspace(1, 2, 5)
        model = PCA().setK(3).fit(x)
        path = str(tmp_path / "ours_rt")
        model.write.overwrite().save(path)
        files = [
            f
            for f in os.listdir(os.path.join(path, "data"))
            if f.endswith(".parquet")
        ]
        row = pq.read_table(os.path.join(path, "data", files[0])).to_pylist()[0]
        pc_struct = row["pc"]
        pc = np.asarray(pc_struct["values"]).reshape(
            pc_struct["numCols"], pc_struct["numRows"]
        ).T  # column-major, as Spark's DenseMatrix stores
        np.testing.assert_allclose(pc, model.pc)

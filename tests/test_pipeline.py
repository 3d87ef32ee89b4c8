"""Pipeline tests — sequential composition of this package's estimators."""

import numpy as np
import pytest

from spark_rapids_ml_tpu.core.data import DataFrame
from spark_rapids_ml_tpu.clustering import KMeans
from spark_rapids_ml_tpu.feature import PCA
from spark_rapids_ml_tpu.pipeline import Pipeline, PipelineModel


def _clustered_data(rng, n_per=40, d=8):
    centers = np.zeros((3, d))
    centers[0, 0] = 10
    centers[1, 1] = 10
    centers[2, 2] = 10
    x = np.concatenate([rng.normal(size=(n_per, d)) + c for c in centers])
    return x, np.repeat(np.arange(3), n_per)


class TestPipeline:
    def test_pca_then_kmeans(self, rng):
        x, labels = _clustered_data(rng)
        df = DataFrame({"features": list(x)})
        pipe = Pipeline(
            stages=[
                PCA().setK(3).setInputCol("features").setOutputCol("pca"),
                KMeans().setK(3).setFeaturesCol("pca").setSeed(0),
            ]
        )
        model = pipe.fit(df)
        assert isinstance(model, PipelineModel)
        assert len(model.stages) == 2
        out = model.transform(df)
        assert "pca" in out.columns and "prediction" in out.columns
        preds = np.asarray(out.select("prediction"))
        # Clustering in PCA space must recover the 3 blobs (up to relabeling).
        for c in range(3):
            blok = preds[labels == c]
            assert np.mean(blok == np.bincount(blok).argmax()) > 0.95

    def test_transformer_stage_passthrough(self, rng):
        # A fitted model used directly as a pipeline stage (pure transformer).
        x, _ = _clustered_data(rng, n_per=20)
        df = DataFrame({"features": list(x)})
        pca_model = PCA().setK(2).setInputCol("features").setOutputCol("pca").fit(df)
        pipe = Pipeline(stages=[pca_model, KMeans().setK(3).setFeaturesCol("pca")])
        model = pipe.fit(df)
        out = model.transform(df)
        assert "prediction" in out.columns

    def test_bad_stage_type(self):
        with pytest.raises(TypeError):
            Pipeline(stages=["not a stage"]).fit(None)

    def test_unfitted_pipeline_roundtrip(self, tmp_path):
        pipe = Pipeline(
            stages=[
                PCA().setK(2).setInputCol("features").setOutputCol("pca"),
                KMeans().setK(3).setFeaturesCol("pca").setSeed(1),
            ]
        )
        path = str(tmp_path / "pipe_unfitted")
        pipe.save(path)
        loaded = Pipeline.load(path)
        assert len(loaded.stages) == 2
        assert loaded.stages[0].getK() == 2
        assert loaded.stages[1].getK() == 3
        assert loaded.stages[1].getFeaturesCol() == "pca"

    def test_persistence_roundtrip(self, tmp_path, rng):
        x, _ = _clustered_data(rng, n_per=20)
        df = DataFrame({"features": list(x)})
        model = Pipeline(
            stages=[
                PCA().setK(2).setInputCol("features").setOutputCol("pca"),
                KMeans().setK(3).setFeaturesCol("pca").setSeed(1),
            ]
        ).fit(df)
        path = str(tmp_path / "pipe")
        model.save(path)
        loaded = PipelineModel.load(path)
        assert len(loaded.stages) == 2
        out_a = model.transform(df)
        out_b = loaded.transform(df)
        np.testing.assert_array_equal(
            np.asarray(out_a.select("prediction")),
            np.asarray(out_b.select("prediction")),
        )

    def test_load_rejects_foreign_class(self, tmp_path):
        """Metadata naming a class outside this package must not be imported
        (untrusted model dirs as import gadgets)."""
        import json

        pipe = Pipeline(stages=[PCA().setK(2)])
        path = str(tmp_path / "pipe_evil")
        pipe.save(path)
        meta_file = tmp_path / "pipe_evil" / "metadata" / "part-00000"
        meta = json.loads(meta_file.read_text())
        meta["stageClasses"] = ["os.system"]
        meta_file.write_text(json.dumps(meta) + "\n")
        with pytest.raises(ValueError, match="refusing to import"):
            Pipeline.load(path)
        # A path inside the package that resolves to a re-exported foreign
        # attribute (e.g. a numpy module alias) must be rejected too.
        meta["stageClasses"] = ["spark_rapids_ml_tpu.tuning.np"]
        meta_file.write_text(json.dumps(meta) + "\n")
        with pytest.raises(ValueError, match="refusing to load"):
            Pipeline.load(path)

    def test_allow_persisted_package_escape_hatch(self):
        """Extension libraries register their root package to make their
        custom stages loadable (the restriction is a default, not a wall)."""
        from spark_rapids_ml_tpu.core.persistence import (
            _LOADABLE_PACKAGES,
            allow_persisted_package,
            resolve_persisted_class,
        )

        with pytest.raises(ValueError, match="refusing to import"):
            resolve_persisted_class("collections.OrderedDict")
        allow_persisted_package("collections")
        try:
            import collections

            assert resolve_persisted_class("collections.OrderedDict") is collections.OrderedDict
        finally:
            _LOADABLE_PACKAGES.discard("collections")
        with pytest.raises(ValueError, match="bare top-level"):
            allow_persisted_package("a.b")

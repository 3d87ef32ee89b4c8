"""Stage spans (utils/tracing.py::StageRange): the leaves of the host fit
path — ``admit``, ``densify``, ``convert``, ``place``, ``solve`` — whose
``fit.stage.<stage>.ns`` / ``.calls`` / ``.bytes`` counters split a fit's
host time for the benchmark's ``host_*`` metrics.

What is held here: a stage is a TraceRange plus its two counters and
nothing else; stages of one thread never nest (conftest's
``stages_never_nest`` fixture fires on a planted nesting); a host-partition
PCA fit opens every stage, places its rows ONCE (means and Gram in one
pass, counter ``rowmatrix.cov.one_pass``), and its stages add up to no more
than its wall; inside a profiler session the stages are in the trace's host
plane; a device-array fit opens none of the host stages; every pass over
host partitions keeps a bounded number of placements in flight
(core/ingest.py::PlacementWindow: it waits for the oldest, inside ``place``,
counters ``ingest.place.waits`` / ``.wait_ns``) and fits the same model.
"""

import glob
import os
import threading
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_ml_tpu.core import ingest
from spark_rapids_ml_tpu.core.ingest import PlacementWindow, dense_partitions
from spark_rapids_ml_tpu.models.kmeans import KMeans
from spark_rapids_ml_tpu.models.pca import PCA
from spark_rapids_ml_tpu.utils import tracing
from spark_rapids_ml_tpu.utils.tracing import STAGES, StageRange, TraceRange


def stage_counters() -> dict:
    return tracing.counters("fit.stage.")


def delta(before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in stage_counters().items()}


def f32_partitions(seed: int, parts: int = 4, rows: int = 25, cols: int = 6) -> list:
    rng = np.random.default_rng(seed)
    return [rng.standard_normal((rows, cols)).astype(np.float32) for _ in range(parts)]


class TestStageRange:
    def test_adds_to_its_two_counters_and_nothing_else_new(self):
        before = tracing.counters()
        with StageRange("convert") as span:
            time.sleep(0.002)
        after = tracing.counters()
        changed = {k: after[k] - before.get(k, 0) for k in after if after[k] != before.get(k, 0)}
        assert set(changed) == {"fit.stage.convert.ns", "fit.stage.convert.calls"}
        assert changed["fit.stage.convert.calls"] == 1
        # the counter holds the span's own duration, not a second reading
        name, start, end = tracing.recent_events()[-1]
        assert name == "convert"
        assert changed["fit.stage.convert.ns"] == int((end - start) * 1e9) >= 2_000_000
        assert isinstance(span, TraceRange) and span.ok

    def test_a_stage_that_raises_is_still_counted(self):
        before = stage_counters()
        with pytest.raises(KeyError):
            with StageRange("place") as span:
                raise KeyError("x")
        assert delta(before)["fit.stage.place.calls"] == 1
        assert not span.ok and span.exc_type == "KeyError"

    def test_a_stage_is_a_node_of_the_run_tree(self):
        from spark_rapids_ml_tpu.observability import events
        from spark_rapids_ml_tpu.observability.report import build_stage_tree

        with events.run_scope("job", "stages") as ctx:
            with TraceRange("parent"):
                with StageRange("solve"):
                    pass
            tree = build_stage_tree(ctx.span_window(0))
        (parent,) = [n for n in tree if n["name"] == "parent"]
        assert [c["name"] for c in parent["children"]] == ["solve"]


class TestStagesNeverNest:
    def test_planted_nesting_fires(self):
        with pytest.raises(AssertionError, match="'place' opened inside stage 'solve'"):
            with StageRange("solve"):
                with StageRange("place"):
                    pass

    def test_unknown_stage_is_refused(self):
        with pytest.raises(ValueError, match="unknown stage 'gemm'"):
            StageRange("gemm")

    def test_a_plain_range_inside_a_stage_is_no_nesting(self):
        with StageRange("solve"):
            with TraceRange("retry:0"):
                pass
        with StageRange("solve"):  # and the stage before it was closed
            pass

    def test_stages_of_two_threads_may_be_open_at_once(self):
        inside, release, errors = threading.Event(), threading.Event(), []

        def other():
            try:
                with StageRange("place"):
                    inside.set()
                    release.wait(10)
            except BaseException as exc:  # noqa: BLE001 - handed to the asserting thread
                errors.append(exc)

        worker = threading.Thread(target=other)
        worker.start()
        assert inside.wait(10)
        with StageRange("solve"):
            pass
        release.set()
        worker.join(10)
        assert not worker.is_alive() and not errors


class TestHostPartitionFit:
    def test_pca_fit_opens_every_stage_and_places_its_rows_once(self):
        parts = f32_partitions(11)
        before = stage_counters()
        t0 = time.perf_counter()
        model = PCA().setK(2).fit(parts)
        np.asarray(model.pc)
        wall_ns = (time.perf_counter() - t0) * 1e9
        got = delta(before)
        for stage in STAGES:
            assert got[f"fit.stage.{stage}.calls"] > 0, stage
            assert got[f"fit.stage.{stage}.ns"] >= 0, stage
        # one conversion and one placement a partition, in the compute
        # dtype: the means are taken in the pass that makes the Gram
        assert got["fit.stage.convert.calls"] == got["fit.stage.place.calls"] == len(parts)
        rows, cols = sum(p.shape[0] for p in parts), parts[0].shape[1]
        itemsize = np.dtype(jnp.zeros(0).dtype).itemsize  # float64 under the tests' x64
        assert got["fit.stage.place.bytes"] == rows * cols * itemsize
        # RowMatrix densifies in the compute dtype, float64 under the tests'
        # x64: every float32 partition is written anew
        assert got["fit.stage.densify.calls"] == 1
        assert got["fit.stage.densify.bytes"] == rows * cols * 8
        assert sum(got[f"fit.stage.{stage}.ns"] for stage in STAGES) <= wall_ns

    def test_fit_report_shows_the_stages_under_their_parents(self):
        model = PCA().setK(2).fit(f32_partitions(12))
        tree = model.fit_report().stage_tree()

        def find(nodes, name):
            for node in nodes:
                if node["name"] == name:
                    return node
                hit = find(node["children"], name)
                if hit is not None:
                    return hit
            return None

        cov = find(tree, "compute cov")
        assert {c["name"] for c in cov["children"]} == {"convert", "place", "solve"}
        assert find(tree, "mean center") is None  # no pass of its own for the means
        assert [c["name"] for c in find(tree, "auto eigh")["children"]] == ["solve"]
        assert find(tree, "admit") is not None and find(tree, "densify") is not None
        assert find(tree, "gemm") is None  # the span the three stages replaced

    def test_printed_report_folds_the_leaves_of_one_name(self):
        parts = f32_partitions(17)
        report = PCA().setK(2).fit(parts).fit_report()
        text = str(report)
        # the one pass: a conversion and a placement a partition, and a
        # dispatch a partition between the one that makes the accumulator
        # and the one that scales it
        assert f"convert x{len(parts)}" in text and f"place x{len(parts)}" in text
        assert f"solve x{len(parts) + 2}" in text
        assert text.count("convert") == 1  # one line for the pass, not one a partition
        assert report.stage_totals()["convert"]["calls"] == len(parts)

    def test_a_float64_partition_already_dense_is_not_written(self):
        rng = np.random.default_rng(13)
        f64, f32 = rng.standard_normal((8, 3)), rng.standard_normal((8, 3)).astype(np.float32)
        before = stage_counters()
        parts = dense_partitions([f64, f32])
        assert parts[0] is f64 and parts[1].dtype == np.float64
        assert delta(before)["fit.stage.densify.bytes"] == f32.size * 8

    def test_kmeans_host_fit_densifies_and_places_through_the_funnel(self):
        x = np.random.default_rng(14).standard_normal((60, 4)).astype(np.float32)
        before = stage_counters()
        KMeans().setK(3).setMaxIter(2).fit(x)
        got = delta(before)
        assert got["fit.stage.densify.calls"] >= 1
        itemsize = np.dtype(jnp.zeros(0).dtype).itemsize
        assert got["fit.stage.place.bytes"] >= x.size * itemsize
        assert got.get("fit.stage.convert.calls", 0) == 0  # the funnel densifies in the compute dtype


@pytest.fixture
def row_matrices(monkeypatch):
    """The RowMatrix of every ``PCA.fit`` of the test, in order."""
    from spark_rapids_ml_tpu.models import pca as pca_module

    made = []

    class Recorded(pca_module.RowMatrix):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            made.append(self)

    monkeypatch.setattr(pca_module, "RowMatrix", Recorded)
    return made


@pytest.mark.usefixtures("chip_dtypes")
class TestHostPartitionFitWithoutX64:
    def test_float32_partitions_are_handed_on_as_they_are(self, row_matrices):
        parts = f32_partitions(21)
        before = stage_counters()
        model = PCA().setK(2).fit(parts)
        np.asarray(model.pc)
        got = delta(before)
        (mat,) = row_matrices
        assert all(held is given for held, given in zip(mat.partitions, parts))
        assert got["fit.stage.densify.calls"] == 1
        assert got["fit.stage.densify.bytes"] == 0
        # the stages stay open round a conversion that has nothing to do
        assert got["fit.stage.convert.calls"] == got["fit.stage.place.calls"] == len(parts)
        assert got["fit.stage.place.bytes"] == sum(p.size for p in parts) * 4

    def test_a_float64_source_at_highest_is_narrowed_once(self, row_matrices):
        parts = [p.astype(np.float64) for p in f32_partitions(22)]
        before = stage_counters()
        PCA().setK(2).setPrecision("highest").fit(parts)
        got = delta(before)
        (mat,) = row_matrices
        assert {p.dtype for p in mat.partitions} == {np.dtype(np.float32)}
        entries = sum(p.size for p in parts)
        assert got["fit.stage.densify.bytes"] == entries * 4
        assert got["fit.stage.place.bytes"] == entries * 4

    @pytest.mark.parametrize(
        "route",
        [lambda pca: pca.setPrecision("dd"), lambda pca: pca.setUseGemm(False)],
        ids=["dd", "packed"],
    )
    def test_the_host_float64_routes_keep_float64_partitions(self, row_matrices, route):
        parts = f32_partitions(23)
        route(PCA().setK(2)).fit(parts)
        (mat,) = row_matrices
        assert {p.dtype for p in mat.partitions} == {np.dtype(np.float64)}
        assert all(np.array_equal(held, given) for held, given in zip(mat.partitions, parts))

    def test_float32_and_float64_partitions_of_one_value_fit_one_model(self):
        parts = f32_partitions(24)
        narrow = PCA().setK(3).fit(parts)
        wide = PCA().setK(3).setPrecision("highest").fit([p.astype(np.float64) for p in parts])
        assert np.array_equal(np.asarray(narrow.pc), np.asarray(wide.pc))
        assert np.array_equal(
            np.asarray(narrow.explainedVariance), np.asarray(wide.explainedVariance)
        )


class TestOnePassCounter:
    """``rowmatrix.cov.one_pass``: one per covariance that takes its means
    and its Gram from a single pass over host partitions, and none on the
    routes that never made two."""

    @staticmethod
    def moved_by(fit) -> int:
        before = tracing.counter_value("rowmatrix.cov.one_pass")
        model = fit()
        np.asarray(model.pc)
        return tracing.counter_value("rowmatrix.cov.one_pass") - before

    @pytest.mark.parametrize("backend", ["xla", "pallas"])
    def test_moves_by_one_a_host_partition_fit(self, backend):
        parts = f32_partitions(31)
        pca = PCA().setK(2).setCovarianceBackend(backend)
        assert self.moved_by(lambda: pca.fit(parts)) == 1
        assert self.moved_by(lambda: pca.fit(parts)) == 1  # every fit, not every compile

    @pytest.mark.parametrize(
        "fit",
        [
            lambda parts: PCA().setK(2).fit(jnp.asarray(np.concatenate(parts))),
            lambda parts: PCA().setK(2).setMeanCentering(False).fit(parts),
            lambda parts: PCA().setK(2).setPrecision("dd").fit(parts),
            lambda parts: PCA().setK(2).setUseGemm(False).fit(parts),
            lambda parts: PCA().setK(2).fit(iter(parts)),
        ],
        ids=["device_array", "uncentred", "dd", "packed", "streaming"],
    )
    def test_does_not_move_on_the_other_routes(self, fit):
        parts = f32_partitions(32)
        assert self.moved_by(lambda: fit(parts)) == 0


class Placed:
    """What a fake ``put`` returns: it knows whether it has been waited for."""

    def __init__(self, made: list):
        self.ready = False
        self.waited_with = None  # placements in flight when this one was waited for
        self._made = made
        made.append(self)

    def block_until_ready(self):
        self.waited_with = sum(not p.ready for p in self._made)
        self.ready = True
        return self


class TestPlacementWindow:
    """``core/ingest.py::PlacementWindow``: one a pass over host partitions;
    before a placement that would be one too many it waits for the OLDEST
    in flight, inside the ``place`` stage."""

    @staticmethod
    def room_for(monkeypatch, blocks: int, block_nbytes: int) -> None:
        monkeypatch.setattr(ingest, "PLACEMENT_WINDOW_BYTES", blocks * block_nbytes)

    @staticmethod
    def moved(fit) -> dict:
        names = ("ingest.place.waits", "ingest.place.wait_ns", "fit.stage.place.calls")
        before = {n: tracing.counter_value(n) for n in names}
        model = fit()
        if hasattr(model, "pc"):
            np.asarray(model.pc)
        return {n: tracing.counter_value(n) - before[n] for n in names}

    @pytest.mark.parametrize("parts", [2, 3, 7])
    @pytest.mark.parametrize(
        "route, passes",
        [
            (lambda pca: pca, 1),
            (lambda pca: pca.setMeanCentering(False), 1),
            (lambda pca: pca.setUseGemm(False), 2),
        ],
        ids=["centred", "uncentred", "packed_fallback"],
    )
    def test_a_pass_waits_once_a_partition_beyond_the_window(self, monkeypatch, route, passes, parts):
        from spark_rapids_ml_tpu import native

        monkeypatch.setattr(native, "available", lambda: False)  # the packed route's jitted fallback
        host = f32_partitions(41, parts=parts)
        self.room_for(monkeypatch, 3, host[0].size * 8)  # float64 on the device under the tests' x64
        got = self.moved(lambda: route(PCA().setK(2)).fit(host))
        assert got["ingest.place.waits"] == passes * max(0, parts - 3)
        assert got["fit.stage.place.calls"] == passes * parts  # the wait opens no stage of its own
        assert (got["ingest.place.wait_ns"] > 0) == (parts > 3)

    @pytest.mark.parametrize(
        "fit",
        [
            lambda x: PCA().setK(2).fit(jnp.asarray(x)),
            lambda x: KMeans().setK(3).setMaxIter(2).fit(x.astype(np.float32)),
        ],
        ids=["pca_device_rows", "kmeans_host_rows_through_prepare_rows"],
    )
    def test_a_fit_that_places_once_or_never_does_not_wait(self, monkeypatch, fit):
        monkeypatch.setattr(ingest, "PLACEMENT_WINDOW_BYTES", 1)  # by route, not by size
        x = np.random.default_rng(42).standard_normal((60, 4))
        got = self.moved(lambda: fit(x))
        assert got["ingest.place.waits"] == 0 and got["ingest.place.wait_ns"] == 0

    @pytest.mark.parametrize("room", [2, 3, 5])
    def test_never_more_than_the_window_in_flight_and_the_oldest_goes_first(self, monkeypatch, room):
        block = np.zeros((10, 3), np.float32)
        self.room_for(monkeypatch, room, block.nbytes)
        made, window = [], PlacementWindow()
        before = stage_counters()
        for i in range(9):
            placed = window.place(block, lambda b: Placed(made))
            assert placed is made[i]
            assert sum(not p.ready for p in made) <= room
            assert [p.ready for p in made] == [j <= i - room for j in range(i + 1)]
        # every wait found the window full, and was for one placement only
        assert [p.waited_with for p in made] == [room] * (9 - room) + [None] * room
        got = delta(before)
        assert got["fit.stage.place.calls"] == 9
        assert got["fit.stage.place.bytes"] == 9 * block.nbytes

    @pytest.mark.parametrize("nbytes", [1, 2, 1000])
    def test_a_block_larger_than_the_constant_still_overlaps_with_one_more(self, nbytes):
        big = SimpleNamespace(nbytes=nbytes * ingest.PLACEMENT_WINDOW_BYTES + 1)
        made, window = [], PlacementWindow()
        for i in range(5):
            window.place(big, lambda b: Placed(made))
            assert sum(not p.ready for p in made) == min(i + 1, 2)  # 2, never 1 or 0

    def test_an_empty_partition_is_placed_like_any_other(self):
        host = f32_partitions(44, parts=2) + [np.zeros((0, 6), np.float32)] + f32_partitions(45, parts=2)
        got = self.moved(lambda: PCA().setK(2).fit(host))
        assert got["fit.stage.place.calls"] == 5 and got["ingest.place.waits"] == 0

    def test_windows_of_two_passes_share_nothing(self, monkeypatch):
        block = np.zeros((10, 3), np.float32)
        self.room_for(monkeypatch, 2, block.nbytes)
        made = []
        for _ in range(2):
            window = PlacementWindow()
            for _ in range(2):
                window.place(block, lambda b: Placed(made))
        assert not any(p.ready for p in made)

    def test_the_windowed_fit_is_the_unbounded_loops_model_to_the_bit(self, monkeypatch):
        host = f32_partitions(43, parts=9, rows=40, cols=12)
        self.room_for(monkeypatch, 2, host[0].size * 8)
        before = tracing.counter_value("ingest.place.waits")
        windowed = PCA().setK(3).fit(host)
        assert tracing.counter_value("ingest.place.waits") - before == 7
        self.room_for(monkeypatch, 1_000, host[0].size * 8)
        unbounded = PCA().setK(3).fit(host)
        assert tracing.counter_value("ingest.place.waits") - before == 7
        assert np.array_equal(np.asarray(windowed.pc), np.asarray(unbounded.pc))
        assert np.array_equal(
            np.asarray(windowed.explainedVariance), np.asarray(unbounded.explainedVariance)
        )


class TestDeviceArrayFit:
    def test_opens_no_host_stage(self):
        x = jnp.asarray(np.random.default_rng(15).standard_normal((40, 5)))
        before = stage_counters()
        model = PCA().setK(2).fit(x)
        np.asarray(model.pc)
        got = delta(before)
        for stage in ("densify", "convert", "place"):
            assert got.get(f"fit.stage.{stage}.calls", 0) == 0, stage
            assert got.get(f"fit.stage.{stage}.bytes", 0) == 0, stage
        assert got["fit.stage.admit.calls"] == 1 and got["fit.stage.solve.calls"] == 1


class TestProfilerSession:
    def test_stages_are_in_the_host_plane_inside_the_enclosing_span(self, tmp_path):
        from jax.profiler import ProfileData

        parts = f32_partitions(16)
        PCA().setK(2).fit(parts)  # compile outside the session
        jax.profiler.start_trace(str(tmp_path))
        try:
            with TraceRange("enclosing fit"):
                model = PCA().setK(2).fit(parts)
                np.asarray(model.pc)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
        names = set(STAGES) | {"enclosing fit", "compute cov"}
        events = {}
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name in names:
                            events.setdefault(ev.name, []).append(
                                (ev.start_ns, ev.start_ns + ev.duration_ns)
                            )
        (fit,) = events["enclosing fit"]
        for stage in STAGES:
            assert events.get(stage), f"no {stage} event in the host plane"
            for start, end in events[stage]:
                assert fit[0] <= start <= end <= fit[1], stage
        assert len(events["convert"]) == len(events["place"]) == len(parts)
        # and under the reference's parent: every conversion and placement
        # lies inside the covariance span
        (cov,) = events["compute cov"]
        for stage in ("convert", "place"):
            assert all(cov[0] <= s and e <= cov[1] for s, e in events[stage])

"""Program cost ledger contracts (ISSUE 8).

The load-bearing promises, each pinned here:

  - every compile at a chokepoint == one ledger entry with XLA's cost
    AND memory analyses (or an explicit ``unavailable`` marker) —
    counter- and ``jax_log_compiles``-asserted;
  - with the ledger DISABLED (the default), the compile and serve paths
    add zero events, zero ledger state, and stay allocation-light;
  - the retrace watchdog classifies compiles and fires (structured
    warning + ``compile.retrace`` counter) on a seeded bucket bypass;
  - admission pricing switches from the declared-spec estimate to the
    program's measured temp+output bytes after its first compile;
  - ``tpuml_prof --diff`` gates a seeded flops regression non-zero;
  - gang shards merge: run counters sum, HBM watermarks max;
  - segmented fits under the ledger are BIT-IDENTICAL to the plain
    jitted path (the ledger observes, never perturbs).
"""

import json
import logging
import os
import tracemalloc
import warnings

import jax
import numpy as np
import pytest

from spark_rapids_ml_tpu.core import serving
from spark_rapids_ml_tpu.core.serving import clear_program_cache, serve_rows
from spark_rapids_ml_tpu.observability import costs, events
from spark_rapids_ml_tpu.observability.costs import (
    HbmSampler,
    RetraceStormWarning,
    attribute_hbm_growth,
    merge_ledger_docs,
    validate_ledger,
)
from spark_rapids_ml_tpu.utils.tracing import clear_counters, counter_value

from tools import tpuml_prof


def _kernel(x, w):
    return x @ w


def _kernel2(x, w):
    return x @ w + 1.0


@pytest.fixture
def ledger(monkeypatch):
    """An armed, empty ledger with clean chokepoint caches + counters."""
    monkeypatch.setenv("TPUML_COST_LEDGER", "1")
    clear_program_cache()
    clear_counters("compile.")
    clear_counters("serving.admission")
    costs.reset_for_tests()
    led = costs.active()
    assert led is not None
    yield led
    costs.configure(enable=False)
    clear_program_cache()


@pytest.fixture
def no_ledger(monkeypatch):
    monkeypatch.delenv("TPUML_COST_LEDGER", raising=False)
    clear_program_cache()
    clear_counters("compile.")
    costs.reset_for_tests()
    assert costs.active() is None
    yield
    clear_program_cache()


class TestLedgerCapture:
    def test_compiles_equal_ledger_entries(self, ledger, rng, caplog):
        """Three distinct buckets -> three compiles -> three AOT ledger
        entries, each carrying cost+memory analyses (or explicit
        markers); the warm repeat adds invocations but neither compiles
        (jax's own log asserts it) nor entries."""
        import jax.numpy as jnp

        w = jnp.asarray(rng.normal(size=(6, 3)).astype(np.float32))
        batches = [rng.normal(size=(n, 6)).astype(np.float32)
                   for n in (4, 30, 200)]
        for x in batches:
            serve_rows(_kernel, x, (w,), name="costs.kernel")
        doc = costs.ledger_snapshot()
        assert validate_ledger(doc) == []
        aot = [e for e in doc["entries"] if e["kind"] == "aot"]
        assert len(aot) == 3
        assert serving.program_cache_stats()["compiles"] == 3
        assert (
            counter_value("compile.new_program")
            + counter_value("compile.new_bucket")
            == 3
        )
        for e in aot:
            # CPU reports both analyses; the contract either way is
            # "values or an explicit marker", never silently absent.
            if "cost_analysis" not in e["unavailable"]:
                assert e["flops"] > 0 and e["bytes_accessed"] > 0
            if "memory_analysis" not in e["unavailable"]:
                assert e["output_bytes"] > 0
            assert e["compiles"] == 1 and e["invocations"] == 1

        jax.config.update("jax_log_compiles", True)
        try:
            with caplog.at_level(logging.WARNING, logger="jax._src.dispatch"):
                for x in batches:
                    serve_rows(_kernel, x, (w,), name="costs.kernel")
        finally:
            jax.config.update("jax_log_compiles", False)
        assert [
            r for r in caplog.records if "XLA compilation" in r.getMessage()
        ] == []
        doc2 = costs.ledger_snapshot()
        aot2 = [e for e in doc2["entries"] if e["kind"] == "aot"]
        assert len(aot2) == 3
        assert all(e["invocations"] == 2 for e in aot2)
        assert sum(e["rows_served"] for e in aot2) == 2 * (4 + 30 + 200)

    def test_segment_entries_and_bit_identity(self, rng, tmp_path, monkeypatch):
        """A segmented KMeans fit under the ledger records a `segment`
        entry — and produces BIT-IDENTICAL centers to the same fit with
        the ledger off (same XLA program, different bookkeeping)."""
        from spark_rapids_ml_tpu.clustering import KMeans

        x = rng.normal(size=(120, 8)).astype(np.float32)
        monkeypatch.setenv("TPUML_CHECKPOINT_EVERY", "3")
        monkeypatch.setenv("TPUML_CHECKPOINT_DIR", str(tmp_path / "ck"))

        monkeypatch.delenv("TPUML_COST_LEDGER", raising=False)
        costs.reset_for_tests()
        plain = KMeans().setK(3).setSeed(5).fit(x)
        assert costs.ledger_snapshot() is None

        monkeypatch.setenv("TPUML_COST_LEDGER", "1")
        monkeypatch.setenv("TPUML_CHECKPOINT_DIR", str(tmp_path / "ck2"))
        costs.reset_for_tests()
        ledgered = KMeans().setK(3).setSeed(5).fit(x)
        doc = costs.ledger_snapshot()
        segs = [e for e in doc["entries"] if e["kind"] == "segment"]
        assert len(segs) == 1
        assert segs[0]["family"] == "kmeans.lloyd.segment"
        assert segs[0]["invocations"] >= 1
        np.testing.assert_array_equal(
            np.asarray(plain.clusterCenters()),
            np.asarray(ledgered.clusterCenters()),
        )
        # The fit report renders the per-stage flops/bytes table.
        rep = ledgered.fit_report()
        fams = [r["family"] for r in rep.cost_table()]
        assert "kmeans.lloyd.segment" in fams
        assert "costs" in rep.summary()
        assert "where the FLOPs and bytes went" in str(rep)
        costs.configure(enable=False)

    def test_umap_segment_and_device_auc_are_ledgered(self, ledger, rng, tmp_path, monkeypatch):
        """The two families the retired cost-ledger scenario pinned and
        no test did: a checkpointed UMAP layout records a `segment`
        entry under `umap.layout.segment`, and the device AUC program
        (both metrics) records under `metrics.binary_auc`; the whole
        document validates."""
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.manifold import UMAP
        from spark_rapids_ml_tpu.ops.metrics import binary_auc_device

        monkeypatch.setenv("TPUML_CHECKPOINT_EVERY", "5")
        monkeypatch.setenv("TPUML_CHECKPOINT_DIR", str(tmp_path / "ck"))
        monkeypatch.setenv("TPUML_CHECKPOINT_UMAP", "1")
        xu = rng.normal(size=(128, 8)).astype(np.float32)
        model = UMAP().setNNeighbors(5).setNEpochs(10).setSeed(1).fit(xu)
        assert model.embedding.shape == (128, 2)
        ys = (rng.uniform(size=512) < 0.5).astype(np.float32)
        ss = (ys * 0.4 + rng.normal(size=512)).astype(np.float32)
        for metric in ("areaUnderROC", "areaUnderPR"):
            auc = float(binary_auc_device(jnp.asarray(ys), jnp.asarray(ss), metric=metric))
            assert 0.0 < auc < 1.0
        doc = costs.ledger_snapshot()
        assert validate_ledger(doc) == []
        by_family = {}
        for e in doc["entries"]:
            by_family.setdefault(e["family"], []).append(e)
        assert {e["kind"] for e in by_family["umap.layout.segment"]} == {"segment"}
        assert sum(e["invocations"] for e in by_family["umap.layout.segment"]) >= 2
        assert len(by_family["metrics.binary_auc"]) == 2

    def test_fallback_entry_for_sharded_weights(self, ledger, rng):
        """Mesh-sharded weights route through the plain-jit fallback,
        which is ledgered from the LOWERING: cost analysis present,
        memory explicitly unavailable (never compiled twice)."""
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec

        if len(jax.devices()) < 2:
            pytest.skip("needs the 8-device test mesh")
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("m",))
        w = jax.device_put(
            jnp.asarray(rng.normal(size=(6, 2)).astype(np.float32)),
            NamedSharding(mesh, PartitionSpec("m", None)),
        )
        out = serve_rows(
            _kernel, rng.normal(size=(5, 6)).astype(np.float32), (w,),
            name="costs.sharded",
        )
        assert np.shape(out) == (5, 2)
        doc = costs.ledger_snapshot()
        fb = [e for e in doc["entries"] if e["kind"] == "fallback"]
        assert len(fb) == 1
        assert "memory_analysis" in fb[0]["unavailable"]
        assert fb[0]["invocations"] == 1
        assert validate_ledger(doc) == []


class TestDisabledPath:
    def test_disabled_zero_events_entries_allocations(self, no_ledger, rng):
        """Ledger off: no ledger document, no compile-classification
        counters, no events, and the WARM serve path stays within a
        tight per-call allocation budget (a ledger row or exe-key dict
        per call would blow it)."""
        import jax.numpy as jnp

        w = jnp.asarray(rng.normal(size=(4, 2)).astype(np.float32))
        x = rng.normal(size=(5, 4)).astype(np.float32)
        serve_rows(_kernel, x, (w,), name="costs.disabled")  # warm the bucket
        before_events = events.emitted_count()

        n = 200
        tracemalloc.start()
        base, _ = tracemalloc.get_traced_memory()
        for _ in range(n):
            serve_rows(_kernel, x, (w,), name="costs.disabled")
        current, peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()

        assert costs.ledger_snapshot() is None
        assert counter_value("compile.new_program") == 0
        assert counter_value("compile.retrace") == 0
        assert events.emitted_count() == before_events
        # Warm host-path serve: pad scratch + device_put + slice — well
        # under 64 KiB/call; ledger bookkeeping leaking into the
        # disabled path would add per-call dict/list growth.
        assert peak - base < n * 65536


class TestRetraceWatchdog:
    def test_seeded_bucket_bypass_fires(self, ledger, rng):
        """Shapes INSIDE an existing bucket, forced through the AOT
        chokepoint: classified `retrace`, counted, and the storm warning
        fires at the TPUML_RETRACE_STORM'th strike."""
        import jax.numpy as jnp

        w = jnp.asarray(np.ones((4, 2), np.float32))

        def spec(rows):
            return jax.ShapeDtypeStruct((rows, 4), jnp.float32)

        serving._get_program(_kernel, spec(16), (w,), {}, donate=False,
                             name="costs.bypass")
        assert counter_value("compile.new_program") == 1
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            for rows in (12, 11, 10):
                serving._get_program(_kernel, spec(rows), (w,), {},
                                     donate=False, name="costs.bypass")
        assert counter_value("compile.retrace") == 3
        storms = [w_ for w_ in caught
                  if issubclass(w_.category, RetraceStormWarning)]
        assert len(storms) == 1
        assert "costs.bypass" in str(storms[0].message)
        doc = costs.ledger_snapshot()
        assert doc["retraces"]["total"] == 3
        assert doc["retraces"]["families"] == {"costs.bypass": 3}

    def test_new_bucket_is_not_a_retrace(self, ledger, rng):
        """Pow-2 buckets in ANY order are the contract working: a big
        batch first and a small one later compiles the small bucket —
        that is a new program, not a retrace (the misfire a real
        fit-then-serve sequence exposed: transform 5000 rows, then 7)."""
        import jax.numpy as jnp

        w = jnp.asarray(np.ones((4, 2), np.float32))
        for rows in (8, 16, 32):  # growing pow-2 buckets
            serving._get_program(
                _kernel, jax.ShapeDtypeStruct((rows, 4), jnp.float32), (w,),
                {}, donate=False, name="costs.buckets",
            )
        for rows in (8192, 128):  # descending buckets after a big one
            serving._get_program(
                _kernel2, jax.ShapeDtypeStruct((rows, 4), jnp.float32), (w,),
                {}, donate=False, name="costs.buckets.desc",
            )
        assert counter_value("compile.retrace") == 0
        # 16, 32 for the first family; 128 (after 8192) for the second —
        # a smaller bucket following a bigger one is still just a bucket.
        assert counter_value("compile.new_bucket") == 3

    def test_eviction_refill_classified(self, ledger, rng, monkeypatch):
        import jax.numpy as jnp

        monkeypatch.setenv("TPUML_SERVING_CACHE_SIZE", "1")
        w = jnp.asarray(np.ones((4, 2), np.float32))
        s8 = jax.ShapeDtypeStruct((8, 4), jnp.float32)
        s16 = jax.ShapeDtypeStruct((16, 4), jnp.float32)
        serving._get_program(_kernel, s8, (w,), {}, donate=False, name="c.ev")
        serving._get_program(_kernel, s16, (w,), {}, donate=False, name="c.ev")
        # s8 was evicted by s16 (capacity 1): recompiling it is a refill,
        # not a retrace.
        serving._get_program(_kernel, s8, (w,), {}, donate=False, name="c.ev")
        assert counter_value("compile.eviction_refill") == 1
        assert counter_value("compile.retrace") == 0


class TestMeasuredAdmission:
    def test_switch_to_measured_after_first_compile(self, ledger, rng):
        from spark_rapids_ml_tpu.clustering import KMeans
        from spark_rapids_ml_tpu.serving.server import ServingRuntime

        x = rng.normal(size=(64, 8)).astype(np.float32)
        model = KMeans().setK(3).setSeed(1).fit(x)
        d0 = counter_value("serving.admission.declared")
        m0 = counter_value("serving.admission.measured")
        with ServingRuntime() as rt:
            rt.register("km", model)
            rt.submit("km", x[:5]).result(timeout=30)
            d1 = counter_value("serving.admission.declared")
            m1 = counter_value("serving.admission.measured")
            rt.submit("km", x[:5]).result(timeout=30)
            d2 = counter_value("serving.admission.declared")
            m2 = counter_value("serving.admission.measured")
        # First submit of the bucket: priced from the declared spec
        # (nothing compiled yet). After its dispatch compiled the
        # program, the SAME bucket prices from measured bytes.
        assert (d1 - d0, m1 - m0) == (1, 0)
        assert (d2 - d1, m2 - m1) == (0, 1)

    def test_measured_bytes_are_temp_plus_output(self, ledger, rng):
        import jax.numpy as jnp

        w = jnp.asarray(rng.normal(size=(6, 3)).astype(np.float32))
        x = rng.normal(size=(5, 6)).astype(np.float32)
        serve_rows(_kernel, x, (w,), name="costs.price")
        [entry] = [e for e in costs.ledger_snapshot()["entries"]
                   if e["family"] == "costs.price"]
        measured = costs.measured_request_bytes(
            _kernel, {}, 8, 6, np.float32, (w,)
        )
        if "memory_analysis" in entry["unavailable"]:
            assert measured is None  # pragma: no cover - non-CPU backends
        else:
            assert measured == entry["temp_bytes"] + entry["output_bytes"]

    def test_unpriced_before_compile(self, ledger):
        assert costs.measured_request_bytes(_kernel, {}, 8, 6, np.float32, ()) is None


class TestProfCLI:
    def _doc(self, flops=100.0, invocations=4):
        return {
            "version": costs.LEDGER_VERSION,
            "ts": 0.0,
            "pid": 1,
            "entries": [
                {
                    "key": "fam.a|aot|8x4:float32|abc",
                    "family": "fam.a",
                    "kind": "aot",
                    "static": "",
                    "spec": "8x4:float32",
                    "rows": 8,
                    "classification": "new_program",
                    "flops": flops,
                    "transcendentals": 0.0,
                    "bytes_accessed": 10.0 * flops,
                    "argument_bytes": 128,
                    "output_bytes": 64,
                    "temp_bytes": 32,
                    "alias_bytes": 0,
                    "generated_code_bytes": 0,
                    "unavailable": [],
                    "compiles": 1,
                    "compile_seconds": 0.1,
                    "invocations": invocations,
                    "wall_seconds": 0.5,
                    "rows_served": invocations * 5,
                }
            ],
            "watermarks": {"0": {"in_use": 100, "peak_bytes": 200}},
            "retraces": {"total": 0, "families": {}},
            "peaks": {"flops_per_sec": None, "bytes_per_sec": None},
        }

    def test_diff_gates_seeded_regression(self, tmp_path, capsys):
        old, new = tmp_path / "old.json", tmp_path / "new.json"
        old.write_text(json.dumps(self._doc(flops=100.0)))
        new.write_text(json.dumps(self._doc(flops=200.0)))  # seeded 2x
        assert tpuml_prof.main(
            ["--diff", str(old), str(new), "--max-regress", "50"]
        ) == 1
        assert "REGRESSION" in capsys.readouterr().err
        # Identical ledgers pass the same gate.
        assert tpuml_prof.main(
            ["--diff", str(old), str(old), "--max-regress", "50"]
        ) == 0

    def test_diff_new_family_is_note_not_failure(self, tmp_path):
        old, new = tmp_path / "old.json", tmp_path / "new.json"
        doc_new = self._doc()
        doc_new["entries"][0]["family"] = "fam.b"
        doc_new["entries"][0]["key"] = "fam.b|aot|8x4:float32|abc"
        old.write_text(json.dumps(self._doc()))
        new.write_text(json.dumps(doc_new))
        assert tpuml_prof.main(
            ["--diff", str(old), str(new), "--max-regress", "10"]
        ) == 0

    def test_validate_gates_malformed(self, tmp_path, capsys):
        bad = self._doc()
        del bad["entries"][0]["flops"]
        p = tmp_path / "bad.json"
        p.write_text(json.dumps(bad))
        assert tpuml_prof.main([str(p), "--validate"]) == 1
        assert "INVALID" in capsys.readouterr().err

    def test_dump_renders(self, tmp_path, capsys):
        p = tmp_path / "led.json"
        p.write_text(json.dumps(self._doc()))
        assert tpuml_prof.main([str(p), "--sort", "flops"]) == 0
        out = capsys.readouterr().out
        assert "fam.a" in out and "per-family rollup" in out
        assert "peak 200 bytes" in out

    def test_missing_unavailable_marker_rejected(self):
        doc = self._doc()
        doc["entries"][0]["flops"] = None  # no marker either -> invalid
        assert any(
            "unavailable marker" in p for p in validate_ledger(doc)
        )


class TestGangMerge:
    def test_shards_merge_sum_counters_max_watermarks(self, tmp_path):
        a = TestProfCLI()._doc(invocations=3)
        b = TestProfCLI()._doc(invocations=5)
        b["watermarks"]["0"]["peak_bytes"] = 999
        b["retraces"] = {"total": 2, "families": {"fam.a": 2}}
        merged = merge_ledger_docs([a, b])
        [entry] = merged["entries"]
        assert entry["invocations"] == 8
        assert entry["compiles"] == 2
        assert entry["flops"] == 100.0  # analyzed cost: agree, not sum
        assert merged["watermarks"]["0"]["peak_bytes"] == 999
        assert merged["watermarks"]["0"]["in_use"] == 100
        assert merged["retraces"]["total"] == 2
        # And through the CLI's directory loader.
        (tmp_path / "costs-1.json").write_text(json.dumps(a))
        (tmp_path / "costs-2.json").write_text(json.dumps(b))
        doc, problems = tpuml_prof.load_ledger(str(tmp_path))
        assert problems == []
        assert doc["merged_from"] == 2
        assert doc["entries"][0]["invocations"] == 8

    def test_telemetry_shard_and_manifest(self, ledger, rng, tmp_path,
                                          monkeypatch):
        """flush_telemetry writes costs-<pid>.json beside the event
        shard and names it in the manifest; gang_report merges it."""
        import jax.numpy as jnp

        monkeypatch.setenv("TPUML_TELEMETRY_DIR", str(tmp_path))
        events.configure()
        try:
            w = jnp.asarray(rng.normal(size=(4, 2)).astype(np.float32))
            serve_rows(_kernel, rng.normal(size=(3, 4)).astype(np.float32),
                       (w,), name="costs.gang")
            manifest_path = events.flush_telemetry()
            assert manifest_path is not None
            manifest = json.loads(open(manifest_path).read())
            assert manifest["costs"] == f"costs-{os.getpid()}.json"
            shard = json.load(open(tmp_path / manifest["costs"]))
            assert validate_ledger(shard) == []

            from spark_rapids_ml_tpu.observability.report import gang_report

            rep = gang_report(str(tmp_path))
            assert rep["costs"]["members"] == 1
            fams = [e["family"] for e in rep["costs"]["merged"]["entries"]]
            assert "costs.gang" in fams
        finally:
            monkeypatch.delenv("TPUML_TELEMETRY_DIR")
            events.configure()


class TestHbmSampler:
    def test_sampler_gauges_watermarks_and_attribution(self, ledger):
        seq = iter([
            {"0": {"bytes_in_use": 100, "peak_bytes_in_use": 100}},
            {"0": {"bytes_in_use": 300, "peak_bytes_in_use": 400}},
            {"0": {"bytes_in_use": 200, "peak_bytes_in_use": 650}},
        ])
        smp = HbmSampler(period_ms=1000.0, stats_fn=lambda: next(seq))
        samples = [smp.sample_once() for _ in range(3)]
        assert all(s is not None for s in samples)
        from spark_rapids_ml_tpu.observability.metrics import default_registry

        assert default_registry.gauge("device.memory.peak_bytes").value(
            device="0"
        ) == 650
        doc = costs.ledger_snapshot()
        assert doc["watermarks"]["0"] == {"in_use": 300, "peak_bytes": 650}

        # Growth between samples attributes to the deepest covering span.
        t0, t1, t2 = (s[0] for s in samples)
        spans = [
            {"name": "fit", "start": t0 - 1, "end": t2 + 1, "depth": 0},
            {"name": "solver segment", "start": (t0 + t1) / 2,
             "end": (t1 + t2) / 2, "depth": 1},
        ]
        hbm = attribute_hbm_growth(samples, spans)
        assert hbm["delta"] == 550
        assert hbm["by_span"]["solver segment"] == 300
        assert hbm["by_span"]["fit"] == 250

    def test_sampler_knob_starts_thread(self, monkeypatch):
        monkeypatch.setenv("TPUML_COST_LEDGER", "1")
        monkeypatch.setenv("TPUML_HBM_SAMPLE_EVERY_MS", "5")
        costs.reset_for_tests()
        try:
            smp = costs.sampler()
            assert smp is not None and smp.alive()
        finally:
            # Drop BOTH knobs before re-reading them: resetting with
            # TPUML_COST_LEDGER still in the env would re-arm the ledger
            # and leak it into every later test module.
            monkeypatch.delenv("TPUML_HBM_SAMPLE_EVERY_MS")
            monkeypatch.delenv("TPUML_COST_LEDGER")
            costs.reset_for_tests()
            assert costs.sampler() is None
            assert costs.active() is None


class TestTopHotSpot:
    """The roofline table flags the costliest residual family — every
    fit report answers "what pays the most to optimize next"."""

    def _report(self, costs_rows):
        from spark_rapids_ml_tpu.observability.report import RunReport

        return RunReport(
            run_id="r1", kind="fit", label="t", wall_seconds=1.0,
            spans=[], counters={}, device_memory={}, ok=True,
            costs=costs_rows,
        )

    def test_flags_largest_wall_share(self):
        rep = self._report([
            {"family": "a.small", "kind": "aot", "invocations": 1,
             "wall_seconds": 0.1},
            {"family": "b.big", "kind": "segment", "invocations": 4,
             "wall_seconds": 0.3},
        ])
        hot = rep.top_hot_spot()
        assert hot["family"] == "b.big"
        assert hot["wall_share"] == pytest.approx(0.75)
        rendered = str(rep)
        assert "<< hot spot (75% of wall)" in rendered
        # Only the hot row carries the marker.
        assert rendered.count("<< hot spot") == 1

    def test_no_costs_no_flag(self):
        rep = self._report([])
        assert rep.top_hot_spot() is None
        assert "hot spot" not in str(rep)

    def test_zero_wall_rows_ignored(self):
        rep = self._report([
            {"family": "compiled.never.ran", "kind": "aot",
             "invocations": 0, "wall_seconds": 0.0},
        ])
        assert rep.top_hot_spot() is None

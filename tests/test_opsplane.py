"""Live ops plane (ISSUE 19): scrape endpoints, gang /statusz, SLO error
budgets, and the crash flight recorder.

The acceptance surface:

  - **Off by default.** With no ``TPUML_OPS_PORT`` / ``TPUML_SLO`` /
    ``TPUML_FLIGHT`` there is no server, no monitor, no ring — the
    instrumented emit path stays one None-check.
  - **One exposition renderer.** ``/metrics``, ``TPUML_METRICS_DUMP``
    and ``tools/tpuml_metrics.py`` all render through
    :func:`metrics.render_prometheus_snapshot`;
    :func:`metrics.parse_exposition` round-trips it (the conformance
    oracle CI also runs over scraped ``.prom`` artifacts).
  - **Live == post-hoc.** A routed 2-member gang's ``/statusz`` (merged
    with ``trace.merge_metrics``) agrees exactly, counter for counter,
    with ``tpuml_trace``'s post-mortem assemble of the same gang's
    telemetry shards.
  - **/healthz flips before EOF.** A member frozen by the
    ``ipc.recv=...:stall`` fault keeps its socket open; its OWN
    ``/healthz`` goes 503 on heartbeat age (``TPUML_OPS_STALL_S``)
    while the router still counts it live.
  - **SLO burn is a control input.** A declared latency objective under
    injected bad latency fires a breach edge (``slo`` event), the
    ElasticScaler's tick consumes the burn gauge as a scale-up vote, and
    the DriftMonitor's subscription lowers its refit window floor — all
    proven by event-log join.
  - **Flight recorder closes the killed-member hole.** A process that
    dies SIGKILL-adjacent (``os._exit`` — no atexit, no manifest) leaves
    a ``flight-<pid>.json`` that ``tpuml_trace --validate --strict``
    merges with zero orphan spans.
"""

from __future__ import annotations

import importlib.util
import json
import os
import signal
import subprocess
import sys
import textwrap
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from spark_rapids_ml_tpu.lifecycle.drift import DriftMonitor
from spark_rapids_ml_tpu.models.kmeans import KMeansModel
from spark_rapids_ml_tpu.observability import events
from spark_rapids_ml_tpu.observability import flightrec
from spark_rapids_ml_tpu.observability import opsplane
from spark_rapids_ml_tpu.observability import slo as slolib
from spark_rapids_ml_tpu.observability import trace as tracelib
from spark_rapids_ml_tpu.observability.metrics import (
    Registry,
    gauge,
    histogram,
    parse_exposition,
    percentile_from_histogram,
)
from spark_rapids_ml_tpu.robustness import faults
from spark_rapids_ml_tpu.serving import ElasticScaler, RoutingRuntime
from spark_rapids_ml_tpu.utils import tracing
from spark_rapids_ml_tpu.utils.envknobs import env_str
from spark_rapids_ml_tpu.utils.tracing import bump_counter

REPO = Path(__file__).resolve().parents[1]
TRACE_CLI = REPO / "tools" / "tpuml_trace.py"
TOP_CLI = REPO / "tools" / "tpuml_top.py"

D = 8


def dyadic(rng, shape, scale=4):
    return rng.integers(-4 * scale, 4 * scale, size=shape).astype(np.float64) / 4.0


_PREV_LOG = env_str(events.EVENT_LOG_ENV)


def _restore_sink():
    events.configure(_PREV_LOG if _PREV_LOG else None)


@pytest.fixture
def telemetry(tmp_path):
    """A fresh telemetry dir as the active sink, exported to the
    environment so spawned members inherit it and write their own shards
    (the tests/test_serving_router.py arrangement)."""
    d = str(tmp_path / "telemetry")
    prev = env_str(events.TELEMETRY_DIR_ENV)
    os.environ[events.TELEMETRY_DIR_ENV] = d
    events.configure()
    try:
        yield Path(d)
    finally:
        if prev is None:
            os.environ.pop(events.TELEMETRY_DIR_ENV, None)
        else:
            os.environ[events.TELEMETRY_DIR_ENV] = prev
        _restore_sink()


def _http_get(url: str, timeout: float = 10.0):
    """(status, content_type, body) — non-2xx comes back as data, not an
    exception (a 503 /healthz IS the answer under test)."""
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return (resp.status, resp.headers.get("Content-Type", ""),
                    resp.read().decode("utf-8"))
    except urllib.error.HTTPError as exc:
        return exc.code, exc.headers.get("Content-Type", ""), exc.read().decode("utf-8")


def _artifact(name: str, body: str) -> None:
    """Drop a scraped body where CI's conformance gate picks it up."""
    d = env_str("TPUML_TEST_OPS_ARTIFACTS")
    if not d:
        return
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, name), "w") as f:
        f.write(body)


def _shard_records(telemetry_dir) -> list:
    events.flush_telemetry()
    recs = []
    for shard in sorted(Path(telemetry_dir).glob("events-*.jsonl")):
        for line in open(shard):
            if line.strip():
                recs.append(json.loads(line))
    return recs


# ---------------------------------------------------------------------------
# off by default: no port knob -> no server, no monitor, no ring
# ---------------------------------------------------------------------------


class TestOffByDefault:
    @pytest.mark.skipif(
        bool(env_str(opsplane.OPS_PORT_ENV)),
        reason="TPUML_OPS_PORT armed for this run",
    )
    def test_no_server_without_port_knob(self):
        assert opsplane.active() is None
        assert opsplane.active_port() is None
        assert opsplane.maybe_start_from_env() is None

    @pytest.mark.skipif(
        bool(env_str(slolib.SLO_ENV)),
        reason="TPUML_SLO armed for this run",
    )
    def test_no_slo_monitor_without_spec(self):
        assert slolib.active() is None
        assert slolib.maybe_start_from_env() is None

    @pytest.mark.skipif(
        bool(env_str(events.FLIGHT_ENV)),
        reason="TPUML_FLIGHT armed for this run",
    )
    def test_disabled_emit_is_one_none_check(self):
        if events.enabled():
            pytest.skip("an event sink is active in this run")
        assert events.flight_ring() is None
        before = events.emitted_count()
        for _ in range(100):
            events.emit("fault", action="noop")
        assert events.emitted_count() == before


# ---------------------------------------------------------------------------
# percentile_from_histogram: None on no-signal, callers must not divide
# ---------------------------------------------------------------------------


class TestPercentileNone:
    def test_empty_histogram_returns_none(self):
        r = Registry()
        h = r.histogram("t.lat", "empty", buckets=(1.0, 2.0, 4.0))
        assert percentile_from_histogram(h.value(), 0.95) is None
        assert percentile_from_histogram(h.value(), 0.5) is None

    def test_all_mass_in_overflow_returns_none(self):
        r = Registry()
        h = r.histogram("t.lat", "inf-only", buckets=(1.0, 2.0, 4.0))
        for _ in range(3):
            h.observe(100.0)
        assert percentile_from_histogram(h.value(), 0.95) is None

    def test_interpolation_inside_finite_buckets(self):
        r = Registry()
        h = r.histogram("t.lat", "interp", buckets=(1.0, 2.0, 4.0))
        for v in (0.5, 1.5, 3.0):
            h.observe(v)
        assert percentile_from_histogram(h.value(), 0.5) == pytest.approx(1.5)

    def test_overflow_with_finite_mass_reports_top_edge(self):
        r = Registry()
        h = r.histogram("t.lat", "mixed", buckets=(1.0, 2.0, 4.0))
        h.observe(0.5)
        h.observe(100.0)
        assert percentile_from_histogram(h.value(), 0.99) == pytest.approx(4.0)


# ---------------------------------------------------------------------------
# ONE exposition renderer + the parse-back conformance oracle
# ---------------------------------------------------------------------------


class TestExpositionRoundTrip:
    def _registry(self) -> Registry:
        r = Registry()
        # A backslash in the label value exercises render-time escaping
        # (snapshot keys store label values raw; quotes/newlines are not
        # representable there, so the escaping contract covers "\\").
        r.counter("rt.count", "requests served").inc(3, model="a\\c d")
        r.counter("rt.count").inc(4, model="plain")
        r.gauge("rt.gauge", "a level").set(2.5, host="x")
        h = r.histogram("rt.lat", "latency", buckets=(1.0, 2.0, 5.0))
        for v in (0.5, 1.5, 99.0):
            h.observe(v)
        return r

    def test_round_trip_values_types_and_help(self):
        r = self._registry()
        text = r.render_prometheus()
        doc = parse_exposition(text)

        count = doc["tpuml_rt_count"]
        assert count["type"] == "counter"
        assert count["help"] == "requests served"
        assert sorted(count["series"].values()) == [3.0, 4.0]
        # The escaped label value survives the round trip unescaped.
        assert 'tpuml_rt_count{model="a\\c d"}' in count["series"]

        g = doc["tpuml_rt_gauge"]
        assert g["type"] == "gauge"
        assert list(g["series"].values()) == [2.5]

        hist = doc["tpuml_rt_lat"]
        assert hist["type"] == "histogram"
        series = hist["series"]
        assert series["tpuml_rt_lat_count"] == 3.0
        assert series["tpuml_rt_lat_sum"] == pytest.approx(101.0)
        assert series['tpuml_rt_lat_bucket{le="+Inf"}'] == 3.0
        finite = [v for k, v in series.items()
                  if k.startswith("tpuml_rt_lat_bucket") and "+Inf" not in k]
        assert max(finite) == 2.0  # 0.5 and 1.5; 99 only in +Inf

    def test_default_registry_renderer_is_the_shared_one(self):
        """Registry.render_prometheus delegates to the one snapshot
        renderer: rendering its own snapshot must be byte-identical
        (modulo the snapshot's wall-clock ts, which the renderer
        ignores)."""
        from spark_rapids_ml_tpu.observability.metrics import (
            render_prometheus_snapshot,
        )

        r = self._registry()
        helps = {name: m.help for name, m in r.metrics().items() if m.help}
        assert r.render_prometheus() == render_prometheus_snapshot(
            r.snapshot(), helps=helps
        )

    def test_cli_snapshot_renderer_delegates(self, tmp_path):
        """tools/tpuml_metrics.py render path == the library renderer."""
        spec = importlib.util.spec_from_file_location(
            "tpuml_metrics_under_test", REPO / "tools" / "tpuml_metrics.py"
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        snap = self._registry().snapshot()
        text = mod.render_snapshot_prometheus(snap)
        doc = parse_exposition(text)
        assert doc["tpuml_rt_lat"]["series"]["tpuml_rt_lat_count"] == 3.0


# ---------------------------------------------------------------------------
# the per-process ops server: /metrics /healthz /varz /tracez
# ---------------------------------------------------------------------------


@pytest.fixture(scope="class")
def ops_server():
    srv = opsplane.OpsServer(0)
    try:
        yield srv
    finally:
        srv.close()


class TestOpsServerEndpoints:
    def test_metrics_scrape_is_valid_exposition(self, ops_server):
        bump_counter("opsplane.test.scrape")
        status, ctype, body = _http_get(f"{ops_server.url}/metrics")
        assert status == 200
        assert ctype.startswith("text/plain")
        doc = parse_exposition(body)
        assert "tpuml_opsplane_test_scrape" in doc
        _artifact("endpoints-metrics.prom", body)

    def test_varz_serves_the_live_registry(self, ops_server):
        bump_counter("opsplane.test.varz")
        status, ctype, body = _http_get(f"{ops_server.url}/varz")
        assert status == 200 and ctype.startswith("application/json")
        doc = json.loads(body)
        assert doc["pid"] == os.getpid()
        assert doc["metrics"]["counters"]["opsplane.test.varz"] >= 1
        assert "serving" in doc and "routers" in doc

    def test_tracez_reports_recent_spans(self, ops_server):
        with tracing.TraceRange("opsplane-span"):
            pass
        status, _, body = _http_get(f"{ops_server.url}/tracez")
        assert status == 200
        doc = json.loads(body)
        assert "open" in doc
        assert any(r["name"] == "opsplane-span" for r in doc["recent"])

    def test_healthz_flips_on_failing_probe_and_recovers(self, ops_server):
        status0, _, body0 = _http_get(f"{ops_server.url}/healthz")
        doc0 = json.loads(body0)
        assert status0 == (200 if doc0["ok"] else 503)

        opsplane.add_probe("test.opsplane.flip", lambda: False)
        try:
            status, _, body = _http_get(f"{ops_server.url}/healthz")
            assert status == 503
            doc = json.loads(body)
            assert doc["ok"] is False
            assert doc["checks"]["test.opsplane.flip"]["ok"] is False
        finally:
            opsplane.remove_probe("test.opsplane.flip")
        status2, _, _ = _http_get(f"{ops_server.url}/healthz")
        assert status2 == status0

    def test_raising_probe_is_a_failed_probe(self, ops_server):
        def boom():
            raise RuntimeError("probe died")

        opsplane.add_probe("test.opsplane.boom", boom)
        try:
            status, _, body = _http_get(f"{ops_server.url}/healthz")
            assert status == 503
            assert json.loads(body)["checks"]["test.opsplane.boom"] == {
                "ok": False, "exc": "RuntimeError",
            }
        finally:
            opsplane.remove_probe("test.opsplane.boom")

    def test_unknown_path_404_lists_endpoints(self, ops_server):
        status, _, body = _http_get(f"{ops_server.url}/nope")
        assert status == 404
        assert "/metrics" in json.loads(body)["endpoints"]

    def test_remove_endpoint_identity_guard(self, ops_server):
        """A closing owner must not tear down a path a newer owner has
        since claimed (the stacked-routers /statusz hazard)."""
        fn1 = lambda: (200, "text/plain", "one\n")  # noqa: E731
        fn2 = lambda: (200, "text/plain", "two\n")  # noqa: E731
        opsplane.add_endpoint("/test-guard", fn1)
        opsplane.add_endpoint("/test-guard", fn2)
        try:
            opsplane.remove_endpoint("/test-guard", fn1)  # stale owner
            status, _, body = _http_get(f"{ops_server.url}/test-guard")
            assert (status, body) == (200, "two\n")
        finally:
            opsplane.remove_endpoint("/test-guard")
        status, _, _ = _http_get(f"{ops_server.url}/test-guard")
        assert status == 404


# ---------------------------------------------------------------------------
# /statusz: the live gang-merged view == the post-hoc shard merge
# ---------------------------------------------------------------------------


class TestStatuszLiveEqualsPostHoc:
    N = 24

    def test_live_statusz_matches_posthoc_merge(
        self, telemetry, monkeypatch
    ):
        """Route real traffic across a 2-member spawned gang whose
        members run ops servers (ports learned from contact cards),
        scrape the router's /statusz over HTTP after the traffic
        quiesces, then close the gang and assemble its telemetry shards
        post-hoc: the serving.* counters and histograms must agree
        EXACTLY — same merge function, same answer, live or dead."""
        monkeypatch.setenv(opsplane.OPS_PORT_ENV, "0")
        rng = np.random.default_rng(91)
        model = KMeansModel("ops-km", dyadic(rng, (4, D)))
        probes = dyadic(rng, (self.N, D))
        expected = model.predict(probes)

        local = opsplane.start(0)
        rt = RoutingRuntime(workers=2, launch="spawn", max_delay_ms=1.0)
        try:
            rt.register("km", model, warm_buckets=(1,))
            for i in range(self.N):
                out = rt.submit("km", probes[i]).result(timeout=60)
                np.testing.assert_array_equal(
                    np.asarray(out), expected[i : i + 1]
                )

            # Traffic quiesced: scrape the gang through the HTTP surface
            # the operator would use.
            status, ctype, body = _http_get(f"{local.url}/statusz")
            assert status == 200 and ctype.startswith("application/json")
            live = json.loads(body)

            members = live["members"]
            assert len(members) == 2
            for cell in members.values():
                assert cell["ok"] is True, cell
                assert isinstance(cell["ops_port"], int)
                assert cell["pid"] != os.getpid()

            # Member /metrics scrapes are valid exposition (CI re-parses
            # the dropped artifacts through the same oracle).
            for mid, cell in sorted(members.items()):
                ms, _, mbody = _http_get(
                    f"http://127.0.0.1:{cell['ops_port']}/metrics"
                )
                assert ms == 200
                mdoc = parse_exposition(mbody)
                assert "tpuml_serving_worker_ops" in mdoc
                _artifact(f"member-{mid}.prom", mbody)

            # The new CLI renders the same document.
            spec = importlib.util.spec_from_file_location(
                "tpuml_top_under_test", TOP_CLI
            )
            top = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(top)
            assert top.normalize_url("8321") == (
                "http://127.0.0.1:8321/statusz"
            )
            frame = top.render_frame(live)
            assert live["router"]["router"] in frame
            assert "gang:" in frame and "live" in frame
        finally:
            rt.close()
            opsplane.stop()

        live_counters = {
            k: v for k, v in live["merged"]["counters"].items()
            if k.startswith("serving.")
        }
        live_hists = {
            k: v for k, v in live["merged"]["histograms"].items()
            if k.startswith("serving.")
        }

        events.flush_telemetry()
        merged = tracelib.assemble(str(telemetry))
        assert merged["problems"] == []
        post = merged["metrics"]["merged"]
        post_counters = {
            k: v for k, v in post["counters"].items()
            if k.startswith("serving.")
        }
        post_hists = {
            k: v for k, v in post["histograms"].items()
            if k.startswith("serving.")
        }

        # Counter for counter: the live merge and the post-mortem merge
        # are the same function over the same state.
        assert live_counters == post_counters
        assert live_counters["serving.requests"] >= self.N

        assert sorted(live_hists) == sorted(post_hists)
        for name, series in live_hists.items():
            for skey, cell in series.items():
                other = post_hists[name][skey]
                assert cell["buckets"] == other["buckets"], (name, skey)
                assert cell["count"] == other["count"], (name, skey)
                assert cell["sum"] == pytest.approx(other["sum"])


# ---------------------------------------------------------------------------
# /healthz flips 503 on a wedged member BEFORE its socket ever EOFs
# ---------------------------------------------------------------------------


class TestHealthzStallFlip:
    def test_stalled_member_healthz_flips_before_eof(
        self, telemetry, monkeypatch
    ):
        """Freeze a member's frame loop with the ``:stall`` fault: its
        manual heartbeat stops beating, so its OWN /healthz goes 503 on
        heartbeat age (TPUML_OPS_STALL_S) while its socket is still open
        and the router still counts it live — the wedge is visible from
        the outside before any EOF. The stall-retire ladder then
        recovers every parked request bitwise intact."""
        monkeypatch.setenv(opsplane.OPS_PORT_ENV, "0")
        monkeypatch.setenv(opsplane.OPS_STALL_ENV, "1.0")
        rng = np.random.default_rng(92)
        model = KMeansModel("healthz-km", dyadic(rng, (4, D)))
        probes = dyadic(rng, (12, D))
        expected = model.predict(probes)

        rt = RoutingRuntime(workers=1, launch="spawn", max_delay_ms=1.0)
        try:
            rt.register("km", model, warm_buckets=(1,))
            # Arm ONLY the joiner; its recv sequence is hello(0), replay
            # register(1), replay warm(2) — @3 freezes on the first
            # routed frame, after a clean join.
            monkeypatch.setenv(faults.FAULTS_ENV, "ipc.recv=always@3:stall")
            stalled_id = rt.add_member()
            monkeypatch.delenv(faults.FAULTS_ENV)

            card = rt.statusz()["members"][str(stalled_id)]
            assert card["ok"] is True
            url = f"http://127.0.0.1:{card['ops_port']}/healthz"

            # Healthy first: the select-gated frame loop beats every
            # 0.2 s, well inside the 1 s limit.
            deadline = time.monotonic() + 10.0
            status = None
            while time.monotonic() < deadline:
                status, _, _ = _http_get(url)
                if status == 200:
                    break
                time.sleep(0.1)
            assert status == 200

            # The burst lands at least one frame on the armed member and
            # freezes its loop.
            futs = [rt.submit("km", probes[i]) for i in range(12)]

            deadline = time.monotonic() + 30.0
            doc = None
            while time.monotonic() < deadline:
                status, _, body = _http_get(url)
                if status == 503:
                    doc = json.loads(body)
                    break
                time.sleep(0.1)
            assert doc is not None, "stalled member /healthz never flipped"
            hb = doc["checks"]["heartbeat"]
            assert hb["ok"] is False
            assert hb["max_age_s"] > 1.0

            # ... and at flip time the router has seen NO EOF: the
            # member is still in the selection set, socket open.
            by_id = {m["member"]: m for m in rt.snapshot()["members"]}
            assert by_id[stalled_id]["dead"] is False

            # Recovery: the liveness ladder retires the wedge and every
            # parked request redispatches losslessly.
            deadline = time.monotonic() + 30.0
            retired: list = []
            while stalled_id not in retired:
                assert time.monotonic() < deadline, "stall retire never fired"
                retired += rt.retire_stalled(1.0)
                time.sleep(0.05)
            for i, fut in enumerate(futs):
                np.testing.assert_array_equal(
                    np.asarray(fut.result(timeout=60)), expected[i : i + 1]
                )
        finally:
            rt.close()

        recs = _shard_records(telemetry)
        stalls = [r for r in recs if r.get("action") == "member_stalled"]
        assert [r["member"] for r in stalls] == [stalled_id]


# ---------------------------------------------------------------------------
# SLO error budgets: burn-rate gauges, breach edges, scale/refit votes
# ---------------------------------------------------------------------------


class TestSloSpec:
    def test_parse_spec(self):
        objs = slolib.parse_slo(
            "serving.p95_ms<=50;shed.rate<=0.01;freshness.age_s<=600"
        )
        assert [(o.name, o.op, o.threshold) for o in objs] == [
            ("serving.p95_ms", "<=", 50.0),
            ("shed.rate", "<=", 0.01),
            ("freshness.age_s", "<=", 600.0),
        ]
        assert objs[0].spec() == "serving.p95_ms<=50"
        assert slolib.parse_slo("") == []
        assert slolib.parse_slo(" ; ") == []

    def test_malformed_spec_refused_loudly(self):
        with pytest.raises(slolib.SloSpecError, match="malformed"):
            slolib.parse_slo("serving.p95_ms<50")
        with pytest.raises(slolib.SloSpecError, match="malformed"):
            slolib.parse_slo("p95==nope")


class _FakeRouter:
    """The ElasticScaler's whole view of a gang, minus the gang."""

    def __init__(self):
        self.added = 0

    def snapshot(self):
        return {
            "members": [
                {"member": 0, "dead": False, "joining": False,
                 "retiring": False, "depth": 0, "outstanding": 0}
            ]
        }

    def add_member(self, **kwargs):
        self.added += 1
        return self.added

    def retire_member(self, member_id, **kwargs):  # pragma: no cover
        raise AssertionError("scaler must not retire under SLO pressure")

    def retire_stalled(self, max_age):
        return []


class TestSloControlLoop:
    def test_latency_breach_edge_scaler_and_drift_votes(self, telemetry):
        """The flagship joined path: injected bad latency burns the
        declared p95 budget -> breach edge (``slo`` event + burn gauge),
        the ElasticScaler's next tick votes scale-up on an otherwise
        idle gang, and the subscribed DriftMonitor lowers its refit
        window floor — every hop visible in the event log."""
        monitor = slolib.SloMonitor("serving.p95_ms<=5")
        edges: list = []
        dm = DriftMonitor("slo-ops", threshold=10.0, min_count=50)
        hist = histogram(
            "serving.router.latency_ms", "router-observed request latency"
        )
        try:
            monitor.tick()  # absorb whatever history this process has
            for _ in range(40):
                hist.observe(1.0)  # a good window: tail mass 0
            out = monitor.tick()
            assert out["serving.p95_ms"]["breached"] is False
            # Only NOW wire the consumers: the process is provably in
            # the non-breached state, so the next edge is the breach.
            monitor.subscribe(edges.append)
            monitor.subscribe(dm.on_slo_breach)

            for _ in range(40):
                hist.observe(100.0)  # the injected latency fault
            out = monitor.tick()
            cell = out["serving.p95_ms"]
            assert cell["breached"] is True
            assert cell["burn"] == pytest.approx(20.0)  # 100%/5% budget
            assert [e["action"] for e in edges] == ["breach"]
            assert slolib.burn_rates()["serving.p95_ms"] > 1.0

            # The scaler consumes the burn gauge: an idle gang under a
            # burning SLO still scales up.
            fake = _FakeRouter()
            scaler = ElasticScaler(
                fake, min_members=1, max_members=4, hysteresis=1,
                cooldown_ms=0.0, stall_after_s=0.0,
            )
            assert scaler.tick() == "scale_up"
            assert fake.added == 1
            assert scaler.decisions == [("scale_up", 1)]

            # The drift monitor's vote drops its window floor: 10
            # observations evaluate NOW instead of waiting out 50.
            assert dm._slo_votes == 1
            dm.observe_many(np.linspace(0.0, 1.0, 10))
            assert dm.tick() is None  # bootstrap tick -> baseline
            assert dm._window == []   # ... which proves it evaluated

            # Recovery edge on the next good window.
            for _ in range(40):
                hist.observe(1.0)
            out = monitor.tick()
            assert out["serving.p95_ms"]["breached"] is False
            assert edges[-1]["action"] == "recover"
        finally:
            gauge(slolib.BURN_GAUGE).remove(objective="serving.p95_ms")

        # Event-log join: breach -> scale_up(slo_burn) -> slo_vote.
        # (The absorb tick may have emitted an extra breach/recover pair
        # out of whatever latency history this process carries, so the
        # assertion anchors on the LAST edge pair — the injected one.)
        recs = _shard_records(telemetry)
        slo_recs = [r for r in recs if r.get("event") == "slo"]
        assert [r["action"] for r in slo_recs[-2:]] == ["breach", "recover"]
        breach = slo_recs[-2]
        assert breach["objective"] == "serving.p95_ms"
        assert breach["burn"] > 1.0

        ups = [r for r in recs
               if r.get("event") == "elastic" and r.get("action") == "scale_up"]
        assert len(ups) == 1
        assert ups[0]["slo_burn"] == pytest.approx(20.0)

        votes = [r for r in recs if r.get("action") == "slo_vote"]
        assert len(votes) == 1
        assert votes[0]["objective"] == "serving.p95_ms"
        assert votes[0]["votes"] == 1
        baselined = [r for r in recs if r.get("action") == "drift_baseline"]
        assert [r["count"] for r in baselined] == [10]

    def test_shed_rate_objective_windows_counter_deltas(self):
        monitor = slolib.SloMonitor("shed.rate<=0.01")
        try:
            monitor.tick()  # baseline the cumulative counters
            bump_counter("serving.router.shed", 5)
            bump_counter("serving.router.requests", 5)
            cell = monitor.tick()["shed.rate"]
            assert cell["value"] == pytest.approx(0.5)  # 5 shed / 10 offered
            assert cell["burn"] == pytest.approx(50.0)
            assert cell["breached"] is True

            # A clean follow-up window recovers.
            bump_counter("serving.router.requests", 100)
            cell = monitor.tick()["shed.rate"]
            assert cell["value"] == pytest.approx(0.0)
            assert cell["breached"] is False
        finally:
            gauge(slolib.BURN_GAUGE).remove(objective="shed.rate")

    def test_value_objective_uses_registered_source(self):
        monitor = slolib.SloMonitor("freshness.age_s<=600")
        age = {"v": 1200.0}
        monitor.set_source("freshness.age_s", lambda: age["v"])
        try:
            cell = monitor.tick()["freshness.age_s"]
            assert cell["burn"] == pytest.approx(2.0)
            assert cell["breached"] is True
            age["v"] = 60.0
            cell = monitor.tick()["freshness.age_s"]
            assert cell["burn"] == pytest.approx(0.1)
            assert cell["breached"] is False
        finally:
            gauge(slolib.BURN_GAUGE).remove(objective="freshness.age_s")

    def test_recover_records_are_not_refit_votes(self):
        dm = DriftMonitor("slo-ignore", threshold=10.0, min_count=50)
        dm.on_slo_breach({"action": "recover", "objective": "x"})
        assert dm._slo_votes == 0
        dm.on_slo_breach({"action": "breach", "objective": "x", "burn": 2.0})
        assert dm._slo_votes == 1


# ---------------------------------------------------------------------------
# flight recorder: the crash dump that survives a skipped atexit
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_ring_captures_without_any_sink(self, tmp_path):
        """TPUML_FLIGHT arms the bounded ring even with NO event sink:
        the crash dump works where no event log was ever configured."""
        prev_flight = env_str(events.FLIGHT_ENV)
        prev_dir = env_str(events.TELEMETRY_DIR_ENV)
        os.environ[events.FLIGHT_ENV] = "8"
        os.environ[events.TELEMETRY_DIR_ENV] = ""
        os.environ[events.EVENT_LOG_ENV] = ""
        events.configure()
        try:
            assert not events.enabled()
            ring = events.flight_ring()
            assert ring is not None and ring.maxlen == 8
            before = events.emitted_count()
            for i in range(20):
                events.emit("fault", action="arm", seq=i)
            assert events.emitted_count() == before  # no sink: not written
            assert len(ring) == 8
            assert [r["seq"] for r in ring] == list(range(12, 20))

            flightrec.reset()
            dest = str(tmp_path / "flight-ring.json")
            assert flightrec.dump("test-ring", path=dest) == dest
            doc = json.load(open(dest))
            assert doc["kind"] == flightrec.DOC_KIND
            assert doc["pid"] == os.getpid()
            assert [r["seq"] for r in doc["ring"]] == list(range(12, 20))
            assert doc["threads"]  # all-thread stacks rode along
            assert isinstance(doc["metrics"], dict)

            # once=True dedupes a dump storm per reason.
            assert flightrec.dump("test-ring", path=dest) is None
        finally:
            if prev_flight is None:
                os.environ.pop(events.FLIGHT_ENV, None)
            else:
                os.environ[events.FLIGHT_ENV] = prev_flight
            if prev_dir is None:
                os.environ.pop(events.TELEMETRY_DIR_ENV, None)
            else:
                os.environ[events.TELEMETRY_DIR_ENV] = prev_dir
            if _PREV_LOG is None:
                os.environ.pop(events.EVENT_LOG_ENV, None)
            else:
                os.environ[events.EVENT_LOG_ENV] = _PREV_LOG
            flightrec.reset()
            events.configure()

    def test_dump_lands_under_the_temp_dir_when_no_dir_is_named(
        self, tmp_path, monkeypatch
    ):
        """With neither ``TPUML_FLIGHT_DIR`` nor a telemetry dir, a dump
        goes to ``tpuml-flight`` under the temporary directory and never
        to the working directory (dumps once landed in the checkout and
        were committed)."""
        import tempfile

        monkeypatch.delenv(flightrec.FLIGHT_DIR_ENV, raising=False)
        monkeypatch.delenv(events.TELEMETRY_DIR_ENV, raising=False)
        monkeypatch.setattr(tempfile, "tempdir", str(tmp_path / "tmp"))
        cwd = tmp_path / "checkout"
        cwd.mkdir()
        monkeypatch.chdir(cwd)
        assert flightrec.flight_dir() == str(tmp_path / "tmp" / "tpuml-flight")
        flightrec.reset()
        try:
            dest = flightrec.dump("test-no-dir")
        finally:
            flightrec.reset()
        assert dest == str(
            tmp_path / "tmp" / "tpuml-flight" / f"flight-{os.getpid()}.json"
        )
        assert json.load(open(dest))["reason"] == "test-no-dir"
        assert not list(cwd.iterdir())
        # a named directory still wins, then the telemetry dir
        monkeypatch.setenv(events.TELEMETRY_DIR_ENV, str(tmp_path / "telemetry"))
        assert flightrec.flight_dir() == str(tmp_path / "telemetry")
        monkeypatch.setenv(flightrec.FLIGHT_DIR_ENV, str(tmp_path / "named"))
        assert flightrec.flight_dir() == str(tmp_path / "named")

    def test_sigterm_flush_publishes_manifest_and_flight(self, telemetry):
        """The SIGTERM handler the serving worker and barrier members
        install: flight dump + telemetry flush BEFORE SystemExit(143),
        so a TERM'd member never leaves a manifest-less shard."""
        flightrec.reset()
        undo = events.install_sigterm_flush()
        try:
            with pytest.raises(SystemExit) as excinfo:
                signal.raise_signal(signal.SIGTERM)
            assert excinfo.value.code == 143
        finally:
            undo()
            flightrec.reset()

        pid = os.getpid()
        manifest = json.load(open(telemetry / f"manifest-{pid}.json"))
        assert manifest["pid"] == pid
        assert (telemetry / f"metrics-{pid}.json").exists()
        flight = json.load(open(telemetry / f"flight-{pid}.json"))
        assert flight["reason"] == "sigterm"

    def test_install_off_main_thread_degrades_to_noop(self):
        out: dict = {}

        def _t():
            out["undo"] = events.install_sigterm_flush()

        t = threading.Thread(target=_t)
        t.start()
        t.join()
        out["undo"]()  # callable, and a no-op
        # The main-thread SIGTERM disposition was never touched.
        assert signal.getsignal(signal.SIGTERM) != 143

    def test_crash_dump_merges_into_the_posthoc_trace(self, telemetry):
        """A SIGKILL-adjacent death (os._exit: no atexit, no manifest,
        in-registry metrics lost) leaves flight-<pid>.json; the merge
        accepts it as manifest + metrics stand-in and the strict
        validation gate passes with zero orphan spans."""
        code = textwrap.dedent(
            """
            import os
            from spark_rapids_ml_tpu.observability import events, flightrec
            from spark_rapids_ml_tpu.utils import tracing

            with events.run_scope("job", "crash-test"):
                with tracing.TraceRange("doomed-work"):
                    events.emit("fault", action="arm", site="flight-crash")
                    flightrec.dump("test-crash")
                    os._exit(1)
            """
        )
        env = {
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            events.TELEMETRY_DIR_ENV: str(telemetry),
            events.FLIGHT_ENV: "64",
        }
        r = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, cwd=str(REPO), env=env,
        )
        assert r.returncode == 1, r.stdout + r.stderr

        flights = list(Path(telemetry).glob("flight-*.json"))
        assert len(flights) == 1
        doc = json.load(open(flights[0]))
        crash_pid = doc["pid"]
        assert doc["reason"] == "test-crash"
        assert any(rec.get("site") == "flight-crash" for rec in doc["ring"])
        assert not (telemetry / f"manifest-{crash_pid}.json").exists()

        events.flush_telemetry()
        merged = tracelib.assemble(str(telemetry))
        assert merged["problems"] == []
        assert merged["orphan_problems"] == []
        assert [os.path.basename(f) for f in merged["flights"]] == [
            f"flight-{crash_pid}.json"
        ]
        # The synthesized manifest stands in for the lost atexit flush.
        stand_in = [m for m in merged["manifests"] if m.get("pid") == crash_pid]
        assert len(stand_in) == 1
        assert stand_in[0]["flight"] == "test-crash"
        # ... and the dump's metrics snapshot joined the gang merge.
        assert any(
            m["file"] == f"flight-{crash_pid}.json"
            for m in merged["metrics"]["members"]
        )

        cli = subprocess.run(
            [sys.executable, str(TRACE_CLI), str(telemetry),
             "--validate", "--strict"],
            capture_output=True, text=True, cwd=str(REPO),
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert cli.returncode == 0, cli.stdout + cli.stderr
        assert "flight recorder dump merged" in cli.stdout

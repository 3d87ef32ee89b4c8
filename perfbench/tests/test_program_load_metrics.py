"""The readers of the program-load counters: the set-up's share of each is
its total after the window less its change over the window."""

from types import SimpleNamespace

import pytest

from perfbench import run as bench_run
from perfbench.drivers import fit_loop
from perfbench.metrics import (
    setup_cache_load_s,
    setup_compile_s,
    setup_programs,
    setup_trace_s,
)
from spark_rapids_ml_tpu.utils import tracing

READERS = {
    "setup_trace_s": setup_trace_s,
    "setup_compile_s": setup_compile_s,
    "setup_cache_load_s": setup_cache_load_s,
    "setup_programs": setup_programs,
}
LAYER = "Program load (package import, persistent cache, warm-up fit)"

# made-up totals after a window, and the window's own change
TOTALS = {
    "program.trace.ns": 3_000_000_000, "program.trace.calls": 900,
    "program.lower.ns": 1_500_000_000, "program.lower.calls": 40,
    "program.compile.ns": 250_000_000, "program.compile.calls": 3,
    "program.cache_load.ns": 14_000_000_000, "program.cache_load.calls": 12,
}
WINDOW = {"program.trace.ns": 500_000_000, "program.trace.calls": 10,
          "program.compile.ns": 50_000_000, "program.compile.calls": 1}
SETUP = {
    "setup_trace_s": (3.0 - 0.5) + 1.5,
    "setup_compile_s": 0.25 - 0.05,
    "setup_cache_load_s": 14.0,
    "setup_programs": (3 - 1) + 12,
}


def totals(monkeypatch, values):
    monkeypatch.setattr(tracing, "counters", lambda prefix="": {
        k: v for k, v in values.items() if k.startswith(prefix)})


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_takes_the_window_off_the_total(name, monkeypatch):
    totals(monkeypatch, TOTALS)
    ctx = SimpleNamespace(record={"counters": dict(WINDOW)})
    assert READERS[name].read(ctx) == pytest.approx(SETUP[name])
    # a record without the window's counters: the whole total is set-up
    assert READERS[name].read(SimpleNamespace(record={})) >= SETUP[name]


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_reports_nothing_where_the_program_counts_nothing(name, monkeypatch):
    totals(monkeypatch, {"fit.stage.solve.ns": 5})  # a program without the listener
    assert READERS[name].read(SimpleNamespace(record={"counters": {}})) is None
    # one counter of the pair missing is as good as none
    totals(monkeypatch, {k: v for k, v in TOTALS.items() if not k.startswith("program.lower.")})
    if name == "setup_trace_s":
        assert READERS[name].read(SimpleNamespace(record={"counters": {}})) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_benchmark_json_and_the_metric_file_agree(name):
    bench = bench_run.load_json("BENCHMARK.json")
    entry = bench_run.named(bench["per_layer"], name, "metric")
    spec = bench_run.load_json("perfbench", "metrics", name + ".json")
    for key in ("unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
    assert entry["source"] == "program_counter" and entry["layer"] == LAYER
    assert entry["moves"] == "setup_s" and entry["better"] == "lower"
    assert "workloads" not in entry  # every cell reports setup_s, so every cell reports this
    assert "WHOLE set-up" in spec["reads"] and "`rows`" in spec["reads"]
    for cell in bench["workloads"]:
        assert name in [m["name"] for m in bench_run.cell_metrics(bench, cell["name"], "per_layer")]


def test_a_rehearsal_counts_what_its_set_up_loaded(monkeypatch, capsys):
    before = tracing.counters("program.")  # this process loaded programs before the run
    seen = []
    sound = fit_loop.run

    def run(ctx):
        sound(ctx)
        # what the harness reads after the window, in a process of the run's own:
        # the counters as if they had started with the run
        own = SimpleNamespace(record={"counters": {
            k: ctx.record["counters"].get(k, 0) + before.get(k, 0) for k in tracing.counters("program.")}})
        seen.append((ctx, {name: reader.read(own) for name, reader in READERS.items()}))

    monkeypatch.setattr(fit_loop, "run", run)
    rc = bench_run.main(["--workload", "linreg_3000.device_rows", "--seed", "3000000038",
                         "--seconds", "0.2", "--trace", "0", "--rehearse"])
    capsys.readouterr()
    (ctx, read), = seen
    assert rc == 1 and all(v is not None for v in read.values()), read
    assert read["setup_programs"] >= 1 and read["setup_trace_s"] > 0
    parts = ctx.record["setup_parts"]
    loaded = read["setup_trace_s"] + read["setup_compile_s"] + read["setup_cache_load_s"]
    assert loaded <= parts["import"] + parts["rows"] + parts["warmup"] + parts["arm"]
    # the warm-up left nothing to load inside the window
    window = ctx.record["counters"]
    assert all(window.get(f"program.{p}.calls", 0) == 0 for p in ("lower", "compile", "cache_load"))

"""The set-up's parts: what a run marks, and what the readers make of them."""

import json
from types import SimpleNamespace

import pytest

from perfbench import run as bench_run
from perfbench.drivers import fit_loop
from perfbench.metrics import (
    process_start_s,
    setup_import_s,
    setup_rows_s,
    setup_s,
    setup_warmup_s,
)

BENCH = bench_run.load_json("BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]
PARTS = ["start", "import", "rows", "warmup", "arm"]
READERS = {
    "process_start_s": (process_start_s, "start"),
    "setup_import_s": (setup_import_s, "import"),
    "setup_rows_s": (setup_rows_s, "rows"),
    "setup_warmup_s": (setup_warmup_s, "warmup"),
}
# a made-up record, in seconds: a host cell's set-up as the chip reads it
MADE_UP = {"start": 9.5, "import": 0.25, "rows": 7.75, "warmup": 18.5, "arm": 0.001}


def record(parts=MADE_UP):
    return SimpleNamespace(record={"setup_parts": dict(parts)})


@pytest.mark.parametrize("cell", CELLS)
def test_the_five_parts_of_a_rehearsal_add_up_to_process_start_to_window(cell, capsys, monkeypatch):
    seen = []
    sound = fit_loop.run
    monkeypatch.setattr(fit_loop, "run", lambda ctx: (sound(ctx), seen.append(ctx)))
    rc = bench_run.main(["--workload", cell, "--seed", "3000000028", "--seconds", "0.2",
                         "--trace", "0", "--rehearse"])
    out, err = capsys.readouterr()
    ctx, = seen
    parts = ctx.record["setup_parts"]
    assert rc == 1 and list(parts) == PARTS and all(v >= 0 for v in parts.values())
    # what setup_s read until PR 28: the first line of run.py to the window's start
    old_total = ctx.record["window"][0] - ctx.t0
    assert sum(parts.values()) == pytest.approx(old_total, abs=1e-6)
    assert setup_s.read(ctx) + process_start_s.read(ctx) == pytest.approx(old_total, abs=1e-6)
    result = json.loads(out.strip().splitlines()[-1])
    assert result["setup_parts"] == parts and list(result)[-1] == "compared"
    assert "set-up: start " in err and "process start: " in err


def test_mark_ends_a_part_where_the_next_begins():
    ctx = bench_run.Context(None, {}, {}, {}, {}, {}, {}, t0=100.0)
    assert ctx.mark("start", at=109.5) == 109.5 and ctx.mark("import", at=109.75) == 109.75
    ctx.mark("rows", at=117.5)
    assert ctx.record["setup_parts"] == {"start": 9.5, "import": 0.25, "rows": 7.75}
    assert ctx.mark("warmup") > 117.5  # the clock's own reading where none is given


def test_setup_s_holds_every_part_but_the_process_start():
    assert setup_s.read(record()) == pytest.approx(0.25 + 7.75 + 18.5 + 0.001)
    assert setup_s.read(record()) + process_start_s.read(record()) == pytest.approx(sum(MADE_UP.values()))
    # a driver of another shape marks other parts: all of them count, ``start`` never
    other = {"start": 11.0, "import": 0.3, "model": 4.0, "sweep": 2.5}
    assert setup_s.read(record(other)) == pytest.approx(6.8)
    assert setup_s.read(SimpleNamespace(record={})) is None
    assert setup_s.read(record({"start": 9.5})) is None  # nothing but the process start: no set-up read


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_part_reads_as_marked_and_none_where_it_was_not(name):
    reader, part = READERS[name]
    assert reader.read(record()) == MADE_UP[part]
    assert reader.read(SimpleNamespace(record={})) is None
    assert reader.read(SimpleNamespace(record={"setup_parts": None})) is None
    assert reader.read(record({k: v for k, v in MADE_UP.items() if k != part})) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_every_cell_reports_the_part_beside_setup_s(name):
    entry = bench_run.named(BENCH["per_layer"], name, "metric")
    assert entry["moves"] == "setup_s" and "workloads" not in entry
    for cell in CELLS:
        assert name in [m["name"] for m in bench_run.cell_metrics(BENCH, cell, "per_layer")]
        assert "setup_s" in [m["name"] for m in bench_run.cell_metrics(BENCH, cell, "end_to_end")]

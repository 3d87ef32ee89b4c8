"""A whole run with the timed path broken underneath ends ``correct`` false.

The harness's look for a chip is skipped (``--rehearse`` sizes on the CPU);
everything after it runs: rows from the seed, the window, the reference, the
comparison. ``comparison_passed`` is the rehearsal's name for what a chip run
prints as ``correct``.
"""

import json

import pytest

from perfbench import run as bench_run
from perfbench.drivers import fit_loop

BENCH = bench_run.load_json("BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]


def reference_of(cell):
    config = bench_run.named(BENCH["workloads"], cell, "workload")["config"]
    return bench_run.module_for("reference", config)


CELL_FAULTS = [(cell, fault) for cell in CELLS for fault in sorted(reference_of(cell).faults())]


def run_cell(cell, capsys):
    rc = bench_run.main(["--workload", cell, "--seed", "3000000019", "--seconds", "0.2",
                         "--trace", "0", "--rehearse"])
    last = capsys.readouterr().out.strip().splitlines()[-1]
    return rc, json.loads(last)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_compares_equal_and_never_passes_for_a_chip_run(cell, capsys):
    rc, result = run_cell(cell, capsys)
    assert rc == 1 and result["correct"] is False and result["metrics"] == {}
    assert result["device"]["platform"] == "cpu"
    assert result["rehearsal"]["comparison_passed"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "compared"


@pytest.mark.parametrize("cell,fault", CELL_FAULTS)
def test_fault_comes_out_not_correct(cell, fault, capsys, monkeypatch):
    plant = reference_of(cell).faults()[fault]
    sound = fit_loop.one_fit
    calls = []

    def broken(ctx, x):
        fit = sound(ctx, x)
        calls.append(1)
        if len(calls) == 2:  # the first fit of the window, not the warm-up
            fit["result"] = plant(ctx, x)
        return fit

    monkeypatch.setattr(fit_loop, "one_fit", broken)
    rc, result = run_cell(cell, capsys)
    assert len(calls) >= 2
    assert result["rehearsal"]["comparison_passed"] is False
    assert result["failed"] == 1

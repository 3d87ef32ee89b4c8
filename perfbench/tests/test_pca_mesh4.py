"""``pca_3000.mesh4``: rows sharded over four chips (four CPU devices here), the
fault that only a mesh has, and the collective reader on hand-made traces.
(The cell's sound run and ``pca_3000``'s faults under a whole run are cases of
``test_faults.py``.)"""

import os

# A rehearsal of a four-chip cell needs four devices in the process
# (perfbench.run sets this for a process of its own): set before jax starts.
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import json
from types import SimpleNamespace

import pytest

from perfbench import run as bench_run
from perfbench import xplane
from perfbench.drivers import fit_loop, mesh_fit_loop
from perfbench.metrics import collective_exposed_ms
from perfbench.xplane import Op, Trace


def run_cell(capsys):
    rc = bench_run.main(["--workload", "pca_3000.mesh4", "--seed", "3000000019",
                         "--seconds", "0.2", "--trace", "0", "--rehearse"])
    return rc, json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_rows_and_fits_are_on_the_mesh(capsys, monkeypatch):
    import jax

    seen = []
    sound = fit_loop.one_fit

    def spy(ctx, x):
        seen.append((len(x.sharding.device_set), ctx.config["estimator"]["class"]))
        return sound(ctx, x)

    monkeypatch.setattr(fit_loop, "one_fit", spy)
    rc, result = run_cell(capsys)
    assert rc == 1 and result["rehearsal"]["comparison_passed"] is True
    assert result["device"]["count"] == len(jax.devices()) >= 4
    assert seen and all(n == len(jax.devices()) for n, _ in seen)
    assert {c for _, c in seen} == {"perfbench.drivers.mesh_fit_loop:estimator"}
    assert mesh_fit_loop.estimator().mesh is not None


def test_one_chip_rows_comes_out_not_correct(capsys, monkeypatch):
    sound = fit_loop.one_fit
    calls = []

    def broken(ctx, x):
        fit = sound(ctx, x)
        calls.append(1)
        if len(calls) == 2:  # the first fit of the window
            fit["result"] = mesh_fit_loop.one_chip_rows(ctx, x)
        return fit

    monkeypatch.setattr(fit_loop, "one_fit", broken)
    rc, result = run_cell(capsys)
    assert len(calls) >= 2
    assert result["rehearsal"]["comparison_passed"] is False and result["failed"] == 1


def op(name, start, dur):
    return Op(f"%{name} = f32[3000,3000]{{1,0}} {name.split('.')[0]}(...)", start, dur)


def reduced(ops_by_device, fits=1):
    spans = [("fit", i * 100.0, i * 100.0 + 90.0) for i in range(fits)]
    spans.append(("model_read", fits * 100.0 - 10.0, fits * 100.0))
    return xplane.reduce(Trace(devices=ops_by_device, spans=spans), chips=len(ops_by_device))


def test_collective_exposed_is_what_no_compute_covers():
    # device 0: an all-reduce of 30 ns, 10 of them under a fusion; device 1: 25
    # ns of all-gather alone. Worst chip 25 ns over 2 fits: 12.5 ns = 1.25e-5 ms
    dev0 = [op("all-reduce.1", 10.0, 30.0), op("fusion.2", 0.0, 20.0)]
    dev1 = [op("all-gather-start.3", 50.0, 25.0), op("fusion.4", 0.0, 40.0)]
    ctx = SimpleNamespace(record={"trace": reduced({0: dev0, 1: dev1}, fits=2)})
    assert collective_exposed_ms.read(ctx) == pytest.approx(12.5e-6)


def test_no_collective_reads_nothing():
    ctx = SimpleNamespace(record={"trace": reduced({0: [op("fusion.1", 0.0, 50.0)]})})
    assert collective_exposed_ms.read(ctx) is None
    assert collective_exposed_ms.read(SimpleNamespace(record={})) is None

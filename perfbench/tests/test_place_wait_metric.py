"""The reader of the placement window's wait, on a hand-made record."""

from types import SimpleNamespace

import pytest

from perfbench.metrics import host_place_ms, host_place_wait_ms

FITS = [{"t0": 100.0, "t1": 100.4}, {"t0": 100.4, "t1": 100.8}]
COUNTERS = {
    "fit.stage.place.ns": 600_000_000, "fit.stage.place.calls": 80,
    "ingest.place.wait_ns": 500_000_000, "ingest.place.waits": 72,
}


def ctx(counters, fits=FITS):
    return SimpleNamespace(record={"counters": counters, "fits": fits})


def test_reads_the_mean_wait_per_fit_inside_the_place_stage():
    assert host_place_wait_ms.read(ctx(COUNTERS)) == pytest.approx(250.0)
    assert host_place_wait_ms.read(ctx(COUNTERS)) <= host_place_ms.read(ctx(COUNTERS))


@pytest.mark.parametrize("counters", [
    {"fit.stage.place.ns": 90_000_000, "fit.stage.place.calls": 80},  # the parent: no window
    {**COUNTERS, "ingest.place.wait_ns": 0, "ingest.place.waits": 0},  # a fit that never filled it
    {},
], ids=["parent", "never_waited", "no_counters"])
def test_none_and_never_nought_without_a_wait(counters):
    assert host_place_wait_ms.read(ctx(counters)) is None


def test_none_without_fits_or_counters_at_all():
    assert host_place_wait_ms.read(ctx(COUNTERS, fits=[])) is None
    assert host_place_wait_ms.read(SimpleNamespace(record={"fits": FITS})) is None

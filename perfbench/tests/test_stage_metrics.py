"""The readers of the program's stage counters, on a hand-made record."""

from types import SimpleNamespace

import pytest

from perfbench.metrics import (
    host_convert_ms,
    host_densify_ms,
    host_other_ms,
    host_place_ms,
    host_placed_gb,
    host_solve_ms,
)

READERS = {
    "host_densify_ms": host_densify_ms,
    "host_convert_ms": host_convert_ms,
    "host_place_ms": host_place_ms,
    "host_placed_gb": host_placed_gb,
    "host_solve_ms": host_solve_ms,
    "host_other_ms": host_other_ms,
}

# two fits of 10 s and 12 s; the counters are the window's, so per fit half
FITS = [{"t0": 100.0, "t1": 110.0}, {"t0": 110.0, "t1": 122.0}]
COUNTERS = {
    "fit.stage.densify.ns": 4_000_000_000, "fit.stage.densify.calls": 2,
    "fit.stage.densify.bytes": 19_200_000_000,
    "fit.stage.convert.ns": 6_000_000_000, "fit.stage.convert.calls": 160,
    "fit.stage.place.ns": 8_000_000_000, "fit.stage.place.calls": 160,
    "fit.stage.place.bytes": 19_200_000_000,
    "fit.stage.solve.ns": 1_000_000_000, "fit.stage.solve.calls": 170,
    "fit.stage.admit.ns": 40_000, "fit.stage.admit.calls": 2,
    "fit.admission.admitted": 2,
}


def ctx(counters, fits=FITS):
    return SimpleNamespace(record={"counters": counters, "fits": fits})


@pytest.mark.parametrize("name, want", [
    ("host_densify_ms", 2000.0), ("host_convert_ms", 3000.0), ("host_place_ms", 4000.0),
    ("host_placed_gb", 9.6), ("host_solve_ms", 500.0),
    ("host_other_ms", 11000.0 - 2000.0 - 3000.0 - 4000.0 - 500.0),
])
def test_reads_the_mean_per_fit(name, want):
    assert READERS[name].read(ctx(COUNTERS)) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(READERS))
def test_none_for_a_program_without_stage_counters(name):
    parent = {"fit.admission.admitted": 2, "serving.cache.hit": 3}
    assert READERS[name].read(ctx(parent)) is None
    assert READERS[name].read(ctx({})) is None
    assert READERS[name].read(SimpleNamespace(record={"fits": FITS})) is None


@pytest.mark.parametrize("name", sorted(set(READERS) - {"host_other_ms"}))
def test_none_and_never_nought_for_a_counter_that_did_not_move(name):
    assert READERS[name].read(ctx({k: 0 for k in COUNTERS})) is None


def test_other_takes_an_absent_stage_as_nothing_explained():
    # a fit through the funnel (KMeans): densify and place only
    funnel = {k: v for k, v in COUNTERS.items() if "convert" not in k and "solve" not in k}
    assert host_other_ms.read(ctx(funnel)) == pytest.approx(11000.0 - 2000.0 - 4000.0)
    assert host_convert_ms.read(ctx(funnel)) is None

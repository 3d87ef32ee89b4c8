"""Every control a configuration lists comes out NOT correct and the sound
fit correct, through the same limits check as a run's, at a size a test run
can hold (the chip readings at the cell's own size are in PERF.md)."""

import numpy as np
import pytest

from perfbench import control, data
from perfbench import run as bench_run

BENCH = bench_run.load_json("BENCHMARK.json")
FIRST_CELL = {}
for _cell in BENCH["workloads"]:
    FIRST_CELL.setdefault(_cell["config"], _cell["name"])


@pytest.mark.parametrize("config,seeds", [
    ("pca_3000", "5,2147483665,77"),
    ("kmeans_3000_k1000", "5,2147483665,77"),
])
def test_controls_fail_and_fit_passes(config, seeds, capsys):
    # perfbench.control exits 0 only if, on every seed, the program passes the
    # configuration's limits and every control it lists fails one of them
    rc = control.main(["--workload", FIRST_CELL[config], "--seeds", seeds, "--rehearse"])
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, lines
    assert len(lines) == 3


def test_same_seed_same_rows():
    for spec, params in [("perfbench.data:low_rank_rows", {"top_variances": [16.0, 8.0, 4.0]}),
                         ("perfbench.data:blobs", {"centers": 3})]:
        a = np.asarray(data.generate(spec, 2**31 + 5, 64, 16, params))
        b = np.asarray(data.generate(spec, 2**31 + 5, 64, 16, params))
        c = np.asarray(data.generate(spec, 5, 64, 16, params))
        assert np.array_equal(a, b) and not np.array_equal(a, c)

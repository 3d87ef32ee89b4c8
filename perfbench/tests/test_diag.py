"""``perfbench.diag``: a rehearsed run writes each fit's stamps, and the
per-fit device view is read off the recorded trace."""

import json
import os

from perfbench import diag, xplane

FIXTURE = os.path.join(os.path.dirname(__file__), "..", "fixtures", "pca_tiny_tpu_v5e.xplane.pb")


def test_a_rehearsed_run_is_stamped_fit_by_fit(tmp_path, capsys):
    out = tmp_path / "diag.json"
    rc = diag.main(["--out", str(out), "--workload", "logreg_3000.device_rows", "--seed", "7",
                    "--seconds", "0.5", "--trace", "0", "--rehearse"])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    doc = json.loads(out.read_text())
    assert rc == 1 and doc["rc"] == 1  # a rehearsal is never a pass
    assert len(doc["fits"]) == result["attempted"] >= 1
    for fit in doc["fits"]:
        assert fit["wall_ms"] == fit["fit_ms"] + fit["read_ms"] and fit["gap_ms"] >= 0
    low, mid, high = doc["wall_ms_min_med_max"]
    assert low <= mid <= high == max(f["wall_ms"] for f in doc["fits"])
    # the program's counters of the window: the passes of every fit alike
    assert doc["counters"]["logreg.lbfgs.x_passes"] == 402 * len(doc["fits"])
    assert "traced_fits" not in doc


def test_device_side_of_the_recorded_trace():
    reduced = xplane.reduce(xplane.load(FIXTURE), chips=1)
    fits = diag.device_side(reduced)
    assert len(fits) == len(reduced.fit_spans()) == 3
    for fit in fits:
        assert 0 < fit["busy_ms"] < fit["span_ms"] and fit["ops"] == 300
        assert fit["sweeps"] == 0 and fit["sweep_ms_max"] == 0.0  # a tiny fit has no operation over 1 ms
        assert 0 < fit["idle_gap_ms_max"] < fit["span_ms"] - fit["busy_ms"]

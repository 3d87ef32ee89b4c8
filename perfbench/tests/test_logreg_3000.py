"""``logreg_3000``: its work against hand counts, its generator, its controls
through ``perfbench.control`` and its four readers on hand-made records. (Its
faults, and a sound run, are cases of ``test_faults.py``, which takes every
cell of ``BENCHMARK.json``.)"""

import json
from types import SimpleNamespace

import numpy as np
import pytest

from perfbench import control, data, xplane
from perfbench.metrics import lbfgs_iters, linesearch_trials, objective_roofline, x_passes
from perfbench.work import logreg_3000
from perfbench.xplane import Op, Trace

GENERATOR = "perfbench.data_classification:classification"


def test_work_hand_count():
    # 10 rows x 4 columns: a point is one read of 40 float32 = 160 bytes and
    # 40 multiply-adds for the margins + 40 for the gradient = 160 operations;
    # fits of 2 and 4 iterations average 3, and the start is a point: 4 points
    w = logreg_3000.work(10, 4, {"max_iter": 200}, [{"n_iter": 2}, {"n_iter": 4}])
    assert w == {"fit_flops": 4 * 160, "fit_bytes": 4 * 160}
    # no fit to read: the configuration's maxIter
    assert logreg_3000.work(10, 4, {"max_iter": 200}, [])["fit_bytes"] == 201 * 160


def test_work_at_the_cell_size():
    w = logreg_3000.work(500_000, 3000, {"max_iter": 200}, [{"n_iter": 200}])
    assert w["fit_bytes"] == 201 * 6.0e9 and "gemm_flops" not in w


def test_same_seed_same_rows_and_labels():
    made = [data.generate(GENERATOR, seed, 64, 12, {"n_classes": 2}) for seed in (2**31 + 5, 2**31 + 5, 5)]
    (xa, ya), (xb, yb), (xc, yc) = ((np.asarray(x), np.asarray(y)) for x, y in made)
    assert xa.shape == (64, 12) and xa.dtype == np.float32
    assert ya.shape == (64,) and ya.dtype == np.int32 and set(ya) == {0, 1}
    assert np.array_equal(xa, xb) and np.array_equal(ya, yb)
    assert not np.array_equal(xa, xc) and not np.array_equal(ya, yc)
    # the redundant columns are combinations of the informative ones: rank 8 of 12
    assert np.linalg.matrix_rank(xa[:, :8].astype(np.float64), tol=1e-3) == 4


def test_controls_fail_and_fit_passes(capsys):
    # perfbench.control exits 0 only if, on every seed, the program passes the
    # configuration's limits and every control it lists fails one of them
    rc = control.main(["--workload", "logreg_3000.device_rows", "--seeds", "5,2147483665,77",
                       "--rehearse"])
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, lines
    assert len(lines) == 3
    for line in map(json.loads, lines):
        # at this size the fit reaches float32's floor and bfloat16 passes show
        # in every number; at the cell's size gradient_rel alone holds them
        assert line["controls"]["one_pass"]["correct"] is False
        assert line["controls"]["one_pass"]["numbers"]["gradient_rel"] > 100 * line["limits"]["gradient_rel"]
        assert line["controls"]["early_stop"]["correct"] is False
        assert line["controls"]["three_pass"]["numbers"] == line["program"]["numbers"]  # a CPU ignores "high"


def test_gradient_rel_is_the_reported_gradient_against_the_references():
    from perfbench.reference import logreg_3000 as reference

    rng = np.random.default_rng(3)
    x = rng.normal(size=(64, 5)).astype(np.float32)
    y = (rng.uniform(size=64) < 0.5).astype(np.int32)
    w, b, reg = rng.normal(size=5) * 0.1, 0.05, 1e-3
    f, gw, gb = reference.evaluate(x, y, w, b, reg)
    grad0 = float(np.linalg.norm(np.append(*reference.evaluate(x, y, np.zeros(5), 0.0, reg)[1:])))
    ref = {"x": x, "y": y, "seen": {}, "objective": f, "grad0_norm": grad0,
           "config": {"max_iter": 200, "reg_param": reg}}
    sound = {"coefficients": w, "intercept": b, "n_iter": 200, "objective": f,
             "gradient": np.append(gw, gb)[:, None]}
    assert reference.compare(sound, ref)["gradient_rel"] == 0.0
    off = dict(sound, gradient=sound["gradient"] + 3e-4 * grad0 / np.sqrt(6))
    assert reference.compare(off, ref)["gradient_rel"] == pytest.approx(3e-4)
    # a fit that reports no gradient of the right shape is not compared at all
    assert reference.compare(dict(sound, gradient=np.zeros(5)), ref)["gradient_rel"] == float("inf")


COUNTERS = {"logreg.lbfgs.iters": 600, "logreg.lbfgs.x_passes": 1206,
            "logreg.lbfgs.linesearch_trials": 655}
FITS = [{"result": {"n_iter": 200}}] * 3


@pytest.mark.parametrize("reader,want", [(lbfgs_iters, 200.0), (x_passes, 402.0),
                                         (linesearch_trials, 655 / 3)])
def test_counter_readers_give_the_mean_per_fit_and_none_without_the_counter(reader, want):
    assert reader.read(SimpleNamespace(record={"counters": COUNTERS, "fits": FITS})) == want
    parent = {"fit.stage.admit.calls": 3}  # a program without the counters
    assert reader.read(SimpleNamespace(record={"counters": parent, "fits": FITS})) is None
    assert reader.read(SimpleNamespace(record={"fits": FITS})) is None


def roofline_ctx(ops):
    # one fit of 2 iterations on 1000 rows x 8 columns: 3 points of 32,000 bytes
    trace = xplane.reduce(Trace({0: ops}, [("fit", 0, 900), ("model_read", 900, 1000)]), chips=1)
    return SimpleNamespace(
        record={"trace": trace, "fits": [{"result": {"n_iter": 2}}], "rows": 1000},
        cols=8, chips=1, config={"max_iter": 200}, cell={"config": "logreg_3000"},
        peaks={"hbm_bytes_per_s": 1e12, "bf16_flops_per_s": 1e14})


def test_objective_roofline_is_required_bytes_over_the_time_of_what_reads_the_rows():
    ops = [
        Op("%while.3 = while(...)", 0, 800),
        Op("%fusion.7 = f32[1000]{0} fusion(f32[1000,8]{1,0} %x, f32[8]{0} %d), kind=kLoop", 100, 200),
        Op("%fusion.9 = f32[8]{0} fusion(f32[1000]{0} %r, f32[1000,8]{0,1} %x), kind=kLoop", 300, 280),
        Op("%fusion.2 = f32[1000]{0} fusion(f32[1000]{0} %z, f32[1000]{0} %u), kind=kLoop", 600, 100),
    ]
    # 96,000 bytes at 1e12 bytes/s = 96 ns, over the 480 ns of the two passes
    assert objective_roofline.read(roofline_ctx(ops)) == pytest.approx(100.0 * 96 / 480)


def test_objective_roofline_reads_nothing_where_no_operation_reads_the_rows():
    ops = [Op("%fusion.2 = f32[1000]{0} fusion(f32[1000]{0} %z, f32[1000]{0} %u), kind=kLoop", 600, 100)]
    assert objective_roofline.read(roofline_ctx(ops)) is None
    no_trace = roofline_ctx(ops)
    no_trace.record["trace"] = None
    assert objective_roofline.read(no_trace) is None

"""``linreg_3000``: its work against a hand count, its generator, its reference
against hand-made moments, its controls through ``perfbench.control`` and its
reader on hand-made records. (Its faults, and a sound run of both cells that
PR 36 added, are cases of ``test_faults.py``, which takes every cell of
``BENCHMARK.json``.)"""

import json
from types import SimpleNamespace

import numpy as np
import pytest

from perfbench import control, data
from perfbench.metrics import fista_iters
from perfbench.reference import linreg_3000 as reference
from perfbench.work import linreg_3000

GENERATOR = "perfbench.data_regression:regression"
CONFIG = {"standardization": True, "fit_intercept": True, "reg_param": 0.02,
          "elastic_net_param": 0.5, "max_iter": 10}


def test_work_hand_count():
    # 10 rows x 4 columns with their labels: the (4 x 10)(10 x 5) product of the
    # rows with [X | y] is 20 entries of 10 multiply-adds = 400 operations, over
    # 50 float32 values = 200 bytes; the proximal loop counts nothing
    w = linreg_3000.work(10, 4, {"max_iter": 10}, [{"n_iter": 10}])
    assert w == {"gemm_flops": 400, "gemm_bytes": 200, "fit_flops": 400}
    assert linreg_3000.work(10, 4, {"max_iter": 10}, []) == w  # from shapes alone


def test_work_at_the_cell_size():
    w = linreg_3000.work(500_000, 3000, {}, [])
    assert w["gemm_flops"] == 2 * 500_000 * 3000 * 3001 and w["gemm_bytes"] == 4 * 500_000 * 3001


def test_same_seed_same_rows_and_labels():
    params = {"noise": 10.0, "bias": 0.0}
    made = [data.generate(GENERATOR, seed, 600, 12, params) for seed in (2**31 + 5, 2**31 + 5, 5)]
    (xa, ya), (xb, yb), (xc, yc) = ((np.asarray(x), np.asarray(y)) for x, y in made)
    assert xa.shape == (600, 12) and xa.dtype == np.float32
    assert ya.shape == (600,) and ya.dtype == np.float32
    assert np.array_equal(xa, xb) and np.array_equal(ya, yb)
    assert not np.array_equal(xa, xc) and not np.array_equal(ya, yc)
    # make_regression's law: the first d // 3 columns carry coefficients in
    # (0, 100), the rest none, and the residual is the noise
    coef, *_ = np.linalg.lstsq(xa.astype(np.float64), ya.astype(np.float64), rcond=None)
    assert np.all(coef[:4] > -5) and np.all(coef[:4] < 105) and np.max(np.abs(coef[4:])) < 3
    assert 7 < np.std(ya - xa @ coef) < 13
    quiet = np.asarray(data.generate(GENERATOR, 5, 600, 12, {"n_informative": 2})[1])
    assert np.std(quiet) > 1  # no noise, no bias: labels are the two columns' sum


def hand_rows(n=20_000, d=5, seed=3):
    rng = np.random.default_rng(seed)
    x = (rng.normal(size=(n, d)) * np.linspace(0.5, 2.0, d) + 2.0).astype(np.float32)
    y = (x @ np.array([3.0, -2.0, 0.0, 1.0, 0.0]) + 0.7 + rng.normal(size=n)).astype(np.float32)
    return x, y


def test_reference_moments_against_a_float64_two_pass():
    x, y = hand_rows()
    m = reference.moments(x, y)
    xd, yd = x.astype(np.float64), y.astype(np.float64)
    xc, yc = xd - xd.mean(axis=0), yd - yd.mean()
    want = xc.T @ xc
    scale = np.sqrt(np.outer(np.diag(want), np.diag(want)))
    assert np.max(np.abs(m["a"] - want) / scale) < 1e-6
    np.testing.assert_allclose(m["b"], xc.T @ yc, rtol=2e-6, atol=1e-2)
    assert m["yy"] == pytest.approx(yc @ yc, rel=2e-6) and m["n"] == len(y)
    np.testing.assert_allclose(m["x_mean"], xd.mean(axis=0), rtol=1e-7)
    one = reference.one_shot_moments(x, y)
    assert np.max(np.abs(one["a"] - want) / scale) < 1e-4  # the same law, summed at once


def test_reference_optimum_and_a_sound_answer_compare_clean():
    x, y = hand_rows()
    ref = reference.reference((x, y), CONFIG)
    p, m = ref["problem"], ref["moments"]
    b = ref["optimum"]
    # the optimum's sub-gradient condition, coordinate by coordinate
    g = p["q"] @ b - p["lin"]
    on = b != 0
    assert on.sum() >= 3
    np.testing.assert_allclose(g[on], -p["l1"][on] * np.sign(b[on]), atol=1e-9)
    assert np.all(np.abs(g[~on]) <= p["l1"][~on] + 1e-9)
    # evaluate's objective is the plain one over the rows, with no moment in it
    b0 = m["y_mean"] - m["x_mean"] @ b
    f, grad = reference.evaluate(ref, b, b0)
    xd, yd = x.astype(np.float64), y.astype(np.float64)
    r = yd - xd @ b - b0
    s = xd.std(axis=0, ddof=1)
    plain = r @ r / (2 * len(y)) + 0.02 * (0.5 * np.sum(s * np.abs(b)) + 0.25 * np.sum(s * s * b * b))
    assert f == pytest.approx(plain, rel=1e-6)
    np.testing.assert_allclose(grad, g, atol=2e-5)
    # what a fit of ten iterations on these moments hands back compares clean
    numbers = reference.compare(reference.solved_from(m, CONFIG), ref)
    assert abs(numbers["objective_gap"]) < 1e-6 and numbers["objective_rel"] < 1e-5
    assert numbers["gradient_rel"] < 1e-5 and numbers["n_iter"] == 10
    # and one that reports no iteration count, or a gradient of another shape, not at all
    sound = reference.solved_from(m, CONFIG)
    assert reference.compare(dict(sound, n_iter=None), ref)["coef_rel"] == float("inf")
    assert reference.compare(dict(sound, gradient=np.zeros(3)), ref)["gradient_rel"] == float("inf")
    off = dict(sound, gradient=sound["gradient"] + 3e-4 * ref["grad0_norm"] / np.sqrt(5))
    assert reference.compare(off, ref)["gradient_rel"] == pytest.approx(3e-4, rel=0.05)


def test_controls_fail_and_fit_passes(capsys):
    # perfbench.control exits 0 only if, on every seed, the program passes the
    # configuration's limits and every control it lists fails one of them
    # (those that only the chip's arithmetic shows are read and not held here)
    rc = control.main(["--workload", "linreg_3000.device_rows", "--seeds", "5,2147483665,77",
                       "--faults", "--rehearse"])
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, lines
    assert len(lines) == 3
    for line in map(json.loads, lines):
        assert line["program"]["numbers"]["n_iter"] == 10
        assert line["controls"]["early_stop"]["correct"] is False
        assert line["controls"]["early_stop"]["numbers"]["n_iter"] == 3
        assert line["controls"]["three_pass"]["numbers"] == line["program"]["numbers"]  # a CPU ignores "high"
        assert set(line["controls"]) == {"three_pass", "early_stop", "one_short", "one_shot_sum"}
        assert not any(fault["correct"] for fault in line["faults"].values())


def test_pca_device_rows_control_fails_and_fit_passes(capsys):
    rc = control.main(["--workload", "pca_3000.device_rows", "--seeds", "5,2147483665", "--rehearse"])
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, lines
    assert len(lines) == 2


def test_fista_iters_reader_gives_the_mean_per_fit_and_none_without_the_counter():
    fits = [{"result": {"n_iter": 10}}] * 4
    record = {"counters": {"linreg.fista.iters": 40, "gram.blocks": 200}, "fits": fits}
    assert fista_iters.read(SimpleNamespace(record=record)) == 10.0
    parent = {"fit.stage.admit.calls": 4}  # a program without the counter
    assert fista_iters.read(SimpleNamespace(record={"counters": parent, "fits": fits})) is None
    assert fista_iters.read(SimpleNamespace(record={"fits": fits})) is None

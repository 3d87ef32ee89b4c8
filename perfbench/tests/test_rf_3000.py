"""``rf_3000``: its work against hand counts, its controls through
``perfbench.control``, the reference's restated randomness against the
program's, and its three readers on hand-made records. (Its faults, and a sound
run, are cases of ``test_faults.py``, which takes every cell of
``BENCHMARK.json``.)"""

import json
from types import SimpleNamespace

import numpy as np
import pytest

from perfbench import control, xplane
from perfbench.metrics import forest_grow_roofline, forest_selected_gelems, forest_tree_levels
from perfbench.reference import rf_3000 as reference
from perfbench.work import rf_3000
from perfbench.xplane import Op, Trace

SMALL = {"num_trees": {"run": 2}, "max_depth": 3, "features_per_node": 4, "max_bins": 8,
         "num_classes": 2}
CELL = {"num_trees": {"run": 13}, "max_depth": 13, "features_per_node": 55, "max_bins": 128,
        "num_classes": 2}


def test_work_hand_count():
    # 2 trees x 3 levels over 10 rows of 6 columns, 4 features a node, 8 bins, 2 classes:
    # a tree-level reads 4 ids + a node id + a weight a row and writes a node id: 16 B a row
    w = rf_3000.work(10, 6, SMALL, [])
    assert w["tree_levels"] == 6 and w["selected_elems"] == 6 * 10 * 4
    assert w["fit_bytes"] == 6 * 10 * 16 and w["bin_bytes"] == 4 * 10 * 6
    # one addition a selected element and class; 7 nodes a tree x 4 x 8 candidate
    # splits x 2 classes x 9 operations; binning 3 comparisons a value
    assert w["fit_flops"] == 6 * 10 * 4 * 2 + 2 * 7 * 4 * 8 * 2 * 9 + 10 * 6 * 3
    assert "gemm_flops" not in w


def test_work_at_the_cell_size():
    w = rf_3000.work(250_000, 3000, CELL, [])
    assert w["tree_levels"] == 169
    assert w["selected_elems"] == pytest.approx(2.32375e9)
    assert w["fit_bytes"] == 169 * 250_000 * 67  # 2.83 GB: 3.5 ms at 819 GB/s
    assert w["fit_bytes"] / 819e9 == pytest.approx(3.46e-3, rel=1e-2)


def test_controls_fail_and_fit_passes(capsys):
    rc = control.main(["--workload", "rf_3000.device_rows", "--seeds", "5,2147483665", "--rehearse"])
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, lines
    for line in map(json.loads, lines):
        assert line["program"]["numbers"]["count_mismatch"] == 0
        held_by = {"shallow": "leaf_regret", "coarse_bins": "split_regret",
                   "no_bootstrap": "count_mismatch", "few_features": "subset_fault"}
        for name, number in held_by.items():
            got = line["controls"][name]
            assert got["correct"] is False
            assert got["numbers"][number] > line["limits"][number], (name, got)


def test_the_restated_randomness_is_the_programs():
    import jax

    from spark_rapids_ml_tpu.ops import trees

    k_sample, k_feat = jax.random.split(jax.random.key(7))
    w = np.asarray(trees.sample_weights(k_sample, np.arange(3), 500, 1.0, True))
    np.testing.assert_array_equal(reference.bootstrap_weights(7, 3, 500), w)
    for tree, node in ((0, 0), (2, 5), (1, 8190)):
        mine = reference.node_subset(7, tree, node, 3000, 55)
        theirs = trees.node_feature_subsets(jax.random.fold_in(k_feat, tree),
                                            np.array([node], np.int32), 3000, 55)[0]
        np.testing.assert_array_equal(mine, np.asarray(theirs))
        assert len(set(mine.tolist())) == 55


def test_best_gain_and_regret_on_a_hand_made_node():
    # four rows, one column: classes 0 0 1 1 at values 1 2 3 4; edges 1.5 2.5 3.5
    values = np.array([[1.0], [2.0], [3.0], [4.0]])
    wy = np.array([[1, 0], [1, 0], [0, 1], [0, 1]], float)
    edges = np.array([[1.5, 2.5, 3.5]])
    assert reference.best_gain_in_subset(values, edges, wy) == pytest.approx(0.5)  # the clean cut
    off = reference.split_gain(wy.sum(axis=0), wy[values[:, 0] <= 1.5].sum(axis=0))
    assert float(off) == pytest.approx(0.5 - 0.75 * (1 - (1 / 3) ** 2 - (2 / 3) ** 2))
    # weight on one side only is no split
    assert reference.split_gain(wy.sum(axis=0), wy[values[:, 0] <= 9].sum(axis=0)) == -np.inf


def _ctx(counters, fits=2, trace=None):
    record = {"fits": [{"result": {}}] * fits, "counters": counters, "rows": 250_000}
    if trace is not None:
        record["trace"] = trace
    return SimpleNamespace(record=record, cols=3000, chips=1, config=CELL,
                           cell={"config": "rf_3000"}, peaks={"hbm_bytes_per_s": 819e9})


def test_counter_readers():
    ctx = _ctx({"forest.grow.tree_levels": 338, "forest.grow.selected_elems": 2 * 2.32375e9})
    assert forest_tree_levels.read(ctx) == 169
    assert forest_selected_gelems.read(ctx) == pytest.approx(2.32375)
    # a program without the level builder (the parent) has no such counter
    assert forest_tree_levels.read(_ctx({})) is None and forest_selected_gelems.read(_ctx({})) is None
    assert forest_grow_roofline.read(_ctx({})) is None


def test_grow_roofline_leaves_the_binning_pass_out():
    s = 1e9
    ops = [Op("%sort.1 = f32[250000,3000]{1,0} sort(...)", 0.0 * s, 1.0 * s),       # binning
           Op("%fusion.7 = s32[32,128,750]{2,1,0} fusion(...)", 1.0 * s, 2.0 * s),  # growth
           Op("%fusion.9 = f32[256,2,55,128]{3,2,1,0} fusion(...)", 3.0 * s, 1.0 * s)]
    trace = xplane.reduce(Trace(devices={0: ops}, spans=[("fit", 0.0, 4.0 * s)]), chips=1)
    ctx = _ctx({"forest.grow.tree_levels": 169}, fits=1, trace=trace)
    least = 169 * 250_000 * 67 / 819e9
    assert forest_grow_roofline.read(ctx) == pytest.approx(100 * least / 3.0)

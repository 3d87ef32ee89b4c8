"""``umap_3000``: its work against a hand count, its reference against a plain
float64 one, its controls and faults through ``perfbench.control`` at the
rehearsal's size, and its readers on hand-made records. (A sound run and its
faults under a whole run are also cases of ``test_faults.py``, which takes
every cell of ``BENCHMARK.json``.)"""

import json
from types import SimpleNamespace

import numpy as np
import pytest

from perfbench import control, xplane
from perfbench import run as bench_run
from perfbench.metrics import layout_roofline, umap_epochs, umap_knn_ms
from perfbench.reference import umap_3000 as reference
from perfbench.work import umap_3000
from perfbench.xplane import Op, Trace

CONFIG = bench_run.load_json("perfbench", "configs", "umap_3000.json")


def test_work_hand_count():
    # 10 rows x 4 columns: the (10 x 4)(4 x 10) distance product is 100 entries of
    # 4 multiply-adds = 800 operations over 40 float32 = 160 bytes; an epoch of
    # 3 neighbours and 2 components reads 10 x 3 edges of 8 bytes and reads and
    # writes the 10 x 2 layout: 240 + 160 bytes
    w = umap_3000.work(10, 4, {"n_neighbors": 3, "n_components": 2, "n_epochs": 5}, [])
    assert w == {"gemm_flops": 800, "gemm_bytes": 160, "fit_flops": 800,
                 "layout_bytes": 5 * 400}


def test_work_at_the_cell_size():
    w = umap_3000.work(250_000, 3000, CONFIG, [])
    assert w["gemm_flops"] == pytest.approx(3.75e14)
    assert w["layout_bytes"] / 200 == 34e6  # 41.5 us an epoch at 819 GB/s


def blobs(n=1500, d=24, seed=5):
    rng = np.random.default_rng(seed)
    mu = rng.uniform(-10, 10, size=(3, d))
    return (mu[rng.integers(0, 3, size=n)] + rng.normal(size=(n, d))).astype(np.float32)


def test_reference_against_plain_float64():
    import jax.numpy as jnp

    x = blobs()
    config = {"n_neighbors": 7, "sample_rows": 50}
    ref = reference.reference(jnp.asarray(x), config)
    x64 = x.astype(np.float64)
    d2 = np.sum((x64[ref["sample"], None, :] - x64[None, :, :]) ** 2, axis=2)
    d2[np.arange(50), ref["sample"]] = np.inf
    np.testing.assert_allclose(ref["nearest"], np.sqrt(np.sort(d2, axis=1)[:, :7]), rtol=1e-12)
    assert np.array_equal(ref["sample"], reference.sample_of(jnp.asarray(x), 50))
    # the exact graph compares clean; its neighbours' distances read again
    full = np.sum((x64[:, None, :] - x64[None, :, :]) ** 2, axis=2)
    np.fill_diagonal(full, np.inf)
    idx = np.argsort(full, axis=1)[:, :7]
    again = reference.edge_sq_dists(jnp.asarray(x), idx)
    np.testing.assert_allclose(again, np.take_along_axis(full, idx, axis=1), rtol=1e-6)
    model = SimpleNamespace(knnIndices=idx, knnDists=np.sqrt(np.take_along_axis(full, idx, 1)))
    emb = x64[:, :2]
    got = reference.compare({"embedding": emb, "model": np.asarray(model)}, ref)
    assert got["knn_dist_rel"] == 0.0 and got["dist_rel"] < 1e-6
    # trustworthiness: sklearn's definition on the sample, ranks against every
    # row; the reference ranks by the float32 product (near ties may swap)
    assert 1.0 - got["trust_loss"] == pytest.approx(
        _trust_on_sample(x64, emb, ref["sample"], 7), abs=1e-4)


def _trust_on_sample(x, emb, sample, k):
    n = len(x)
    d = np.sum((x[sample, None, :] - x[None, :, :]) ** 2, axis=2)
    e = np.sum((emb[sample, None, :] - emb[None, :, :]) ** 2, axis=2)
    d[np.arange(len(sample)), sample] = np.inf
    e[np.arange(len(sample)), sample] = np.inf
    penalty = 0.0
    for i in range(len(sample)):
        rank = np.empty(n)
        rank[np.argsort(d[i], kind="stable")] = np.arange(1, n + 1)
        near = np.argsort(e[i], kind="stable")[:k]
        penalty += np.sum(np.maximum(rank[near] - k, 0))
    return 1.0 - 2.0 * penalty / (len(sample) * k * (2.0 * n - 3.0 * k - 1.0))


def test_a_wrong_answer_reads_over_every_limit_it_should():
    import jax.numpy as jnp

    x = blobs()
    ref = reference.reference(jnp.asarray(x), {"n_neighbors": 7, "sample_rows": 50})
    x64 = x.astype(np.float64)
    full = np.sum((x64[:, None, :] - x64[None, :, :]) ** 2, axis=2)
    np.fill_diagonal(full, np.inf)
    idx = np.argsort(full, axis=1)[:, :7]
    dist = np.sqrt(np.take_along_axis(full, idx, 1))
    far = idx.copy()
    row = full[ref["sample"][0]]
    far[ref["sample"][0], -1] = np.argmax(np.where(np.isfinite(row), row, -1.0))  # the farthest
    got = reference.compare({"embedding": x64[:, :2], "model": SimpleNamespace(
        knnIndices=far, knnDists=np.sqrt(np.take_along_axis(full, far, 1)))}, ref)
    assert got["knn_dist_rel"] > 0.1 and got["dist_rel"] < 1e-6
    off = dist * (1 + 1e-3)
    got = reference.compare({"embedding": x64[:, :2], "model": SimpleNamespace(
        knnIndices=idx, knnDists=off)}, ref)
    assert got["dist_rel"] == pytest.approx(1e-3, rel=1e-3)
    got = reference.compare({"embedding": x64[:700, :2], "model": SimpleNamespace(
        knnIndices=idx, knnDists=dist)}, ref)
    assert all(v == float("inf") for v in got.values())


def test_controls_and_faults_at_the_rehearsal_size(capsys):
    # exit 0: the program is correct on both seeds and one_pass (the listed
    # control) is not; every planted fault is not either
    rc = control.main(["--workload", "umap_3000.device_rows", "--seeds", "5,2147483665",
                       "--faults", "--rehearse"])
    lines = [json.loads(line) for line in capsys.readouterr().out.strip().splitlines()]
    assert rc == 0, lines
    for line in lines:
        assert line["program"]["correct"] and not line["controls"]["one_pass"]["correct"]
        assert not any(f["correct"] for f in line["faults"].values()), line["faults"]
        assert line["faults"]["shuffled_embedding"]["numbers"]["trust_loss"] > 0.4
        assert line["faults"]["one_list_replaced"]["numbers"]["dist_rel"] > 0.1


def counted(counters, fits=2):
    return SimpleNamespace(record={"fits": [{}] * fits, "counters": counters})


def test_stage_and_epoch_readers():
    ctx = counted({"fit.stage.knn.ns": 54e9, "umap.layout.epochs": 400})
    assert umap_knn_ms.read(ctx) == 27_000.0 and umap_epochs.read(ctx) == 200
    assert umap_knn_ms.read(counted({})) is None  # the parent counts no stage


def test_layout_roofline_reads_the_epoch_loop():
    rows, dim = 10, 2
    # the chip's text: layouts hold parentheses, the body names other shapes
    loop = Op(f"%while.7 = (s32[]{{:T(128)}}, f32[{rows},{dim}]{{1,0:T(8,128)}}, u32[2]{{0:T(128)}}) "
              "while((s32[]{:T(128)}, f32[10,2]{1,0:T(8,128)}) %tuple.3), condition=%c, body=%b",
              100.0, 1e9)
    other = Op("%while.3 = (s32[]{:T(128)}, f32[10,16]{1,0:T(8,128)}) while(f32[10,2]{1,0} %t)",
               0.0, 5e9)
    trace = Trace(devices={0: [loop, other]},
                  spans=[("fit", 0.0, 2e9), ("model_read", 2e9, 2.1e9)])
    reduced = xplane.reduce(trace, chips=1)
    ctx = SimpleNamespace(record={"trace": reduced, "fits": [{"result": {}}], "rows": rows},
                          rows=rows, cols=4, chips=1, cell={"config": "umap_3000"},
                          config={"n_neighbors": 3, "n_components": dim, "n_epochs": 200},
                          peaks={"hbm_bytes_per_s": 819e9})
    want = 100.0 * umap_3000.work(rows, 4, ctx.config, [])["layout_bytes"] / 819e9 / 1.0
    assert layout_roofline.read(ctx) == pytest.approx(want)
    ctx.record["trace"] = xplane.reduce(Trace(devices={0: [other]}, spans=trace.spans), chips=1)
    assert layout_roofline.read(ctx) is None

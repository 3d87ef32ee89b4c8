"""perfbench/work against hand counts at a small shape."""

from perfbench.work import pca_3000


def test_pca_work_hand_count():
    # 10 rows x 4 columns: the (4 x 10)(10 x 4) product is 16 entries of 10
    # multiply-adds each = 320 operations; the matrix is 40 float32 = 160 bytes
    w = pca_3000.work(10, 4, {}, [])
    assert w["gemm_flops"] == 320
    assert w["gemm_bytes"] == 160
    assert w["fit_flops"] == w["gemm_flops"]
    assert w["host_bytes"] == 160


def test_pca_work_at_the_cell_size():
    w = pca_3000.work(500_000, 3000, {}, [])
    assert w["gemm_flops"] == 9.0e12
    assert w["gemm_bytes"] == 6.0e9


def test_kmeans_work_hand_count():
    from perfbench.work import kmeans_3000_k1000

    # 10 rows x 4 columns against 3 centres: 30 distances of 4 multiply-adds
    # = 240 operations a pass; fits of 2 and 4 iterations average 3, and one
    # more pass prices the returned centres: 4 passes
    config = {"k": 3, "max_iter": 30}
    w = kmeans_3000_k1000.work(10, 4, config, [{"n_iter": 2}, {"n_iter": 4}])
    assert w["gemm_flops"] == 4 * 240
    assert w["gemm_bytes"] == 4 * 160
    assert w["fit_flops"] == w["gemm_flops"]
    # no fit to read: the configuration's maxIter
    assert kmeans_3000_k1000.work(10, 4, config, [])["gemm_flops"] == 31 * 240


def test_kmeans_work_at_the_cell_size():
    from perfbench.work import kmeans_3000_k1000

    w = kmeans_3000_k1000.work(500_000, 3000, {"k": 1000, "max_iter": 30}, [{"n_iter": 30}])
    assert w["gemm_flops"] == 31 * 3.0e12

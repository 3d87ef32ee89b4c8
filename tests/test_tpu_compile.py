"""Compile-only guards for the chip: every Pallas kernel that is on by
default on a TPU, and the fused PCA fit, compiled for a DESCRIBED (not
attached) TPU v5e at the shapes the estimators hand them.

Interpret mode cannot see what the chip's compiler refuses — a block that
is not a whole number of (8, 128) tiles, a reshape Mosaic cannot lay out,
a kernel over its VMEM limit. These compiles raise exactly what the chip
would raise, on the CPU, in about a second each, so a refused kernel fails
here before it costs chip time. A compile that passes is not a run:
``chip_smoke.py`` is the run.

Shapes are given as explicit float32 ``ShapeDtypeStruct``s and the traces
run with x64 OFF, the chip's own configuration (conftest turns it on for
the CPU oracle; under it ``jnp.argmin`` yields int64, which Mosaic refuses
and the chip never sees). jax's persistent compilation cache is off around
the compiles (a described-device executable can be written to it but not
read back).
"""

from __future__ import annotations

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest

N_KMEANS = 2_000_000


@pytest.fixture(scope="module")
def v5e():
    """One described v5e chip as a sharding, or skip where the topology
    cannot be described (no TPU compiler in this installation)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # no libtpu / unknown topology name
        pytest.skip(f"cannot describe a v5e topology here: {exc}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _as_on_the_chip():
    """x64 off (the chip's configuration) and no persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with jax.enable_x64(False):
        yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _f32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def _lloyd_as_fit_calls_it(d: int, k: int, sharding, precision: str = "highest"):
    """``lloyd_fused`` lowered exactly as ``KMeans._fit_in_memory`` calls
    it at (N_KMEANS, d) with k centres — block size, padding and the
    packed flag included."""
    from spark_rapids_ml_tpu.ops.pallas import kmeans as pk

    packed = pk.packed_feasible(d, k)
    bn = pk.auto_block_n(d, k, packed=packed)
    d_pad = d + (-d) % 8
    n_pad = N_KMEANS + (-N_KMEANS) % bn
    return packed, pk.lloyd_fused.lower(
        _f32((d_pad, n_pad), sharding), N_KMEANS, _f32((k, d), sharding),
        max_iter=20, tol=1e-4, block_n=bn, precision=precision,
        cosine=False, interpret=False, packed=packed,
    )


class TestKMeansKernels:
    def test_assign_stats_fused_d16_k100(self, v5e):
        from spark_rapids_ml_tpu.ops.pallas import kmeans as pk

        bn = pk.auto_block_n(16, 100)
        n_pad = N_KMEANS + (-N_KMEANS) % bn
        compiled = pk.assign_stats_fused.lower(
            _f32((16, n_pad), v5e), _f32((100, 16), v5e),
            block_n=bn, precision="highest", interpret=False,
        ).compile()
        assert _has_kernel(compiled)

    def test_fused_fit_route_d16_k100_high(self, v5e):
        """The whole Lloyd program around the kernel, at the 3-pass
        compensated precision (the test above compiles "highest")."""
        packed, lowered = _lloyd_as_fit_calls_it(16, 100, v5e, "high")
        assert not packed  # k=100 overflows the d=16 group slot
        assert _has_kernel(lowered.compile())

    @pytest.mark.parametrize(
        "d,k", [(16, 16), (8, 16), (32, 32), (64, 64)]
    )
    def test_packed_fit_route_compiles(self, v5e, d, k):
        """KMeans().setK(16) at d=16 — today's packed route. The chip's
        compiler refused the original kernel at every shape (a packed
        block of block_n // P = 1008 columns; a lane-splitting reshape);
        while ``packed_feasible`` offers a shape, the kernel must compile
        for it."""
        packed, lowered = _lloyd_as_fit_calls_it(d, k, v5e)
        assert packed
        assert _has_kernel(lowered.compile())

    def test_packed_block_is_lane_aligned(self):
        from spark_rapids_ml_tpu.ops.pallas import kmeans as pk

        for d, k in [(8, 4), (16, 16), (32, 32), (64, 64)]:
            p = pk._packed_geometry(d + (-d) % 8, k)[0]
            bn = pk.auto_block_n(d, k, packed=True)
            assert bn % (128 * p) == 0 and bn <= pk.auto_block_n(d, k)

    def test_compiled_packed_kernel_refuses_an_unaligned_block(self):
        """The alignment is checked where the block is chosen, with the
        reason — not left to the lowering's error."""
        from spark_rapids_ml_tpu.ops.pallas import kmeans as pk

        xt = jnp.zeros((16, 8064 * 2), jnp.float32)
        centers = jnp.zeros((16, 16), jnp.float32)
        with pytest.raises(ValueError, match="block_n % 1024 == 0"):
            pk.assign_stats_packed(xt, centers, block_n=8064, interpret=False)


class TestWideKMeansUpdate:
    """The XLA Lloyd fit at the benchmark cell's shape (500,000 x 3000
    float32, k = 1000, ``highest``): the centre update as three bf16
    passes on an exact split of the rows (ops/kmeans.py)."""

    def test_three_convolutions_with_fused_producers_and_no_row_sized_temporary(self, v5e):
        import re

        from spark_rapids_ml_tpu.ops import kmeans as km

        n, d, k = 500_000, 3000, 1000
        compiled = km.lloyd.lower(
            _f32((n, d), v5e), _f32((n,), v5e), _f32((k, d), v5e),
            max_iter=30, tol=1e-20, precision="highest",
        ).compile()
        text = compiled.as_text()
        # The update is three convolutions over the row axis, and the
        # split's roundings are kept (hi; hi, mid; hi, mid -> lo), not
        # simplified away as an astype round trip is.
        passes = re.findall(r"convolution\([^)]*\), dim_labels=fb_io->bf", text)
        assert len(passes) == 3, passes
        assert text.count(" reduce-precision(") >= 5
        # Each piece is made inside its convolution's fusion: three
        # written-out bfloat16 pieces would be 9 GB of temporaries beside
        # the 6 GB of rows (the compiler is free to choose the rows'
        # layout here, so not even its relayout copy is counted).
        stats = compiled.memory_analysis()
        assert stats.temp_size_in_bytes < 1 << 30, stats
        # The distance GEMM stays the one HIGHEST product it was.
        assert "operand_precision={highest,highest}" in text


class TestUMAPTailKernel:
    def test_tail_accumulate_50k_x_15_x_2(self, v5e):
        from spark_rapids_ml_tpu.ops.pallas import umap as pu

        n, k, dim = 50_000, 15, 2
        assert pu.plan_feasible(n, k, dim)
        idx = np.random.default_rng(0).integers(0, n, size=(n, k))
        plan, cfg = pu.build_tail_plan(idx, n, dim)
        plan_spec = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e), plan
        )
        compiled = pu.tail_accumulate.lower(
            _f32((n * k, dim), v5e), plan_spec, cfg, interpret=False
        ).compile()
        assert _has_kernel(compiled)


class TestCovariance:
    def test_centered_gram_pallas_block_at_d1024(self, v5e):
        from spark_rapids_ml_tpu.ops.pallas.covariance import centered_gram_pallas

        compiled = centered_gram_pallas.lower(
            _f32((65_536, 1024), v5e), _f32((1024,), v5e), interpret=False
        ).compile()
        assert _has_kernel(compiled)

    @pytest.mark.parametrize("backend,d", [("xla", 3000), ("pallas", 1024)])
    def test_one_pass_step_of_a_host_partition(self, v5e, backend, d):
        """``comoment_add_block`` as ``RowMatrix._covariance_gemm`` calls it
        on a 10,000-row partition (``pca_3000.host_parts``'s, and the
        kernel's width): the block's means, its Gram centred on them and
        the merge are one program that updates the donated (d, d)
        accumulator in place and writes nothing else of that size."""
        from spark_rapids_ml_tpu.ops.covariance import comoment_add_block

        state = (_f32((), v5e), _f32((d,), v5e), _f32((d,), v5e), _f32((d, d), v5e))
        compiled = comoment_add_block.lower(
            state, _f32((10_000, d), v5e), precision="highest",
            backend=backend, interpret=False,
        ).compile()
        assert _has_kernel(compiled) == (backend == "pallas")
        stats = compiled.memory_analysis()
        assert stats.alias_size_in_bytes >= d * d * 4, stats
        # the XLA route holds the four-pass Gram's bfloat16 pieces, written
        # once, and ONE (d, d) temporary, its ``S`` (the transpose is read
        # in the fusion that ends the step, PR 37), into which the merge
        # fuses; the kernel hands its (padded) Gram over once
        wide = -(-d // 128) * 128
        xla = 4 * wide * wide + 3 * 2 * 10_000 * wide
        assert stats.temp_size_in_bytes <= (xla if backend == "xla" else 3 * d * d * 4), stats

    def test_fused_pca_fit_program_1m_x_1024(self, v5e):
        """The whole device-resident fit (``_pca_fit_device``, what
        ``PCA().fit(jax_array)`` runs) at 1M x 1024: must compile and fit one chip's HBM beside its
        4.1 GB input. ``eigenSolver="topk"`` keeps this to seconds — the
        default "auto" adds the full (d, d) eigensolver, a minute of
        compile that ``chip_smoke.py`` pays on the chip instead."""
        from spark_rapids_ml_tpu.linalg.row_matrix import _pca_fit_device

        compiled = _pca_fit_device.lower(
            _f32((1_000_000, 1024), v5e), 16, center=True,
            precision="highest", eigen_solver="topk", eigen_iters=8,
        ).compile()
        mem = compiled.memory_analysis()
        assert mem.argument_size_in_bytes == 1_000_000 * 1024 * 4
        assert mem.temp_size_in_bytes < (4 << 30)  # no second copy of X

    def test_blocked_moments_with_labels_at_the_linreg_cell_size(self, v5e):
        """``normal_eq_stats`` at ``linreg_3000.device_rows``'s shape: the
        blocked sum of ``[X | y]`` beside its 6.0 GB of rows holds a block
        and the (d + 1, d + 1) accumulator, never a second copy of X (a
        reshape to blocks, or a concatenation of the label column outside
        the scan, would be one)."""
        from spark_rapids_ml_tpu.ops.linear import normal_eq_stats

        n, d = 500_000, 3000
        compiled = normal_eq_stats.lower(
            _f32((n, d), v5e), _f32((n,), v5e), None, precision="highest"
        ).compile()
        mem = compiled.memory_analysis()
        assert mem.argument_size_in_bytes >= n * d * 4
        assert mem.temp_size_in_bytes < (1 << 30)


def _gram_passes(text: str, width: int) -> list:
    """The (width, width) products over the row axis in compiled HLO."""
    import re

    return re.findall(
        rf"f32\[{width},{width}\][^\n]*? convolution\([^)]*\), dim_labels=fb_io->bf[^\n]*", text
    )


class TestBlockedGramFourPasses:
    """The two blocked-Gram cells' programs at their shape (500,000 x 3000
    float32, ``highest``): the Gram of each block is four single-pass
    bfloat16 products on an exact split (``ops/covariance.py::_gram_split4``),
    never a six-pass ``HIGHEST`` one, and the temporaries stay block-sized."""

    def _check(self, compiled, width):
        import re

        text = compiled.as_text()
        passes = _gram_passes(text, width)
        assert len(passes) == 4, passes
        assert not any("operand_precision={highest,highest}" in p for p in passes)
        # the split's roundings are kept (hi; mid), not simplified away, and
        # made once a block: the pieces are written out (180 MB), not redone
        # inside every product
        assert text.count(" reduce-precision(") == 2
        piece = rf"bf16\[10000,{width}\]\{{[^}}]*\}}"
        assert re.search(rf"\((?:{piece}, ){{2}}{piece}", text), "no written-out pieces"
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30

    def test_fused_pca_fit_at_the_pca_cell_size(self, v5e):
        from spark_rapids_ml_tpu.linalg.row_matrix import _pca_fit_device

        compiled = _pca_fit_device.lower(
            _f32((500_000, 3000), v5e), 3, center=True,
            precision="highest", eigen_solver="topk", eigen_iters=8,
        ).compile()
        self._check(compiled, 3000)

    def test_blocked_moments_at_the_linreg_cell_size(self, v5e):
        from spark_rapids_ml_tpu.ops.linear import normal_eq_stats

        compiled = normal_eq_stats.lower(
            _f32((500_000, 3000), v5e), _f32((500_000,), v5e), None, precision="highest"
        ).compile()
        self._check(compiled, 3001)

    def test_the_mesh_fit_all_reduces_one_gram(self):
        """Rows sharded over a described v5e:2x2 (``pca_3000.mesh4``'s
        program): each chip sums its 500,000 rows in blocks of four
        single-pass products, and the merge across chips all-reduces ONE
        (d, d) matrix, not ``h^T h``, ``m^T m`` and ``S`` apart."""
        import re

        from jax.experimental import topologies
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from spark_rapids_ml_tpu.linalg.row_matrix import _pca_fit_device
        from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS

        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as exc:  # no libtpu / unknown topology name
            pytest.skip(f"cannot describe a v5e topology here: {exc}")
        mesh = Mesh(np.array(topo.devices), (DATA_AXIS,))
        rows = jax.ShapeDtypeStruct(
            (2_000_000, 3000), jnp.float32, sharding=NamedSharding(mesh, P(DATA_AXIS, None))
        )
        compiled = _pca_fit_device.lower(
            rows, 3, center=True, precision="highest", eigen_solver="topk",
            eigen_iters=8, mesh=mesh,
        ).compile()
        self._check(compiled, 3000)
        text = compiled.as_text()
        reduced = re.findall(r"= (.+?) all-reduce(?:-start)?\(", text)
        assert sum(r.count("f32[3000,3000]") for r in reduced) == 1, reduced

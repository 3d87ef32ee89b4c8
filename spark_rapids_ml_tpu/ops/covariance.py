"""Covariance kernels — fused center+scale+GEMM as single XLA executables.

Reference pipeline (RapidsRowMatrix.scala:149-257): per-row JVM centering
(:176-182, HOT LOOP 1), concat to row-major B (:183-189), JNI dgemm C=BᵀB
(:195), Spark reduce of n×n partials (:201). SURVEY.md §7 flags the per-row
JVM centering as the thing that belongs *inside* the compiled program on TPU —
here centering, scaling and the rank-k update are one jitted computation that
XLA fuses; there is no host-side row loop at all.

Normalization: the reference GEMM path scales by 1/√(numCols−1) while the spr
path divides by numRows−1 (RapidsRowMatrix.scala:169 vs :240-246) — a quirk
SURVEY.md §7 says to fix, not copy. Both paths here normalize by (n_rows − 1).
PCA outputs are invariant to the scalar, so the test oracle is unaffected.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_ml_tpu.ops.linalg import _triu_indices_packed
from spark_rapids_ml_tpu.ops.precision import is_highest_matmul, make_dot, split3_bf16
from spark_rapids_ml_tpu.utils.tracing import bump_counter


def _gram_split4(b: jax.Array) -> jax.Array:
    """``b^T b`` of float32 ``b`` in FOUR single-pass bfloat16 products with
    float32 accumulation: ``h^T h + m^T m + S + S^T``, ``S = h^T m + h^T l``
    on the exact split ``(h, m, l) = split3_bf16(b)``. ``HIGHEST`` keeps six
    of the nine piece products and drops ``m^T l``, ``l^T m``, ``l^T l``;
    with both operands ``b``, its ``m^T h`` and ``l^T h`` are ``S``'s
    transposes, so these are exactly its terms in two fewer passes.

    The pieces are written out once, behind an optimization barrier: fused
    into each product's operand fetch the split is redone for every tile
    of the output, and four such products took longer than ``HIGHEST``'s
    six (PERF.md section 6, PR 37)."""
    h, m, lo = jax.lax.optimization_barrier(split3_bf16(b))

    def one_pass(u, v):
        return jnp.matmul(u.T, v, preferred_element_type=jnp.float32)

    s = one_pass(h, m) + one_pass(h, lo)
    return one_pass(h, h) + (one_pass(m, m) + (s + s.T))


@partial(jax.jit, static_argnames=("precision",))
def centered_gram(x: jax.Array, mean: jax.Array, precision: str = "highest") -> jax.Array:
    """(x - mean)^T (x - mean) of ONE block of rows: the kernel inside the
    co-moment step (:func:`comoment_add_block` for a host partition,
    :func:`comoment_resident` for a block of resident rows), which calls it
    on the block's OWN means.

    One float32 contraction over all the rows of a large matrix reads low
    on the diagonal on the chip's matrix unit (PERF.md section 6, PR 24:
    -2.35e-5 at 500,000 rows, +1.1e-7 in blocks of 10,000), so no fit hands
    this more than :data:`GRAM_BLOCK_ROWS` resident rows at once; a host
    partition is as tall as its caller made it.

    The product adapts to what it sees at trace time. float32 rows under
    the full-precision policy (``f32`` / ``highest``): the symmetric
    product takes four bf16 passes (:func:`_gram_split4`), not the six
    ``HIGHEST`` spends. Anything else (float64 rows, ``high``, ``bf16x3``,
    ``bf16``, ``default``, test modes): ``make_dot(precision)(b.T, b)`` as
    before, bit for bit. Counters ``gram.split4`` / ``gram.matmul`` count
    the traces of this kernel with each.
    """
    b = x - mean
    dot = make_dot(precision)
    if b.dtype == jnp.float32 and is_highest_matmul(dot):
        bump_counter("gram.split4")
        return _gram_split4(b)
    bump_counter("gram.matmul")
    return dot(b.T, b)


def mean_and_covariance(x: jax.Array, precision: str = "highest"):
    """Single-device path: returns (column means, covariance) of resident
    rows, summed in blocks (:func:`comoment_resident`).

    Covariance normalized by (n − 1), matching the spr/treeAggregate path
    (RapidsRowMatrix.scala:240-246) — the statistically correct sample
    covariance.
    """
    count, mean, mean_lo, m = comoment_resident(jnp.asarray(x), precision=precision)
    return mean + mean_lo, m / (count - 1)


def covariance(x: jax.Array, precision: str = "highest") -> jax.Array:
    return mean_and_covariance(x, precision=precision)[1]


@jax.jit
def centered_gram_packed(x: jax.Array, mean: jax.Array) -> jax.Array:
    """Packed-upper-triangular centered Gram — the spr/treeAggregate path.

    Surface parity with the reference's packed accumulation
    (RapidsRowMatrix.scala:207-233, layout of cublasDspr FILL_MODE_UPPER).
    Computed as a full Gram then packed: on TPU a dense MXU matmul beats
    n_rows sequential rank-1 updates by orders of magnitude, so the packed
    layout is kept only as the aggregation/wire format (n ≤ 65535 constraint
    inherited from the layout, RapidsRowMatrix.scala:66-68).
    """
    full = centered_gram(x, mean)
    rows, cols = _triu_indices_packed(x.shape[1])
    return full[rows, cols]


def shifted_block_scan(blocks, center: bool, gram_fn, min_rows: int = 2):
    """Shared scaffold of the one-pass shifted covariance accumulations
    (this module's fp32/HIGHEST path and ops.doubledouble's dd path — ONE
    home for the streaming algebra).

    The exact mean is unknown until the stream ends, so blocks are centered
    on the FIRST block's column means (exact host-fp64 subtract — the
    shifted-accumulation scheme of the native Kahan runtime,
    native/src/tpuml_host.cpp, there for the reference's streamed
    ``mapPartitions`` contract, RapidsRowMatrix.scala:170); ``gram_fn``
    maps each shifted host block to its Gram contribution. Returns
    ``(shift, gram, s, n)`` — finish with :func:`finalize_shifted_gram`.
    """
    from spark_rapids_ml_tpu.core.data import _block_to_dense
    from spark_rapids_ml_tpu.core.serving import prefetch_blocks

    shift = gram = s = None
    n = 0
    # Double-buffered at the densify level: block k+1's host decode
    # (parquet batch → ndarray) overlaps block k's Gram program. The
    # shift itself comes from the FIRST block, so centering and upload
    # stay in the loop — values and order are bit-identical.
    for b in prefetch_blocks(blocks, _block_to_dense):
        if b.shape[0] == 0:
            continue
        if shift is None:
            shift = b.mean(axis=0) if center else np.zeros(b.shape[1])
        bs = b - shift
        g = gram_fn(bs)
        gram = g if gram is None else gram + g
        sb = bs.sum(axis=0)
        s = sb if s is None else s + sb
        n += b.shape[0]
    if n < min_rows:
        # min_rows=0 callers (per-process partial scans that merge across
        # processes) accept empty results — shift/gram/s are None then.
        raise ValueError(f"need at least 2 rows to compute a covariance, got {n}")
    return shift, gram, s, n


def finalize_shifted_gram(shift, gram, s, n, center: bool):
    """Recover (mean, cov, n) from a shifted scan: the closed-form
    correction ``Σx̃ᵀx̃ − n·δδᵀ`` (δ = mean of shifted values) yields the
    true centered Gram; with ``center=False`` the shift is identically zero
    so the accumulated Gram already IS the raw second moment. Cov is
    normalized by (n − 1)."""
    delta = s / n
    mean = shift + delta
    gram = np.asarray(gram, dtype=np.float64)
    if center:
        gram = gram - n * np.outer(delta, delta)
    return mean, gram / (n - 1), n


def streaming_mean_and_covariance(
    blocks, center: bool = True, dtype=None, precision: str = "highest"
):
    """ONE-pass covariance over an iterable of host blocks — the
    constant-memory fit path (each block visited exactly once, device
    memory bounded by one block + the (d, d) accumulator). Shifted Gram
    accumulates on the accelerator; returns host fp64 ``(mean, cov, n)``.
    """
    if dtype is None:
        dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32

    def gram_fn(bs):
        return centered_gram(
            jnp.asarray(bs, dtype=dtype),
            jnp.zeros(bs.shape[1], dtype=dtype),
            precision=precision,
        )

    return finalize_shifted_gram(*shifted_block_scan(blocks, center, gram_fn), center)


@lru_cache(maxsize=None)
def _sharded_block_gram(mesh, precision: str):
    """Cached jitted program: Gram of a row-sharded block with the
    replicated (d, d) result — XLA inserts one psum over the data axis
    per block (the cross-chip reduce of the streamed mesh covariance)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    dot = make_dot(precision)

    @partial(jax.jit, out_shardings=NamedSharding(mesh, P()))
    def gram(xs):
        return dot(xs.T, xs)

    return gram


def streaming_mean_and_covariance_mesh(
    blocks, mesh, center: bool = True, dtype=None, precision: str = "highest"
):
    """ONE-pass covariance over streamed host blocks, each block
    row-sharded over the mesh data axis — the streamed deployment loop
    (no cell yet: ROADMAP.md Reach 5): stream from disk, shard each block over the
    chips, accumulate the replicated (d, d) Gram on device with one psum
    per block riding ICI. Host and per-device memory stay bounded by one
    block; the same shifted-accumulation algebra as the single-device
    streaming path. Returns host fp64 ``(mean, cov, n)``.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS

    if jax.process_count() > 1:
        raise ValueError(
            "this single-process sharded-block path has a multi-process "
            "sibling: parallel.distributed.streaming_covariance_process_local "
            "(each process streams its LOCAL blocks; RowMatrix routes there "
            "automatically)"
        )
    if dtype is None:
        dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    dp = int(mesh.shape[DATA_AXIS])
    x_sharding = NamedSharding(mesh, P(DATA_AXIS, None))
    device_gram = _sharded_block_gram(mesh, precision)

    def gram_fn(bs):
        # Pad rows to the data-axis multiple with zeros — zero rows
        # contribute exactly nothing to the Gram (the caller's column sums
        # use the unpadded block).
        pad = (-bs.shape[0]) % dp
        if pad:
            # Match dtype: a default-f64 zeros block would upcast (and
            # copy) the whole concatenated block.
            bs = np.concatenate([bs, np.zeros((pad, bs.shape[1]), dtype=bs.dtype)])
        xs = jax.device_put(bs.astype(np.dtype(dtype), copy=False), x_sharding)
        return device_gram(xs)

    # One home for the streaming algebra: shifted_block_scan.
    return finalize_shifted_gram(*shifted_block_scan(blocks, center, gram_fn), center)


def welford_init(d: int, dtype=jnp.float64) -> tuple:
    """(count, mean, M2) accumulator for streaming column stats.

    The reference's mean pass is mllib ``Statistics.colStats``
    (RapidsRowMatrix.scala:156), a Welford-style treeAggregate. These three
    functions reproduce that contract for partitioned/distributed input.
    """
    return (
        jnp.zeros((), dtype=dtype),
        jnp.zeros((d,), dtype=dtype),
        jnp.zeros((d,), dtype=dtype),
    )


@jax.jit
def welford_add_block(state: tuple, x: jax.Array) -> tuple:
    count, mean, m2 = state
    n_b = x.shape[0]
    if n_b == 0:  # static shape: an empty partition contributes nothing
        return state
    mean_b = jnp.mean(x, axis=0)
    m2_b = jnp.sum((x - mean_b) ** 2, axis=0)
    new_count = count + n_b
    delta = mean_b - mean
    new_mean = mean + delta * (n_b / new_count)
    new_m2 = m2 + m2_b + delta**2 * (count * n_b / new_count)
    return (new_count, new_mean, new_m2)


@jax.jit
def welford_merge(a: tuple, b: tuple) -> tuple:
    count_a, mean_a, m2_a = a
    count_b, mean_b, m2_b = b
    count = count_a + count_b
    safe = jnp.maximum(count, 1)
    delta = mean_b - mean_a
    mean = mean_a + delta * (count_b / safe)
    m2 = m2_a + m2_b + delta**2 * (count_a * count_b / safe)
    return (count, mean, m2)


def comoment_init(d: int, dtype=jnp.float64) -> tuple:
    """(count, mean (d,), mean_lo (d,), M (d, d)) accumulator: the
    ``welford_*`` three with a MATRIX second moment, ``M = sum (x - m)^T
    (x - m)`` over the rows seen, ``m = mean + mean_lo`` their column
    means. Column means and the centred Gram of host partitions in ONE
    pass, each partition placed once (``linalg/row_matrix.py``'s GEMM
    route); ``M / (count - 1)`` is the covariance.

    ``mean_lo`` is what rounding took from ``mean`` (a few units in its
    last place at most). A pairwise merge feeds the means' error into
    ``M`` at first order, ``|column mean| / spread`` roundings where the
    two-pass form (Gram centred on the finished mean) has them squared;
    carried in two pieces the means lose nothing, and float32 columns a
    thousand spreads off zero read as they do in two passes."""
    return (
        jnp.zeros((), dtype=dtype),
        jnp.zeros((d,), dtype=dtype),
        jnp.zeros((d,), dtype=dtype),
        jnp.zeros((d, d), dtype=dtype),
    )


def _merge_means(a: tuple, b: tuple) -> tuple:
    """The ``(count, mean, mean_lo)`` part of :func:`comoment_merge`, and
    the ``delta`` of the means and ``weight`` of ``b`` its ``M`` needs."""
    count_a, mean_a, lo_a = a
    count_b, mean_b, lo_b = b
    count = count_a + count_b
    # counts are weight sums under ``weightCol``: any positive total divides
    weight = count_b / jnp.where(count > 0, count, 1)
    # Means that lie close together against their size (where their
    # rounding matters) differ by an exact float; the trailing pieces ride
    # along, and what the new leading piece rounds off joins them.
    delta_hi, delta_lo = mean_b - mean_a, lo_b - lo_a
    shift = delta_hi * weight
    mean = mean_a + shift
    lo = lo_a + delta_lo * weight + (shift - (mean - mean_a))
    return count, mean, lo, delta_hi + delta_lo, weight


@jax.jit
def comoment_merge(a: tuple, b: tuple) -> tuple:
    """Chan's pairwise merge of two co-moment states. Exact algebra, no
    approximation of the two-pass form: ``sum_b (X_b - m)^T (X_b - m) =
    sum_b [(X_b - m_b)^T (X_b - m_b) + n_b (m_b - m)(m_b - m)^T]``."""
    count, mean, lo, delta, weight = _merge_means(a[:3], b[:3])
    m = a[3] + b[3] + jnp.outer(delta, delta) * (a[0] * weight)
    return (count, mean, lo, m)


def _block_comoments(x, weights, precision, backend="xla", interpret=False, center=True):
    """The co-moment state ``(count, mean, mean_lo, M)`` of ONE block of
    rows alone: its own column means in two pieces and its Gram centred on
    them (:func:`centered_gram`, or the Pallas kernel under
    ``backend="pallas"``). ``weights`` (n_b,) or None: a per-row weight of
    the block (``weightCol``; nought for a padding row), so counts are
    weight sums and means and Gram weighted ones. ``center=False``: the
    block's raw second moment about zero, the means left at nought."""
    n_b, d = x.shape
    if not center:
        zero = jnp.zeros((d,), dtype=x.dtype)
        rows = x if weights is None else x * weights[:, None]
        count = n_b if weights is None else jnp.sum(weights)
        return count, zero, zero, make_dot(precision)(rows.T, x)
    if weights is None:
        count = n_b
        # The division INSIDE the sum, so that the means leave a reduction
        # as one rounded array: as ``sum / n_b`` the compiler recomputes the
        # division in each consumer's fusion, where a fused multiply-add
        # (XLA:CPU) skips its rounding and the uses disagree by it.
        mean_b = jnp.sum(x * (1.0 / n_b), axis=0)
        # what that rounding left: the rows' mean is mean_b + lo_b
        lo_b = jnp.sum((x - mean_b) * (1.0 / n_b), axis=0)
        if backend == "pallas":
            from spark_rapids_ml_tpu.ops.pallas.covariance import centered_gram_pallas

            m_b = centered_gram_pallas(x, mean_b, interpret=interpret)
        else:
            m_b = centered_gram(x, mean_b, precision=precision)
    else:
        count = jnp.sum(weights)
        # a block of padding alone weighs nothing and leaves the state as it is
        share = (weights * jnp.where(count > 0, 1.0 / count, 0.0))[:, None]
        mean_b = jnp.sum(x * share, axis=0)
        lo_b = jnp.sum((x - mean_b) * share, axis=0)
        b = x - mean_b
        m_b = make_dot(precision)((b * weights[:, None]).T, b)
    return count, mean_b, lo_b, m_b - jnp.outer(lo_b, lo_b) * count


@partial(
    jax.jit,
    static_argnames=("precision", "backend", "interpret"),
    donate_argnums=(0,),
)
def comoment_add_block(
    state: tuple,
    x: jax.Array,
    precision: str = "highest",
    backend: str = "xla",
    interpret: bool = False,
) -> tuple:
    """One partition into the state, one program: the block's own column
    means, its Gram centred on THEM, then :func:`comoment_merge`. Centring
    on the block's mean, not on the first block's as
    :func:`shifted_block_scan` does, leaves no ``n * delta delta^T`` to
    cancel at the end when partitions are sorted or clustered. ``state`` is
    donated: the (d, d) accumulator is updated in place."""
    n_b = x.shape[0]
    if n_b == 0:  # static shape: an empty partition contributes nothing
        return state
    count, mean_b, lo_b, m_b = _block_comoments(x, None, precision, backend, interpret)
    return comoment_merge(state, (jnp.asarray(count, state[0].dtype), mean_b, lo_b, m_b))


#: Rows of a resident matrix summed in one float32 contraction. The size
#: with chip readings (PERF.md sections 5 and 6: a step of 10,000 x 3000
#: rows is 6.84 ms and reads +1.1e-7 on the Gram's diagonal where one
#: contraction over 500,000 rows reads -2.35e-5).
GRAM_BLOCK_ROWS = 10_000


def count_resident_blocks(n: int) -> None:
    """``gram.blocks`` / ``gram.rows``: what one :func:`comoment_resident`
    over ``n`` rows adds up, from the shape, at dispatch (never read back)."""
    bump_counter("gram.blocks", -(-n // GRAM_BLOCK_ROWS))
    bump_counter("gram.rows", n)


@partial(jax.jit, static_argnames=("precision", "center"))
def comoment_resident(
    x: jax.Array,
    y: jax.Array | None = None,
    weights: jax.Array | None = None,
    precision: str = "highest",
    center: bool = True,
) -> tuple:
    """The co-moment state ``(count, mean, mean_lo, M)`` of rows that are
    RESIDENT on one device, one program: a ``lax.scan`` over blocks of
    :data:`GRAM_BLOCK_ROWS` rows whose body is the step
    :func:`comoment_add_block` applies to a host partition (the block's own
    means, its Gram centred on them at ``precision``, Chan's merge), the
    last, short block a static remainder after the scan. Every fit on
    resident rows sums through here: the fused PCA fit and ``RowMatrix``'s
    resident route (``linalg/row_matrix.py``), and linear regression's
    sufficient statistics (``ops/linear.py::normal_eq_stats``).

    ``y`` (n,): a label column riding along as column ``d`` of each block,
    so the state is that of ``[x | y]``: ``M[:d, d]`` is ``Xc^T yc``,
    ``M[d, d]`` is ``yc^T yc`` and ``mean[d]`` the labels' mean, by the
    same step and the same merge. ``weights`` (n,): per-row weights (see
    :func:`_block_comoments`). ``center=False``: the raw second moment
    about zero, in blocks, the means left at nought.

    Blocks are sliced in place (a reshape to (blocks, rows, d) would copy
    all the rows)."""
    n, d = x.shape
    width = d if y is None else d + 1
    state = comoment_init(width, dtype=x.dtype)
    if n == 0:
        return state
    step = min(n, GRAM_BLOCK_ROWS)

    def add(state, lo, rows):
        def take(a):
            return jax.lax.dynamic_slice_in_dim(a, lo, rows, axis=0)

        blk = take(x)
        if y is not None:
            blk = jnp.concatenate([blk, take(y).astype(x.dtype)[:, None]], axis=1)
        w = None if weights is None else take(weights).astype(x.dtype)
        count, *rest = _block_comoments(blk, w, precision, center=center)
        return comoment_merge(state, (jnp.asarray(count, x.dtype), *rest))

    state, _ = jax.lax.scan(
        lambda s, i: (add(s, i * step, step), None), state, jnp.arange(n // step)
    )
    if n % step:
        state = add(state, n - n % step, n % step)
    return state


@lru_cache(maxsize=None)
def _comoment_sharded_fn(mesh, precision: str, center: bool):
    """The jitted ``shard_map`` program of :func:`comoment_sharded` for one
    (mesh, policy) — cached, as jit's cache is keyed on the function."""
    from jax.sharding import PartitionSpec as P

    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS
    from spark_rapids_ml_tpu.utils.compat import shard_map

    chips = int(mesh.shape[DATA_AXIS])

    def local(x):
        # this chip's rows, summed in blocks as on one device
        count, mean, lo, m = comoment_resident(x, precision=precision, center=center)
        count = jnp.asarray(count, x.dtype)
        counts, means, los = (
            jax.lax.all_gather(a, DATA_AXIS) for a in (count, mean, lo)
        )
        # every chip folds the chips' (count, mean, mean_lo) alike, in
        # comoment_merge's order and algebra
        merged = (counts[0], means[0], los[0])
        for c in range(1, chips):
            merged = _merge_means(merged, (counts[c], means[c], los[c]))[:3]
        # this chip's M about the merged means, then ONE (d, d) all-reduce:
        # the two-pass identity that comoment_merge applies pair by pair
        delta = (mean - merged[1]) + (lo - merged[2])
        m = m + jnp.outer(delta, delta) * count
        return (*merged, jax.lax.psum(m, DATA_AXIS))

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(DATA_AXIS, None),),
        out_specs=(P(), P(), P(), P()),
        # the merged state is the same on every chip by construction
        check_vma=False,
    )
    return jax.jit(fn)


def comoment_sharded(
    x: jax.Array, mesh, precision: str = "highest", center: bool = True
) -> tuple:
    """The co-moment state ``(count, mean, mean_lo, M)`` of rows SHARDED
    over ``mesh``'s data axis, replicated: each chip sums its own rows in
    blocks of :data:`GRAM_BLOCK_ROWS` (:func:`comoment_resident` under
    ``shard_map``; a ``lax.scan`` over a sharded axis would gather every
    block), then one merge across chips: the chips' counts and means
    gathered (d floats a chip) and their ``M`` all-reduced ONCE about the
    merged means. Rows must divide the data axis (a shard per chip)."""
    return _comoment_sharded_fn(mesh, precision, center)(x)


def count_sharded_blocks(n: int, chips: int) -> None:
    """:func:`count_resident_blocks` of :func:`comoment_sharded`: every
    chip's blocks of its own ``n / chips`` rows."""
    bump_counter("gram.blocks", chips * -(-(n // chips) // GRAM_BLOCK_ROWS))
    bump_counter("gram.rows", n)

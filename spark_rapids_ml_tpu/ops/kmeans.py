"""KMeans kernels — Lloyd iterations as MXU matmuls.

Beyond-PCA capability (RAFT kmeans -> XLA; the benchmark cell is
kmeans_3000_k1000.device_rows, PERF.md). The reference repo itself has no kmeans; the
RAPIDS family's implementation is RAFT's fused distance kernel + cuBLAS. The
TPU formulation keeps everything on the MXU:

  - assignment: pairwise squared distances via the expansion
    ||x||^2 - 2 x C^T + ||c||^2 — one (n,d)x(d,k) matmul, no materialized
    (n,k,d) intermediate;
  - update: cluster sums as one_hot(labels)^T X — a (k,n)x(n,d) matmul —
    so the "scatter-add" is also a systolic-array op. A one-hot matrix is
    exact in ONE bfloat16 piece, so for float32 rows at full precision the
    product is three single-pass bf16 matmuls on an exact three-piece
    split of the rows (hi + mid + lo == x), accumulated in float32: the
    sums a HIGHEST matmul gives, at half its six passes;
  - the whole fit is ONE jitted lax.while_loop (movement tolerance + max
    iterations), compiler-friendly static shapes throughout;
  - empty clusters keep their previous center (Spark/RAFT behavior);
  - masked rows (mask=0) support padding for sharded execution: a padded
    row contributes to no cluster and no cost.

Distributed: row-shard x/mask over a mesh data axis and jit with replicated
out-shardings — XLA inserts psum for the segment sums/counts/cost (see
tests/test_kmeans.py::TestDistributed).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_ml_tpu.ops.precision import (
    as_dot,
    is_highest_matmul,
    make_dot,
    split3_bf16,
)
from spark_rapids_ml_tpu.utils.tracing import bump_counter


def _sq_dists(x, centers, x2, dot):
    """(n, k) squared euclidean distances via the Gram expansion.
    ``dot`` is the policy-resolved matmul (ops.precision.make_dot)."""
    c2 = jnp.sum(centers * centers, axis=1)
    xc = dot(x, centers.T)
    return jnp.maximum(x2[:, None] - 2.0 * xc + c2[None, :], 0.0)


@partial(jax.jit, static_argnames=("precision",))
def assign_clusters(x, centers, precision: str = "highest"):
    """Labels + per-row squared distance to the nearest center."""
    dot = make_dot(precision)
    x2 = jnp.sum(x * x, axis=1)
    d2 = _sq_dists(x, centers, x2, dot)
    labels = jnp.argmin(d2, axis=1)
    return labels, jnp.take_along_axis(d2, labels[:, None], axis=1)[:, 0]


def _onehot_sums_split3(labels, k, mb, xb):
    """Weighted cluster sums ``(k, d)`` and counts ``(k,)`` of float32
    rows in THREE single-pass bfloat16 products. The one-hot of the labels
    on the mask's support is 0/1, exact in one bfloat16 piece; the
    weighted rows split exactly into three (``split3_bf16``), and each
    product accumulates in float32: the same sums a ``HIGHEST`` matmul
    gives (to rounding order) at half its six passes."""
    one_hot = jax.nn.one_hot(labels, k, dtype=jnp.bool_) & (mb > 0)[:, None]
    one_pass = partial(
        jnp.matmul, one_hot.T.astype(jnp.bfloat16), preferred_element_type=jnp.float32
    )
    hi, mid, lo = split3_bf16(xb * mb[:, None])
    sums = (one_pass(hi) + one_pass(mid)) + one_pass(lo)
    counts = jnp.sum(jnp.where(one_hot, mb[:, None], 0.0), axis=0)
    return sums, counts


def _assign_and_accumulate(xb, mb, x2b, centers, k, dot):
    """Block-local assignment + sufficient stats: (sums (k,d), counts (k),
    cost) for one row block — everything stays block-sized, so XLA fuses
    the distance GEMM, argmin, and one-hot matmul without ever writing an
    (n, k) array to HBM.

    The update adapts to what it can see at trace time. float32 rows under
    the full-precision policy (``f32`` / ``highest``): the one-hot operand
    is exact in bfloat16, so the sums take three bf16 passes
    (:func:`_onehot_sums_split3`), not the six ``HIGHEST`` spends, three
    of them on zero pieces. Anything else (float64 rows, ``high``,
    ``bf16x3``, ``bf16``, ``default``, test modes): ``dot(one_hot.T, xb)``
    as before, bit for bit. Counters ``kmeans.update.split3`` /
    ``kmeans.update.matmul`` count the programs TRACED with each."""
    d2 = _sq_dists(xb, centers, x2b, dot)
    labels = jnp.argmin(d2, axis=1)
    min_d2 = jnp.min(d2, axis=1)
    if xb.dtype == jnp.float32 and is_highest_matmul(dot):
        bump_counter("kmeans.update.split3")
        sums, counts = _onehot_sums_split3(labels, k, mb, xb)
    else:
        bump_counter("kmeans.update.matmul")
        one_hot = jax.nn.one_hot(labels, k, dtype=xb.dtype) * mb[:, None]
        sums = dot(one_hot.T, xb)  # (k, d) on MXU
        counts = jnp.sum(one_hot, axis=0)
    cost = jnp.sum(min_d2 * mb)
    return sums, counts, cost


def lloyd_step(x, mask, centers, x2, dot, cosine: bool = False,
               block_rows: int | None = None):
    """One Lloyd iteration. Returns (new_centers, cost).

    ``dot`` is the policy matmul (ops.precision.make_dot); legacy
    spellings (a mode string or a bare ``lax.Precision``) coerce.

    ``cosine``: renormalize updated centers to unit norm (Spark's
    CosineDistanceMeasure.updateClusterCenter) so assignments stay true
    cosine argmins given unit-normalized input rows.

    ``block_rows``: stream rows through a ``lax.scan`` in fixed blocks.
    The unblocked step materializes two (n, k) arrays per iteration —
    ~2·n·k·4 bytes of HBM write+read traffic that dominates the wall clock
    once n·k outgrows the caches; the blocked step's per-iteration traffic
    is one read of x. Rows must already be padded (mask=0) to a multiple
    of ``block_rows`` by the caller-facing :func:`lloyd`.
    """
    dot = as_dot(dot)
    k = centers.shape[0]
    if block_rows is None or x.shape[0] <= block_rows:
        sums, counts, cost = _assign_and_accumulate(x, mask, x2, centers, k, dot)
    else:
        nb = x.shape[0] // block_rows

        def body(carry, blk):
            s, c, j = carry
            xb, mb, x2b = blk
            sb, cb, jb = _assign_and_accumulate(xb, mb, x2b, centers, k, dot)
            return (s + sb, c + cb, j + jb), None

        init = (
            jnp.zeros((k, x.shape[1]), x.dtype),
            jnp.zeros((k,), x.dtype),
            jnp.asarray(0.0, x.dtype),
        )
        (sums, counts, cost), _ = jax.lax.scan(
            body,
            init,
            (
                x.reshape(nb, block_rows, -1),
                mask.reshape(nb, block_rows),
                x2.reshape(nb, block_rows),
            ),
        )
    new_centers = jnp.where(
        counts[:, None] > 0, sums / jnp.maximum(counts, 1.0)[:, None], centers
    )
    if cosine:
        new_centers = normalize_rows(new_centers)
    return new_centers, cost


def _auto_block_rows(n: int, k: int, data_shards: int, block_rows):
    """Resolve ``block_rows=None`` — shared by the monolithic
    :func:`lloyd` and the segmented :func:`lloyd_resumable` so both
    pick the identical blocking (a prerequisite for bit-identity).

    With ``TPUML_AUTOTUNE=on`` the block is sized from MEASURED HBM
    headroom instead of the static 9 GB guess. Inside the jitted
    :func:`lloyd` this resolves at trace time, so a tuned value freezes
    into the trace keyed on ``block_rows=None`` — stale-but-correct if
    the tune store moves mid-process; ``lloyd_resumable`` re-resolves on
    every fit. Off is the static heuristic bit-for-bit."""
    if block_rows is not None:
        return block_rows
    from spark_rapids_ml_tpu.observability import autotune as _autotune

    tuner = _autotune.active()
    if tuner is not None:
        tuned = tuner.recommend_kmeans_block_rows(n, k, data_shards)
        if tuned is not None:
            return tuned
    # Per-device (n, k) fp32 temporary vs the HBM budget.
    if 4 * n * k // max(data_shards, 1) > 9_000_000_000:
        # Block sized so block*k*4B stays ~1 GB (no larger floor: a
        # floor above this budget would reintroduce the OOM for big k).
        return max(8, (250_000_000 // max(k, 1) // 8) * 8)
    return n + 1  # unblocked


@partial(
    jax.jit,
    static_argnames=("max_iter", "precision", "cosine", "block_rows", "data_shards"),
)
def lloyd(
    x: jax.Array,
    mask: jax.Array,
    init_centers: jax.Array,
    max_iter: int = 20,
    tol: float = 1e-4,
    precision: str = "highest",
    cosine: bool = False,
    block_rows: Optional[int] = None,
    data_shards: int = 1,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Full Lloyd fit: returns (centers, cost, n_iters).

    Convergence criterion matches Spark ML KMeans: stop when no center moves
    more than ``tol`` (euclidean), or at ``max_iter``. With ``cosine``,
    centers stay unit-normalized every iteration (input rows must already be
    unit-normalized), so the returned cost is the cosine-distance potential.

    ``block_rows``: None = auto. The unblocked step is taken as the fast
    path because the distance reduction fuses into the GEMM epilogue and a
    scan only adds sequential dependencies: a choice that predates the
    chip (the cell kmeans_3000_k1000.device_rows runs unblocked; blocked
    against it is not measured: ROADMAP.md Design 13 / Design 14).
    Blocking exists for MEMORY: once the
    (n, k) one-hot temporary approaches HBM capacity (~9 GB here), rows
    stream through a scan in blocks sized to ~1 GB of temporaries.

    ``data_shards``: number of mesh data-axis shards the rows are spread
    over (1 = single device). The auto threshold compares the PER-DEVICE
    (n/shards, k) temporary against HBM — a row-sharded multi-chip fit must
    not fall onto the sequential blocked path dp times too early.
    """
    dot = make_dot(precision)
    n = x.shape[0]
    k = init_centers.shape[0]
    block_rows = _auto_block_rows(n, k, data_shards, block_rows)
    blocked = n > block_rows
    if blocked:
        pad = (-n) % block_rows
        if pad:
            x = jnp.concatenate([x, jnp.zeros((pad, x.shape[1]), x.dtype)])
            mask = jnp.concatenate([mask, jnp.zeros((pad,), mask.dtype)])
    x2 = jnp.sum(x * x, axis=1)
    br = block_rows if blocked else None

    def cond(state):
        _, moved, it, _ = state
        return jnp.logical_and(moved > tol * tol, it < max_iter)

    def body(state):
        centers, _, it, _ = state
        new_centers, cost = lloyd_step(
            x, mask, centers, x2, dot, cosine=cosine, block_rows=br
        )
        moved = jnp.max(jnp.sum((new_centers - centers) ** 2, axis=1))
        return new_centers, moved, it + 1, cost

    init_state = (init_centers, jnp.asarray(jnp.inf, x.dtype), 0, jnp.asarray(0.0, x.dtype))
    centers, _, n_iter, cost = jax.lax.while_loop(cond, body, init_state)
    # One final cost evaluation against the converged centers.
    _, final_cost = lloyd_step(x, mask, centers, x2, dot, cosine=cosine, block_rows=br)
    return centers, final_cost, n_iter


@partial(
    jax.jit, static_argnames=("max_iter", "every", "precision", "cosine", "block_rows")
)
def _lloyd_segment(
    x, mask, centers, moved, it, cost, tol,
    max_iter: int, every: int,
    precision: str, cosine: bool, block_rows,
):
    """Up to ``every`` Lloyd iterations from an explicit solver state.

    Exactly :func:`lloyd`'s loop body and stopping rule, plus a segment
    budget in the cond — so a sequence of segments executes the SAME
    iteration sequence as the monolithic while_loop, with the full state
    (centers, movement, iteration counter, cost) visible as a pytree
    between segments (the checkpointable form). ``x`` must already be
    padded to the block multiple (the driver owns the padding, once)."""
    dot = make_dot(precision)
    x2 = jnp.sum(x * x, axis=1)
    br = block_rows if (block_rows is not None and x.shape[0] > block_rows) else None

    def cond(state):
        _, moved, it, _, seg = state
        return jnp.logical_and(
            jnp.logical_and(moved > tol * tol, it < max_iter), seg < every
        )

    def body(state):
        centers, _, it, _, seg = state
        new_centers, cost = lloyd_step(
            x, mask, centers, x2, dot, cosine=cosine, block_rows=br
        )
        moved = jnp.max(jnp.sum((new_centers - centers) ** 2, axis=1))
        return new_centers, moved, it + 1, cost, seg + 1

    centers, moved, it, cost, _ = jax.lax.while_loop(
        cond, body, (centers, moved, it, cost, 0)
    )
    return centers, moved, it, cost


@partial(jax.jit, static_argnames=("precision", "cosine", "block_rows"))
def _lloyd_final_cost(x, mask, centers, precision: str, cosine: bool, block_rows):
    """The converged-centers cost evaluation :func:`lloyd` ends with,
    as its own program for the segmented driver."""
    dot = make_dot(precision)
    x2 = jnp.sum(x * x, axis=1)
    br = block_rows if (block_rows is not None and x.shape[0] > block_rows) else None
    _, cost = lloyd_step(x, mask, centers, x2, dot, cosine=cosine, block_rows=br)
    return cost


def lloyd_resumable(
    x: jax.Array,
    mask: jax.Array,
    init_centers: jax.Array,
    checkpointer,
    max_iter: int = 20,
    tol: float = 1e-4,
    precision: str = "highest",
    cosine: bool = False,
    block_rows: Optional[int] = None,
    data_shards: int = 1,
    mesh=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Preemption-tolerant :func:`lloyd`: a host-side outer loop running
    ``checkpointer.every`` iterations per jitted segment, the solver
    state snapshotted asynchronously after each segment, and the fit
    resumed mid-solve from the latest valid checkpoint. Same returns,
    bit-identical centers/cost/iterations (tests/test_checkpoint.py)."""
    from spark_rapids_ml_tpu.robustness.checkpoint import (
        replicate_state_onto_mesh,
        segment_boundary,
    )
    import time

    from spark_rapids_ml_tpu.observability.costs import ledgered_call
    from spark_rapids_ml_tpu.observability.metrics import observe_segment_seconds
    from spark_rapids_ml_tpu.robustness.faults import fault_point
    from spark_rapids_ml_tpu.utils.tracing import TraceColor, TraceRange

    n = x.shape[0]
    k = init_centers.shape[0]
    block_rows = _auto_block_rows(n, k, data_shards, block_rows)
    if n > block_rows:
        pad = (-n) % block_rows
        if pad:
            x = jnp.concatenate([x, jnp.zeros((pad, x.shape[1]), x.dtype)])
            mask = jnp.concatenate([mask, jnp.zeros((pad,), mask.dtype)])

    state = (
        init_centers,
        jnp.asarray(jnp.inf, x.dtype),
        jnp.asarray(0),
        jnp.asarray(0.0, x.dtype),
    )
    restored = checkpointer.restore_latest(template=state)
    if restored is not None:
        _, state = restored
        if mesh is not None:
            state = replicate_state_onto_mesh(state, mesh)

    tol_sq = float(tol) * float(tol)
    while True:
        moved, it = float(state[1]), int(state[2])
        if not (moved > tol_sq and it < max_iter):
            break
        seg_t0 = time.perf_counter()
        with TraceRange("segment kmeans.lloyd", TraceColor.PURPLE):
            fault_point("solver.segment")
            state = ledgered_call(
                _lloyd_segment, (x, mask, *state, tol),
                static=dict(
                    max_iter=max_iter, every=checkpointer.every,
                    precision=precision, cosine=cosine, block_rows=block_rows,
                ),
                name="kmeans.lloyd.segment",
            )
            bump_counter("checkpoint.segments")
            # int() blocks on the segment's device work, so the range —
            # and the histogram — cover dispatch + execution.
            bump_counter("checkpoint.solver_iters", int(state[2]) - it)
        observe_segment_seconds("kmeans.lloyd", time.perf_counter() - seg_t0)
        checkpointer.save_async(int(state[2]), state)
        segment_boundary(checkpointer)

    centers, _, n_iter, _ = state
    cost = _lloyd_final_cost(
        x, mask, centers, precision=precision, cosine=cosine, block_rows=block_rows
    )
    checkpointer.finalize_success()
    return centers, cost, n_iter


@partial(jax.jit, static_argnames=("block_rows", "precision"))
def assign_clusters_blocked(
    x: jax.Array,
    centers: jax.Array,
    block_rows: int = 65536,
    precision: str = "highest",
):
    """Row-blocked :func:`assign_clusters` — the (n, k) distance matrix
    never materializes (one (block, k) buffer per ``lax.map`` step).
    The assignment path for n*k shapes whose full distance matrix would
    blow HBM (e.g. the IVF coarse quantizer at 3M x 2048)."""
    dot = make_dot(precision)
    n = x.shape[0]
    nb = -(-n // block_rows)
    pad = nb * block_rows - n
    xp = jnp.pad(x, ((0, pad), (0, 0)))

    def one(xb):
        x2 = jnp.sum(xb * xb, axis=1)
        d2 = _sq_dists(xb, centers, x2, dot)
        return jnp.argmin(d2, axis=1), jnp.min(d2, axis=1)

    labs, d2s = jax.lax.map(one, xp.reshape(nb, block_rows, -1))
    return labs.reshape(-1)[:n], d2s.reshape(-1)[:n]


@partial(jax.jit, static_argnames=("precision",))
def block_suff_stats(xb: jax.Array, centers: jax.Array, precision: str = "highest"):
    """Lloyd sufficient statistics of ONE full (unmasked) row block against
    fixed centers: (sums (k, d), counts (k,), cost). The streaming fit's
    per-block kernel — accumulating these across blocks and dividing is
    exactly one Lloyd iteration at O(block + k*d) memory."""
    dot = make_dot(precision)
    x2 = jnp.sum(xb * xb, axis=1)
    mb = jnp.ones(xb.shape[0], xb.dtype)
    return _assign_and_accumulate(xb, mb, x2, centers, centers.shape[0], dot)


def reservoir_sample_rows(blocks, cap: int, seed: int, dtype=None):
    """One-pass uniform row reservoir (Algorithm R, vectorized per block).

    Returns ``(sample (min(cap, n), d), n_seen)``. Gives the streaming fit
    an unbiased seeding set without materializing the dataset — the
    standard trick for k-means++ on out-of-core data (cuML seeds its
    streaming k-means from a sample the same way).
    """
    from spark_rapids_ml_tpu.core.data import _block_to_dense

    rng = np.random.default_rng(seed)
    buf = None
    seen = 0
    for blk in blocks:
        b = _block_to_dense(blk, dtype=dtype)
        if b.shape[0] == 0:
            continue
        if buf is None:
            buf = np.empty((cap, b.shape[1]), dtype=b.dtype)
        i = 0
        # Fill phase: the first `cap` rows enter directly.
        if seen < cap:
            take = min(cap - seen, b.shape[0])
            buf[seen : seen + take] = b[:take]
            seen += take
            i = take
        # Replacement phase: global row t replaces slot j ~ U[0, t] if j < cap.
        nb = b.shape[0] - i
        if nb > 0:
            t = seen + np.arange(nb)  # global indices of remaining rows
            js = rng.integers(0, t + 1)
            hit = js < cap
            # Later duplicates into one slot must win in stream order.
            buf[js[hit]] = b[i:][hit]
            seen += nb
    if buf is None:
        raise ValueError("streaming source yielded no rows")
    return buf[: min(cap, seen)], seen


def lloyd_streaming(
    blocks_factory,
    init_centers: jax.Array,
    max_iter: int = 20,
    tol: float = 1e-4,
    precision: str = "highest",
    cosine: bool = False,
    dtype=None,
):
    """Multi-pass Lloyd over a RE-ITERABLE block source at constant memory.

    One data pass per iteration: each host block uploads once, its
    sufficient statistics (:func:`block_suff_stats`) accumulate on device
    (O(k*d) state), and the center update + movement check happen between
    passes. Semantics match :func:`lloyd` (empty clusters keep their
    center, movement-tol stop, final cost evaluated at the converged
    centers). Shares the re-iterable block contract of the streamed PCA
    sketch (linalg/row_matrix.py) — beats the materialize-everything
    ceiling the reference also had.
    """
    from spark_rapids_ml_tpu.core.data import _block_to_dense
    from spark_rapids_ml_tpu.robustness.faults import fault_point

    centers = jnp.asarray(init_centers)
    k, d = centers.shape
    np_dtype = np.dtype(dtype) if dtype is not None else np.dtype(centers.dtype)

    def _upload(blk):
        b = _block_to_dense(blk, dtype=np_dtype)
        if b.shape[0] == 0:
            return None
        xb = jnp.asarray(b)
        if cosine:
            xb = normalize_rows(xb)
        return xb

    def blocks_dev():
        # Double-buffered: block k+1 densifies and uploads while block
        # k's suff-stats program runs (serve_stream's overlap pattern via
        # prefetch_blocks); values and order are bit-identical.
        from spark_rapids_ml_tpu.core.serving import prefetch_blocks

        for xb in prefetch_blocks(blocks_factory(), _upload):
            if xb is not None:
                yield xb

    def one_pass(cs):
        fault_point("solver.segment")
        sums = jnp.zeros((k, d), cs.dtype)
        counts = jnp.zeros((k,), cs.dtype)
        cost = jnp.zeros((), cs.dtype)
        for xb in blocks_dev():
            sb, cb, jb = block_suff_stats(xb, cs, precision=precision)
            sums, counts, cost = sums + sb, counts + cb, cost + jb
        return sums, counts, cost

    n_iter = 0
    cost = jnp.zeros((), centers.dtype)
    for n_iter in range(1, max_iter + 1):
        sums, counts, cost = one_pass(centers)
        new_centers = jnp.where(
            counts[:, None] > 0, sums / jnp.maximum(counts, 1.0)[:, None], centers
        )
        if cosine:
            new_centers = normalize_rows(new_centers)
        moved = float(jnp.max(jnp.sum((new_centers - centers) ** 2, axis=1)))
        centers = new_centers
        if moved <= tol * tol:
            break
    # One final cost evaluation against the converged centers (lloyd parity).
    _, _, cost = one_pass(centers)
    return centers, cost, n_iter


@partial(jax.jit, static_argnames=("k", "precision"))
def kmeans_plusplus_init(
    x: jax.Array,
    mask: jax.Array,
    key: jax.Array,
    k: int,
    precision: str = "highest",
) -> jax.Array:
    """Greedy k-means++ seeding, fully on device via lax.fori_loop.

    D^2 sampling (Arthur & Vassilvitskii) with the greedy refinement sklearn
    uses: at each step, draw ``2 + ceil(log2 k)`` candidate rows with
    probability proportional to their squared distance to the nearest chosen
    center (Gumbel-top-t trick — no host sync), then keep the candidate that
    minimizes the resulting total potential. Single-candidate sequential
    k-means++ misses well-separated clusters often enough to matter at
    k >= 20; the greedy variant is the industrial default. Each step is two
    MXU matmuls — (n,d)x(d,k) for current distances and (t,d)x(d,n) for the
    candidate evaluation. Masked (padded) rows are never selected and never
    contribute to the potential.
    """
    dot = make_dot(precision)
    n, d = x.shape
    neg_inf = jnp.asarray(-jnp.inf, x.dtype)
    t = 2 + max(int(np.ceil(np.log2(k))), 0)

    x2 = jnp.sum(x * x, axis=1)
    key0, key_loop = jax.random.split(key)
    # First center: uniform over unmasked rows (Gumbel-max over the mask).
    g0 = jax.random.gumbel(key0, (n,), dtype=x.dtype)
    first = jnp.argmax(jnp.where(mask > 0, g0, neg_inf))
    centers = jnp.zeros((k, d), x.dtype).at[0].set(x[first])
    # min_d2: UNWEIGHTED distance to the nearest chosen center, maintained
    # incrementally. The mask (which may carry fractional weightCol weights)
    # enters only at the sampling probabilities and the potential — scaling
    # min_d2 itself would compound weights across iterations (w^i) and
    # compare weighted against unweighted candidate distances.
    min_d2 = jnp.maximum(x2 - 2.0 * dot(x, x[first]) + x2[first], 0.0)

    def body(i, carry):
        centers, min_d2, key = carry
        key, sub = jax.random.split(key)
        # Gumbel-top-t draw of candidates ∝ weight * min_d2 (weighted D^2).
        logw = jnp.where(
            (mask > 0) & (min_d2 > 0), jnp.log(mask * min_d2), neg_inf
        )
        g = jax.random.gumbel(sub, (n,), dtype=x.dtype)
        _, cand = jax.lax.top_k(logw + g, t)
        # all-zero residual (duplicate data): fall back to the first row
        degenerate = jnp.logical_not(jnp.isfinite(jnp.max(logw)))
        cand = jnp.where(degenerate, first, cand)
        # Evaluate each candidate: potential = sum_j min(min_d2, d2(x_j, c)).
        xc = x[cand]                                            # (t, d)
        d2c = jnp.maximum(
            x2[None, :] - 2.0 * dot(xc, x.T)
            + jnp.sum(xc * xc, axis=1)[:, None],
            0.0,
        )                                                       # (t, n)
        pot = jnp.sum(jnp.minimum(min_d2[None, :], d2c) * mask[None, :], axis=1)
        best = jnp.argmin(pot)
        idx = cand[best]
        new_min_d2 = jnp.minimum(min_d2, d2c[best])
        return centers.at[i].set(x[idx]), new_min_d2, key

    centers, _, _ = jax.lax.fori_loop(1, k, body, (centers, min_d2, key_loop))
    return centers


@partial(jax.jit, static_argnames=("k", "assume_unmasked"))
def random_init(x: jax.Array, mask: jax.Array, key: jax.Array, k: int,
                assume_unmasked: bool = False) -> jax.Array:
    """Random seeding: k distinct unmasked rows via Gumbel scores.

    ``assume_unmasked=True`` (caller guarantees every row is real —
    no mesh padding, no weightCol) swaps the exact top-k for the
    hardware ``approx_max_k``: the scores are iid noise, so which of
    them surface is a uniform random distinct sample either way, and
    the approximate reduction skips the full sort network (what that
    saves is not measured on the chip; exact on CPU). With a
    REAL mask the exact top-k is required — the approximate per-tile
    reduction could let -inf (masked) scores survive when valid rows
    are few or concentrated."""
    n = x.shape[0]
    g = jax.random.gumbel(key, (n,), dtype=x.dtype)
    if assume_unmasked:
        _, idx = jax.lax.approx_max_k(g, k)
    else:
        scores = jnp.where(mask > 0, g, -jnp.inf)
        _, idx = jax.lax.top_k(scores, k)
    return x[idx]


def normalize_rows(x: jax.Array, eps: float = 1e-12) -> jax.Array:
    """Unit-normalize rows — cosine distance == euclidean on normalized data."""
    norms = jnp.sqrt(jnp.sum(x * x, axis=1, keepdims=True))
    return x / jnp.maximum(norms, eps)

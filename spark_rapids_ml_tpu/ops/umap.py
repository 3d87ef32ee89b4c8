"""UMAP kernels — fuzzy simplicial set + batched SGD layout, all on-chip.

Beyond-the-reference capability (the reference ships only PCA — SURVEY.md
§2; the modern RAPIDS Spark-ML line grew UMAP on cuML). The cuML lineage
optimizes the layout with per-edge sequential SGD (scatter races resolved by
atomics); the TPU-first formulation instead runs *synchronous* epochs: every
epoch applies ALL attractive edge gradients and a fresh draw of negative
samples in one fused program — gathers + elementwise + two scatter-adds —
inside a ``lax.fori_loop``. Shapes are static (E = n * k edges, E * m
negatives), determinism comes for free, and the annealed learning rate plays
the role of umap-learn's per-edge epoch scheduling (edge sample frequency ∝
membership weight becomes a per-edge gradient weight).

Graph construction reuses the exact kNN GEMM kernels (:mod:`ops.knn`); the
smooth-kNN sigma search is a vectorized 64-step bisection over all points at
once instead of umap-learn's per-point Python loop.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def _pool_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """The negative pool's products (``y @ pool.T``, ``c @ pool``) at
    ``HIGHEST``: the layout's coordinates go to tens or more, and one bf16
    pass of them is no distance (see :func:`optimize_layout`)."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


class FuzzyGraph(NamedTuple):
    """Directed kNN edge list with symmetrized membership weights.

    ``weight[i, j]`` is the probabilistic t-conorm w_ij + w_ji - w_ij * w_ji,
    halved for mutual edges (which appear in both endpoints' lists) so each
    undirected edge carries its weight exactly once across the edge set.
    """

    indices: jax.Array  # (n, k) int32 neighbor ids
    weight: jax.Array  # (n, k) float32 symmetrized membership
    sigmas: jax.Array  # (n,) smooth-kNN bandwidths
    rhos: jax.Array  # (n,) distance to nearest neighbor


@partial(jax.jit, static_argnames=("n_iter",))
def smooth_knn_dist(
    knn_dists: jax.Array, k: float, n_iter: int = 64
) -> Tuple[jax.Array, jax.Array]:
    """Per-point bandwidth sigma and connectivity offset rho.

    Solves sum_j exp(-max(d_ij - rho_i, 0) / sigma_i) = log2(k) for every
    point simultaneously by bisection — the all-points-at-once analogue of
    umap-learn's smooth_knn_dist loop.
    """
    target = jnp.log2(k)
    # rho: smallest positive neighbor distance (umap-learn with
    # local_connectivity=1).
    pos = jnp.where(knn_dists > 0, knn_dists, jnp.inf)
    rho = jnp.min(pos, axis=1)
    rho = jnp.where(jnp.isfinite(rho), rho, 0.0)

    def psum(sigma):
        return jnp.sum(
            jnp.exp(-jnp.maximum(knn_dists - rho[:, None], 0.0) / sigma[:, None]),
            axis=1,
        )

    lo = jnp.full(knn_dists.shape[0], 1e-12, knn_dists.dtype)
    # Bracket expansion (umap-learn doubles hi until the target is
    # bracketed): a fixed cap would silently saturate on data whose
    # distance scale is large, collapsing all memberships toward zero.
    hi = jnp.full(knn_dists.shape[0], 1.0, knn_dists.dtype)

    def expand(_, hi):
        return jnp.where(psum(hi) < target, hi * 2.0, hi)

    hi = lax.fori_loop(0, 48, expand, hi)  # 2^48 spans any float32 scale

    def body(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) / 2.0
        too_high = psum(mid) > target  # sum decreases as sigma shrinks
        return jnp.where(too_high, lo, mid), jnp.where(too_high, mid, hi)

    lo, hi = lax.fori_loop(0, n_iter, body, (lo, hi))
    sigma = (lo + hi) / 2.0
    # Floor, as in umap-learn: sigma no smaller than 1e-3 * mean distance.
    mean_d = jnp.mean(knn_dists)
    return jnp.maximum(sigma, 1e-3 * mean_d), rho


@jax.jit
def fuzzy_simplicial_set(knn_idx: jax.Array, knn_dists: jax.Array) -> FuzzyGraph:
    """Membership strengths + symmetrization over the directed kNN edges.

    The reverse weight w_ji is looked up by scanning j's neighbor list for i
    (a (n, k, k) compare — O(n k^2) elementwise, negligible next to the kNN
    GEMM); absent reverse edges contribute 0, exactly like the sparse
    transpose in umap-learn/cuML.
    """
    n, k = knn_idx.shape
    sigmas, rhos = smooth_knn_dist(knn_dists, float(k))
    w = jnp.exp(
        -jnp.maximum(knn_dists - rhos[:, None], 0.0) / sigmas[:, None]
    )  # (n, k) directed memberships

    # Reverse lookup: for edge (i -> j), find i in row j of knn_idx.
    src = jnp.broadcast_to(jnp.arange(n, dtype=knn_idx.dtype)[:, None], (n, k))
    rows_j = knn_idx  # (n, k): the j of each edge
    match = knn_idx[rows_j] == src[:, :, None]  # (n, k, k)
    w_rev_rows = w[rows_j]  # (n, k, k): weights of j's edges
    w_ji = jnp.sum(jnp.where(match, w_rev_rows, 0.0), axis=2)
    mutual = jnp.any(match, axis=2)

    w_sym = w + w_ji - w * w_ji
    w_sym = jnp.where(mutual, 0.5 * w_sym, w_sym)
    return FuzzyGraph(knn_idx.astype(jnp.int32), w_sym.astype(jnp.float32), sigmas, rhos)


def find_ab_params(spread: float, min_dist: float) -> Tuple[float, float]:
    """Fit the rational low-dimensional similarity curve 1/(1 + a d^2b) to
    the desired (min_dist, spread) offset-exponential — same least-squares
    target as umap-learn."""
    from scipy.optimize import curve_fit

    xv = np.linspace(0, spread * 3, 300)
    yv = np.where(
        xv < min_dist, 1.0, np.exp(-(xv - min_dist) / spread)
    )

    def curve(x, a, b):
        return 1.0 / (1.0 + a * x ** (2 * b))

    (a, b), _ = curve_fit(curve, xv, yv, p0=[1.0, 1.0], maxfev=10000)
    return float(a), float(b)


@partial(
    jax.jit,
    static_argnames=(
        "n_epochs", "neg_rate", "neg_pool", "move_other", "tail_cfg",
        "tail_interpret",
    ),
)
def optimize_layout(
    embedding: jax.Array,  # (n, dim) initial layout
    graph: FuzzyGraph,
    key: jax.Array,
    *,
    n_epochs: int,
    neg_rate: int = 5,
    neg_pool: int = 256,
    learning_rate: float = 1.0,
    repulsion: float = 1.0,
    a: float = 1.577,
    b: float = 0.895,
    move_other: bool = True,
    target: jax.Array | None = None,
    tail_plan=None,
    tail_cfg=None,
    tail_interpret: bool = False,
) -> jax.Array:
    """Synchronous-epoch UMAP layout optimization.

    Every epoch: gradients of the fuzzy cross-entropy for all E edges
    (attraction, weighted by membership) and a repulsion term from
    uniformly drawn negatives, applied with a linearly annealed step —
    umap-learn's sampling schedule folded into weights. ``target`` (if
    given) is a fixed reference point set the tail of each edge attracts
    to instead of the live embedding — the transform-time mode where
    train points stay put; ``move_other=False`` then skips the tail
    update.

    TPU layout (r4): the edge
    list is EXACTLY (n heads x k neighbors), so every head-side access is
    STRUCTURED — the head "gather" is a broadcast of y and the head
    "scatter" is a dense (n, k, ...) sum over k — leaving only the
    genuinely random accesses on the slow scalarized path.

    Negative sampling (r5): ``neg_pool > 0`` (default) replaces the
    E * neg_rate per-edge random gathers with ONE shared pool of ``neg_pool``
    uniform draws per epoch. Repulsion of every head against the pool is
    dense algebra: squared distances via ``y @ pool.T`` (MXU GEMM) plus
    norm broadcasts, and because the per-sample coefficient (not the
    per-component gradient) carries the clip, the gradient factorizes as
    ``rowsum(c) * y - c @ pool`` — two dense contractions, no gather.
    The estimator stays unbiased w.r.t. the per-edge one: each head's
    k * neg_rate uniform draws with per-edge weights w_ij are replaced
    by neg_pool shared uniform draws importance-weighted by
    sum_j(w_ij) * neg_rate / neg_pool, and the clip cap scales by the
    same ratio so the maximum per-epoch repulsion magnitude is preserved
    (cap * n_samples is invariant). Pool samples are shared across heads
    (correlated within an epoch, fresh draw every epoch); per-head
    expectation and total weight match the per-edge formulation exactly.
    ``neg_pool=0`` keeps the legacy per-edge path.

    ``tail_plan``/``tail_cfg`` (from :func:`ops.pallas.umap.
    build_tail_plan`) replace the per-epoch tail scatter-add with the
    Pallas bucketed-accumulation kernel over the tail-sorted static edge
    list (the scatter was ~70% of the SGD wall). Tolerance
    parity with the scatter path — in-tile accumulation order differs.
    """
    n, dim = embedding.shape
    epoch = _make_epoch_fn(
        embedding.shape, graph, target,
        n_epochs=n_epochs, neg_rate=neg_rate, neg_pool=neg_pool,
        learning_rate=learning_rate, repulsion=repulsion, a=a, b=b,
        move_other=move_other, tail_plan=tail_plan, tail_cfg=tail_cfg,
        tail_interpret=tail_interpret,
    )
    y, _ = lax.fori_loop(0, n_epochs, epoch, (embedding, key))
    return y


def _make_epoch_fn(
    shape, graph: FuzzyGraph, target,
    *, n_epochs, neg_rate, neg_pool, learning_rate, repulsion, a, b, move_other,
    tail_plan=None, tail_cfg=None, tail_interpret=False,
):
    """Build ONE epoch of the synchronous layout SGD — the single home of
    the epoch body, closed over by the monolithic :func:`optimize_layout`
    program and the segmented :func:`_layout_segment` program so both run
    literally the same per-epoch math (checkpoint bit-identity; a tail
    plan, when given, is shared by both, so the invariant survives the
    Pallas tail path too)."""
    n, dim = shape
    k = graph.indices.shape[1]
    dst = graph.indices  # (n, k)
    w = graph.weight  # (n, k)
    n_ref = n if target is None else target.shape[0]
    w_sum = jnp.sum(w, axis=1)  # (n,) total edge weight per head

    def epoch(ep, carry):
        y, key = carry
        key, k_neg = jax.random.split(key)
        alpha = learning_rate * (1.0 - ep / n_epochs)

        # Edge gathers stay in ROW form: splitting the (n, k, dim)
        # gather into dim flat (n,) -> (n, k) lookups pays per element,
        # where the row gather amortizes index handling across the
        # dim-wide row (the opposite of the forest per-class-gather
        # lesson, whose tables are hundreds wide). The choice predates
        # the chip; not measured on it (no UMAP cell: ROADMAP.md Reach 9).
        yi = y[:, None, :]  # (n, 1, dim) — the head side is a broadcast
        ref_y = y if target is None else target
        yj = ref_y[dst]  # (n, k, dim)
        diff = yi - yj
        d2 = jnp.sum(diff * diff, axis=2)  # (n, k)
        # Attractive: d/dy_i of log(1/(1 + a d^2b)) -> -2ab d^{2(b-1)}/(1+a d^2b)
        att = (-2.0 * a * b * jnp.power(jnp.maximum(d2, 1e-12), b - 1.0)) / (
            1.0 + a * jnp.power(d2, b)
        )
        g_att = jnp.clip((att * w)[:, :, None] * diff, -4.0, 4.0)  # (n, k, dim)

        if neg_pool > 0:
            # Shared pool: neg_pool gathers per epoch (vs n*k*neg_rate),
            # then repulsion is dense (n, s) work on the MXU/VPU.
            pool_idx = jax.random.randint(k_neg, (neg_pool,), 0, n_ref)
            pool = (y if target is None else target)[pool_idx]  # (s, dim)
            y2 = jnp.sum(y * y, axis=1)  # (n,)
            p2 = jnp.sum(pool * pool, axis=1)  # (s,)
            # Both pool products at HIGHEST: at the chip's default (one
            # bf16 pass) the norm expansion below cancels, near pairs read
            # d2n ~ 0, the clip's cap / sqrt(d2n) lets the coefficient reach
            # ~1e6 and rows are flung (embedding spread 5,324 at 250,000 x
            # 3000 on the chip, PERF.md section 6).
            cross = _pool_dot(y, pool.T)  # (n, s) GEMM
            d2n = jnp.maximum(y2[:, None] + p2[None, :] - 2.0 * cross, 0.0)
            rep = (2.0 * repulsion * b) / (
                (0.001 + d2n) * (1.0 + a * jnp.power(d2n, b))
            )
            # Importance weight: each pool sample stands for
            # k * neg_rate / s per-edge draws of mean weight w_sum / k.
            c = rep * (w_sum[:, None] * (neg_rate / neg_pool))
            # Clip on the coefficient: per-edge path caps each of the
            # k * neg_rate per-sample gradients at 4; each pool sample
            # represents k * neg_rate / s of them, so cap scales by that
            # ratio (|c * diff| <= c * sqrt(d2n) <= cap).
            cap = 4.0 * k * neg_rate / neg_pool
            c = jnp.minimum(c, cap / jnp.sqrt(d2n + 1e-12))
            g_rep_head = (
                jnp.sum(c, axis=1, keepdims=True) * y - _pool_dot(c, pool)
            )  # (n, dim): sum_p c_ip (y_i - pool_p), factorized
            grad_head = jnp.sum(g_att, axis=1) + g_rep_head
        else:
            # Legacy per-edge negatives: draw (E, m), view as (n, k, m).
            neg_idx = jax.random.randint(
                k_neg, (n * k, neg_rate), 0, n_ref
            ).reshape(n, k, neg_rate)
            # Negatives come from the LIVE layout in fit mode (repulsion
            # must track the moving points), frozen targets in transform.
            yn = ref_y[neg_idx]  # (n, k, m, dim)
            diff_n = y[:, None, None, :] - yn
            d2n = jnp.sum(diff_n * diff_n, axis=3)  # (n, k, m)
            rep = (2.0 * repulsion * b) / (
                (0.001 + d2n) * (1.0 + a * jnp.power(d2n, b))
            )
            g_rep = jnp.clip(
                (rep * w[:, :, None])[:, :, :, None] * diff_n, -4.0, 4.0
            )
            grad_head = jnp.sum(g_att + jnp.sum(g_rep, axis=2), axis=1)

        # Head moves along both terms (att < 0 pulls toward the neighbor,
        # rep > 0 pushes off the negatives): a DENSE sum — no scatter.
        # The tail mirrors attraction (true scatter, dst random) — unless
        # a tail plan routes it through the Pallas bucketed accumulator.
        delta = alpha * grad_head
        if move_other and target is None:
            tail_g = -alpha * g_att.reshape(-1, dim)
            if tail_plan is not None:
                from spark_rapids_ml_tpu.ops.pallas.umap import tail_accumulate

                delta = delta + tail_accumulate(
                    tail_g, tail_plan, tail_cfg, interpret=tail_interpret
                )
            else:
                delta = delta + jnp.zeros_like(y).at[dst.reshape(-1)].add(
                    tail_g
                )
        return y + delta, key

    return epoch


@partial(
    jax.jit,
    static_argnames=(
        "n_epochs", "neg_rate", "neg_pool", "move_other", "tail_cfg",
        "tail_interpret",
    ),
)
def _layout_segment(
    y, key_data, ep_start, ep_stop, graph: FuzzyGraph,
    learning_rate, repulsion, a, b, target, tail_plan=None,
    *, n_epochs: int, neg_rate: int, neg_pool: int, move_other: bool,
    tail_cfg=None, tail_interpret: bool = False,
):
    """Epochs [ep_start, ep_stop) of :func:`optimize_layout` from an
    explicit (layout, RNG) state — the checkpointable form. The RNG key
    travels as raw ``key_data`` (uint32) so the state pytree serializes;
    traced bounds keep ONE compiled program across all segments."""
    key = jax.random.wrap_key_data(key_data)
    epoch = _make_epoch_fn(
        y.shape, graph, target,
        n_epochs=n_epochs, neg_rate=neg_rate, neg_pool=neg_pool,
        learning_rate=learning_rate, repulsion=repulsion, a=a, b=b,
        move_other=move_other, tail_plan=tail_plan, tail_cfg=tail_cfg,
        tail_interpret=tail_interpret,
    )
    y, key = lax.fori_loop(ep_start, ep_stop, epoch, (y, key))
    return y, jax.random.key_data(key)


def optimize_layout_resumable(
    embedding: jax.Array,
    graph: FuzzyGraph,
    key: jax.Array,
    checkpointer,
    *,
    n_epochs: int,
    neg_rate: int = 5,
    neg_pool: int = 256,
    learning_rate: float = 1.0,
    repulsion: float = 1.0,
    a: float = 1.577,
    b: float = 0.895,
    move_other: bool = True,
    target: jax.Array | None = None,
    tail_plan=None,
    tail_cfg=None,
    tail_interpret: bool = False,
) -> jax.Array:
    """Preemption-tolerant :func:`optimize_layout`: ``checkpointer.every``
    epochs per jitted segment, the (layout, RNG key data, epoch) state
    snapshotted asynchronously between segments, resumed mid-schedule
    from the latest valid checkpoint. Bit-identical final layout."""
    from spark_rapids_ml_tpu.robustness.checkpoint import segment_boundary
    import time

    from spark_rapids_ml_tpu.observability.costs import ledgered_call
    from spark_rapids_ml_tpu.observability.metrics import observe_segment_seconds
    from spark_rapids_ml_tpu.robustness.faults import fault_point
    from spark_rapids_ml_tpu.utils.tracing import TraceColor, TraceRange, bump_counter

    state = (embedding, jax.random.key_data(key), jnp.asarray(0))
    restored = checkpointer.restore_latest(template=state)
    if restored is not None:
        _, state = restored
    y, kd, ep = state
    while int(ep) < n_epochs:
        start = int(ep)
        stop = min(start + checkpointer.every, n_epochs)
        seg_t0 = time.perf_counter()
        with TraceRange("segment umap.layout", TraceColor.PURPLE):
            fault_point("solver.segment")
            y, kd = ledgered_call(
                _layout_segment,
                (y, kd, jnp.asarray(start), jnp.asarray(stop), graph,
                 learning_rate, repulsion, a, b, target, tail_plan),
                static=dict(
                    n_epochs=n_epochs, neg_rate=neg_rate, neg_pool=neg_pool,
                    move_other=move_other, tail_cfg=tail_cfg,
                    tail_interpret=tail_interpret,
                ),
                name="umap.layout.segment",
            )
            ep = jnp.asarray(stop)
            bump_counter("checkpoint.segments")
            bump_counter("checkpoint.solver_iters", stop - start)
        observe_segment_seconds("umap.layout", time.perf_counter() - seg_t0)
        checkpointer.save_async(stop, (y, kd, ep))
        segment_boundary(checkpointer)
    checkpointer.finalize_success()
    return y


@lru_cache(maxsize=None)
def _sharded_layout_fn(
    mesh, n: int, k_nbrs: int, n_epochs: int, neg_rate: int, neg_pool: int
):
    """Build (and cache) the jitted shard_map epoch program for one
    (mesh, shape) combination — jit's cache is keyed on the function
    object, so the closure must not be rebuilt per call (the
    knn/ann/dbscan cached-builder pattern). Float hyperparameters enter
    as TRACED scalars, not cache keys: a tuning sweep over learning rate
    or min_dist must reuse one executable, not pin one per float value.
    """
    from jax.sharding import PartitionSpec as P
    from spark_rapids_ml_tpu.utils.compat import axis_size, shard_map

    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS

    def local(dst_b, w_b, y0, key, learning_rate, repulsion, a, b):
        # Edges shard by HEAD ROW (n_local, k) — the same structured-head
        # layout as the single-device epoch: the head gather is a
        # dynamic slice of y, the head scatter a dense sum + one
        # dynamic-update-slice; only the dst/negative gathers and the
        # tail scatter stay on the scalarized path.
        #
        # Pooled mode (neg_pool > 0) draws the shared pool from the UNFOLDED
        # (replicated) key so every shard scores the identical pool — no
        # per-shard randomness remains, and the epoch matches the
        # single-device pooled path up to psum reduction order. Only the
        # legacy per-edge path folds the key per shard.
        shard_key = jax.random.fold_in(key, lax.axis_index(DATA_AXIS))
        if neg_pool <= 0:
            key = shard_key
        n_local = dst_b.shape[0]
        row0 = lax.axis_index(DATA_AXIS) * n_local
        n_pad_total = n_local * axis_size(DATA_AXIS)
        dim = y0.shape[1]
        w_sum_b = jnp.sum(w_b, axis=1)  # (n_local,)

        def epoch(ep, carry):
            y, key = carry
            key, k_neg = jax.random.split(key)
            alpha = learning_rate * (1.0 - ep / n_epochs)
            yh = lax.dynamic_slice_in_dim(y, row0, n_local)  # (n_local, dim)
            # Row gather, as in the single-device epoch (see there).
            yj = y[dst_b]  # (n_local, k, dim)
            diff = yh[:, None, :] - yj
            d2 = jnp.sum(diff * diff, axis=2)
            att = (-2.0 * a * b * jnp.power(jnp.maximum(d2, 1e-12), b - 1.0)) / (
                1.0 + a * jnp.power(d2, b)
            )
            g_att = jnp.clip((att * w_b)[:, :, None] * diff, -4.0, 4.0)
            if neg_pool > 0:
                pool_idx = jax.random.randint(k_neg, (neg_pool,), 0, n)
                pool = y[pool_idx]  # (s, dim) — y is replicated
                yh2 = jnp.sum(yh * yh, axis=1)
                p2 = jnp.sum(pool * pool, axis=1)
                cross = _pool_dot(yh, pool.T)  # (n_local, s), HIGHEST as above
                d2n = jnp.maximum(
                    yh2[:, None] + p2[None, :] - 2.0 * cross, 0.0
                )
                rep = (2.0 * repulsion * b) / (
                    (0.001 + d2n) * (1.0 + a * jnp.power(d2n, b))
                )
                c = rep * (w_sum_b[:, None] * (neg_rate / neg_pool))
                cap = 4.0 * k_nbrs * neg_rate / neg_pool
                c = jnp.minimum(c, cap / jnp.sqrt(d2n + 1e-12))
                g_rep_head = jnp.sum(c, axis=1, keepdims=True) * yh - _pool_dot(c, pool)
                grad_head = jnp.sum(g_att, axis=1) + g_rep_head
            else:
                neg_idx = jax.random.randint(
                    k_neg, (n_local, k_nbrs, neg_rate), 0, n
                )
                yn = y[neg_idx]  # (n_local, k, m, dim)
                diff_n = yh[:, None, None, :] - yn
                d2n = jnp.sum(diff_n * diff_n, axis=3)
                rep = (2.0 * repulsion * b) / (
                    (0.001 + d2n) * (1.0 + a * jnp.power(d2n, b))
                )
                g_rep = jnp.clip(
                    (rep * w_b[:, :, None])[:, :, :, None] * diff_n, -4.0, 4.0
                )
                grad_head = jnp.sum(g_att + jnp.sum(g_rep, axis=2), axis=1)
            delta = jnp.zeros_like(y).at[dst_b.reshape(-1)].add(
                -alpha * g_att.reshape(-1, dim)
            )
            head_block = (
                lax.dynamic_slice_in_dim(delta, row0, n_local)
                + alpha * grad_head
            )
            delta = lax.dynamic_update_slice_in_dim(delta, head_block, row0, 0)
            # ONE collective per epoch: merge the shards' deltas so every
            # device applies the identical (replicated) update.
            delta = lax.psum(delta, DATA_AXIS)
            return y + delta, key

        # Pad y to the sharded row total so head slices never clamp;
        # padded rows carry zero weight and are never sampled (negatives
        # draw from [0, n)).
        y_pad = jnp.pad(y0, ((0, n_pad_total - n), (0, 0)))
        y_pad, _ = lax.fori_loop(0, n_epochs, epoch, (y_pad, key))
        return y_pad[:n]

    fit = shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(DATA_AXIS, None), P(DATA_AXIS, None), P(), P(),
            P(), P(), P(), P(),
        ),
        out_specs=P(),
        check_vma=False,  # the psum-merged y is replicated by construction
    )
    return jax.jit(fit)


def optimize_layout_sharded(
    mesh,
    embedding: jax.Array,
    graph: FuzzyGraph,
    key: jax.Array,
    *,
    n_epochs: int,
    neg_rate: int = 5,
    neg_pool: int = 256,
    learning_rate: float = 1.0,
    repulsion: float = 1.0,
    a: float = 1.577,
    b: float = 0.895,
) -> jax.Array:
    """Mesh-sharded synchronous-epoch layout optimization (fit mode).

    The epoch shards edges by HEAD ROW over the mesh data axis (the
    structured-head layout of the single-device epoch: the head side of
    every edge is a slice/dense-sum, never a gather/scatter); each shard
    accumulates its gradient contributions into a local (n, dim) delta,
    and ONE psum per epoch merges the deltas over ICI — the embedding
    stays replicated, so the per-epoch wire cost is the (n, dim) delta,
    independent of edge count (previously
    only the kNN-graph stage sharded).

    Pooled negatives (``neg_pool > 0``, default) draw ONE shared pool per
    epoch from the replicated key, so all shards score the identical pool
    and the result matches the single-device pooled path up to psum
    reduction order. The legacy per-edge path (``neg_pool=0``) draws
    negatives per shard (key folded with the shard index): same sampling
    distribution and count per edge, different RNG stream — like any
    reseeded SGD run.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS

    n, dim = embedding.shape
    k = graph.indices.shape[1]
    dst = graph.indices  # (n, k)
    w = graph.weight
    dp = int(mesh.shape[DATA_AXIS])
    pad = (-n) % dp
    if pad:
        # Padded head rows carry zero weight: their attractive AND
        # repulsive terms are scaled by w, so they contribute nothing.
        dst = jnp.concatenate([dst, jnp.zeros((pad, k), jnp.int32)])
        w = jnp.concatenate([w, jnp.zeros((pad, k), w.dtype)])

    row_sharding = NamedSharding(mesh, P(DATA_AXIS, None))
    dst = jax.device_put(dst, row_sharding)
    w = jax.device_put(w, row_sharding)
    y0 = jax.device_put(embedding.astype(jnp.float32), NamedSharding(mesh, P()))

    fit = _sharded_layout_fn(mesh, n, k, n_epochs, neg_rate, neg_pool)
    f32 = jnp.float32
    return fit(
        dst, w, y0, key,
        jnp.asarray(learning_rate, f32), jnp.asarray(repulsion, f32),
        jnp.asarray(a, f32), jnp.asarray(b, f32),
    )


def spectral_init(
    graph: FuzzyGraph, n: int, dim: int, key: jax.Array
) -> jax.Array:
    """Normalized-Laplacian spectral embedding of the fuzzy graph (dense —
    one symmetric eigh on the device; used below a size cap, random init
    above it). Scaled to the ±10 box with a small noise break, as in
    umap-learn."""
    w = jnp.zeros((n, n), dtype=jnp.float32)
    src = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None], graph.indices.shape)
    w = w.at[src.reshape(-1), graph.indices.reshape(-1)].add(graph.weight.reshape(-1))
    w = w + w.T  # undirected (weights were already de-duplicated for mutuals)
    deg = jnp.maximum(jnp.sum(w, axis=1), 1e-8)
    d_inv_sqrt = 1.0 / jnp.sqrt(deg)
    lap = jnp.eye(n, dtype=jnp.float32) - d_inv_sqrt[:, None] * w * d_inv_sqrt[None, :]
    vals, vecs = jnp.linalg.eigh(lap)
    emb = vecs[:, 1 : dim + 1]  # skip the trivial constant eigenvector
    expansion = 10.0 / jnp.maximum(jnp.max(jnp.abs(emb)), 1e-8)
    noise = jax.random.normal(key, emb.shape, dtype=emb.dtype) * 1e-4
    return emb * expansion + noise

"""Brute-force k-nearest-neighbors kernels — distance GEMM + blocked top-k.

Beyond-the-reference capability (the reference ships only PCA — SURVEY.md
§2; the modern RAPIDS Spark-ML line grew a brute-force NearestNeighbors on
cuML). TPU-first design: the pairwise distance matrix is one
(nq, d) x (d, n) GEMM on the MXU — the expansion
||q - x||^2 = ||q||^2 - 2 q.x + ||x||^2 never materializes the (nq, n)
matrix for large item sets; instead items stream through a ``lax.scan`` in
fixed-size blocks with a running (nq, k) top-k merge, so memory is
O(nq * (k + block)) and shapes stay static for XLA.

Distributed: shard items over the mesh data axis with ``shard_map``; each
shard computes its local top-k, then the (nq, k) candidate lists ride ICI
via ``all_gather`` and one final merge selects the global top-k — the
candidate traffic is k/n_items of the naive all-gather of distances.
"""

from __future__ import annotations

import functools
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from spark_rapids_ml_tpu.ops.precision import as_dot, make_dot
from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS
from spark_rapids_ml_tpu.utils.tracing import bump_counter


def _block_sq_distances(q: jax.Array, xb: jax.Array, q_sq: jax.Array, dot) -> jax.Array:
    """(nq, B) squared euclidean distances of queries to one item block;
    ``dot`` is the policy's product (``ops/precision.py::make_dot``) or a
    bare ``lax.Precision``."""
    xb_sq = jnp.sum(xb * xb, axis=1)
    cross = as_dot(dot)(q, xb.T)
    d2 = q_sq[:, None] - 2.0 * cross + xb_sq[None, :]
    return jnp.maximum(d2, 0.0)


def _auto_block_items(nq: int, n_items: int) -> int:
    """Item-block size: capped at 65536 rows (a knee chosen before the
    chip, not measured on it: ROADMAP.md Reach 9, Design 14); under the
    cap a ~2 GiB f32 (nq, block) buffer budget
    shrinks blocks for large query batches (memory safety), floored at
    1024 so the scan stays coarse."""
    return min(n_items, 65536, max(1024, (1 << 29) // max(nq, 1)))


def count_knn_blocks(nq: int, n_items: int, block_items: int | None = None) -> None:
    """``knn.blocks``: the item blocks one :func:`knn_sq_euclidean` of
    ``nq`` queries over ``n_items`` items scans (each a (nq, block)
    distance product and a top-k merge), from the shapes, at dispatch."""
    block = min(block_items or _auto_block_items(nq, n_items), n_items)
    bump_counter("knn.blocks", -(-n_items // block))


@partial(jax.jit, static_argnames=("k", "block_items", "precision", "approx"))
def knn_sq_euclidean(
    queries: jax.Array,
    items: jax.Array,
    k: int,
    item_mask: jax.Array | None = None,
    block_items: int | None = None,
    precision: str = "highest",
    approx: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Top-k by squared euclidean distance — exact by default.

    Returns (distances (nq, k) ascending, indices (nq, k) int32 into
    ``items``). ``item_mask``: 1.0 real / 0.0 padded rows (padded items are
    pushed to +inf so they never surface). Items are processed in
    ``block_items``-row blocks via ``lax.scan``; with fewer items than one
    block the scan has a single step (no penalty).

    ``approx=True`` replaces the per-block exact ``top_k`` with the
    TPU-native ``lax.approx_min_k`` (the PartialReduce op the hardware
    has a fast path for; exact on CPU) while the cross-block candidate
    merge stays exact. The TPU-first ANN design: a dense MXU scoring
    pass + hardware approximate top-k in place of the inverted-list
    gathers of ``ops/ann.ivf_search``, because TPU gathers are scalarized
    while the distance GEMM rides the systolic array. The crossover
    between the two predates the chip and is not measured on it
    (ROADMAP.md Reach 9 is its cell; Design 14).
    ``block_items=None`` picks the block from the query count
    (:func:`_auto_block_items` — the estimator path reaches benchmark-
    grade blocks without a knob); pass an explicit value to pin it.
    """
    n_items = items.shape[0]
    if not 1 <= k <= n_items:
        raise ValueError(f"k must be in [1, {n_items}], got {k}")
    if block_items is None:
        block_items = _auto_block_items(queries.shape[0], n_items)
    dot = make_dot(precision)
    dtype = queries.dtype
    nq = queries.shape[0]
    q_sq = jnp.sum(queries * queries, axis=1)

    block = min(block_items, n_items)
    n_blocks = -(-n_items // block)
    pad = n_blocks * block - n_items
    items_p = jnp.pad(items, ((0, pad), (0, 0)))
    # With no user mask and no padding, the mask is identically 1 — skip
    # the (nq, block) where-pass entirely (static decision at trace time).
    need_mask = item_mask is not None or pad > 0
    mask_p = jnp.ones(n_items, dtype=dtype) if item_mask is None else item_mask.astype(dtype)
    mask_p = jnp.pad(mask_p, (0, pad))
    item_blocks = items_p.reshape(n_blocks, block, -1)
    mask_blocks = mask_p.reshape(n_blocks, block)

    init_d = jnp.full((nq, k), jnp.inf, dtype=dtype)
    init_i = jnp.full((nq, k), -1, dtype=jnp.int32)

    def step(carry, blk):
        best_d, best_i = carry
        xb, mb, start = blk
        d2 = _block_sq_distances(queries, xb, q_sq, dot)
        if need_mask:
            d2 = jnp.where(mb[None, :] > 0, d2, jnp.inf)
            # Masked (padded) items keep index -1 so that when k exceeds
            # the real item count the unfilled slots surface as (inf, -1)
            # rather than as plausible-looking indices of padding rows.
            idx = jnp.where(mb > 0, start + jnp.arange(block, dtype=jnp.int32), -1)
        else:
            idx = start + jnp.arange(block, dtype=jnp.int32)
        if approx:
            # Hardware partial-reduce narrows the block to k candidates;
            # the candidate merge below stays exact.
            blk_d, blk_pos = lax.approx_min_k(d2, k)
            blk_i = jnp.take_along_axis(
                jnp.broadcast_to(idx, (nq, block)), blk_pos, axis=1
            )
            cand_d = jnp.concatenate([best_d, blk_d], axis=1)
            cand_i = jnp.concatenate([best_i, blk_i], axis=1)
        else:
            cand_d = jnp.concatenate([best_d, d2], axis=1)
            cand_i = jnp.concatenate(
                [best_i, jnp.broadcast_to(idx, (nq, block))], axis=1
            )
        # top_k selects LARGEST; negate for smallest-distance selection.
        neg_top, pos = lax.top_k(-cand_d, k)
        return (-neg_top, jnp.take_along_axis(cand_i, pos, axis=1)), None

    starts = (jnp.arange(n_blocks, dtype=jnp.int32) * block)
    (best_d, best_i), _ = lax.scan(step, (init_d, init_i), (item_blocks, mask_blocks, starts))
    return best_d, best_i


@partial(
    jax.jit, static_argnames=("k", "block_items", "metric", "precision", "approx")
)
def knn(
    queries: jax.Array,
    items: jax.Array,
    k: int,
    item_mask: jax.Array | None = None,
    block_items: int | None = None,
    metric: str = "euclidean",
    precision: str = "highest",
    approx: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Top-k under ``euclidean`` | ``sqeuclidean`` | ``cosine``.

    Cosine distance = 1 - cos(q, x); implemented by L2-normalizing both
    sides, where it reduces to half the squared euclidean distance.
    ``approx`` selects the hardware approximate per-block top-k (see
    :func:`knn_sq_euclidean`).
    """
    if metric not in ("euclidean", "sqeuclidean", "cosine"):
        raise ValueError(f"unknown metric {metric!r}")
    if metric == "cosine":
        qn = queries / jnp.maximum(
            jnp.linalg.norm(queries, axis=1, keepdims=True), 1e-30
        )
        xn = items / jnp.maximum(jnp.linalg.norm(items, axis=1, keepdims=True), 1e-30)
        d2, idx = knn_sq_euclidean(
            qn, xn, k, item_mask, block_items, precision, approx
        )
        return d2 / 2.0, idx
    d2, idx = knn_sq_euclidean(
        queries, items, k, item_mask, block_items, precision, approx
    )
    if metric == "euclidean":
        return jnp.sqrt(d2), idx
    return d2, idx


@partial(jax.jit, static_argnames=("k", "approx", "precision"))
def _merge_block_topk(best_d, best_i, queries, q_sq, xb, start, k,
                      approx: bool, precision: str = "highest"):
    """One streamed-block update of the running (nq, k) top-k state —
    the same candidate-merge math as :func:`knn_sq_euclidean`'s scan step,
    jitted standalone so a HOST loop can drive it block by block."""
    nq = queries.shape[0]
    block = xb.shape[0]
    d2 = _block_sq_distances(queries, xb, q_sq, make_dot(precision))
    idx = start + jnp.arange(block, dtype=jnp.int32)
    if approx:
        # A block smaller than k (ragged tail, fine-grained sources)
        # cannot be approx-reduced to k candidates — take it whole.
        blk_d, blk_pos = lax.approx_min_k(d2, min(k, block))
        blk_i = jnp.take_along_axis(
            jnp.broadcast_to(idx, (nq, block)), blk_pos, axis=1
        )
        cand_d = jnp.concatenate([best_d, blk_d], axis=1)
        cand_i = jnp.concatenate([best_i, blk_i], axis=1)
    else:
        cand_d = jnp.concatenate([best_d, d2], axis=1)
        cand_i = jnp.concatenate(
            [best_i, jnp.broadcast_to(idx, (nq, block))], axis=1
        )
    neg_top, pos = lax.top_k(-cand_d, k)
    return -neg_top, jnp.take_along_axis(cand_i, pos, axis=1)


def knn_host_streamed(
    queries: jax.Array,
    item_blocks,
    k: int,
    metric: str = "euclidean",
    precision: str = "highest",
    approx: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Top-k against an item set STREAMED from beyond device memory.

    ``item_blocks``: an iterable of host (rows_i, d) blocks (list,
    generator, ``NpyBlockReader.iter_blocks()`` — one pass is enough).
    Each block uploads once, its candidates merge into the running
    (nq, k) state on device (:func:`_merge_block_topk` — the same merge
    discipline as the resident-scan path), and the block's buffers are
    then free: device memory is O(nq*k + block), item capacity is bounded
    by the SOURCE, not HBM (the regime the
    models/approximate_nearest_neighbors docstring used to hand to
    inverted lists on faith). Whether streaming beats a compressed
    resident index (ivfpq) depends on source bandwidth; the crossover is
    not measured on the chip (ROADMAP.md Reach 5, Design 14).

    Equal-size blocks reuse one compiled merge; a ragged final block
    compiles once more.
    """
    from spark_rapids_ml_tpu.core.data import _block_to_dense

    if metric not in ("euclidean", "sqeuclidean", "cosine"):
        raise ValueError(f"unknown metric {metric!r}")
    import numpy as np

    q = queries
    if metric == "cosine":
        q = q / jnp.maximum(jnp.linalg.norm(q, axis=1, keepdims=True), 1e-30)
    q_sq = jnp.sum(q * q, axis=1)
    nq = q.shape[0]
    dtype = q.dtype
    best_d = jnp.full((nq, k), jnp.inf, dtype=dtype)
    best_i = jnp.full((nq, k), -1, dtype=jnp.int32)
    offset = 0
    np_dtype = np.dtype(dtype)
    for blk in item_blocks:
        b = _block_to_dense(blk, dtype=np_dtype)
        if b.shape[0] == 0:
            continue
        xb = jnp.asarray(b)
        if metric == "cosine":
            xb = xb / jnp.maximum(
                jnp.linalg.norm(xb, axis=1, keepdims=True), 1e-30
            )
        best_d, best_i = _merge_block_topk(
            best_d, best_i, q, q_sq, xb, jnp.int32(offset), k,
            approx=approx, precision=precision,
        )
        offset += b.shape[0]
    if offset < k:
        raise ValueError(f"k={k} exceeds streamed item count {offset}")
    if metric == "euclidean":
        return jnp.sqrt(best_d), best_i
    if metric == "cosine":
        return best_d / 2.0, best_i
    return best_d, best_i


def shard_items(items, mesh, metric: str = "euclidean") -> Tuple[jax.Array, jax.Array]:
    """Place a host (n, d) item matrix on the mesh for :func:`knn_sharded`:
    rows padded up to a multiple of the data axis and sharded P(data),
    features REPLICATED (the model axis contributes nothing to the top-k
    merge, so column-sharding would only buy an implicit all-gather per
    query batch). ``metric="cosine"`` L2-normalizes rows on the host BEFORE
    the upload, so the sharded index is ready for cosine search. Returns
    (items_sharded, item_mask_sharded)."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    items = np.asarray(items)
    if metric == "cosine":
        items = items / np.maximum(
            np.linalg.norm(items, axis=1, keepdims=True), 1e-30
        )
    n = items.shape[0]
    dp = mesh.shape[DATA_AXIS]
    n_pad = (-n) % dp
    if n_pad:
        items = np.pad(items, ((0, n_pad), (0, 0)))
    mask = np.zeros(n + n_pad, dtype=items.dtype)
    mask[:n] = 1.0
    xs = jax.device_put(items, NamedSharding(mesh, P(DATA_AXIS)))
    ms = jax.device_put(mask, NamedSharding(mesh, P(DATA_AXIS)))
    return xs, ms


@functools.lru_cache(maxsize=None)
def _sharded_knn_fn(mesh, k: int, n_shard: int, precision: str, approx: bool = False):
    """Build (and cache) the jitted shard_map program for one
    (mesh, k, shard-size, precision) combination — jit's cache is keyed on
    the function object, so the closure must not be rebuilt per call."""
    from spark_rapids_ml_tpu.utils.compat import shard_map
    from jax.sharding import PartitionSpec as P

    dot = make_dot(precision)
    k_loc = min(k, n_shard)

    def _local(q, x_blk, m_blk):
        # Local top-k on the full (nq, n_shard) shard distance matrix — the
        # shard already bounds memory (a lax.scan carry would fight
        # shard_map's varying-axis tracking; see test_knn).
        shard_i = lax.axis_index(DATA_AXIS)
        q_sq = jnp.sum(q * q, axis=1)
        d2 = _block_sq_distances(q, x_blk, q_sq, dot)
        d2 = jnp.where(m_blk[None, :] > 0, d2, jnp.inf)
        if approx:
            # Hardware partial-reduce per shard; the all-gathered
            # candidate merge below stays exact (same contract as the
            # single-device approx path in knn_sq_euclidean).
            d_loc, i_loc = lax.approx_min_k(d2, k_loc)
        else:
            neg_top, i_loc = lax.top_k(-d2, k_loc)
            d_loc = -neg_top
        i_glob = i_loc + shard_i * n_shard
        # (n_dev, nq, k) candidates on every device.
        cand_d = lax.all_gather(d_loc, DATA_AXIS)
        cand_i = lax.all_gather(i_glob, DATA_AXIS)
        nq = q.shape[0]
        cand_d = jnp.moveaxis(cand_d, 0, 1).reshape(nq, -1)
        cand_i = jnp.moveaxis(cand_i, 0, 1).reshape(nq, -1)
        neg_top, pos = lax.top_k(-cand_d, k)
        return -neg_top, jnp.take_along_axis(cand_i, pos, axis=1)

    fit = shard_map(
        _local,
        mesh=mesh,
        in_specs=(P(), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=(P(), P()),
        # all_gather leaves values device-varying in the vma system even
        # though every device holds identical candidates; the final top_k is
        # deterministic, so replication holds — skip the static check.
        check_vma=False,
    )
    return jax.jit(fit)


def knn_sharded(
    queries: jax.Array,
    items: jax.Array,
    item_mask: jax.Array,
    mesh,
    k: int,
    precision: str = "highest",
    metric: str = "sqeuclidean",
    approx: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Mesh path: items row-sharded P(data) (see :func:`shard_items`),
    queries replicated. ``approx``: hardware approximate per-shard top-k
    (see :func:`knn_sq_euclidean`); the cross-shard merge stays exact.

    Each device computes its shard's local (nq, k) top-k, candidates are
    all-gathered over ICI (k per shard per query — tiny), and one final
    merge picks the global winners. Indices returned are GLOBAL item rows.

    ``metric``: "sqeuclidean" (default, the raw merge quantity) |
    "euclidean" | "cosine". Cosine expects the items to have been sharded
    with ``shard_items(..., metric="cosine")`` (rows pre-normalized);
    queries are normalized here — the same sqeuclidean reduction
    :func:`knn` uses, owned in one place for both call paths.
    """
    if metric not in ("euclidean", "sqeuclidean", "cosine"):
        raise ValueError(f"unknown metric {metric!r}")
    if metric == "cosine":
        queries = queries / jnp.maximum(
            jnp.linalg.norm(queries, axis=1, keepdims=True), 1e-30
        )
    n_shard = items.shape[0] // mesh.shape[DATA_AXIS]
    fn = _sharded_knn_fn(mesh, k, n_shard, precision, approx)
    d2, idx = fn(queries, items, item_mask)
    if metric == "euclidean":
        return jnp.sqrt(d2), idx
    if metric == "cosine":
        return d2 / 2.0, idx
    return d2, idx

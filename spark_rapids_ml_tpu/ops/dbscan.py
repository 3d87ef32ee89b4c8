"""DBSCAN kernels — blocked epsilon-graph sweeps + min-label propagation.

Beyond-the-reference capability (the reference ships only PCA — SURVEY.md §2;
the modern RAPIDS Spark-ML line grew DBSCAN on cuML). The cuML algorithm is
a vertex-degree + BFS frontier expansion over an adjacency structure; that
shape is host-sequential and pointer-chasing, which is exactly what a TPU is
bad at. TPU-first redesign:

  - The epsilon graph is never materialized. Every sweep recomputes blocked
    pairwise squared distances as (Bq, d) x (d, Bi) GEMMs on the MXU —
    FLOPs are cheap, HBM is not.
  - Core points: one sweep counting eps-neighbors (``core_point_mask``).
  - Clusters: connected components of the core-core epsilon graph via
    iterative **min-label diffusion** inside ``lax.while_loop``: every core
    point takes the minimum label over its core eps-neighbors, followed by
    pointer-jumping (``labels[labels]``) for near-logarithmic convergence —
    the classic shortcutting trick from parallel union-find, expressed as a
    gather so XLA can keep everything on-chip.
  - Border points attach to the minimum-label core neighbor in one final
    sweep; everything else is noise (-1).

All shapes are static: rows pad to a block multiple and ride a ``lax.scan``
over item blocks nested in a ``lax.map`` over query blocks, so one compiled
program serves any n at O(block_q * block_i) live memory.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from spark_rapids_ml_tpu.ops.knn import _block_sq_distances
from spark_rapids_ml_tpu.ops.linalg import _dot_precision

_INT_MAX = jnp.iinfo(jnp.int32).max


def _pad_rows(x: jax.Array, block: int) -> Tuple[jax.Array, int]:
    n = x.shape[0]
    n_blocks = -(-n // block)
    pad = n_blocks * block - n
    if pad:
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    return x, n_blocks


def _eps_sweep(x, valid, eps_sq, per_block, combine, init, block_q, block_i,
               prec, x_items=None, valid_items=None):
    """Generic blocked sweep over the epsilon graph.

    For every query block, scans all item blocks; ``per_block(adj, j0)``
    maps the (Bq, Bi) boolean adjacency (already masked to valid items,
    self-pairs INCLUDED) to a partial result, folded with ``combine`` from
    ``init``. Returns the per-query results concatenated to the padded
    query count. ``x_items``/``valid_items`` default to the query set; a
    distinct item set is the distributed case (local query shard against
    the replicated full point set).
    """
    if x_items is None:
        x_items, valid_items = x, valid
    xp, n_qblocks = _pad_rows(x, block_q)
    xi, n_iblocks = _pad_rows(x_items, block_i)
    validp = jnp.pad(valid, (0, xp.shape[0] - valid.shape[0]))
    validi = jnp.pad(valid_items, (0, xi.shape[0] - valid_items.shape[0]))
    item_blocks = xi.reshape(n_iblocks, block_i, -1)
    item_valid = validi.reshape(n_iblocks, block_i)
    j_starts = jnp.arange(n_iblocks, dtype=jnp.int32) * block_i

    def one_query_block(args):
        qb, qvalid = args
        q_sq = jnp.sum(qb * qb, axis=1)

        def step(carry, blk):
            xb, ivalid, j0 = blk
            d2 = _block_sq_distances(qb, xb, q_sq, prec)
            adj = (d2 <= eps_sq) & ivalid[None, :] & qvalid[:, None]
            return combine(carry, per_block(adj, j0)), None

        out, _ = lax.scan(step, init, (item_blocks, item_valid, j_starts))
        return out

    qblocks = xp.reshape(n_qblocks, block_q, -1)
    qvalids = validp.reshape(n_qblocks, block_q)
    outs = lax.map(one_query_block, (qblocks, qvalids))
    return outs.reshape((-1,) + outs.shape[2:])


@partial(jax.jit, static_argnames=("block_q", "block_i", "precision"))
def core_point_mask(
    x: jax.Array,
    eps: float,
    min_pts: int,
    row_mask: jax.Array | None = None,
    block_q: int = 2048,
    block_i: int = 8192,
    precision: str = "highest",
) -> jax.Array:
    """Boolean (n,) mask of core points: >= min_pts neighbors within eps.

    Neighbor counts include the point itself (sklearn/cuML convention).
    ``row_mask`` flags real rows (1) vs padding (0).
    """
    n = x.shape[0]
    valid = jnp.ones(n, bool) if row_mask is None else row_mask.astype(bool)
    eps_sq = jnp.asarray(eps, x.dtype) ** 2
    counts = _eps_neighbor_counts(
        x, valid, eps_sq, block_q, block_i, _dot_precision(precision)
    )[:n]
    return (counts >= min_pts) & valid


def _eps_neighbor_counts(x, valid, eps_sq, block_q, block_i, prec,
                         x_items=None, valid_items=None):
    """(padded_n,) eps-neighbor counts — the one home of the counting sweep
    (shared by the single-device and sharded paths)."""
    return _eps_sweep(
        x,
        valid,
        eps_sq,
        per_block=lambda adj, j0: jnp.sum(adj, axis=1, dtype=jnp.int32),
        combine=lambda a, b: a + b,
        init=jnp.zeros(block_q, jnp.int32),
        block_q=block_q,
        block_i=block_i,
        prec=prec,
        x_items=x_items,
        valid_items=valid_items,
    )


def _min_core_neighbor_label(x, valid, core, labels, eps_sq, block_q, block_i,
                             prec, x_items=None, valid_items=None):
    """For every point, min label over its CORE eps-neighbors (incl. itself
    when core). _INT_MAX where it has none. ``core``/``labels`` describe
    the ITEM set (= the query set in the single-device case)."""
    n = x.shape[0]
    labels_i, _ = _pad_rows(labels, block_i)
    core_i, _ = _pad_rows(core, block_i)

    def per_block(adj, j0):
        lab = lax.dynamic_slice(labels_i, (j0,), (adj.shape[1],))
        cor = lax.dynamic_slice(core_i, (j0,), (adj.shape[1],))
        masked = jnp.where(adj & cor[None, :], lab[None, :], _INT_MAX)
        return jnp.min(masked, axis=1)

    return _eps_sweep(
        x,
        valid,
        eps_sq,
        per_block=per_block,
        combine=jnp.minimum,
        init=jnp.full(block_q, _INT_MAX, jnp.int32),
        block_q=block_q,
        block_i=block_i,
        prec=prec,
        x_items=x_items,
        valid_items=valid_items,
    )[:n]


def _compress_labels(labels: jax.Array, core: jax.Array, n: int) -> jax.Array:
    """Pointer-jump ``labels[labels]`` to a FIXPOINT (full path compression).

    Labels are point indices, so ``labels[labels]`` hops to the
    representative's current representative (union-find shortcutting); each
    iteration doubles the compressed hop depth, so a chain of length L
    collapses in O(log L) cheap (n,) gathers. Running this to convergence
    between epsilon sweeps is what makes the number of EXPENSIVE O(n^2 d)
    sweeps O(log n) instead of O(cluster diameter) (a
    long-chain dataset previously degraded the sweep count arbitrarily).
    _INT_MAX entries clamp to a safe no-op gather.
    """

    def jcond(state):
        _, changed = state
        return changed

    def jbody(state):
        lab, _ = state
        safe = jnp.clip(lab, 0, n - 1)
        jumped = jnp.where(core, jnp.minimum(lab, lab[safe]), lab)
        return (jumped, jnp.any(jumped != lab))

    labels, _ = lax.while_loop(jcond, jbody, (labels, jnp.asarray(True)))
    return labels


@partial(
    jax.jit,
    static_argnames=("block_q", "block_i", "precision", "return_sweeps"),
)
def dbscan_labels(
    x: jax.Array,
    eps: float,
    min_pts: int,
    row_mask: jax.Array | None = None,
    block_q: int = 2048,
    block_i: int = 8192,
    precision: str = "highest",
    return_sweeps: bool = False,
):
    """Full DBSCAN: returns (labels (n,) int32, core_mask (n,) bool).

    Labels are cluster ids that are *representative point indices* (the
    minimum point index in each cluster's core set), -1 for noise. Use
    :func:`relabel_consecutive` on the host for 0..C-1 ids. Border points
    attach to their minimum-label core neighbor (deterministic; sklearn
    attaches to the first core neighbor in scan order, so individual border
    assignments may differ between ties — cluster *membership structure* of
    core points is identical).

    Each diffusion round is one epsilon sweep (blocked GEMMs, the expensive
    part) followed by pointer-jumping to a fixpoint (cheap (n,) gathers),
    so rounds grow O(log n) in the worst chain topology, not O(diameter).
    ``return_sweeps=True`` appends the number of epsilon sweeps executed
    (diffusion rounds + the final convergence-check round).
    """
    n = x.shape[0]
    valid = jnp.ones(n, bool) if row_mask is None else row_mask.astype(bool)
    eps_sq = jnp.asarray(eps, x.dtype) ** 2
    prec = _dot_precision(precision)

    core = core_point_mask(
        x, eps, min_pts, row_mask=valid, block_q=block_q, block_i=block_i, precision=precision
    )

    labels0 = jnp.where(core, jnp.arange(n, dtype=jnp.int32), _INT_MAX)

    def cond(state):
        labels, changed, _ = state
        return changed

    def body(state):
        labels, _, sweeps = state
        neigh = _min_core_neighbor_label(x, valid, core, labels, eps_sq, block_q, block_i, prec)
        new = jnp.where(core, jnp.minimum(labels, neigh), labels)
        jumped = _compress_labels(new, core, n)
        return (jumped, jnp.any(jumped != labels), sweeps + 1)

    labels, _, sweeps = lax.while_loop(
        cond, body, (labels0, jnp.asarray(True), jnp.zeros((), jnp.int32))
    )

    # Border attachment: non-core points take the min core-neighbor label.
    neigh = _min_core_neighbor_label(x, valid, core, labels, eps_sq, block_q, block_i, prec)
    border = (~core) & (neigh < _INT_MAX) & valid
    labels = jnp.where(border, neigh, labels)
    labels = jnp.where(labels == _INT_MAX, -1, labels)
    labels = jnp.where(valid, labels, -1)
    if return_sweeps:
        return labels, core, sweeps
    return labels, core


def relabel_consecutive(labels: np.ndarray) -> np.ndarray:
    """Host-side: map representative-index labels to consecutive 0..C-1,
    ordered by first appearance (sklearn convention); noise stays -1."""
    labels = np.asarray(labels)
    out = np.full_like(labels, -1)
    pos = np.flatnonzero(labels >= 0)
    if pos.size == 0:
        return out
    reps, inverse = np.unique(labels[pos], return_inverse=True)
    # Order clusters by first appearance: rank representatives by the
    # minimum row index at which each occurs.
    first_row = np.full(reps.size, labels.size, dtype=np.int64)
    np.minimum.at(first_row, inverse, pos)
    rank = np.empty(reps.size, dtype=np.int64)
    rank[np.argsort(first_row, kind="stable")] = np.arange(reps.size)
    out[pos] = rank[inverse]
    return out


import functools


@functools.lru_cache(maxsize=None)
def _sharded_dbscan_fn(mesh, n_tot: int, n_loc: int, block_q: int,
                       block_i: int, precision: str):
    """Build (and cache) the jitted shard_map DBSCAN program for one
    (mesh, shape, block, precision) combination — jit's cache is keyed on
    the function object, so the closure must not be rebuilt per call (same
    discipline as ops.knn._sharded_knn_fn). eps/min_pts are traced
    arguments: a parameter sweep reuses one compiled program."""
    from spark_rapids_ml_tpu.utils.compat import shard_map
    from jax.sharding import PartitionSpec as P

    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS

    prec = _dot_precision(precision)

    def local(xq, vq, x_all, v_all, eps_sq, min_pts):
        offset = lax.axis_index(DATA_AXIS) * n_loc

        counts = _eps_neighbor_counts(
            xq, vq, eps_sq, block_q, block_i, prec,
            x_items=x_all, valid_items=v_all,
        )[:n_loc]
        core_loc = (counts >= min_pts) & vq
        core = lax.all_gather(core_loc, DATA_AXIS).reshape(n_tot)

        labels0 = jnp.where(core, jnp.arange(n_tot, dtype=jnp.int32), _INT_MAX)

        def cond(state):
            _, changed = state
            return changed

        def body(state):
            labels, _ = state
            neigh_loc = _min_core_neighbor_label(
                xq, vq, core, labels, eps_sq, block_q, block_i, prec,
                x_items=x_all, valid_items=v_all,
            )
            lab_loc = lax.dynamic_slice(labels, (offset,), (n_loc,))
            new_loc = jnp.where(core_loc, jnp.minimum(lab_loc, neigh_loc), lab_loc)
            new = lax.all_gather(new_loc, DATA_AXIS).reshape(n_tot)
            # Full path compression on the replicated vector (identical on
            # every device, no collective needed): O(log n) sweeps total.
            jumped = _compress_labels(new, core, n_tot)
            return (jumped, jnp.any(jumped != labels))

        labels, _ = lax.while_loop(cond, body, (labels0, jnp.asarray(True)))

        neigh_loc = _min_core_neighbor_label(
            xq, vq, core, labels, eps_sq, block_q, block_i, prec,
            x_items=x_all, valid_items=v_all,
        )
        lab_loc = lax.dynamic_slice(labels, (offset,), (n_loc,))
        border = (~core_loc) & (neigh_loc < _INT_MAX) & vq
        lab_loc = jnp.where(border, neigh_loc, lab_loc)
        lab_loc = jnp.where(lab_loc == _INT_MAX, -1, lab_loc)
        lab_loc = jnp.where(vq, lab_loc, -1)
        labels_out = lax.all_gather(lab_loc, DATA_AXIS).reshape(n_tot)
        return labels_out, core

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(), P(), P(), P()),
        out_specs=(P(), P()),
        # all_gather results are identical on every device; replication
        # holds but the vma checker cannot prove it (as in ops.knn).
        check_vma=False,
    )
    return jax.jit(fn)


def dbscan_labels_sharded(
    mesh,
    x: np.ndarray,
    eps: float,
    min_pts: int,
    block_q: int = 2048,
    block_i: int = 8192,
    precision: str = "highest",
) -> Tuple[jax.Array, jax.Array]:
    """Mesh DBSCAN: query rows shard over the data axis, the point set is
    replicated (the epsilon sweeps are compute-bound at O(n^2 d); splitting
    the query dimension divides that by the device count while the
    all-gathered label vector — 4n bytes — rides ICI once per diffusion
    round). Returns replicated (labels, core_mask), identical semantics to
    :func:`dbscan_labels`.
    """
    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS

    x = np.asarray(x)
    n, _ = x.shape
    dp = mesh.shape[DATA_AXIS]
    pad = (-n) % dp
    xp = np.pad(x, ((0, pad), (0, 0)))
    validp = np.zeros(n + pad, dtype=bool)
    validp[:n] = True
    n_tot = n + pad
    fn = _sharded_dbscan_fn(mesh, n_tot, n_tot // dp, block_q, block_i, precision)
    xj = jnp.asarray(xp)
    labels, core = fn(
        xj, jnp.asarray(validp), xj, jnp.asarray(validp),
        jnp.asarray(eps, xj.dtype) ** 2, jnp.asarray(min_pts, jnp.int32),
    )
    return labels[:n], core[:n]

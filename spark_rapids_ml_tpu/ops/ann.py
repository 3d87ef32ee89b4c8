"""Approximate nearest neighbors — IVF-Flat and IVF-PQ, redesigned for the MXU.

Beyond-the-reference capability (the reference ships only PCA — SURVEY.md
§2; the modern RAPIDS Spark-ML line grew ApproximateNearestNeighbors on
cuML, algorithms ``ivfflat`` and ``ivfpq``). cuML's IVF walks per-list
inverted indices with variable-length lists and warp-level scans — dynamic
shapes and pointer-chasing a TPU can't tile. TPU-first redesign:

  - **Coarse quantizer**: k-means over the items (``ops.kmeans`` — GEMM
    Lloyd on the MXU).
  - **Inverted lists as one dense tensor**: items grouped by list into a
    (n_lists, L_max, d) array padded to the longest list, with a parallel
    mask and original-index tensor. Padding trades HBM for static shapes —
    the XLA-friendly version of CSR lists.
  - **Search**: one (Bq, d) x (d, n_lists) GEMM ranks centroids, then a
    ``lax.scan`` over the ``n_probe`` chosen lists: gather the (Bq, L_max)
    candidate block, batched distance via einsum (MXU), and a running
    top-k merge — identical merge discipline to ``ops.knn``. Live memory
    is O(Bq * L_max * d), independent of n_probe and the item count.

Setting ``n_probe = n_lists`` makes the search exact (every list probed),
which the tests exploit as a brute-force oracle.

**IVF-PQ** adds product quantization of the per-list residuals: the feature
axis splits into M subspaces, each residual subvector is snapped to one of
2^n_bits codebook entries (codebooks trained by the same GEMM Lloyd,
vmapped over subspaces), and search replaces the per-item distance GEMM
with an ADC lookup — a (Bq, M, K) distance table per probed list (one small
batched GEMM) followed by M table gathers summed over subspaces. Memory per
item drops from 4·d bytes to M code bytes; the table gather is the TPU
analogue of cuML's shared-memory LUT walk.
"""

from __future__ import annotations

import functools
from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from spark_rapids_ml_tpu.ops.kmeans import assign_clusters, kmeans_plusplus_init, lloyd
from spark_rapids_ml_tpu.ops.linalg import _dot_precision


class IVFIndex(NamedTuple):
    """Dense IVF-Flat index. All arrays are device-placeable.

    centroids: (n_lists, d)
    lists:     (n_lists, L_max, d)  — items grouped by nearest centroid
    list_mask: (n_lists, L_max)     — 1.0 real row / 0.0 padding
    list_ids:  (n_lists, L_max)     — original item indices, -1 at padding
    """

    centroids: jax.Array
    lists: jax.Array
    list_mask: jax.Array
    list_ids: jax.Array

    @property
    def n_lists(self) -> int:
        return self.lists.shape[0]


def _coarse_quantizer(items: np.ndarray, n_lists: int, seed: int,
                      kmeans_iters: int, mesh=None):
    """k-means++ + Lloyd over the items; with a mesh the rows shard over
    the data axis and the per-iteration stats merge through GSPMD-inserted
    psums (the same sharded Lloyd the KMeans estimator uses). Returns
    (centroids (n_lists, d), labels (n,)) as host arrays."""
    n, d = items.shape
    key = jax.random.key(seed)
    if mesh is None:
        x = jnp.asarray(items)
        mask = jnp.ones(n, dtype=x.dtype)
        data_shards = 1
    else:
        from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS, shard_rows

        x, mask, _ = shard_rows(items, mesh)
        data_shards = mesh.shape[DATA_AXIS]
    init = kmeans_plusplus_init(x, mask, key, n_lists)
    centroids, _, _ = lloyd(
        x, mask, init, max_iter=kmeans_iters, tol=1e-4, data_shards=data_shards
    )
    if 4 * n * n_lists > 2_000_000_000:
        # The full (n, n_lists) assignment matrix would blow HBM at
        # beyond-HBM-benchmark scales — block the final assignment.
        from spark_rapids_ml_tpu.ops.kmeans import assign_clusters_blocked

        labels, _ = assign_clusters_blocked(x, centroids)
    else:
        labels, _ = assign_clusters(x, centroids)
    # Strip row padding (mesh) and model-axis feature padding.
    return np.asarray(centroids)[:, :d], np.asarray(labels)[:n]


def build_ivf_index(
    items: np.ndarray,
    n_lists: int,
    seed: int = 0,
    kmeans_iters: int = 10,
    mesh=None,
) -> IVFIndex:
    """Train the coarse quantizer and pack the inverted lists.

    The quantizer runs on device (k-means++ init + Lloyd — mesh-sharded
    over the data axis when ``mesh`` is given); the group-by-list packing is a host-side argsort (one pass,
    done once at fit time).
    """
    items = np.asarray(items)
    n, d = items.shape
    if not 1 <= n_lists <= n:
        raise ValueError(f"n_lists must be in [1, {n}], got {n_lists}")

    centroids, labels = _coarse_quantizer(items, n_lists, seed, kmeans_iters, mesh)

    order = np.argsort(labels, kind="stable")
    counts = np.bincount(labels, minlength=n_lists)
    l_max = max(int(counts.max()), 1)

    lists = np.zeros((n_lists, l_max, d), dtype=items.dtype)
    list_mask = np.zeros((n_lists, l_max), dtype=items.dtype)
    list_ids = np.full((n_lists, l_max), -1, dtype=np.int32)
    starts = np.concatenate([[0], np.cumsum(counts)])
    for lid in range(n_lists):
        sel = order[starts[lid] : starts[lid + 1]]
        lists[lid, : sel.size] = items[sel]
        list_mask[lid, : sel.size] = 1.0
        list_ids[lid, : sel.size] = sel

    return IVFIndex(
        centroids=jnp.asarray(centroids),
        lists=jnp.asarray(lists),
        list_mask=jnp.asarray(list_mask),
        list_ids=jnp.asarray(list_ids),
    )


def _probe_scaffold(index, queries, k, n_probe, block_q, prec, list_d2_fn):
    """Shared IVF search scaffold: query blocking/padding, coarse centroid
    ranking, scan over probed lists with a running top-k merge.

    ``list_d2_fn(qb, q_sq, lid)`` computes the (Bq, L_max) squared-distance
    estimate of query block ``qb`` against list ``lid`` — the ONLY piece
    that differs between IVF-Flat (exact GEMM) and IVF-PQ (ADC tables).
    Unfilled slots surface as (inf, -1).
    """
    n_lists = index.list_mask.shape[0]
    if not 1 <= n_probe <= n_lists:
        raise ValueError(f"n_probe must be in [1, {n_lists}], got {n_probe}")
    nq, d = queries.shape
    dtype = queries.dtype

    n_qblocks = -(-nq // block_q)
    pad = n_qblocks * block_q - nq
    qp = jnp.pad(queries, ((0, pad), (0, 0)))

    def one_query_block(qb):
        q_sq = jnp.sum(qb * qb, axis=1)
        c_sq = jnp.sum(index.centroids * index.centroids, axis=1)
        qc = jnp.matmul(qb, index.centroids.T, precision=prec)
        cd2 = q_sq[:, None] - 2.0 * qc + c_sq[None, :]
        _, probe_ids = lax.top_k(-cd2, n_probe)  # (Bq, n_probe)

        init = (
            jnp.full((block_q, k), jnp.inf, dtype=dtype),
            jnp.full((block_q, k), -1, jnp.int32),
        )

        def probe_step(carry, p):
            best_d, best_i = carry
            lid = probe_ids[:, p]  # (Bq,)
            d2 = list_d2_fn(qb, q_sq, lid)
            d2 = jnp.where(index.list_mask[lid] > 0, d2, jnp.inf)
            cand_d = jnp.concatenate([best_d, d2], axis=1)
            cand_i = jnp.concatenate([best_i, index.list_ids[lid]], axis=1)
            neg_top, pos = lax.top_k(-cand_d, k)
            return (-neg_top, jnp.take_along_axis(cand_i, pos, axis=1)), None

        (best_d, best_i), _ = lax.scan(
            probe_step, init, jnp.arange(n_probe, dtype=jnp.int32)
        )
        return best_d, best_i

    qblocks = qp.reshape(n_qblocks, block_q, d)
    best_d, best_i = lax.map(one_query_block, qblocks)
    return (
        best_d.reshape(n_qblocks * block_q, k)[:nq],
        best_i.reshape(n_qblocks * block_q, k)[:nq],
    )


@partial(jax.jit, static_argnames=("k", "n_probe", "block_q", "precision"))
def ivf_search(
    index: IVFIndex,
    queries: jax.Array,
    k: int,
    n_probe: int,
    block_q: int = 1024,
    precision: str = "highest",
) -> Tuple[jax.Array, jax.Array]:
    """Top-k approximate neighbors: (sq-distances (nq, k), indices (nq, k)).

    Indices are original item indices; unfilled slots (fewer than k
    candidates in the probed lists) are (inf, -1).
    """
    prec = _dot_precision(precision)
    item_sq = jnp.sum(index.lists * index.lists, axis=2)  # (n_lists, L_max)

    def list_d2(qb, q_sq, lid):
        xb = index.lists[lid]  # (Bq, L_max, d) gather
        cross = jnp.einsum("bd,bld->bl", qb, xb, precision=prec)
        return jnp.maximum(q_sq[:, None] - 2.0 * cross + item_sq[lid], 0.0)

    return _probe_scaffold(index, queries, k, n_probe, block_q, prec, list_d2)


class IVFPQIndex(NamedTuple):
    """Dense IVF-PQ index: coarse lists + per-subspace residual codebooks.

    centroids: (n_lists, d)
    codebooks: (M, K, ds)        — K = 2^n_bits entries per subspace
    codes:     (n_lists, L_max, M) int32 — residual code per item/subspace
    list_mask: (n_lists, L_max)
    list_ids:  (n_lists, L_max)  — original item indices, -1 at padding
    """

    centroids: jax.Array
    codebooks: jax.Array
    codes: jax.Array
    list_mask: jax.Array
    list_ids: jax.Array

    @property
    def n_lists(self) -> int:
        return self.codes.shape[0]


def build_ivfpq_index(
    items: np.ndarray,
    n_lists: int,
    m_subspaces: int,
    n_bits: int = 8,
    seed: int = 0,
    kmeans_iters: int = 10,
    pq_iters: int = 10,
    mesh=None,
) -> IVFPQIndex:
    """Train the coarse quantizer, then per-subspace residual codebooks.

    Builds on the IVF-Flat packer for grouping; the PQ training runs one
    GEMM Lloyd per subspace over the residuals — with a mesh, both the
    coarse quantizer AND each codebook Lloyd shard their rows over the
    data axis.
    """
    items = np.asarray(items)
    n, d = items.shape
    if d % m_subspaces != 0:
        raise ValueError(f"d={d} not divisible by M={m_subspaces} subspaces")
    if not 1 <= n_bits <= 8:
        raise ValueError(f"n_bits must be in [1, 8], got {n_bits}")
    ds = d // m_subspaces
    n_codes = min(1 << n_bits, n)

    flat = build_ivf_index(
        items, n_lists, seed=seed, kmeans_iters=kmeans_iters, mesh=mesh
    )
    # Residuals of the REAL items, flattened over lists (padding excluded
    # from training via its zero mask weight).
    residuals = flat.lists - flat.centroids[:, None, :]  # (n_lists, L_max, d)
    r = residuals.reshape(-1, d)
    w = flat.list_mask.reshape(-1)

    if mesh is not None:
        from spark_rapids_ml_tpu.parallel.mesh import (
            DATA_AXIS,
            shard_rows,
            weights_as_mask,
        )

        data_shards = mesh.shape[DATA_AXIS]
        # Shard the FULL residual matrix once; per-subspace training
        # slices its columns device-side (no per-subspace host round-trip
        # or mask rebuild — all M Lloyds reuse the same placement).
        r_s, _, _ = shard_rows(np.asarray(r), mesh)
        w_s = weights_as_mask(np.asarray(w), r_s.shape[0], r_s.dtype, mesh)
    else:
        data_shards = 1

    key = jax.random.key(seed + 1)
    codebooks = []
    codes = []
    r_sub = r.reshape(r.shape[0], m_subspaces, ds)
    for m in range(m_subspaces):
        rm = r_sub[:, m, :]
        if mesh is not None:
            rm_s = r_s[:, m * ds : (m + 1) * ds]
            init = kmeans_plusplus_init(rm_s, w_s, jax.random.fold_in(key, m), n_codes)
            cb, _, _ = lloyd(
                rm_s, w_s, init, max_iter=pq_iters, tol=1e-4,
                data_shards=data_shards,
            )
        else:
            init = kmeans_plusplus_init(rm, w, jax.random.fold_in(key, m), n_codes)
            cb, _, _ = lloyd(rm, w, init, max_iter=pq_iters, tol=1e-4)
        code_m, _ = assign_clusters(rm, jnp.asarray(cb))
        codebooks.append(jnp.asarray(cb))
        codes.append(code_m)
    codebooks = jnp.stack(codebooks)  # (M, K, ds)
    # uint8 delivers the documented M-bytes-per-item footprint (n_bits <= 8
    # guarantees codes fit); search upcasts per probed block for indexing.
    codes = jnp.stack(codes, axis=-1).reshape(
        flat.lists.shape[0], flat.lists.shape[1], m_subspaces
    ).astype(jnp.uint8)

    return IVFPQIndex(
        centroids=flat.centroids,
        codebooks=codebooks,
        codes=codes,
        list_mask=flat.list_mask,
        list_ids=flat.list_ids,
    )


@partial(jax.jit, static_argnames=("k", "n_probe", "block_q", "precision"))
def ivfpq_search(
    index: IVFPQIndex,
    queries: jax.Array,
    k: int,
    n_probe: int,
    block_q: int = 1024,
    precision: str = "highest",
) -> Tuple[jax.Array, jax.Array]:
    """Top-k by ADC (asymmetric distance): (sq-distances (nq, k), ids (nq, k)).

    Per probed list: residual r = q - centroid, one batched GEMM builds the
    (Bq, M, K) subspace distance table, then d2(item) = sum_m LUT[m, code_m]
    via M gathers. Distances are quantization approximations of the true
    squared euclidean distance (standard IVF-PQ semantics).
    """
    n_lists, l_max, m_sub = index.codes.shape
    _, n_codes, ds = index.codebooks.shape
    prec = _dot_precision(precision)
    cb_sq = jnp.sum(index.codebooks * index.codebooks, axis=2)  # (M, K)

    def list_d2(qb, q_sq, lid):
        bq = qb.shape[0]
        r = (qb - index.centroids[lid]).reshape(bq, m_sub, ds)
        # ADC table: ||r_m - cb[m, j]||^2 for every subspace/entry.
        r_sq = jnp.sum(r * r, axis=2)  # (Bq, M)
        cross = jnp.einsum(
            "bms,mjs->bmj", r, index.codebooks, precision=prec
        )  # (Bq, M, K)
        lut = jnp.maximum(r_sq[:, :, None] - 2.0 * cross + cb_sq[None, :, :], 0.0)
        codes_b = index.codes[lid].astype(jnp.int32)  # (Bq, L_max, M)
        rows = jnp.arange(bq)[:, None]
        d2 = jnp.zeros((bq, l_max), dtype=qb.dtype)
        for m in range(m_sub):  # static M: unrolled table gathers
            d2 = d2 + lut[:, m, :][rows, codes_b[:, :, m]]
        return d2

    return _probe_scaffold(index, queries, k, n_probe, block_q, prec, list_d2)


def dispatch_search(index):
    """The one home of the index-type -> search-kernel dispatch."""
    return ivfpq_search if isinstance(index, IVFPQIndex) else ivf_search


@functools.lru_cache(maxsize=None)
def _sharded_ann_fn(mesh, is_pq: bool, n_fields: int, k: int, n_probe: int,
                    block_q: int, precision: str):
    """Build (and cache) the jitted shard_map search for one configuration —
    jit's cache is keyed on the function object, so the closure must not be
    rebuilt per call (same discipline as ops.knn._sharded_knn_fn)."""
    from spark_rapids_ml_tpu.utils.compat import shard_map
    from jax.sharding import PartitionSpec as P

    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS

    search = ivfpq_search if is_pq else ivf_search
    index_cls = IVFPQIndex if is_pq else IVFIndex

    def local(q, *fields):
        return search(
            index_cls(*fields), q, k=k, n_probe=n_probe, block_q=block_q,
            precision=precision,
        )

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(DATA_AXIS),) + (P(),) * n_fields,
        out_specs=(P(DATA_AXIS), P(DATA_AXIS)),
        check_vma=False,
    )
    return jax.jit(fn)


def ann_search_sharded(
    mesh,
    index,
    queries: jax.Array,
    k: int,
    n_probe: int,
    block_q: int = 1024,
    precision: str = "highest",
) -> Tuple[jax.Array, jax.Array]:
    """Mesh ANN search: QUERIES shard over the data axis, the index is
    replicated — each device probes its query shard independently (per-query
    results need no cross-device merge), dividing search compute by the
    device count. Works for both IVF-Flat and IVF-PQ indexes.

    (The complementary layout — lists sharded, queries replicated — would
    divide index MEMORY instead but leave every device doing the full probe
    compute; query sharding is the right default for the search-throughput
    regime the estimator serves.)
    """
    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS

    dp = mesh.shape[DATA_AXIS]
    nq = queries.shape[0]
    pad = (-nq) % dp
    qp = jnp.pad(queries, ((0, pad), (0, 0)))
    fn = _sharded_ann_fn(
        mesh, isinstance(index, IVFPQIndex), len(index), k, n_probe, block_q,
        precision,
    )
    d2, ids = fn(qp, *index)
    return d2[:nq], ids[:nq]

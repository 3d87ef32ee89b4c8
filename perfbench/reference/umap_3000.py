"""The plain reference for ``umap_3000``: a fit judged by what it returned.

Straight numpy and ``jax.numpy``; imports nothing of the program. A UMAP
layout is a stochastic optimisation with no one answer to match, and the
kNN graph of blobs in 3000 columns has near ties, so the fit is held to the
exact geometry of its rows:

* ``sample_rows`` rows, picked by a generator seeded from the rows
  themselves (the same ``--seed`` picks the same rows), against ALL rows:
  their squared distances to every row as one ``"highest"`` product on the
  device, only to rank the rows and to pick each sampled row's
  ``CANDIDATES`` nearest; those, and the fit's own neighbours, are then
  measured in float64 on the host (the rows' differences). The f32 product
  errs by about 1e-5 of a distance, the 15th and the 48th nearest lie about
  1% apart: every exact 15 nearest is among the candidates.
* every row's reported neighbours: their distances read again from the rows
  on the device as plain differences (no norm expansion, so no
  cancellation), squared and summed in float32 over chunks of at most 500
  columns, the chunks added up in float64 on the host.

The numbers, each held to at most its limit:

* ``knn_dist_rel``: over the sampled rows and their j-th returned
  neighbour, its exact distance less the exact j-th smallest, over that;
  the worst. A neighbour that is not among the nearest reads positive;
  ties swapped by a rounding read what the rounding is.
* ``dist_rel``: over ALL rows and their neighbours, the reported distance
  against the one read again, relative; the worst. It reads the ARITHMETIC
  of the distance product, and a neighbour list that is not its row's.
* ``trust_loss``: 1 less the trustworthiness at ``n_neighbors`` (sklearn's
  definition, upstream's own quality score) of the sampled rows: their
  nearest rows in the returned embedding, ranked by exact distance in the
  rows' own space.
"""

from __future__ import annotations

from functools import partial

import numpy as np

CANDIDATES = 48  # a sampled row's nearest by the f32 product, measured again
EDGE_ROWS = 2000  # rows whose neighbours are read again in one device call
CHUNK_COLS = 500  # columns summed in float32 before the host's float64 sum
EMBED_ROWS = 100  # sampled rows ranked at a time


def _divisor(n: int, most: int) -> int:
    return next(b for b in range(min(n, most), 0, -1) if n % b == 0)


def sample_of(x, s: int) -> np.ndarray:
    """``s`` row numbers, sorted, from a generator seeded by the rows' bits."""
    ends = np.concatenate([np.asarray(x[0, :4]), np.asarray(x[-1, :4])])
    rng = np.random.default_rng([int(b) for b in ends.astype(np.float32).view(np.uint32)])
    return np.sort(rng.choice(x.shape[0], size=s, replace=False))


def exact_sq_dists(x, rows: np.ndarray, others: np.ndarray) -> np.ndarray:
    """float64 squared distances of ``rows[i]`` to ``others[i, j]``: the
    rows' differences, on the host."""
    a = np.asarray(x[np.asarray(rows)], dtype=np.float64)
    out = np.empty(others.shape)
    for j in range(others.shape[1]):
        b = np.asarray(x[np.asarray(others[:, j])], dtype=np.float64)
        out[:, j] = np.sum((a - b) ** 2, axis=1)
    return out


def edge_sq_dists(x, idx: np.ndarray) -> np.ndarray:
    """(n, k) float64 squared distances of every row to its listed rows, read
    again from the rows as plain differences (float32 sums of at most
    ``CHUNK_COLS`` squares, added up in float64 here)."""
    import jax
    import jax.numpy as jnp

    n, d = x.shape
    rows = _divisor(n, EDGE_ROWS)
    chunk = _divisor(d, CHUNK_COLS)

    @partial(jax.jit, static_argnames="rows")
    def partial_sums(x, lo, nbrs, rows):
        xi = jax.lax.dynamic_slice_in_dim(x, lo, rows, axis=0)
        diff = xi[:, None, :] - x[nbrs]  # (rows, k, d)
        sq = (diff * diff).reshape(rows, nbrs.shape[1], d // chunk, chunk)
        return jnp.sum(sq, axis=3)

    xs = jnp.asarray(x)
    out = np.empty(idx.shape)
    for lo in range(0, n, rows):
        nbrs = jnp.asarray(idx[lo : lo + rows], dtype=jnp.int32)
        part = partial_sums(xs, lo, nbrs, rows=rows)
        out[lo : lo + rows] = np.asarray(part, dtype=np.float64).sum(axis=2)
    return out


def reference(x, config: dict) -> dict:
    import jax
    import jax.numpy as jnp

    n = x.shape[0]
    k = int(config["n_neighbors"])
    sample = sample_of(x, min(int(config["sample_rows"]), n - 1))
    xs = jnp.asarray(x)

    @jax.jit
    def sample_d2(xs, rows):
        q = xs[rows]
        cross = jnp.matmul(q, xs.T, precision="highest")
        d2 = jnp.sum(q * q, axis=1)[:, None] - 2.0 * cross + jnp.sum(xs * xs, axis=1)
        # a row is no neighbour of itself
        return d2.at[jnp.arange(rows.shape[0]), rows].set(jnp.inf)

    d2 = sample_d2(xs, jnp.asarray(sample, dtype=jnp.int32))
    cand = np.asarray(jax.lax.top_k(-d2, min(CANDIDATES, n - 1))[1])
    exact = exact_sq_dists(x, sample, cand)
    nearest = np.sqrt(np.sort(exact, axis=1)[:, :k])
    return {"x": x, "k": k, "sample": sample, "d2": d2, "nearest": nearest}


def trustworthiness(d2, sample: np.ndarray, emb: np.ndarray, k: int) -> float:
    """sklearn's trustworthiness of the sampled rows: ranks among ALL ``n``
    rows by ``d2`` (the sampled rows' squared distances, on the device), the
    sum over the sample, normalised by the sample's size."""
    import jax
    import jax.numpy as jnp

    rows_at_once = _divisor(len(sample), EMBED_ROWS)

    @jax.jit
    def penalty_of(d2, lo, nbrs):
        d2_rows = jax.lax.dynamic_slice_in_dim(d2, lo, rows_at_once, axis=0)
        mine = jnp.take_along_axis(d2_rows, nbrs, axis=1)  # (rows, k)
        # rank 1 is the nearest other row; the row itself is +inf
        rank = 1 + jnp.sum(d2_rows[:, None, :] < mine[:, :, None], axis=2)
        return jnp.sum(jnp.maximum(rank - k, 0))

    n = emb.shape[0]
    e_sq = np.sum(emb * emb, axis=1)
    penalty = 0.0
    for lo in range(0, len(sample), rows_at_once):
        rows = sample[lo : lo + rows_at_once]
        e2 = e_sq[rows, None] + e_sq - 2.0 * (emb[rows] @ emb.T)
        e2[np.arange(len(rows)), rows] = np.inf
        near = np.argpartition(e2, k - 1, axis=1)[:, :k]
        penalty += float(penalty_of(d2, lo, jnp.asarray(near, dtype=jnp.int32)))
    return 1.0 - 2.0 * penalty / (len(sample) * k * (2.0 * n - 3.0 * k - 1.0))


def compare(result: dict, ref: dict) -> dict:
    bad = {"knn_dist_rel": float("inf"), "dist_rel": float("inf"), "trust_loss": float("inf")}
    model = result["model"]
    model = model.item() if isinstance(model, np.ndarray) else model
    emb = np.asarray(result["embedding"], dtype=np.float64)
    idx, dist = model.knnIndices, model.knnDists
    n, k = ref["x"].shape[0], ref["k"]
    if emb.shape[0] != n or idx is None or np.shape(idx) != (n, k) or np.shape(dist) != (n, k):
        return bad
    idx = np.asarray(idx, dtype=np.int64)
    if idx.min() < 0 or idx.max() >= n:
        return bad
    dist = np.asarray(dist, dtype=np.float64)
    sample = ref["sample"]
    got = np.sqrt(exact_sq_dists(ref["x"], sample, idx[sample]))
    again = np.sqrt(edge_sq_dists(ref["x"], idx))
    with np.errstate(divide="ignore", invalid="ignore"):
        knn_dist_rel = np.max((got - ref["nearest"]) / ref["nearest"])
        dist_rel = np.max(np.abs(dist - again) / again)
    return {
        "knn_dist_rel": float(knn_dist_rel),
        "dist_rel": float(dist_rel),
        "trust_loss": 1.0 - trustworthiness(ref["d2"], sample, emb, k),
    }


def _fit(ctx, x, **setters):
    from perfbench.drivers import fit_loop

    est = fit_loop.build_estimator(ctx.config)
    for name, value in setters.items():
        getattr(est, "set" + name)(value)
    return fit_loop.read_model(est.fit(x), ctx.config)


_last_sound = []  # (rows, their sound fit): the faults that alter a sound fit share one


def sound_fit(ctx, x) -> dict:
    if not _last_sound or _last_sound[0][0] is not x:
        _last_sound[:] = [(x, _fit(ctx, x))]
    return _last_sound[0][1]


def controls() -> dict:
    """name -> ``control(ctx, x)``: a fit with one stated guarantee broken,
    put in the sound fit's place. Those the configuration lists have to come
    out NOT correct."""

    def setting(**setters):
        return lambda ctx, x: _fit(ctx, x, **setters)

    return {
        # "float32 at 'highest'": one bf16 pass, and three
        "one_pass": setting(Precision="bf16"),
        "three_pass": setting(Precision="high"),
        # "200 layout epochs": twenty of them
        "early_stop": setting(NEpochs=20),
    }


def faults() -> dict:
    """Planted faults of the timed path, name -> ``fault(ctx, x)`` that
    returns what a broken fit would hand the comparison."""
    from types import SimpleNamespace

    def half_rows(ctx, x):
        return _fit(ctx, x[: x.shape[0] // 2])

    def stale_model(ctx, x):
        # the fit of another seed's rows handed back for these
        from perfbench import data

        gen = ctx.config["data"]
        other = data.generate(gen["generator"], ctx.args.seed + 1, x.shape[0], x.shape[1],
                              gen["params"])
        return _fit(ctx, other)

    def shuffled_embedding(ctx, x):
        out = sound_fit(ctx, x)
        perm = np.random.default_rng(7).permutation(out["embedding"].shape[0])
        return {**out, "embedding": out["embedding"][perm]}

    def one_list_replaced(ctx, x):
        # row 0's neighbours and distances are those of the middle row
        out = sound_fit(ctx, x)
        model = out["model"].item()
        idx, dist = model.knnIndices.copy(), model.knnDists.copy()
        idx[0], dist[0] = idx[len(idx) // 2], dist[len(idx) // 2]
        return {**out, "model": SimpleNamespace(knnIndices=idx, knnDists=dist)}

    return {
        "half_rows": half_rows,
        "stale_model": stale_model,
        "shuffled_embedding": shuffled_embedding,
        "one_list_replaced": one_list_replaced,
    }

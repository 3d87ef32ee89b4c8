"""Randomized PCA — range-finder GEMMs for the wide-feature regime.

The reference's scaling axis is the feature dimension n (SURVEY.md §5: its
packed spr path caps at n <= 65535 columns, and the GEMM path requires the
(d, d) covariance to fit on one device). The covariance route is O(n d^2)
FLOPs and O(d^2) memory — at d ~ 10^5 the d x d Gram alone is 40 GB.
Randomized subspace iteration (Halko-Martinsson-Tropp) sidesteps both: two
streaming GEMM passes over X per power iteration with an (n, l) sketch,
l = k + oversample << d, and a final small SVD.

TPU-first details:
  - Orthonormalization is Cholesky-QR2 — two (l, l) Gram matmuls + two
    triangular solves — instead of Householder QR, which XLA would run as
    a sequential panel algorithm. CQR2's second pass restores the
    orthogonality CQR1 loses at fp32 (condition-squaring), and everything
    is MXU work.
  - Mean centering is FOLDED into the GEMMs (rank-one corrections), so the
    centered matrix is never materialized.
  - The total variance (denominator of explainedVariance) is exact — the
    trace of the covariance from column moments — so the ratios match the
    covariance path, not just the top-l approximation of it.
  - Deterministic: fixed PRNG key, sign-flip on the components.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from spark_rapids_ml_tpu.ops.eigh import sign_flip
from spark_rapids_ml_tpu.ops.precision import make_dot


def _chol_qr2(y: jax.Array, dot) -> jax.Array:
    """Orthonormalize the columns of (n, l) via two Cholesky-QR passes.
    ``dot`` is the policy-resolved matmul (ops.precision.make_dot)."""
    eps = jnp.finfo(y.dtype).eps

    def once(y):
        g = dot(y.T, y)
        # Tiny ridge: guards the Cholesky when the sketch is near-rank-
        # deficient (e.g. data with fewer than l independent directions).
        g = g + (eps * jnp.trace(g)) * jnp.eye(g.shape[0], dtype=y.dtype)
        r = jnp.linalg.cholesky(g).T  # upper
        return jax.scipy.linalg.solve_triangular(r.T, y.T, lower=True).T

    return once(once(y))


@partial(
    jax.jit,
    static_argnames=("k", "oversample", "power_iters", "precision", "center"),
)
def randomized_pca(
    x: jax.Array,
    k: int,
    key: jax.Array,
    oversample: int = 10,
    power_iters: int = 2,
    precision: str = "highest",
    center: bool = True,
    mask: jax.Array | None = None,
    n_true: jax.Array | int | None = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k principal components without forming the covariance.

    Returns (components (d, k), explained_variance_ratio (k,), mean (d,)).
    ``power_iters`` subspace iterations sharpen the spectrum (q=2 is the
    standard accuracy/cost point); each costs two GEMM passes over x.
    ``center=False`` runs second-moment PCA (the meanCentering=False
    semantics of the covariance path).

    ``mask``/``n_true`` make the sketch MESH-READY: a
    row-sharded mesh placement zero-pads rows, and the mask keeps those
    rows out of the mean, the sketch panels, and the total variance. All
    ops are tall-skinny GEMMs + (l, l) work, so under GSPMD a sharded
    ``x`` runs with one psum per rmatmul and NO (d, d) covariance on any
    device — the sketch shards exactly like the covariance does.
    """
    n, d = x.shape
    if k > min(n, d):
        raise ValueError(
            f"randomized PCA needs k <= min(n_rows, n_features) = "
            f"{min(n, d)}, got k={k}"
        )
    l = min(k + oversample, d, n)
    dot = make_dot(precision)
    dtype = x.dtype
    if n_true is None:
        n_true = n
    n_eff = jnp.asarray(n_true, dtype=dtype)

    # Padded rows are zero ALREADY (placement contract), so plain column
    # sums are exact; the mask matters for anything that SUBTRACTS the
    # mean (a padded row would otherwise contribute (0 - mean)).
    mean = jnp.sum(x, axis=0) / n_eff if center else jnp.zeros((d,), dtype)

    def apply_mask(u):
        return u if mask is None else u * mask[:, None]

    def center_matmul(v):  # Xc @ v without materializing Xc, padded rows 0
        return apply_mask(
            dot(x, v)
            - jnp.outer(jnp.ones((n,), dtype), mean @ v)
        )

    def center_rmatmul(u):  # Xc^T @ u for ALREADY-masked u
        return dot(x.T, u) - jnp.outer(
            mean, jnp.sum(u, axis=0)
        )

    omega = jax.random.normal(key, (d, l), dtype=dtype)
    y = center_matmul(omega)  # (n, l)
    q = _chol_qr2(y, dot)
    for _ in range(power_iters):  # static unroll; q small
        z = _chol_qr2(center_rmatmul(q), dot)  # (d, l)
        q = _chol_qr2(center_matmul(z), dot)

    b = center_rmatmul(q).T  # (l, d): Q^T Xc
    # SVD of the small projected matrix: right singular vectors approximate
    # the top principal directions.
    _, s, vt = jnp.linalg.svd(b, full_matrices=False)
    comps = sign_flip(vt[:k].T)  # (d, k)

    # Exact total variance from a centered two-pass trace (the
    # explainedVariance denominator must cover ALL directions, not just the
    # sketched l). E[x^2] - mean^2 would cancel catastrophically in fp32
    # for large-offset features; the centered sum does not. Padded rows
    # would each contribute ||mean||^2 — mask them.
    sq = jnp.sum((x - mean) ** 2, axis=1)
    if mask is not None:
        sq = sq * mask
    total_var = jnp.sum(sq) / jnp.maximum(n_eff - 1, 1)
    explained = (s[:k] ** 2) / jnp.maximum(n_eff - 1, 1)
    ratio = explained / jnp.maximum(total_var, jnp.finfo(dtype).tiny)
    return comps, ratio, mean


@partial(jax.jit, static_argnames=("precision",))
def _gram_power_block(z, acc, rsum, xb, mean, precision="highest"):
    """One block's contribution to Xcᵀ(Xc·Z): two tall-skinny GEMMs, no
    (d, d) anything. Returns updated ``(acc (d, l), rsum scalar-vector)``
    where ``rsum`` accumulates Σ rows of Xc·Z (the rank-one mean
    correction of the rmatmul)."""
    dot = make_dot(precision)
    t = dot(xb, z) - jnp.outer(
        jnp.ones((xb.shape[0],), xb.dtype), mean @ z
    )  # (b, l) = Xcb Z
    return (
        acc + dot(xb.T, t),
        rsum + jnp.sum(t, axis=0),
    )


@partial(jax.jit, static_argnames=("precision",))
def _sketch_gram_block(z, g, xb, mean, precision="highest"):
    """One block's contribution to (Xc·Z)ᵀ(Xc·Z) — the (l, l) Rayleigh-
    Ritz Gram of the converged sketch basis."""
    dot = make_dot(precision)
    t = dot(xb, z) - jnp.outer(
        jnp.ones((xb.shape[0],), xb.dtype), mean @ z
    )
    return g + dot(t.T, t)


def randomized_pca_streaming(
    make_blocks,
    k: int,
    key: jax.Array,
    oversample: int = 10,
    power_iters: int = 2,
    precision: str = "highest",
    center: bool = True,
    dtype=None,
    device=None,
):
    """Top-k PCA over a RE-ITERABLE block stream at O(d·l + block) memory
    — the wide-feature regime with NO (d, d) covariance and NO (n, l)
    sketch panel anywhere (beat the reference's
    RapidsRowMatrix.scala:66-68 cap AND the GEMM path's one-device
    (d, d) requirement simultaneously).

    Subspace iteration on the implicit Gram: per pass, each block
    contributes Xcᵦᵀ(Xcᵦ·Z) via two tall-skinny MXU GEMMs (the (d, l)
    state is the only cross-block memory), then CholeskyQR2
    re-orthonormalizes. A final pass builds the (l, l) Rayleigh–Ritz Gram
    whose eigensolve yields Ritz values (exact explained-variance ratios
    against the streamed total variance) and components ``Z·U``.

    ``make_blocks`` is a zero-arg callable returning a fresh block
    iterator — multi-pass algorithms need re-iterable sources (an
    ``NpyBlockReader``, an iterator factory, a list of blocks). Passes:
    1 (moments) + power_iters (gram-power) + 1 (Rayleigh–Ritz).
    ``device`` pins the block GEMMs (the gpuId semantics); blocks are
    zero/mean-padded to power-of-two row buckets so ragged streams reuse
    a handful of compiled kernels instead of one per distinct height.

    Returns ``(components (d, k), explained_variance_ratio (k,),
    mean (d,), n_rows)``.
    """
    import numpy as np

    from spark_rapids_ml_tpu.core.data import _block_to_dense

    if dtype is None:
        dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    if device is None:
        device = jax.local_devices()[0]

    # Pass 0 — moments: mean and centered total variance via a shifted
    # fp64 host accumulation (exact; the shift kills the cancellation a
    # raw E[x²] − mean² would suffer).
    shift = None
    s_sum = None
    sq_sum = 0.0
    n = 0
    d = None
    for blk in make_blocks():
        b = _block_to_dense(blk)
        if b.shape[0] == 0:
            continue
        if shift is None:
            d = b.shape[1]
            shift = b.mean(axis=0) if center else np.zeros(d)
            s_sum = np.zeros(d)
        bs = b - shift
        s_sum += bs.sum(axis=0)
        sq_sum += float((bs * bs).sum())
        n += b.shape[0]
    if n < 2:
        raise ValueError(f"need at least 2 rows, got {n}")
    if k > min(n, d):
        raise ValueError(
            f"randomized PCA needs k <= min(n_rows, n_features) = "
            f"{min(n, d)}, got k={k}"
        )
    delta = s_sum / n
    mean_h = shift + delta if center else np.zeros(d)
    # Σ‖x − mean‖² = Σ‖x − shift‖² − n‖δ‖² (the shifted-trace identity).
    # With center=False the Ritz values are RAW second moments, so the
    # denominator must be the raw trace — no mean-energy subtraction.
    raw = sq_sum - (n * float(delta @ delta) if center else 0.0)
    total_var = max(raw, 0.0) / (n - 1)

    l = min(k + oversample, d, n)
    dot = make_dot(precision)
    mean_np = (mean_h if center else np.zeros(d)).astype(
        np.dtype(dtype), copy=False
    )
    mean_dev = jax.device_put(mean_np, device)
    z = jax.device_put(jax.random.normal(key, (d, l), dtype=dtype), device)

    def bucketed(b):
        """Pad rows to a power-of-two bucket WITH MEAN ROWS: a mean row
        centers to zero, so it contributes nothing to any accumulator —
        and ragged streams hit a handful of compiled shapes."""
        rows = b.shape[0]
        bucket = max(128, 1 << (rows - 1).bit_length())
        if bucket > rows:
            b = np.concatenate(
                [b, np.broadcast_to(mean_np, (bucket - rows, d))]
            )
        return jax.device_put(b.astype(np.dtype(dtype), copy=False), device)

    # Power passes: Z ← orth(Xcᵀ(Xc·Z)), one streamed pass each.
    for _ in range(max(power_iters, 1)):
        acc = jax.device_put(jnp.zeros((d, l), dtype=dtype), device)
        rsum = jax.device_put(jnp.zeros((l,), dtype=dtype), device)
        for blk in make_blocks():
            b = _block_to_dense(blk)
            if b.shape[0] == 0:
                continue
            acc, rsum = _gram_power_block(
                z, acc, rsum, bucketed(b), mean_dev, precision=precision
            )
        # Complete the rmatmul's mean correction: Xcᵀ = Xᵀ − mean·1ᵀ, so
        # Xcᵀ(XcZ) = Σ Xᵦᵀtᵦ − mean·Σ rows(t).
        acc = acc - jnp.outer(mean_dev, rsum)
        z = _chol_qr2(acc, dot)

    # Rayleigh–Ritz pass: G = Zᵀ Xcᵀ Xc Z streamed as (l, l).
    g = jax.device_put(jnp.zeros((l, l), dtype=dtype), device)
    for blk in make_blocks():
        b = _block_to_dense(blk)
        if b.shape[0] == 0:
            continue
        g = _sketch_gram_block(z, g, bucketed(b), mean_dev, precision=precision)
    w, u = jnp.linalg.eigh(g / (n - 1))  # ascending
    w = jnp.maximum(w[::-1][:k], 0)
    comps = sign_flip(dot(z, u[:, ::-1][:, :k]))
    ratio = np.asarray(w, dtype=np.float64) / max(total_var, 1e-300)
    return np.asarray(comps), ratio, mean_h, n

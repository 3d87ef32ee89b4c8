"""Symmetric eigendecomposition + SVD-from-covariance, in XLA.

Replaces the reference's ``calSVD`` JNI export (rapidsml_jni.cu:302-356):
raft::linalg::eigDC (cuSolver syevd) -> colReverse/rowReverse (descending
order) -> seqRoot (sqrt eigenvalues -> singular values) -> deterministic
signFlip (thrust device lambda, rapidsml_jni.cu:37-64).

On TPU, ``jnp.linalg.eigh`` lowers to XLA's self-adjoint eigensolver (a
QDWH/Jacobi family algorithm — the cyclic-Jacobi approach cited in SURVEY.md
§7); the reverse/sqrt/sign-flip postprocessing ops fuse into the same
executable instead of being separate RAFT kernel launches.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


@jax.jit
def sign_flip(u: jax.Array) -> jax.Array:
    """Deterministic per-column sign convention.

    For each column, if the element with the largest |value| is negative,
    negate the column — exactly the reference's thrust ``signFlip`` device
    lambda (rapidsml_jni.cu:37-64). ``argmax`` ties resolve to the first
    index, matching the sequential scan in the reference's for-loop.
    """
    idx = jnp.argmax(jnp.abs(u), axis=0)
    pivot = u[idx, jnp.arange(u.shape[1])]
    signs = jnp.where(pivot < 0, -1.0, 1.0).astype(u.dtype)
    return u * signs[None, :]


@jax.jit
def eigh_descending(a: jax.Array):
    """Eigendecomposition of symmetric ``a`` with eigenvalues descending.

    Returns ``(eigenvalues, eigenvectors)`` with columns sign-flipped
    deterministically. Covers eigDC + colReverse + rowReverse + signFlip
    (rapidsml_jni.cu:338-343).
    """
    w, v = jnp.linalg.eigh(a)  # ascending
    w = w[::-1]
    v = v[:, ::-1]
    return w, sign_flip(v)


def _sign_flip_host(v):
    """Numpy twin of :func:`sign_flip` — ONE home for the host-side sign
    convention (the reference's signFlip contract)."""
    import numpy as np

    idx = np.argmax(np.abs(v), axis=0)
    pivot = v[idx, np.arange(v.shape[1])]
    return v * np.where(pivot < 0, -1.0, 1.0)[None, :]


def eigh_descending_host(a):
    """Host (NumPy/LAPACK) fallback with the same contract as
    :func:`eigh_descending` — the reference's driver-CPU breeze-SVD branch
    (RapidsRowMatrix.scala:110-123), for callers that opt out of the
    accelerator (``useCuSolverSVD=False``)."""
    import numpy as np

    w, v = np.linalg.eigh(np.asarray(a, dtype=np.float64))
    return w[::-1], _sign_flip_host(v[:, ::-1])


# "auto" treats eigenIters as a CAP on its early-exiting while_loop, with
# this quality floor: fewer than ~12 iterations cannot separate "converged"
# from "degenerate" reliably (ONE home for the floor — RowMatrix call
# sites use auto_max_iters, never a bare max()).
AUTO_MIN_ITERS = 12


def auto_max_iters(eigen_iters: int) -> int:
    return max(int(eigen_iters), AUTO_MIN_ITERS)


def _subspace_l(d: int, k: int) -> int:
    """Oversampled subspace width shared by the iterative solvers."""
    return min(d, max(2 * k, k + 8))


def _start_basis(d: int, l: int, dtype) -> jax.Array:
    """Deterministic orthonormal start basis (fixed key: the fitted model
    must never depend on placement or call order)."""
    q0 = jax.random.normal(jax.random.key(0), (d, l), dtype=dtype)
    q0, _ = jnp.linalg.qr(q0)
    return q0


def _cholqr(z: jax.Array):
    """CholeskyQR re-orthonormalization of a tall-skinny block.

    ``Q = Z · L⁻ᵀ`` with ``LLᵀ = ZᵀZ`` — two MXU matmuls plus an (l, l)
    Cholesky instead of a full Householder QR, which on TPU is the
    dominant cost of a subspace-iteration step (the panel factorization
    is sequential; the Gram/solve here are dense MXU work). A relative
    jitter keeps the Gram PD under fp32 rounding; the resulting loss of
    orthogonality only perturbs the iteration's conditioning, not the
    subspace span, and callers finish with one true QR before
    Rayleigh–Ritz. Returns ``(q, tr(ZᵀZ))`` — the trace is the captured
    second-moment objective the auto solver watches for stagnation.
    """
    l = z.shape[1]
    prec = jax.lax.Precision.HIGHEST
    g = jnp.matmul(z.T, z, precision=prec)
    s = jnp.trace(g)
    eps = 1e-6 if z.dtype == jnp.float32 else 1e-14
    gj = g + (eps * s / l) * jnp.eye(l, dtype=z.dtype)
    lo = jnp.linalg.cholesky(gj)
    linv = jax.scipy.linalg.solve_triangular(
        lo, jnp.eye(l, dtype=z.dtype), lower=True
    )
    return jnp.matmul(z, linv.T, precision=prec), s


def _rayleigh_ritz(a: jax.Array, q: jax.Array, k: int):
    """Final extraction: true QR (exact orthonormality), Rayleigh–Ritz,
    descending top-k with the deterministic sign flip."""
    prec = jax.lax.Precision.HIGHEST
    q, _ = jnp.linalg.qr(q)
    b = jnp.matmul(q.T, jnp.matmul(a, q, precision=prec), precision=prec)
    w, u = jnp.linalg.eigh(b)  # ascending, (l,), (l, l)
    w = w[::-1][:k]
    v = jnp.matmul(q, u[:, ::-1][:, :k], precision=prec)
    return w, sign_flip(v)


@partial(jax.jit, static_argnames=("k", "iters"))
def eigh_topk(a: jax.Array, k: int, iters: int = 8):
    """Top-k eigenpairs of a symmetric PSD matrix by subspace iteration +
    Rayleigh–Ritz — O(iters · d² · l) MXU matmuls instead of the full
    eigensolver's O(d³) iteration, the right tool when k ≪ d and the
    spectrum decays (PCA's usual regime; ``eigenSolver="topk"``).

    Returns ``(eigenvalues (k,), eigenvectors (d, k))`` descending with the
    deterministic sign flip. Exact explained-variance RATIOS need only
    ``trace(a)``, not the full spectrum, so the caller loses nothing
    there. Deterministic: the start basis comes from a fixed key. For
    near-flat spectra (no decay) the subspace converges but individual
    vectors are as ill-determined as they are for the exact solver.
    Inner steps re-orthonormalize with CholeskyQR (:func:`_cholqr`) and a
    single true QR precedes the final Rayleigh–Ritz.
    """
    d = a.shape[0]
    l = _subspace_l(d, k)
    q0 = _start_basis(d, l, a.dtype)
    prec = jax.lax.Precision.HIGHEST

    def body(_, q):
        z = jnp.matmul(a, q, precision=prec)
        q_new, _ = _cholqr(z)
        return q_new

    q = jax.lax.fori_loop(0, iters, body, q0)
    return _rayleigh_ritz(a, q, k)


@partial(jax.jit, static_argnames=("k", "max_iters", "cluster_tol"))
def eigh_auto(a: jax.Array, k: int, max_iters: int = 16, cluster_tol: float = 0.05):
    """Self-selecting top-k eigensolver (``eigenSolver="auto"``): subspace
    iteration with a runtime acceptance check that PROMOTES itself to the
    full eigensolver when the spectrum defeats it, replacing the static
    full-vs-topk choice.

    Decision rule (all on device, one ``lax.while_loop`` + one
    ``lax.cond``):
      - iterate ``Z = A·Q`` + CholeskyQR, exiting early when the captured
        second-moment objective ``s = tr(QᵀA²Q)`` (free — the trace of the
        CholeskyQR Gram) stagnates: converged spectra stop in a handful of
        steps; slow/degenerate spectra run to ``max_iters``.
      - Rayleigh–Ritz extract over the full l-wide band, then ACCEPT iff
        every kept pair is either
        (a) CONVERGED: ``residᵢ = ‖A·vᵢ − wᵢ·vᵢ‖ ≤ vec_tol·wᵢ`` — a true
        eigenpair to working precision, or
        (b) DEGENERATE: its local Ritz spacing is below its residual
        (``min gap to neighboring Ritz values ≤ residᵢ``) AND
        ``residᵢ ≤ cluster_tol·wᵢ``. By the Davis–Kahan/residual bound
        such a pair mixes only among eigen-directions whose eigenvalues
        lie within ``residᵢ`` of ``wᵢ`` — and the spacing test certifies
        the spectrum is genuinely unresolved at that resolution, where
        the exact solver's vectors are equally arbitrary basis choices
        inside the cluster. Eigenvalues (hence explained-variance ratios)
        stay correct to ``cluster_tol`` relative either way.
        A spectrum with REAL gaps at the residual scale (resolvable but
        unconverged — slow decay) fails both arms and falls through to
        ``eigh_descending`` (the promoted branch executes only when
        taken — ``lax.cond``).

    Returns ``(w (k,), v (d, k), promoted)`` descending, sign-flipped;
    ``promoted`` reports which solver produced the result. The acceptance
    thresholds are validated by an adversarial spectrum sweep in
    ``tests/test_device_input.py`` (geometric ratios, steps, clusters,
    Marchenko–Pastur noise).
    """
    d = a.shape[0]
    if k >= d:  # no subspace to iterate — the full solve IS the answer
        w, v = eigh_descending(a)
        return w[:k], v[:, :k], jnp.asarray(True)
    l = _subspace_l(d, k)
    q0 = _start_basis(d, l, a.dtype)
    prec = jax.lax.Precision.HIGHEST
    f32 = a.dtype == jnp.float32
    stag_tol = 1e-5 if f32 else 1e-11
    vec_tol = 1e-3 if f32 else 1e-8
    eps_abs = 1e-5 if f32 else 1e-12

    def cond_fn(state):
        i, _, _, stagnated = state
        return jnp.logical_and(i < max_iters, jnp.logical_not(stagnated))

    def body_fn(state):
        i, q, s_prev, _ = state
        z = jnp.matmul(a, q, precision=prec)
        q_new, s = _cholqr(z)
        stagnated = jnp.abs(s - s_prev) <= stag_tol * s
        return i + 1, q_new, s, stagnated

    neg = jnp.asarray(-jnp.inf, dtype=a.dtype)
    _, q, _, _ = jax.lax.while_loop(
        cond_fn, body_fn, (0, q0, neg, jnp.asarray(False))
    )
    # Inline Rayleigh–Ritz keeping ALL l Ritz values: the acceptance test
    # needs the kept components' neighbors to measure local spacing.
    q, _ = jnp.linalg.qr(q)
    b = jnp.matmul(q.T, jnp.matmul(a, q, precision=prec), precision=prec)
    w_all, u = jnp.linalg.eigh(b)  # ascending
    w_all = w_all[::-1]  # (l,) descending
    w_k = w_all[:k]
    v_k = sign_flip(jnp.matmul(q, u[:, ::-1][:, :k], precision=prec))
    r = jnp.matmul(a, v_k, precision=prec) - v_k * w_k[None, :]
    resid = jnp.linalg.norm(r, axis=0)
    scale = eps_abs * w_all[0]
    # Local Ritz spacing of each kept component (right neighbor always
    # exists: l >= k+1 here since k < d and l > k by construction).
    gap_right = w_k - w_all[1 : k + 1]
    gap_left = jnp.concatenate(
        [jnp.full((1,), jnp.inf, dtype=w_all.dtype), w_all[: k - 1] - w_k[1:]]
    ) if k > 1 else jnp.full((1,), jnp.inf, dtype=w_all.dtype)
    spacing = jnp.minimum(gap_left, gap_right)
    converged = resid <= vec_tol * w_k + scale
    degenerate = jnp.logical_and(
        spacing <= resid, resid <= cluster_tol * w_k + scale
    )
    accept = jnp.all(jnp.logical_or(converged, degenerate))

    def keep(_):
        return w_k, v_k

    def promote(_):
        w, v = eigh_descending(a)
        return w[:k], v[:, :k]

    w, v = jax.lax.cond(accept, keep, promote, None)
    return w, v, jnp.logical_not(accept)


def eigh_topk_host(a, k: int):
    """Host fp64 twin of :func:`eigh_topk` for the dd precision path (the
    covariance is exact-fp64 host data there; a device solve would round
    it to fp32). Uses ARPACK (scipy eigsh) with a dense-LAPACK fallback.
    Same contract: descending top-k eigenpairs, deterministic sign flip.
    """
    import numpy as np

    a = np.asarray(a, dtype=np.float64)
    try:
        from scipy.sparse.linalg import eigsh

        w, v = eigsh(a, k=k, which="LA")
        order = np.argsort(w)[::-1]
        w, v = w[order], v[:, order]
    except Exception:  # pragma: no cover - tiny k near d, or no scipy
        w_all, v_all = np.linalg.eigh(a)
        w, v = w_all[::-1][:k], v_all[:, ::-1][:, :k]
    return w, _sign_flip_host(v)


@jax.jit
def cal_svd(a: jax.Array):
    """SVD of a symmetric PSD matrix via eigendecomposition.

    Returns ``(u, s)`` with singular values ``s = sqrt(max(eigenvalues, 0))``
    descending — the reference's full ``calSVD`` contract
    (rapidsml_jni.cu:302-356, seqRoot at :341). Negative eigenvalues (tiny
    numerical noise on a PSD input) clamp to zero rather than produce NaN.
    """
    w, v = eigh_descending(a)
    s = jnp.sqrt(jnp.maximum(w, 0))
    return v, s

"""Pallas TPU kernel: fused KMeans assignment + update statistics.

The XLA Lloyd step (ops.kmeans.lloyd_step) materializes two (n, k) HBM
temporaries per iteration — the distance matrix (consumed by argmin/min)
and the one-hot matrix (operand of the stats GEMM). At 20M x 16, k=100
that is ~32 GB of HBM write+read traffic per pass against a 1.3 GB data
read: the pass is temporary-bound, not data-bound (the
bytes-roofline gap). This kernel keeps both temporaries in VMEM: per row
block it computes scores, argmin, one-hot, and the (k, d) partial sums
without writing anything block-sized back to HBM. The only HBM traffic is
the streaming read of X — the true roofline.

Why round 3's attempt was ~20x SLOWER and this one is not: the r3 kernel
read X in its natural (n, d) layout, so at d=16 each VMEM tile used 16 of
128 lanes (and the HBM layout paid the same padding). Here X arrives
TRANSPOSED — (d, n): n runs along the lane dimension (dense tiles at any
d), d along sublanes (padded to 8, zeros contribute nothing). The two
dot_generals contract over d (scores) and over the block dimension
(stats) — both MXU ops; argmin/one-hot live on the VPU between them.

Padding rows (zero columns of x_t beyond n_true) all land in the SAME
deterministic cluster argmin(c2) with distance min(c2) and zero vector
sum — the caller subtracts that closed-form contribution instead of
streaming a mask (lloyd_fused below).

Supports the unweighted fit (the adapter's weighted path keeps the
masked XLA formulation).
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from spark_rapids_ml_tpu.ops.precision import pallas_precision

# Unused-slot score sentinel. Historically +inf; a FINITE bf16-exact
# power of two now, because the 3-pass compensated split is undefined on
# non-finite values (hi(inf) = inf, lo = inf - inf = NaN — and bf16
# saturates to inf at 3.4e38, earlier than many f32 intermediates). Any
# real squared-norm score is astronomically below 2^125 ≈ 4.3e37, so the
# argmin/min semantics are unchanged bit-for-bit.
_UNUSED_SCORE = 2.0 ** 125


def _split_hi_lo(a):
    """bf16 hi/lo split (in f32 containers): a == hi + lo with both parts
    bf16-representable, so DEFAULT-precision (1-pass) dots on the parts
    are exact products — the building block of the 3-pass f32-grade dot."""
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, a - hi


def _dot_prec(a, b, dims, precision):
    """dot_general at the named precision. Mosaic has no HIGH mapping, so
    "high" is emulated as the classic 3-pass bf16 split
    (hi*hi + hi*lo + lo*hi — drops only the lo*lo term, ~f32 accuracy at
    half of HIGHEST's six passes)."""
    kw = dict(dimension_numbers=dims, preferred_element_type=jnp.float32)
    if precision == "high":
        a_hi, a_lo = _split_hi_lo(a)
        b_hi, b_lo = _split_hi_lo(b)
        default = jax.lax.Precision.DEFAULT
        return (
            jax.lax.dot_general(a_hi, b_hi, precision=default, **kw)
            + jax.lax.dot_general(a_hi, b_lo, precision=default, **kw)
            + jax.lax.dot_general(a_lo, b_hi, precision=default, **kw)
        )
    prec = (
        jax.lax.Precision.HIGHEST
        if precision == "highest"
        else jax.lax.Precision.DEFAULT
    )
    return jax.lax.dot_general(a, b, precision=prec, **kw)


def _assign_stats_kernel(xt_ref, ct_ref, c2_ref, sums_ref, counts_ref,
                         cost_ref, *, precision):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        sums_ref[:] = jnp.zeros_like(sums_ref)
        counts_ref[:] = jnp.zeros_like(counts_ref)
        # Dtype pinned explicitly: under x64, older interpret-mode state
        # discharge writes the weak 0.0 literal as f64 into the f32 ref.
        cost_ref[0, 0] = jnp.float32(0.0)

    xt = xt_ref[:]  # (d_pad, bn)
    # scores = c2 - 2 x.c  (the x2 term is argmin-invariant per row; the
    # true distance comes back via sum(x2) added to sum(min scores)).
    xc = _dot_prec(
        xt, ct_ref[:], (((0,), (0,)), ((), ())), precision
    )  # (bn, k_pad)
    scores = c2_ref[:] - 2.0 * xc
    m = jnp.min(scores, axis=1, keepdims=True)  # (bn, 1)
    labels = jnp.argmin(scores, axis=1)  # (bn,)
    oh = (
        jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        == labels[:, None]
    ).astype(jnp.float32)  # (bn, k_pad), exact 0/1
    # Stats GEMM: oh is EXACT in bf16 (0/1), so "high" needs only the x
    # split — oh.x_hi + oh.x_lo is exact-product f32-grade in 2 passes.
    if precision == "high":
        xt_hi, xt_lo = _split_hi_lo(xt)
        default = jax.lax.Precision.DEFAULT
        kw = dict(
            dimension_numbers=(((0,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        sums_ref[:] += jax.lax.dot_general(
            oh, xt_hi, precision=default, **kw
        ) + jax.lax.dot_general(oh, xt_lo, precision=default, **kw)
    else:
        sums_ref[:] += _dot_prec(
            oh, xt, (((0,), (1,)), ((), ())), precision
        )  # (k_pad, d_pad)
    counts_ref[:] += jnp.sum(oh, axis=0, keepdims=True)  # (1, k_pad)
    cost_ref[0, 0] += jnp.sum(xt * xt) + jnp.sum(m)


@partial(jax.jit, static_argnames=("block_n", "precision", "interpret"))
def assign_stats_fused(
    xt: jax.Array,
    centers: jax.Array,
    block_n: int = 4096,
    precision: str = "highest",
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused Lloyd statistics for TRANSPOSED input.

    ``xt``: (d_pad, n_pad) with d padded to 8 and n padded to ``block_n``
    multiples, both zero-filled (use :func:`pad_transposed`). ``centers``:
    (k, d_pad). Returns raw ``(sums (k, d_pad), counts (k,), cost,
    c2 (k,))`` INCLUDING the padding rows' contribution — callers subtract
    it in closed form (see :func:`lloyd_fused`). ``c2`` is the EXACT
    squared-norm row the kernel scored against (computed from the
    transposed ``ct`` buffer): the padding correction must take its argmin
    over THIS buffer, not a recomputation from ``centers`` — a different
    reduction order/layout can flip the argmin on a near-tie (e.g. cosine
    mode where every unit-norm center has c2 ~ 1), subtracting the padding
    count from a different cluster than the kernel assigned it to.
    """
    precision = pallas_precision(precision)
    d_pad, n_pad = xt.shape
    k = centers.shape[0]
    if centers.shape[1] != d_pad:
        raise ValueError(f"centers width {centers.shape[1]} != x width {d_pad}")
    k_pad = k + ((-k) % 128)
    ct = jnp.pad(centers.T, ((0, 0), (0, k_pad - k)))  # (d_pad, k_pad)
    c2 = jnp.sum(ct * ct, axis=0, keepdims=True)  # (1, k_pad)
    # Padded center columns are all-zero -> c2 = 0 would WIN every argmin.
    # Push them to the finite sentinel so no real row ever lands there
    # (NOT +inf: the "high" path's hi/lo split turns inf into NaN).
    if k_pad > k:
        col = jax.lax.broadcasted_iota(jnp.int32, (1, k_pad), 1)
        c2 = jnp.where(col < k, c2, _UNUSED_SCORE)
    if precision not in ("highest", "high", "default"):
        raise ValueError(f"precision must be highest|high|default, got {precision!r}")
    nb = n_pad // block_n

    sums, counts, cost = pl.pallas_call(
        partial(_assign_stats_kernel, precision=precision),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((d_pad, block_n), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((d_pad, k_pad), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, k_pad), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((k_pad, d_pad), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, k_pad), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((k_pad, d_pad), jnp.float32),
            jax.ShapeDtypeStruct((1, k_pad), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ),
        interpret=interpret,
    )(xt, ct, c2)
    return sums[:k], counts[0, :k], cost[0, 0], c2[0, :k]


def _packed_geometry(d_pad: int, k: int):
    """(P, dg, kg) for the lane-packed kernel, or None when packing
    cannot help: dg is the per-group feature stride (16/32/64), P = 128
    // dg groups share one contraction, kg = 128 // P score slots per
    group. Packing needs d_pad <= 64 (else the lane tile is already
    well used) and k <= kg (each group's scores must fit its slot)."""
    for dg in (16, 32, 64):
        if d_pad <= dg:
            p = 128 // dg
            if k <= 128 // p:
                return p, dg, 128 // p
            return None
    return None


def packed_feasible(d: int, k: int) -> bool:
    """True when :func:`assign_stats_packed` can run at this (d, k)."""
    return _packed_geometry(d + ((-d) % 8), k) is not None


def _assign_stats_packed_kernel(
    xp_ref, cpt_ref, c2p_ref, sums_ref, counts_ref, cost_ref,
    *, precision, groups, kg,
):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        sums_ref[:] = jnp.zeros_like(sums_ref)
        counts_ref[:] = jnp.zeros_like(counts_ref)
        cost_ref[0, 0] = jnp.float32(0.0)

    xp = xp_ref[:]  # (128, bn): P groups of dg feature sublanes
    bn = xp.shape[1]
    # ONE 128-deep contraction scores all P groups: cpt is block-diagonal,
    # so group g's score slot sees only group g's features. The scores
    # come out TRANSPOSED, (P*kg, bn): the groups lie along SUBLANES, so
    # each group's slot is a static tile-aligned slice (kg is a multiple
    # of 16) — see assign_stats_packed on why not (bn, P*kg).
    xc = _dot_prec(
        cpt_ref[:], xp, (((1,), (0,)), ((), ())), precision
    )  # (P*kg, bn)
    scores = c2p_ref[:] - 2.0 * xc
    slot = jax.lax.broadcasted_iota(jnp.int32, (kg, bn), 0)
    one_hots = []
    min_sum = jnp.float32(0.0)
    for g in range(groups):
        s = scores[g * kg:(g + 1) * kg, :]  # (kg, bn)
        m = jnp.min(s, axis=0, keepdims=True)
        # First slot attaining the minimum == argmin's tie-break, written
        # with min/compare/select only.
        first = jnp.min(jnp.where(s == m, slot, kg), axis=0, keepdims=True)
        one_hots.append((slot == first).astype(jnp.float32))
        min_sum += jnp.sum(m)
    oh = jnp.concatenate(one_hots, axis=0)  # (P*kg, bn), exact 0/1
    # Packed stats GEMM: (P*kg, P*dg) in one tile; only the P diagonal
    # (kg, dg) blocks are wanted — the off-diagonal blocks are the price
    # of the shared contraction and are discarded by the caller.
    stats_dims = (((1,), (1,)), ((), ()))
    if precision == "high":
        xp_hi, xp_lo = _split_hi_lo(xp)
        default = jax.lax.Precision.DEFAULT
        kw = dict(
            dimension_numbers=stats_dims,
            preferred_element_type=jnp.float32,
        )
        sums_ref[:] += jax.lax.dot_general(
            oh, xp_hi, precision=default, **kw
        ) + jax.lax.dot_general(oh, xp_lo, precision=default, **kw)
    else:
        sums_ref[:] += _dot_prec(oh, xp, stats_dims, precision)
    counts_ref[:] += jnp.sum(oh, axis=1, keepdims=True)  # (P*kg, 1)
    cost_ref[0, 0] += jnp.sum(xp * xp) + min_sum


@partial(jax.jit, static_argnames=("block_n", "precision", "interpret"))
def assign_stats_packed(
    xt: jax.Array,
    centers: jax.Array,
    block_n: int = 4096,
    precision: str = "highest",
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Lane-packed :func:`assign_stats_fused` for small d AND small k.

    At d=16, k<=16 the fused kernel's score contraction uses 16 of 128
    MXU lanes and 16 of 128 output columns — 112 lanes of zeros ride
    along every tile. This variant packs P = 128/dg
    INDEPENDENT row blocks into one contraction: X regroups to (128,
    n/P) with each group's d features at its own sublane offset, the
    centers become a block-diagonal (128, 128) operand, and both the
    score and stats GEMMs cover P row blocks per MXU tile — an
    algebraically identical assignment (same c2 values, same per-group
    argmin) at 1/P the tile count. Same contract as
    :func:`assign_stats_fused` (raw stats INCLUDING padding rows).

    The tile-count win is a TPU systolic-array property and is not
    measured on the chip: the kernel and its auto route predate it
    (ROADMAP.md Reach 10, Design 14).

    What the chip's compiler accepts (found compile-only for a described
    v5e, then run on the chip by ``chip_smoke.py``): the packed array's
    lane block must be a multiple of 128 (``block_n % (128 * P) == 0`` —
    :func:`auto_block_n` with ``packed=True``; the earlier ``block_n // P``
    of 1008/1696/2496 columns was refused by the Pallas TPU lowering), and
    the per-group argmin runs over sublane slices of TRANSPOSED scores (a
    (bn, P*kg) -> (bn, P, kg) lane-splitting reshape is refused by Mosaic:
    "infer-vector-layout: unsupported shape cast").
    """
    precision = pallas_precision(precision)
    d_pad, n_pad = xt.shape
    k = centers.shape[0]
    if centers.shape[1] != d_pad:
        raise ValueError(f"centers width {centers.shape[1]} != x width {d_pad}")
    geom = _packed_geometry(d_pad, k)
    if geom is None:
        raise ValueError(f"packing infeasible at d_pad={d_pad}, k={k}")
    p, dg, kg = geom
    # block_n counts rows of X per grid step, as in the unpacked kernel;
    # the packed array carries block_n // P of them per lane block.
    if n_pad % block_n or block_n % p:
        raise ValueError(
            f"n_pad {n_pad} must be a multiple of block_n {block_n}, and "
            f"block_n of the pack factor {p}"
        )
    np_rows = n_pad // p
    block_p = block_n // p
    if not interpret and block_p % 128 and block_p != np_rows:
        # The chip's compiler tiles the lane dimension in 128s (the Pallas
        # TPU lowering refuses any other block); auto_block_n(packed=True)
        # hands out aligned sizes.
        raise ValueError(
            f"compiled packed kernel needs block_n % {128 * p} == 0, got "
            f"{block_n}"
        )
    if precision not in ("highest", "high", "default"):
        raise ValueError(f"precision must be highest|high|default, got {precision!r}")

    # (d_pad, P*np) -> (P, d_pad, np) -> zero-pad each group to dg
    # sublanes -> (128, np): group g's features live at sublane g*dg.
    xp = xt.reshape(d_pad, p, np_rows).transpose(1, 0, 2)
    xp = jnp.pad(xp, ((0, 0), (0, dg - d_pad), (0, 0))).reshape(
        p * dg, np_rows
    )
    ct = centers.T  # (d_pad, k)
    c2_col = jnp.sum(ct * ct, axis=0)  # (k,) — same reduction as fused
    # Block-diagonal centers, slot-major: group g rows [g*kg, g*kg+k) x
    # cols [g*dg, g*dg+d_pad).
    eye = jnp.eye(p, dtype=xt.dtype)  # (P, P)
    cpt = jnp.einsum(
        "ab,kd->akbd", eye, jnp.pad(centers, ((0, kg - k), (0, dg - d_pad)))
    ).reshape(p * kg, p * dg)
    # Unused score slots (k..kg) push to the finite sentinel so no row
    # lands there (NOT +inf: the "high" split turns inf into NaN).
    slot = jax.lax.broadcasted_iota(jnp.int32, (kg,), 0)
    c2_slot = jnp.where(slot < k, jnp.pad(c2_col, (0, kg - k)), _UNUSED_SCORE)
    c2p = jnp.tile(c2_slot, p)[:, None]  # (P*kg, 1)

    nb = np_rows // block_p
    sums, counts, cost = pl.pallas_call(
        partial(
            _assign_stats_packed_kernel,
            precision=precision, groups=p, kg=kg,
        ),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((p * dg, block_p), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((p * kg, p * dg), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((p * kg, 1), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((p * kg, p * dg), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((p * kg, 1), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((p * kg, p * dg), jnp.float32),
            jax.ShapeDtypeStruct((p * kg, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ),
        interpret=interpret,
    )(xp, cpt, c2p)

    # Keep the P diagonal (kg, dg) blocks; the off-diagonal blocks are
    # cross-group garbage from the shared stats tile.
    sums4 = sums.reshape(p, kg, p, dg)
    sums_kd = sum(sums4[g, :, g, :] for g in range(p))  # (kg, dg)
    counts_k = jnp.sum(counts.reshape(p, kg), axis=0)
    return (
        sums_kd[:k, :d_pad],
        counts_k[:k],
        cost[0, 0],
        c2_slot[:k],
    )


def fused_feasible(d: int, k: int) -> bool:
    """True when the kernel's fixed VMEM residents (centers + c2 + the
    (k, d) accumulator) plus one minimum 128-column block fit the budget.
    The KMeans backend resolver consults this — auto falls back to XLA,
    an explicit backend='fused' raises."""
    return auto_block_n(d, k) is not None


def auto_block_n(d: int, k: int, packed: bool = False):
    """Row-block size that keeps the kernel's VMEM residents (x tile
    double-buffered + scores + one-hot + split scratch) within ~10 MB,
    or None when even the minimum 128-column block would not fit (very
    wide d x large k — the XLA path handles those). ``packed=True``
    (caller checked :func:`packed_feasible`) rounds down to a multiple of
    128 * P, so the packed array's block of ``block_n // P`` columns stays
    a whole number of 128-lane tiles — the chip's compiler accepts no
    other block shape."""
    d_pad = d + ((-d) % 8)
    k_pad = k + ((-k) % 128)
    per_col = 4 * d_pad + 2 * k_pad  # f32 elements per block column
    fixed = 2 * d_pad * k_pad + k_pad * d_pad + k_pad  # ct + sums + c2
    budget_elems = (10 << 20) // 4 - fixed
    bn = budget_elems // per_col if budget_elems > 0 else 0
    if bn < 128:
        return None
    align = 128 * _packed_geometry(d_pad, k)[0] if packed else 128
    return (min(8192, bn) // align) * align


def pad_transposed(x: jax.Array, block_n: int = 4096) -> Tuple[jax.Array, int]:
    """(n, d) -> zero-padded (d_pad, n_pad) transposed copy for the fused
    kernel (one extra HBM round trip of X, amortized over all Lloyd
    iterations). Returns (xt, n_true)."""
    n, d = x.shape
    d_pad = (-d) % 8
    n_pad = (-n) % block_n
    xt = x.T
    if d_pad or n_pad:
        xt = jnp.pad(xt, ((0, d_pad), (0, n_pad)))
    return xt, n


@partial(
    jax.jit,
    static_argnames=(
        "n_true", "max_iter", "block_n", "precision", "cosine", "interpret",
        "packed",
    ),
)
def lloyd_fused(
    xt: jax.Array,
    n_true: int,
    init_centers: jax.Array,
    max_iter: int = 20,
    tol: float = 1e-4,
    block_n: int = 4096,
    precision: str = "highest",
    cosine: bool = False,
    interpret: bool = False,
    packed: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Full Lloyd fit on the fused kernel: (centers, cost, n_iter).

    Same convergence semantics as :func:`ops.kmeans.lloyd` (movement tol,
    empty clusters keep their center, final cost at converged centers).
    ``xt`` comes from :func:`pad_transposed`; ``init_centers`` is (k, d)
    and is zero-padded to the kernel width internally. The returned
    centers carry the same d_pad width — slice ``[:, :d]`` outside.

    Padding correction: the n_pad zero columns all score argmin(c2) with
    distance min(c2) and contribute zero to sums — subtracted in closed
    form each pass, so results are EXACTLY the masked formulation's.

    ``packed=True`` routes each pass through
    :func:`assign_stats_packed` (lane-packed contraction for small d and
    k; caller checks :func:`packed_feasible` first). Padding rows behave
    identically — each group's unused score slots are +inf, so zero rows
    land on the global argmin(c2) in every group.
    """
    d_pad = xt.shape[0]
    n_pad_rows = xt.shape[1] - n_true
    k = init_centers.shape[0]
    init = jnp.pad(
        init_centers.astype(jnp.float32),
        ((0, 0), (0, d_pad - init_centers.shape[1])),
    )

    def correct(stats):
        # c2 comes back from the kernel call — the same buffer the scores
        # were computed against, so this argmin agrees with the kernel's
        # padding-row assignment even on exact ties.
        sums, counts, cost, c2 = stats
        pad_label = jnp.argmin(c2)
        counts = counts.at[pad_label].add(-jnp.float32(n_pad_rows))
        cost = cost - n_pad_rows * c2[pad_label]
        return sums, counts, cost

    assign = assign_stats_packed if packed else assign_stats_fused

    def step(centers):
        stats = assign(
            xt, centers, block_n=block_n, precision=precision,
            interpret=interpret,
        )
        sums, counts, cost = correct(stats)
        new_centers = jnp.where(
            counts[:, None] > 0, sums / jnp.maximum(counts, 1.0)[:, None], centers
        )
        if cosine:
            norms = jnp.sqrt(jnp.sum(new_centers * new_centers, axis=1, keepdims=True))
            new_centers = new_centers / jnp.maximum(norms, 1e-12)
        return new_centers, cost

    def cond(state):
        _, moved, it, _ = state
        return jnp.logical_and(moved > tol * tol, it < max_iter)

    def body(state):
        centers, _, it, _ = state
        new_centers, cost = step(centers)
        moved = jnp.max(jnp.sum((new_centers - centers) ** 2, axis=1))
        return new_centers, moved, it + 1, cost

    state0 = (
        init,
        jnp.asarray(jnp.inf, jnp.float32),
        0,
        jnp.asarray(0.0, jnp.float32),
    )
    centers, _, n_iter, _ = jax.lax.while_loop(cond, body, state0)
    # Final cost at the converged centers (lloyd parity).
    _, _, cost = correct(
        assign(
            xt, centers, block_n=block_n, precision=precision,
            interpret=interpret,
        )
    )
    return centers, cost, n_iter

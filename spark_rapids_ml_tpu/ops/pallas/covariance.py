"""Pallas TPU kernel: fused center + covariance accumulation.

The hot op of PCA fit (SURVEY.md §3.1 hot loops 1+2: per-row centering +
C = BᵀB). The XLA scan version (ops.covariance.comoment_resident) copies
each block of rows out of the matrix before the matmul reads it; this kernel keeps
the centered tile AND the (d, d) accumulator in VMEM — the only HBM traffic
is the single streaming read of X. Grid steps run sequentially on a TPU
core, so the revisited accumulator block is race-free.

Layout constraints (pallas_guide.md tiling): d padded to a lane multiple
(128), row tiles padded to sublane multiples; padded rows are filled with the
mean so their centered contribution is exactly zero (same trick as the scan
path), padded columns with zeros.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _cov_kernel(x_ref, mean_ref, acc_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    b = x_ref[:] - mean_ref[:]
    # bᵀ b on the MXU: contract the row (tile) dimension of both operands.
    # precision=HIGHEST: without it f32 operands take the single-pass bf16
    # MXU route on real hardware (~1e-3 relative error), far below the
    # 1e-5 oracle bar — and invisible to interpret-mode tests.
    acc_ref[:] += jax.lax.dot_general(
        b,
        b,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=acc_ref.dtype,
        precision=jax.lax.Precision.HIGHEST,
    )


@partial(jax.jit, static_argnames=("block_rows", "interpret"))
def centered_gram_pallas(
    x: jax.Array,
    mean: jax.Array,
    block_rows: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    """(x − mean)ᵀ(x − mean) with centering fused into the streaming kernel.

    ``interpret=True`` runs the Pallas interpreter (CPU testing). Output is
    (d, d) in x.dtype; accumulation is fp32 (or the input dtype if wider).
    """
    n, d = x.shape
    if n == 0:
        return jnp.zeros((d, d), dtype=x.dtype)
    # Pad d to a lane multiple and rows to a whole number of tiles.
    d_pad = (-d) % 128
    # VMEM budget: x tile (double-buffered) + centered temp + the HIGHEST-
    # precision dot's multi-pass scratch (6 bf16 passes keep ~6 tile-sized
    # operand splits live) + (dp, dp) accumulator, all within the ~16 MB
    # scoped limit. Empirically on v5e at d=1024 a 256-row tile compiles and
    # 512 does not, which matches an 8*tile + acc model against a 12 MB
    # budget — so clamp block_rows to (12 MB/4 - dp^2) / (8*dp), keeping a
    # sublane multiple.
    dp_ = d + d_pad
    budget_elems = (12 << 20) // 4
    max_block = (budget_elems - dp_ * dp_) // (8 * dp_)
    if max_block < 8:
        raise ValueError(
            f"d={d} needs a ({dp_}, {dp_}) VMEM accumulator that exceeds the "
            "~16 MB VMEM budget; use backend='xla' (ops.covariance.comoment_resident)"
        )
    # Sublane alignment applies to the user-passed tile size too, not just
    # the VMEM clamp — Mosaic rejects non-final block tiles that are not a
    # multiple of 8 rows.
    block_rows = max(8, (int(min(block_rows, max_block)) // 8) * 8)
    nb = -(-n // block_rows)
    n_pad = nb * block_rows - n
    mean_p = jnp.pad(mean, (0, d_pad)) if d_pad else mean
    x_p = jnp.pad(x, ((0, 0), (0, d_pad))) if d_pad else x
    if n_pad:
        x_p = jnp.concatenate(
            [x_p, jnp.broadcast_to(mean_p, (n_pad, d + d_pad))], axis=0
        )
    dp = d + d_pad
    acc_dtype = x.dtype if jnp.finfo(x.dtype).bits >= 32 else jnp.float32

    out = pl.pallas_call(
        _cov_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_rows, dp), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((dp,), lambda i: (0,), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((dp, dp), lambda i: (0, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((dp, dp), acc_dtype),
        interpret=interpret,
    )(x_p, mean_p)
    return out[:d, :d].astype(x.dtype)

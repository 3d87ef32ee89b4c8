"""Config 17: KMeans small-d lane packing shoot-out.

At d=16 the fused assignment kernel wastes 7/8 of every MXU tile: the
(8, 128) x (128, 128) systolic step contracts only 16 live lanes. The
packed layout regroups 8 row-groups of X into the 128 sublanes of ONE
tile-dense operand — (n/8, 128) @ (128, 128) block-diagonal centers —
recovering the dead lanes at identical algebraic FLOPs.

On TPU this times `assign_stats_packed` vs `assign_stats_fused` at the
config-3 feature width (d=16) with k=16 — the packed geometry at d=16
budgets kg=128/groups=16 center slots per group, so this config measures
the packable small-k regime (config 3's k=100 stays on the unpacked
kernel, and `packed_feasible` routes it there). Off-TPU the Pallas kernels
only run under the interpreter (which times the interpreter, not the
layout), so the shoot-out falls back to the XLA GEMM-shape proxy of the
SAME two shape pairs — a CPU figure (4.93x when last run), never a chip
speed; every line it prints names the device it ran on.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import emit, roofline, time_median

N, D, K = 1_048_576, 16, 16


def main() -> None:
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.pallas.kmeans import (
        assign_stats_fused,
        assign_stats_packed,
        packed_feasible,
    )

    on_tpu = jax.default_backend() == "tpu"
    assert packed_feasible(D, K), "config-17 shape must be packable"

    key = jax.random.key(17)
    kx, kc = jax.random.split(key)
    xt = jax.random.normal(kx, (D, N), dtype=jnp.float32)
    centers = jax.random.normal(kc, (K, D), dtype=jnp.float32)
    float(jnp.sum(xt[0, :8]))

    # d_pad / k_pad as the fused path sees them (lane-width multiples).
    d_pad = max(8, D)
    k_pad = ((K + 127) // 128) * 128
    flop = 4.0 * N * d_pad * k_pad  # two GEMMs: scores + stats

    if on_tpu:
        xt_pad = jnp.pad(xt, ((0, d_pad - D), (0, 0)))

        def run_variant(fn) -> float:
            def run() -> None:
                sums, counts, cost, _ = fn(xt_pad, centers)
                float(cost)  # one scalar readback syncs the stream

            return time_median(run)

        t_fused = run_variant(assign_stats_fused)
        t_packed = run_variant(assign_stats_packed)
        env = "tpu"
        precision = "highest"
    else:
        # XLA GEMM proxy of the exact shape pairs (scores + stats GEMM),
        # unpacked vs packed. Equal FLOPs; only the tile shape differs.
        groups = 128 // d_pad  # lane-packing group count (8 at d=16)

        @jax.jit
        def unpacked(x, ct, oh):
            return (x @ ct).sum() + (oh.T @ x).sum()

        @jax.jit
        def packed(xp, cp, ohp):
            return (xp @ cp).sum() + (ohp.T @ xp).sum()

        x = xt.T  # (N, d)
        x_pad = jnp.pad(x, ((0, 0), (0, d_pad - D)))
        ct = jnp.pad(centers.T, ((0, d_pad - D), (0, k_pad - K)))
        oh = jnp.zeros((N, k_pad), dtype=jnp.float32)
        xp = x_pad.reshape(N // groups, groups * d_pad)
        cp = jnp.zeros((groups * d_pad, 128), dtype=jnp.float32)
        ohp = jnp.zeros((N // groups, 128), dtype=jnp.float32)
        for a in (x_pad, ct, oh, xp, cp, ohp):
            float(jnp.sum(a[0, :4]))

        t_fused = time_median(lambda: float(unpacked(x_pad, ct, oh)))
        t_packed = time_median(lambda: float(packed(xp, cp, ohp)))
        env = "cpu_gemm_proxy"
        precision = None

    emit(
        "kmeans_packed_shootout_1Mx16_k16",
        N / t_packed,
        "rows/s",
        wall_packed_s=round(t_packed, 4),
        wall_unpacked_s=round(t_fused, 4),
        speedup=round(t_fused / t_packed, 2),
        environment=env,
        **roofline(flop, t_packed, precision),
    )


if __name__ == "__main__":
    main()

"""Precision sweep: accuracy + throughput of every GEMM-dominated family
per policy mode (ops/precision.py), against an f32/fp64 reference.

Two parts:

1. The original covariance sweep vs the fp64 host oracle on
   ILL-CONDITIONED input (column means >> stddevs, the case that exposes
   precision loss) — extended with the named policy modes. Accuracy rows
   measure END-TO-END PIPELINE error including each path's input
   representation: f32-family modes consume the f32-cast input (their
   pipeline contract), dd consumes the original fp64 input (ITS
   contract — the hi+lo split carries ~48 mantissa bits).

2. Per-family shoot-outs (covariance, logistic, linear, kmeans, and the
   packed pallas kmeans kernel at the config17 shape pair): mode x wall
   x max rel err vs the f32 run of the SAME kernel. This is the table
   the autotuner's commit bars (precision.REL_TOL) are checked against.

One JSON line with ``metric`` goes last (the run_all.py contract).
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import device_peaks, emit  # noqa: E402

# An N-pass f32 emulation divides the bf16 peak.
PASSES = {"default": 1, "high": 3, "highest": 6, "bf16": 1, "bf16x3": 3, "f32": 6}

#: The policy modes every family sweeps (f32 is the reference row).
MODES = ("f32", "bf16x3", "bf16")


def _time_best(run, repeats: int = 5) -> float:
    """Min wall over ``repeats`` after one warmup (compile excluded)."""
    run()
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        run()
        best = min(best, time.perf_counter() - t0)
    return best


def _family_sweep(name: str, make_run, flop: float | None = None) -> dict:
    """Run ``make_run(mode)`` for every policy mode; each call returns a
    zero-arg runner whose result converts to a host ndarray. Returns
    {mode: {"wall_s", "max_rel_err"}} with errors vs the f32 run."""
    rows: dict[str, dict] = {}
    ref = None
    for mode in MODES:
        run = make_run(mode)
        wall = _time_best(lambda: np.asarray(run()))
        out = np.asarray(run())
        if ref is None:
            ref = out
            err = 0.0
        else:
            scale = float(np.max(np.abs(ref))) or 1.0
            err = float(np.max(np.abs(out - ref))) / scale
        row = {"wall_s": round(wall, 6), "max_rel_err": err}
        if flop is not None:
            row["tflops"] = round(flop / wall / 1e12, 3)
        rows[mode] = row
    print(f"\n### {name}: mode x wall x max rel err vs f32\n")
    print("| mode | passes | wall s | max rel err vs f32 |")
    print("|---|---|---|---|")
    for mode, row in rows.items():
        print(
            f"| {mode} | {PASSES[mode]}x bf16 | {row['wall_s']:.4g} | "
            f"{row['max_rel_err']:.2e} |"
        )
    return rows


def main() -> None:
    import jax
    import jax.numpy as jnp

    from benchmarks.common import time_amortized
    from spark_rapids_ml_tpu.ops.covariance import centered_gram
    from spark_rapids_ml_tpu.ops.doubledouble import covariance_dd_blocks

    on_tpu = jax.default_backend() == "tpu"

    # --- accuracy: 20k x 256, means ~1e4, unit-ish stddevs (small: the
    # accuracy inputs are host arrays) ---
    rng = np.random.default_rng(0)
    d_acc = 256
    n_acc = 20_000
    x_acc = 1e4 * (1.0 + np.arange(d_acc)) / d_acc + np.linspace(
        1.0, 2.0, d_acc
    ) * rng.normal(size=(n_acc, d_acc))
    oracle = np.cov(x_acc, rowvar=False)
    mean64 = x_acc.mean(axis=0)

    acc_modes = ("default", "high", "highest", "bf16", "bf16x3", "f32")
    accs = {}
    xj = jnp.asarray(x_acc, dtype=jnp.float32)
    mj = jnp.asarray(mean64, dtype=jnp.float32)
    for prec in acc_modes:
        cov = np.asarray(centered_gram(xj, mj, precision=prec)) / (n_acc - 1)
        accs[prec] = float(np.max(np.abs(cov - oracle)))
    _, cov_dd, _ = covariance_dd_blocks([x_acc])
    accs["dd"] = float(np.max(np.abs(cov_dd - oracle)))

    # --- throughput: 1M x 1024 f32 on-device (scaled down off-TPU) ---
    n, d = (1_000_000, 1024) if on_tpu else (100_000, 256)
    x = jax.random.normal(jax.random.key(7), (n, d), dtype=jnp.float32)
    mean = jnp.mean(x, axis=0)
    float(mean[0])
    flop = 2.0 * n * d * d
    thr = {}
    for prec in acc_modes:
        t = time_amortized(
            lambda prec=prec: centered_gram(x, mean, precision=prec),
            lambda ev: float(ev[0, 0]),
            inner=5,
        )
        thr[prec] = flop / t / 1e12
    # dd DEVICE throughput: time matmul_dd on on-device split operands
    # (host split + transfer would measure the host link, not the
    # kernel). Logical FLOPs = the one fp64 GEMM being emulated.
    from spark_rapids_ml_tpu.ops.doubledouble import matmul_dd

    n_dd = 200_000 if on_tpu else 20_000
    a_hi = jax.random.normal(jax.random.key(1), (d, n_dd), dtype=jnp.float32)
    a_lo = a_hi * 1e-8
    b_hi = jnp.swapaxes(a_hi, 0, 1)
    b_lo = b_hi * 1e-8
    float(a_hi[0, 0])
    t = time_amortized(
        lambda: matmul_dd(a_hi, a_lo, b_hi, b_lo)[0],
        lambda ev: float(ev[0, 0]),
        inner=3,
    )
    thr["dd"] = (2.0 * n_dd * d * d) / t / 1e12

    # % of peak only where the device has a published peak (on the CPU
    # parity run there is none to divide by).
    peak = device_peaks()["bf16_tflops"] if on_tpu else None

    def pct(tflops: float) -> str:
        return "n/a" if peak is None else f"{100 * tflops / peak:.0f}%"

    print("| precision | passes | max abs err vs fp64 (ill-cond.) | TFLOP/s | % of bf16 peak |")
    print("|---|---|---|---|---|")
    for prec in acc_modes:
        print(
            f"| {prec} | {PASSES[prec]}x bf16 | {accs[prec]:.2e} | "
            f"{thr[prec]:.1f} | {pct(thr[prec])} |"
        )
    print(
        f"| dd | 3x HIGHEST-matmul scan | {accs['dd']:.2e} | {thr['dd']:.1f} "
        f"(device kernel only) | {pct(thr['dd'])} |"
    )

    # --- per-family shoot-outs: mode x wall x max rel err vs f32 ---
    families: dict[str, dict] = {}

    # covariance (the sweep above measured absolute accuracy; this row
    # set measures the RELATIVE bar the autotuner commits against)
    families["covariance"] = _family_sweep(
        "covariance centered_gram",
        lambda mode: lambda: centered_gram(x, mean, precision=mode),
        flop=flop,
    )

    # logistic: the serving/forward X-sweep GEMM (n, d) @ (d, c)
    from spark_rapids_ml_tpu.ops.logistic import predict_logistic

    c = 8
    w = jax.random.normal(jax.random.key(2), (d, c), dtype=jnp.float32) * 0.1
    b = jnp.zeros((c,), dtype=jnp.float32)
    families["logistic"] = _family_sweep(
        "logistic forward sweep",
        lambda mode: lambda: predict_logistic(
            x, w, b, n_classes=c, precision=mode
        )[2],
        flop=2.0 * n * d * c,
    )

    # linear: the normal-equation sufficient statistics (XtX dominates)
    from spark_rapids_ml_tpu.ops.linear import normal_eq_stats

    y = jax.random.normal(jax.random.key(3), (n,), dtype=jnp.float32)
    families["linear"] = _family_sweep(
        "linear normal_eq_stats",
        lambda mode: lambda: normal_eq_stats(x, y, None, precision=mode)[0],
        flop=2.0 * n * d * d,
    )

    # kmeans: the assignment distance GEMM (n, d) @ (d, k)
    from spark_rapids_ml_tpu.ops.kmeans import assign_clusters

    k = 64
    centers = jax.random.normal(jax.random.key(4), (k, d), dtype=jnp.float32)
    families["kmeans"] = _family_sweep(
        "kmeans assign_clusters",
        lambda mode: lambda: assign_clusters(x, centers, precision=mode)[1],
        flop=2.0 * n * d * k,
    )

    # packed pallas kernel at the config17 shape pair (D=16, K=16):
    # lane packing shares one MXU tile across row groups; off-TPU the
    # kernel runs in interpret mode at a reduced N.
    from spark_rapids_ml_tpu.ops.pallas.kmeans import (
        assign_stats_packed,
        packed_feasible,
        pad_transposed,
    )

    D17, K17 = 16, 16
    if packed_feasible(D17, K17):
        n17 = 1_048_576 if on_tpu else 4096
        bn17 = 4096 if on_tpu else 256
        xp = jax.random.normal(
            jax.random.key(5), (n17, D17), dtype=jnp.float32
        )
        xt, _ = pad_transposed(xp, block_n=bn17)
        cent17 = jnp.pad(xp[:K17], ((0, 0), (0, xt.shape[0] - D17)))

        def make_packed(mode):
            def run():
                sums, counts, cost, _ = assign_stats_packed(
                    xt, cent17, block_n=bn17, precision=mode,
                    interpret=not on_tpu,
                )
                return np.concatenate(
                    [np.asarray(sums).ravel(), np.asarray(counts).ravel()]
                )

            return run

        families["kmeans_packed"] = _family_sweep(
            "kmeans packed kernel (config17 shape pair)", make_packed,
            flop=2.0 * n17 * D17 * K17,
        )

    # With the autotuner armed (TPUML_AUTOTUNE=on), run every family
    # through the precision gate against the live store: each candidate
    # commits iff its measured probe wall beats the f32 incumbent AND
    # parity holds. On CPU the compensated mode pays 3 real f32 GEMMs,
    # so the fit families MUST keep the f32 incumbent — the CI
    # bit-identity premise, asserted here.
    from spark_rapids_ml_tpu.observability import autotune
    from spark_rapids_ml_tpu.ops.precision import FAMILIES, tune_precision

    tuner = autotune.active()
    if tuner is not None:
        decisions = {fam: tune_precision(fam, tuner=tuner) for fam in FAMILIES}
        print(f"### autotuner precision decisions: {decisions}")
        if jax.default_backend() == "cpu":
            fit_only = {f: m for f, m in decisions.items() if f != "serving"}
            assert all(m == "f32" for m in fit_only.values()), fit_only

    wall_ref = families["covariance"]["f32"]["wall_s"]
    wall_cand = families["covariance"]["bf16x3"]["wall_s"]
    emit(
        "precision_sweep_bf16x3_speedup",
        wall_ref / wall_cand,
        "x vs f32",
        environment=jax.default_backend(),
        acc_abs_err={k: round(v, 10) for k, v in accs.items()},
        families=families,
    )


if __name__ == "__main__":
    main()

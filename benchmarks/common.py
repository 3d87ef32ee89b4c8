"""Shared benchmark harness.

Each config script prints ONE JSON line (same shape as bench.py), and every
line names the device it ran on (``platform`` / ``device_kind`` / count, as
jax reports them): a number taken on a CPU can never pass for a chip's.
Chip configs call :func:`require_chip` first and refuse to run without one;
every %-of-peak figure takes its peak from :data:`DEVICE_PEAKS`, where an
unknown device is an error. Data is generated on-device (host->device
transfer is set-up, not the framework). Timing is median-of-3 after a
compile warmup.
"""

from __future__ import annotations

import json
import sys
import time
from typing import Callable


# Published per-chip peaks, keyed by jax's ``device_kind``. Source: Google
# Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at
# 819 GB/s. ``bf16_tflops`` is the denominator of every %-of-peak / MFU
# figure (bench.py and the precision sweep must agree on it);
# ``hbm_gbps`` is the denominator of the BYTES roofline (FLOP MFU is the
# wrong lens for memory-bound shapes; every config reports its fraction of
# BOTH ceilings). A device that is not in the table is an error, never a
# default.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_tflops": 197.0, "hbm_gbps": 819.0},
}


def device_info() -> dict:
    """{platform, device_kind, count} of the default backend, as jax
    reports them — attached to every line :func:`emit` prints."""
    import jax

    dev = jax.devices()[0]
    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "count": len(jax.devices()),
    }


def device_peaks() -> dict:
    """The published peaks of the device jax found, from
    :data:`DEVICE_PEAKS`; a ``RuntimeError`` for any other device."""
    info = device_info()
    peaks = DEVICE_PEAKS.get(info["device_kind"])
    if peaks is None:
        raise RuntimeError(
            f"no published peaks for device {info}: add its device_kind to "
            "benchmarks.common.DEVICE_PEAKS with a source, or run on a "
            "listed chip"
        )
    return peaks


def require_chip() -> dict:
    """First call of every chip config: returns :func:`device_info` on a
    listed chip; anywhere else prints ``{"ok": false, ...}`` and exits 1
    — a chip metric is never timed on a CPU."""
    info = device_info()
    if info["device_kind"] not in DEVICE_PEAKS:
        print(json.dumps({
            "ok": False,
            "error": "chip benchmark refused: jax found no listed chip",
            "device": info,
        }))
        sys.exit(1)
    return info


def time_median(fn: Callable[[], None], repeats: int = 3) -> float:
    """Median wall-clock of ``fn`` over ``repeats`` runs (after 1 warmup)."""
    fn()  # warmup: compile
    times = sorted(_timed(fn) for _ in range(repeats))
    return times[len(times) // 2]


def time_amortized(dispatch: Callable[[], object], sync: Callable[[object], None],
                   inner: int = 8, repeats: int = 3) -> float:
    """Per-execution wall-clock with the FIXED sync cost removed by a
    two-point slope.

    A sync (one scalar readback) costs a fixed host round trip that can
    exceed several configs' entire compute, and AMORTIZING alone still
    leaves fixed/inner ms baked into every per-exec figure. The batch
    wall is affine in the batch size,
    ``T(i) = fixed + i * t`` (the device stream is in-order and
    ``dispatch`` enqueues asynchronously; ``sync`` blocks on the LAST
    output), so the slope between a small and a large batch recovers the
    true steady-state per-execution time ``t`` with the fixed term
    cancelled exactly. Median of ``repeats`` rounds per point; falls back
    to the plain large-batch amortized figure if noise produces a
    non-positive slope.
    """
    sync(dispatch())  # warmup: compile
    inner_small = max(1, inner // 4)
    inner_big = max(2 * inner, inner_small + 4)

    def batch_wall(i: int) -> float:
        # MIN over repeats (standard minimum-time practice): a host
        # stall landing in the SMALL batch would deflate the slope below
        # the true per-exec time — an impossible >100%-of-roofline
        # reading. Stalls only ever ADD time, so the minimum is the clean
        # estimate of fixed + i*t.
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            out = None
            for _ in range(i):
                out = dispatch()
            sync(out)
            times.append(time.perf_counter() - t0)
        return min(times)

    t_small = batch_wall(inner_small)
    t_big = batch_wall(inner_big)
    slope = (t_big - t_small) / (inner_big - inner_small)
    if slope <= 0:  # host stall noise — keep the conservative estimate
        return t_big / inner_big
    return slope


def _timed(fn: Callable[[], None]) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


# MXU ceiling divisor per matmul precision: HIGHEST runs ~6 bf16 passes,
# HIGH 3, DEFAULT 1 — the denominator every per-config MFU figure uses.
_PRECISION_PASSES = {"default": 1, "high": 3, "highest": 6}


def roofline(flop: float, elapsed: float, precision: str | None = "highest") -> dict:
    """{tflops, pct_ceiling} for a kernel of ``flop`` FLOPs that took
    ``elapsed`` seconds at the given matmul precision — so every
    benchmarked family reports how much of the chip it uses, not just
    rows/s. ``flop`` should count the DOMINANT documented GEMMs
    (undercounting auxiliary ops makes the reported MFU conservative).
    ``precision=None`` emits tflops only (off-accelerator runs, where no
    MXU ceiling applies); any other value needs a device listed in
    :data:`DEVICE_PEAKS`."""
    tflops = flop / elapsed / 1e12
    out = {"tflops": round(tflops, 4 if tflops < 0.1 else 2)}
    if precision is not None:
        ceiling = device_peaks()["bf16_tflops"] / _PRECISION_PASSES[precision]
        out["pct_ceiling"] = round(100.0 * tflops / ceiling, 1)
    return out


def bytes_roofline(bytes_moved: float, elapsed: float) -> dict:
    """{gb_moved, gbps, pct_hbm_roofline} for a kernel that must move
    ``bytes_moved`` bytes of HBM traffic in ``elapsed`` seconds.

    ``bytes_moved`` should count the MINIMUM required traffic of the
    algorithm (each input read once per documented pass + outputs written
    once) — so pct_hbm_roofline reads as "fraction of the no-waste ideal":
    100% means the schedule is at the bytes bound; a low number with high
    MFU means the shape is compute-bound, and a low number with low MFU
    means there is schedule headroom (temporaries, relayouts) to attack.
    """
    gb = bytes_moved / 1e9
    bw = gb / elapsed
    return {
        "gb_moved": round(gb, 2),
        "gbps": round(bw, 1),
        "pct_hbm_roofline": round(100.0 * bw / device_peaks()["hbm_gbps"], 1),
    }


def emit(metric: str, value: float, unit: str, vs_baseline: float | None = None, **extra) -> None:
    rec = {"metric": metric, "value": round(value, 3), "unit": unit}
    if vs_baseline is not None:
        rec["vs_baseline"] = round(vs_baseline, 3)
    rec.update(extra)
    rec["device"] = device_info()
    print(json.dumps(rec))
